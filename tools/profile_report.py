#!/usr/bin/env python
"""Render the measured-vs-predicted kernel profile from a schema-4
``BENCH_serving.json`` (or any JSON file carrying a ``profile`` block —
``launch/serve --profile-dir`` writes a bare ``profile.json``).

Per kernel: measured mean dispatch time (block-until-ready, jit warmup
excluded), compile-time ``cost_analysis()`` flops / bytes, measured
arithmetic intensity, the achieved fraction of the analytical roofline,
and the model's predicted best-case time + bottleneck term — the
measurement loop ``serve/profiler.py`` closes over
``distributed/roofline.py``. The memory-ledger tier bytes print below the
table.

``--smoke`` (the ``make profile-smoke`` CI target) skips file reading and
instead profiles one CPU-interpret fused-serve burst end to end: engine +
``KernelProfiler`` + tiered store + ``MemoryLedger``, asserting that the
report renders, the ledger conserves, and the produced block passes
``tools/bench_check.py``'s schema-4 ``check_profile`` validator — so a
drifted profile schema fails CI before a real benchmark run ever writes it.

Usage: python tools/profile_report.py [--smoke] [path/to/BENCH_serving.json]
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_PATH = os.path.join(REPO_ROOT, "BENCH_serving.json")


def _load_bench_check():
    """Load tools/bench_check.py by path (works however this file is run)."""
    p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "bench_check.py")
    spec = importlib.util.spec_from_file_location("bench_check", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def render(profile: dict) -> str:
    """Measured-vs-predicted table for a ``profile`` block
    (``{"per_kernel": {...}, "mem": {...}}``)."""
    pk = profile.get("per_kernel") or {}
    hdr = (f"{'kernel':<20} {'calls':>5} {'time_ms':>9} {'flops':>10} "
           f"{'bytes':>10} {'AI':>7} {'pct_peak':>8} {'pred_ms':>9} "
           f"{'bound':<10}")
    lines = ["measured roofline (per dispatch; warmup excluded):", hdr,
             "-" * len(hdr)]
    for name in sorted(pk):
        rec = pk[name]
        pred = rec.get("predicted") or {}
        pred_ms = pred.get("roofline_ms")
        pred_col = f"{pred_ms:>9.4f}" if pred_ms is not None else f"{'-':>9}"
        pct = rec.get("pct_peak")
        pct_col = f"{pct:>8.3f}" if pct is not None else f"{'-':>8}"
        lines.append(
            f"{name:<20} {rec.get('calls', 0):>5} "
            f"{rec.get('time_ms', 0.0):>9.4f} "
            f"{rec.get('flops', 0.0):>10.3g} "
            f"{rec.get('bytes', 0.0):>10.3g} "
            f"{rec.get('ai', 0.0):>7.3f} "
            f"{pct_col} "
            f"{pred_col} {pred.get('bottleneck', '-'):<10}")
    if not pk:
        lines.append("(no profiled kernels)")
    mem = profile.get("mem") or {}
    if mem:
        lines.append(
            f"mem ledger: hot {mem.get('hot_bytes', 0)} B (device), "
            f"warm {mem.get('warm_bytes', 0)} B (host), "
            f"cold {mem.get('cold_bytes', 0)} B (disk)")
    return "\n".join(lines)


def smoke() -> int:
    """CPU-interpret profile of one fused-serve burst: builds the whole
    measurement stack, then validates its own output with the CI
    schema-4 checker. Exit 0 on success; any assertion raises."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import EngineConfig, SDIMEngine
    from repro.serve.bse_server import BSEServer
    from repro.serve.metrics import MetricsRegistry
    from repro.serve.profiler import KernelProfiler, MemoryLedger

    d, L, C, H = 16, 16, 4, 8
    W = 2 * H
    emb_i = jax.random.normal(jax.random.PRNGKey(1), (256, d // 2))
    emb_c = jax.random.normal(jax.random.PRNGKey(2), (16, d // 2))

    def embed(params, items, cats):
        return jnp.concatenate([emb_i[jnp.asarray(items) % 256],
                                emb_c[jnp.asarray(cats) % 16]], axis=-1)

    # CPU-interpret: the Pallas kernels run under the interpreter — the
    # smoke proves the measurement plumbing, not TPU numbers
    eng = SDIMEngine(EngineConfig(m=8, tau=2, d=d, backend="pallas",
                                  interpret=True))
    metrics = MetricsRegistry()
    prof = KernelProfiler(metrics=metrics)
    prof.attach(eng)
    tmp = tempfile.mkdtemp(prefix="profile-smoke-")
    try:
        srv = BSEServer(embed, None, eng, wire_dtype=jnp.float32,
                        hot_capacity=H, warm_capacity=H // 2, store_dir=tmp,
                        metrics=metrics)
        ledger = MemoryLedger(metrics=metrics)
        ledger.attach(srv.store)
        rng = np.random.default_rng(0)
        for lo in range(0, W, H):
            srv.ingest_histories(list(range(lo, lo + H)),
                                 rng.integers(0, 256, (H, L)),
                                 rng.integers(0, 16, (H, L)))
        users = list(range(H))
        q = embed(None, rng.integers(0, 256, (H, C)),
                  rng.integers(0, 16, (H, C)))
        for _ in range(3):                        # burst 1 warms, 2-3 measure
            jax.block_until_ready(srv.serve_candidates(users, q))
        errs = ledger.verify()
        assert not errs, f"memory ledger broken: {errs}"
        profile = {"per_kernel": prof.to_dict(), "mem": ledger.snapshot()}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    fused = profile["per_kernel"].get("serve_fused")
    assert fused and fused["calls"] >= 1, \
        f"fused-serve kernel not profiled: {sorted(profile['per_kernel'])}"
    report = render(profile)
    assert "serve_fused" in report and "mem ledger" in report, report
    summary = _load_bench_check().check_profile(profile)   # schema-4 gate
    print(report)
    print(f"profile-smoke OK — {summary}")
    return 0


def main(argv: list[str]) -> int:
    if "--smoke" in argv:
        return smoke()
    path = argv[1] if len(argv) > 1 else DEFAULT_PATH
    if not os.path.exists(path):
        print(f"profile_report: {path} missing — run `make bench-smoke` "
              f"(schema 4) or `launch/serve --profile-dir`", file=sys.stderr)
        return 1
    with open(path) as f:
        doc = json.load(f)
    profile = doc.get("profile") if "profile" in doc else doc
    if not isinstance(profile, dict) or not profile.get("per_kernel"):
        print(f"profile_report: {path} has no profile block "
              f"(schema {doc.get('schema')!r}; schema 4 writes one)",
              file=sys.stderr)
        return 1
    print(render(profile))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
