#!/usr/bin/env python
"""CI gate for BENCH_serving.json (written by benchmarks/table5_serving.py).

Fails (exit 1) when the file is missing, unparseable, or structurally
malformed: every serving variant must report finite positive users/sec and
ordered latency percentiles, the quantization block must carry the
bytes-ratio and AUC-parity measurements, tier hit-rates must be
probabilities, and the ingest block must report both latency phases with
an accounted event balance (folded + dropped covers submitted — no event
goes silently missing). Schema 2 additionally requires the ``slo`` section
(open-loop Zipf+Poisson tail latency with shed/degrade rates, ISSUE 8);
schema 3 additionally requires the ``trace`` section (span-coverage
fraction + jit-compile span count from the traced replay, ISSUE 9) and a
``git_rev`` stamp; schema 4 additionally requires the ``profile`` section
(measured per-kernel time / cost_analysis flops+bytes / arithmetic
intensity with pct_peak in [0,1] or null, plus the memory-ledger tier bytes,
ISSUE 10); schema 1/2/3 files remain readable for back-compat with
older checkouts. Thresholds (1.5x speedup, 3.5x bytes, 1e-3 AUC
gap, 1.2x under-ingest p95) are
PR-acceptance numbers measured on dedicated hardware — this check pins the
*schema* so a silently-skipped section can't pass CI, without making CI
flaky on loaded machines.

Usage: python tools/bench_check.py [path/to/BENCH_serving.json]
"""
from __future__ import annotations

import json
import math
import os
import sys

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_PATH = os.path.join(REPO_ROOT, "BENCH_serving.json")
VARIANTS = ("two_dispatch", "fused", "fused_int8")
PCTS = ("p50_ms", "p95_ms", "p99_ms")


class Malformed(Exception):
    pass


def _num(d: dict, key: str, lo: float = None, hi: float = None,
         where: str = "") -> float:
    v = d.get(key)
    if not isinstance(v, (int, float)) or isinstance(v, bool) \
            or not math.isfinite(v):
        raise Malformed(f"{where}.{key}: expected finite number, got {v!r}")
    if lo is not None and v < lo:
        raise Malformed(f"{where}.{key}={v} below {lo}")
    if hi is not None and v > hi:
        raise Malformed(f"{where}.{key}={v} above {hi}")
    return float(v)


def check(bench: dict) -> list[str]:
    """Validate the parsed benchmark dict; returns human-readable summary
    lines (raises Malformed on any structural problem). Schema 1 files
    (pre-SLO, ISSUE 7) stay readable; schema 2 adds the mandatory ``slo``
    section (open-loop tail latency + shed/degrade rates, ISSUE 8);
    schema 3 adds the mandatory ``trace`` section (span coverage +
    compile-span count, ISSUE 9) and the ``git_rev`` stamp; schema 4 adds
    the mandatory ``profile`` section (measured roofline + memory ledger,
    ISSUE 10)."""
    schema = bench.get("schema")
    if schema not in (1, 2, 3, 4):
        raise Malformed(f"schema: expected 1, 2, 3 or 4, got {schema!r}")
    lines = []

    backends = bench.get("backends")
    if not isinstance(backends, dict) or not backends:
        raise Malformed("backends: expected non-empty dict")
    for bk, st in backends.items():
        where = f"backends.{bk}"
        if not isinstance(st, dict):
            raise Malformed(f"{where}: expected dict")
        _num(st, "n_users", lo=1, where=where)
        for var in VARIANTS:
            if not isinstance(st.get(var), dict):
                raise Malformed(f"{where}.{var}: missing variant block")
            _num(st[var], "users_per_sec", lo=1e-9, where=f"{where}.{var}")
            p = [_num(st[var], k, lo=0, where=f"{where}.{var}") for k in PCTS]
            if not p[0] <= p[1] <= p[2]:
                raise Malformed(f"{where}.{var}: percentiles not ordered {p}")
        sp = _num(st, "speedup_fused_vs_two_dispatch", lo=1e-9, where=where)
        lines.append(f"{bk}: fused {sp:.2f}x two-dispatch "
                     f"({st['fused']['users_per_sec']:.0f} users/s, "
                     f"p99 {st['fused']['p99_ms']}ms)")

    q = bench.get("quantization")
    if not isinstance(q, dict):
        raise Malformed("quantization: expected dict")
    where = "quantization"
    _num(q, "table_bytes_fp32", lo=1, where=where)
    _num(q, "table_bytes_int8", lo=1, where=where)
    ratio = _num(q, "bytes_ratio", lo=1e-9, where=where)
    a32 = _num(q, "auc_fp32_unfused", lo=0.0, hi=1.0, where=where)
    a8 = _num(q, "auc_int8_fused", lo=0.0, hi=1.0, where=where)
    gap = _num(q, "auc_gap", lo=0.0, hi=1.0, where=where)
    lines.append(f"int8: {ratio:.2f}x smaller tables, "
                 f"AUC {a32:.4f} -> {a8:.4f} (gap {gap:.1e})")

    rl = bench.get("roofline")
    if not isinstance(rl, dict) or not rl:
        raise Malformed("roofline: expected non-empty dict")
    for k in rl:
        _num(rl, k, lo=0, where="roofline")

    hr = bench.get("hit_rate")
    if not isinstance(hr, dict) or not hr:
        raise Malformed("hit_rate: expected non-empty dict")
    for bk in hr:
        _num(hr, bk, lo=0.0, hi=1.0, where="hit_rate")
    lines.append("hit_rate: " + ", ".join(f"{k}={v:.2f}"
                                          for k, v in sorted(hr.items())))

    ing = bench.get("ingest")
    if not isinstance(ing, dict) or not ing:
        raise Malformed("ingest: expected non-empty dict")
    where = "ingest"
    _num(ing, "n_users", lo=1, where=where)
    _num(ing, "n_bursts", lo=1, where=where)
    for phase in ("read_only", "under_ingest"):
        blk = ing.get(phase)
        if not isinstance(blk, dict):
            raise Malformed(f"{where}.{phase}: missing latency block")
        p50 = _num(blk, "p50_ms", lo=0, where=f"{where}.{phase}")
        p95 = _num(blk, "p95_ms", lo=0, where=f"{where}.{phase}")
        if p50 > p95:
            raise Malformed(
                f"{where}.{phase}: p50={p50} above p95={p95}")
    ratio = _num(ing, "p95_ratio", lo=1e-9, where=where)
    eps = _num(ing, "events_per_sec", lo=1e-9, where=where)
    _num(ing, "events_submitted", lo=0, where=where)
    folded = _num(ing, "events_folded", lo=0, where=where)
    dropped = _num(ing, "n_dropped", lo=0, where=where)
    if folded + dropped < _num(ing, "events_submitted", lo=0, where=where):
        raise Malformed(
            f"{where}: folded({folded}) + dropped({dropped}) below "
            f"submitted({ing['events_submitted']}) — events went missing")
    _num(ing, "staleness_p95", lo=0, where=where)
    _num(ing, "max_queue_depth", lo=0, where=where)
    lines.append(f"ingest: under-ingest p95 {ratio:.2f}x read-only "
                 f"({eps:.0f} events/s folded, "
                 f"{int(dropped)} dropped, "
                 f"staleness p95 {ing['staleness_p95']})")

    if schema >= 2:
        slo = bench.get("slo")
        if not isinstance(slo, dict):
            raise Malformed("slo: schema 2 requires the SLO section "
                            "(open-loop tail latency under overload)")
        where = "slo"
        _num(slo, "n_requests", lo=1, where=where)
        _num(slo, "offered_rps", lo=1e-9, where=where)
        p = [_num(slo, k, lo=0, where=where) for k in PCTS]
        if not p[0] <= p[1] <= p[2]:
            raise Malformed(f"{where}: percentiles not ordered {p}")
        shed = _num(slo, "shed_rate", lo=0.0, hi=1.0, where=where)
        degr = _num(slo, "degrade_rate", lo=0.0, hi=1.0, where=where)
        lines.append(f"slo: p50/p95/p99 {p[0]}/{p[1]}/{p[2]}ms at "
                     f"{slo['offered_rps']:.0f} rps offered "
                     f"(shed {shed:.1%}, degraded {degr:.1%})")

    if schema >= 3:
        tr = bench.get("trace")
        if not isinstance(tr, dict):
            raise Malformed("trace: schema 3 requires the trace section "
                            "(span coverage of the traced request path)")
        where = "trace"
        cov = _num(tr, "span_coverage", lo=0.0, hi=1.0, where=where)
        ncs = _num(tr, "n_compile_spans", lo=0, where=where)
        _num(tr, "n_traces", lo=1, where=where)
        _num(tr, "n_spans", lo=1, where=where)
        rev = bench.get("git_rev")
        if not isinstance(rev, str) or not rev:
            raise Malformed("git_rev: schema 3 requires a non-empty "
                            "revision stamp (or 'unknown')")
        lines.append(f"trace: {cov:.1%} span coverage over "
                     f"{int(tr['n_traces'])} traces, "
                     f"{int(ncs)} compile spans (rev {rev})")

    if schema >= 4:
        lines.append(check_profile(bench.get("profile")))
    return lines


def check_profile(profile) -> str:
    """Validate a schema-4 ``profile`` block (also called standalone by
    ``tools/profile_report.py --smoke``): ``per_kernel`` must be a
    non-empty dict of kernels with non-negative time/flops/bytes/ai and
    ``pct_peak`` in [0,1], or null where the device has no listed peaks
    (each ``predicted`` sub-block, when present,
    non-negative as well), and ``mem`` must report non-negative
    hot/warm/cold tier bytes. Returns a one-line summary; raises
    ``Malformed`` on any structural problem."""
    if not isinstance(profile, dict):
        raise Malformed("profile: schema 4 requires the profile section "
                        "(measured roofline + memory ledger)")
    pk = profile.get("per_kernel")
    if not isinstance(pk, dict) or not pk:
        raise Malformed("profile.per_kernel: expected non-empty dict")
    for name, rec in pk.items():
        where = f"profile.per_kernel.{name}"
        if not isinstance(rec, dict):
            raise Malformed(f"{where}: expected dict")
        _num(rec, "time_ms", lo=0, where=where)
        _num(rec, "flops", lo=0, where=where)
        _num(rec, "bytes", lo=0, where=where)
        _num(rec, "ai", lo=0, where=where)
        if rec.get("pct_peak") is not None:
            _num(rec, "pct_peak", lo=0.0, hi=1.0, where=where)
        pred = rec.get("predicted")
        if pred is not None:
            if not isinstance(pred, dict):
                raise Malformed(f"{where}.predicted: expected dict")
            for k in ("t_compute_ms", "t_memory_ms", "roofline_ms"):
                _num(pred, k, lo=0, where=f"{where}.predicted")
    mem = profile.get("mem")
    if not isinstance(mem, dict):
        raise Malformed("profile.mem: expected dict (memory-ledger tiers)")
    for k in ("hot_bytes", "warm_bytes", "cold_bytes"):
        _num(mem, k, lo=0, where="profile.mem")
    return (f"profile: {len(pk)} kernels measured, mem "
            f"hot={int(mem['hot_bytes'])}B warm={int(mem['warm_bytes'])}B "
            f"cold={int(mem['cold_bytes'])}B")


def main(argv: list[str]) -> int:
    path = argv[1] if len(argv) > 1 else DEFAULT_PATH
    if not os.path.exists(path):
        print(f"bench_check: {path} missing — run "
              f"`make bench-smoke` (benchmarks/table5_serving.py writes it)",
              file=sys.stderr)
        return 1
    try:
        with open(path) as f:
            bench = json.load(f)
        lines = check(bench)
    except (json.JSONDecodeError, Malformed) as e:
        print(f"bench_check: {path} malformed: {e}", file=sys.stderr)
        return 1
    print(f"bench_check: {os.path.relpath(path, REPO_ROOT)} OK "
          f"(generated {bench.get('generated_utc', '?')}, "
          f"quick={bench.get('quick')})")
    for ln in lines:
        print(f"  {ln}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
