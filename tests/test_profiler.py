"""Kernel profiler + memory ledger tests (ISSUE 10; serve/profiler.py).

The profiler half is deterministic by construction: a ``StepClock``
(every read advances a fixed step) pins each measured dispatch to an
exact duration, so warmup exclusion and the mean/min/max math are tested
against known numbers, not wall-clock noise. The ledger half sweeps the
conservation invariant — event-accumulated bytes == tier-reported bytes —
under hypothesis-generated op bursts (grow / evict / promote / demote /
spill / quantize / snapshot-restore) on fp32 and int8 tiered stores, plus
engine-driven ingest on BOTH kernel backends, plus the 8-way sharded
store in a subprocess (same contract as test_sharded_store.py: the XLA
device count must be set before jax initializes).
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st
from repro.core.engine import EngineConfig, SDIMEngine
from repro.serve.metrics import MetricsRegistry
from repro.serve.profiler import KernelProfiler, KernelRecord, MemoryLedger
from repro.serve.tiered_store import TieredTableStore
from repro.serve.tracing import Tracer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
D = 16
# distinctive static config: no other suite jits the engine with m=21, so
# the first dispatch in THIS process is guaranteed to be a jit warmup
_CFG = dict(m=21, tau=3, d=D)


def _engine(backend="xla"):
    return SDIMEngine(EngineConfig(
        backend=backend,
        interpret=None if backend == "xla" else
        jax.default_backend() != "tpu", **_CFG))


def _batch(b=3, l=11, seed=0):
    seq = jax.random.normal(jax.random.PRNGKey(seed), (b, l, D))
    return seq, jnp.ones((b, l))


class StepClock:
    """Every read advances time by ``step`` — a dispatch (two reads)
    always measures exactly ``step`` seconds."""

    def __init__(self, step: float = 0.25):
        self.t, self.step = 0.0, step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# profiler: warmup exclusion, deterministic timing, cost capture
# ---------------------------------------------------------------------------
def test_warmup_excluded_and_timing_deterministic():
    eng = _engine()
    metrics = MetricsRegistry()
    prof = KernelProfiler(clock=StepClock(0.25), metrics=metrics)
    prof.attach(eng)
    seq, mask = _batch()
    for _ in range(3):
        jax.block_until_ready(eng.encode(seq, mask))
    rec = prof.records["encode"]
    assert rec.n_compiles == 1                # first dispatch == jit warmup
    assert rec.n_calls == 2                   # ...and is NOT in the sample
    assert rec.time_ms == pytest.approx(250.0)
    assert rec.min_s == rec.max_s == pytest.approx(0.25)
    snap = metrics.snapshot()
    assert snap["counters"]["kernel.compiles"] == 1
    assert snap["histograms"]["kernel.encode_ms"]["count"] == 2


def test_cost_capture_and_report_render():
    eng = _engine()
    prof = KernelProfiler()
    prof.attach(eng)
    seq, mask = _batch()
    table = None
    for _ in range(2):
        table = eng.encode(seq, mask)
    d = prof.to_dict()["encode"]
    # cost_analysis flops/bytes captured once, non-negative, AI consistent
    assert d["flops"] > 0 and d["bytes"] > 0
    assert d["ai"] == pytest.approx(d["flops"] / d["bytes"])
    # the host's device has no listed peaks: no prediction, no pct_peak
    assert prof.peaks is None
    assert d["pct_peak"] is None and "predicted" not in d
    report = prof.roofline_report()
    assert "encode" in report and "pct_peak" in report
    # a second kernel shows up as its own row
    q = jax.random.normal(jax.random.PRNGKey(9), (3, D))
    eng.query(q, table)
    assert "query" in prof.roofline_report()


def test_profiled_dispatch_output_parity():
    """Attaching a profiler must not change any numeric output."""
    eng_p, eng_n = _engine(), _engine()
    KernelProfiler().attach(eng_p)
    seq, mask = _batch(seed=3)
    t_p, t_n = eng_p.encode(seq, mask), eng_n.encode(seq, mask)
    np.testing.assert_allclose(np.asarray(t_p), np.asarray(t_n))
    q = jax.random.normal(jax.random.PRNGKey(4), (3, 5, D))
    np.testing.assert_allclose(np.asarray(eng_p.query(q, t_p)),
                               np.asarray(eng_n.query(q, t_n)))
    np.testing.assert_allclose(np.asarray(eng_p.serve(q, seq, mask)),
                               np.asarray(eng_n.serve(q, seq, mask)))


def test_kernel_spans_carry_cost_attrs():
    tracer = Tracer()
    eng = _engine()
    prof = KernelProfiler(tracer=tracer)
    prof.attach(eng)
    # distinct L: an already-warm jit cache (earlier tests share shapes)
    # would otherwise hide the compile span
    seq, mask = _batch(l=13, seed=5)
    with tracer.span("request"):
        eng.encode(seq, mask)
        eng.encode(seq, mask)
    (trace,) = tracer.traces()
    kernel_spans = [s for s in trace.spans if s.name == "kernel.encode"]
    assert len(kernel_spans) == 2
    first, second = kernel_spans
    assert first.attrs.get("compile") is True        # warmup is marked
    assert "compile" not in (second.attrs or {})
    for s in kernel_spans:
        assert s.attrs["flops"] > 0 and s.attrs["bytes"] > 0
        assert s.attrs["ai"] == pytest.approx(
            s.attrs["flops"] / s.attrs["bytes"])
        assert s.attrs["time_ms"] >= 0


def test_empty_record_is_all_zero():
    rec = KernelRecord("x")
    assert rec.time_ms == 0.0 and rec.ai == 0.0 and rec.pct_peak is None
    d = rec.to_dict()
    assert d["min_ms"] == 0.0 and "predicted" not in d
    assert "(no profiled dispatches)" in KernelProfiler().roofline_report()


def test_pct_peak_only_with_a_prediction():
    """pct_peak is predicted over measured time, clamped to 1, and exists
    only once a prediction (from a listed device's peaks) and a timed call
    do — here on synthetic numbers, nothing measured."""
    from repro.distributed import roofline

    rec = KernelRecord("k")
    rec.add(2e-6)
    assert rec.pct_peak is None                    # timed, no prediction
    rec.predicted = roofline.RooflineRecord(
        name="k", n_chips=1, flops_per_chip=0.0,
        hbm_bytes_per_chip=819e9 * 1e-6,           # 1 us at v5e HBM speed
        collective_bytes_per_chip=0.0, collective_breakdown={},
        peak_memory_per_chip=0.0, peaks=roofline.PEAKS[roofline.V5E])
    assert rec.pct_peak == pytest.approx(0.5)
    fast = KernelRecord("k", predicted=rec.predicted)
    fast.add(5e-7)                                 # faster than the bound
    assert fast.pct_peak == 1.0


# ---------------------------------------------------------------------------
# ledger: conservation under hypothesis op bursts (fp32 + int8 tiers)
# ---------------------------------------------------------------------------
def _tiered(dtype, store_dir):
    store = TieredTableStore(2, 4, 8, hot_capacity=3, dtype=dtype,
                             warm_capacity=2, store_dir=store_dir)
    ledger = MemoryLedger()
    ledger.attach(store)
    return store, ledger


def _write_users(store, users, seed):
    rng = np.random.default_rng(seed)
    slots = store.assign(users)
    rows = jnp.asarray(rng.normal(size=(len(users), *store.row_shape))
                       .astype(np.float32))
    store.write(slots, rows)


_OPS = st.lists(
    st.tuples(st.sampled_from(["write", "touch", "evict", "restore"]),
              st.integers(0, 17)),
    min_size=1, max_size=10)


def _run_conservation_ops(dtype, ops):
    """Apply an op burst to a fresh tiered store, asserting the
    conservation invariant after every single op."""
    tmp = tempfile.mkdtemp(prefix="ledger-sweep-")
    try:
        store, ledger = _tiered(dtype, os.path.join(tmp, "cold"))
        live = set()
        for i, (op, x) in enumerate(ops):
            if op == "write":                 # grow + demote + spill chains
                users = [x, x + 1, x + 2]
                _write_users(store, users, seed=i)
                live.update(users)
            elif op == "touch" and live:      # promotes demoted users back
                store.assign(sorted(live)[: 2])
            elif op == "evict" and live:
                u = sorted(live)[x % len(live)]
                store.evict(u)
                live.discard(u)
            elif op == "restore":             # snapshot -> NEW store
                snap = os.path.join(tmp, f"snap{i}")
                store.snapshot(snap)
                store = TieredTableStore.restore(snap)
                ledger = MemoryLedger()       # fresh ledger, fresh baseline
                ledger.attach(store)
            assert ledger.verify() == [], f"after op {i}: {(op, x)}"
        snap = ledger.snapshot()
        assert snap["total_bytes"] == (snap["hot_bytes"]
                                       + snap["warm_bytes"]
                                       + snap["cold_bytes"])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


@settings(max_examples=20, deadline=None, derandomize=True)
@given(dtype=st.sampled_from(["fp32", "int8"]), ops=_OPS)
def test_ledger_conservation_sweep(dtype, ops):
    """Every grow / quantize / demote / spill / promote / evict /
    snapshot-restore burst leaves the ledger balanced against what the
    tiers themselves report (hypothesis sweep, ISSUE 10 satellite)."""
    _run_conservation_ops(dtype, ops)


@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_ledger_conservation_fixed_burst(dtype):
    """Deterministic fallback for the hypothesis sweep (hypothesis is an
    optional dep): one handcrafted burst hitting every transition —
    grow, demote, spill, promote, evict, quantize and snapshot-restore."""
    _run_conservation_ops(dtype, [
        ("write", 0), ("write", 3), ("touch", 0), ("write", 6),
        ("evict", 2), ("restore", 0), ("write", 9), ("touch", 1),
        ("evict", 0), ("write", 12), ("restore", 0), ("touch", 2),
    ])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ledger_conserves_under_engine_ingest(backend):
    """Engine-driven writes (encode ingest + donated update) against a
    tiered int8 store keep the ledger balanced on both kernel backends."""
    from repro.serve.bse_server import BSEServer

    emb = jax.random.normal(jax.random.PRNGKey(7), (64, D))

    def embed(params, items, cats):
        return emb[jnp.asarray(items) % 64]

    tmp = tempfile.mkdtemp(prefix="ledger-ingest-")
    try:
        srv = BSEServer(embed, None, _engine(backend),
                        wire_dtype=jnp.float32, table_dtype="int8",
                        hot_capacity=4, warm_capacity=2, store_dir=tmp,
                        metrics=MetricsRegistry())
        ledger = MemoryLedger(metrics=srv.metrics)
        ledger.attach(srv.store)
        rng = np.random.default_rng(0)
        for lo in range(0, 12, 4):
            users = list(range(lo, lo + 4))
            srv.ingest_histories(users, rng.integers(0, 64, (4, 9)),
                                 rng.integers(0, 16, (4, 9)))
            assert ledger.verify() == []
        srv.ingest_events(list(range(4)), rng.integers(0, 64, 4),
                          rng.integers(0, 16, 4))
        assert ledger.verify() == []
        q = emb[rng.integers(0, 64, (4, 6))]
        jax.block_until_ready(srv.serve_candidates(list(range(4)), q))
        assert ledger.verify() == []
        snap = ledger.snapshot()
        assert snap["events"].get("grow", 0) >= 1
        assert snap["events"].get("demote", 0) >= 1
        assert snap["hot_bytes"] > 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_ledger_conserves_on_sharded_store_subprocess():
    """8-way sharded store conservation: growth, donated sharded updates
    and evictions, in a subprocess where XLA fakes 8 host devices."""
    code = f"""
import json, os
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.compat import make_auto_mesh
from repro.core.engine import EngineConfig, SDIMEngine
from repro.serve.bse_server import BSEServer
from repro.serve.profiler import MemoryLedger

D = {D}
emb = jax.random.normal(jax.random.PRNGKey(7), (64, D))
def embed(params, items, cats):
    return emb[jnp.asarray(items) % 64]

eng = SDIMEngine(EngineConfig(m=12, tau=2, d=D, backend="xla"))
mesh = make_auto_mesh((8,), ("model",))
srv = BSEServer(embed, None, eng, wire_dtype=jnp.float32, capacity=8,
                mesh=mesh)
ledger = MemoryLedger()
ledger.attach(srv.store)
rng = np.random.default_rng(0)
errs = []
for lo in range(0, 24, 8):                     # forces per-shard growth
    users = list(range(lo, lo + 8))
    srv.ingest_histories(users, rng.integers(0, 64, (8, 9)),
                         rng.integers(0, 16, (8, 9)))
    errs += ledger.verify()
srv.ingest_events(list(range(8)), rng.integers(0, 64, 8),
                  rng.integers(0, 16, 8))
errs += ledger.verify()
for u in range(0, 6):
    assert srv.evict(u)
errs += ledger.verify()
snap = ledger.snapshot()
print(json.dumps({{"errs": errs, "n_shards": srv.store.n_shards,
                  "events": snap["events"],
                  "hot_bytes": snap["hot_bytes"]}}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().split("\n")[-1])
    assert res["errs"] == []
    assert res["n_shards"] == 8
    assert res["events"].get("grow", 0) >= 1    # growth really happened
    assert res["events"].get("evict", 0) >= 6
    assert res["hot_bytes"] > 0


def test_ledger_detects_missed_event():
    """A byte mutation that bypasses the event sites MUST show up in
    verify() — the invariant is falsifiable, not vacuously true."""
    store, ledger = _tiered("fp32", None)
    _write_users(store, [0, 1], seed=0)
    assert ledger.verify() == []
    # silently double the device allocation behind the ledger's back
    store.hot.ledger = None
    store.hot._grow()
    errs = ledger.verify()
    assert errs and "hot" in errs[0]
