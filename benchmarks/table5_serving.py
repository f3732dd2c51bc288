"""Table 5 — online serving ablation: BSE-decoupled SDIM vs inline SDIM vs
exact target attention, at the paper's online scale (T=2000 behaviors,
B candidates per request).

The paper reports: long-seq TA undeployable (+50% latency, 25–30 ms);
SDIM+BSE ≈ +1 ms (mostly transmission). Here we measure CTR-server wall time
per request on CPU and the decoupled/inline/TA ratios + the fixed
transmission size. Every SDIM deployment goes through the ``SDIMEngine``
and is measured on BOTH backends side by side — ``xla`` (reference
formulation) and ``pallas`` (fused kernels; interpret mode off-TPU) — so
the serving benchmark finally measures the kernel path.

The **throughput** section measures the multi-user TableStore path: N users
served per ``handle_requests`` burst (one ``fetch_many`` gather + one
scoring dispatch) vs the per-user ``handle_request`` loop, and batched
``ingest_events`` vs the per-event loop — users/sec and events/sec on both
backends (the per-dispatch overhead the per-user loop pays N times is
exactly what §4.4's "millions of users" deployment cannot afford).

The **sharded** section reports the same store flow against a
``ShardedTableStore`` row-sharded over every visible device (the ``shards``
CSV column): run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
to exercise an 8-way host-local mesh on CPU.

The **fused** section measures the single-pass serve megakernel
(``kernels/sdim_fused_serve`` via ``BSEServer.serve_candidates``) against
the two-dispatch path (``fetch_many`` gather + model-side ``engine.query``)
at N users per backend: users/sec and per-burst p50/p95/p99 latency, plus
the int8-quantized store (same fused path, dequant-in-kernel) and a
roofline bytes-accessed comparison of the compiled graphs. The **auc**
section pins quantization quality: a trained CTR model served through the
int8 fused path must match the fp32 unfused oracle's AUC on held-out
graded synthetic data. Both sections feed ``BENCH_serving.json`` at the
repo root (schema checked by ``tools/bench_check.py`` — ``make ci`` fails
if it is missing or malformed).

The **ingest** section measures the async ingestion runtime
(``serve/ingest.py``) under a mixed read/write workload: Poisson event
arrivals submitted while Zipf-distributed fetch bursts are served, with the
writer loop folding concurrently. It records read-only vs under-ingest
serve-latency percentiles (the acceptance bound is under-ingest p95 within
1.2x of read-only p95 — reads gather from the committed view and never join
a fold), folded events/sec, backpressure drops, and the staleness p95, all
into ``BENCH_serving.json``.

The **capacity-pressure** section measures the tiered store
(``serve/tiered_store.py``): Zipf-distributed traffic over a working set
4x the device-hot capacity, so every burst promotes from the host warm pool
/ disk cold segments and demotes under pressure — hit-rate, promote/demote
bytes and users/sec vs the unbounded store, on both backends. Promote and
demote are batched per burst (the gather/scatter counters in the derived
column stay O(#bursts), never O(users)).

The **slo** section (schema 2) runs the full production request path —
``CTRServer.handle_requests`` behind admission control (token-bucket rate
limit + concurrency bound), a tiered store spilling past the hot tier, and
the cold-tier circuit breaker armed — under an open-loop Zipf+Poisson
overload that deliberately offers more than the bucket admits. It writes
p50/p95/p99 tail latency plus the shed and degrade rates into
``bench['slo']`` (validated by ``tools/bench_check.py``; ``make ci`` fails
if the section is missing), pinning the §4.4 latency-guarantee story:
under overload the server sheds explicitly and degrades cold reads to
counted misses — it never stalls and never drops silently.

The **trace** section (schema 3) replays a small tiered workload with the
request tracer (``serve/tracing.py``) enabled and records the span-coverage
fraction and jit-compile span count into ``bench['trace']``. Every run is
also stamped with ``git_rev`` and appended as one summary line to
``results/bench_history.jsonl`` — ``tools/bench_trend.py`` prints the
per-commit p95 / users-per-sec trajectory from that history.

The **profile** section (schema 4) runs a small tiered fused-serve workload
under the measured-profiling layer (``serve/profiler.py``): a
``KernelProfiler`` on the engine records per-dispatch block-until-ready
times (jit warmup excluded) plus compile-time ``cost_analysis()``
flops/bytes and the analytical roofline prediction per kernel, and a
``MemoryLedger`` accounts HBM/host/disk bytes across every
grow/evict/promote/demote/quantize event (conservation asserted inline).
``bench['profile']`` carries ``per_kernel`` (time_ms / flops / bytes / ai /
pct_peak / predicted) and ``mem`` (hot/warm/cold bytes) — so the fused-serve
kernel's measured time sits next to its cost-model prediction on every run
(required at schema 4 by ``tools/bench_check.py``; rendered by
``tools/profile_report.py``).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.interest import InterestConfig
from repro.data.synthetic import SyntheticCTRConfig, generate_batch
from repro.models.ctr import CTRModel, CTRConfig
from repro.serve.ctr_server import CTRServer


def run(quick: bool = True):
    bench = {"schema": 4, "quick": bool(quick),
             "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime()),
             "backends": {}, "quantization": {}, "roofline": {},
             "hit_rate": {}, "ingest": {}, "slo": {}, "trace": {},
             "profile": {}}
    T = 2000
    B = 256 if quick else 1024
    n_req = 5 if quick else 20
    dcfg = SyntheticCTRConfig(hist_len=T, n_items=4000, n_cats=50)
    rows = []
    servers = {}
    variants = [("decoupled", "sdim", "xla"), ("decoupled", "sdim", "pallas"),
                ("inline", "sdim", "xla"), ("inline", "sdim", "pallas"),
                ("target_attention", "target", None)]
    for mode, kind, backend in variants:
        interest = InterestConfig(kind=kind, m=48, tau=3,
                                  backend=backend or "auto")
        cfg = CTRConfig(arch="din", n_items=4000, n_cats=50, long_len=T,
                        short_len=16, mlp_hidden=(64, 32), interest=interest)
        model = CTRModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        server = CTRServer.build(model, params, mode)
        rng = np.random.default_rng(0)
        raw = generate_batch(dcfg, 1, 0)
        user = {k: jnp.asarray(v) for k, v in raw.items() if k.startswith("hist")}
        ci = jnp.asarray(rng.integers(0, 4000, B).astype(np.int32))
        cc = jnp.asarray(rng.integers(0, 50, B).astype(np.int32))
        ctx = jnp.zeros((B, 4))
        server.handle_request("u", user, ci, cc, ctx)   # warm (compile + encode)
        server.stats.n_requests = 0
        server.stats.total_time_s = 0.0
        for i in range(n_req):
            server.handle_request("u", user, ci, cc, ctx)
        tag = f"{mode}[{backend}]" if backend else mode
        servers[tag] = server
        rows.append({"name": f"table5/{tag}", "us_per_call":
                     1e3 * server.stats.ms_per_request,
                     "shards": 1 if mode == "decoupled" else "-",
                     "derived": f"ms_per_request={server.stats.ms_per_request:.2f}"})
    dec = servers["decoupled[xla]"].stats.ms_per_request
    ta = servers["target_attention"].stats.ms_per_request
    inl = servers["inline[xla]"].stats.ms_per_request
    rows.append({"name": "table5/latency_saved_vs_TA", "us_per_call": 0.0,
                 "derived": f"decoupled_saves={100 * (1 - dec / ta):.1f}%_of_TA_"
                            f"(paper:95%);inline/decoupled={inl / dec:.2f}x"})
    rows.append({"name": "table5/backend_ratio", "us_per_call": 0.0,
                 "derived": "pallas/xla_decoupled="
                            f"{servers['decoupled[pallas]'].stats.ms_per_request / dec:.2f}x"
                            "(interpret_mode_off-TPU)"})
    rows.append({"name": "table5/transmission_bytes", "us_per_call": 0.0,
                 "derived": f"{servers['decoupled[xla]'].bse.table_bytes()}"
                            "B_fixed_(L-free,bf16_wire)"})
    rows.extend(throughput_rows(quick))
    rows.extend(fused_rows(quick, bench))
    rows.extend(auc_parity_rows(quick, bench))
    rows.extend(sharded_rows(quick))
    rows.extend(ingest_rows(quick, bench))
    rows.extend(pressure_rows(quick, bench))
    rows.extend(slo_rows(quick, bench))
    rows.extend(trace_rows(quick, bench))
    rows.extend(profile_rows(quick, bench))
    _write_bench_json(bench)
    return rows


def throughput_rows(quick: bool = True, n_users: int = 1024,
                    chunk: int = 256) -> list[dict]:
    """Multi-user TableStore throughput: batched fetch_many+serve vs the
    per-user loop at N users, and batched vs per-event ingest."""
    L, C = 256, 8
    rows = []
    for backend in ("xla", "pallas"):
        # interpret-mode Pallas on CPU is a python-loop simulator; keep its
        # user count bounded in quick mode (the 5x claim is XLA@N=1024)
        n = n_users if backend == "xla" or not quick else 128
        ch = min(chunk, n)
        loop_n = min(n, 128 if backend == "xla" else 16)
        dcfg = SyntheticCTRConfig(hist_len=L, n_items=4000, n_cats=50)
        cfg = CTRConfig(arch="din", n_items=4000, n_cats=50, long_len=L,
                        short_len=8, mlp_hidden=(32,), embed_dim=16,
                        interest=InterestConfig(kind="sdim", m=24, tau=3,
                                                backend=backend))
        model = CTRModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        ctr = CTRServer.build(model, params, "decoupled", capacity=n)
        bse = ctr.bse
        rng = np.random.default_rng(0)
        raw = generate_batch(dcfg, n, 0)
        hists = {k: v for k, v in raw.items() if k.startswith("hist")}
        for lo in range(0, n, ch):                         # batched bootstrap
            hi = min(lo + ch, n)
            sl = slice(lo, hi)
            bse.ingest_histories(
                list(range(lo, hi)), raw["hist_items"][sl],
                raw["hist_cats"][sl], raw["hist_mask"][sl])
        ci = rng.integers(0, 4000, (n, C)).astype(np.int32)
        cc = rng.integers(0, 50, (n, C)).astype(np.int32)
        zctx = np.zeros((C, 4), np.float32)

        # requests carry HOST arrays (they arrive from the network in
        # production); both paths pay the same device upload
        def request(u):
            return (u, {k: v[u][None] for k, v in hists.items()},
                    ci[u], cc[u], zctx)

        reqs = [request(u) for u in range(n)]
        ctr.handle_request(*reqs[0])                       # warm per-user jit
        ctr.handle_requests(reqs[:ch])                     # warm batched jit
        if n % ch:
            ctr.handle_requests(reqs[-(n % ch):])          # warm tail shape
        t0 = time.perf_counter()
        for r in reqs[:loop_n]:
            ctr.handle_request(*r)
        loop_ups = loop_n / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        for lo in range(0, n, ch):
            ctr.handle_requests(reqs[lo:lo + ch])
        batch_ups = n / (time.perf_counter() - t0)

        ev_i = rng.integers(0, 4000, n)
        ev_c = rng.integers(0, 50, n)
        bse.ingest_event(0, int(ev_i[0]), int(ev_c[0]))    # warm both paths
        bse.ingest_events(list(range(ch)), ev_i[:ch], ev_c[:ch])
        if n % ch:                                         # warm tail shape
            bse.ingest_events(list(range(n % ch)),
                              ev_i[:n % ch], ev_c[:n % ch])
        # ingest is async (no fetch forces completion) — sync before reading
        # timers so events/sec measures compute, not dispatch
        bse.store.data.block_until_ready()
        t0 = time.perf_counter()
        for u in range(loop_n):
            bse.ingest_event(u, int(ev_i[u]), int(ev_c[u]))
        bse.store.data.block_until_ready()
        loop_eps = loop_n / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        for lo in range(0, n, ch):
            hi = min(lo + ch, n)
            bse.ingest_events(list(range(lo, hi)),
                              ev_i[lo:hi], ev_c[lo:hi])
        bse.store.data.block_until_ready()
        batch_eps = n / (time.perf_counter() - t0)

        tag = f"throughput[{backend}]"
        rows.append({"name": f"table5/{tag}/users_per_sec",
                     "us_per_call": 1e6 / batch_ups, "shards": 1,
                     "derived": f"batched={batch_ups:.0f}/s_loop={loop_ups:.0f}/s"
                                f"_speedup={batch_ups / loop_ups:.1f}x_N={n}"})
        rows.append({"name": f"table5/{tag}/events_per_sec",
                     "us_per_call": 1e6 / batch_eps, "shards": 1,
                     "derived": f"batched={batch_eps:.0f}/s_loop={loop_eps:.0f}/s"
                                f"_speedup={batch_eps / loop_eps:.1f}x_N={n}"})
    return rows


def fused_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """Fused serve megakernel vs the two-dispatch path, fp32 and int8
    stores: users/sec + per-burst latency percentiles per backend, the
    stored-bytes ratio, and compiled bytes-accessed (roofline) for the
    three graphs. The int8 server runs the SAME ``serve_candidates`` call —
    dequantization happens inside the gather+query dispatch."""
    from repro.core.engine import EngineConfig, SDIMEngine
    from repro.distributed import roofline
    from repro.kernels.sdim_fused_serve.ref import sdim_fused_serve_ref
    from repro.serve.bse_server import BSEServer

    d, C, L = 32, 8, 64
    reps = 3 if quick else 10
    emb_i = jax.random.normal(jax.random.PRNGKey(11), (4000, d // 2))
    emb_c = jax.random.normal(jax.random.PRNGKey(12), (50, d // 2))

    def embed(params, items, cats):
        return jnp.concatenate([emb_i[jnp.asarray(items) % 4000],
                                emb_c[jnp.asarray(cats) % 50]], axis=-1)

    rows = []
    for backend in ("xla", "pallas"):
        # interpret-mode Pallas on CPU is a python-loop simulator; the
        # 1.5x acceptance claim is XLA@N=1024
        N = 1024 if backend == "xla" else (128 if quick else 512)
        bs = min(256, N)
        eng = SDIMEngine(EngineConfig(
            m=24, tau=3, d=d, backend=backend,
            interpret=None if backend == "xla"
            else jax.default_backend() != "tpu"))
        rng = np.random.default_rng(0)
        hist_i = rng.integers(0, 4000, (N, L))
        hist_c = rng.integers(0, 50, (N, L))
        servers = {}
        for dt in ("fp32", "int8"):
            # fp32 wire so the two paths differ ONLY in fused-vs-two
            # dispatch (and the bytes row compares stored fp32 vs int8,
            # not the bf16 wire default)
            srv = BSEServer(embed, None, eng, capacity=N,
                            wire_dtype=jnp.float32, table_dtype=dt)
            for lo in range(0, N, bs):
                us = list(range(lo, lo + bs))
                srv.ingest_histories(us, hist_i[lo:lo + bs],
                                     hist_c[lo:lo + bs])
            servers[dt] = srv
        q = embed(None, rng.integers(0, 4000, (N, C)),
                  rng.integers(0, 50, (N, C)))
        users = list(range(N))

        def two_dispatch(lo):
            tables = servers["fp32"].fetch_many(users[lo:lo + bs])
            return eng.query(q[lo:lo + bs], jnp.asarray(tables, jnp.float32))

        def fused(lo):
            return servers["fp32"].serve_candidates(users[lo:lo + bs],
                                                    q[lo:lo + bs])

        def fused_int8(lo):
            return servers["int8"].serve_candidates(users[lo:lo + bs],
                                                    q[lo:lo + bs])

        variants = {"two_dispatch": two_dispatch, "fused": fused,
                    "fused_int8": fused_int8}
        stats, outs = {}, {}
        for name, fn in variants.items():
            outs[name] = np.asarray(jax.block_until_ready(fn(0)))  # warm
            lat = []
            t0 = time.perf_counter()
            for _ in range(reps):
                for lo in range(0, N, bs):
                    tb = time.perf_counter()
                    jax.block_until_ready(fn(lo))
                    lat.append(time.perf_counter() - tb)
            ups = reps * N / (time.perf_counter() - t0)
            stats[name] = {
                "users_per_sec": round(ups, 1),
                "p50_ms": round(1e3 * float(np.percentile(lat, 50)), 3),
                "p95_ms": round(1e3 * float(np.percentile(lat, 95)), 3),
                "p99_ms": round(1e3 * float(np.percentile(lat, 99)), 3),
            }
        speedup = (stats["fused"]["users_per_sec"]
                   / stats["two_dispatch"]["users_per_sec"])
        err_fused = float(np.abs(outs["fused"] - outs["two_dispatch"]).max())
        err_int8 = float(np.abs(outs["fused_int8"] - outs["two_dispatch"]).max())
        tag = f"fused[{backend}]"
        rows.append({"name": f"table5/{tag}/users_per_sec",
                     "us_per_call": 1e6 / stats["fused"]["users_per_sec"],
                     "shards": 1,
                     "derived": f"fused={stats['fused']['users_per_sec']:.0f}/s"
                                f"_two_dispatch="
                                f"{stats['two_dispatch']['users_per_sec']:.0f}/s"
                                f"_speedup={speedup:.2f}x_N={N}_burst={bs}"})
        rows.append({"name": f"table5/{tag}/latency",
                     "us_per_call": 1e3 * stats["fused"]["p50_ms"],
                     "shards": 1,
                     "derived": f"p50={stats['fused']['p50_ms']}ms"
                                f"_p95={stats['fused']['p95_ms']}ms"
                                f"_p99={stats['fused']['p99_ms']}ms"
                                f"_int8_p50={stats['fused_int8']['p50_ms']}ms"})
        rows.append({"name": f"table5/{tag}/parity",
                     "us_per_call": 0.0, "shards": 1,
                     "derived": f"max|fused-two_dispatch|={err_fused:.1e}"
                                f"_max|int8-fp32|={err_int8:.1e}"})
        if bench is not None:
            bench["backends"][backend] = {
                "n_users": N, "burst": bs, **stats,
                "speedup_fused_vs_two_dispatch": round(speedup, 3),
                "max_abs_err_fused_vs_two_dispatch": err_fused,
                "max_abs_err_int8_vs_fp32": err_int8,
            }

        if backend == "xla":
            b_fp32 = servers["fp32"].table_bytes()
            b_int8 = servers["int8"].table_bytes()
            ratio = b_fp32 / b_int8
            rows.append({"name": "table5/quantized/table_bytes",
                         "us_per_call": 0.0, "shards": 1,
                         "derived": f"fp32={b_fp32}B_int8={b_int8}B"
                                    f"_ratio={ratio:.2f}x_(payload+scales)"})
            if bench is not None:
                bench["quantization"].update({
                    "table_bytes_fp32": int(b_fp32),
                    "table_bytes_int8": int(b_int8),
                    "bytes_ratio": round(ratio, 3),
                })
            # roofline: compiled bytes-accessed per graph — int8 shrinks
            # the store operand ~4x. The fused-vs-two-dispatch win is
            # dispatch count + host round-trip, which the cost model does
            # not price; that shows up in the wall-clock rows above.
            st32 = servers["fp32"].store
            st8 = servers["int8"].store
            slots = jnp.arange(bs, dtype=jnp.int32)
            qb = q[:bs]
            gather = jax.jit(lambda dat, sl: dat[sl].astype(jnp.float32))
            query = jax.jit(lambda tb, qq: eng.query(qq, tb))
            fused_j = jax.jit(lambda dat, sl, qq: sdim_fused_serve_ref(
                dat, sl, qq, eng.R, eng.cfg.tau))
            fused8_j = jax.jit(lambda dat, sc, sl, qq: sdim_fused_serve_ref(
                dat, sl, qq, eng.R, eng.cfg.tau, scales=sc))
            tables = gather(st32.data, slots)
            recs = {
                "two_dispatch": roofline.analyze(
                    "gather", gather.lower(st32.data, slots).compile(),
                    1).hbm_bytes_per_chip + roofline.analyze(
                    "query", query.lower(tables, qb).compile(),
                    1).hbm_bytes_per_chip,
                "fused": roofline.analyze(
                    "fused", fused_j.lower(st32.data, slots, qb).compile(),
                    1).hbm_bytes_per_chip,
                "fused_int8": roofline.analyze(
                    "fused_int8", fused8_j.lower(
                        st8.data, st8.scales, slots, qb).compile(),
                    1).hbm_bytes_per_chip,
            }
            rows.append({"name": "table5/fused/roofline_bytes",
                         "us_per_call": 0.0, "shards": 1,
                         "derived": "_".join(f"{k}={v:.0f}B"
                                             for k, v in recs.items())})
            if bench is not None:
                bench["roofline"] = {k: float(v) for k, v in recs.items()}
    return rows


def auc_parity_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """AUC parity gate for int8 storage: train one SDIM CTR model on the
    graded synthetic data (table 2/3 smoke depth), then serve the SAME
    held-out examples through (a) the fp32 unfused oracle path and (b) the
    int8 fused megakernel path, and compare serving-path AUCs. Per-row
    scales cancel under Eq. 12's ℓ2-normalize, so the gap should sit well
    inside the 1e-3 acceptance bound."""
    from benchmarks.common import auc, paper_data_config, paper_model_config
    from repro.data.pipeline import DeterministicStream
    from repro.data.synthetic import generate_batch_graded
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig

    steps = 200 if quick else 400
    n_eval = 512 if quick else 2048
    batch, burst, long_len = 128, 128, 64
    dcfg = paper_data_config(long_len)
    mcfg = paper_model_config("sdim", long_len, m=24)
    model = CTRModel(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    loss_fn = lambda p, b: model.loss(p, b)[0]
    init_state, step_fn = make_train_step(
        loss_fn, OptimizerConfig(kind="adamw", lr=2e-3), donate=False)
    state = init_state(params)
    stream = DeterministicStream(lambda s: generate_batch_graded(dcfg, batch, s),
                                 base_seed=0)
    for _ in range(steps):
        b = {k: jnp.asarray(v) for k, v in next(stream).items()}
        state, _ = step_fn(state, b)
    params = state["params"]

    servers = {
        "fp32_unfused": CTRServer.build(
            model, params, "decoupled", capacity=n_eval,
            wire_dtype=jnp.float32, table_dtype="fp32"),
        "int8_fused": CTRServer.build(
            model, params, "decoupled", capacity=n_eval,
            wire_dtype=jnp.float32, table_dtype="int8", fused=True),
    }
    scores = {k: [] for k in servers}
    labels = []
    for lo in range(0, n_eval, burst):
        eb = generate_batch_graded(dcfg, burst, 10_000_000 + lo)
        labels.append(eb["label"])
        reqs = [(lo + i,
                 {k: eb[k][i][None] for k in ("hist_items", "hist_cats",
                                              "hist_mask")},
                 eb["cand_item"][i:i + 1], eb["cand_cat"][i:i + 1],
                 eb["ctx"][i][None])
                for i in range(burst)]
        for name, srv in servers.items():
            scores[name].extend(float(s[0]) for s in srv.handle_requests(reqs))
    labels = np.concatenate(labels)
    auc_fp32 = auc(labels, np.asarray(scores["fp32_unfused"]))
    auc_int8 = auc(labels, np.asarray(scores["int8_fused"]))
    gap = abs(auc_fp32 - auc_int8)
    if bench is not None:
        bench["quantization"].update({
            "auc_fp32_unfused": round(auc_fp32, 5),
            "auc_int8_fused": round(auc_int8, 5),
            "auc_gap": round(gap, 6),
            "train_steps": steps, "eval_examples": n_eval,
        })
    return [{"name": "table5/quantized/auc_parity", "us_per_call": 0.0,
             "shards": 1,
             "derived": f"fp32_unfused={auc_fp32:.4f}"
                        f"_int8_fused={auc_int8:.4f}_gap={gap:.1e}"
                        f"_(bound_1e-3)_steps={steps}_eval={n_eval}"}]


def profile_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """Measured roofline + memory ledger (schema 4): a small tiered
    fused-serve workload with ``serve/profiler.py`` attached — the
    ``KernelProfiler`` on the engine times every dispatch (block-until-ready,
    jit warmup excluded) and captures compile-time ``cost_analysis()``
    flops/bytes + the analytical roofline prediction per kernel; the
    ``MemoryLedger`` accounts device/host/disk bytes across every
    grow/evict/promote/demote/quantize/spill event, with conservation
    (ledger == tier-reported nbytes) asserted before anything is written.
    XLA backend: the measured-vs-predicted comparison needs the compiled
    graph, not the interpret-mode python simulator."""
    from repro.core.engine import EngineConfig, SDIMEngine
    from repro.serve.bse_server import BSEServer
    from repro.serve.metrics import MetricsRegistry
    from repro.serve.profiler import KernelProfiler, MemoryLedger

    d, L, C = 16, 32, 8
    H = 16                         # hot capacity
    W = 3 * H                      # working set: spills warm + cold
    n_bursts = 8 if quick else 32
    emb_i = jax.random.normal(jax.random.PRNGKey(11), (4000, d // 2))
    emb_c = jax.random.normal(jax.random.PRNGKey(12), (50, d // 2))

    def embed(params, items, cats):
        return jnp.concatenate([emb_i[jnp.asarray(items) % 4000],
                                emb_c[jnp.asarray(cats) % 50]], axis=-1)

    eng = SDIMEngine(EngineConfig(m=24, tau=3, d=d, backend="xla"))
    metrics = MetricsRegistry()
    prof = KernelProfiler(metrics=metrics)
    prof.attach(eng)
    tmp = tempfile.mkdtemp(prefix="bse-profile-")
    try:
        srv = BSEServer(embed, None, eng, wire_dtype=jnp.float32,
                        hot_capacity=H, warm_capacity=H, store_dir=tmp,
                        metrics=metrics)
        ledger = MemoryLedger(metrics=metrics)
        ledger.attach(srv.store)
        rng = np.random.default_rng(0)
        hist_i = rng.integers(0, 4000, (W, L))
        hist_c = rng.integers(0, 50, (W, L))
        for lo in range(0, W, H):                       # encode dispatches
            srv.ingest_histories(list(range(lo, lo + H)),
                                 hist_i[lo:lo + H], hist_c[lo:lo + H])
        p = 1.0 / (np.arange(1, W + 1) ** 1.1)          # Zipf(1.1) traffic
        p /= p.sum()
        for _ in range(n_bursts):                       # fused-serve bursts
            us = [int(u) for u in rng.choice(W, size=H, p=p)]
            uniq = list(dict.fromkeys(us))
            q = embed(None, rng.integers(0, 4000, (len(uniq), C)),
                      rng.integers(0, 50, (len(uniq), C)))
            jax.block_until_ready(srv.serve_candidates(uniq, q))
            srv.ingest_events(uniq, rng.integers(0, 4000, len(uniq)),
                              rng.integers(0, 50, len(uniq)))   # update path
        conservation = ledger.verify()
        assert not conservation, f"memory ledger broken: {conservation}"
        mem = ledger.snapshot()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    per_kernel = prof.to_dict()
    if bench is not None:
        bench["profile"] = {"per_kernel": per_kernel, "mem": mem}
    fused = per_kernel.get("serve_fused", {})
    pred = fused.get("predicted", {})
    rows = [
        {"name": "table5/profile/serve_fused", "us_per_call":
         1e3 * fused.get("time_ms", 0.0), "shards": 1,
         "derived": f"measured={fused.get('time_ms', 0.0):.4f}ms"
                    f"_predicted={pred.get('roofline_ms', 0.0):.4f}ms"
                    f"_bound={pred.get('bottleneck', '-')}"
                    f"_ai={fused.get('ai', 0.0):.2f}"
                    f"_pct_peak={fused.get('pct_peak')}"
                    f"_calls={fused.get('calls', 0)}"},
        {"name": "table5/profile/mem_ledger", "us_per_call": 0.0,
         "shards": 1,
         "derived": f"hot={mem['hot_bytes']}B_warm={mem['warm_bytes']}B"
                    f"_cold={mem['cold_bytes']}B_conservation=OK"},
    ]
    return rows


def _git_rev() -> str:
    """Short commit hash of the checkout the benchmark ran in, or
    ``"unknown"`` outside a git repo / without a git binary."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        rev = out.stdout.strip()
        if out.returncode == 0 and rev:
            return rev
    except Exception:
        pass
    return "unknown"


def _append_bench_history(bench: dict, root: str) -> str:
    """Append a one-line summary of this run to
    ``results/bench_history.jsonl`` — the per-commit trajectory that
    ``tools/bench_trend.py`` renders. Append-only: each benchmark run adds
    one record; the full ``BENCH_serving.json`` keeps only the latest."""
    slo = bench.get("slo") or {}
    trace = bench.get("trace") or {}
    fused = {}
    for backend, d in (bench.get("backends") or {}).items():
        ups = (d.get("fused") or {}).get("users_per_sec") \
            if isinstance(d, dict) else None
        if ups is not None:
            fused[backend] = ups
    profile = bench.get("profile") or {}
    fused_kernel = (profile.get("per_kernel") or {}).get("serve_fused") or {}
    mem = profile.get("mem") or {}
    rec = {
        "git_rev": bench.get("git_rev", "unknown"),
        "generated_utc": bench.get("generated_utc"),
        "schema": bench.get("schema"),
        "quick": bench.get("quick"),
        "slo_p50_ms": slo.get("p50_ms"),
        "slo_p95_ms": slo.get("p95_ms"),
        "slo_p99_ms": slo.get("p99_ms"),
        "shed_rate": slo.get("shed_rate"),
        "fused_users_per_sec": fused,
        "span_coverage": trace.get("span_coverage"),
        "n_compile_spans": trace.get("n_compile_spans"),
        "fused_time_ms": fused_kernel.get("time_ms"),
        "hot_bytes": mem.get("hot_bytes"),
    }
    hist_dir = os.path.join(root, "results")
    os.makedirs(hist_dir, exist_ok=True)
    path = os.path.join(hist_dir, "bench_history.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")
    return path


def _write_bench_json(bench: dict) -> str:
    """Atomically write ``BENCH_serving.json`` at the repo root (schema
    validated by ``tools/bench_check.py``), stamped with the current git
    revision, and append this run's summary to the benchmark history."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    bench.setdefault("git_rev", _git_rev())
    path = os.path.join(root, "BENCH_serving.json")
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _append_bench_history(bench, root)
    return path


def sharded_rows(quick: bool = True, n_users: int = 512,
                 chunk: int = 128) -> list[dict]:
    """ShardedTableStore over every visible device (the ``shards`` column):
    batched ingest_histories / fetch_many / ingest_events against the
    row-sharded store, with the single-device TableStore numbers inline for
    comparison. XLA backend only — kernel (Pallas) parity under sharding is
    pinned by ``tests/test_sharded_store.py``; interpret mode would measure
    the simulator, not the path. On one device the sharded store still runs
    (a 1-shard mesh), so the column is always populated."""
    from repro.distributed.compat import make_auto_mesh

    S = len(jax.devices())
    n = min(n_users, 128) if quick and S == 1 else n_users
    ch = min(chunk, n)
    L = 128
    dcfg = SyntheticCTRConfig(hist_len=L, n_items=4000, n_cats=50)
    cfg = CTRConfig(arch="din", n_items=4000, n_cats=50, long_len=L,
                    short_len=8, mlp_hidden=(32,), embed_dim=16,
                    interest=InterestConfig(kind="sdim", m=24, tau=3,
                                            backend="xla"))
    model = CTRModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    raw = generate_batch(dcfg, n, 0)
    rng = np.random.default_rng(0)
    ev_i = rng.integers(0, 4000, n)
    ev_c = rng.integers(0, 50, n)
    rows = []
    mesh = make_auto_mesh((S,), ("model",))
    perf = {}
    for variant, m in (("single", None), ("sharded", mesh)):
        ctr = CTRServer.build(model, params, "decoupled", mesh=m, capacity=n)
        bse = ctr.bse

        def ingest_all():
            for lo in range(0, n, ch):
                hi = min(lo + ch, n)
                sl = slice(lo, hi)
                bse.ingest_histories(
                    list(range(lo, hi)), raw["hist_items"][sl],
                    raw["hist_cats"][sl], raw["hist_mask"][sl])

        ingest_all()                                       # warm (compile)
        bse.store.clear()                                  # re-ingest from empty
        t0 = time.perf_counter()
        ingest_all()
        jax.block_until_ready(bse.store.data)
        enc_ups = n / (time.perf_counter() - t0)

        users = list(range(n))
        bse.fetch_many(users[:ch])                         # warm
        t0 = time.perf_counter()
        for lo in range(0, n, ch):
            jax.block_until_ready(bse.fetch_many(users[lo:lo + ch]))
        fetch_ups = n / (time.perf_counter() - t0)

        bse.ingest_events(users[:ch], ev_i[:ch], ev_c[:ch])  # warm
        jax.block_until_ready(bse.store.data)
        t0 = time.perf_counter()
        for lo in range(0, n, ch):
            hi = min(lo + ch, n)
            bse.ingest_events(users[lo:hi], ev_i[lo:hi], ev_c[lo:hi])
        jax.block_until_ready(bse.store.data)
        perf[variant] = (enc_ups, fetch_ups, n / (time.perf_counter() - t0))

    enc, fetch, ev = perf["sharded"]
    enc1, fetch1, ev1 = perf["single"]
    rows.append({"name": "table5/sharded/fetch_users_per_sec",
                 "us_per_call": 1e6 / fetch, "shards": S,
                 "derived": f"sharded={fetch:.0f}/s_single={fetch1:.0f}/s"
                            f"_N={n}_chunk={ch}"})
    rows.append({"name": "table5/sharded/encode_users_per_sec",
                 "us_per_call": 1e6 / enc, "shards": S,
                 "derived": f"sharded={enc:.0f}/s_single={enc1:.0f}/s"})
    rows.append({"name": "table5/sharded/events_per_sec",
                 "us_per_call": 1e6 / ev, "shards": S,
                 "derived": f"sharded={ev:.0f}/s_single={ev1:.0f}/s"
                            f"_capacity_scales_{S}x"})
    return rows


def ingest_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """Async ingestion under a mixed read/write workload: the writer loop
    folds Poisson-arriving events while the main thread serves Zipf fetch
    bursts off the committed view. The workload is OPEN-LOOP — bursts are
    spaced by exponential think time rather than issued back-to-back, which
    is the correct methodology for a latency claim (a closed loop saturates
    the host and measures throughput, not serving latency). The read-only
    baseline runs the identical request schedule with no events flowing.
    Reports read-only vs under-ingest fetch latency (the §4.4 claim:
    ingestion is latency-free for serving — reads never join a fold; bound:
    under-ingest p95 within 1.2x of read-only p95), folded events/sec,
    drops, and staleness. XLA backend only: the contention being measured
    is host-thread + dispatch, which the interpret-mode Pallas simulator
    would drown in python."""
    from repro.core.engine import EngineConfig, SDIMEngine
    from repro.serve.bse_server import BSEServer

    d = 16
    N = 256                       # users, all device-hot (unbounded store)
    C = 64                        # Zipf fetch burst
    L = 32
    n_bursts = 80 if quick else 240
    lam = 32                      # mean Poisson event arrivals per burst
    gap_s = 0.03                  # mean exponential think time between bursts
    emb_i = jax.random.normal(jax.random.PRNGKey(11), (4000, d // 2))
    emb_c = jax.random.normal(jax.random.PRNGKey(12), (50, d // 2))

    def embed(params, items, cats):
        return jnp.concatenate([emb_i[jnp.asarray(items) % 4000],
                                emb_c[jnp.asarray(cats) % 50]], axis=-1)

    eng = SDIMEngine(EngineConfig(m=24, tau=3, d=d, backend="xla"))
    srv = BSEServer(embed, None, eng, capacity=N, wire_dtype=jnp.float32,
                    async_ingest=True, queue_depth=8192, max_staleness=512,
                    drain_batch=256)
    rt = srv.async_ingest
    # linger: batch many bursts of arrivals per fold, so the serving path
    # contends with a handful of folds per run, not one per burst
    rt.linger_s = 0.5
    rng = np.random.default_rng(0)
    srv.ingest_histories(list(range(N)), rng.integers(0, 4000, (N, L)),
                         rng.integers(0, 50, (N, L)))
    rt.flush()                                      # bootstrap committed
    p = 1.0 / (np.arange(1, N + 1) ** 1.1)          # Zipf(1.1) fetch traffic
    p /= p.sum()
    bursts = [[int(u) for u in rng.choice(N, size=C, p=p)]
              for _ in range(n_bursts)]
    arrivals = rng.poisson(lam, n_bursts)           # events between bursts
    ev_users = [[int(u) for u in rng.choice(N, size=max(int(k), 1), p=p)]
                for k in arrivals]
    gaps = rng.exponential(gap_s, n_bursts)

    jax.block_until_ready(srv.fetch_many(bursts[0]))         # warm fetch jit
    srv.ingest_events(ev_users[0], rng.integers(0, 4000, len(ev_users[0])),
                      rng.integers(0, 50, len(ev_users[0])))
    rt.flush()                                               # warm fold jits

    lat = []                                                 # read-only
    for b, g in zip(bursts, gaps):
        tb = time.perf_counter()
        jax.block_until_ready(srv.fetch_many(b))
        lat.append(time.perf_counter() - tb)
        time.sleep(g)
    read_p50 = 1e3 * float(np.percentile(lat, 50))
    read_p95 = 1e3 * float(np.percentile(lat, 95))

    rt.stats = type(rt.stats)()                              # mixed phase
    rt.start()
    submitted = 0
    lat = []
    t0 = time.perf_counter()
    for b, us, g in zip(bursts, ev_users, gaps):
        submitted += srv.ingest_events(
            us, rng.integers(0, 4000, len(us)), rng.integers(0, 50, len(us)))
        time.sleep(g)
        tb = time.perf_counter()
        jax.block_until_ready(srv.fetch_many(b))
        lat.append(time.perf_counter() - tb)
    wall = time.perf_counter() - t0
    rt.stop(flush=True)
    mixed_p50 = 1e3 * float(np.percentile(lat, 50))
    mixed_p95 = 1e3 * float(np.percentile(lat, 95))
    st = rt.stats
    eps = st.n_events_folded / wall
    ratio = mixed_p95 / max(read_p95, 1e-9)
    if bench is not None:
        bench["ingest"] = {
            "n_users": N, "burst": C, "n_bursts": n_bursts,
            "poisson_lambda": lam,
            "read_only": {"p50_ms": round(read_p50, 3),
                          "p95_ms": round(read_p95, 3)},
            "under_ingest": {"p50_ms": round(mixed_p50, 3),
                             "p95_ms": round(mixed_p95, 3)},
            "p95_ratio": round(ratio, 3),
            "events_per_sec": round(eps, 1),
            "events_submitted": int(submitted),
            "events_folded": int(st.n_events_folded),
            "n_dropped": int(st.n_dropped),
            "n_folds": int(st.n_folds),
            "max_queue_depth": int(st.max_queue_depth),
            "max_drain_batch": int(st.max_drain_batch),
            "staleness_p95": round(st.staleness_p95(), 2),
        }
    return [
        {"name": "table5/ingest/serve_latency",
         "us_per_call": 1e3 * mixed_p95, "shards": 1,
         "derived": f"under_ingest_p95={mixed_p95:.2f}ms"
                    f"_read_only_p95={read_p95:.2f}ms_ratio={ratio:.2f}x"
                    f"_(bound_1.2x)_p50={mixed_p50:.2f}ms"},
        {"name": "table5/ingest/events_per_sec",
         "us_per_call": 1e6 / max(eps, 1e-9), "shards": 1,
         "derived": f"folded={eps:.0f}/s_submitted={submitted}"
                    f"_dropped={st.n_dropped}_folds={st.n_folds}"
                    f"_max_queue={st.max_queue_depth}"
                    f"_staleness_p95={st.staleness_p95():.1f}"},
    ]


def pressure_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """Capacity-pressure: the tiered store under Zipf traffic whose working
    set is 4x the hot capacity (the acceptance bound), vs the unbounded
    single-tier store. The serving path is ``fetch_many`` — the op the CTR
    server drives — so what's measured is gather + batched promote/demote,
    never per-user dispatches (the gather/scatter counters prove it)."""
    import jax.numpy as jnp

    from repro.core.engine import EngineConfig, SDIMEngine
    from repro.serve.bse_server import BSEServer
    from repro.serve.tiered_store import TierStats

    d = 16
    emb_i = jax.random.normal(jax.random.PRNGKey(11), (4000, d // 2))
    emb_c = jax.random.normal(jax.random.PRNGKey(12), (50, d // 2))

    def embed(params, items, cats):
        return jnp.concatenate([emb_i[jnp.asarray(items) % 4000],
                                emb_c[jnp.asarray(cats) % 50]], axis=-1)

    rows = []
    for backend in ("xla", "pallas"):
        # interpret-mode Pallas on CPU simulates the kernels in python —
        # keep its ingest volume bounded in quick mode
        H = 32 if backend == "xla" or not quick else 16     # hot capacity
        W = 4 * H                                           # working set
        L = 64 if backend == "xla" else 32
        n_bursts = 16
        eng = SDIMEngine(EngineConfig(
            m=24, tau=3, d=d, backend=backend,
            interpret=None if backend == "xla"
            else jax.default_backend() != "tpu"))
        tmp = tempfile.mkdtemp(prefix="bse-cold-")
        try:
            tiered = BSEServer(embed, None, eng, hot_capacity=H,
                               warm_capacity=2 * H, store_dir=tmp,
                               policy="clock")
            flat = BSEServer(embed, None, eng, capacity=W)
            rng = np.random.default_rng(0)
            hist_i = rng.integers(0, 4000, (W, L))
            hist_c = rng.integers(0, 50, (W, L))
            for lo in range(0, W, H):                       # batched bootstrap
                us = list(range(lo, lo + H))
                for s in (tiered, flat):
                    s.ingest_histories(us, hist_i[lo:lo + H],
                                       hist_c[lo:lo + H])
            # Zipf(1.1) over the working set: a hot head the size of the
            # hot tier, a long tail that lives warm/cold
            p = 1.0 / (np.arange(1, W + 1) ** 1.1)
            p /= p.sum()
            bursts = [[int(u) for u in rng.choice(W, size=H, p=p)]
                      for _ in range(n_bursts)]
            for s in (tiered, flat):                        # warm the jits
                s.fetch_many(bursts[0])
            tiered.store.stats = TierStats()                # serving-only
            t0 = time.perf_counter()
            for b in bursts:
                jax.block_until_ready(tiered.fetch_many(b))
            tiered_ups = n_bursts * H / (time.perf_counter() - t0)
            t0 = time.perf_counter()
            for b in bursts:
                jax.block_until_ready(flat.fetch_many(b))
            flat_ups = n_bursts * H / (time.perf_counter() - t0)
            ts = tiered.store.stats
            tiers = tiered.store.tier_sizes()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        if bench is not None:
            bench["hit_rate"][backend] = round(float(ts.hit_rate), 4)
        tag = f"pressure[{backend}]"
        rows.append({
            "name": f"table5/{tag}/users_per_sec",
            "us_per_call": 1e6 / tiered_ups, "shards": 1,
            "derived": f"tiered={tiered_ups:.0f}/s_unbounded={flat_ups:.0f}/s"
                       f"_hot={H}_working_set={W}_zipf1.1"})
        rows.append({
            "name": f"table5/{tag}/hit_rate",
            "us_per_call": 0.0, "shards": 1,
            "derived": f"hit_rate={ts.hit_rate:.2f}"
                       f"_promote={ts.warm_promotions + ts.cold_promotions}"
                       f"(cold={ts.cold_promotions})_demote={ts.demotions}"
                       f"_tiers={tiers}".replace(" ", "")})
        rows.append({
            "name": f"table5/{tag}/bytes_moved",
            "us_per_call": 0.0, "shards": 1,
            "derived": f"promote={ts.promote_bytes}B_demote={ts.demote_bytes}B"
                       f"_spill={ts.spill_bytes}B_hot_gathers="
                       f"{ts.n_hot_gathers}_hot_scatters={ts.n_hot_scatters}"
                       f"_bursts={n_bursts}"})
    return rows


def slo_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """Tail latency under overload through the FULL production request path:
    ``CTRServer.handle_requests`` with admission control (token-bucket rate
    limit + concurrency bound), a tiered store whose working set spills past
    the hot tier, and the cold-tier circuit breaker armed. The workload is
    OPEN-LOOP — Zipf(1.1) user popularity, Poisson request arrivals per
    burst, exponential think gaps — and deliberately offers more traffic
    than the token bucket admits, so the shed path (explicit ``None``
    scores, every one counted) is exercised at its real rate rather than
    never. Reports per-burst p50/p95/p99 over admitted bursts plus the shed
    and degrade rates into ``bench['slo']`` (schema 2 — ``tools/bench_check``
    fails ``make ci`` when the section is missing or its percentiles are
    unordered). Conservation is asserted inline: offered == served + shed,
    same invariant the fault harness (tests/test_runtime_faults.py) pins
    under injected faults."""
    from repro.serve.tiered_store import TierStats

    dcfg = SyntheticCTRConfig(hist_len=32, n_items=200, n_cats=20)
    cfg = CTRConfig(arch="din", n_items=200, n_cats=20, long_len=32,
                    short_len=8, mlp_hidden=(16,),
                    interest=InterestConfig(kind="sdim", m=8, tau=2,
                                            backend="xla"))
    model = CTRModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    N = 96                        # working set (users)
    H = 32                        # device-hot capacity (rest spills cold)
    C = 8                         # requests per arriving burst (Poisson mean)
    CAND = 16                     # candidates per request
    n_bursts = 60 if quick else 200
    rate_limit = 200.0            # admitted requests/sec
    rate_burst = 16.0
    gap_s = 0.02                  # mean think gap -> ~C/gap offered rps
    tmp = tempfile.mkdtemp(prefix="bse-slo-")
    try:
        server = CTRServer.build(
            model, params, "decoupled", wire_dtype=jnp.float32,
            hot_capacity=H, warm_capacity=0, store_dir=tmp,
            cold_deadline_s=0.05, rate_limit=rate_limit,
            rate_burst=rate_burst, max_concurrency=4)
        rng = np.random.default_rng(0)
        raw = generate_batch(dcfg, 1, 0)
        ub = {k: jnp.asarray(v) for k, v in raw.items()
              if k.startswith("hist")}
        hist_i = rng.integers(0, 200, (N, 32))
        hist_c = rng.integers(0, 20, (N, 32))
        for lo in range(0, N, H):                       # bootstrap all tiers
            server.bse.ingest_histories(list(range(lo, lo + H)),
                                        hist_i[lo:lo + H], hist_c[lo:lo + H])
        p = 1.0 / (np.arange(1, N + 1) ** 1.1)          # Zipf(1.1) popularity
        p /= p.sum()
        sizes = np.maximum(rng.poisson(C, n_bursts), 1)  # Poisson arrivals
        gaps = rng.exponential(gap_s, n_bursts)

        def burst(k):
            us = rng.choice(N, size=k, p=p)
            return [(int(u), ub,
                     jnp.asarray(rng.integers(0, 200, CAND).astype(np.int32)),
                     jnp.asarray(rng.integers(0, 20, CAND).astype(np.int32)),
                     jnp.zeros((CAND, 4))) for u in us]

        # warm one burst per Poisson size (each request-count pads/compiles
        # its own scorer shape) with admission off, so no warm burst sheds
        # and the timed loop measures serving, not compilation
        adm, server.admission = server.admission, None
        for k in sorted({int(s) for s in sizes}):
            server.handle_requests(burst(k))
        server.admission = adm
        server.stats = type(server.stats)()
        server.bse.store.stats = TierStats()
        lat, offered, shed = [], 0, 0
        t0 = time.perf_counter()
        for k, g in zip(sizes, gaps):
            reqs = burst(int(k))
            tb = time.perf_counter()
            scores = server.handle_requests(reqs)
            live = [s for s in scores if s is not None]
            if live:
                jax.block_until_ready(live)
            dt = time.perf_counter() - tb
            offered += len(reqs)
            shed += len(reqs) - len(live)
            if live:                    # fully-shed bursts cost ~0: excluded
                lat.append(dt)
            time.sleep(g)
        wall = time.perf_counter() - t0
        st = server.stats
        assert offered == st.n_requests + st.n_shed, \
            f"conservation: offered={offered} served={st.n_requests} " \
            f"shed={st.n_shed}"
        n_degraded = server.bse.store.stats.n_degraded
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    p50, p95, p99 = (1e3 * float(np.percentile(lat, q))
                     for q in (50, 95, 99))
    offered_rps = offered / max(wall, 1e-9)
    shed_rate = shed / max(offered, 1)
    degrade_rate = n_degraded / max(st.n_requests, 1)
    if bench is not None:
        bench["slo"] = {
            "n_requests": int(offered),
            "n_served": int(st.n_requests),
            "n_shed": int(shed),
            "n_degraded": int(n_degraded),
            "n_bursts": int(n_bursts),
            "offered_rps": round(offered_rps, 1),
            "admitted_rps_limit": rate_limit,
            "p50_ms": round(p50, 3),
            "p95_ms": round(p95, 3),
            "p99_ms": round(p99, 3),
            "shed_rate": round(shed_rate, 4),
            "degrade_rate": round(degrade_rate, 4),
            "hot_capacity": H, "working_set": N,
        }
    return [
        {"name": "table5/slo/tail_latency",
         "us_per_call": 1e3 * p99, "shards": 1,
         "derived": f"p50/p95/p99={p50:.2f}/{p95:.2f}/{p99:.2f}ms"
                    f"_over_{len(lat)}_admitted_bursts"},
        {"name": "table5/slo/overload",
         "us_per_call": 0.0, "shards": 1,
         "derived": f"offered={offered_rps:.0f}rps_limit={rate_limit:.0f}rps"
                    f"_shed={shed_rate:.1%}_degraded={degrade_rate:.1%}"
                    f"_conserved={offered}=={st.n_requests}+{shed}"},
    ]


def trace_rows(quick: bool = True, bench: dict = None) -> list[dict]:
    """Span coverage of the traced request path (schema 3): replay a small
    admission-controlled tiered-store workload with the request tracer
    (``serve/tracing.py``) enabled, then report what fraction of retained
    root-span wall time is accounted for by instrumented child stages
    (admission / assemble / fetch / score / tier movement) plus the number
    of explicit jit-compile spans detected via the scorer's cache size.
    Runs on its OWN small server so the slo section above stays untraced —
    its p50/p95/p99 remain directly comparable across PRs; the
    disabled-tracer overhead bound is pinned by tests/test_tracing.py.
    Writes ``bench['trace']`` (required at schema 3 by
    ``tools/bench_check.py``)."""
    from repro.serve.tracing import Tracer

    dcfg = SyntheticCTRConfig(hist_len=32, n_items=200, n_cats=20)
    cfg = CTRConfig(arch="din", n_items=200, n_cats=20, long_len=32,
                    short_len=8, mlp_hidden=(16,),
                    interest=InterestConfig(kind="sdim", m=8, tau=2,
                                            backend="xla"))
    model = CTRModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    N = 48                        # working set spills past the hot tier
    H = 16
    CAND = 16
    n_bursts = 20 if quick else 80
    tracer = Tracer(slow_ms=None)   # reservoir keeps this whole small run
    tmp = tempfile.mkdtemp(prefix="bse-trace-")
    try:
        server = CTRServer.build(
            model, params, "decoupled", wire_dtype=jnp.float32,
            hot_capacity=H, warm_capacity=0, store_dir=tmp,
            cold_deadline_s=0.05, rate_limit=400.0, rate_burst=8.0,
            max_concurrency=4, tracer=tracer)
        rng = np.random.default_rng(0)
        raw = generate_batch(dcfg, 1, 0)
        ub = {k: jnp.asarray(v) for k, v in raw.items()
              if k.startswith("hist")}
        hist_i = rng.integers(0, 200, (N, 32))
        hist_c = rng.integers(0, 20, (N, 32))
        for lo in range(0, N, H):
            server.bse.ingest_histories(list(range(lo, lo + H)),
                                        hist_i[lo:lo + H],
                                        hist_c[lo:lo + H])
        p = 1.0 / (np.arange(1, N + 1) ** 1.1)
        p /= p.sum()
        for _ in range(n_bursts):
            us = rng.choice(N, size=4, p=p)
            reqs = [(int(u), ub,
                     jnp.asarray(rng.integers(0, 200, CAND)
                                 .astype(np.int32)),
                     jnp.asarray(rng.integers(0, 20, CAND)
                                 .astype(np.int32)),
                     jnp.zeros((CAND, 4))) for u in us]
            server.handle_requests(reqs)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    s = tracer.summary()
    if bench is not None:
        bench["trace"] = {
            "span_coverage": round(s["span_coverage"], 4),
            "n_compile_spans": int(s["n_compile_spans"]),
            "n_traces": int(s["n_traces"]),
            "n_spans": int(s["n_spans"]),
            "n_retained_tail": int(s["n_retained_tail"]),
            "n_retained_sampled": int(s["n_retained_sampled"]),
            "n_dropped": int(s["n_dropped"]),
            "n_bursts": int(n_bursts),
        }
    return [
        {"name": "table5/trace/span_coverage", "us_per_call": 0.0,
         "shards": 1,
         "derived": f"coverage={s['span_coverage']:.1%}"
                    f"_over_{s['n_finished']}_traces"
                    f"_{s['n_spans']}_spans"
                    f"_compile_spans={s['n_compile_spans']}"},
    ]
