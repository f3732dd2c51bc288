"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

recsys archs -> BSE + CTR server loop over synthetic requests (the paper's
deployment); LM archs -> decode loop (exact KV or --sdim-kv compressed).

``--shards N`` (or an explicit ``--mesh DxM``) shards the BSE table store
over a device mesh's model axis. On a CPU host, fake the devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.serve --arch sdim-paper --shards 8

``--hot-capacity K --store-dir D --policy clock`` swaps in the tiered store
(serve/tiered_store.py): at most K users stay device-resident, the rest
demote to a host pool and spill to ``.npz`` segments under D.

``--async-ingest`` (with ``--queue-depth``/``--max-staleness``) runs BSE
ingestion on a writer thread off the request path (serve/ingest.py):
reads serve the last committed table version and never block on a fold.

``--rate-limit R`` (requests/sec, headroom ``--rate-burst``) and
``--max-concurrency K`` arm admission control (serve/admission.py):
overloaded bursts SHED — every refused request prints an explicit shed
line and is counted, never silently dropped. ``--cold-deadline-ms D``
arms the tiered store's cold-tier circuit breaker (serve/tiered_store.py):
cold reads slower than D open the circuit and later cold reads degrade to
counted misses instead of stalling the request path. The run ends with a
liveness/readiness snapshot (serve/health.py) and a metrics summary
(serve/metrics.py) — the same surfaces a production sidecar would scrape.

``--trace`` threads a span tracer (serve/tracing.py) through the whole
request path — admission, assembly, BSE fetch, tier movement, scoring
dispatch (jit compiles shown explicitly) and the async-ingest fold — and
ends the run with the slowest-5 trace breakdown. ``--trace-dir D`` also
writes Perfetto-loadable Chrome trace-event JSON to ``D/trace.json``;
``--trace-slow-ms T`` always retains traces slower than T ms (shed/
degraded/force-drained requests are always retained regardless).

``--profile`` wraps the SDIM engine's dispatch sites in a kernel profiler
(serve/profiler.py): per-dispatch block-until-ready device time (jit
warmup excluded) plus compile-time ``cost_analysis()`` flops/bytes,
compared against the analytical roofline (distributed/roofline.py), and a
device-memory ledger over the table-store tiers. The run ends with the
measured-roofline table and the ledger balance; ``--profile-dir D`` also
writes ``D/profile.json`` (render with ``tools/profile_report.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.engine import BACKENDS


def build_mesh(shards: int, mesh_spec: str = None, err=None):
    """``--mesh "2x4"`` ((data, model) axes) or ``--shards N`` ((model,)
    only) -> a ``MeshCtx`` over host-local devices; ``None`` when serving
    unsharded. The table store shards over the model axis.

    Flag validation goes through ``err`` (``parser.error`` when called from
    ``main``) — not ``assert`` — so bad flags fail with a usable message
    even under ``python -O``."""

    def fail(msg: str):
        if err is not None:
            err(msg)                       # parser.error raises SystemExit
        raise SystemExit(f"error: {msg}")

    if mesh_spec:
        try:
            dims = tuple(int(x) for x in mesh_spec.lower().split("x"))
        except ValueError:
            dims = ()
        if len(dims) != 2 or min(dims) < 1:
            fail(f'--mesh wants "DxM" (two positive ints, e.g. "2x4"), '
                 f"got {mesh_spec!r}")
        shape, axes = dims, ("data", "model")
    else:
        if shards < 1:
            fail(f"--shards must be a positive device count, got {shards}")
        if shards == 1:
            return None
        shape, axes = (shards,), ("model",)
    if math.prod(shape) > len(jax.devices()):
        fail(f"mesh {shape} needs {math.prod(shape)} devices, have "
             f"{len(jax.devices())}; on CPU set "
             f"XLA_FLAGS=--xla_force_host_platform_device_count="
             f"{math.prod(shape)}")
    from repro.distributed.compat import make_auto_mesh
    from repro.distributed.mesh_ctx import MeshCtx

    return MeshCtx(make_auto_mesh(shape, axes))


def build_ctr_server(cfg, *, backend: str = "auto", params=None,
                     **build_kw):
    """The serving deployment of one ``CTRConfig``: the model with its SDIM
    engine on ``backend``, its params (``params``, else a fresh init from
    key 0) and ``CTRServer.build`` — decoupled BSE + CTR servers for an
    SDIM interest, inline scoring otherwise. ``build_kw`` goes to
    ``CTRServer.build``. Returns ``(model, params, server)``, ``params``
    being the server's own (embedding tables lane-packed, see
    ``Embedding.pack``), so a caller holds one copy of each table."""
    from repro.models.ctr import CTRModel
    from repro.serve.ctr_server import CTRServer

    if cfg.interest.kind == "sdim":
        cfg = dataclasses.replace(
            cfg, interest=dataclasses.replace(cfg.interest, backend=backend))
    model = CTRModel(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    mode = "decoupled" if cfg.interest.kind == "sdim" else "inline"
    server = CTRServer.build(model, params, mode, **build_kw)
    return model, server.params, server


def synthetic_requests(cfg, n_requests: int, n_candidates: int) -> list:
    """``handle_requests`` tuples for ``cfg``: request r comes from user
    ``u{r}``, whose history is ``generate_batch``'s for that user, with
    ``n_candidates`` candidates drawn from seed 0."""
    from repro.data.synthetic import SyntheticCTRConfig, generate_batch

    dcfg = SyntheticCTRConfig(hist_len=cfg.long_len, n_items=cfg.n_items,
                              n_cats=cfg.n_cats)
    rng = np.random.default_rng(0)
    out = []
    for r in range(n_requests):
        raw = generate_batch(dcfg, 1, r)
        user = {k: jnp.asarray(v) for k, v in raw.items()
                if k.startswith("hist")}
        ci = rng.integers(0, cfg.n_items, n_candidates).astype(np.int32)
        cc = rng.integers(0, cfg.n_cats, n_candidates).astype(np.int32)
        out.append((f"u{r}", user, jnp.asarray(ci), jnp.asarray(cc),
                    jnp.zeros((n_candidates, cfg.ctx_dim))))
    return out


def serve_requests(server, requests: list, micro_batch: int = 1) -> list:
    """Serve ``requests`` in bursts of ``micro_batch`` (one fetch or fused
    dispatch plus one scoring dispatch per burst). Returns one (C,) score
    array per request, ``None`` where admission shed it."""
    out = []
    for lo in range(0, len(requests), micro_batch):
        out += server.handle_requests(requests[lo:lo + micro_batch])
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--candidates", type=int, default=128)
    p.add_argument("--backend", default="auto", choices=BACKENDS,
                   help="SDIM compute backend (auto: Pallas on TPU, XLA elsewhere)")
    p.add_argument("--micro-batch", type=int, default=1,
                   help="serve requests in bursts of this size: one "
                        "fetch_many + one scoring dispatch per burst")
    p.add_argument("--shards", type=int, default=1,
                   help="shard the BSE table store over this many devices "
                        "(model-axis mesh; see module docstring for the "
                        "host-local XLA_FLAGS recipe)")
    p.add_argument("--mesh", default=None,
                   help='explicit mesh shape "DxM" (data x model); '
                        "overrides --shards")
    p.add_argument("--hot-capacity", type=int, default=None,
                   help="tier the BSE store: at most this many users stay "
                        "device-resident; the rest demote to a host pool "
                        "(and to --store-dir segments)")
    p.add_argument("--store-dir", default=None,
                   help="cold-tier directory for spilled .npz segments "
                        "(enables the disk tier)")
    p.add_argument("--policy", default=None, choices=("clock", "lru"),
                   help="hot-tier eviction policy (default clock)")
    p.add_argument("--warm-capacity", type=int, default=None,
                   help="bound the host warm pool; overflow spills to "
                        "--store-dir")
    p.add_argument("--table-dtype", default="fp32",
                   help="BSE table STORAGE dtype: fp32 | bf16 | int8 | fp8 "
                        "(int8/fp8 quantize on write with per-row scales; "
                        "fp8 only where jax exposes float8_e4m3fn)")
    p.add_argument("--fused-serve", action="store_true",
                   help="serve micro-batches through the fused megakernel "
                        "(one gather+dequant+query dispatch instead of "
                        "fetch_many + model-side query)")
    p.add_argument("--async-ingest", action="store_true",
                   help="run BSE ingestion off the request path: submits "
                        "enqueue onto a bounded queue drained by a writer "
                        "thread; reads serve the last committed version "
                        "(serve/ingest.py)")
    p.add_argument("--queue-depth", type=int, default=1024,
                   help="async ingest queue bound; submits past it are "
                        "dropped and counted, never blocked on")
    p.add_argument("--max-staleness", type=int, default=64,
                   help="max un-folded entries per user before a submit "
                        "folds inline (bounds how stale a served table "
                        "can be)")
    p.add_argument("--rate-limit", type=float, default=None,
                   help="token-bucket admission: sustained requests/sec; "
                        "over-budget requests shed with an explicit None "
                        "score (counted, never silent)")
    p.add_argument("--rate-burst", type=float, default=None,
                   help="token-bucket burst headroom (defaults to "
                        "--rate-limit); needs --rate-limit")
    p.add_argument("--max-concurrency", type=int, default=None,
                   help="bound concurrent serving bursts; a burst arriving "
                        "at the bound sheds whole (explicit None scores)")
    p.add_argument("--cold-deadline-ms", type=float, default=None,
                   help="cold-tier circuit breaker deadline: cold reads "
                        "slower than this open the circuit and later cold "
                        "reads degrade to counted misses instead of "
                        "stalling (needs the tiered store)")
    p.add_argument("--trace", action="store_true",
                   help="per-request span tracing (serve/tracing.py): "
                        "prints the slowest-5 trace breakdown at end of "
                        "run")
    p.add_argument("--trace-dir", default=None,
                   help="write Chrome trace-event JSON (Perfetto-loadable) "
                        "to this directory as trace.json (implies --trace)")
    p.add_argument("--trace-slow-ms", type=float, default=None,
                   help="always retain traces with root latency >= this "
                        "(ms); flagged traces (shed/degraded/forced-drain) "
                        "are always retained regardless (implies --trace)")
    p.add_argument("--profile", action="store_true",
                   help="measured kernel profiling (serve/profiler.py): "
                        "per-dispatch device time + compile-time "
                        "cost_analysis flops/bytes against the analytical "
                        "roofline, plus the device-memory ledger; prints "
                        "the measured-roofline table at end of run")
    p.add_argument("--profile-dir", default=None,
                   help="write the profile block as profile.json to this "
                        "directory (tools/profile_report.py renders it; "
                        "implies --profile)")
    p.add_argument("--tokens", type=int, default=32, help="LM decode steps")
    p.add_argument("--sdim-kv", action="store_true",
                   help="LM: SDIM bucket-compressed KV decode")
    args = p.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.quant import TABLE_DTYPES, resolve_table_dtype
    from repro.serve.tiered_store import is_tiered

    if args.table_dtype not in TABLE_DTYPES:
        p.error(f"--table-dtype {args.table_dtype!r} not available; have "
                f"{sorted(TABLE_DTYPES)}"
                + ("" if "fp8" in TABLE_DTYPES or args.table_dtype != "fp8"
                   else " (this jax has no float8_e4m3fn)"))
    table_dtype = resolve_table_dtype(args.table_dtype)

    enable_compile_cache()
    mod = registry.get(args.arch)
    cfg = mod.SMOKE
    tiered = is_tiered(args.hot_capacity, args.store_dir, args.policy,
                       args.warm_capacity)
    if mod.FAMILY != "recsys" and (args.mesh or args.shards > 1):
        p.error(f"--shards/--mesh shard the BSE table store (recsys serving "
                f"only); arch {args.arch!r} is family {mod.FAMILY!r}")
    if mod.FAMILY != "recsys" and tiered:
        p.error(f"--hot-capacity/--store-dir/--policy tier the BSE table "
                f"store (recsys serving only); arch {args.arch!r} is family "
                f"{mod.FAMILY!r}")
    if mod.FAMILY != "recsys" and (args.table_dtype != "fp32"
                                   or args.fused_serve):
        p.error(f"--table-dtype/--fused-serve configure the BSE table store "
                f"(recsys serving only); arch {args.arch!r} is family "
                f"{mod.FAMILY!r}")
    if args.fused_serve and args.micro_batch < 2:
        p.error("--fused-serve rides the micro-batched path; give "
                "--micro-batch >= 2")
    if mod.FAMILY != "recsys" and args.async_ingest:
        p.error(f"--async-ingest decouples the BSE write path (recsys "
                f"serving only); arch {args.arch!r} is family "
                f"{mod.FAMILY!r}")
    if args.queue_depth < 1:
        p.error(f"--queue-depth must be >= 1, got {args.queue_depth}")
    if args.max_staleness < 1:
        p.error(f"--max-staleness must be >= 1, got {args.max_staleness}")
    if args.rate_burst is not None and args.rate_limit is None:
        p.error("--rate-burst is token-bucket headroom over --rate-limit; "
                "give --rate-limit too")
    if args.rate_limit is not None and args.rate_limit <= 0:
        p.error(f"--rate-limit must be > 0 requests/sec, got "
                f"{args.rate_limit}")
    if args.rate_burst is not None and args.rate_burst <= 0:
        p.error(f"--rate-burst must be > 0 tokens, got {args.rate_burst}")
    if args.max_concurrency is not None and args.max_concurrency < 1:
        p.error(f"--max-concurrency must be >= 1, got "
                f"{args.max_concurrency}")
    if args.cold_deadline_ms is not None and args.cold_deadline_ms <= 0:
        p.error(f"--cold-deadline-ms must be > 0, got "
                f"{args.cold_deadline_ms}")
    if args.cold_deadline_ms is not None and not tiered:
        p.error("--cold-deadline-ms arms the cold-tier circuit breaker, "
                "which needs the tiered store (give --hot-capacity/"
                "--store-dir/--policy/--warm-capacity)")
    hardened = (args.rate_limit is not None
                or args.max_concurrency is not None
                or args.cold_deadline_ms is not None)
    if mod.FAMILY != "recsys" and hardened:
        p.error(f"--rate-limit/--max-concurrency/--cold-deadline-ms harden "
                f"the CTR request path (recsys serving only); arch "
                f"{args.arch!r} is family {mod.FAMILY!r}")
    if args.trace_slow_ms is not None and args.trace_slow_ms < 0:
        p.error(f"--trace-slow-ms must be >= 0, got {args.trace_slow_ms}")
    tracing = (args.trace or args.trace_dir is not None
               or args.trace_slow_ms is not None)
    if mod.FAMILY != "recsys" and tracing:
        p.error(f"--trace/--trace-dir/--trace-slow-ms trace the CTR request "
                f"path (recsys serving only); arch {args.arch!r} is family "
                f"{mod.FAMILY!r}")
    profiling = args.profile or args.profile_dir is not None
    if mod.FAMILY != "recsys" and profiling:
        p.error(f"--profile/--profile-dir profile the SDIM serving kernels "
                f"(recsys serving only); arch {args.arch!r} is family "
                f"{mod.FAMILY!r}")
    # NOTE: --micro-batch may exceed --hot-capacity: BSEServer auto-chunks
    # oversized bursts into hot-capacity-sized sub-bursts (extra dispatches,
    # same scores), so no launcher-level rejection is needed
    if tiered and args.hot_capacity is not None and args.hot_capacity < 1:
        p.error(f"--hot-capacity must be >= 1, got {args.hot_capacity}")
    if mod.FAMILY == "recsys":
        mode = "decoupled" if cfg.interest.kind == "sdim" else "inline"
        if mode != "decoupled" and (args.mesh or args.shards > 1):
            p.error(f"--shards/--mesh shard the BSE table store, which only "
                    f"the decoupled (sdim) deployment has; arch "
                    f"{args.arch!r} serves {mode!r}")
        if mode != "decoupled" and tiered:
            p.error(f"--hot-capacity/--store-dir/--policy tier the BSE table "
                    f"store, which only the decoupled (sdim) deployment has; "
                    f"arch {args.arch!r} serves {mode!r}")
        if mode != "decoupled" and (args.table_dtype != "fp32"
                                    or args.fused_serve):
            p.error(f"--table-dtype/--fused-serve configure the BSE table "
                    f"store, which only the decoupled (sdim) deployment has; "
                    f"arch {args.arch!r} serves {mode!r}")
        if mode != "decoupled" and args.async_ingest:
            p.error(f"--async-ingest decouples the BSE write path, which "
                    f"only the decoupled (sdim) deployment has; arch "
                    f"{args.arch!r} serves {mode!r}")
        if mode != "decoupled" and profiling:
            p.error(f"--profile/--profile-dir wrap the SDIM engine dispatch "
                    f"sites, which only the decoupled (sdim) deployment "
                    f"has; arch {args.arch!r} serves {mode!r}")
        mesh_ctx = (build_mesh(args.shards, args.mesh, err=p.error)
                    if mode == "decoupled" else None)
        tracer = None
        if tracing:
            from repro.serve.tracing import Tracer
            tracer = Tracer(slow_ms=args.trace_slow_ms)
        model, params, server = build_ctr_server(
            cfg, backend=args.backend, mesh=mesh_ctx,
            hot_capacity=args.hot_capacity, store_dir=args.store_dir,
            policy=args.policy, warm_capacity=args.warm_capacity,
            table_dtype=table_dtype, fused=args.fused_serve,
            async_ingest=args.async_ingest, queue_depth=args.queue_depth,
            max_staleness=args.max_staleness,
            max_concurrency=args.max_concurrency,
            rate_limit=args.rate_limit, rate_burst=args.rate_burst,
            cold_deadline_s=(None if args.cold_deadline_ms is None
                             else args.cold_deadline_ms / 1e3),
            tracer=tracer)
        bse = server.bse
        profiler = ledger = None
        if profiling:
            from repro.serve.profiler import KernelProfiler, MemoryLedger
            profiler = KernelProfiler(metrics=server.metrics, tracer=tracer)
            profiler.attach(bse.engine)
            ledger = MemoryLedger(metrics=server.metrics)
            ledger.attach(bse.store)
        if args.async_ingest:
            bse.async_ingest.start()
        if cfg.interest.kind == "sdim":
            print(f"SDIM engine backend: {model.engine.backend}"
                  f"{' (interpret)' if model.engine.backend == 'pallas' and model.engine.interpret else ''}")
        if mesh_ctx is not None:
            print(f"BSE table store sharded over "
                  f"{bse.store.n_shards} devices "
                  f"(mesh {dict(mesh_ctx.mesh.shape)})")
        requests = synthetic_requests(cfg, args.requests, args.candidates)
        if cfg.arch == "wide_deep":
            # inline arch with sparse fields: scored straight off the model
            rng = np.random.default_rng(1)
            apply = jax.jit(model.apply)
            L = cfg.long_len
            results = [apply(params, {
                "hist_items": jnp.broadcast_to(u["hist_items"], (len(ci), L)),
                "hist_cats": jnp.broadcast_to(u["hist_cats"], (len(ci), L)),
                "hist_mask": jnp.broadcast_to(u["hist_mask"], (len(ci), L)),
                "cand_item": ci, "cand_cat": cc, "ctx": ctx,
                "sparse_ids": jnp.asarray(rng.integers(
                    0, cfg.field_vocab, (len(ci), cfg.n_sparse)).astype(np.int32))})
                for _, u, ci, cc, ctx in requests]
        else:
            results = serve_requests(server, requests, args.micro_batch)
        for r, scores in enumerate(results):
            if scores is None:          # shed by admission control — counted
                print(f"req {r}: SHED (admission control)")
            else:
                print(f"req {r}: top candidate {int(jnp.argmax(scores))} "
                      f"(score {float(jnp.max(scores)):+.3f})")
        if bse and bse.async_ingest is not None:
            bse.async_ingest.stop(flush=True)   # quiesce before reporting
            ist = bse.async_ingest.stats
            print(f"async ingest: {ist.n_enqueued} enqueued, "
                  f"{ist.n_events_folded + ist.n_histories_folded} folded "
                  f"in {ist.n_folds} drains "
                  f"(max batch {ist.max_drain_batch}, "
                  f"max queue {ist.max_queue_depth}), "
                  f"{ist.n_dropped} dropped, "
                  f"staleness p95 {ist.staleness_p95():.1f}")
        if bse:
            print(f"{server.stats.ms_per_request:.1f} ms/request"
                  f"{' (fused serve)' if args.fused_serve else ''}; "
                  f"table {bse.table_bytes()} B "
                  f"({args.table_dtype} storage)")
            if tiered:
                ts = bse.store.stats
                print(f"tiered store {bse.store.tier_sizes()} "
                      f"(hot cap {bse.store.hot_capacity}, "
                      f"policy {bse.store.policy.name}): "
                      f"hit-rate {ts.hit_rate:.2f}, "
                      f"promote {ts.promote_bytes} B, "
                      f"demote {ts.demote_bytes} B"
                      + (f", degraded {ts.n_degraded}"
                         if ts.n_degraded else ""))
        if server.admission is not None:
            ast = server.admission.stats
            print(f"admission: {ast.n_admitted} admitted, "
                  f"{ast.n_shed} shed of {ast.n_offered} offered "
                  f"(rate {args.rate_limit or 'off'}/s, "
                  f"concurrency {args.max_concurrency or 'unbounded'})")
        from repro.serve.health import health_snapshot
        h = health_snapshot(server)
        print(f"health: live={h['live']} ready={h['ready']} ["
              + " ".join(f"{name}:{'ok' if c['ok'] else 'FAIL'}"
                         for name, c in sorted(h["checks"].items())) + "]")
        if server.metrics is not None:
            snap = server.metrics.snapshot()
            req = snap["histograms"].get("ctr.request_ms")
            if req and req["count"]:
                print(f"metrics: ctr.request_ms p50/p95/p99 "
                      f"{req['p50']:.2f}/{req['p95']:.2f}/{req['p99']:.2f} "
                      f"ms (n={req['count']})")
            if snap["counters"]:
                print("counters: " + ", ".join(
                    f"{k}={v}" for k, v in sorted(snap["counters"].items())))
        if tracer is not None:
            print(tracer.report(5))
            if args.trace_dir is not None:
                import os
                os.makedirs(args.trace_dir, exist_ok=True)
                out = tracer.save_chrome_trace(
                    os.path.join(args.trace_dir, "trace.json"))
                print(f"chrome trace written to {out} "
                      f"(load in Perfetto / chrome://tracing)")
        if profiler is not None:
            print(profiler.roofline_report())
            print(ledger.report())
            errs = ledger.verify()
            if errs:   # surfaced, not raised: a broken ledger must not
                print("memory ledger MISMATCH: "   # mask the serve output
                      + "; ".join(errs))
            if args.profile_dir is not None:
                import json
                import os
                os.makedirs(args.profile_dir, exist_ok=True)
                out = os.path.join(args.profile_dir, "profile.json")
                with open(out, "w") as f:
                    json.dump({"per_kernel": profiler.to_dict(),
                               "mem": ledger.snapshot()}, f, indent=2)
                print(f"profile written to {out} "
                      f"(render with tools/profile_report.py)")
    elif mod.FAMILY == "lm":
        from repro.models.lm import LMModel

        model = LMModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tok = jnp.zeros((1, 1), jnp.int32)
        if args.sdim_kv:
            cache = model.init_sdim_cache(1)
            step = jax.jit(model.sdim_decode_step)
            for i in range(args.tokens):
                logits, cache = step(params, tok, cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
        else:
            cache = model.init_cache(1, args.tokens + 1, jnp.float32)
            step = jax.jit(model.decode_step)
            for i in range(args.tokens):
                logits, cache = step(params, tok, cache, i)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
        print(f"decoded {args.tokens} tokens "
              f"({'SDIM-compressed' if args.sdim_kv else 'exact'} KV); "
              f"last token id {int(tok[0, 0])}")
    else:
        raise SystemExit("gatedgcn has no serving mode (node classification)")


if __name__ == "__main__":
    main()
