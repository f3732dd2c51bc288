"""One run of one cell: set-up, the measured window and the check.

Set-up builds the deployment through the program's own entry points
(``launch.serve.build_ctr_server`` -> ``CTRServer.build``), ingests
every user's history through ``bse.ingest_histories`` in fixed chunks,
and warms every burst size from 1 to ``max_burst`` through
``handle_requests``.

On one chip the store is built at the population's capacity, so it
never grows. A cell of several chips passes ``mesh=``, a mesh over its
first ``chips`` devices (``launch.serve.build_mesh``), so the BSE tables
live row-sharded in the program's ``ShardedTableStore``. That store
makes its zeros on one device and slices them there before it shards
them, so it is built at a sixteenth of the population, and ingest grows
it, each shard in place (``ShardedTableStore.grow``), by four doublings
to the whole population; nothing grows in the window.

The window then drives ``handle_requests`` open-loop
from one thread: whenever the server is free it is handed every request
that is due, up to ``max_burst``, as one burst. A request's latency runs
from its due time until its scores are on the host.
"""
from __future__ import annotations

import contextlib
import gc
import os
import sys
import time
import traceback
import types

import numpy as np

from chipbench import checks, histories, spec, traffic, weights

CHUNK = 1024          # users per ingest_histories call
SAMPLE = 64           # requests compared with the reference
GRACE_S = 60.0        # how long past the window a due request may finish
SHARDED_START = 16    # a sharded store starts at population / SHARDED_START
CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def prepare_process() -> None:
    """Before JAX is imported: the program on the path, and JAX's
    persistent compilation cache at one fixed directory in the checkout
    (the program's ``enable_compile_cache`` takes the directory from
    ``JAX_COMPILATION_CACHE_DIR``)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(cfg: dict):
    """The program's ``CTRConfig``: the shared widths, and the keywords of
    the tower that the configuration's ``arch`` names."""
    from repro.core.interest import InterestConfig
    from repro.models.ctr import CTRConfig

    return CTRConfig(
        arch=cfg["arch"], n_items=cfg["n_items"], n_cats=cfg["n_cats"],
        embed_dim=cfg["embed_dim"], short_len=cfg["short_len"],
        long_len=cfg["long_len"], mlp_hidden=tuple(cfg["mlp_hidden"]),
        ctx_dim=cfg["ctx_dim"],
        interest=InterestConfig(kind="sdim", m=cfg["m"], tau=cfg["tau"]),
        **spec.tower(cfg["arch"]).config_kwargs(cfg))


def history_shape(cfg: dict) -> histories.HistoryShape:
    return histories.HistoryShape(cfg["n_items"], cfg["n_cats"],
                                  cfg["long_len"])


def recent_window(h: dict, s: int):
    """(3, B, s) int32: the newest ``s`` items, categories and mask bits of
    a chunk of histories, what a request carries of its user."""
    import jax.numpy as jnp

    return jnp.stack([h["hist_items"][:, -s:], h["hist_cats"][:, -s:],
                      h["hist_mask"][:, -s:].astype(jnp.int32)], axis=0)


def request_inputs(sched: traffic.Schedule, recent, ks: list,
                   users: list) -> dict:
    """The inputs of requests ``ks`` as the reference takes them: their
    candidates, context and recent window, and ``slot``, the row of each
    request's user in ``users``."""
    slot_of = {u: s for s, u in enumerate(users)}
    rec = np.asarray(recent[:, sched.users[ks]])
    return {"slot": np.array([slot_of[int(sched.users[k])] for k in ks]),
            "cand_items": sched.cand_items[ks],
            "cand_cats": sched.cand_cats[ks], "ctx": sched.ctx[ks],
            "hi": rec[0], "hc": rec[1], "hm": rec[2].astype(np.float32)}


def histories_of(cfg: dict, key, users: list) -> dict:
    """The full histories of ``users`` (sorted), made again from the key."""
    shape = history_shape(cfg)
    out = {"items": [], "cats": [], "mask": []}
    by_chunk = {}
    for u in users:
        by_chunk.setdefault(u // CHUNK, []).append(u % CHUNK)
    order = []
    for c, rows in sorted(by_chunk.items()):
        h = histories.chunk(key, c, shape=shape, batch=CHUNK)
        idx = np.array(rows)
        for k, name in (("items", "hist_items"), ("cats", "hist_cats"),
                        ("mask", "hist_mask")):
            out[k].append(np.asarray(h[name])[idx])
        order += [c * CHUNK + r for r in rows]
    if order != list(users):
        raise ValueError("users must be sorted and distinct")
    return {k: np.concatenate(v) for k, v in out.items()}


class CompileCounter:
    """Counts the traces and compilations JAX reports inside ``with``."""

    def __init__(self):
        self.n = 0

    def _event(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)


class Run:
    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 trace: bool, log=print):
        self.cell = cell
        self.cfg = cell.config
        self.mix = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = trace
        self.log = log
        self.errors = 0
        # a run on the chip must run the chip's compiled kernels; tests on
        # a CPU host turn this off to drive the rest of a run
        self.require_chip = True

    # ------------------------------------------------------------------
    # set-up
    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.launch.serve import build_ctr_server, build_mesh
        from repro.serve.tracing import Tracer

        cfg, mix = self.cfg, self.mix
        t = time.perf_counter()
        self.k_weights, self.k_hist = jax.random.split(
            weights.model_key(self.seed))
        w = weights.make(cfg, self.k_weights)
        jax.block_until_ready(w)
        self.tracer = (Tracer(max_sampled=1 << 30, max_tail=1)
                       if self.trace else None)
        self.model, self.params, self.server = build_ctr_server(
            model_config(cfg), backend="auto", params=w,
            capacity=cfg["population"] // (
                1 if self.cell.chips == 1 else SHARDED_START),
            fused=cfg["fused"],
            table_dtype=jnp.dtype(cfg["table_dtype"]),
            wire_dtype=jnp.dtype(cfg["wire_dtype"]), tracer=self.tracer,
            mesh=build_mesh(self.cell.chips))
        eng = self.model.engine
        if self.require_chip and (eng.backend != "pallas" or eng.interpret):
            raise RuntimeError(f"engine backend {eng.backend} (interpret "
                               f"{eng.interpret}): not the chip's kernels")
        self.log(f"weights and server: {time.perf_counter() - t:.2f} s")

        t = time.perf_counter()
        self.ingest()
        self.log(f"ingest of {cfg['population']} histories of "
                 f"{cfg['long_len']}: {time.perf_counter() - t:.2f} s")

        t = time.perf_counter()
        self.sched = traffic.schedule(mix, cfg, self.seconds, self.seed,
                                      self.interests)
        self.requests = self.make_requests(self.sched)
        self.sample = checks.sample(len(self.sched), self.seed, SAMPLE)
        self.in_sample = np.zeros(len(self.sched), bool)
        self.in_sample[self.sample] = True
        self.install_capture()
        self.warm_up()
        self.log(f"{len(self.requests)} requests made and every burst size "
                 f"warmed: {time.perf_counter() - t:.2f} s")

    def ingest(self) -> None:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        n = cfg["population"]
        if n % CHUNK:
            raise ValueError(f"population {n} is not a multiple of {CHUNK}")
        shape = history_shape(cfg)
        s = cfg["short_len"]
        recent, interests = [], []
        bse = self.server.bse
        for c in range(n // CHUNK):
            h = histories.chunk(self.k_hist, c, shape=shape, batch=CHUNK)
            bse.ingest_histories(list(range(c * CHUNK, (c + 1) * CHUNK)),
                                 h["hist_items"], h["hist_cats"],
                                 h["hist_mask"])
            recent.append(recent_window(h, s))
            interests.append(h["interests"])
        jax.block_until_ready(bse.store.data)
        self.recent = jnp.concatenate(recent, axis=1)        # (3, N, s)
        self.interests = np.asarray(jnp.concatenate(interests))

    def make_requests(self, sched: traffic.Schedule) -> list:
        """``handle_requests`` tuples holding host arrays only."""
        rec = np.asarray(self.recent[:, sched.users])          # (3, n, s)
        hi, hc = rec[0], rec[1]
        hm = rec[2].astype(np.float32)
        return [(int(u), {"hist_items": hi[k:k + 1], "hist_cats": hc[k:k + 1],
                          "hist_mask": hm[k:k + 1]},
                 sched.cand_items[k], sched.cand_cats[k], sched.ctx[k])
                for k, u in enumerate(sched.users)]

    def install_capture(self) -> None:
        """Keep what the BSE side hands to the scorer in bursts that hold a
        sampled request: the fetched tables (two-dispatch) or the interest
        vectors (fused). It keeps a reference and adds no device work."""
        bse = self.server.bse
        name = "serve_candidates" if self.cfg["fused"] else "fetch_many"
        inner = getattr(bse, name)
        self.captured, self.capture_key = {}, None

        def capture(*args, **kw):
            out = inner(*args, **kw)
            if self.capture_key is not None:
                self.captured[self.capture_key] = out
            return out

        setattr(bse, name, capture)

    def warm_up(self) -> None:
        """Every burst size the window can hand over, twice, through
        ``handle_requests``; the results are discarded."""
        mb = self.mix["max_burst"]
        need = mb * (mb + 1)
        warm = traffic.schedule(self.mix, self.cfg,
                                need / self.mix["rate_per_s"] + 1.0,
                                self.seed, self.interests, stream=2)
        reqs = self.make_requests(warm)
        k = 0
        for _ in range(2):
            for b in range(1, mb + 1):
                self.server.handle_requests(reqs[k:k + b])
                k += b

    # ------------------------------------------------------------------
    # the window
    # ------------------------------------------------------------------
    def window(self) -> None:
        import jax

        annotate = (jax.profiler.TraceAnnotation if self.trace
                    else lambda name: contextlib.nullcontext())
        due = self.sched.due
        n = len(due)
        mb = self.mix["max_burst"]
        server, reqs = self.server, self.requests
        self.latency = np.full(n, np.nan)
        self.scores = {}
        self.bursts = []          # (start, end, i, j) seconds from t0
        self.late = []            # generator lateness after each wait
        failed = 0
        i = 0
        # what set-up made lives on: keep the collector from scanning it
        # inside the window
        gc.collect()
        gc.freeze()
        self.compiles = CompileCounter()
        with self.compiles, annotate("bench.window"):
            t0 = self.t0 = time.perf_counter()
            while i < n:
                now = time.perf_counter() - t0
                if now > self.seconds + GRACE_S:
                    break
                if due[i] > now:
                    with annotate("bench.wait_arrival"):
                        time.sleep(max(due[i] - now, 0.0))
                    self.late.append(time.perf_counter() - t0 - due[i])
                    continue
                j = traffic.bursts(due, now, i, mb)
                self.capture_key = i if self.in_sample[i:j].any() else None
                start = time.perf_counter() - t0
                try:
                    with annotate("bench.handle_requests"):
                        out = server.handle_requests(reqs[i:j])
                except Exception:
                    self.errors += 1
                    if self.errors == 1:
                        traceback.print_exc()
                    out = [None] * (j - i)
                end = time.perf_counter() - t0
                self.bursts.append((start, end, i, j))
                for k, o in enumerate(out, start=i):
                    if o is None:
                        failed += 1
                        continue
                    self.latency[k] = end - due[k]
                    if self.in_sample[k]:
                        self.scores[k] = o
                i = j
            self.t1 = time.perf_counter()
        gc.unfreeze()
        self.capture_key = None
        self.attempted = n
        self.failed = failed + (n - i)

    # ------------------------------------------------------------------
    # the check
    # ------------------------------------------------------------------
    def program_outputs(self) -> tuple[dict, dict, list]:
        """What the timed path produced for the served sampled requests,
        on the host: the stored tables of their users, their interest
        vectors and their scores; with the inputs the reference needs."""
        import jax.numpy as jnp

        ks = [k for k in self.sample if k in self.scores]
        if not ks:
            raise RuntimeError("no sampled request was served")
        burst_of = {}
        for _, _, i, j in self.bursts:
            for k in range(i, j):
                burst_of[k] = i, j
        users = sorted({int(self.sched.users[k]) for k in ks})
        store = self.server.bse.store
        rows = np.asarray(store.rows(store.slots(users)), np.float32)
        interest = []
        seen = {}
        for k in ks:
            i, j = burst_of[k]
            if i not in seen:
                out = self.captured[i]
                if not self.cfg["fused"]:
                    # sdim_query on the fetched tables, as the scorer runs it
                    te = self.server._embed_targets(
                        self.params,
                        jnp.asarray(self.sched.cand_items[i:j]),
                        jnp.asarray(self.sched.cand_cats[i:j]))
                    out = self.model.engine.query(
                        te, out, R=self.params["interest"]["buffers"]["R"])
                seen[i] = np.asarray(out, np.float32)
            interest.append(seen[i][k - i])
        got = {"tables": rows, "interest": np.stack(interest),
               "scores": np.stack([self.scores[k] for k in ks])}
        return got, request_inputs(self.sched, self.recent, ks, users), users

    def free_program(self) -> None:
        """Drop every reference to the program's device state."""
        self.server = self.model = self.params = None
        self.captured = {}
        self.recent = None
        gc.collect()

    def check(self) -> dict:
        import jax
        import jax.numpy as jnp

        got, req, users = self.program_outputs()
        self.free_program()
        hist = histories_of(self.cfg, self.k_hist, users)
        w = weights.make(self.cfg, self.k_weights)
        want = checks.reference_outputs(
            self.cfg, w, hist, req, dtype=jnp.float32,
            qmax=self.cfg.get("qmax"), wire=self.cfg["wire_dtype"])
        del w
        jax.clear_caches()
        return checks.compare(got, want, self.cfg["limits"])

    # ------------------------------------------------------------------
    # end-to-end metrics
    # ------------------------------------------------------------------
    def latency_ms(self) -> np.ndarray:
        """Latencies of the served requests, due time to scores, in ms."""
        return self.latency[np.isfinite(self.latency)] * 1e3

    def end_to_end(self, setup_s: float) -> dict:
        """The cell's end-to-end metrics: the median latency over every
        request served in the window, and the set-up time."""
        every = {"p50_ms": (float(np.percentile(self.latency_ms(), 50)),
                            "ms"),
                 "setup_s": (setup_s, "s")}
        return {m["name"]: {"value": every[m["name"]][0],
                            "unit": every[m["name"]][1]}
                for m in self.cell.end_to_end}

    def per_layer_context(self, trace, reduced) -> types.SimpleNamespace:
        """What a per-layer metric reader reads."""
        from chipbench import peaks, shapes, tracereduce

        import jax

        spans = []
        if self.tracer is not None:
            for t in self.tracer.finished():
                if t.root.t0 >= self.t0 and t.root.t1 <= self.t1:
                    spans += [s for s in t.spans if s.t1 is not None]
        return types.SimpleNamespace(
            cell=self.cell, cfg=self.cfg, mix=self.mix,
            peaks=peaks.peaks_for(jax.devices()[0].device_kind),
            shapes=shapes, tracereduce=tracereduce, trace=trace,
            reduced=reduced, spans=spans, latency_ms=self.latency_ms(),
            bursts=[(s, e, j - i, self.mix["candidates"])
                    for s, e, i, j in self.bursts])

