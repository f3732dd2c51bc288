"""CTR server (paper §4.4, Fig. 1): scores B candidate items per request.

Two deployments, matching the paper's ablation:
  * ``mode="decoupled"`` — fetch the user's bucket table from the BSE server
    (latency-free long-term interest: candidate hashing only, O(B·m·log d));
  * ``mode="inline"``    — hash the raw behavior sequence inside the request
    (what SDIM costs *without* the BSE split);
  * ``mode="target_attention"`` — exact long-seq attention (the DIN(Long
    Seq.) deployment the paper could not keep online).

Requests are served one at a time (``handle_request``) or **micro-batched**
(``handle_requests``): a burst of N requests becomes ONE ``fetch_many``
gather against the BSE ``TableStore`` plus ONE scoring dispatch over the
padded (N, C_max) candidate block — the per-dispatch overhead that kills
per-user serving at scale is paid once per burst.

``ServeStats`` records wall-clock per stage for benchmarks/table5.

All SDIM compute (decoupled bucket reads AND the inline hash path) reaches
the kernels through the model's ``SDIMEngine``, so the server inherits the
engine's backend (``xla`` reference vs fused ``pallas`` kernels) from the
model config with no server-side branching.

``CTRServer.build`` is the mesh-aware constructor for the whole serving
pair: it wires the model's behavior-embedding fn and checkpointed hash
family into the BSE server, and a ``mesh=`` shards the BSE table store over
the mesh's model axis (see docs/ARCHITECTURE.md) — the request path above
is unchanged, ``fetch_many`` just resolves against the sharded store.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.ctr import CTRModel
from repro.serve.admission import AdmissionController
from repro.serve.bse_server import BSEServer
from repro.serve.metrics import MetricsRegistry, observe_ms
from repro.serve.tracing import NOOP_SPAN, Tracer


@dataclasses.dataclass
class ServeStats:
    n_requests: int = 0        # requests actually served
    n_shed: int = 0            # requests refused by admission (never served)
    total_time_s: float = 0.0
    fetch_time_s: float = 0.0

    @property
    def ms_per_request(self) -> float:
        return 1e3 * self.total_time_s / max(self.n_requests, 1)


class CTRServer:
    @classmethod
    def build(cls, model: CTRModel, params: Any, mode: str = "decoupled",
              *, mesh: Any = None, capacity: int = 64,
              wire_dtype: Any = jnp.bfloat16, hot_capacity: int = None,
              store_dir: str = None, policy: str = None,
              warm_capacity: int = None, table_dtype: Any = jnp.float32,
              fused: bool = False, async_ingest: bool = False,
              queue_depth: int = 1024,
              max_staleness: int = 64,
              max_concurrency: int = None,
              rate_limit: float = None,
              rate_burst: float = None,
              cold_deadline_s: float = None,
              metrics: MetricsRegistry = None,
              tracer: Tracer = None,
              clock=None) -> "CTRServer":
        """Mesh-aware construction of the whole serving pair: wires the
        model's behavior-embedding fn and checkpointed hash family ``R``
        into a ``BSEServer`` (decoupled mode), sharding its table store over
        ``mesh``'s model axis when a Mesh/MeshCtx is given. Any of
        ``hot_capacity``/``store_dir``/``policy``/``warm_capacity`` selects
        the tiered store (bounded device tier + host/disk overflow +
        snapshot-restore; see serve/tiered_store.py) — the request path is
        unchanged, ``fetch_many`` just promotes through the tiers. Every
        launcher and benchmark builds through here so the embed/R plumbing
        lives in one place.

        ``table_dtype`` picks the BSE table STORAGE dtype (fp32 | bf16 |
        int8 | fp8, see serve/quant.py). ``fused=True`` routes decoupled
        micro-batches through ``BSEServer.serve_candidates`` — ONE fused
        gather+dequant+query dispatch instead of ``fetch_many`` + the
        model-side ``engine.query``; only the (B, C, e) interest crosses
        between the servers.

        ``async_ingest=True`` runs BSE ingestion OFF the request path
        (serve/ingest.py): missing users are enqueued, not encoded inline
        — they score with zero long-term interest until the writer loop
        folds and commits them (bounded by ``max_staleness``; queue drops
        past ``queue_depth`` are counted). Reads never block on a fold.

        Production runtime knobs (serve/admission.py, serve/metrics.py):
        ``max_concurrency`` bounds concurrent ``handle_requests`` bursts
        (excess bursts shed whole — every request returns an explicit
        ``None`` score and is counted, never silently dropped);
        ``rate_limit`` (requests/sec, burst headroom ``rate_burst``)
        token-bucket-limits admission — the tail of an over-budget burst
        sheds. ``cold_deadline_s`` arms the tiered store's cold-tier
        circuit breaker (degrade-to-miss instead of stalling on a slow
        disk). ``metrics`` is the shared registry (created when omitted)
        every layer reports into; ``tracer`` (serve/tracing.py) threads
        per-request spans through every layer of the pair — admission,
        assembly, BSE fetch, tier movement, scoring dispatch and the
        async-ingest fold; ``clock`` injects a virtual clock for
        deterministic fault tests."""
        from repro.serve.tiered_store import is_tiered

        bse = None
        tiered = is_tiered(hot_capacity, store_dir, policy, warm_capacity)
        metrics = MetricsRegistry() if metrics is None else metrics
        if mode != "decoupled" and async_ingest:
            raise ValueError(
                f"async ingestion feeds the BSE table store, which only the "
                f"decoupled deployment has (mode={mode!r})")
        if mode != "decoupled" and mesh is not None:
            raise ValueError(
                f"mesh shards the BSE table store, which only the decoupled "
                f"deployment has (mode={mode!r})")
        if mode != "decoupled" and tiered:
            raise ValueError(
                f"hot_capacity/store_dir/policy tier the BSE table store, "
                f"which only the decoupled deployment has (mode={mode!r})")
        if mode != "decoupled" and fused:
            raise ValueError(
                f"fused serving reads the BSE table store, which only the "
                f"decoupled deployment has (mode={mode!r})")
        if cold_deadline_s is not None and not tiered:
            raise ValueError(
                "cold_deadline_s arms the cold-tier circuit breaker, which "
                "needs the tiered store (pass hot_capacity=/store_dir=/"
                "policy=/warm_capacity=)")
        # the BSE server's ingest embeds from the same packed tables, in
        # one program per shape: op by op, every intermediate of the packed
        # gather would be a buffer as large as the embeddings
        params = model.pack_tables(params)
        if mode == "decoupled":
            def bse_embed(p, items, cats):
                return model._embed_behaviors(p, items, cats)

            bse = BSEServer(jax.jit(bse_embed), params, model.engine,
                            pack_params=model.pack_tables,
                            R=params["interest"]["buffers"]["R"],
                            wire_dtype=wire_dtype, capacity=capacity,
                            mesh=mesh, hot_capacity=hot_capacity,
                            store_dir=store_dir, policy=policy,
                            warm_capacity=warm_capacity,
                            table_dtype=table_dtype,
                            async_ingest=async_ingest,
                            queue_depth=queue_depth,
                            max_staleness=max_staleness,
                            metrics=metrics,
                            tracer=tracer,
                            cold_deadline_s=cold_deadline_s,
                            clock=clock)
        admission = None
        if max_concurrency is not None or rate_limit is not None:
            import time as _time
            admission = AdmissionController(
                max_concurrency=max_concurrency, rate=rate_limit,
                burst=rate_burst,
                clock=_time.monotonic if clock is None else clock)
        return cls(model, params, bse, mode=mode, fused=fused,
                   admission=admission, metrics=metrics, tracer=tracer)

    def __init__(self, model: CTRModel, params: Any,
                 bse_server: Optional[BSEServer] = None,
                 mode: str = "decoupled", fused: bool = False,
                 admission: Optional[AdmissionController] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        assert mode in ("decoupled", "inline", "target_attention")
        if mode == "decoupled":
            assert bse_server is not None
        self.model = model
        # narrow embedding tables are stored lane-packed (Embedding.pack):
        # a row gather then reads the table in place instead of copying
        # all of it to row-major in every program (idempotent)
        self.params = model.pack_tables(params)
        self.bse = bse_server
        self.mode = mode
        self.fused = fused
        self.admission = admission
        self.metrics = metrics if metrics is not None else (
            bse_server.metrics if bse_server is not None else None)
        self.tracer = tracer
        self.stats = ServeStats()
        if self.metrics is not None:
            self.metrics.gauge("ctr.packed_tables").set(
                model.n_packed_tables(self.params))
        # named functions, so each compiled program carries a fixed module
        # name (jit_ctr_score_tables, ...) that the device trace shows
        def ctr_score_tables(p, u, ci, cc, ctx, tb):
            return model.score_candidates_many(p, u, ci, cc, ctx,
                                               bucket_tables=tb)

        def ctr_score_interest(p, u, ci, cc, ctx, it):
            return model.score_candidates_many(p, u, ci, cc, ctx,
                                               interest=it)

        def ctr_score_raw(p, u, ci, cc, ctx):
            return model.score_candidates_many(p, u, ci, cc, ctx)

        def ctr_embed_targets(p, ci, cc):
            return model._embed_behaviors(p, ci, cc)

        self._score_many_table = jax.jit(ctr_score_tables)
        self._score_many_interest = jax.jit(ctr_score_interest)
        self._score_many_raw = jax.jit(ctr_score_raw)
        self._embed_targets = jax.jit(ctr_embed_targets)

    def handle_request(self, user: Any, user_batch: dict,
                       cand_items, cand_cats, ctx):
        """Single-request serving: a burst of ONE through the exact
        ``handle_requests`` path — same admission accounting, same timing,
        same spans — so singular and batched metrics/traces agree
        (``score_candidates`` is the B=1 case of ``score_candidates_many``,
        so scores are unchanged). ``user_batch``: hist_* (1, L) arrays.
        Returns the (C,) scores, or ``None`` when admission shed the
        request."""
        return self.handle_requests(
            [(user, user_batch, cand_items, cand_cats, ctx)])[0]

    def handle_requests(self, requests) -> list:
        """Micro-batched serving: ``requests`` is a list of ``(user,
        user_batch, cand_items, cand_cats, ctx)`` tuples (the
        ``handle_request`` signature). Candidate lists are right-padded to
        the burst max and the padded scores sliced off, so callers get back
        exactly one (C_i,) score array per request.

        Decoupled mode pre-encodes all missing users in ONE batched
        ``ingest_histories`` and reads all tables in ONE ``fetch_many``
        (on an async-ingest server the encode is enqueued instead — the
        request never waits on the write path). An empty burst is a no-op:
        ``[]`` in, ``[]`` out, nothing dispatched.

        With an ``AdmissionController`` attached (``CTRServer.build``'s
        ``max_concurrency``/``rate_limit``), overload SHEDS instead of
        queueing: a burst arriving while ``max_concurrency`` bursts are in
        flight is refused whole; a burst over the token-bucket budget is
        served as an admitted prefix. Every shed request still gets a list
        slot — an explicit ``None`` score — and is counted
        (``stats.n_shed``, ``ctr.shed``): callers always receive
        ``len(requests)`` entries, degradation is never silent."""
        if not requests:
            return []
        tr = self.tracer
        if tr is not None and tr.enabled:
            root = tr.span("ctr.request", n=len(requests))
        else:
            root, tr = NOOP_SPAN, None
        with root:
            adm = self.admission
            if adm is None:
                return self._handle_admitted(requests)
            with (tr.span("ctr.admission") if tr is not None
                  else NOOP_SPAN) as asp:
                entered = adm.enter()
                k = adm.admit(len(requests)) if entered else 0
                asp.set(offered=len(requests), admitted=k)
            if not entered:
                self._note_shed(len(requests))
                adm.shed_all(len(requests))
                if tr is not None:
                    tr.flag("shed")
                return [None] * len(requests)
            try:
                if k < len(requests):
                    self._note_shed(len(requests) - k)
                    if tr is not None:
                        tr.flag("shed")
                out = self._handle_admitted(requests[:k]) if k else []
                return out + [None] * (len(requests) - k)
            finally:
                adm.exit()

    def _note_shed(self, n: int) -> None:
        self.stats.n_shed += n
        if self.metrics is not None:
            self.metrics.counter("ctr.shed").inc(n)

    def _dispatch(self, fn, *args):
        """Jitted scoring dispatch, synchronized. When tracing, a dispatch
        that GREW the jit cache is recorded as an explicit
        ``ctr.jit_compile`` span (and ``ctr.jit_compiles`` counter) so
        compile stalls are attributed instead of polluting serve tails;
        its ``ctr.device_wait`` child covers the wait for the device, so
        the span's self time is the dispatch."""
        tr = self.tracer
        if tr is None or not tr.enabled:
            scores = fn(*args)
            scores.block_until_ready()
            return scores
        size = getattr(fn, "_cache_size", None)
        before = size() if size is not None else -1
        with tr.span("ctr.score") as sp:
            scores = fn(*args)
            with tr.span("ctr.device_wait"):
                scores.block_until_ready()
            if size is not None and size() > before:
                sp.name = "ctr.jit_compile"
                if self.metrics is not None:
                    self.metrics.counter("ctr.jit_compiles").inc()
        return scores

    def _handle_admitted(self, requests) -> list:
        tr = self.tracer
        if tr is not None and not tr.enabled:
            tr = None
        t0 = time.perf_counter()
        users = [r[0] for r in requests]
        n_cands = [len(r[2]) for r in requests]
        c_max = max(n_cands)

        def pad_c(x, c):
            x = np.asarray(x)
            return np.pad(x, [(0, c_max - c)] + [(0, 0)] * (x.ndim - 1))

        # assemble the burst host-side: ONE upload per operand, not one
        # device op per request. Decoupled scoring reads only the recent
        # short_len window (the long branch reads the fetched tables), so
        # don't ship (B, L) histories it will never touch.
        with (tr.span("ctr.assemble", n=len(requests), c_max=c_max)
              if tr is not None else NOOP_SPAN):
            lo = -self.model.cfg.short_len if self.mode == "decoupled" else 0
            ci = jnp.asarray(np.stack([pad_c(r[2], c)
                                       for r, c in zip(requests, n_cands)]))
            cc = jnp.asarray(np.stack([pad_c(r[3], c)
                                       for r, c in zip(requests, n_cands)]))
            ctx = jnp.asarray(np.stack([pad_c(r[4], c)
                                        for r, c in zip(requests, n_cands)]))
            hist = {k: jnp.asarray(np.concatenate(
                        [np.asarray(r[1][k])[:, lo:] for r in requests]))
                    for k in ("hist_items", "hist_cats", "hist_mask")}

        if self.mode == "decoupled":
            tf0 = time.perf_counter()
            missing = {}
            with (tr.span("ctr.lookup") if tr is not None else NOOP_SPAN):
                for r in requests:
                    if r[0] not in self.bse.tables:
                        missing.setdefault(r[0], r[1])
            if missing:
                with (tr.span("ctr.ingest_missing", n=len(missing))
                      if tr is not None else NOOP_SPAN):
                    self.bse.ingest_histories(
                        list(missing),
                        np.concatenate([np.asarray(b["hist_items"])
                                        for b in missing.values()]),
                        np.concatenate([np.asarray(b["hist_cats"])
                                        for b in missing.values()]),
                        np.concatenate([np.asarray(b["hist_mask"])
                                        for b in missing.values()]),
                    )
            if self.fused:
                # fused deployment: the megakernel gathers + dequantizes +
                # queries in one dispatch on the BSE side; only (B, C, e)
                # interest vectors reach the scoring graph
                with (tr.span("ctr.embed_targets") if tr is not None
                      else NOOP_SPAN):
                    target_e = self._embed_targets(self.params, ci, cc)
                interest = self.bse.serve_candidates(users, target_e)
                self.stats.fetch_time_s += time.perf_counter() - tf0
                scores = self._dispatch(self._score_many_interest,
                                        self.params, hist, ci, cc,
                                        ctx, interest)
            else:
                tables = self.bse.fetch_many(users)
                self.stats.fetch_time_s += time.perf_counter() - tf0
                scores = self._dispatch(self._score_many_table,
                                        self.params, hist, ci, cc,
                                        ctx, tables)
        else:
            scores = self._dispatch(self._score_many_raw,
                                    self.params, hist, ci, cc, ctx)
        dt = time.perf_counter() - t0
        self.stats.total_time_s += dt
        self.stats.n_requests += len(requests)
        trace_id = None
        if tr is not None:
            cur = tr.current()
            trace_id = cur.trace_id if cur is not None else None
            # the recorded latency rides the trace too, so a histogram
            # exemplar resolves to a trace that can vouch for its bucket
            tr.annotate(request_ms=1e3 * dt)
        if self.metrics is not None:
            observe_ms(self.metrics, "ctr.request_ms", dt,
                       exemplar=trace_id)
            self.metrics.counter("ctr.requests").inc(len(requests))
        # one device->host transfer, then per-request views (slicing the
        # device array would issue one tiny device op per request)
        with (tr.span("ctr.to_host") if tr is not None else NOOP_SPAN):
            host = np.asarray(scores)
            return [host[i, :c] for i, c in enumerate(n_cands)]
