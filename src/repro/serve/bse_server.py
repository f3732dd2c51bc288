"""BSE server (paper §4.4): user-wise behavior-sequence hashing, decoupled
from the CTR server.

Responsibilities modeled faithfully:
  * maintain per-user bucket tables (the FULL serving state: (G, U, d),
    L-free — "no matter how long the user's behavior is, we only need to
    transmit fixed-length vectors") in a contiguous multi-user
    ``TableStore`` — one (N, G, U, d) device array + user→slot index with
    amortized-doubling growth and slot recycling on eviction — or, given a
    ``mesh``, a ``ShardedTableStore`` row-sharded over the mesh's model
    axis, so the serving state scales past one device's HBM;
  * ingest real-time behavior events incrementally (O(m·d) per event, no
    re-encode of history) — and *batched*: ``ingest_events`` folds B events
    for B (possibly repeated) users in ONE ``SDIMEngine.update`` dispatch,
    ``ingest_histories`` encodes B full histories in ONE encode dispatch;
  * answer CTR-server fetches, accounting transmission bytes (the paper's
    8KB / ~1ms budget). ``fetch_many`` serves N users per gather. The wire
    dtype is explicit: tables are stored fp32 but CAST to ``wire_dtype``
    (default bf16, the paper's 8KB figure) on fetch, so the byte accounting
    matches the array actually transmitted — and the CTR server really
    scores with wire-precision buckets.

The class is split along the paper's own deployment seam (§4.4: BSE runs
OFF the CTR request path) into two halves that share only the table store
and the stats counters:

  * ``BSEIngestor`` — the write path: embeds behaviors with the current
    params and folds them into the store (``ingest_histories`` /
    ``ingest_events``). Oversized bursts are auto-chunked to the tiered
    store's ``hot_capacity`` bound (extra dispatches, never a ValueError
    out of the request path).
  * ``BSEFetcher`` — the read path: ``fetch``/``fetch_many``/
    ``serve_candidates`` against the store; with an ``AsyncIngestor``
    attached (``serve/ingest.py``), reads resolve against the last
    COMMITTED version of the hot state instead of the live store, so they
    never block on (or observe) an in-flight fold.

``BSEServer`` remains the facade composing both halves — every existing
call site keeps working — and ``async_ingest=True`` inserts the queue +
writer-loop runtime between them.

All SDIM compute goes through an ``SDIMEngine``, so the server follows the
engine's backend (XLA reference vs fused Pallas kernels) without any
server-side branching.

The embedding of raw behavior ids depends on the CTR model's current tables,
so the ingestor holds an ``embed_fn`` + params snapshot; ``refresh_params``
models the model-push cycle after each training deployment (the whole store
is invalidated — index emptied, array zeroed — and re-encoded lazily).

Storage backends (the ``serve/`` storage seam):
  * default — unbounded ``TableStore`` (grows by doubling);
  * ``mesh=`` — ``ShardedTableStore`` over the mesh's model axis;
  * any of ``hot_capacity``/``store_dir``/``policy`` — a ``TieredTableStore``
    (device-hot / host-warm / disk-cold, see ``serve/tiered_store.py``),
    composing with ``mesh``. ``snapshot()``/``restore()`` then round-trip
    the FULL serving state (all tiers + indices + hash family ``R`` +
    stats): a restarted server answers identically with no re-ingest.

Unknown-user contract: ``fetch_many`` returns an all-zero row for a user no
tier knows (counted in ``stats.n_misses``) — never a garbage slot gather,
never an exception; callers that want the user served ingest its history
first (``CTRServer.handle_requests`` does exactly that). Under async
ingestion the same contract extends to NOT-YET-COMMITTED users: they read
as zero-row misses until the writer loop folds and commits them (bounded
staleness), and each miss enqueues a promotion touch so tiered stores pull
the user hot off the request path.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import SDIMEngine
from repro.serve.metrics import MetricsRegistry, observe_ms
from repro.serve.table_store import ShardedTableStore, TableStore
from repro.serve.tracing import NOOP_SPAN, Tracer, maybe_span
from repro.serve.tiered_store import (TieredTableStore, _atomic_json,
                                      _atomic_npz, burst_cap, burst_chunks,
                                      is_tiered)


@dataclasses.dataclass
class BSEStats:
    n_encodes: int = 0
    n_updates: int = 0
    n_fetches: int = 0
    n_misses: int = 0          # fetches of users the store does not hold
    bytes_transmitted: int = 0
    encode_time_s: float = 0.0


class _TablesView:
    """Read-only dict-like view over the store, keyed by user (back-compat
    with the old per-user ``dict[user, table]`` surface)."""

    def __init__(self, store: TableStore):
        self._store = store

    def __getitem__(self, user: Any) -> jax.Array:
        row = self._store.row(user)
        if row is None:
            raise KeyError(user)
        return row

    def __contains__(self, user: Any) -> bool:
        return user in self._store

    def __len__(self) -> int:
        return len(self._store)

    def __iter__(self):
        return self._store.users()

    def values(self):
        return (self[u] for u in self._store.users())


class BSEIngestor:
    """Write half of the BSE server: embed behaviors, fold them into the
    shared table store. Owns the params snapshot (embeddings change on
    model push); shares ONLY the store and the stats counters with the
    read half.

    ``donate=False`` (set by the async runtime) makes every device write
    copy-on-write instead of donating buffers, so committed reader
    snapshots taken before a fold stay valid during and after it.
    """

    def __init__(self, embed_fn: Callable, params: Any, engine: SDIMEngine,
                 R: jax.Array, store: Any, stats: BSEStats,
                 metrics: Optional[MetricsRegistry] = None):
        self.embed_fn = embed_fn
        self.params = params
        self.engine = engine
        self.R = R
        self.store = store
        self.stats = stats
        self.metrics = metrics
        self.donate = True

    def ingest_histories(self, users: Sequence[Any], items: np.ndarray,
                         cats: np.ndarray,
                         masks: Optional[np.ndarray] = None) -> None:
        """Batched full (re-)encode: B distinct users' histories (B, L) in
        ONE ``engine.encode`` dispatch, scattered into their slots. A burst
        wider than the tiered store's hot capacity is auto-chunked into
        sub-bursts of ≤ ``hot_capacity`` users (more dispatches, same
        result)."""
        assert len(set(users)) == len(users), "duplicate users in one encode"
        cap = burst_cap(self.store)
        if cap is not None and len(users) > cap:
            items, cats = np.asarray(items), np.asarray(cats)
            for lo, hi in burst_chunks(list(users), cap):
                self.ingest_histories(
                    users[lo:hi], items[lo:hi], cats[lo:hi],
                    None if masks is None else np.asarray(masks)[lo:hi])
            return
        t0 = time.perf_counter()
        seq_e = self.embed_fn(self.params, np.asarray(items), np.asarray(cats))
        m = jnp.asarray(masks) if masks is not None else None
        tables = self.engine.encode(seq_e, m, R=self.R)       # (B, G, U, d)
        tables.block_until_ready()
        dt = time.perf_counter() - t0
        self.stats.encode_time_s += dt
        self.stats.n_encodes += len(users)
        observe_ms(self.metrics, "bse.ingest_encode_ms", dt)
        # assign_fresh: every row is overwritten below, so a tiered store
        # drops stale warm/cold copies instead of promoting them
        self.store.write(self.store.assign_fresh(users), tables)

    def ingest_events(self, users: Sequence[Any], items: np.ndarray,
                      cats: np.ndarray,
                      mask: Optional[np.ndarray] = None) -> None:
        """Batched real-time events: one event-block per user — items/cats
        (B,) or (B, E) — folded into the store in ONE ``engine.update``
        dispatch. Users may repeat (duplicate slots accumulate); unseen
        users start from a zero table. Bursts touching more distinct users
        than the hot tier holds are auto-chunked like
        ``ingest_histories``."""
        items = np.asarray(items)
        cats = np.asarray(cats)
        mask = None if mask is None else np.asarray(mask)
        if items.ndim == 1:
            items, cats = items[:, None], cats[:, None]
            mask = None if mask is None else mask[:, None]
        if mask is not None:
            assert mask.shape == items.shape, (mask.shape, items.shape)
        cap = burst_cap(self.store)
        if cap is not None and len(set(users)) > cap:
            for lo, hi in burst_chunks(list(users), cap):
                self.ingest_events(
                    users[lo:hi], items[lo:hi], cats[lo:hi],
                    None if mask is None else mask[lo:hi])
            return
        ev_e = self.embed_fn(self.params, items, cats)        # (B, E, d)
        m = None if mask is None else jnp.asarray(mask)
        slots = self.store.assign(users)
        if self.store.quantized:
            # int8/fp8 payloads can't take an in-place scatter-add (the raw
            # bytes are meaningless without their scales): encode the event
            # deltas, fold duplicates, then read-modify-write the touched
            # rows — one dequantizing gather + one requantizing scatter
            deltas = self.engine.encode(ev_e, m, R=self.R)    # (B, G, U, d)
            uniq, inv = np.unique(np.asarray(slots), axis=0,
                                  return_inverse=True)
            deltas = jax.ops.segment_sum(deltas, jnp.asarray(inv.ravel()),
                                         num_segments=len(uniq))
            self.store.write(uniq, self.store.rows(uniq) + deltas)
        elif self.store.sharded:
            self.store.data = self.engine.update_sharded(
                self.store.data, slots, ev_e, m, R=self.R,
                mesh=self.store.mesh_ctx, donate=self.donate)
        else:
            self.store.data = self.engine.update(self.store.data, slots,
                                                 ev_e, m, R=self.R,
                                                 donate=self.donate)
        self.stats.n_updates += int(items.size if mask is None
                                    else np.sum(np.asarray(mask) > 0))


class BSEFetcher:
    """Read half of the BSE server: gather / fused-score against the table
    store, cast to the wire dtype, account bytes. With an ``AsyncIngestor``
    attached, every read resolves against the last COMMITTED version of the
    hot state (``serve/ingest.py``) — lock-free, never blocked by an
    in-flight fold — and misses enqueue promotion touches instead of
    promoting inline."""

    def __init__(self, engine: SDIMEngine, R: jax.Array, store: Any,
                 wire_dtype: Any, stats: BSEStats,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        self.engine = engine
        self.R = R
        self.store = store
        self.wire_dtype = jnp.dtype(wire_dtype)
        self.stats = stats
        self.metrics = metrics
        self.tracer = tracer
        self._async = None      # AsyncIngestor once attached

    def attach(self, runtime) -> None:
        self._async = runtime

    def _view(self):
        """Committed snapshot to read from, or None for the live store."""
        return None if self._async is None else self._async.committed

    def _touch_misses(self, users: Sequence[Any], present) -> None:
        if self._async is not None:
            for u, p in zip(users, present):
                if not p:
                    self._async.submit_touch(u)

    def fetch(self, user: Any) -> Optional[jax.Array]:
        """CTR-server fetch: cast to the wire dtype and account exactly the
        bytes of the array that crosses the wire. Unknown user -> ``None``
        (counted in ``stats.n_misses``). A single fetch is a burst of one:
        on a tiered store it promotes the user and touches the eviction
        policy exactly like ``fetch_many`` (no silent cold-tier re-reads).
        Async: a user not in the committed version reads as a miss and is
        queued for promotion."""
        view = self._view()
        if view is not None:
            row = view.row(user)
            if row is None:
                self.stats.n_misses += 1
                self._async.submit_touch(user)
                return None
            table = row
        else:
            if user not in self.store:
                self.stats.n_misses += 1
                return None
            table = self.store.rows(self.store.slots([user]))[0]
        wire = table.astype(self.wire_dtype)
        self.stats.n_fetches += 1
        self.stats.bytes_transmitted += wire.size * self.wire_dtype.itemsize
        return wire

    def fetch_many(self, users: Sequence[Any]) -> jax.Array:
        """Batched fetch: ONE gather -> (B, G, U, d) in the wire dtype.
        A user the store does not hold gets an ALL-ZERO row and bumps
        ``stats.n_misses`` — never a garbage slot gather, never an
        exception (callers that need the user served ingest first). On a
        tiered store, warm/cold users are batch-promoted and hit — with the
        burst auto-chunked when it touches more distinct users than the hot
        tier holds. Bytes are accounted for the array actually returned."""
        tr = self.tracer
        sp = (tr.span("bse.fetch_many", n=len(users))
              if tr is not None and tr.enabled else NOOP_SPAN)
        with sp:
            t0 = time.perf_counter()
            view = self._view()
            if view is not None:
                with maybe_span(tr, "bse.lookup"):
                    slots, present = view.lookup(users)
                rows = view.rows(slots)
                self._touch_misses(users, present)
            else:
                cap = burst_cap(self.store)
                if cap is not None:
                    chunks = burst_chunks(list(users), cap)
                    if len(chunks) > 1:
                        # chunked: each sub-burst observes its own dispatch
                        # (and its own child span)
                        return jnp.concatenate(
                            [self.fetch_many(users[lo:hi])
                             for lo, hi in chunks])
                with maybe_span(tr, "bse.lookup"):
                    slots, present = self.store.lookup(users)
                rows = self.store.rows(slots)
            misses = len(users) - int(present.sum())
            if misses:
                rows = rows * jnp.asarray(present,
                                          rows.dtype)[:, None, None, None]
            wire = rows.astype(self.wire_dtype)
            self.stats.n_fetches += len(users)
            self.stats.n_misses += misses
            self.stats.bytes_transmitted += \
                wire.size * self.wire_dtype.itemsize
            sp.set(misses=misses)
            if self.metrics is not None:
                observe_ms(self.metrics, "bse.fetch_many_ms",
                           time.perf_counter() - t0)
                self.metrics.counter("bse.fetches").inc(len(users))
                self.metrics.counter("bse.misses").inc(misses)
            return wire

    def serve_candidates(self, users: Sequence[Any], q: jax.Array,
                         R: Optional[jax.Array] = None) -> jax.Array:
        """Fused serving: score candidates ``q`` (B, C, d) for ``users`` in
        ONE dispatch — the megakernel gathers each user's row straight out
        of the table store (dequantizing in VMEM for int8/fp8 stores) and
        returns interest vectors (B, C, d); the (B, G, U, d) table batch
        that ``fetch_many`` materializes never exists. Unknown users get
        zero interest (same miss contract as ``fetch_many``, including
        burst auto-chunking on tiered stores and committed-version reads
        under async ingestion). What crosses to the CTR server is the
        (B, C, d) interest array in the wire dtype — C·d floats per user
        instead of G·U·d."""
        tr = self.tracer
        sp = (tr.span("bse.serve_candidates", n=len(users))
              if tr is not None and tr.enabled else NOOP_SPAN)
        with sp:
            t0 = time.perf_counter()
            view = self._view()
            if view is not None:
                with maybe_span(tr, "bse.lookup"):
                    slots, present = view.lookup(users)
                data, scales = view.data, view.scales
                self._touch_misses(users, present)
            else:
                cap = burst_cap(self.store)
                if cap is not None:
                    chunks = burst_chunks(list(users), cap)
                    if len(chunks) > 1:
                        return jnp.concatenate(
                            [self.serve_candidates(users[lo:hi], q[lo:hi],
                                                   R=R)
                             for lo, hi in chunks])
                with maybe_span(tr, "bse.lookup"):
                    slots, present = self.store.lookup(users)
                data, scales = self.store.data, self.store.scales
            if self.store.sharded:
                out = self.engine.serve_fused_sharded(
                    data, slots, q, present=present, scales=scales,
                    R=self.R if R is None else R, mesh=self.store.mesh_ctx)
            else:
                out = self.engine.serve_fused(
                    data, slots, q, present=present, scales=scales,
                    R=self.R if R is None else R)
            wire = out.astype(self.wire_dtype)
            misses = len(users) - int(present.sum())
            self.stats.n_fetches += len(users)
            self.stats.n_misses += misses
            self.stats.bytes_transmitted += \
                wire.size * self.wire_dtype.itemsize
            sp.set(misses=misses)
            if self.metrics is not None:
                observe_ms(self.metrics, "bse.serve_candidates_ms",
                           time.perf_counter() - t0)
                self.metrics.counter("bse.fetches").inc(len(users))
                self.metrics.counter("bse.misses").inc(misses)
            return wire


class BSEServer:
    def __init__(
        self,
        embed_fn: Callable[[Any, np.ndarray, np.ndarray], jax.Array],
        params: Any,
        engine: SDIMEngine,
        R: Optional[jax.Array] = None,
        wire_dtype: Any = jnp.bfloat16,
        capacity: int = 64,
        mesh: Any = None,
        hot_capacity: Optional[int] = None,
        store_dir: Optional[str] = None,
        policy: Optional[str] = None,
        warm_capacity: Optional[int] = None,
        store: Any = None,
        table_dtype: Any = jnp.float32,
        async_ingest: bool = False,
        queue_depth: int = 1024,
        max_staleness: int = 64,
        drain_batch: int = 256,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
        cold_deadline_s: Optional[float] = None,
        clock: Optional[Callable[[], float]] = None,
        pack_params: Optional[Callable[[Any], Any]] = None,
    ):
        """``mesh`` (a Mesh or MeshCtx) shards the table store over the
        mesh's model axis (``ShardedTableStore``): capacity scales with the
        mesh, ingest/fetch stay one dispatch each, event folds go through
        ``SDIMEngine.update_sharded``. ``None`` keeps the single-device
        ``TableStore``.

        Any of ``hot_capacity`` (device-tier user bound), ``store_dir``
        (cold-tier segment directory), ``policy`` (``"clock"``/``"lru"``)
        or ``warm_capacity`` selects the ``TieredTableStore`` instead —
        bounded HBM, host/disk overflow, snapshot-restore — wrapping the
        sharded hot tier when ``mesh`` is also given. An explicit ``store``
        (e.g. from ``TieredTableStore.restore``) overrides all of these.

        ``table_dtype`` is the STORAGE dtype of the bucket tables
        (``serve/quant.py``: fp32 | bf16 | int8 | fp8). Quantized stores
        keep per-row scales, quantize on write, and serve through either
        ``fetch_many`` (dequantized gather) or ``serve_candidates`` (the
        fused megakernel dequantizes in VMEM).

        ``async_ingest=True`` decouples the write path (paper §4.4's
        latency-free claim): ``ingest_*`` calls enqueue onto a bounded
        host-side queue (depth ``queue_depth``, non-blocking — drops are
        counted, see ``serve/ingest.py``) drained by a writer loop in
        batches of ≤ ``drain_batch``; reads serve the last committed
        version and never block on a fold; a user's un-folded backlog is
        bounded by ``max_staleness`` (the submitting thread folds inline
        past it — backpressure lands on writers, never on readers).

        ``metrics`` is the shared ``MetricsRegistry`` (one is created when
        not given): every layer reports per-path latency histograms and
        counters into it. ``tracer`` (serve/tracing.py) adds per-request
        spans on the read path, tier movement, and — riding each queue
        entry — the async fold that commits a submit.
        ``cold_deadline_s`` arms the tiered store's
        cold-tier circuit breaker (degrade-to-miss, see
        serve/tiered_store.py); ``clock`` injects a virtual clock for
        deterministic fault tests. ``pack_params`` puts the params of
        every ``refresh_params`` in the layout ``params`` already has
        (``CTRModel.pack_tables``)."""
        self.pack_params = (lambda p: p) if pack_params is None \
            else pack_params
        self.engine = engine
        self.R = engine.R if R is None else R
        self.wire_dtype = jnp.dtype(wire_dtype)
        self.metrics = MetricsRegistry() if metrics is None else metrics
        self.tracer = tracer
        cfg = engine.cfg
        tiered = is_tiered(hot_capacity, store_dir, policy, warm_capacity)
        if cold_deadline_s is not None and not tiered and store is None:
            raise ValueError(
                "cold_deadline_s arms the cold-tier circuit breaker, which "
                "needs the tiered store (pass hot_capacity=/store_dir=/"
                "policy=/warm_capacity=)")
        if store is not None:
            assert tuple(store.row_shape) == \
                (cfg.n_groups, cfg.n_buckets, cfg.d), \
                (store.row_shape, cfg)
            self.store = store
            # an injected store (e.g. TieredTableStore.restore) joins this
            # server's observability/runtime config
            if isinstance(store, TieredTableStore):
                store.metrics = self.metrics
                store.tracer = tracer
                if clock is not None:
                    store._clock = clock
                if cold_deadline_s is not None and store.breaker is None:
                    from repro.serve.admission import CircuitBreaker
                    store.breaker = CircuitBreaker(
                        deadline_s=cold_deadline_s, clock=store._clock)
        elif tiered:
            self.store = TieredTableStore(
                cfg.n_groups, cfg.n_buckets, cfg.d,
                hot_capacity=capacity if hot_capacity is None else hot_capacity,
                mesh=mesh, policy=policy or "clock", store_dir=store_dir,
                warm_capacity=warm_capacity, dtype=table_dtype,
                cold_deadline_s=cold_deadline_s, clock=clock,
                metrics=self.metrics, tracer=tracer)
        elif mesh is None:
            self.store = TableStore(cfg.n_groups, cfg.n_buckets, cfg.d,
                                    capacity=capacity, dtype=table_dtype)
        else:
            self.store = ShardedTableStore(cfg.n_groups, cfg.n_buckets,
                                           cfg.d, mesh, capacity=capacity,
                                           dtype=table_dtype)
        self.tables = _TablesView(self.store)
        self.stats = BSEStats()
        self.ingestor = BSEIngestor(embed_fn, params, engine, self.R,
                                    self.store, self.stats,
                                    metrics=self.metrics)
        self.fetcher = BSEFetcher(engine, self.R, self.store,
                                  self.wire_dtype, self.stats,
                                  metrics=self.metrics, tracer=tracer)
        self.async_ingest = None
        if async_ingest:
            from repro.serve.ingest import AsyncIngestor
            self.async_ingest = AsyncIngestor(
                self.ingestor, self.store, queue_depth=queue_depth,
                max_staleness=max_staleness, drain_batch=drain_batch,
                metrics=self.metrics, tracer=tracer)
            self.fetcher.attach(self.async_ingest)

    # the params/embed snapshot lives on the write half; expose it here so
    # existing callers (and refresh_params) keep one source of truth
    @property
    def params(self) -> Any:
        return self.ingestor.params

    @params.setter
    def params(self, value: Any) -> None:
        self.ingestor.params = value

    @property
    def embed_fn(self) -> Callable:
        return self.ingestor.embed_fn

    @embed_fn.setter
    def embed_fn(self, value: Callable) -> None:
        self.ingestor.embed_fn = value

    def refresh_params(self, params: Any) -> None:
        """Model push: new embeddings invalidate the whole store (re-encoded
        lazily; the slot index is emptied so no stale slot can be read).
        Async: queued-but-unfolded behaviors are from the OLD model and are
        dropped with the store; the runtime commits a fresh empty version.
        The pushed params go through ``pack_params`` first."""
        params = self.pack_params(params)
        if self.async_ingest is not None:
            self.async_ingest.refresh(params)
            return
        self.ingestor.params = params
        self.store.clear()

    # ------------------------------------------------------------------
    # ingest (async servers enqueue; sync servers fold inline)
    # ------------------------------------------------------------------
    def ingest_history(self, user: Any, items: np.ndarray, cats: np.ndarray,
                       mask: Optional[np.ndarray] = None) -> None:
        """Full (re-)encode of a user's history."""
        self.ingest_histories(
            [user], np.asarray(items)[None], np.asarray(cats)[None],
            None if mask is None else np.asarray(mask)[None])

    def ingest_histories(self, users: Sequence[Any], items: np.ndarray,
                         cats: np.ndarray,
                         masks: Optional[np.ndarray] = None):
        """Batched full (re-)encode (see ``BSEIngestor.ingest_histories``).
        On an async server this ENQUEUES (returns the accepted count;
        rejects are counted drops) and the writer loop folds later."""
        if self.async_ingest is not None:
            return self.async_ingest.submit_histories(users, items, cats,
                                                      masks)
        return self.ingestor.ingest_histories(users, items, cats, masks)

    def ingest_event(self, user: Any, item: int, cat: int) -> None:
        """Real-time behavior event: incremental O(m·d) table update (the
        bucket table is a sum, so new behaviors just fold in)."""
        self.ingest_events([user], np.array([item]), np.array([cat]))

    def ingest_events(self, users: Sequence[Any], items: np.ndarray,
                      cats: np.ndarray,
                      mask: Optional[np.ndarray] = None):
        """Batched real-time events (see ``BSEIngestor.ingest_events``).
        On an async server this ENQUEUES per-user event blocks (returns the
        accepted count; rejects are counted drops)."""
        if self.async_ingest is not None:
            return self.async_ingest.submit_events(users, items, cats, mask)
        return self.ingestor.ingest_events(users, items, cats, mask)

    def evict(self, user: Any) -> bool:
        """Drop a user's table; its slot is zeroed and recycled."""
        if self.async_ingest is not None:
            return self.async_ingest.evict(user)
        return self.store.evict(user)

    # ------------------------------------------------------------------
    # fetch (delegates to the read half)
    # ------------------------------------------------------------------
    def fetch(self, user: Any) -> Optional[jax.Array]:
        return self.fetcher.fetch(user)

    def fetch_many(self, users: Sequence[Any]) -> jax.Array:
        return self.fetcher.fetch_many(users)

    def serve_candidates(self, users: Sequence[Any], q: jax.Array,
                         R: Optional[jax.Array] = None) -> jax.Array:
        return self.fetcher.serve_candidates(users, q, R=R)

    def table_bytes(self) -> int:
        """Per-user serving-state bytes. Quantized stores report the STORED
        bytes (payload + per-row scales — the fused path serves straight
        from storage); float stores keep the historical wire-cast figure
        (the paper's 8KB budget is about the fetched array)."""
        if len(self.store) == 0:
            return 0
        if self.store.quantized:
            return self.store.row_nbytes()
        return int(np.prod(self.store.row_shape)) * self.wire_dtype.itemsize

    # ------------------------------------------------------------------
    # snapshot / restore (tiered store only — the durable deployment)
    # ------------------------------------------------------------------
    def snapshot(self, dir: str) -> str:
        """Persist the FULL serving state under ``dir``: every tier of the
        store (arrays + user indices + eviction recency + tier stats) plus
        the hash family ``R``, the wire dtype and the serving stats. A
        server restored from it answers identically with no re-ingest.
        Async servers quiesce first (queue flushed, all folds committed)."""
        if not isinstance(self.store, TieredTableStore):
            raise TypeError(
                "snapshot() needs the tiered store (pass hot_capacity=/"
                "store_dir=/policy= when building the BSEServer)")
        if self.async_ingest is not None:
            self.async_ingest.flush()
        self.store.snapshot(dir)
        _atomic_npz(os.path.join(dir, "server.npz"), R=np.asarray(self.R))
        _atomic_json(os.path.join(dir, "server.json"),
                     {"wire_dtype": str(self.wire_dtype),
                      "stats": dataclasses.asdict(self.stats)})
        return dir

    @classmethod
    def restore(cls, dir: str, embed_fn: Callable, params: Any,
                engine: SDIMEngine, mesh: Any = None,
                store_dir: Optional[str] = None) -> "BSEServer":
        """Rebuild a server from ``snapshot(dir)``: tiers, indices, policy
        state, stats and ``R`` all come from disk — only the embed fn,
        params and engine (code, not state) are supplied by the caller. A
        sharded snapshot needs a ``mesh`` with the same shard count."""
        store = TieredTableStore.restore(dir, mesh=mesh, store_dir=store_dir)
        with np.load(os.path.join(dir, "server.npz")) as z:
            R = jnp.asarray(z["R"])
        with open(os.path.join(dir, "server.json")) as f:
            meta = json.load(f)
        srv = cls(embed_fn, params, engine, R=R,
                  wire_dtype=jnp.dtype(meta["wire_dtype"]), store=store)
        srv.stats = BSEStats(**meta["stats"])
        return srv
