"""SDIMEngine — the single backend-dispatching SDIM compute layer.

Every consumer of SDIM compute in this repo (the ``InterestModule`` inside
CTR models, the BSE/CTR servers, the serving launcher, and the table-5
benchmark) reaches hash → bucket → gather through this one object, so a
config flag flips the whole stack between the reference XLA formulation and
the fused Pallas kernels.

Paper-equation map per backend
------------------------------

================  =====================================================
operation         what it computes (paper §3.3 / §4)
================  =====================================================
``encode``        Eq. 8/11: SimHash signatures of every behavior, then
                  the per-group signature-bucket sums T[g,u] = Σ 1[sig=u]·s
                  (the BSE table, §4.4). ``xla``: ``simhash.signatures`` +
                  ``sdim.bucket_table`` (one-hot einsum). ``pallas``: the
                  fused ``sdim_bucket`` kernel — projection, sign/pack and
                  bucket scatter in one VMEM pass, the L×m code matrix
                  never reaches HBM.
``query``         Eq. 9/11/12: hash the candidate, read its own bucket in
                  every group, ℓ2-normalize, mean over groups. ``xla``:
                  ``sdim.fused_query`` (single flat matmul). ``pallas``:
                  the ``sdim_query`` kernel (same trick on the MXU).
``attend``        Eq. 12 end-to-end: ``query(q, encode(seq, mask))`` — the
                  estimator Attn(q; S) used in the training graph.
``serve``         §4.4 online path: C candidates vs one user in a single
                  call. ``xla``: encode + query composed under one jit.
                  ``pallas``: the fused ``sdim_serve`` kernel, where the
                  bucket table lives only in VMEM scratch (never
                  materialized in HBM).
``serve_fused``   §4.4 decoupled serving at multi-user scale: gather a
                  batch of precomputed rows out of the (N, G, U, d) table
                  store by slot, dequantize (per-row scales for int8/fp8
                  stores) and score candidates — one dispatch, no
                  materialized intermediate rows. ``xla``: gather +
                  ``sdim.fused_query`` (``kernels/sdim_fused_serve/ref``).
                  ``pallas``: the ``sdim_fused_serve`` megakernel — the
                  slot gather is the scalar-prefetch block index map, the
                  dequant happens in VMEM, and the store blocks stream
                  double-buffered across the user grid.
``update``        §4.4 real-time ingest at multi-user scale: scatter-add a
                  batch of event-behavior deltas into selected rows of a
                  contiguous (N, G, U, d) table store. ``xla``: bucket the
                  events, then one O(B)-row scatter-add (the segment_sum
                  oracle lives in ``kernels/sdim_update/ref.py``).
                  ``pallas``: the fused ``sdim_update`` kernel — hash,
                  bucket and slot gather in one VMEM pass (scalar-prefetch
                  block index map over the slots).
================  =====================================================

Backends: ``xla`` | ``pallas`` | ``auto`` (Pallas on TPU, XLA elsewhere).
On non-TPU hosts an explicit ``backend="pallas"`` runs the kernels in
interpret mode (bit-close to XLA, atol ≲1e-5) so the kernel path is
testable anywhere. On a TPU the kernels always compile: there is no
interpret mode and no fallback to XLA, so a kernel the chip's compiler
refuses fails the call.

Hash families: ``dense`` (plain GEMM SimHash, paper-faithful) | ``srht``
(subsampled randomized Hadamard transform, the paper's "Approximating
Random Projection" citation). The SRHT family is densified once at
construction (``SRHTHashes.dense_matrix``) so both backends consume the
same (m, d) projection operand and agree bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import sdim, simhash

BACKENDS = ("auto", "xla", "pallas")
FAMILIES = ("dense", "srht")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    m: int = 48               # hash functions (paper: 48 online)
    tau: int = 3              # signature width (paper: 3 online)
    d: int = 128              # behavior embedding dim
    family: str = "dense"     # "dense" | "srht"
    backend: str = "auto"     # "auto" | "xla" | "pallas"
    hash_seed: int = 1234
    block_l: int = 128        # Pallas L-tile
    block_c: int = 128        # Pallas C-tile
    interpret: Optional[bool] = None  # None: interpret iff not on TPU

    @property
    def n_groups(self) -> int:
        assert self.m % self.tau == 0, (self.m, self.tau)
        return self.m // self.tau

    @property
    def n_buckets(self) -> int:
        return 1 << self.tau


def resolve_backend(backend: str) -> str:
    assert backend in BACKENDS, backend
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def make_hash_family(cfg: EngineConfig) -> jax.Array:
    """The (m, d) projection operand for ``cfg.family``, from ``hash_seed``."""
    key = jax.random.PRNGKey(cfg.hash_seed)
    if cfg.family == "dense":
        return simhash.make_hashes(key, cfg.m, cfg.d)
    if cfg.family == "srht":
        return simhash.srht_hashes(key, cfg.m, cfg.d).dense_matrix()
    raise ValueError(f"unknown hash family: {cfg.family}")


# ---------------------------------------------------------------------------
# jitted dispatch bodies (module-level so jax.jit caches across engines)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("tau", "backend", "block_l", "interpret"))
def _encode(seq, mask, R, *, tau, backend, block_l, interpret):
    if backend == "xla":
        sig = simhash.signatures(seq, R, tau)
        return sdim.bucket_table(seq, sig, mask, 1 << tau)
    from repro.kernels.sdim_bucket.sdim_bucket import bse_encode

    if mask is None:
        mask = jnp.ones(seq.shape[:2], seq.dtype)
    return bse_encode(seq, mask, R, tau, block_l=block_l, interpret=interpret)


@partial(jax.jit, static_argnames=("tau", "backend", "block_c", "interpret"))
def _query(q, table, R, *, tau, backend, block_c, interpret):
    if backend == "xla":
        sig_q = simhash.signatures(q, R, tau)
        return sdim.fused_query(table, sig_q)
    from repro.kernels.sdim_query.sdim_query import sdim_query

    single = q.ndim == 2
    qc = q[:, None, :] if single else q
    out = sdim_query(qc, table, R, tau, block_c=block_c, interpret=interpret)
    return out[:, 0] if single else out


def _update_impl(store, slots, events, mask, R, *, tau, backend, block_l,
                 interpret):
    if mask is None:
        mask = jnp.ones(events.shape[:2], events.dtype)
    if backend == "xla":
        sig = simhash.signatures(events, R, tau)
        deltas = sdim.bucket_table(events, sig, mask, 1 << tau)  # (B, G, U, d)
        # scatter-add (duplicate slots accumulate): touches O(B) rows, unlike
        # the segment_sum oracle in kernels/sdim_update/ref.py which builds a
        # store-sized dense intermediate — O(N) per call
        return store.astype(jnp.float32).at[slots].add(deltas)
    from repro.kernels.sdim_update.sdim_update import sdim_update

    return sdim_update(store, slots, events, mask, R, tau,
                       block_e=block_l, interpret=interpret)


_STATIC_UPDATE = ("tau", "backend", "block_l", "interpret")
_update = jax.jit(_update_impl, static_argnames=_STATIC_UPDATE)
# owners that immediately replace their store reference (BSEServer) donate it,
# so XLA updates the (N, G, U, d) buffer in place instead of copying it
_update_donated = jax.jit(_update_impl, static_argnames=_STATIC_UPDATE,
                          donate_argnums=(0,))


# ---------------------------------------------------------------------------
# sharded dispatch bodies (shard_map over a mesh axis; cached per mesh)
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def _sharded_update_fn(mesh, axis, tau, backend, block_l, interpret, donate):
    """One dispatch folding a replicated event batch into a row-sharded
    (S, C, G, U, d) store: every shard hashes the whole batch (events are
    O(B·E·d), tiny next to the store) but applies only the rows it owns —
    foreign rows get their mask zeroed and their slot clamped to 0, so both
    the XLA scatter-add and the Pallas ``sdim_update`` kernel write
    ``store[0] + 0`` for them, a no-op that composes with real slot-0 runs."""
    from jax.sharding import PartitionSpec as P

    def fn(store, shard_ids, locals_, events, mask, R):
        def body(block, sh, lo, ev, mk, r):
            mine = sh == jax.lax.axis_index(axis)
            new = _update_impl(
                block[0], jnp.where(mine, lo, 0), ev,
                mk * mine[:, None].astype(mk.dtype), r,
                tau=tau, backend=backend, block_l=block_l,
                interpret=interpret)
            return new[None]

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, None, None, None, None), P(None), P(None),
                      P(None, None, None), P(None, None), P(None, None)),
            out_specs=P(axis, None, None, None, None),
            check_vma=False)(store, shard_ids, locals_, events, mask, R)

    return jax.jit(fn, donate_argnums=(0,) if donate else ())


@lru_cache(maxsize=None)
def _sharded_serve_fn(mesh, axis, tau, backend, block_l, interpret):
    """Batch-parallel fused serve: the (B, …) request batch is sharded over
    ``axis`` (callers pad B to a multiple of the axis size); each shard runs
    the whole encode+query pipeline on its B/S users independently."""
    from jax.sharding import PartitionSpec as P

    def fn(q, seq, mask, R):
        def body(q, seq, mask, r):
            return _serve(q, seq, mask, r, tau=tau, backend=backend,
                          block_l=block_l, interpret=interpret)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None),
                      P(axis, None), P(None, None)),
            out_specs=P(axis, None), check_vma=False)(q, seq, mask, R)

    return jax.jit(fn)


def _serve_fused_impl(store, slots, present, q, scales, R, *, tau, backend,
                      block_c, interpret):
    if backend == "xla":
        from repro.kernels.sdim_fused_serve.ref import sdim_fused_serve_ref

        return sdim_fused_serve_ref(store, slots, q, R, tau,
                                    scales=scales, present=present)
    from repro.kernels.sdim_fused_serve.sdim_fused_serve import \
        sdim_fused_serve

    return sdim_fused_serve(store, slots, q, R, tau, scales=scales,
                            present=present, block_c=block_c,
                            interpret=interpret)


_serve_fused = jax.jit(_serve_fused_impl, static_argnames=(
    "tau", "backend", "block_c", "interpret"))


@lru_cache(maxsize=None)
def _sharded_fused_serve_fn(mesh, axis, tau, backend, block_c, interpret,
                            quantized):
    """One dispatch serving a replicated candidate batch off a row-sharded
    (S, C, G, U, d) store: every shard runs the fused megakernel over the
    whole batch but owns only its rows — foreign users get their slot
    clamped to 0 and ``present`` zeroed (the kernel's output mask), so the
    psum reassembles exactly one real interest vector per user."""
    from jax.sharding import PartitionSpec as P

    rep3 = P(None, None, None)

    def run_shard(block, sc, sh, lo, pr, q, r):
        mine = sh == jax.lax.axis_index(axis)
        out = _serve_fused_impl(
            block[0], jnp.where(mine, lo, 0), jnp.logical_and(pr, mine),
            q, sc, r, tau=tau, backend=backend, block_c=block_c,
            interpret=interpret)
        return jax.lax.psum(out, axis)

    if quantized:
        def fn(store, scales, shard_ids, locals_, present, q, R):
            def body(block, scb, sh, lo, pr, q, r):
                return run_shard(block, scb[0], sh, lo, pr, q, r)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis, None, None, None, None),
                          P(axis, None, None, None),
                          P(None), P(None), P(None), rep3, P(None, None)),
                out_specs=rep3, check_vma=False)(
                store, scales, shard_ids, locals_, present, q, R)
    else:
        def fn(store, shard_ids, locals_, present, q, R):
            def body(block, sh, lo, pr, q, r):
                return run_shard(block, None, sh, lo, pr, q, r)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis, None, None, None, None),
                          P(None), P(None), P(None), rep3, P(None, None)),
                out_specs=rep3, check_vma=False)(
                store, shard_ids, locals_, present, q, R)

    return jax.jit(fn)


@partial(jax.jit, static_argnames=("tau", "backend", "block_l", "interpret"))
def _serve(q, seq, mask, R, *, tau, backend, block_l, interpret):
    if backend == "xla":
        return sdim.sdim_attention(
            q.astype(jnp.float32), seq.astype(jnp.float32), mask, R, tau
        )
    from repro.kernels.sdim_serve.sdim_serve import bse_serve

    if mask is None:
        mask = jnp.ones(seq.shape[:2], seq.dtype)
    return bse_serve(q, seq, mask, R, tau, block_l=block_l, interpret=interpret)


class SDIMEngine:
    """Owns the hash family and dispatches encode/query/attend/serve.

    ``R`` may be overridden per call (the CTR models keep it in the params
    tree as a checkpointed buffer); when omitted the engine's own family —
    created from ``cfg.hash_seed`` — is used, so standalone servers need no
    params plumbing.
    """

    def __init__(self, cfg: EngineConfig, R: Optional[jax.Array] = None):
        assert cfg.family in FAMILIES, cfg.family
        cfg.n_groups  # fail fast on m % tau != 0 (not at first call)
        self.cfg = cfg
        self.backend = resolve_backend(cfg.backend)
        self.R = make_hash_family(cfg) if R is None else R
        assert self.R.shape == (cfg.m, cfg.d), (self.R.shape, cfg)
        # measurement seam: a serve/profiler.KernelProfiler attaches here
        # (profiler.attach(engine)); None costs one branch per dispatch.
        self.profiler = None

    @property
    def interpret(self) -> bool:
        """Interpret mode is for hosts without a TPU. On a TPU the kernels
        always compile: asking for interpret mode there is an error, so a
        chip run can never be a silent interpreter run."""
        on_tpu = jax.default_backend() == "tpu"
        if self.cfg.interpret and on_tpu:
            raise ValueError("interpret=True on a TPU: the Pallas kernels "
                             "must compile for the chip")
        return not on_tpu if self.cfg.interpret is None else self.cfg.interpret

    def _R(self, R: Optional[jax.Array]) -> jax.Array:
        return self.R if R is None else R

    def _dispatch(self, kernel: str, fn, args: tuple, kwargs: dict):
        """Every jitted dispatch funnels through here so an attached
        ``KernelProfiler`` sees all of them (cost capture, block-until-ready
        timing, warmup exclusion); without one it is a plain call."""
        if self.profiler is None:
            return fn(*args, **kwargs)
        return self.profiler.profile(kernel, fn, args, kwargs)

    # ------------------------------------------------------------------
    def encode(self, seq: jax.Array, mask: Optional[jax.Array] = None,
               R: Optional[jax.Array] = None) -> jax.Array:
        """Behaviors (B, L, d) [+ mask (B, L)] -> bucket table (B, G, U, d)."""
        return self._dispatch(
            "encode", _encode, (seq, mask, self._R(R)),
            dict(tau=self.cfg.tau, backend=self.backend,
                 block_l=self.cfg.block_l, interpret=self.interpret))

    def query(self, q: jax.Array, table: jax.Array,
              R: Optional[jax.Array] = None) -> jax.Array:
        """Candidates (B, d)/(B, C, d) x table (B, G, U, d) -> interest with
        q's leading shape + (d,)."""
        return self._dispatch(
            "query", _query, (q, table, self._R(R)),
            dict(tau=self.cfg.tau, backend=self.backend,
                 block_c=self.cfg.block_c, interpret=self.interpret))

    def attend(self, q: jax.Array, seq: jax.Array,
               mask: Optional[jax.Array] = None,
               R: Optional[jax.Array] = None) -> jax.Array:
        """End-to-end SDIM attention (training graph): query ∘ encode."""
        table = self.encode(seq, mask, R)
        return self.query(q, table, R).astype(seq.dtype)

    def serve(self, q: jax.Array, seq: jax.Array,
              mask: Optional[jax.Array] = None,
              R: Optional[jax.Array] = None) -> jax.Array:
        """Fused §4.4 serving path: (B, C, d) candidates vs (B, L, d)
        history in ONE call — on Pallas the bucket table never leaves VMEM."""
        return self._dispatch(
            "serve", _serve, (q, seq, mask, self._R(R)),
            dict(tau=self.cfg.tau, backend=self.backend,
                 block_l=self.cfg.block_l,
                 interpret=self.interpret)).astype(seq.dtype)

    def serve_fused(self, store: jax.Array, slots, q: jax.Array,
                    present: Optional[jax.Array] = None,
                    scales: Optional[jax.Array] = None,
                    R: Optional[jax.Array] = None) -> jax.Array:
        """§4.4 decoupled serving in ONE dispatch: gather rows ``slots``
        (B,) out of the (N, G, U, d) table store, dequantize (``scales``
        per-row for int8/fp8 stores), and score candidates (B, C, d) —
        no materialized (B, G, U, d) intermediate. ``present`` (B,) zeroes
        absent users' interest (the ``fetch_many`` miss contract). Returns
        (B, C, d) fp32."""
        return self._dispatch(
            "serve_fused", _serve_fused,
            (store, jnp.asarray(slots, jnp.int32),
             None if present is None else jnp.asarray(present),
             q, scales, self._R(R)),
            dict(tau=self.cfg.tau, backend=self.backend,
                 block_c=self.cfg.block_c, interpret=self.interpret))

    def serve_fused_sharded(self, store: jax.Array, slots, q: jax.Array,
                            present: Optional[jax.Array] = None,
                            scales: Optional[jax.Array] = None,
                            R: Optional[jax.Array] = None, *,
                            mesh) -> jax.Array:
        """``serve_fused`` against a row-sharded (S, C, G, U, d) store:
        ``slots`` is the (B, 2) [shard, local] handle array a
        ``ShardedTableStore`` hands out; each shard serves the rows it owns
        and a psum reassembles the batch. Semantics match ``serve_fused``."""
        from repro.distributed.mesh_ctx import MeshCtx

        ctx = MeshCtx.wrap(mesh)
        slots = jnp.asarray(slots, jnp.int32)
        if present is None:
            present = jnp.ones((q.shape[0],), bool)
        present = jnp.asarray(present, bool)
        fn = _sharded_fused_serve_fn(
            ctx.mesh, ctx.model_axis, self.cfg.tau, self.backend,
            self.cfg.block_c, self.interpret, scales is not None)
        args = (store,) if scales is None else (store, scales)
        return self._dispatch(
            "serve_fused_sharded", fn,
            (*args, slots[:, 0], slots[:, 1], present, q, self._R(R)), {})

    def update(self, store: jax.Array, slots, events: jax.Array,
               mask: Optional[jax.Array] = None,
               R: Optional[jax.Array] = None, *,
               donate: bool = False) -> jax.Array:
        """Batched real-time ingest: fold events (B, E, d) [+ mask (B, E)]
        into rows ``slots`` (B,) of the table store (N, G, U, d) — one
        dispatch for the whole batch; duplicate slots accumulate. Returns
        the updated store (fp32; the bucket table is a sum, Eq. 8).
        ``donate=True`` hands the store buffer to XLA for in-place update —
        only safe when the caller drops its reference (INVALIDATES it)."""
        fn = _update_donated if donate else _update
        return self._dispatch(
            "update", fn,
            (store, jnp.asarray(slots, jnp.int32), events, mask, self._R(R)),
            dict(tau=self.cfg.tau, backend=self.backend,
                 block_l=self.cfg.block_l, interpret=self.interpret))

    # ------------------------------------------------------------------
    # sharded entry points (ShardedTableStore / device-mesh serving)
    # ------------------------------------------------------------------
    def update_sharded(self, store: jax.Array, slots, events: jax.Array,
                       mask: Optional[jax.Array] = None,
                       R: Optional[jax.Array] = None, *, mesh,
                       donate: bool = False) -> jax.Array:
        """``update`` against a row-sharded (S, C, G, U, d) store: one
        ``shard_map`` dispatch in which each shard folds exactly the rows it
        owns. ``slots`` is the (B, 2) [shard, local] handle array a
        ``ShardedTableStore`` hands out; ``mesh`` a Mesh/MeshCtx whose model
        axis the store is sharded over. Semantics (duplicate accumulation,
        fp32 sums, donation) match ``update``."""
        from repro.distributed.mesh_ctx import MeshCtx

        ctx = MeshCtx.wrap(mesh)
        if mask is None:
            mask = jnp.ones(events.shape[:2], events.dtype)
        slots = jnp.asarray(slots, jnp.int32)
        fn = _sharded_update_fn(ctx.mesh, ctx.model_axis, self.cfg.tau,
                                self.backend, self.cfg.block_l,
                                self.interpret, donate)
        return self._dispatch(
            "update_sharded", fn,
            (store, slots[:, 0], slots[:, 1], events, mask, self._R(R)), {})

    def serve_sharded(self, q: jax.Array, seq: jax.Array,
                      mask: Optional[jax.Array] = None,
                      R: Optional[jax.Array] = None, *, mesh) -> jax.Array:
        """``serve`` with the request batch sharded over the mesh's model
        axis: B users' fused encode+query run S-way parallel (B is padded to
        a multiple of S internally; padded rows are sliced off)."""
        from repro.distributed.mesh_ctx import MeshCtx

        ctx = MeshCtx.wrap(mesh)
        S = ctx.mesh.shape[ctx.model_axis]
        B = q.shape[0]
        pad = -B % S
        if mask is None:
            mask = jnp.ones(seq.shape[:2], seq.dtype)
        if pad:
            zeros = lambda x: jnp.zeros((pad, *x.shape[1:]), x.dtype)
            q = jnp.concatenate([q, zeros(q)])
            seq = jnp.concatenate([seq, zeros(seq)])
            mask = jnp.concatenate([mask, zeros(mask)])
        fn = _sharded_serve_fn(ctx.mesh, ctx.model_axis, self.cfg.tau,
                               self.backend, self.cfg.block_l, self.interpret)
        out = self._dispatch("serve_sharded", fn,
                             (q, seq, mask, self._R(R)), {})
        return out[:B].astype(seq.dtype)


def engine_from_interest(icfg, d: Optional[int] = None) -> SDIMEngine:
    """Build an engine from an ``InterestConfig``-shaped object (m, tau, d,
    hash_seed and, when present, backend/family/use_pallas plus the kernel
    knobs block_l/block_c/interpret — all threaded through, so an interest
    config can pin tile sizes or force interpret mode end to end)."""
    backend = getattr(icfg, "backend", "auto")
    if getattr(icfg, "use_pallas", False):
        backend = "pallas"
    defaults = EngineConfig()
    return SDIMEngine(EngineConfig(
        m=icfg.m, tau=icfg.tau, d=icfg.d if d is None else d,
        family=getattr(icfg, "family", "dense"), backend=backend,
        hash_seed=icfg.hash_seed,
        block_l=getattr(icfg, "block_l", defaults.block_l),
        block_c=getattr(icfg, "block_c", defaults.block_c),
        interpret=getattr(icfg, "interpret", defaults.interpret),
    ))
