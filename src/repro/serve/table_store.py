"""TableStore — contiguous multi-user BSE state (paper §4.4 at scale).

The BSE server's job is to absorb the hashing cost for *millions* of users,
which a ``dict[user, (G, U, d) array]`` cannot do: every ingest/fetch pays a
full dispatch for one user. The store instead keeps

  * one contiguous ``(N, G, U, d)`` device array (``data``) — N slots of
    fixed-size bucket tables, so batched ops (gather N rows, scatter-add N
    event deltas) are single XLA/Pallas dispatches;
  * a host-side user → slot index with **amortized-doubling growth** (the
    device array doubles when the free list empties, so k ingests cost O(k)
    amortized device copies) and **slot recycling on eviction** (evicted
    slots are zeroed and pushed to the free list; the next new user reuses
    them, keeping the array dense).

Storage dtype (``table_dtype`` end to end): rows live in fp32 by default,
but the store also speaks ``bf16`` and the *quantized* dtypes ``int8`` /
``fp8`` (see ``serve/quant.py``). Quantized stores keep a parallel per-row
``scales`` array — shape ``(N, G, U)``, one fp32 scale per bucket row —
quantize on ``write`` and dequantize on ``rows``, so every consumer above
this class still sees fp32 tables while HBM holds ~4x fewer bytes. The
raw-byte seam ``rows_raw``/``write_raw`` moves (payload, scales) verbatim
for tier demotion/promotion (``serve/tiered_store.py``), which must be
bit-exact. Non-quantized narrow dtypes get a SATURATING cast on ``write``
(clip to the representable range + ``n_saturated`` counter + one warning)
instead of ``astype``'s silent wrap — an fp32 outlier can no longer clip
unnoticed.

``ShardedTableStore`` is the same contract partitioned over a device mesh:
the store becomes a ``(S, C, G, U, d)`` array row-sharded over the mesh's
model axis (per the recsys layout in ``distributed/sharding.py`` — the user
tables ARE the model), a slot handle becomes a ``(shard, local)`` pair, and
every batched op stays ONE dispatch: a ``shard_map`` body in which each
shard gathers/scatters only the rows it owns (foreign rows are masked out,
then a psum assembles gathers; foreign scatters are dropped out-of-range).
Doubling growth and slot recycling work per shard, so capacity scales with
the mesh instead of with one device's HBM.

The store itself is compute-free: callers (``BSEServer``) produce rows via
``SDIMEngine.encode`` and fold events via ``SDIMEngine.update`` (sharded:
``SDIMEngine.update_sharded``); this class only owns the memory and the
index.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.mesh_ctx import MeshCtx
from repro.distributed.sharding import table_store_spec
from repro.serve.quant import (dequantize_rows, is_quantized,
                               quantize_rows_checked, resolve_table_dtype,
                               saturate_cast, _range)


# the store drops its reference the moment the scatter returns, so the buffer
# is donated: XLA writes the touched rows in place instead of copying N slots
@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_set(data, slots, rows):
    return data.at[slots].set(rows)


# quantized stores scatter payload + scales in ONE dispatch, both donated
@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_set2(data, scales, slots, rows, row_scales):
    return data.at[slots].set(rows), scales.at[slots].set(row_scales)


# non-donating twins for double-buffered readers (``donate_writes=False``):
# the async ingest runtime publishes committed snapshots that keep a
# reference to the PREVIOUS device array, so a write must copy instead of
# aliasing — lock-free readers keep gathering from the old buffer
@jax.jit
def _scatter_set_copy(data, slots, rows):
    return data.at[slots].set(rows)


@jax.jit
def _scatter_set2_copy(data, scales, slots, rows, row_scales):
    return data.at[slots].set(rows), scales.at[slots].set(row_scales)


@jax.jit
def _gather_dequant(data, scales, slots):
    return dequantize_rows(data[slots], scales[slots])


class TableStore:
    sharded = False
    # accounting seam: a serve/profiler.MemoryLedger sets both on attach;
    # event sites below report allocation deltas / traffic through it
    ledger = None
    _ledger_key = None

    def __init__(self, n_groups: int, n_buckets: int, d: int,
                 capacity: int = 64, dtype: Any = jnp.float32):
        assert capacity >= 1
        self.row_shape = (n_groups, n_buckets, d)
        self.dtype = jnp.dtype(resolve_table_dtype(dtype))
        self.quantized = is_quantized(self.dtype)
        self._check_range = (not self.quantized
                             and _range(self.dtype) is not None)
        self.data = jnp.zeros((capacity, *self.row_shape), self.dtype)
        # one fp32 scale per (G, U) bucket row (see serve/quant.py)
        self.scales = (jnp.zeros((capacity, n_groups, n_buckets), jnp.float32)
                       if self.quantized else None)
        self._slot_of: dict[Any, int] = {}
        self._user_of: dict[int, Any] = {}
        self._free = list(range(capacity - 1, -1, -1))
        self.n_grows = 0
        self.n_evictions = 0
        self.n_saturated = 0
        self.n_nonfinite = 0
        # False = copy-on-write scatters (async ingest double-buffering)
        self.donate_writes = True

    def _nbytes(self) -> int:
        """Bytes this store holds on device right now (ledger ground truth)."""
        n = self.data.nbytes
        if self.quantized:
            n += self.scales.nbytes
        return n

    def _note_saturation(self, n: int) -> None:
        if n and not self.n_saturated:
            warnings.warn(
                f"TableStore({self.dtype}): {n} value(s) outside the "
                f"storage dtype's range were saturated (see n_saturated)",
                stacklevel=3)
        self.n_saturated += n

    def _note_nonfinite(self, n: int) -> None:
        if n and not self.n_nonfinite:
            warnings.warn(
                f"TableStore({self.dtype}): {n} row(s) containing inf/NaN "
                "were zeroed on write (see n_nonfinite)", stacklevel=3)
        self.n_nonfinite += n

    # ------------------------------------------------------------------
    # index
    # ------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def __len__(self) -> int:
        return len(self._slot_of)

    def __contains__(self, user: Any) -> bool:
        return user in self._slot_of

    def users(self) -> Iterator[Any]:
        return iter(self._slot_of)

    def slot(self, user: Any) -> Optional[int]:
        return self._slot_of.get(user)

    def slots(self, users: Sequence[Any]) -> np.ndarray:
        """Slots of known users; raises KeyError naming the unknown ones."""
        missing = [u for u in users if u not in self._slot_of]
        if missing:
            raise KeyError(f"users not in table store: {missing}")
        return np.asarray([self._slot_of[u] for u in users], np.int32)

    def lookup(self, users: Sequence[Any]) -> tuple[np.ndarray, np.ndarray]:
        """Miss-tolerant ``slots``: ``(slots, present)`` where unknown users
        get slot 0 (always a valid gather index) and ``present=False`` — the
        caller masks their rows to zero (the ``fetch_many`` contract)."""
        present = np.asarray([u in self._slot_of for u in users], bool)
        slots = np.asarray([self._slot_of.get(u, 0) for u in users], np.int32)
        return slots, present

    def assign(self, users: Sequence[Any]) -> np.ndarray:
        """Slots for ``users``, allocating for unknown ones (growing the
        device array by doubling when the free list runs dry). Duplicate
        users in one call share one slot; fresh slots read all-zero."""
        need = len({u for u in users if u not in self._slot_of})
        while len(self._free) < need:
            self._grow()
        slots = []
        for u in users:
            s = self._slot_of.get(u)
            if s is None:
                s = self._free.pop()
                self._slot_of[u] = s
                self._user_of[s] = u
            slots.append(s)
        return np.asarray(slots, np.int32)

    def assign_fresh(self, users: Sequence[Any]) -> np.ndarray:
        """``assign`` for callers about to overwrite every row wholesale
        (full re-encode). Here it's an alias; the tiered store overrides it
        to skip promoting row data that would be thrown away."""
        return self.assign(users)

    def _grow(self) -> None:
        cap = self.capacity
        old = self._nbytes()
        self.data = jnp.concatenate([self.data, jnp.zeros_like(self.data)])
        if self.quantized:
            self.scales = jnp.concatenate(
                [self.scales, jnp.zeros_like(self.scales)])
        self._free[:0] = range(2 * cap - 1, cap - 1, -1)
        self.n_grows += 1
        if self.ledger is not None:
            self.ledger.add(self._ledger_key, self._nbytes() - old, "grow")

    def evict(self, user: Any) -> bool:
        """Drop a user; the zeroed slot is recycled by the next allocation."""
        return self.evict_many([user]) == 1

    def evict_many(self, users: Sequence[Any]) -> int:
        """Batched evict: all known users' slots zeroed in ONE scatter (the
        tiered store's demotion path must never pay per-user dispatches) and
        recycled. Unknown users are ignored, duplicates deduped; returns
        the evicted count."""
        known = [u for u in dict.fromkeys(users) if u in self._slot_of]
        if not known:
            return 0
        slots = self.slots(known)
        # recycled slots must read zero
        self.write(slots, jnp.zeros((len(known), *self.row_shape), self.dtype))
        for u in known:
            s = self._slot_of.pop(u)
            del self._user_of[s]
            self._free.append(s)
        self.n_evictions += len(known)
        if self.ledger is not None:
            self.ledger.count("evict", len(known))
        return len(known)

    def clear(self) -> None:
        """Invalidate everything (model push): index emptied, array zeroed,
        growth/eviction counters reset — the store is as-new."""
        self._slot_of.clear()
        self._user_of.clear()
        self._free = list(range(self.capacity - 1, -1, -1))
        self.data = jnp.zeros_like(self.data)
        if self.quantized:
            self.scales = jnp.zeros_like(self.scales)
        self.n_grows = 0
        self.n_evictions = 0
        if self.ledger is not None:   # same-shape zeroing: allocation keeps
            self.ledger.count("clear")

    # ------------------------------------------------------------------
    # rows
    # ------------------------------------------------------------------
    def rows(self, slots: Sequence[int]) -> jax.Array:
        """One gather: (B,) slots -> (B, G, U, d). Quantized stores
        dequantize in the same dispatch — callers always see fp32 rows."""
        slots = jnp.asarray(slots, jnp.int32)
        if self.quantized:
            return _gather_dequant(self.data, self.scales, slots)
        return self.data[slots]

    def row(self, user: Any) -> Optional[jax.Array]:
        s = self._slot_of.get(user)
        if s is None:
            return None
        if self.quantized:
            return dequantize_rows(self.data[s], self.scales[s])
        return self.data[s]

    def write(self, slots: Sequence[int], rows: jax.Array) -> None:
        """One scatter: overwrite (B,) slots with rows (B, G, U, d).
        Quantized stores quantize-on-write (payload + per-row scales, still
        one dispatch) with non-finite rows zeroed and counted in
        ``n_nonfinite`` (a single inf/NaN would otherwise poison the row
        with ``scale=inf``); narrow float targets take a saturating cast
        instead of a silent ``astype`` wrap (counted in ``n_saturated``)."""
        slots = jnp.asarray(slots, jnp.int32)
        if self.quantized:
            payload, row_scales, n_bad = quantize_rows_checked(
                rows, dtype=self.dtype)
            self._note_nonfinite(int(n_bad))
            scatter2 = _scatter_set2 if self.donate_writes else _scatter_set2_copy
            self.data, self.scales = scatter2(
                self.data, self.scales, slots, payload, row_scales)
            if self.ledger is not None:
                self.ledger.count("quantize", int(slots.shape[0]))
            return
        if self._check_range:
            rows, n = saturate_cast(rows, dtype=self.dtype)
            self._note_saturation(int(n))
        else:
            rows = rows.astype(self.dtype)
        scatter = _scatter_set if self.donate_writes else _scatter_set_copy
        self.data = scatter(self.data, slots, rows)

    # ------------------------------------------------------------------
    # raw-byte seam (tier demotion/promotion must be bit-exact)
    # ------------------------------------------------------------------
    def rows_raw(self, slots) -> tuple[jax.Array, Optional[jax.Array]]:
        """(B,) slots -> (stored payload (B, G, U, d) in the STORAGE dtype,
        per-row scales (B, G, U) or None) — no dequantize, no cast."""
        slots = jnp.asarray(slots, jnp.int32)
        payload = self.data[slots]
        return payload, (self.scales[slots] if self.quantized else None)

    def write_raw(self, slots, payload: jax.Array,
                  scales: Optional[jax.Array] = None) -> None:
        """Inverse of ``rows_raw``: scatter already-quantized bytes back
        verbatim (promotion path) — bit-exact, never re-quantized."""
        slots = jnp.asarray(slots, jnp.int32)
        assert payload.dtype == self.dtype, (payload.dtype, self.dtype)
        if self.quantized:
            assert scales is not None
            scatter2 = _scatter_set2 if self.donate_writes else _scatter_set2_copy
            self.data, self.scales = scatter2(
                self.data, self.scales, slots, payload,
                jnp.asarray(scales, jnp.float32))
        else:
            assert scales is None
            scatter = _scatter_set if self.donate_writes else _scatter_set_copy
            self.data = scatter(self.data, slots, payload)

    def row_nbytes(self) -> int:
        """Stored bytes per user row: payload + (quantized) its scales."""
        n = int(np.prod(self.row_shape)) * self.dtype.itemsize
        if self.quantized:
            n += int(np.prod(self.row_shape[:-1])) * 4
        return n

    # ------------------------------------------------------------------
    # serialization seam (tiered snapshot/restore)
    # ------------------------------------------------------------------
    def host_state(self) -> dict:
        """Full store state as host objects: the device array (one D2H copy)
        plus the user→slot index as a json-able list of pairs (quantized
        stores add the scales array)."""
        state = {"data": np.asarray(self.data),
                 "index": [[u, int(s)] for u, s in self._slot_of.items()]}
        if self.quantized:
            state["scales"] = np.asarray(self.scales)
        return state

    def load_host_state(self, state: dict) -> None:
        """Inverse of ``host_state``: replaces array + index wholesale. The
        free list is rebuilt as the complement of the indexed slots, so a
        restored store allocates exactly like the snapshotted one."""
        data = np.asarray(state["data"])
        assert data.shape[1:] == self.row_shape, (data.shape, self.row_shape)
        old = self._nbytes()
        self.data = jnp.asarray(data, self.dtype)
        if self.quantized:
            self.scales = jnp.asarray(np.asarray(state["scales"]),
                                      jnp.float32)
        self._slot_of = {u: int(s) for u, s in state["index"]}
        self._user_of = {s: u for u, s in self._slot_of.items()}
        self._free = [s for s in range(self.capacity - 1, -1, -1)
                      if s not in self._user_of]
        if self.ledger is not None:   # wholesale replace: shape may differ
            self.ledger.add(self._ledger_key, self._nbytes() - old, "restore")


# ---------------------------------------------------------------------------
# sharded store: (S, C, G, U, d) row-sharded over the mesh's model axis
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _sharded_ops(mesh, axis: str, rank: int = 3):
    """jitted shard_map bodies for one (mesh, axis, per-row rank); cached so
    every store on the same mesh shares compilations. ``rank`` is the ndim
    of one stored row — 3 for the (G, U, d) table payload, 2 for the (G, U)
    per-row scales of a quantized store — so the same bodies serve both
    arrays. All three are ONE dispatch each:

      * gather  — every shard reads ``locals`` from its own block, masks the
        rows it doesn't own to zero, and a psum over ``axis`` assembles the
        replicated (B, …) result (exactly one shard contributes each row;
        integer payloads are summed in int32 and cast back, exact);
      * scatter — foreign rows are routed to the out-of-range index C and
        dropped (``mode="drop"``), so each shard writes only its own rows;
      * grow    — per-shard doubling: each shard concatenates a zero block of
        its own size, (S, C, …) -> (S, 2C, …) with no cross-shard traffic.
    """
    rowspec = P(axis, *([None] * (rank + 1)))
    rep1 = P(None)
    repn = P(*([None] * (rank + 1)))

    def gather(data, shard_ids, locals_):
        def body(block, sh, lo):
            mine = sh == jax.lax.axis_index(axis)
            rows = block[0][lo]                              # (B, …)
            integer = jnp.issubdtype(rows.dtype, jnp.integer)
            if integer:                  # int8 would overflow/reject psum
                rows = rows.astype(jnp.int32)
            rows = jnp.where(mine.reshape((-1,) + (1,) * rank), rows, 0)
            out = jax.lax.psum(rows, axis)
            return out.astype(block.dtype) if integer else out

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(rowspec, rep1, rep1),
                             out_specs=repn, check_vma=False)(
            data, shard_ids, locals_)

    def scatter(data, shard_ids, locals_, rows):
        cap = data.shape[1]

        def body(block, sh, lo, rw):
            tgt = jnp.where(sh == jax.lax.axis_index(axis), lo, cap)
            return block[0].at[tgt].set(rw.astype(block.dtype),
                                        mode="drop")[None]

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(rowspec, rep1, rep1, repn),
                             out_specs=rowspec, check_vma=False)(
            data, shard_ids, locals_, rows)

    def grow(data):
        def body(block):
            return jnp.concatenate([block, jnp.zeros_like(block)], axis=1)

        return jax.shard_map(body, mesh=mesh, in_specs=(rowspec,),
                             out_specs=rowspec, check_vma=False)(data)

    # grow's output is twice its input — donation could never alias, it
    # would only emit "donated buffers were not usable" warnings. The
    # non-donating scatter twin serves ``donate_writes=False`` stores whose
    # previous buffer is still referenced by a committed reader snapshot.
    return (jax.jit(gather),
            jax.jit(scatter, donate_argnums=(0,)),
            jax.jit(scatter),
            jax.jit(grow))


class ShardedTableStore:
    """``TableStore`` partitioned by slot over a mesh axis (default: the
    model axis, matching the recsys row-sharding rule — the per-user tables
    ARE the model). Same contract, two representation changes:

      * ``data`` is ``(S, C, G, U, d)`` with shard ``k`` owning block
        ``data[k]`` (``NamedSharding`` over ``axis``); global capacity is
        ``S·C`` and grows by doubling every shard's ``C`` at once;
      * a slot handle is a ``(shard, local)`` pair — ``assign``/``slots``
        return an ``(B, 2)`` int32 array that ``rows``/``write`` and
        ``SDIMEngine.update_sharded`` consume. New users go to the shard
        with the most free slots, so occupancy stays balanced within ±1.

    Handles stay valid across growth (a shard's block only gains rows), so
    the host index never needs remapping.
    """

    sharded = True

    def __init__(self, n_groups: int, n_buckets: int, d: int, mesh,
                 capacity: int = 64, dtype: Any = jnp.float32,
                 axis: Optional[str] = None):
        assert capacity >= 1
        self.mesh_ctx = MeshCtx.wrap(mesh)
        self.axis = self.mesh_ctx.model_axis if axis is None else axis
        self.row_shape = (n_groups, n_buckets, d)
        self.dtype = jnp.dtype(resolve_table_dtype(dtype))
        self.quantized = is_quantized(self.dtype)
        self._check_range = (not self.quantized
                             and _range(self.dtype) is not None)
        S = self.n_shards
        per = max(1, -(-capacity // S))                  # ceil; ≥1 per shard
        self._sharding = NamedSharding(
            self.mesh_ctx.mesh, table_store_spec(self.axis))
        self.data = jax.device_put(
            jnp.zeros((S, per, *self.row_shape), self.dtype), self._sharding)
        (self._gather, self._scatter_donate, self._scatter_copy,
         self._grow_op) = _sharded_ops(self.mesh_ctx.mesh, self.axis)
        if self.quantized:
            self._scale_sharding = NamedSharding(
                self.mesh_ctx.mesh, P(self.axis, None, None, None))
            self.scales = jax.device_put(
                jnp.zeros((S, per, n_groups, n_buckets), jnp.float32),
                self._scale_sharding)
            (self._sgather, self._sscatter_donate, self._sscatter_copy,
             self._sgrow_op) = _sharded_ops(
                self.mesh_ctx.mesh, self.axis, rank=2)
        else:
            self.scales = None
        self._slot_of: dict[Any, tuple[int, int]] = {}
        self._user_of: dict[tuple[int, int], Any] = {}
        self._free = [list(range(per - 1, -1, -1)) for _ in range(S)]
        self.n_grows = 0
        self.n_evictions = 0
        self.n_saturated = 0
        self.n_nonfinite = 0
        self.donate_writes = True

    _note_saturation = TableStore._note_saturation
    _note_nonfinite = TableStore._note_nonfinite
    _nbytes = TableStore._nbytes
    ledger = None
    _ledger_key = None

    @property
    def _scatter(self):
        return (self._scatter_donate if self.donate_writes
                else self._scatter_copy)

    @property
    def _sscatter(self):
        return (self._sscatter_donate if self.donate_writes
                else self._sscatter_copy)

    # ------------------------------------------------------------------
    # index
    # ------------------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return self.mesh_ctx.mesh.shape[self.axis]

    @property
    def per_shard_capacity(self) -> int:
        return self.data.shape[1]

    @property
    def capacity(self) -> int:
        return self.data.shape[0] * self.data.shape[1]

    def __len__(self) -> int:
        return len(self._slot_of)

    def __contains__(self, user: Any) -> bool:
        return user in self._slot_of

    def users(self) -> Iterator[Any]:
        return iter(self._slot_of)

    def slot(self, user: Any) -> Optional[tuple[int, int]]:
        return self._slot_of.get(user)

    def shard_load(self) -> list[int]:
        """Live users per shard (balance is an invariant worth asserting)."""
        per = self.per_shard_capacity
        return [per - len(f) for f in self._free]

    def slots(self, users: Sequence[Any]) -> np.ndarray:
        """(B, 2) [shard, local] handles; KeyError names unknown users."""
        missing = [u for u in users if u not in self._slot_of]
        if missing:
            raise KeyError(f"users not in table store: {missing}")
        return np.asarray([self._slot_of[u] for u in users], np.int32)

    def lookup(self, users: Sequence[Any]) -> tuple[np.ndarray, np.ndarray]:
        """Miss-tolerant ``slots``: ``(handles, present)`` where unknown
        users get handle (0, 0) and ``present=False`` — the caller masks
        their rows to zero (the ``fetch_many`` contract)."""
        present = np.asarray([u in self._slot_of for u in users], bool)
        slots = np.asarray([self._slot_of.get(u, (0, 0)) for u in users],
                           np.int32)
        return slots, present

    def assign(self, users: Sequence[Any]) -> np.ndarray:
        """(B, 2) handles for ``users``, allocating unknown ones on the
        least-loaded shard (growing every shard by doubling when all free
        lists run dry). Duplicate users in one call share one handle; fresh
        slots read all-zero."""
        for u in users:
            if u in self._slot_of:
                continue
            k = max(range(self.n_shards), key=lambda i: len(self._free[i]))
            if not self._free[k]:
                self.grow()
            s = (k, self._free[k].pop())
            self._slot_of[u] = s
            self._user_of[s] = u
        return np.asarray([self._slot_of[u] for u in users], np.int32)

    def assign_fresh(self, users: Sequence[Any]) -> np.ndarray:
        """``assign`` for full-overwrite callers (see ``TableStore``)."""
        return self.assign(users)

    def grow(self) -> None:
        per = self.per_shard_capacity
        old = self._nbytes()
        self.data = self._grow_op(self.data)
        if self.quantized:
            self.scales = self._sgrow_op(self.scales)
        for f in self._free:
            f[:0] = range(2 * per - 1, per - 1, -1)
        self.n_grows += 1
        if self.ledger is not None:
            self.ledger.add(self._ledger_key, self._nbytes() - old, "grow")

    def evict(self, user: Any) -> bool:
        """Drop a user; the zeroed slot is recycled by the next allocation."""
        return self.evict_many([user]) == 1

    def evict_many(self, users: Sequence[Any]) -> int:
        """Batched evict: all known users' slots zeroed in ONE sharded
        scatter and recycled. Unknown users are ignored, duplicates
        deduped; returns the evicted count."""
        known = [u for u in dict.fromkeys(users) if u in self._slot_of]
        if not known:
            return 0
        self.write(self.slots(known),
                   jnp.zeros((len(known), *self.row_shape), self.dtype))
        for u in known:
            s = self._slot_of.pop(u)
            del self._user_of[s]
            self._free[s[0]].append(s[1])
        self.n_evictions += len(known)
        if self.ledger is not None:
            self.ledger.count("evict", len(known))
        return len(known)

    def clear(self) -> None:
        """Invalidate everything (model push): index emptied, array zeroed,
        growth/eviction counters reset — the store is as-new."""
        per = self.per_shard_capacity
        self._slot_of.clear()
        self._user_of.clear()
        self._free = [list(range(per - 1, -1, -1))
                      for _ in range(self.n_shards)]
        self.data = jax.device_put(jnp.zeros_like(self.data), self._sharding)
        if self.quantized:
            self.scales = jax.device_put(jnp.zeros_like(self.scales),
                                         self._scale_sharding)
        self.n_grows = 0
        self.n_evictions = 0
        if self.ledger is not None:   # same-shape zeroing: allocation keeps
            self.ledger.count("clear")

    # ------------------------------------------------------------------
    # rows
    # ------------------------------------------------------------------
    def rows(self, slots) -> jax.Array:
        """One sharded gather per array: (B, 2) handles -> replicated
        (B, G, U, d); quantized stores gather payload + scales and
        dequantize, so callers always see fp32 rows."""
        slots = jnp.asarray(slots, jnp.int32)
        payload = self._gather(self.data, slots[:, 0], slots[:, 1])
        if self.quantized:
            scales = self._sgather(self.scales, slots[:, 0], slots[:, 1])
            return dequantize_rows(payload, scales)
        return payload

    def row(self, user: Any) -> Optional[jax.Array]:
        s = self._slot_of.get(user)
        return None if s is None else self.rows(np.asarray([s], np.int32))[0]

    def write(self, slots, rows: jax.Array) -> None:
        """One sharded scatter per array: overwrite (B, 2) handles with
        (B, G, U, d) — quantize-on-write / saturating cast as TableStore."""
        slots = jnp.asarray(slots, jnp.int32)
        if self.quantized:
            payload, row_scales, n_bad = quantize_rows_checked(
                rows, dtype=self.dtype)
            self._note_nonfinite(int(n_bad))
            self.data = self._scatter(self.data, slots[:, 0], slots[:, 1],
                                      payload)
            self.scales = self._sscatter(self.scales, slots[:, 0],
                                         slots[:, 1], row_scales)
            if self.ledger is not None:
                self.ledger.count("quantize", int(slots.shape[0]))
            return
        if self._check_range:
            rows, n = saturate_cast(rows, dtype=self.dtype)
            self._note_saturation(int(n))
        else:
            rows = rows.astype(self.dtype)
        self.data = self._scatter(self.data, slots[:, 0], slots[:, 1], rows)

    # ------------------------------------------------------------------
    # raw-byte seam (tier demotion/promotion must be bit-exact)
    # ------------------------------------------------------------------
    def rows_raw(self, slots) -> tuple[jax.Array, Optional[jax.Array]]:
        """(B, 2) handles -> (stored payload in the STORAGE dtype, per-row
        scales or None) — the sharded twin of ``TableStore.rows_raw``."""
        slots = jnp.asarray(slots, jnp.int32)
        payload = self._gather(self.data, slots[:, 0], slots[:, 1])
        scales = (self._sgather(self.scales, slots[:, 0], slots[:, 1])
                  if self.quantized else None)
        return payload, scales

    def write_raw(self, slots, payload: jax.Array,
                  scales: Optional[jax.Array] = None) -> None:
        slots = jnp.asarray(slots, jnp.int32)
        assert payload.dtype == self.dtype, (payload.dtype, self.dtype)
        self.data = self._scatter(self.data, slots[:, 0], slots[:, 1],
                                  payload)
        if self.quantized:
            assert scales is not None
            self.scales = self._sscatter(self.scales, slots[:, 0],
                                         slots[:, 1],
                                         jnp.asarray(scales, jnp.float32))
        else:
            assert scales is None

    row_nbytes = TableStore.row_nbytes

    # ------------------------------------------------------------------
    # serialization seam (tiered snapshot/restore)
    # ------------------------------------------------------------------
    def host_state(self) -> dict:
        """Full store state as host objects: the (S, C, G, U, d) array (one
        D2H copy) plus the user→(shard, local) index as json-able pairs
        (quantized stores add the (S, C, G, U) scales)."""
        state = {"data": np.asarray(self.data),
                 "index": [[u, [int(s[0]), int(s[1])]]
                           for u, s in self._slot_of.items()]}
        if self.quantized:
            state["scales"] = np.asarray(self.scales)
        return state

    def load_host_state(self, state: dict) -> None:
        """Inverse of ``host_state``. The array must match this store's
        shard count; per-shard free lists are rebuilt as the complement of
        the indexed handles."""
        data = np.asarray(state["data"])
        assert data.shape[0] == self.n_shards, (data.shape, self.n_shards)
        assert data.shape[2:] == self.row_shape, (data.shape, self.row_shape)
        old = self._nbytes()
        self.data = jax.device_put(jnp.asarray(data, self.dtype),
                                   self._sharding)
        if self.quantized:
            self.scales = jax.device_put(
                jnp.asarray(np.asarray(state["scales"]), jnp.float32),
                self._scale_sharding)
        self._slot_of = {u: (int(s[0]), int(s[1])) for u, s in state["index"]}
        self._user_of = {s: u for u, s in self._slot_of.items()}
        per = self.per_shard_capacity
        self._free = [[l for l in range(per - 1, -1, -1)
                       if (k, l) not in self._user_of]
                      for k in range(self.n_shards)]
        if self.ledger is not None:   # wholesale replace: shape may differ
            self.ledger.add(self._ledger_key, self._nbytes() - old, "restore")
