"""Fused BSE-update Pallas kernel: batched event ingest with slot scatter.

The §4.4 real-time flow at multi-user scale: a batch of behavior events —
one short (E, d) event block per request, each aimed at a *slot* of the
contiguous ``(N, G·U, d)`` table store — folds into the store in a single
kernel launch instead of one dispatch per user:

    events are sorted by slot (host-free: one argsort) so duplicate slots
    become consecutive, then per (batch-row, E-tile) grid step:

    slot change?  --DMA-->  acc (VMEM) := store[slot]          (scalar-prefetch
                                                                gather read)
    E_tile (TE, d) --hash/bucket (encode_tile)--> acc += delta
    out[b] := acc                                              (running total)

The store row is fetched by a block index map driven by the scalar-prefetched
slot vector (``PrefetchScalarGridSpec``), so hash → bucket → scatter-source
all happen in one VMEM pass; the event code matrix never reaches HBM.

Because row ``b`` of the kernel output carries the *running* total for its
slot, only the LAST occurrence of each slot holds the full sum; the wrapper
routes earlier duplicates to a trash row and writes the rest back with one
XLA scatter. Validated on CPU via ``interpret=True`` against ``ref.py``'s
segment-sum oracle.

Contract
--------
* **Block specs** — ``PrefetchScalarGridSpec`` with the sorted slot vector
  scalar-prefetched; grid ``(B, E/TE)``; per step: store row ``(1, G·U, d)``
  selected by ``slots[b]`` (the gather IS the block index map), events
  ``(1, TE, d)``, mask ``(1, 1, TE)`` of its ``(B, 1, E)`` view, R ``(m, d)``;
  output row ``(1, G·U, d)``.
* **VMEM residency** — a ``(G·U, d)`` running-total scratch accumulator,
  re-seeded from the store row whenever the (sorted) slot changes and
  carried across duplicate-slot rows. ``block_e`` (= engine ``block_l``)
  is the knob.
* **Ragged padding** — E padded to whole blocks with ``mask=0`` events
  (zero deltas). Zero-masked rows aimed at a clamped slot are exact no-ops
  (``store[slot] + 0`` written back), which is what ``update_sharded``
  relies on for foreign-shard rows.
* **Oracle** — ``ref.py`` (bucket + ``segment_sum``, O(N) dense
  intermediate), pinned by ``tests/test_table_store.py`` kernel-parity
  tests in interpret mode, atol ≲ 1e-4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sdim_bucket.sdim_bucket import (
    encode_tile, pad_axis, padded_blocks)


def _update_kernel(slots_ref, store_ref, ev_ref, mask_ref, r_ref, out_ref,
                   acc_ref, *, tau: int):
    b = pl.program_id(0)
    e = pl.program_id(1)
    slot = slots_ref[b]
    prev = slots_ref[jnp.maximum(b - 1, 0)]
    fresh = jnp.logical_or(b == 0, slot != prev)

    # new slot: seed the accumulator from the store row; duplicate slots are
    # consecutive (sorted), so otherwise the running total simply carries
    @pl.when(jnp.logical_and(e == 0, fresh))
    def _load():
        acc_ref[...] = store_ref[0].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)                       # (m, d)
    s = ev_ref[0].astype(jnp.float32)                        # (TE, d)
    acc_ref[...] += encode_tile(s, mask_ref[0], r, tau=tau)
    out_ref[0] = acc_ref[...]


def sdim_update(
    store: jax.Array,      # (N, G, U, d) fp32 table store
    slots: jax.Array,      # (B,) int32 in [0, N); duplicates accumulate
    events: jax.Array,     # (B, E, d) event-behavior embeddings
    mask: jax.Array,       # (B, E) 1 = valid
    R: jax.Array,          # (m, d)
    tau: int,
    *,
    block_e: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns the updated (N, G, U, d) store (fp32)."""
    N, G, U, d = store.shape
    B, E, _ = events.shape
    m = R.shape[0]
    assert m % tau == 0 and G == m // tau and U == 1 << tau, (store.shape, m, tau)
    slots = slots.astype(jnp.int32)
    order = jnp.argsort(slots)             # duplicates made consecutive so the
    slots_s = slots[order]                 # VMEM accumulator can carry the sum
    events = events[order]
    mask = mask[order]
    block_e, E_pad = padded_blocks(E, block_e)
    events = pad_axis(events, 1, E_pad)
    mask = pad_axis(mask, 1, E_pad)
    store2d = store.reshape(N, G * U, d).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, E_pad // block_e),
        in_specs=[
            pl.BlockSpec((1, G * U, d), lambda b, e, slots: (slots[b], 0, 0)),
            pl.BlockSpec((1, block_e, d), lambda b, e, slots: (b, e, 0)),
            pl.BlockSpec((1, 1, block_e), lambda b, e, slots: (b, 0, e)),
            pl.BlockSpec((m, d), lambda b, e, slots: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G * U, d), lambda b, e, slots: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G * U, d), jnp.float32)],
    )
    rows = pl.pallas_call(
        functools.partial(_update_kernel, tau=tau),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G * U, d), jnp.float32),
        interpret=interpret,
    )(slots_s, store2d, events, mask.astype(jnp.float32)[:, None, :], R)

    # row b holds the RUNNING total of its slot: only the last occurrence has
    # the full sum, so earlier duplicates are routed to a trash row
    is_last = jnp.concatenate(
        [slots_s[1:] != slots_s[:-1], jnp.ones((1,), bool)])
    target = jnp.where(is_last, slots_s, N)
    padded = jnp.concatenate(
        [store2d, jnp.zeros((1, G * U, d), store2d.dtype)])
    return padded.at[target].set(rows)[:N].reshape(N, G, U, d)
