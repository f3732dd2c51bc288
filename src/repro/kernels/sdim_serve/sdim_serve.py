"""Fused BSE-serve Pallas kernel: encode + multi-candidate query, one call.

The §4.4 serving scenario scores C candidates against ONE user's history.
Running the encode and query kernels back to back materializes the
(G·U, d) bucket table in HBM twice (encode writes it, query reads it).
This kernel keeps the table in VMEM scratch for its whole life:

    grid step l < nL : S_tile (TL, d) --hash/scatter--> += table (VMEM)
    grid step l == nL: ℓ2-normalize table; Q (C, d) --hash/gather--> out

The innermost grid dimension is sequential on TPU, so the L-tiles stream
through VMEM, the accumulator persists across steps, and the final step
flips from encode to query — the table never touches HBM at all.

Ragged L and C are padded internally (padded behaviors carry mask=0;
padded candidates are computed on zeros and sliced off).

Contract
--------
* **Block specs** — grid ``(B, L/TL + 1)``: steps ``l < nL`` stream seq
  tiles ``(1, TL, d)`` + mask ``(1, 1, TL)`` of its ``(B, 1, L)`` view; the
  final step reads the whole
  candidate block ``(1, C_pad, d)`` and writes the output ``(1, C_pad, d)``;
  R ``(m, d)`` replicated throughout.
* **VMEM residency** — the bucket table is a ``(G·U, d)`` scratch
  accumulator alive across the whole grid row: encoded into during the L
  steps, ℓ2-normalized and queried in the final step. It NEVER reaches HBM
  (running encode+query back to back would materialize it twice).
  ``block_l`` (default 128) is the knob; C is one block.
* **Ragged padding** — L padded with ``mask=0`` behaviors; C padded with
  zero candidates, sliced off the output.
* **Oracle** — ``ref.py`` (encode ∘ query composition), pinned by
  ``tests/test_kernels.py`` in interpret mode, atol ≲ 1e-5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sdim_bucket.sdim_bucket import (
    encode_tile, l2_normalize_rows, pad_axis, padded_blocks, query_tile)


def _serve_kernel(q_ref, seq_ref, mask_ref, r_ref, out_ref, table_ref,
                  *, tau: int, groups: int, n_l_steps: int):
    li = pl.program_id(1)
    r = r_ref[...].astype(jnp.float32)                       # (m, d)

    @pl.when(li == 0)
    def _init():
        table_ref[...] = jnp.zeros_like(table_ref)

    @pl.when(li < n_l_steps)
    def _encode():
        s = seq_ref[0].astype(jnp.float32)                   # (TL, d)
        table_ref[...] += encode_tile(s, mask_ref[0], r, tau=tau)

    @pl.when(li == n_l_steps)
    def _query():
        tnorm = l2_normalize_rows(table_ref[...])
        q = q_ref[0].astype(jnp.float32)                     # (C, d)
        out_ref[0] = query_tile(q, tnorm, r, tau=tau, groups=groups)


def bse_serve(
    q: jax.Array,          # (B, C, d) candidates
    seq: jax.Array,        # (B, L, d) behavior history
    mask: jax.Array,       # (B, L) 1 = valid
    R: jax.Array,          # (m, d)
    tau: int,
    *,
    block_l: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns user-interest vectors (B, C, d) fp32 == encode ∘ query."""
    B, C, d = q.shape
    _, L, _ = seq.shape
    m = R.shape[0]
    assert m % tau == 0
    G, U = m // tau, 1 << tau
    block_l, L_pad = padded_blocks(L, block_l)
    seq = pad_axis(seq, 1, L_pad)
    mask = pad_axis(mask, 1, L_pad)
    C_pad = -(-C // 8) * 8                       # sublane-align C, one tile
    q = pad_axis(q, 1, C_pad)
    n_l = L_pad // block_l

    out = pl.pallas_call(
        functools.partial(_serve_kernel, tau=tau, groups=G, n_l_steps=n_l),
        grid=(B, n_l + 1),
        in_specs=[
            pl.BlockSpec((1, C_pad, d), lambda b, l: (b, 0, 0)),
            pl.BlockSpec((1, block_l, d),
                         lambda b, l: (b, jnp.minimum(l, n_l - 1), 0)),
            pl.BlockSpec((1, 1, block_l),
                         lambda b, l: (b, 0, jnp.minimum(l, n_l - 1))),
            pl.BlockSpec((m, d), lambda b, l: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C_pad, d), lambda b, l: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C_pad, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((G * U, d), jnp.float32)],
        interpret=interpret,
    )(q, seq, mask.astype(jnp.float32)[:, None, :], R)
    return out[:, :C]
