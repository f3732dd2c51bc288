"""Fused single-pass serve megakernel: slot gather + dequant + bucket query.

The §4.4 decoupled serving path reads precomputed BSE state instead of
re-encoding history. Before this kernel that was TWO dispatches with a
materialized intermediate: ``fetch_many`` gathers (B, G, U, d) user rows
out of the (N, G, U, d) table store into HBM, then ``sdim_query`` reads
them back to score candidates — the gathered rows cross HBM twice for no
reason. Here the slot gather IS the kernel's block index map:

    grid step (b, c): store[slots[b]] --DMA--> VMEM      (scalar-prefetch
                                                          gather read)
        c == 0:  row × scale --dequant--> ℓ2-normalize --> Tn (scratch)
        every c: Q_tile (TC, d) --hash/one-hot GEMM--> out (TC, d)

Because the innermost grid axis is sequential on TPU, Pallas double-buffers
the streamed store blocks: user b+1's row is DMAing HBM→VMEM while user b's
candidates are scoring on the MXU. The (B, G, U, d) intermediate never
exists, and for int8/fp8 stores only the QUANTIZED bytes move — the per-row
fp32 ``scales`` ride along as a (1, 1, G·U) block and the dequantize happens
in VMEM, so the HBM traffic per user is ~(d+4)/(4d) of the fp32 path.

Dequantize-then-normalize is the oracle contract, though Eq. 12's row
ℓ2-normalize makes the output invariant to any positive per-row scale —
which is exactly why per-row symmetric quantization is AUC-safe here.

Contract
--------
* **Block specs** — ``PrefetchScalarGridSpec`` with the (B,) slot vector
  scalar-prefetched; grid ``(B, C/TC)``; per step: store row ``(1, G·U, d)``
  selected by ``slots[b]`` (the gather is the block index map), scales
  ``(1, 1, G·U)`` of their ``(N, 1, G·U)`` view at the same slot (quantized
  stores only; a ``(1, G·U)`` block of ``(N, G·U)`` breaks Mosaic's
  (8, 128)-or-full-dim rule), q ``(1, TC, d)``,
  R ``(m, d)`` replicated; output ``(1, TC, d)``.
* **VMEM residency** — the dequantized, ℓ2-normalized row lives in a
  ``(G·U, d)`` fp32 scratch computed once at ``c == 0`` and reused by every
  C-tile; the raw store row is only touched at ``c == 0``. ``block_c``
  (default 128) is the knob.
* **Ragged padding** — C is padded to whole blocks; padded candidates are
  computed on zeros and sliced off. Missing users (``present=0``) keep
  slot 0 and have their OUTPUT zero-masked in the wrapper — shared by both
  backends, so the ``fetch_many`` zero-row contract holds bit-exactly.
* **Oracle** — ``ref.py`` (gather → dequant → ``sdim.fused_query``),
  pinned by ``tests/test_fused_serve.py`` in interpret mode, atol ≲ 1e-5.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sdim_bucket.sdim_bucket import (
    l2_normalize_rows, pad_axis, padded_blocks, query_tile, row_to_column)


def _fused_kernel(slots_ref, q_ref, store_ref, r_ref, out_ref, tnorm_ref,
                  *, tau: int, groups: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _prep():
        tnorm_ref[...] = l2_normalize_rows(store_ref[0].astype(jnp.float32))

    q = q_ref[0].astype(jnp.float32)                         # (TC, d)
    r = r_ref[...].astype(jnp.float32)                       # (m, d)
    out_ref[0] = query_tile(q, tnorm_ref[...], r, tau=tau, groups=groups)


def _fused_kernel_quant(slots_ref, q_ref, store_ref, scales_ref, r_ref,
                        out_ref, tnorm_ref, *, tau: int, groups: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _prep():
        rows = store_ref[0].astype(jnp.float32)              # (G·U, d)
        tnorm_ref[...] = l2_normalize_rows(
            rows * row_to_column(scales_ref[0].astype(jnp.float32)))

    q = q_ref[0].astype(jnp.float32)                         # (TC, d)
    r = r_ref[...].astype(jnp.float32)                       # (m, d)
    out_ref[0] = query_tile(q, tnorm_ref[...], r, tau=tau, groups=groups)


def sdim_fused_serve(
    store: jax.Array,      # (N, G, U, d) table store, any storage dtype
    slots: jax.Array,      # (B,) int32 in [0, N)
    q: jax.Array,          # (B, C, d) candidates
    R: jax.Array,          # (m, d)
    tau: int,
    *,
    scales: Optional[jax.Array] = None,   # (N, G, U) per-row quant scales
    present: Optional[jax.Array] = None,  # (B,) 1 = user resident
    block_c: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns user-interest vectors (B, C, d) fp32, zero where absent."""
    N, G, U, d = store.shape
    B, C, _ = q.shape
    m = R.shape[0]
    assert G == m // tau and U == 1 << tau, (store.shape, m, tau)
    assert slots.shape == (B,), (slots.shape, B)
    slots = slots.astype(jnp.int32)
    block_c, C_pad = padded_blocks(C, block_c)
    q = pad_axis(q, 1, C_pad)
    store2d = store.reshape(N, G * U, d)

    in_specs = [
        pl.BlockSpec((1, block_c, d), lambda b, c, slots: (b, c, 0)),
        pl.BlockSpec((1, G * U, d), lambda b, c, slots: (slots[b], 0, 0)),
    ]
    operands = [q, store2d]
    kernel = _fused_kernel
    if scales is not None:
        in_specs.append(
            pl.BlockSpec((1, 1, G * U), lambda b, c, slots: (slots[b], 0, 0)))
        operands.append(scales.reshape(N, 1, G * U))
        kernel = _fused_kernel_quant
    in_specs.append(pl.BlockSpec((m, d), lambda b, c, slots: (0, 0)))
    operands.append(R)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, C_pad // block_c),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_c, d), lambda b, c, slots: (b, c, 0)),
        scratch_shapes=[pltpu.VMEM((G * U, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(kernel, tau=tau, groups=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C_pad, d), jnp.float32),
        interpret=interpret,
    )(slots, *operands)[:, :C]
    if present is not None:
        out = out * present.astype(jnp.float32)[:, None, None]
    return out
