"""Fused BSE-encode Pallas kernel: SimHash + signature pack + bucket scatter.

One VMEM pass over the behavior sequence per (batch, L-tile) grid step:

    S_tile (TL, d) --GEMM--> proj (m, TL) --sign, pack GEMM--> sig (G·U, TL)
          --== bucket id--> one-hot (G·U, TL) --GEMM--> += table (G·U, d)

The ``L×m`` code matrix never hits HBM (ETA materializes it; SDIM doesn't
need to). The bucket "scatter" is expressed as a one-hot matmul so both GEMMs
land on the MXU; with paper dims (G·U = 16·8 = 128) the one-hot operand is
exactly one 128-lane tile.

TPU target: fp32 accumulation in the output block, which is revisited across
the L-grid (sequential innermost dimension). Validated on CPU via
``interpret=True`` against ``ref.py``.

Contract
--------
* **Block specs** — grid ``(B, L/TL)``; per step: seq ``(1, TL, d)``, mask
  ``(1, 1, TL)`` of its ``(B, 1, L)`` view (a ``(1, TL)`` block of ``(B, L)``
  breaks Mosaic's (8, 128)-or-full-dim rule), R ``(m, d)`` replicated,
  output table ``(1, G·U, d)`` at
  block ``(b, 0, 0)`` (same block every L-step — legal because the
  innermost grid axis is sequential on TPU).
* **VMEM residency** — one seq tile + R + the full ``(G·U, d)`` table;
  ``128·d`` floats per table at paper dims, far under budget. ``block_l``
  (default 128) is the ``EngineConfig`` knob.
* **Ragged padding** — L is padded to whole blocks by ``padded_blocks`` /
  ``pad_axis``; padded behaviors carry ``mask=0`` so they scatter nothing.
* **Oracle** — ``ref.py`` (== ``core/sdim.bucket_table`` one-hot einsum),
  pinned by ``tests/test_kernels.py`` in interpret mode, atol ≲ 1e-5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad ``x`` along ``axis`` up to length ``target`` (no-op if equal)."""
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def padded_blocks(n: int, block: int, multiple: int = 8) -> tuple[int, int]:
    """(block, padded_n): shrink ``block`` to a sublane-aligned size covering
    small ``n``, then round ``n`` up to a whole number of blocks. Callers
    zero-pad to ``padded_n`` instead of asserting ``n % block == 0``."""
    block = min(block, -(-n // multiple) * multiple)
    return block, -(-n // block) * block


def _pack_weights(m: int, tau: int, *, buckets_first: bool) -> jax.Array:
    """The signature-packing operand, built from 2-D iotas only: W[k, j] =
    2^t where bit k = g·τ + t belongs to the group g = j >> τ of flat bucket
    column j, else 0. ``bits (N, m) @ W (m, G·U)`` puts each row's group-g
    signature in all U columns of group g; ``buckets_first`` gives Wᵀ."""
    shape = ((m // tau) << tau, m) if buckets_first else (m, (m // tau) << tau)
    k = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if buckets_first else 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if buckets_first else 1)
    t = k - (j >> tau) * tau
    own = jnp.logical_and(t >= 0, t < tau)
    return jnp.where(own, jnp.left_shift(1, jnp.clip(t, 0, tau - 1)),
                     0).astype(jnp.float32)


def signature_onehot(x: jax.Array, r: jax.Array, *, tau: int,
                     buckets_first: bool = False) -> jax.Array:
    """In-kernel SimHash: rows x (N, d) -> flat bucket one-hots (N, G·U), or
    (G·U, N) with ``buckets_first``.

    GEMM projection, sign bits, then a packing GEMM against ``_pack_weights``
    and a compare with the bucket id ``j % U`` — one 1 per group. Only 2-D
    ops, which is what Mosaic lowers: a (N, m) -> (N, G, τ) reshape is an
    unsupported shape cast on TPU. The projection runs at full fp32
    precision so the sign bits are the reference's, not a bf16 pass's."""
    a, b = (r, x) if buckets_first else (x, r)
    proj = jax.lax.dot_general(                      # (m, N) or (N, m)
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    bits = (proj >= 0.0).astype(jnp.float32)
    w = _pack_weights(r.shape[0], tau, buckets_first=buckets_first)
    a, b = (w, bits) if buckets_first else (bits, w)
    sig = jnp.dot(a, b, preferred_element_type=jnp.float32)
    bucket = jax.lax.broadcasted_iota(
        jnp.int32, sig.shape, 0 if buckets_first else 1) & ((1 << tau) - 1)
    return (sig == bucket.astype(jnp.float32)).astype(jnp.float32)


def encode_tile(s: jax.Array, valid: jax.Array, r: jax.Array,
                *, tau: int) -> jax.Array:
    """One L-tile's bucket contribution: (TL, d) x (1, TL) mask -> (G·U, d).
    The one-hot is built buckets-first so the lane-major mask row scales
    its columns without a transpose."""
    onehot = signature_onehot(s, r, tau=tau, buckets_first=True) \
        * valid.astype(jnp.float32)
    return jnp.dot(onehot, s, preferred_element_type=jnp.float32)


def query_tile(q: jax.Array, tnorm: jax.Array, r: jax.Array,
               *, tau: int, groups: int) -> jax.Array:
    """One C-tile's interest read: (TC, d) x ℓ2-normalized table (G·U, d) ->
    (TC, d). The one-hot GEMM gathers each group's bucket AND sums over
    groups in a single MXU contraction (Eq. 12's mean, times G)."""
    onehot = signature_onehot(q, r, tau=tau)
    gathered = jnp.dot(onehot, tnorm, preferred_element_type=jnp.float32)
    return gathered / groups


def row_to_column(v: jax.Array) -> jax.Array:
    """(1, n) -> (n, 1) with 2-D ops only: mask an (n, n) broadcast to its
    diagonal and reduce over lanes (exact: one nonzero per row)."""
    n = v.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True)


def l2_normalize_rows(t: jax.Array) -> jax.Array:
    norm = jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-12)
    return t / norm


def _encode_kernel(seq_ref, mask_ref, r_ref, table_ref, *, tau: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        table_ref[...] = jnp.zeros_like(table_ref)

    s = seq_ref[0].astype(jnp.float32)                       # (TL, d)
    r = r_ref[...].astype(jnp.float32)                       # (m, d)
    table_ref[0] += encode_tile(s, mask_ref[0], r, tau=tau)


def bse_encode(
    seq: jax.Array,        # (B, L, d)
    mask: jax.Array,       # (B, L) 1 = valid
    R: jax.Array,          # (m, d)
    tau: int,
    *,
    block_l: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns bucket table (B, G, U, d) fp32."""
    B, L, d = seq.shape
    m = R.shape[0]
    assert m % tau == 0
    G, U = m // tau, 1 << tau
    # ragged L: pad to a whole number of blocks; padded rows carry mask=0 so
    # they scatter nothing into the table
    block_l, L_pad = padded_blocks(L, block_l)
    seq = pad_axis(seq, 1, L_pad)
    mask = pad_axis(mask, 1, L_pad)

    out = pl.pallas_call(
        functools.partial(_encode_kernel, tau=tau),
        grid=(B, L_pad // block_l),
        in_specs=[
            pl.BlockSpec((1, block_l, d), lambda b, l: (b, l, 0)),
            pl.BlockSpec((1, 1, block_l), lambda b, l: (b, 0, l)),
            pl.BlockSpec((m, d), lambda b, l: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G * U, d), lambda b, l: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G * U, d), jnp.float32),
        interpret=interpret,
    )(seq, mask.astype(jnp.float32)[:, None, :], R)
    return out.reshape(B, G, U, d)
