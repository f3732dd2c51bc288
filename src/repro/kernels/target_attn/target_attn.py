"""Flash-style streaming target attention (DIN baseline hot path).

Online-softmax over L tiles so the (C, L) score matrix never materializes in
HBM — the TPU adaptation of FlashAttention specialized to *target* attention
(no causal mask, single query set vs one behavior sequence):

    per KV tile: s = Q·Kᵀ/√d ; m' = max(m, rowmax(s)) ;
                 acc = acc·e^{m−m'} + e^{s−m'}·V ; l = l·e^{m−m'} + rowsum

Scratch (VMEM): running max (C,1), denom (C,1), accumulator (C,d), all fp32.
The mask streams as ``(1, 1, TL)`` blocks of its ``(B, 1, L)`` view: a
``(1, TL)`` block of ``(B, L)`` breaks Mosaic's (8, 128)-or-full-dim rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ta_kernel(q_ref, seq_ref, mask_ref, out_ref, m_ref, l_ref, acc_ref):
    li = pl.program_id(2)          # L is innermost: scratch accumulates over it
    n_l = pl.num_programs(2)

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                          # (TC, d)
    kv = seq_ref[0].astype(jnp.float32)                       # (TL, d)
    d = q.shape[-1]
    scale = jax.lax.rsqrt(jnp.float32(d))
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                                 # (TC, TL)
    valid = mask_ref[0] > 0                                   # (1, TL)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                       # (TC, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                    # (TC, TL)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, kv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(li == n_l - 1)
    def _finish():
        out_ref[0] = acc_ref[...] / (l_ref[...] + 1e-30)


def target_attention_flash(
    q: jax.Array,          # (B, C, d)
    seq: jax.Array,        # (B, L, d)
    mask: jax.Array,       # (B, L)
    *,
    block_c: int = 128,
    block_l: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, C, d = q.shape
    L = seq.shape[1]
    block_c = min(block_c, C)
    block_l = min(block_l, L)
    assert C % block_c == 0 and L % block_l == 0

    return pl.pallas_call(
        _ta_kernel,
        grid=(B, C // block_c, L // block_l),
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda b, c, l: (b, c, 0)),
            pl.BlockSpec((1, block_l, d), lambda b, c, l: (b, l, 0)),
            pl.BlockSpec((1, 1, block_l), lambda b, c, l: (b, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d), lambda b, c, l: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_c, 1), jnp.float32),
            pltpu.VMEM((block_c, 1), jnp.float32),
            pltpu.VMEM((block_c, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, seq, mask.astype(jnp.float32)[:, None, :])
