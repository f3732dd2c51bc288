"""Attention substrate for the LM family.

Supports:
  * GQA (grouped-query attention) with arbitrary ``n_kv_heads`` (granite,
    command-r+, qwen3) and optional qk-norm (qwen3);
  * MLA (multi-head latent attention, DeepSeek-V2) with a compressed latent
    KV cache (``kv_lora_rank`` + decoupled RoPE key);
  * RoPE;
  * training (full causal), prefill (causal, returns cache) and decode
    (single new token against an existing cache) paths.

All softmax arithmetic is f32 regardless of compute dtype. Grouped einsums
avoid materializing repeated KV heads.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.layers import Linear, RMSNorm
from repro.nn.module import KeyGen

Params = Any


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, positions: jax.Array, theta: float = 10000.0):
    """Return (cos, sin) of shape positions.shape + (head_dim/2,)."""
    half = head_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., T, H, D); cos/sin: (..., T, D/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1).astype(
        x.dtype
    )


def _causal_mask(q_len: int, kv_len: int, q_offset) -> jax.Array:
    """Boolean mask (q_len, kv_len): True = attend. q position i corresponds to
    absolute position q_offset + i."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


def masked_softmax(scores: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GQAttention:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    use_bias: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    # query-chunked (flash-style) attention kicks in at T >= 2*q_chunk:
    # never materializes the (B,H,T,S) score slab, only (B,H,chunk,S)
    q_chunk: int = 1024
    q_chunk_unroll: bool = False

    @property
    def n_groups(self) -> int:
        assert self.n_heads % self.n_kv_heads == 0
        return self.n_heads // self.n_kv_heads

    def init(self, key) -> Params:
        kg = KeyGen(key)
        p = {
            "wq": Linear(self.d_model, self.n_heads * self.head_dim, self.use_bias).init(kg()),
            "wk": Linear(self.d_model, self.n_kv_heads * self.head_dim, self.use_bias).init(kg()),
            "wv": Linear(self.d_model, self.n_kv_heads * self.head_dim, self.use_bias).init(kg()),
            "wo": Linear(self.n_heads * self.head_dim, self.d_model, self.use_bias).init(kg()),
        }
        if self.qk_norm:
            p["q_norm"] = RMSNorm(self.head_dim).init(kg())
            p["k_norm"] = RMSNorm(self.head_dim).init(kg())
        return p

    def _qkv(self, params, x, positions):
        B, T, _ = x.shape
        q = Linear(self.d_model, self.n_heads * self.head_dim, self.use_bias).apply(
            params["wq"], x
        ).reshape(B, T, self.n_heads, self.head_dim)
        k = Linear(self.d_model, self.n_kv_heads * self.head_dim, self.use_bias).apply(
            params["wk"], x
        ).reshape(B, T, self.n_kv_heads, self.head_dim)
        v = Linear(self.d_model, self.n_kv_heads * self.head_dim, self.use_bias).apply(
            params["wv"], x
        ).reshape(B, T, self.n_kv_heads, self.head_dim)
        if self.qk_norm:
            q = RMSNorm(self.head_dim).apply(params["q_norm"], q)
            k = RMSNorm(self.head_dim).apply(params["k_norm"], k)
        cos, sin = rope_frequencies(self.head_dim, positions, self.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        return q, k, v

    def _attend(self, q, k, v, mask):
        """q: (B,T,H,D); k/v: (B,S,Hkv,D), repeated to H heads.

        Per-head layout (not grouped): H is divisible by any sane TP degree,
        so GSPMD shards the (B,H,T,S) score tensor over the model axis even
        when n_kv_heads < TP (Megatron-style KV-head replication under GQA).
        The grouped einsum avoided the K-repeat but left a (K,G,...) score
        layout XLA could not shard when K < TP — measured 200+ GiB of
        all-gather on granite train_4k."""
        B, T, H, D = q.shape
        G = self.n_groups
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                            k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
        if mask is not None:
            mask_b = mask[:, None, :, :] if mask.ndim == 3 else mask[None, None]
            probs = masked_softmax(scores, mask_b)
        else:
            probs = masked_softmax(scores, None)
        out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v)
        return out.reshape(B, T, H * D)

    def _attend_chunked(self, q, k, v):
        """Causal attention scanned over query chunks (assumes q positions are
        0..T-1 against k/v of the same length). Peak score memory is
        (B, H, chunk, S) instead of (B, H, T, S): 32k prefill drops from
        ~50 GiB/chip to ~1.5 GiB. Each chunk body is checkpointed so the
        backward pass replays one chunk at a time."""
        B, T, H, D = q.shape
        c = self.q_chunk
        assert T % c == 0, (T, c)
        G = self.n_groups
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        kv_pos = jnp.arange(T)

        def body(_, qc_i):
            qc, i = qc_i                                  # (B, c, H, D), chunk idx
            q_pos = i * c + jnp.arange(c)
            m = (kv_pos[None, :] <= q_pos[:, None])       # (c, T)
            s = jnp.einsum("bthd,bshd->bhts", qc.astype(jnp.float32),
                           k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
            p = masked_softmax(s, m[None, None])
            o = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)
            return None, o

        qs = q.reshape(B, T // c, c, H, D).transpose(1, 0, 2, 3, 4)
        idx = jnp.arange(T // c)
        ckpt_body = jax.checkpoint(body, prevent_cse=False)
        if self.q_chunk_unroll:
            outs = jnp.stack([ckpt_body(None, (qs[i], idx[i]))[1]
                              for i in range(T // c)])
        else:
            _, outs = jax.lax.scan(ckpt_body, None, (qs, idx))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, T, H * D)
        return out

    def apply(self, params, x, positions=None, mask=None):
        """Training / full-sequence forward. x: (B, T, d_model)."""
        B, T, _ = x.shape
        if positions is None:
            positions = jnp.arange(T)[None, :].astype(jnp.int32)
        q, k, v = self._qkv(params, x, positions)
        if mask is None and self.causal and T >= 2 * self.q_chunk:
            out = self._attend_chunked(q, k, v)
        else:
            if mask is None and self.causal:
                mask = jnp.broadcast_to(_causal_mask(T, T, 0)[None], (B, T, T))
            out = self._attend(q, k, v, mask)
        return Linear(self.n_heads * self.head_dim, self.d_model, self.use_bias).apply(
            params["wo"], out
        )

    # -- serving -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        shape = (batch, max_len, self.n_kv_heads, self.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def decode_step(self, params, x, cache, cache_len):
        """x: (B, 1, d_model); cache holds ``cache_len`` valid positions.

        Returns (out, new_cache). The new token is written at ``cache_len``.
        """
        B = x.shape[0]
        positions = jnp.full((B, 1), cache_len, dtype=jnp.int32)
        q, k_new, v_new = self._qkv(params, x, positions)
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), cache_len, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), cache_len, axis=1)
        S = k.shape[1]
        valid = (jnp.arange(S)[None, None, :] <= cache_len)
        mask = jnp.broadcast_to(valid, (B, 1, S))
        out = self._attend(q, k, v, mask)
        out = Linear(self.n_heads * self.head_dim, self.d_model, self.use_bias).apply(
            params["wo"], out
        )
        return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 style multi-head latent attention)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MLAttention:
    """Multi-head latent attention with decoupled RoPE.

    The KV path is compressed into a ``kv_lora_rank`` latent c_kv; per-head
    nope-keys and values are up-projected from the latent. A single shared
    RoPE key (``rope_head_dim``) carries positional information. The decode
    cache stores only (c_kv, k_rope) — this *is* DeepSeek-V2's memory saving.
    """

    d_model: int
    n_heads: int
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    nope_head_dim: int = 128
    rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    causal: bool = True
    q_chunk: int = 1024
    q_chunk_unroll: bool = False

    def init(self, key) -> Params:
        kg = KeyGen(key)
        H, dn, dr, dv = self.n_heads, self.nope_head_dim, self.rope_head_dim, self.v_head_dim
        return {
            "wq_a": Linear(self.d_model, self.q_lora_rank, False).init(kg()),
            "q_a_norm": RMSNorm(self.q_lora_rank).init(kg()),
            "wq_b": Linear(self.q_lora_rank, H * (dn + dr), False).init(kg()),
            "wkv_a": Linear(self.d_model, self.kv_lora_rank + dr, False).init(kg()),
            "kv_a_norm": RMSNorm(self.kv_lora_rank).init(kg()),
            "wk_b": Linear(self.kv_lora_rank, H * dn, False).init(kg()),
            "wv_b": Linear(self.kv_lora_rank, H * dv, False).init(kg()),
            "wo": Linear(H * dv, self.d_model, False).init(kg()),
        }

    def _q(self, params, x, positions):
        B, T, _ = x.shape
        H, dn, dr = self.n_heads, self.nope_head_dim, self.rope_head_dim
        q_lat = Linear(self.d_model, self.q_lora_rank, False).apply(params["wq_a"], x)
        q_lat = RMSNorm(self.q_lora_rank).apply(params["q_a_norm"], q_lat)
        q = Linear(self.q_lora_rank, H * (dn + dr), False).apply(params["wq_b"], q_lat)
        q = q.reshape(B, T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        cos, sin = rope_frequencies(dr, positions, self.rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)
        return q_nope, q_rope

    def _kv_latent(self, params, x, positions):
        """Returns (c_kv, k_rope): (B,T,r) and (B,T,dr)."""
        dr = self.rope_head_dim
        kv = Linear(self.d_model, self.kv_lora_rank + dr, False).apply(params["wkv_a"], x)
        c_kv, k_rope = kv[..., : self.kv_lora_rank], kv[..., self.kv_lora_rank :]
        c_kv = RMSNorm(self.kv_lora_rank).apply(params["kv_a_norm"], c_kv)
        cos, sin = rope_frequencies(dr, positions, self.rope_theta)
        k_rope = apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]
        return c_kv, k_rope

    def _attend(self, params, q_nope, q_rope, c_kv, k_rope, mask):
        """Latent-space attention: scores via absorbed projections.

        q_nope: (B,T,H,dn); c_kv: (B,S,r); k_rope: (B,S,dr).
        Instead of materializing per-head keys (B,S,H,dn), absorb wk_b into the
        query: q_lat[b,t,h,r] = q_nope · wk_b_h — an O(T·H·dn·r) GEMM — then
        score against the latent directly (O(T·S·H·r) but r is small).
        """
        B, T, H, dn = q_nope.shape
        r = self.kv_lora_rank
        dr, dv = self.rope_head_dim, self.v_head_dim
        wk_b = params["wk_b"]["w"].reshape(r, H, dn)
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, wk_b)
        scale = 1.0 / jnp.sqrt(jnp.asarray(dn + dr, jnp.float32))
        scores = (
            jnp.einsum("bthr,bsr->bhts", q_lat, c_kv)
            + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)
        ) * scale
        if mask is not None:
            mask_b = mask[:, None, :, :]
            probs = masked_softmax(scores, mask_b)
        else:
            probs = masked_softmax(scores, None)
        # output in latent space, then up-project through wv_b
        out_lat = jnp.einsum("bhts,bsr->bthr", probs.astype(c_kv.dtype), c_kv)
        wv_b = params["wv_b"]["w"].reshape(r, H, dv)
        out = jnp.einsum("bthr,rhd->bthd", out_lat, wv_b)
        return out.reshape(B, T, H * dv)

    def _attend_chunked(self, params, q_nope, q_rope, c_kv, k_rope):
        """Query-chunked causal latent attention (see GQAttention version)."""
        B, T, H, dn = q_nope.shape
        c = self.q_chunk
        assert T % c == 0
        r = self.kv_lora_rank
        dr, dv = self.rope_head_dim, self.v_head_dim
        wk_b = params["wk_b"]["w"].reshape(r, H, dn)
        wv_b = params["wv_b"]["w"].reshape(r, H, dv)
        scale = 1.0 / jnp.sqrt(jnp.asarray(dn + dr, jnp.float32))
        kv_pos = jnp.arange(T)

        def body(_, chunk):
            qn, qr, i = chunk
            q_pos = i * c + jnp.arange(c)
            m = (kv_pos[None, :] <= q_pos[:, None])
            q_lat = jnp.einsum("bthd,rhd->bthr", qn, wk_b)
            s = (jnp.einsum("bthr,bsr->bhts", q_lat, c_kv)
                 + jnp.einsum("bthd,bsd->bhts", qr, k_rope)) * scale
            p = masked_softmax(s, m[None, None])
            o_lat = jnp.einsum("bhts,bsr->bthr", p.astype(c_kv.dtype), c_kv)
            return None, jnp.einsum("bthr,rhd->bthd", o_lat, wv_b)

        qns = q_nope.reshape(B, T // c, c, H, dn).transpose(1, 0, 2, 3, 4)
        qrs = q_rope.reshape(B, T // c, c, H, dr).transpose(1, 0, 2, 3, 4)
        idx = jnp.arange(T // c)
        ckpt_body = jax.checkpoint(body, prevent_cse=False)
        if self.q_chunk_unroll:
            outs = jnp.stack([ckpt_body(None, (qns[i], qrs[i], idx[i]))[1]
                              for i in range(T // c)])
        else:
            _, outs = jax.lax.scan(ckpt_body, None, (qns, qrs, idx))
        return outs.transpose(1, 0, 2, 3, 4).reshape(B, T, H * dv)

    def apply(self, params, x, positions=None, mask=None):
        B, T, _ = x.shape
        if positions is None:
            positions = jnp.arange(T)[None, :].astype(jnp.int32)
        q_nope, q_rope = self._q(params, x, positions)
        c_kv, k_rope = self._kv_latent(params, x, positions)
        if mask is None and self.causal and T >= 2 * self.q_chunk:
            out = self._attend_chunked(params, q_nope, q_rope, c_kv, k_rope)
        else:
            if mask is None and self.causal:
                mask = jnp.broadcast_to(_causal_mask(T, T, 0)[None], (B, T, T))
            out = self._attend(params, q_nope, q_rope, c_kv, k_rope, mask)
        return Linear(self.n_heads * self.v_head_dim, self.d_model, False).apply(
            params["wo"], out
        )

    # -- serving -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return {
            "ckv": jnp.zeros((batch, max_len, self.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, self.rope_head_dim), dtype),
        }

    def decode_step(self, params, x, cache, cache_len):
        B = x.shape[0]
        positions = jnp.full((B, 1), cache_len, dtype=jnp.int32)
        q_nope, q_rope = self._q(params, x, positions)
        c_new, kr_new = self._kv_latent(params, x, positions)
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_new.astype(cache["ckv"].dtype), cache_len, axis=1
        )
        krope = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], kr_new.astype(cache["krope"].dtype), cache_len, axis=1
        )
        S = ckv.shape[1]
        mask = jnp.broadcast_to(jnp.arange(S)[None, None, :] <= cache_len, (B, 1, S))
        out = self._attend(params, q_nope, q_rope, ckv, krope, mask)
        out = Linear(self.n_heads * self.v_head_dim, self.d_model, False).apply(
            params["wo"], out
        )
        return out, {"ckv": ckv, "krope": krope}


# ---------------------------------------------------------------------------
# Split-KV sequence-parallel decode (flash-decoding on the mesh)
# ---------------------------------------------------------------------------
def _combined_axis_index(axes):
    """Linear shard index over a tuple of mesh axes (row-major)."""
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _online_combine(m_a, l_a, acc_a, m_b, l_b, acc_b):
    """Merge two (max, denom, acc) partial-softmax states."""
    m = jnp.maximum(m_a, m_b)
    sa = jnp.exp(m_a - m)
    sb = jnp.exp(m_b - m)
    return m, l_a * sa + l_b * sb, acc_a * sa[..., None] + acc_b * sb[..., None]


def gqa_sp_decode_attention(
    q,            # (B, 1, H, D) — replicated over seq_axes
    k_cache,      # (B, S, Hkv, D) — S sharded over seq_axes
    v_cache,      # (B, S, Hkv, D)
    k_new,        # (B, 1, Hkv, D) current token (appended outside)
    v_new,        # (B, 1, Hkv, D)
    cache_len,    # scalar: #valid cache positions
    mesh,
    seq_axes: tuple,
    batch_axes: tuple | None = None,
    n_kv_heads: int = 8,
):
    """Exact decode attention with the KV cache sharded on the sequence dim.

    Each seq-shard computes a local partial softmax (max/denominator/weighted
    values), a psum over ``seq_axes`` combines them (2 small collectives of
    O(B·H·D)), and the current token's contribution is merged on top — the
    TPU-mesh version of flash-decoding / split-K. Never gathers the cache.
    """
    B, _, H, D = q.shape
    G = H // n_kv_heads
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def local(q_l, k_l, v_l, cache_len_l):
        Bl = q_l.shape[0]
        S_loc = k_l.shape[1]
        shard = _combined_axis_index(seq_axes)
        pos = shard * S_loc + jnp.arange(S_loc)
        valid = pos[None, :] < cache_len_l                     # (1, S_loc)
        qg = q_l.reshape(Bl, 1, n_kv_heads, G, D)
        s = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                       k_l.astype(jnp.float32)) * scale        # (B,K,G,1,S)
        s = jnp.where(valid[None, None, None], s, -1e30)
        m_loc = jnp.max(s, axis=-1)                            # (B,K,G,1)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        acc_loc = jnp.einsum("bkgts,bskd->bkgtd", p, v_l.astype(jnp.float32))
        m_g = jax.lax.pmax(m_loc, seq_axes)
        sc = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * sc, seq_axes)
        acc_g = jax.lax.psum(acc_loc * sc[..., None], seq_axes)
        return m_g, l_g, acc_g

    b = batch_axes if batch_axes else None
    kv_spec = P(b, seq_axes, None, None)
    q_spec = P(b, None, None, None)

    m_g, l_g, acc_g = jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=(P(b, None, None, None),
                   P(b, None, None, None),
                   P(b, None, None, None, None)),
        check_vma=False,
    )(q, k_cache, v_cache, cache_len)

    # merge the current token (always visible to itself)
    qg = q.reshape(B, 1, n_kv_heads, G, D)
    s_new = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                       k_new.astype(jnp.float32)) * scale       # (B,K,G,1,1)
    m_n = s_new[..., 0]
    l_n = jnp.ones_like(m_n)
    acc_n = jnp.einsum("bkgts,bskd->bkgtd", jnp.ones_like(s_new),
                       v_new.astype(jnp.float32))
    m_f, l_f, acc_f = _online_combine(m_g, l_g, acc_g, m_n, l_n, acc_n)
    out = acc_f / (l_f[..., None] + 1e-30)                      # (B,K,G,1,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H * D)


def mla_sp_decode_attention(
    q_lat,        # (B, 1, H, r) absorbed queries
    q_rope,       # (B, 1, H, dr)
    ckv_cache,    # (B, S, r) — S sharded over seq_axes
    krope_cache,  # (B, S, dr)
    c_new,        # (B, 1, r)
    kr_new,       # (B, 1, dr)
    cache_len,
    mesh,
    seq_axes: tuple,
    batch_axes: tuple | None = None,
    score_scale: float = 1.0,
):
    """Split-KV decode for MLA: partial softmax over the sharded latent cache.
    Returns latent-space attention output (B, 1, H, r)."""
    B, _, H, r = q_lat.shape

    def local(ql_l, qr_l, c_l, kr_l, cache_len_l):
        S_loc = c_l.shape[1]
        shard = _combined_axis_index(seq_axes)
        pos = shard * S_loc + jnp.arange(S_loc)
        valid = pos[None, :] < cache_len_l
        s = (jnp.einsum("bthr,bsr->bhts", ql_l.astype(jnp.float32), c_l.astype(jnp.float32))
             + jnp.einsum("bthd,bsd->bhts", qr_l.astype(jnp.float32), kr_l.astype(jnp.float32))
             ) * score_scale                                    # (B,H,1,S)
        s = jnp.where(valid[None, None], s, -1e30)
        m_loc = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(valid[None, None], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        acc_loc = jnp.einsum("bhts,bsr->bhtr", p, c_l.astype(jnp.float32))
        m_g = jax.lax.pmax(m_loc, seq_axes)
        sc = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * sc, seq_axes)
        acc_g = jax.lax.psum(acc_loc * sc[..., None], seq_axes)
        return m_g, l_g, acc_g

    b = batch_axes if batch_axes else None
    m_g, l_g, acc_g = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b, None, None, None), P(b, None, None, None),
                  P(b, seq_axes, None), P(b, seq_axes, None), P()),
        out_specs=(P(b, None, None), P(b, None, None), P(b, None, None, None)),
        check_vma=False,
    )(q_lat, q_rope, ckv_cache, krope_cache, cache_len)

    s_new = (jnp.einsum("bthr,bsr->bhts", q_lat.astype(jnp.float32), c_new.astype(jnp.float32))
             + jnp.einsum("bthd,bsd->bhts", q_rope.astype(jnp.float32), kr_new.astype(jnp.float32))
             ) * score_scale
    m_n = s_new[..., 0]
    l_n = jnp.ones_like(m_n)
    acc_n = jnp.einsum("bhts,bsr->bhtr", jnp.ones_like(s_new), c_new.astype(jnp.float32))
    m_f, l_f, acc_f = _online_combine(m_g, l_g, acc_g, m_n, l_n, acc_n)
    out = acc_f / (l_f[..., None] + 1e-30)                      # (B,H,1,r)
    return out.transpose(0, 2, 1, 3)                            # (B,1,H,r)
