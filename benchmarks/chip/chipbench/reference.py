"""Plain reference of the served SDIM CTR model (arXiv:2205.10249 §4).

Straightforward ``jax.numpy``, one request at a time in blocks, with no
kernels, no store, no batching across users and nothing imported from the
program. It follows the paper's equations:

* behaviours and candidates are ``concat(item_emb, cat_emb)`` (width e);
* SimHash: bit i of x is ``[r_i . x >= 0]``; every tau bits form one of
  G = m / tau bucket ids (bit t weighs 2**t);
* the BSE table of a user is, per group g and bucket u, the sum of the
  (masked) behaviours whose signature in g is u (Eq. 8/11);
* a candidate's long-term interest is the mean over groups of the
  l2-normalised bucket it falls in (Eq. 12; an empty bucket reads 0);
* the DIN short-term interest is softmax(q . s / sqrt(e)) attention over
  the recent window;
* the head is an MLP with ReLU on [target, short, long, ctx].

What the deployment adds is modelled too, since it is part of what is
served: tables may be stored as int8 with one symmetric max-abs scale per
bucket row (round half to even), and the BSE server sends tables or
interest vectors in the wire dtype.

Precision is what the configuration states: the SimHash projection at
float32 ``HIGHEST``, every other product at the backend's ``DEFAULT``.
``dtype`` lowers the storage of every array (the control).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


def embed(w: dict, items, cats, n_items: int, n_cats: int, dtype):
    it = w["item_emb"]["table"][items % n_items]
    ct = w["cat_emb"]["table"][cats % n_cats]
    return jnp.concatenate([it, ct], axis=-1).astype(dtype)


def bucket_ids(x, R, tau: int, dtype):
    """(..., e) -> (..., G) bucket ids."""
    prec = HIGHEST if dtype == jnp.float32 else DEFAULT
    proj = jnp.einsum("...e,me->...m", x.astype(dtype), R.astype(dtype),
                      precision=prec,
                      preferred_element_type=dtype)
    bits = (proj >= 0).astype(jnp.int32)
    *lead, m = bits.shape
    bits = bits.reshape(*lead, m // tau, tau)
    return jnp.sum(bits << jnp.arange(tau, dtype=jnp.int32), axis=-1)


def tables(seq, mask, R, tau: int, dtype):
    """One user's behaviours (L, e) and mask (L,) -> table (G, U, e)."""
    sig = bucket_ids(seq, R, tau, dtype)                         # (L, G)
    onehot = (sig[..., None] == jnp.arange(1 << tau)).astype(dtype)
    onehot = onehot * mask[:, None, None].astype(dtype)
    return jnp.einsum("lgu,le->gue", onehot, seq.astype(dtype),
                      precision=DEFAULT, preferred_element_type=dtype)


def quantize(t, qmax: float):
    """Symmetric per-row quantisation and back: rows are the last axis."""
    t = t.astype(jnp.float32)
    scale = jnp.max(jnp.abs(t), axis=-1, keepdims=True) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(jnp.clip(t / safe, -qmax, qmax))
    return jnp.where(scale > 0, q * scale, 0.0)


def interest(q, table, R, tau: int, dtype):
    """Candidates (C, e) against one table (G, U, e) -> (C, e)."""
    G, U, e = table.shape
    t = table.astype(dtype).astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-12)
    tn = (t / norm).astype(dtype).reshape(G * U, e)
    sig = bucket_ids(q, R, tau, dtype)                           # (C, G)
    # Eq. 12 as one product: a row with one 1 per group picks each group's
    # bucket and sums over groups
    flat = sig + jnp.arange(G, dtype=sig.dtype) * U
    multihot = jnp.sum(flat[..., None] == jnp.arange(G * U), axis=1)
    out = jnp.einsum("ck,ke->ce", multihot.astype(dtype), tn,
                     precision=DEFAULT, preferred_element_type=dtype)
    return out / G


def short_interest(q, seq, mask, dtype):
    """DIN target attention: q (C, e) over the recent window (s, e)."""
    e = q.shape[-1]
    s = jnp.einsum("ce,se->cs", q.astype(dtype), seq.astype(dtype),
                   precision=DEFAULT, preferred_element_type=dtype)
    s = s.astype(jnp.float32) / jnp.sqrt(jnp.float32(e))
    s = jnp.where(mask[None, :] > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("cs,se->ce", p, seq.astype(dtype), precision=DEFAULT,
                      preferred_element_type=dtype)


def head(w: dict, x, dtype):
    n = len(w["head"])
    for i in range(n):
        layer = w["head"][f"fc{i}"]
        x = jnp.einsum("ci,io->co", x.astype(dtype),
                       layer["w"].astype(dtype), precision=DEFAULT,
                       preferred_element_type=dtype)
        x = x + layer["b"].astype(dtype)
        if i < n - 1:
            x = jax.nn.relu(x)
    return x[:, 0]


@partial(jax.jit, static_argnames=("cfg", "dtype", "qmax"))
def user_tables(w, items, cats, mask, *, cfg, dtype, qmax):
    """Stored tables of a block of users: (B, L) histories -> (B, G, U, e)
    as the store holds them (quantised when ``qmax`` is given)."""
    c = dict(cfg)
    seq = embed(w, items, cats, c["n_items"], c["n_cats"], dtype)
    R = w["interest"]["buffers"]["R"]
    t = jax.vmap(lambda s, m: tables(s, m, R, c["tau"], dtype))(seq, mask)
    return quantize(t, qmax) if qmax else t.astype(jnp.float32)


@partial(jax.jit, static_argnames=("cfg", "dtype", "wire"))
def serve(w, table, ci, cc, ctx, hi, hc, hm, *, cfg, dtype, wire):
    """A block of B requests against their users' stored tables (B, G, U,
    e): candidates ``ci``/``cc`` (B, C), ``ctx`` (B, C, ctx_dim) and the
    recent window ``hi``/``hc``/``hm`` (B, s). Returns the long-term
    interest as it crosses the wire (B, C, e) and the scores (B, C)."""
    c = dict(cfg)
    R = w["interest"]["buffers"]["R"]
    wd = jnp.dtype(wire)

    def one(tb, ci, cc, ctx, hi, hc, hm):
        q = embed(w, ci, cc, c["n_items"], c["n_cats"], dtype)
        if c["fused"]:
            # the megakernel reads the stored rows and sends the interest
            long = interest(q, tb, R, c["tau"], dtype).astype(wd)
            sent = long
        else:
            # the BSE server sends the tables, the CTR server queries them
            tb = tb.astype(wd)
            long = interest(q, tb, R, c["tau"], dtype)
            sent = long
        seq = embed(w, hi, hc, c["n_items"], c["n_cats"], dtype)
        short = short_interest(q, seq, hm, dtype)
        x = jnp.concatenate([q.astype(dtype), short.astype(dtype),
                             long.astype(dtype), ctx.astype(dtype)], axis=-1)
        return sent.astype(jnp.float32), head(w, x, dtype).astype(jnp.float32)

    return jax.vmap(one)(table, ci, cc, ctx, hi, hc, hm)
