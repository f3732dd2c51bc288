"""DIN's tower, as the SDIM paper's online model uses it (arXiv:2205.10249
§4.4, Fig. 3; DIN is arXiv:1706.06978): target attention of each
candidate over the user's recent window, and an MLP head on
``[target, short, long, ctx]``.

The short-term branch has no weights of its own: the attention scores
are ``q . s / sqrt(e)`` over the masked window, softmaxed, and the
interest is their weighted sum of the window (``models/ctr.py``'s
``target_attention``). The head's input is therefore ``3e + ctx_dim``
wide, ``e`` being the behaviour width (2 x embed_dim).
"""
import jax
import jax.numpy as jnp

DEFAULT = jax.lax.Precision.DEFAULT

# The widths each source publishes for a model with this tower, by the
# configuration's ``source``; a configuration keeps every one of them.
PUBLISHED = {
    "https://arxiv.org/abs/2205.10249": {
        "n_items": 10_000_000, "n_cats": 100_000, "embed_dim": 64,
        "short_len": 50, "long_len": 1024, "mlp_hidden": [1024, 512, 256],
        "ctx_dim": 4, "m": 48, "tau": 3},
}

# The keys a configuration of this tower may list in ``reduced``: its
# scale, never a width.
SCALE = ("population",)


def config_kwargs(cfg: dict) -> dict:
    """DIN needs no ``CTRConfig`` keyword beyond the shared widths."""
    return {}


def head_in(cfg: dict) -> int:
    e = 2 * cfg["embed_dim"]
    return 3 * e + cfg["ctx_dim"]


def params(key, cfg: dict) -> dict:
    """No weights of its own: the attention reads the embeddings."""
    return {}


def short_interest(w: dict, q, seq, mask, *, cfg: dict, dtype):
    """Target attention: q (C, e) over the recent window (s, e)."""
    e = q.shape[-1]
    s = jnp.einsum("ce,se->cs", q.astype(dtype), seq.astype(dtype),
                   precision=DEFAULT, preferred_element_type=dtype)
    s = s.astype(jnp.float32) / jnp.sqrt(jnp.float32(e))
    s = jnp.where(mask[None, :] > 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("cs,se->ce", p, seq.astype(dtype), precision=DEFAULT,
                      preferred_element_type=dtype)


def flops(C: int, cfg: dict) -> int:
    """C candidates over the window of ``short_len``: the scores and the
    weighted sum, 2·s·e each."""
    e = 2 * cfg["embed_dim"]
    return 2 * 2 * C * cfg["short_len"] * e
