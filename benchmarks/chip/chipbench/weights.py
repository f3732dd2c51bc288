"""The served model's weights, made on the device from the seed.

The benchmark makes the weights itself, in the parameter layout the CTR
model reads, so that the reference can make the very same weights again
from the seed without taking anything from the program. All of them are
random: embeddings N(0, emb_std^2), the SimHash projection N(0, 1), the
MLP head's weights N(0, 1/fan_in) and small nonzero biases, so that the
comparison covers every parameter. The configuration's tower
(``towers/<arch>.py``) adds its own weights from a key of its own, a
``fold_in`` of the model key, so a tower never moves the others.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import shapes, spec

TOWER_KEY = 1          # fold_in data of the tower's own key


def model_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: ``PRNGKey`` keeps 32 bits only,
    so the bits above go in through ``fold_in``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@partial(jax.jit, static_argnames=("n_items", "n_cats", "embed_dim", "m",
                                   "dims", "emb_std", "cfg"))
def _make(key, *, n_items, n_cats, embed_dim, m, dims, emb_std, cfg):
    ks = jax.random.split(key, 3 + 2 * (len(dims) - 1))
    head = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        head[f"fc{i}"] = {
            "w": jax.random.normal(ks[3 + 2 * i], (a, b)) / jnp.sqrt(a),
            "b": 0.01 * jax.random.normal(ks[4 + 2 * i], (b,)),
        }
    c = dict(cfg)
    tower = spec.tower(c["arch"]).params(jax.random.fold_in(key, TOWER_KEY),
                                         c)
    return {
        "item_emb": {"table": emb_std * jax.random.normal(
            ks[0], (n_items, embed_dim))},
        "cat_emb": {"table": emb_std * jax.random.normal(
            ks[1], (n_cats, embed_dim))},
        "interest": {"buffers": {"R": jax.random.normal(
            ks[2], (m, 2 * embed_dim))}},
        "head": head,
        **tower,
    }


def make(cfg: dict, key: jax.Array) -> dict:
    """All weights of configuration ``cfg`` in one jitted call."""
    return _make(key, n_items=cfg["n_items"], n_cats=cfg["n_cats"],
                 embed_dim=cfg["embed_dim"], m=cfg["m"],
                 dims=shapes.head_dims(cfg), emb_std=cfg["emb_std"],
                 cfg=spec.frozen(cfg))
