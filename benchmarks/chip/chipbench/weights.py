"""The served model's weights, made on the device from the seed.

The benchmark makes the weights itself, in the parameter layout the CTR
model reads, so that the reference can make the very same weights again
from the seed without taking anything from the program. All of them are
random: embeddings N(0, emb_std^2), the SimHash projection N(0, 1), the
MLP head's weights N(0, 1/fan_in) and small nonzero biases, so that the
comparison covers every parameter.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def model_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: ``PRNGKey`` keeps 32 bits only,
    so the bits above go in through ``fold_in``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def head_dims(cfg: dict) -> tuple:
    e = 2 * cfg["embed_dim"]
    return (3 * e + cfg["ctx_dim"], *cfg["mlp_hidden"], 1)


@partial(jax.jit, static_argnames=("n_items", "n_cats", "embed_dim", "m",
                                   "dims", "emb_std"))
def _make(key, *, n_items, n_cats, embed_dim, m, dims, emb_std):
    ks = jax.random.split(key, 3 + 2 * (len(dims) - 1))
    head = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        head[f"fc{i}"] = {
            "w": jax.random.normal(ks[3 + 2 * i], (a, b)) / jnp.sqrt(a),
            "b": 0.01 * jax.random.normal(ks[4 + 2 * i], (b,)),
        }
    return {
        "item_emb": {"table": emb_std * jax.random.normal(
            ks[0], (n_items, embed_dim))},
        "cat_emb": {"table": emb_std * jax.random.normal(
            ks[1], (n_cats, embed_dim))},
        "interest": {"buffers": {"R": jax.random.normal(
            ks[2], (m, 2 * embed_dim))}},
        "head": head,
    }


def make(cfg: dict, key: jax.Array) -> dict:
    """All weights of configuration ``cfg`` in one jitted call."""
    return _make(key, n_items=cfg["n_items"], n_cats=cfg["n_cats"],
                 embed_dim=cfg["embed_dim"], m=cfg["m"],
                 dims=head_dims(cfg), emb_std=cfg["emb_std"])
