"""Session-structured behaviour histories, generated on the device.

A vectorised copy of the history part of ``repro.data.synthetic.
generate_batch`` (the benchmark imports nothing of it): each user has
``n_interests`` distinct categories; the history is written in sessions of
``session_len`` behaviours, each session on one of the user's interests;
a ``1 - p_in_interest`` share of behaviours is noise from any category;
an item belongs to its category (``item = cat * items_per_cat + r``); and
users have ``uniform(min_hist_frac * L, L)`` valid behaviours, padded at
the front so the newest behaviours are the last positions.

Every chunk of users is a pure function of ``(key, chunk index)``, so the
reference can regenerate any user's history without keeping it.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class HistoryShape:
    n_items: int
    n_cats: int
    hist_len: int
    n_interests: int = 5
    session_len: int = 16
    p_in_interest: float = 0.9
    min_hist_frac: float = 0.3

    @property
    def items_per_cat(self) -> int:
        return self.n_items // self.n_cats


def distinct_interests(key, batch: int, n: int, k: int) -> jax.Array:
    """(batch, k) distinct ids in [0, n) per row: Floyd's sampling."""
    chosen = jnp.zeros((batch, 0), jnp.int32)
    for j, sub in zip(range(n - k, n), jax.random.split(key, k)):
        t = jax.random.randint(sub, (batch,), 0, j + 1, jnp.int32)
        seen = jnp.any(chosen == t[:, None], axis=1)
        pick = jnp.where(seen, j, t)
        chosen = jnp.concatenate([chosen, pick[:, None]], axis=1)
    return chosen


@partial(jax.jit, static_argnames=("shape", "batch"))
def chunk(key, index, *, shape: HistoryShape, batch: int) -> dict:
    """Histories of users ``[index * batch, (index + 1) * batch)``:
    ``hist_items``/``hist_cats`` int32 and ``hist_mask`` float32, each
    (batch, L), plus each user's ``interests`` (batch, n_interests)."""
    L = shape.hist_len
    ks = jax.random.split(jax.random.fold_in(key, index), 7)
    interests = distinct_interests(ks[0], batch, shape.n_cats,
                                   shape.n_interests)
    n_sessions = -(-L // shape.session_len)
    pick = jax.random.randint(ks[1], (batch, n_sessions), 0,
                              shape.n_interests)
    sess = jnp.take_along_axis(interests, pick, axis=1)
    cats = jnp.repeat(sess, shape.session_len, axis=1)[:, :L]
    noise = jax.random.uniform(ks[2], (batch, L)) > shape.p_in_interest
    cats = jnp.where(noise, jax.random.randint(ks[3], (batch, L), 0,
                                               shape.n_cats), cats)
    items = cats * shape.items_per_cat + jax.random.randint(
        ks[4], (batch, L), 0, shape.items_per_cat)
    lengths = jax.random.randint(ks[5], (batch,),
                                 int(shape.min_hist_frac * L), L + 1)
    pos = jnp.arange(L)[None, :]
    mask = (pos >= (L - lengths[:, None])).astype(jnp.float32)
    return {"hist_items": items.astype(jnp.int32),
            "hist_cats": cats.astype(jnp.int32),
            "hist_mask": mask, "interests": interests}
