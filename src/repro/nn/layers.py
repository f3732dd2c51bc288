"""Core dense layers: Linear, LayerNorm, RMSNorm, Embedding, MLP."""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.nn.module import KeyGen, lecun_normal, zeros_init, ones_init


@dataclasses.dataclass(frozen=True)
class Linear:
    in_dim: int
    out_dim: int
    use_bias: bool = True

    def init(self, key):
        kg = KeyGen(key)
        p = {"w": lecun_normal(kg(), (self.in_dim, self.out_dim))}
        if self.use_bias:
            p["b"] = zeros_init(kg(), (self.out_dim,))
        return p

    def apply(self, params, x):
        y = jnp.einsum("...i,io->...o", x, params["w"])
        if self.use_bias:
            y = y + params["b"]
        return y


@dataclasses.dataclass(frozen=True)
class LayerNorm:
    dim: int
    eps: float = 1e-5
    use_bias: bool = True

    def init(self, key):
        kg = KeyGen(key)
        p = {"scale": ones_init(kg(), (self.dim,))}
        if self.use_bias:
            p["bias"] = zeros_init(kg(), (self.dim,))
        return p

    def apply(self, params, x):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps) * params["scale"]
        if self.use_bias:
            y = y + params["bias"]
        return y.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class RMSNorm:
    dim: int
    eps: float = 1e-6

    def init(self, key):
        return {"scale": ones_init(key, (self.dim,))}

    def apply(self, params, x):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + self.eps) * params["scale"]
        return y.astype(x.dtype)


LANES = 128  # a TPU vreg's lane count: the minor tile of a row-major array


@functools.partial(jax.jit, static_argnums=1)
def _pack_rows(table, k):
    v, d = table.shape
    table = jnp.pad(table, ((0, -v % k), (0, 0)))
    return table.reshape(-1, k * d)


@dataclasses.dataclass(frozen=True)
class Embedding:
    """A (vocab, dim) lookup table.

    ``pack`` gives the serving layout of a narrow table: with
    ``k = LANES // dim`` rows per packed row, a (V, dim) table becomes
    (ceil(V / k), LANES), row ``i`` being lane block ``i % k`` of packed row
    ``i // k``. On the TPU a 2-D float array narrower than 128 lanes is laid
    out column-major, so every program that gathers rows from it first
    copies the whole table to row-major; the packed table is row-major
    already and is gathered in place. ``apply`` reads either form, bit for
    bit the same, from the table's width."""

    vocab: int
    dim: int
    init_std: float = 0.02

    def init(self, key):
        return {"table": self.init_std * jax.random.normal(key, (self.vocab, self.dim))}

    def rows_per_packed_row(self, params) -> int:
        """``k``: how many rows of the table one stored row holds (1 for a
        table stored as it was made)."""
        return params["table"].shape[-1] // self.dim

    def pack(self, params):
        """The table lane-packed (class docstring); a table that is packed
        already, or whose ``dim`` is not a proper divisor of ``LANES``, is
        returned as it is. Pad rows are zero and never gathered: ids lie in
        ``[0, vocab)``."""
        k = LANES // self.dim
        if (self.rows_per_packed_row(params) != 1 or k == 1
                or LANES % self.dim):
            return params
        return {**params, "table": _pack_rows(params["table"], k)}

    def apply(self, params, ids):
        table = params["table"]
        k = self.rows_per_packed_row(params)
        if k == 1:
            return jnp.take(table, ids, axis=0)
        # ids lie in [0, vocab) (``pack``), so the packed row is in range
        # and the gather needs no out-of-range fill pass
        rows = table.at[ids // k].get(mode="promise_in_bounds")  # (..., k·dim)
        lane = (ids % k)[..., None]
        out = rows[..., :self.dim]
        for j in range(1, k):
            out = jnp.where(lane == j,
                            rows[..., j * self.dim:(j + 1) * self.dim], out)
        return out

    def attend(self, params, x):
        """Tied-embedding logits: x @ table.T"""
        return jnp.einsum("...d,vd->...v", x, params["table"])


ACTIVATIONS: dict[str, Callable] = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "dice": None,  # handled inside MLP (needs params)
    "identity": lambda x: x,
}


@dataclasses.dataclass(frozen=True)
class PReLU:
    """PReLU used by DIN-family CTR towers."""

    dim: int

    def init(self, key):
        return {"alpha": 0.25 * ones_init(key, (self.dim,))}

    def apply(self, params, x):
        return jnp.where(x >= 0, x, params["alpha"] * x)


@dataclasses.dataclass(frozen=True)
class MLP:
    """Multi-layer perceptron with configurable hidden sizes + activation."""

    in_dim: int
    hidden: Sequence[int]
    activation: str = "relu"
    final_activation: str = "identity"
    use_bias: bool = True

    def _dims(self):
        return [self.in_dim, *self.hidden]

    def init(self, key):
        kg = KeyGen(key)
        dims = self._dims()
        return {
            f"fc{i}": Linear(dims[i], dims[i + 1], self.use_bias).init(kg())
            for i in range(len(dims) - 1)
        }

    def apply(self, params, x):
        dims = self._dims()
        n = len(dims) - 1
        act = ACTIVATIONS[self.activation]
        for i in range(n):
            x = Linear(dims[i], dims[i + 1], self.use_bias).apply(params[f"fc{i}"], x)
            if i < n - 1:
                x = act(x)
        return ACTIVATIONS[self.final_activation](x)


@dataclasses.dataclass(frozen=True)
class GatedMLP:
    """SwiGLU/GeGLU FFN used by the LM family: out = W2(act(W1 x) * W3 x)."""

    d_model: int
    d_ff: int
    activation: str = "silu"
    use_bias: bool = False

    def init(self, key):
        kg = KeyGen(key)
        return {
            "wi_gate": Linear(self.d_model, self.d_ff, self.use_bias).init(kg()),
            "wi_up": Linear(self.d_model, self.d_ff, self.use_bias).init(kg()),
            "wo": Linear(self.d_ff, self.d_model, self.use_bias).init(kg()),
        }

    def apply(self, params, x):
        act = ACTIVATIONS[self.activation]
        gate = Linear(self.d_model, self.d_ff, self.use_bias).apply(params["wi_gate"], x)
        up = Linear(self.d_model, self.d_ff, self.use_bias).apply(params["wi_up"], x)
        return Linear(self.d_ff, self.d_model, self.use_bias).apply(
            params["wo"], act(gate) * up
        )
