"""Mixture-of-Experts FFN (DeepSeek style: shared + fine-grained routed experts).

TPU mapping
-----------
Expert parallelism uses ``shard_map`` over the ``model`` mesh axis: activations
are replicated across the model axis (Megatron convention), so each model
shard simply *selects* the tokens routed to the experts it owns, runs a
capacity-bounded batched FFN ``(E_loc, C, d) x (E_loc, d, f)``, scatters the
weighted results back, and a single ``psum`` over the model axis combines
expert outputs — no all-to-all is required with replicated activations, which
is both simpler and cheaper than dispatch einsums at these expert counts.

Dispatch is sort-based (argsort by expert id + rank-within-expert via
searchsorted), never materializing a ``(T, E, C)`` one-hot: at
T=65k/E=160/C=3k that one-hot would be 3e13 elements.

A meshless path (``mesh_axis=None``) runs the identical dispatch with
``E_loc = E`` for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.layers import ACTIVATIONS
from repro.nn.module import KeyGen, lecun_normal

Params = Any


def _init_expert_ffn(key, n_experts: int, d_model: int, d_ff: int) -> Params:
    kg = KeyGen(key)
    return {
        "wi_gate": lecun_normal(kg(), (n_experts, d_model, d_ff)),
        "wi_up": lecun_normal(kg(), (n_experts, d_model, d_ff)),
        "wo": lecun_normal(kg(), (n_experts, d_ff, d_model), in_axis=-2),
    }


def _expert_ffn(w: Params, x: jax.Array, activation: str) -> jax.Array:
    """x: (E, C, d) -> (E, C, d), batched over experts."""
    act = ACTIVATIONS[activation]
    gate = jnp.einsum("ecd,edf->ecf", x, w["wi_gate"])
    up = jnp.einsum("ecd,edf->ecf", x, w["wi_up"])
    return jnp.einsum("ecf,efd->ecd", act(gate) * up, w["wo"])


def dispatch_combine(
    x: jax.Array,           # (T, d) local tokens
    topk_idx: jax.Array,    # (T, k) global expert ids
    topk_w: jax.Array,      # (T, k) gate weights
    expert_w: Params,       # (E_loc, d, f) weight slices for experts [e0, e0+E_loc)
    e0,                     # first owned expert id
    capacity: int,
    activation: str,
) -> jax.Array:
    """Capacity-bounded sort-based dispatch -> batched FFN -> weighted combine.

    Returns the *partial* output (T, d): contributions of owned experts only.
    """
    T, d = x.shape
    k = topk_idx.shape[1]
    E_loc = expert_w["wi_gate"].shape[0]
    N = T * k

    flat_e = topk_idx.reshape(N)
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = topk_w.reshape(N)

    le = flat_e - e0
    owned = (le >= 0) & (le < E_loc)
    le = jnp.where(owned, le, E_loc)  # sentinel sorts to the end

    order = jnp.argsort(le, stable=True)
    se = le[order]
    tok = flat_tok[order]
    w = flat_w[order]

    # rank within expert = index - first index of this expert id in sorted order
    pos = jnp.arange(N, dtype=jnp.int32) - jnp.searchsorted(
        se, se, side="left"
    ).astype(jnp.int32)
    valid = (se < E_loc) & (pos < capacity)
    e_idx = jnp.where(valid, se, E_loc)  # out of range => dropped by scatter

    buf = jnp.zeros((E_loc, capacity, d), x.dtype)
    buf = buf.at[e_idx, pos].set(x[tok], mode="drop")

    out_buf = _expert_ffn(expert_w, buf, activation)

    y = out_buf[jnp.where(valid, e_idx, 0), jnp.where(valid, pos, 0)]
    y = jnp.where(valid[:, None], y, 0.0) * w[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok].add(y)
    return out


@dataclasses.dataclass(frozen=True)
class MoELayer:
    """Shared + routed experts; gates = top-k of softmax router probs."""

    d_model: int
    d_ff: int                    # per-expert FFN width (fine-grained)
    n_experts: int               # routed experts
    top_k: int
    n_shared: int = 0
    activation: str = "silu"
    capacity_factor: float = 1.25
    routed_scaling: float = 1.0
    norm_topk_prob: bool = False
    aux_loss_coef: float = 0.001

    def init(self, key) -> Params:
        kg = KeyGen(key)
        p = {
            "router": {"w": 0.02 * jax.random.normal(kg(), (self.d_model, self.n_experts))},
            "experts": _init_expert_ffn(kg(), self.n_experts, self.d_model, self.d_ff),
        }
        if self.n_shared:
            p["shared"] = _init_expert_ffn(kg(), 1, self.d_model, self.d_ff * self.n_shared)
        return p

    def _route(self, params, x):
        """x: (B,T,d) -> probs (B,T,E), topk_idx (B,T,k), topk_w (B,T,k), aux loss."""
        logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), params["router"]["w"])
        probs = jax.nn.softmax(logits, axis=-1)
        topk_w, topk_idx = jax.lax.top_k(probs, self.top_k)
        if self.norm_topk_prob:
            topk_w = topk_w / (jnp.sum(topk_w, axis=-1, keepdims=True) + 1e-20)
        topk_w = topk_w * self.routed_scaling
        # switch-style load-balance loss
        E = self.n_experts
        onehot = jax.nn.one_hot(topk_idx[..., 0], E, dtype=jnp.float32)
        f = jnp.mean(onehot, axis=(0, 1))          # dispatch fraction (top-1 proxy)
        p_mean = jnp.mean(probs, axis=(0, 1))
        aux = self.aux_loss_coef * E * jnp.sum(f * p_mean)
        return probs, topk_idx.astype(jnp.int32), topk_w, aux

    def _capacity(self, tokens_local: int, n_experts_local_share: int) -> int:
        cap = int(tokens_local * self.top_k / self.n_experts * self.capacity_factor) + 1
        # round to a multiple of 8 lanes for friendlier layouts
        return max(8, ((cap + 7) // 8) * 8)

    def apply(
        self,
        params: Params,
        x: jax.Array,                 # (B, T, d)
        mesh=None,                    # Mesh | MeshCtx | None
    ):
        """Returns (out, aux_loss)."""
        from repro.distributed.mesh_ctx import MeshCtx

        ctx = MeshCtx.wrap(mesh)
        B, T, d = x.shape
        probs, topk_idx, topk_w, aux = self._route(params, x)

        if ctx is None:
            cap = self._capacity(B * T, 1)
            routed = dispatch_combine(
                x.reshape(B * T, d),
                topk_idx.reshape(B * T, self.top_k),
                topk_w.reshape(B * T, self.top_k).astype(x.dtype),
                params["experts"],
                0,
                cap,
                self.activation,
            ).reshape(B, T, d)
        else:
            model_axis = ctx.model_axis
            ep = ctx.ep
            dp = ctx.dp
            assert self.n_experts % ep == 0, (self.n_experts, ep)
            E_loc = self.n_experts // ep
            cap = self._capacity(max(B // dp, 1) * T, E_loc)
            tok_spec = P(ctx.data_axes, None, None) if ctx.data_axes else P(None, None, None)

            def routed_fn(x_l, idx_l, w_l, experts_l):
                Bl, Tl, _ = x_l.shape
                e0 = jax.lax.axis_index(model_axis) * E_loc
                out = dispatch_combine(
                    x_l.reshape(Bl * Tl, d),
                    idx_l.reshape(Bl * Tl, self.top_k),
                    w_l.reshape(Bl * Tl, self.top_k).astype(x_l.dtype),
                    experts_l,
                    e0,
                    cap,
                    self.activation,
                ).reshape(Bl, Tl, d)
                return jax.lax.psum(out, model_axis)

            routed = jax.shard_map(
                routed_fn,
                mesh=ctx.mesh,
                in_specs=(
                    tok_spec,
                    tok_spec,
                    tok_spec,
                    P(model_axis, None, None),
                ),
                out_specs=tok_spec,
                check_vma=False,
            )(x, topk_idx, topk_w, params["experts"])

        if self.n_shared:
            shared = _expert_ffn(
                params["shared"], x.reshape(1, B * T, d), self.activation
            ).reshape(B, T, d)
            routed = routed + shared
        return routed, aux
