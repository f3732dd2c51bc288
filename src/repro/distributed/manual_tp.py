"""Manual Megatron-style tensor-parallel FFN with explicit collectives.

Why this exists (EXPERIMENTS.md §Perf, hillclimb 1): under GSPMD
auto-sharding the TP activation combine compiles to a full f32 all-reduce
followed by a slice — iterations 5/7/8 proved that casts, master-weight
dtypes and constraint placement cannot steer it. This layer takes manual
control via shard_map:

    x (B, T/tp, d)  --all_gather(model, bf16)-->  x_full (B, T, d)
    wi shards       --all_gather(data,  bf16)-->  (d, f/tp)      [FSDP gather]
    h = act(x@wi_g) * (x@wi_u)                    (B, T, f/tp)   [local MXU]
    y_partial = h @ wo_shard                      (B, T, d)
    --psum_scatter(model, dim=T)-->               (B, T/tp, d)   [RS, not AR!]

Wire bytes per layer per chip vs the GSPMD path: all-gathers move bf16
(2× less) and the combine is a reduce-scatter (tp× less than all-reduce).
Differentiable end-to-end (all_gather/psum_scatter have transpose rules),
remat- and scan-compatible (same discipline as the MoE layer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.layers import ACTIVATIONS


def manual_tp_gated_ffn(
    x: jax.Array,          # (B, T, d) — T sharded over ctx.model_axis (SP)
    params: dict,          # {"wi_gate": {"w": (d, f)}, "wi_up", "wo": (f, d)}
    ctx,                   # MeshCtx
    activation: str = "silu",
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    mesh = ctx.mesh
    model = ctx.model_axis
    dp = ctx.data_axes or ()
    act = ACTIVATIONS[activation]

    def body(x_l, wg_l, wu_l, wo_l):
        # x_l: (B_l, T/tp, d); wi shards (d/dp, f/tp); wo shard (f/tp, d/dp)
        xg = jax.lax.all_gather(x_l.astype(compute_dtype), model,
                                axis=1, tiled=True)            # (B_l, T, d)
        if dp:
            wg = jax.lax.all_gather(wg_l.astype(compute_dtype), dp, axis=0, tiled=True)
            wu = jax.lax.all_gather(wu_l.astype(compute_dtype), dp, axis=0, tiled=True)
            wo = jax.lax.all_gather(wo_l.astype(compute_dtype), dp, axis=1, tiled=True)
        else:
            wg, wu, wo = (w.astype(compute_dtype) for w in (wg_l, wu_l, wo_l))
        h = act(jnp.einsum("btd,df->btf", xg, wg)) * jnp.einsum("btd,df->btf", xg, wu)
        y_part = jnp.einsum("btf,fd->btd", h, wo)              # partial over model
        y = jax.lax.psum_scatter(y_part, model, scatter_dimension=1, tiled=True)
        return y.astype(x_l.dtype)

    x_spec = P(dp if dp else None, model, None)
    # weight shards as stored (sharding.py + zero1): wi (d, f): FSDP on d,
    # TP on f; wo (f, d): TP on f, FSDP on d
    wi_spec = P(dp if dp else None, model)
    wo_spec = P(model, dp if dp else None)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, wi_spec, wi_spec, wo_spec),
        out_specs=x_spec,
        check_vma=False,
    )(x, params["wi_gate"]["w"], params["wi_up"]["w"], params["wo"]["w"])
