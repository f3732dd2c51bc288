"""GatedGCN [arXiv:1711.07553 / benchmarking-gnns 2003.00982].

Message passing is edge-list based (JAX has no CSR): per layer,

    e'_ij = e_ij + ReLU(LN(A h_i + B h_j + C e_ij))
    η_ij  = σ(e'_ij) / (Σ_{j→i} σ(e'_ij) + ε)          (gated, degree-normalized)
    h'_i  = h_i + ReLU(LN(U h_i + Σ_{j→i} η_ij ⊙ V h_j))

The Σ_{j→i} is a ``jax.ops.segment_sum`` scatter over ``edge_index`` — this
IS the system's GNN kernel. (LayerNorm replaces the original BatchNorm: the
standard JAX full-graph reproduction choice; noted in DESIGN.md.)

Distribution: edges are sharded over the *entire* device grid
(``shard_map`` over ("data","model") flattened), each shard scatter-adds
into a replicated node array, one psum combines — the edge-partitioned
regime appropriate for |E| ≫ |V| graphs like ogb_products (62M edges).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn.layers import LayerNorm, Linear, MLP
from repro.nn.module import KeyGen

Params = Any


@dataclasses.dataclass(frozen=True)
class GatedGCNConfig:
    n_layers: int = 16
    d_hidden: int = 70
    d_feat: int = 1433
    d_edge: int = 0             # 0 -> learned constant edge init
    n_classes: int = 16
    readout: str = "node"       # "node" (classification) | "graph" (regression)
    remat: bool = True
    unroll: bool = False        # unrolled lowering (accurate roofline counts)


def _layer_init(key, h: int) -> Params:
    kg = KeyGen(key)
    return {
        "A": Linear(h, h).init(kg()),
        "B": Linear(h, h).init(kg()),
        "C": Linear(h, h).init(kg()),
        "U": Linear(h, h).init(kg()),
        "V": Linear(h, h).init(kg()),
        "ln_h": LayerNorm(h).init(kg()),
        "ln_e": LayerNorm(h).init(kg()),
    }


def _layer_apply(params, h, e, src, dst, edge_mask, n_nodes: int, d: int,
                 mesh=None, axes=("data", "model")):
    """One GatedGCN layer. h (N, d); e (E, d); src/dst (E,)."""
    lin = lambda name, x: Linear(d, d).apply(params[name], x)

    h_src = jnp.take(h, src, axis=0)
    h_dst = jnp.take(h, dst, axis=0)
    e_new = lin("A", h_dst) + lin("B", h_src) + lin("C", e)
    e_new = e + jax.nn.relu(LayerNorm(d).apply(params["ln_e"], e_new))

    gate = jax.nn.sigmoid(e_new)
    if edge_mask is not None:
        gate = gate * edge_mask[:, None]
    msg = gate * lin("V", h_src)

    if mesh is None:
        agg = jax.ops.segment_sum(msg, dst, n_nodes)
        norm = jax.ops.segment_sum(gate, dst, n_nodes)
    else:
        def scatter(msg_l, gate_l, dst_l):
            a = jax.ops.segment_sum(msg_l, dst_l, n_nodes)
            n = jax.ops.segment_sum(gate_l, dst_l, n_nodes)
            return jax.lax.psum((a, n), axes)

        agg, norm = jax.shard_map(
            scatter, mesh=mesh,
            in_specs=(P(axes), P(axes), P(axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )(msg, gate, dst)

    h_agg = agg / (norm + 1e-6)
    h_new = lin("U", h) + h_agg
    h = h + jax.nn.relu(LayerNorm(d).apply(params["ln_h"], h_new))
    return h, e_new


class GatedGCN:
    def __init__(self, cfg: GatedGCNConfig):
        self.cfg = cfg

    def init(self, key) -> Params:
        cfg = self.cfg
        kg = KeyGen(key)
        keys = jax.random.split(kg(), cfg.n_layers)
        p = {
            "node_enc": Linear(cfg.d_feat, cfg.d_hidden).init(kg()),
            "edge_enc": Linear(max(cfg.d_edge, 1), cfg.d_hidden).init(kg()),
            "layers": jax.vmap(lambda k: _layer_init(k, cfg.d_hidden))(keys),
            "out": MLP(cfg.d_hidden, [cfg.d_hidden, cfg.n_classes], "relu").init(kg()),
        }
        return p

    def forward(self, params, graph: dict, mesh=None,
                axes=("data", "model")) -> jax.Array:
        """graph: x (N,F), edge_index (2,E), optional edge_attr (E,de),
        edge_mask (E,), graph_ids (N,). Returns node logits (N, C) or graph
        outputs (n_graphs, C). ``axes``: mesh axes the edge dim shards over."""
        cfg = self.cfg
        x = graph["x"]
        src, dst = graph["edge_index"][0], graph["edge_index"][1]
        n_nodes = x.shape[0]
        h = Linear(cfg.d_feat, cfg.d_hidden).apply(params["node_enc"], x)
        ea = graph.get("edge_attr")
        if ea is None:
            ea = jnp.ones((src.shape[0], 1), h.dtype)
        e = Linear(max(cfg.d_edge, 1), cfg.d_hidden).apply(params["edge_enc"], ea)
        edge_mask = graph.get("edge_mask")

        def body(carry, layer_params):
            h, e = carry
            h, e = _layer_apply(layer_params, h, e, src, dst, edge_mask,
                                n_nodes, cfg.d_hidden, mesh=mesh, axes=axes)
            return (h, e), None

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        if cfg.unroll:
            carry = (h, e)
            for i in range(cfg.n_layers):
                layer = jax.tree_util.tree_map(lambda p: p[i], params["layers"])
                carry, _ = body(carry, layer)
            h, e = carry
        else:
            (h, e), _ = jax.lax.scan(body, (h, e), params["layers"])

        if cfg.readout == "graph":
            gid = graph["graph_ids"]
            n_graphs = graph["n_graphs"]
            pooled = jax.ops.segment_sum(h, gid, n_graphs)
            counts = jax.ops.segment_sum(jnp.ones((h.shape[0], 1), h.dtype), gid, n_graphs)
            h = pooled / jnp.maximum(counts, 1.0)
        return MLP(cfg.d_hidden, [cfg.d_hidden, cfg.n_classes], "relu").apply(
            params["out"], h
        )

    def loss(self, params, graph: dict, mesh=None, axes=("data", "model")):
        out = self.forward(params, graph, mesh=mesh, axes=axes)
        if self.cfg.readout == "graph":
            return jnp.mean(jnp.square(out - graph["y"]))
        y = graph["y"]
        logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[..., 0]
        node_mask = graph.get("node_mask")
        if node_mask is not None:
            return jnp.sum(nll * node_mask) / (jnp.sum(node_mask) + 1e-9)
        return jnp.mean(nll)
