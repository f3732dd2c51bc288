"""Distributed-path correctness on a small fake-device mesh.

These run in SUBPROCESSES because XLA_FLAGS device-count must be set before
jax initializes, and the main pytest process must keep seeing 1 device
(smoke tests / benches contract)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.distributed.compat import make_auto_mesh
mesh = make_auto_mesh((2, 4), ("data", "model"))
"""


def test_moe_shard_map_matches_local():
    out = run_sub(PREAMBLE + """
from repro.nn.moe import MoELayer
layer = MoELayer(d_model=32, d_ff=16, n_experts=8, top_k=2, n_shared=1,
                 capacity_factor=64.0)
p = layer.init(jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
y_ref, _ = layer.apply(p, x)
with mesh:
    y, _ = jax.jit(lambda p, x: layer.apply(p, x, mesh=mesh))(p, x)
print(json.dumps({"diff": float(jnp.max(jnp.abs(y - y_ref)))}))
""")
    assert json.loads(out.splitlines()[-1])["diff"] < 1e-5


def test_sp_decode_matches_exact_on_mesh():
    out = run_sub(PREAMBLE + """
from repro.models.lm import LMModel, LMConfig
from repro.distributed.mesh_ctx import MeshCtx
cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
               head_dim=8, d_ff=64, vocab=64, remat="none")
m = LMModel(cfg)
p = m.init(jax.random.PRNGKey(0))
caches = m.init_cache(4, 16, jnp.float32)
for i in range(12):
    t = jax.random.randint(jax.random.PRNGKey(i), (4, 1), 0, 64)
    _, caches = m.decode_step(p, t, caches, i)
tok = jax.random.randint(jax.random.PRNGKey(99), (4, 1), 0, 64)
lg_ref, _ = m.decode_step(p, tok, caches, 12)
diffs = {}
for name, ctx in [("long", MeshCtx(mesh, data_axes=None, seq_axes=("data", "model"))),
                  ("batch", MeshCtx(mesh, data_axes=("data",), seq_axes=("model",)))]:
    with mesh:
        lg, _ = jax.jit(lambda p, t, c: m.sp_decode_step(p, t, c, 12, ctx))(p, tok, caches)
    diffs[name] = float(jnp.max(jnp.abs(lg_ref - lg)))
print(json.dumps(diffs))
""")
    d = json.loads(out.splitlines()[-1])
    assert d["long"] < 1e-4 and d["batch"] < 1e-4, d


def test_gnn_edge_sharded_matches_local():
    out = run_sub(PREAMBLE + """
from repro.models.gnn import GatedGCN, GatedGCNConfig
from repro.data.graph import random_graph
cfg = GatedGCNConfig(n_layers=3, d_hidden=16, d_feat=8, n_classes=4, remat=False)
g = random_graph(64, 256, 8, seed=0, n_classes=4)
graph = {k: jnp.asarray(v) for k, v in g.items()}
model = GatedGCN(cfg)
p = model.init(jax.random.PRNGKey(0))
loss_ref = model.loss(p, graph)
with mesh:
    loss_sh = jax.jit(lambda p, g: model.loss(p, g, mesh=mesh,
                                              axes=("data", "model")))(p, graph)
print(json.dumps({"diff": abs(float(loss_ref - loss_sh))}))
""")
    assert json.loads(out.splitlines()[-1])["diff"] < 1e-5


def test_lm_train_step_on_mesh_with_sharded_params():
    """Sharded-param LM train step == single-device step (same loss)."""
    out = run_sub(PREAMBLE + """
from repro.models.lm import LMModel, LMConfig
from repro.distributed.mesh_ctx import MeshCtx
from repro.distributed.sharding import shard_params
cfg = LMConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               head_dim=16, d_ff=128, vocab=128, remat="full")
m = LMModel(cfg)
p = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128)
tgts = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 128)
l_ref = m.loss(p, toks, tgts)
ctx = MeshCtx(mesh, data_axes=("data",), act_seq_shard=True)
with mesh:
    ps = shard_params(p, "lm", mesh)
    l_sh = jax.jit(lambda p, a, b: m.loss(p, a, b, mesh=ctx))(ps, toks, tgts)
    g = jax.jit(jax.grad(lambda p: m.loss(p, toks, tgts, mesh=ctx)))(ps)
gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree_util.tree_leaves(g))
print(json.dumps({"diff": abs(float(l_ref - l_sh)), "gnorm_pos": gn > 0}))
""")
    d = json.loads(out.splitlines()[-1])
    assert d["diff"] < 1e-3 and d["gnorm_pos"], d


def test_elastic_restore_across_meshes():
    """Checkpoint saved unsharded restores onto a (2,4) mesh with rules,
    then onto a (4,2) mesh — elastic re-meshing."""
    out = run_sub(PREAMBLE + """
import tempfile
from repro.models.ctr import CTRModel, CTRConfig
from repro.core.interest import InterestConfig
from repro.train import checkpoint as ck
from repro.train.elastic import restore_on_mesh
from repro.distributed.sharding import param_spec, valid_for_mesh
cfg = CTRConfig(arch="din", n_items=512, n_cats=16, long_len=32, short_len=8,
                mlp_hidden=(16,), interest=InterestConfig(kind="sdim", m=8, tau=2))
model = CTRModel(cfg)
p = model.init(jax.random.PRNGKey(0))
rules = lambda path, shape: valid_for_mesh(param_spec("recsys", path, shape), shape, mesh)
with tempfile.TemporaryDirectory() as d:
    ck.save(d, 3, {"params": p})
    r1, s1 = restore_on_mesh(d, {"params": p}, mesh, rules)
    mesh2 = make_auto_mesh((4, 2), ("data", "model"))
    rules2 = lambda path, shape: valid_for_mesh(param_spec("recsys", path, shape), shape, mesh2)
    r2, s2 = restore_on_mesh(d, {"params": p}, mesh2, rules2)
ok = all(bool(jnp.all(a == b)) for a, b in zip(
    jax.tree_util.tree_leaves(r1["params"]), jax.tree_util.tree_leaves(r2["params"])))
sh = r1["params"]["item_emb"]["table"].sharding
print(json.dumps({"equal": ok, "sharded": str(sh.spec)}))
""")
    d = json.loads(out.splitlines()[-1])
    assert d["equal"] and "model" in d["sharded"], d


def test_compressed_psum_on_real_axis():
    out = run_sub(PREAMBLE + """
from repro.train.compression import compressed_psum
g = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
f = jax.shard_map(lambda t: compressed_psum({"g": t}, "data")["g"],
                  mesh=mesh, in_specs=(P("data", None),),
                  out_specs=P("data", None), check_vma=False)
with mesh:
    out = jax.jit(f)(g)
# per-shard mean of the two data shards, within int8 error
ref = (g[:4] + g[4:]) / 2
err = float(jnp.max(jnp.abs(out[:4] - ref)))
print(json.dumps({"err": err}))
""")
    assert json.loads(out.splitlines()[-1])["err"] < 0.05
