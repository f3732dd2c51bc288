"""Roofline extraction: HLO collective parsing + cost accounting sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import roofline as rl


def test_shape_bytes():
    assert rl.shape_bytes("f32[8,128]") == 8 * 128 * 4
    assert rl.shape_bytes("bf16[2,2,2]") == 16
    assert rl.shape_bytes("pred[16]") == 16
    assert rl.shape_bytes("f32[]") == 4
    assert rl.shape_bytes("token[]") == 0


def test_parse_collective_bytes_synthetic_hlo():
    hlo = """
  %all-reduce.1 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %x), replica_groups={}
  %ag = bf16[4,4]{1,0} all-gather(bf16[2,4]{1,0} %y), dimensions={0}
  %t = (f32[2]{0}, f32[4]{0}) all-to-all(f32[2]{0} %a, f32[4]{0} %b)
  %add.5 = f32[8,16]{1,0} add(f32[8,16]{1,0} %x, f32[8,16]{1,0} %x)
"""
    out = rl.parse_collective_bytes(hlo)
    assert out["all-reduce"] == 8 * 16 * 4
    assert out["all-gather"] == 4 * 4 * 2
    assert out["all-to-all"] == 2 * 4 + 4 * 4
    assert out["collective-permute"] == 0


def test_cost_analysis_is_per_partition():
    """Document + pin the XLA behavior our roofline relies on: a psum-summed
    sharded matmul reports ~per-partition FLOPs, not global."""
    import subprocess, sys, os, json

    code = """
import jax, jax.numpy as jnp, json
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.distributed.compat import make_auto_mesh
mesh = make_auto_mesh((4,), ("d",))
N = 512
x = jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=NamedSharding(mesh, P("d", None)))
w = jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=NamedSharding(mesh, P(None, None)))
with mesh:
    c = jax.jit(lambda a, b: a @ b).lower(x, w).compile()
ca = c.cost_analysis()
ca = ca[0] if isinstance(ca, list) else ca
print(json.dumps({"flops": float(ca.get("flops", 0))}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    flops = json.loads(out.stdout.splitlines()[-1])["flops"]
    global_flops = 2 * 512 ** 3
    # per-partition = global/4; accept anything clearly below global
    assert flops < 0.6 * global_flops, (flops, global_flops)


def test_roofline_record_terms_and_bottleneck():
    r = rl.RooflineRecord(
        name="t", n_chips=256,
        flops_per_chip=197e12,          # exactly 1 s of compute
        hbm_bytes_per_chip=819e9 / 2,   # 0.5 s
        collective_bytes_per_chip=50e9 * 2,  # 2 s
        collective_breakdown={}, peak_memory_per_chip=0.0,
        model_flops=197e12 * 256,       # ideal == compute term
        peaks=rl.PEAKS[rl.V5E],
    )
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 0.5) < 1e-9
    assert abs(r.t_collective - 2.0) < 1e-9
    assert r.bottleneck == "collective"
    assert abs(r.roofline_time - 2.0) < 1e-9
    assert abs(r.roofline_fraction - 0.5) < 1e-9  # ideal 1 s / roofline 2 s


def test_peaks_are_keyed_by_device_kind():
    """Time terms exist only with the peaks of a listed device kind; a
    record without peaks keeps its costs and refuses to be timed."""
    assert rl.PEAKS[rl.V5E].flops == 197e12 and rl.PEAKS[rl.V5E].hbm_bw == 819e9
    assert "cpu" not in rl.PEAKS
    r = rl.RooflineRecord(
        name="t", n_chips=1, flops_per_chip=1.0, hbm_bytes_per_chip=2.0,
        collective_bytes_per_chip=0.0, collective_breakdown={},
        peak_memory_per_chip=0.0)
    assert r.hbm_bytes_per_chip == 2.0
    for term in ("t_compute", "t_memory", "roofline_time"):
        with pytest.raises(ValueError, match="no device peaks"):
            getattr(r, term)


def test_unrolled_cost_linear_in_depth():
    """The extrapolation assumption: unrolled per-layer costs are additive."""
    import subprocess, sys, os, json

    code = """
import jax, jax.numpy as jnp, json
from repro.models.lm import LMModel, LMConfig
import dataclasses
outs = {}
for L in (2, 4, 6):
    cfg = LMConfig(name="t", n_layers=L, d_model=64, n_heads=4, n_kv_heads=2,
                   head_dim=16, d_ff=128, vocab=128, remat="none", scan_unroll=True)
    m = LMModel(cfg)
    p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    c = jax.jit(m.loss).lower(p, toks, toks).compile()
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    outs[L] = float(ca.get("flops", 0))
print(json.dumps(outs))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    f = {int(k): v for k, v in json.loads(out.stdout.splitlines()[-1]).items()}
    d1 = f[4] - f[2]
    d2 = f[6] - f[4]
    assert abs(d1 - d2) / max(d1, d2) < 0.05, f
