"""Compile every serving kernel for a described TPU v5e at the paper's widths.

Interpret-mode parity (tests/test_kernels.py) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, vector reshapes
Mosaic cannot lower, too much VMEM. The TPU compiler is installed without a
chip, so each case here lowers and compiles for a ``v5e:2x2`` topology that
is described, not attached, and checks that the kernel is in the program
(``tpu_custom_call``). Nothing runs; a pass is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# paper widths: m=48, tau=3 -> G*U = 16*8 = 128; d=128; L=1024; C=128
M, TAU, D, L, C, B, N, E = 48, 3, 128, 1024, 128, 8, 1024, 16
G, U = M // TAU, 1 << TAU


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compiles_kernel(fn, *args) -> None:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _kernel_cases(spec):
    from repro.kernels.sdim_bucket.sdim_bucket import bse_encode
    from repro.kernels.sdim_fused_serve.sdim_fused_serve import \
        sdim_fused_serve
    from repro.kernels.sdim_query.sdim_query import sdim_query
    from repro.kernels.sdim_serve.sdim_serve import bse_serve
    from repro.kernels.sdim_update.sdim_update import sdim_update
    from repro.kernels.target_attn.target_attn import target_attention_flash

    r, slots, present = spec((M, D)), spec((B,), jnp.int32), spec((B,), bool)
    q, seq, mask = spec((B, C, D)), spec((B, L, D)), spec((B, L))
    return {
        "sdim_bucket": (lambda s, mk, r: bse_encode(s, mk, r, TAU),
                        (seq, mask, r)),
        "sdim_query": (lambda q, t, r: sdim_query(q, t, r, TAU),
                       (q, spec((B, G, U, D)), r)),
        "sdim_serve": (lambda q, s, mk, r: bse_serve(q, s, mk, r, TAU),
                       (q, seq, mask, r)),
        "sdim_update": (lambda st, sl, ev, mk, r: sdim_update(
            st, sl, ev, mk, r, TAU),
            (spec((N, G, U, D)), slots, spec((B, E, D)), spec((B, E)), r)),
        "sdim_fused_serve_fp32": (lambda st, sl, q, r, pr: sdim_fused_serve(
            st, sl, q, r, TAU, present=pr),
            (spec((N, G, U, D)), slots, q, r, present)),
        "sdim_fused_serve_int8": (lambda st, sl, q, r, sc, pr:
                                  sdim_fused_serve(st, sl, q, r, TAU,
                                                   scales=sc, present=pr),
                                  (spec((N, G, U, D), jnp.int8), slots, q, r,
                                   spec((N, G, U)), present)),
        "target_attn": (target_attention_flash, (q, seq, mask)),
    }


@pytest.mark.parametrize("kernel", [
    "sdim_bucket", "sdim_query", "sdim_serve", "sdim_update",
    "sdim_fused_serve_fp32", "sdim_fused_serve_int8", "target_attn"])
def test_kernel_compiles_for_v5e(spec, kernel):
    fn, args = _kernel_cases(spec)[kernel]
    _compiles_kernel(fn, *args)


@pytest.mark.parametrize("op", ["update", "fused_serve_fp32",
                                "fused_serve_int8"])
def test_sharded_store_op_compiles_for_v5e_2x2(topo, op):
    """The row-sharded store's one-dispatch ops over all four chips of the
    described host: each shard runs the Pallas kernel on its own rows."""
    from repro.core import engine

    mesh = jax.sharding.Mesh(topo.devices, ("model",))
    S, cap = len(topo.devices), N // len(topo.devices)

    def on(shape, spec_, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec_))

    store = on((S, cap, G, U, D), P("model"),
               jnp.int8 if op == "fused_serve_int8" else jnp.float32)
    ids = on((B,), P(), jnp.int32)
    r = on((M, D), P())
    if op == "update":
        fn = engine._sharded_update_fn(mesh, "model", TAU, "pallas", 128,
                                       False, True)
        args = (store, ids, ids, on((B, E, D), P()), on((B, E), P()), r)
    else:
        quantized = op == "fused_serve_int8"
        fn = engine._sharded_fused_serve_fn(mesh, "model", TAU, "pallas",
                                            128, False, quantized)
        head = (store, on((S, cap, G, U), P("model"))) if quantized \
            else (store,)
        args = (*head, ids, ids, on((B,), P(), bool), on((B, C, D), P()), r)
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
