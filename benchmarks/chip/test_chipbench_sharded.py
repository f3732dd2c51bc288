"""A four-chip cell's run on four virtual CPU devices: the harness builds
the program's row-sharded store over a mesh of the cell's chips and
drives set-up, the window and the check through it, at the toy size of
``test_chipbench_faults.py``. The check passes a sound run and fails the
same run with each fault the cell can have planted under the timed path
(a bucket table written wrong at ingest; the psum that gathers the
burst's interest vectors from every shard left out; a score altered
where the scorer produces it), and the control.

The device count is fixed before JAX starts, so the runs are one
subprocess (as in ``tests/test_sharded_store.py``) with a time limit of
its own. Its runs keep their compiled programs from one to the next:
``jax.clear_caches``, with which a run frees the chip after its check,
does nothing there."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
sys.path.insert(0, HERE)

from chipbench import checks  # noqa: E402

FAULTS = ("tables", "exchange", "scores")
# the number each fault has to fail
CAUGHT_BY = {"tables": "tables", "exchange": "interest", "scores": "scores"}

SCRIPT = """
import json
import jax
import pytest
from chipbench import harness, spec
from repro.core import engine
import control
import test_chipbench_faults as faults

jax.clear_caches = lambda: None


def leave_out_the_exchange(mp):
    serve = engine.SDIMEngine.serve_fused_sharded
    psum = jax.lax.psum

    def alone(self, *a, **k):
        jax.lax.psum = lambda x, axis, **kw: x
        try:
            return serve(self, *a, **k)
        finally:
            jax.lax.psum = psum

    mp.setattr(engine.SDIMEngine, "serve_fused_sharded", alone)


cfg = faults.toy("sdim_paper_fp32x4_fused")
out = {}
for fault in FAULTS + (None,):
    engine._sharded_fused_serve_fn.cache_clear()
    with pytest.MonkeyPatch.context() as mp:
        if fault == "exchange":
            leave_out_the_exchange(mp)
        elif fault:
            faults.plant(mp, fault)
        cell = spec.Cell("toy", 4, cfg["name"], cfg, "toy", faults.MIX,
                         [], [])
        run = harness.Run(cell, 2**31 + 77, 0.5, False, log=lambda *a: None)
        run.require_chip = False
        run.setup()
        store = run.server.bse.store
        shape = [type(store).__name__, store.n_shards, store.capacity,
                 store.shard_load()]
        run.window()
        out[str(fault)] = {"check": run.check(), "store": shape,
                           "failed": run.failed, "errors": run.errors,
                           "compiles": run.compiles.n}
out["control"] = control.readings(cfg, faults.MIX, 3, 1.0)
print(json.dumps(out))
""".replace("FAULTS", repr(FAULTS))


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([HERE, SRC]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=HERE,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_four_chip_cell_runs_on_the_sharded_store(runs):
    for fault in FAULTS + ("None",):
        r = runs[fault]
        assert (r["failed"], r["errors"], r["compiles"]) == (0, 0, 0), r
        # built at one chip's share, grown per shard to the population
        assert r["store"] == ["ShardedTableStore", 4, 1024,
                              [256, 256, 256, 256]], r
    assert checks.passed(runs["None"]["check"]), runs["None"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_four_chip_check_fails_each_fault(runs, fault):
    bad = runs[fault]["check"]
    assert not checks.passed(bad), bad
    caught = CAUGHT_BY[fault]
    assert bad[caught]["value"] > bad[caught]["limit"], bad


def test_the_four_chip_control_comes_out_not_correct(runs):
    assert not checks.passed(runs["control"]), runs["control"]
