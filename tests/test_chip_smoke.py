"""The chip entry points, rehearsed on the CPU: ``chip_smoke.py`` refuses to
run without a TPU, its phases run end to end at the SMOKE widths with the
Pallas kernels interpreted, and the compile cache goes where it should.
What these show about the chip is nothing: that needs a TPU."""
import os
import subprocess
import sys

import jax

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import sdim_paper  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_chip_smoke_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_phases_on_cpu_interpret(capsys):
    chip_smoke.run_one_chip(sdim_paper.SMOKE, backend="pallas")
    out = capsys.readouterr().out
    for phase in ("ingest tables", "event-fold tables",
                  "two-dispatch interest", "two-dispatch scores",
                  "fused interest", "fused scores"):
        for dtype in ("float32", "int8"):
            assert f"[{dtype}] {phase}: max error" in out, (phase, dtype)


def test_chip_smoke_sharded_phase_on_four_cpu_devices():
    code = ("import chip_smoke\n"
            "from repro.configs import sdim_paper\n"
            "chip_smoke.run_sharded(sdim_paper.SMOKE, 'pallas', 4)\n"
            "print('SHARDED_OK')\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": SRC,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_OK" in r.stdout
    for phase in ("interest", "scores"):
        assert r.stdout.count(f"sharded fused {phase}: max error") == 2


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The environment's directory when it names one (JAX reads it itself,
    nothing is set), else the fixed ``<checkout>/.jax_cache``."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

