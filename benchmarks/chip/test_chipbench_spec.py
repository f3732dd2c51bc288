"""The benchmark's data-driven layout: every name in BENCHMARK.json finds
its file, and the entry point refuses a host without a TPU."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import peaks, spec  # noqa: E402

BENCH = spec.load_benchmark()
WIDTHS = ("n_items", "n_cats", "embed_dim", "short_len", "long_len",
          "mlp_hidden", "ctx_dim", "m", "tau")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name):
    cell = spec.load_cell(name, BENCH)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["rate_per_s"] > 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert cell.config["population"] % 1024 == 0
    assert set(cell.config["limits"]) == {"tables", "interest", "scores"}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(spec.metric_reader(metric))


def test_a_metric_without_a_reader_is_an_error(tmp_path):
    (tmp_path / "metrics").mkdir()
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric", str(tmp_path))


def test_a_new_metric_is_found_by_its_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "answer.v2.py").write_text(
        "def read(ctx):\n    return ctx * 2\n")
    assert spec.metric_reader("answer.v2", str(tmp_path))(21) == 42


def test_configs_keep_the_paper_widths():
    paper = {"n_items": 10_000_000, "n_cats": 100_000, "embed_dim": 64,
             "short_len": 50, "long_len": 1024, "mlp_hidden": [1024, 512, 256],
             "ctx_dim": 4, "m": 48, "tau": 3}
    for c in BENCH["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert {k: cfg[k] for k in WIDTHS} == paper
        assert c["reduced"] == cfg["reduced"] == ["population"]


def test_unlisted_device_has_no_peaks():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_run_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
