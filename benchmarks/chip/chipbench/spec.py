"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  (the ``file`` of the configuration entry),
* ``traffic/<traffic>.json``,
* ``metrics/<metric>.py``, which defines ``read(ctx)``.

A later cell, configuration, mix or metric is added by adding files and
entries; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list        # metric entries this cell reports
    per_layer: list


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _per_layer_of(bench: dict, cell: str) -> list:
    """The per-layer metrics a cell reports: those whose ``workloads``
    list it. Every per-layer metric carries that list."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise KeyError(f"per-layer metric {m['name']!r} has no "
                           f"workloads list")
        if cell in m["workloads"]:
            out.append(m)
    return out


def load_cell(name: str, bench: dict = None, root: str = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = _per_layer_of(bench, name)
    return Cell(name, w["chips"], w["config"], config, w["traffic"],
                traffic, e2e, per_layer)


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
