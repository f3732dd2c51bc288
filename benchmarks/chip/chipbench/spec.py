"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  (the ``file`` of the configuration entry),
* ``traffic/<traffic>.json``,
* ``metrics/<metric>.py``, which defines ``read(ctx)``;
* ``towers/<arch>.py``, the short-term tower a configuration's ``arch``
  names (see ``tower``).

A later cell, configuration, mix, metric or tower is added by adding
files and entries; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import functools
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


@functools.lru_cache(maxsize=None)
def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def frozen(cfg: dict) -> tuple:
    """A configuration as a hashable static argument of a jitted function:
    its top-level numbers, strings and lists (as tuples), sorted by key.
    Nested groups (``limits``, ``control``, ``assumed``) are left out."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items() if not isinstance(v, dict)))


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    return _load_module(path,
                        "chipbench_metric_" + name.replace(".", "_")).read


def tower(arch: str, bench_dir: str = None):
    """The module ``towers/<arch>.py`` (under ``BENCH_DIR`` when
    ``bench_dir`` is not given): the tower of every configuration whose
    ``arch`` is ``arch``. It defines

    * ``config_kwargs(cfg)``: the ``CTRConfig`` keywords beyond the shared
      widths;
    * ``head_in(cfg)``: the MLP head's input width;
    * ``params(key, cfg)``: the tower's own weights, in the layout
      ``models/ctr.py`` reads, drawn from ``key`` inside the one jitted
      call that makes every weight (``chipbench/weights.py``);
    * ``short_interest(w, q, seq, mask, *, cfg, dtype)``: the plain
      reference of the short-term branch, candidates ``q`` (C, e) over the
      recent window ``seq`` (s, e) -> (C, head_in - 2e - ctx_dim);
    * ``flops(C, cfg)``: the branch's model operations per request of C
      candidates (``chipbench/shapes.py`` adds the head's);
    * ``PUBLISHED``: by a configuration's ``source``, the widths that the
      source publishes, which the configuration keeps.
    """
    path = os.path.join(BENCH_DIR if bench_dir is None else bench_dir,
                        "towers", arch + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"configuration arch {arch!r} has no tower "
                                f"at {path}")
    return _load_module(path, "chipbench_tower_" + arch.replace(".", "_"))
