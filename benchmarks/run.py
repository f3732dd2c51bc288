"""Benchmark harness — one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only table1,...]

``--smoke`` (the ``make bench-smoke`` CI gate) runs EVERY module at
pipeline-proof depth: training benchmarks shrink to a few dozen steps, so
the whole suite finishes in minutes — numbers exist but are not meaningful;
the point is that no benchmark is rotten.

Prints ``name,us_per_call,shards,derived`` CSV (plus a roofline summary read
from the dry-run artifacts, if present). ``shards`` is the device count the
row's table store was sharded over (``-`` where sharding doesn't apply);
table5 emits >1 when run under a host-local mesh, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import argparse
import json
import glob
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ALL = ["fig2", "table1", "table23", "table4", "fig5", "table5"]


def _module(name: str):
    import importlib

    return importlib.import_module({
        "fig2": "benchmarks.fig2_attention_patterns",
        "table1": "benchmarks.table1_complexity",
        "table23": "benchmarks.table23_auc",
        "table4": "benchmarks.table4_tau",
        "fig5": "benchmarks.fig5_m_sweep",
        "table5": "benchmarks.table5_serving",
    }[name])


def roofline_rows() -> list[dict]:
    rows = []
    for f in sorted(glob.glob("results/dryrun/*/*.json")):
        r = json.load(open(f))
        rf = r.get("roofline_fraction")
        rows.append({
            "name": f"roofline/{r['mesh']}/{r['arch']}/{r['shape']}"
                    + ("" if r.get("variant", "baseline") == "baseline"
                       else f"+{r['variant']}"),
            "us_per_call": 1e6 * max(r["t_compute_s"], r["t_memory_s"],
                                     r["t_collective_s"]),
            "derived": f"bottleneck={r['bottleneck']};"
                       f"hbm={r['hbm_total_per_chip_gib']}GiB;"
                       f"fits={r['fits_16gib']};"
                       f"roofline_frac={rf if rf is None else round(rf, 4)}",
        })
    return rows


def main() -> None:
    import inspect

    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true", help="long training runs")
    p.add_argument("--smoke", action="store_true",
                   help="pipeline-proof depth: every module, minutes total")
    p.add_argument("--only", default=None)
    args = p.parse_args()
    if args.full and args.smoke:
        p.error("--full and --smoke are mutually exclusive")
    todo = args.only.split(",") if args.only else ALL
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,shards,derived")
    failures = []
    for name in todo:
        t0 = time.time()
        try:
            run = _module(name).run
            kw = ({"smoke": True} if args.smoke and
                  "smoke" in inspect.signature(run).parameters else {})
            rows = run(quick=not args.full, **kw)
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},"
                      f"{r.get('shards', '-')},{r['derived']}")
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            failures.append((name, repr(e)))
        print(f"# {name} done in {time.time() - t0:.0f}s", file=sys.stderr)

    for r in roofline_rows():
        print(f"{r['name']},{r['us_per_call']:.1f},"
              f"{r.get('shards', '-')},{r['derived']}")

    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
