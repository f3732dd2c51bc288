#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate with no growing backlog.

    python3 benchmarks/chip/sweep.py --workload fp32_read --seed 5 \\
        --seconds 8 --rates 300 400 500 600 700 800

One set-up (the cell's configuration and mix), then one open-loop window
per rate, lowest first, each with the mix's rate replaced. For every
rate it prints the offered and served request rates, p50 and p99
latency, the mean burst size and the backlog's trend: the median latency
of the window's last quarter of requests over that of its first quarter.
A backlog that grows all through the window shows as a ratio far above
1 and a served rate below the offered one. The knee is recorded in
``PERF.md`` and the cell's rate (about four fifths of it) in its traffic
file; the benchmark's runs never search for it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, spec, traffic  # noqa: E402


def step(run, rate: float) -> dict:
    import numpy as np

    run.mix = dict(run.mix, rate_per_s=rate)
    run.sched = traffic.schedule(run.mix, run.cfg, run.seconds, run.seed,
                                 run.interests, stream=4)
    run.requests = run.make_requests(run.sched)
    run.in_sample = np.zeros(len(run.sched), bool)
    run.window()
    lat = run.latency * 1e3
    q = len(lat) // 4
    served = np.isfinite(lat)
    elapsed = run.bursts[-1][1]
    return {"rate": rate, "served_per_s": float(served.sum() / elapsed),
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "mean_burst": float(np.mean([j - i for _, _, i, j in
                                         run.bursts])),
            "backlog_trend": float(np.nanmedian(lat[-q:])
                                   / np.nanmedian(lat[:q])),
            "failed": run.failed, "compiles": run.compiles.n}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    harness.prepare_process()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, rate_per_s=max(args.rates)))
    run = harness.Run(cell, args.seed, args.seconds, False)
    run.setup()
    for rate in sorted(args.rates):
        print(json.dumps(step(run, rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
