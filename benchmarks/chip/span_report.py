#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, that also
reports where the device waits in the window, by program span.

    python3 benchmarks/chip/span_report.py --workload fp32_read --seed 7 \\
        --seconds 51 [--fixture trace.json --bursts 6]

Before ``run.py`` removes the profiler trace, it is read once more
(``chipbench/programtrace.py``) and one line of JSON is printed:
``idle_by_span`` (device-0 idle seconds by innermost program span, in
all and per burst), ``device_lead_ms`` (how far the profiler put the
device's clock ahead of the host's) and ``named_share``, the share of
the idle time inside the ``handle_requests`` calls that a program span
other than ``ctr.request`` holds. ``--fixture`` also writes ``--bursts`` bursts from
the middle of the window as a trace fixture (``ops``, ``host``,
``program``, ``modules``). The last line of standard output is
``run.py``'s result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
from chipbench import programtrace as pt  # noqa: E402
from chipbench import tracereduce as tr  # noqa: E402


def cut(trace: tr.Trace, progs: pt.Programs, red: tr.Reduced,
        n: int) -> dict:
    """``n`` bursts from the middle of the window, as a fixture whose
    ``bench.window`` runs from just before the first to just after the
    last."""
    bursts = [h for h in trace.host if h[0] == tr.BURST
              and h[1] >= red.window[0] and h[2] <= red.window[1]]
    k = max(len(bursts) // 2 - n // 2, 0)
    lo, hi = bursts[k][1] - 1000, bursts[min(k + n, len(bursts)) - 1][2] + 1000
    inside = lambda evs: [e for e in evs if e[1] >= lo and e[2] <= hi]
    return {"ops": [inside(dev) for dev in trace.ops],
            "host": [(tr.WINDOW, lo, hi)] + [
                h for h in inside(trace.host) if h[0] != tr.WINDOW],
            "program": inside(progs.program),
            "modules": [inside(dev) for dev in progs.modules]}


def report(log_dir: str, fixture: str, n: int) -> None:
    path = tr.find_xplane(log_dir)
    trace = tr.load(path)
    red = tr.reduce(trace)
    progs = pt.load(path)
    by = pt.idle_by_span(red, progs)
    inside = sum(v for k, v in by.items() if k not in (tr.WAIT, "other"))
    named = inside - by.get(pt.REQUEST, 0.0) - by.get(tr.BURST, 0.0)
    bursts = len(red.bursts)
    print(json.dumps({
        "bursts": bursts, "device_lead_ms": pt.device_lead(progs) * 1e-6,
        "idle_by_span": by,
        "ms_per_burst": {k: 1e3 * v / bursts for k, v in by.items()},
        "named_share": named / inside if inside else None}), flush=True)
    if fixture:
        with open(fixture, "w") as f:
            json.dump(cut(trace, progs, red, n), f)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fixture", default=None)
    p.add_argument("--bursts", type=int, default=6)
    args, rest = p.parse_known_args()
    traced = run.traced_metrics

    def traced_metrics(r, log_dir):
        out = traced(r, log_dir)
        report(log_dir, args.fixture, args.bursts)
        return out

    run.traced_metrics = traced_metrics
    sys.argv = [run.__file__, *rest, "--trace", "1"]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
