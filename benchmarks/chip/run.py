#!/usr/bin/env python3
"""Chip benchmark of the SDIM CTR serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload fp32_read --seed 7 \\
        --seconds 10 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
and a traffic mix; both are read by name (see ``chipbench/spec.py``).
Set-up makes the weights and every user's history from ``--seed``, builds
the server through the program's entry points, ingests the histories and
warms every burst size; ``setup_s`` runs from process start to the
window's first due request. The window then offers the mix open-loop for
``--seconds``; afterwards a sample of the served requests is compared with
the plain reference (``chipbench/checks.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the program with its span tracer and the JAX profiler on, and reports the
per-layer metrics (``metrics/<name>.py``), the device's busy time and a
breakdown. The last line of standard output is the result as JSON; the
last lines of standard error give each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, spec  # noqa: E402


def log(*a) -> None:
    print(*a, flush=True)


def device_info(jax) -> dict:
    d = jax.devices()
    stats = d[0].memory_stats() or {}
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in d)
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "memory_peak_bytes": int(peak),
            "bytes_limit": int(stats.get("bytes_limit", 0))}


def traced_metrics(run, log_dir: str) -> tuple[dict, dict, dict]:
    """Per-layer metrics, the device's busy time and the breakdown from
    the profiler trace of the window and the program's spans."""
    from chipbench import tracereduce

    trace = tracereduce.load(tracereduce.find_xplane(log_dir))
    red = tracereduce.reduce(trace)
    ctx = run.per_layer_context(trace, red)
    metrics = {}
    for m in run.cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"busy_s": red.busy_s, "window_s": red.window_s}
    breakdown = {"device_ops": tracereduce.device_ops(trace, red),
                 "idle_gaps": red.idle_gaps()}
    return metrics, device, breakdown


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args()

    cell = spec.load_cell(args.workload)
    harness.prepare_process()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}; cell "
        f"{cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}")

    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace), log)
    run.setup()
    log_dir = None
    if args.trace:
        log_dir = os.path.join(spec.ROOT, ".bench_trace",
                               f"{cell.name}_{args.seed}")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    run.window()
    if args.trace:
        jax.profiler.stop_trace()
    late = sorted(run.late)
    log(f"window: {run.attempted} requests due in {args.seconds:g} s, "
        f"{len(run.bursts)} bursts, {run.failed} failed, {run.errors} "
        f"errors; compilations in the window: {run.compiles.n}; generator "
        f"lateness after a wait: median "
        f"{1e3 * late[len(late) // 2] if late else 0:.3f} ms, max "
        f"{1e3 * late[-1] if late else 0:.3f} ms")
    lat = run.latency_ms()
    log(f"latency over {lat.size} served requests: p50 "
        f"{np.percentile(lat, 50):.3f} ms, p99 {np.percentile(lat, 99):.3f} "
        f"ms, max {lat.max():.3f} ms")
    longest = sorted(run.bursts, key=lambda b: b[0] - b[1])[:5]
    log("longest bursts (start s, ms, requests): " + ", ".join(
        f"({s:.3f}, {1e3 * (e - s):.1f}, {j - i})" for s, e, i, j in longest))
    device = device_info(jax)
    device.pop("bytes_limit")
    out = {}
    if args.trace:
        metrics, dev, breakdown = traced_metrics(run, log_dir)
        # the trace is read: keep the disk the runs write to small
        shutil.rmtree(log_dir, ignore_errors=True)
        device.update(dev)
        out["breakdown"] = breakdown
    else:
        metrics = run.end_to_end(setup_s)
    t = time.perf_counter()
    result = run.check()
    log(f"check of {harness.SAMPLE} sampled requests against the "
        f"reference: {time.perf_counter() - t:.2f} s")
    from chipbench import checks

    # a request that never got its scores is as wrong as a wrong score
    correct = checks.passed(result) and run.failed == 0
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    line.update(out)
    line["checks"] = result
    for name, v in result.items():
        print(f"check {name}: {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
