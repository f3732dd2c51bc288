#!/usr/bin/env python3
"""Readings of a cell's control: the plain reference put in the program's
place, computed in the precision below the one the configuration states
(its ``control`` entry: ``dtype`` lowers every array, ``qmax`` the
quantised store), on the very inputs a run of that seed would compare.

    python3 benchmarks/chip/control.py --workload fp32_read \\
        --seconds 10 --seeds 11 12 13

Each seed prints the three compared numbers of the control against the
float32 reference, beside the cell's limits; a sound limit lies below
each control reading that the program's own runs do not reach. The
benchmark's runs never run this. Without a TPU it exits with 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import (checks, harness, histories, spec,  # noqa: E402
                       traffic, weights)


def readings(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """The control's compared numbers for one seed, at the cell's size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    k_w, k_h = jax.random.split(weights.model_key(seed))
    shape = harness.history_shape(cfg)
    recent, interests = [], []
    for c in range(cfg["population"] // harness.CHUNK):
        h = histories.chunk(k_h, c, shape=shape, batch=harness.CHUNK)
        recent.append(harness.recent_window(h, cfg["short_len"]))
        interests.append(h["interests"])
    recent = jnp.concatenate(recent, axis=1)
    sched = traffic.schedule(mix, cfg, seconds, seed,
                             np.asarray(jnp.concatenate(interests)))
    ks = list(checks.sample(len(sched), seed, harness.SAMPLE))
    users = sorted({int(sched.users[k]) for k in ks})
    req = harness.request_inputs(sched, recent, ks, users)
    hist = harness.histories_of(cfg, k_h, users)
    w = weights.make(cfg, k_w)
    want = checks.reference_outputs(cfg, w, hist, req, dtype=jnp.float32,
                                    qmax=cfg.get("qmax"),
                                    wire=cfg["wire_dtype"])
    ctl = cfg["control"]
    got = checks.reference_outputs(
        cfg, w, hist, req, dtype=jnp.dtype(ctl.get("dtype", "float32")),
        qmax=ctl.get("qmax", cfg.get("qmax")), wire=cfg["wire_dtype"])
    return checks.compare(got, want, cfg["limits"])


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    harness.prepare_process()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    low = {}
    for seed in args.seeds:
        r = readings(cell.config, cell.traffic, seed, args.seconds)
        print(json.dumps({"seed": seed, "control": r}), flush=True)
        for n, v in r.items():
            low[n] = min(low.get(n, float("inf")), v["value"])
    print(json.dumps({"workload": cell.name, "control_min": low}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
