import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape) cell — 40 total — and each mesh
(single-pod 16×16 = 256 chips, multi-pod 2×16×16 = 512 chips):

    with mesh:
        lowered  = jax.jit(step, donate_argnums=...).lower(*input_specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())    # proves it fits 16 GB/chip
        print(compiled.cost_analysis())      # FLOPs/bytes for §Roofline

plus HLO-text collective parsing -> roofline terms. Results are written
incrementally to results/dryrun/<mesh>/<cell>.json so reruns resume.

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--force]
    python -m repro.launch.dryrun --arch deepseek-v2-236b --shape long_500k \
        --variant sdim_kv
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import registry                       # noqa: E402
from repro.distributed import roofline as rl             # noqa: E402
from repro.launch import flops as flops_lib              # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402
from repro.launch.specs import build_cell, has_scans     # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")
RESULTS_DIR = os.path.abspath(RESULTS_DIR)
# the pods these cells are sized for are v5e: their roofline is a prediction
# for that chip (the cells compile on virtual CPU devices; nothing is timed)
TARGET_PEAKS = rl.PEAKS[rl.V5E]


def out_path(mesh_tag: str, arch: str, shape: str, variant: str) -> str:
    d = os.path.join(RESULTS_DIR, mesh_tag)
    os.makedirs(d, exist_ok=True)
    v = "" if variant == "baseline" else f"__{variant}"
    return os.path.join(d, f"{arch}__{shape}{v}.json")


def run_cell(arch: str, shape: str, multi_pod: bool, variant: str = "baseline",
             verbose: bool = True) -> dict:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    # Pass 1 — production (scan) lowering: memory_analysis is authoritative
    # here (what the fleet actually runs; scan reuses per-layer buffers).
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, variant=variant)
    with mesh:
        jitted = jax.jit(cell.step_fn, donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()

    # Pass 2 — unrolled lowering: cost_analysis/collectives are authoritative
    # here (XLA counts a while-loop body once, under-reporting scanned
    # programs by ~n_layers×). Same math, flat HLO. For the LM family,
    # unrolled costs are exactly linear in scanned depth, so compile reduced
    # depths (4, 8) and extrapolate — 60-layer flat HLO would take >30 min.
    mf = flops_lib.model_flops(arch, shape, variant)
    if registry.family(arch) == "lm":
        from repro.launch.specs import lm_scan_depth

        k1, k2 = 4, 8
        recs = {}
        for k in (k1, k2):
            cell_u = build_cell(arch, shape, mesh, variant=variant,
                                unroll=True, depth_override=k)
            with mesh:
                cu = jax.jit(cell_u.step_fn, donate_argnums=cell_u.donate) \
                    .lower(*cell_u.abstract_args).compile()
            recs[k] = rl.analyze(cell.name, cu, n_chips)   # costs only
        L = lm_scan_depth(arch)

        def extrap(v1, v2):
            slope = (v2 - v1) / (k2 - k1)
            return max(v1 + slope * (L - k1), 0.0)

        record = rl.RooflineRecord(
            name=cell.name, n_chips=n_chips,
            flops_per_chip=extrap(recs[k1].flops_per_chip, recs[k2].flops_per_chip),
            hbm_bytes_per_chip=extrap(recs[k1].hbm_bytes_per_chip,
                                      recs[k2].hbm_bytes_per_chip),
            collective_bytes_per_chip=extrap(
                recs[k1].collective_bytes_per_chip,
                recs[k2].collective_bytes_per_chip),
            collective_breakdown={
                op: int(extrap(recs[k1].collective_breakdown[op],
                               recs[k2].collective_breakdown[op]))
                for op in recs[k1].collective_breakdown},
            peak_memory_per_chip=0.0,   # memory comes from pass 1
            model_flops=mf,
            peaks=TARGET_PEAKS,
        )
    elif has_scans(arch, shape):
        cell_u = build_cell(arch, shape, mesh, variant=variant, unroll=True)
        with mesh:
            cost_compiled = jax.jit(
                cell_u.step_fn, donate_argnums=cell_u.donate
            ).lower(*cell_u.abstract_args).compile()
        record = rl.analyze(cell.name, cost_compiled, n_chips,
                            model_flops=mf, peaks=TARGET_PEAKS)
    else:
        record = rl.analyze(cell.name, compiled, n_chips, model_flops=mf,
                            peaks=TARGET_PEAKS)
    out = record.to_dict()
    out.update({
        "arch": arch, "shape": shape, "variant": variant, "mesh": mesh_tag,
        "kind": cell.kind, "note": cell.note,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": {
            k: int(getattr(mem, k, 0)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
        },
    })
    per_chip_hbm = (out["memory_analysis"]["argument_size_in_bytes"]
                    + out["memory_analysis"]["temp_size_in_bytes"]
                    + out["memory_analysis"]["output_size_in_bytes"]
                    - out["memory_analysis"]["alias_size_in_bytes"])
    out["hbm_total_per_chip_gib"] = round(per_chip_hbm / 2**30, 3)
    out["fits_16gib"] = per_chip_hbm < 16 * 2**30

    if verbose:
        print(f"== {cell.name} [{mesh_tag}] {cell.kind} ==")
        print(f"   memory_analysis: {mem}")
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        print(f"   cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"   per-chip HBM: {out['hbm_total_per_chip_gib']} GiB "
              f"(fits 16 GiB: {out['fits_16gib']})")
        print(f"   roofline: compute={out['t_compute_s']:.4g}s "
              f"memory={out['t_memory_s']:.4g}s "
              f"collective={out['t_collective_s']:.4g}s "
              f"-> bottleneck={out['bottleneck']}")
        print(f"   collectives: {out['collective_breakdown']}")
        print(f"   lower={t_lower:.0f}s compile={t_compile:.0f}s")
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--variant", default="baseline")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true", help="all 40 cells on this mesh")
    p.add_argument("--force", action="store_true")
    args = p.parse_args()
    enable_compile_cache()

    mesh_tag = "pod2x16x16" if args.multi_pod else "pod16x16"
    if args.all:
        todo = [(a, s) for a, s in registry.cells()]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]

    failures = []
    for arch, shape in todo:
        path = out_path(mesh_tag, arch, shape, args.variant)
        if os.path.exists(path) and not args.force:
            print(f"skip (cached): {arch}/{shape} [{mesh_tag}]")
            continue
        try:
            rec = run_cell(arch, shape, args.multi_pod, args.variant)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
