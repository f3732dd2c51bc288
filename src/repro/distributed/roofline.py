"""Roofline-term extraction from compiled dry-run artifacts.

Three-term model per (arch × shape × mesh), all in *seconds per step*:

    compute    = FLOPs_per_chip / peak_FLOPs_per_chip
    memory     = HBM_bytes_per_chip / HBM_bandwidth
    collective = collective_bytes_per_chip / ICI_bandwidth

Sources: ``compiled.cost_analysis()`` supplies flops and bytes accessed for
the *per-partition* module (verified empirically in tests/test_roofline.py);
collective bytes are parsed from the post-SPMD HLO text (per-partition
shapes) — XLA does not expose them in cost_analysis.

The peaks are per device kind (``PEAKS``, keyed by ``jax.Device.device_kind``).
A record without peaks carries the compiled costs only; its time terms are
an error, so no device is ever timed against another device's peaks.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float           # bf16 FLOP/s per chip
    hbm_bw: float          # HBM bytes/s per chip
    ici_bw: float          # interconnect bytes/s per link


V5E = "TPU v5 lite"        # what jax reports as ``device_kind`` on a v5e chip
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of 50 GB/s).
PEAKS = {V5E: Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """'f32[8,128]' -> byte count. '' dims = scalar."""
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in (post-SPMD) HLO."""
    out = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        # '%name = <shape-or-tuple> <op>(' — match ops in the instruction head
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", s)
        if not m:
            continue
        shapes_str, op = m.groups()
        base = re.sub(r"\.\d+$", "", op)
        # normalize fused/started variants: all-gather-start, all-reduce-done...
        for cop in COLLECTIVE_OPS:
            if base == cop or base.startswith(cop + "-start"):
                total = 0
                for sh in re.findall(r"\w+\[[\d,]*\]", shapes_str):
                    total += shape_bytes(sh)
                out[cop] += total
                break
    return out


@dataclasses.dataclass
class RooflineRecord:
    name: str
    n_chips: int
    flops_per_chip: float
    hbm_bytes_per_chip: float
    collective_bytes_per_chip: float
    collective_breakdown: dict
    peak_memory_per_chip: float     # from memory_analysis
    model_flops: Optional[float] = None
    peaks: Optional[Peaks] = None   # the target device's; None: costs only

    def _peaks(self) -> Peaks:
        if self.peaks is None:
            raise ValueError(f"{self.name}: no device peaks, so no time terms")
        return self.peaks

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / self._peaks().flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_chip / self._peaks().hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_chip / self._peaks().ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_time(self) -> float:
        """Lower bound on step time (terms overlap perfectly)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> Optional[float]:
        if not self.model_flops:
            return None
        return self.model_flops / max(self.flops_per_chip * self.n_chips, 1.0)

    @property
    def roofline_fraction(self) -> Optional[float]:
        """MODEL_FLOPS-at-peak time / roofline step time — the perf score."""
        if not self.model_flops:
            return None
        ideal = self.model_flops / (self.n_chips * self._peaks().flops)
        return ideal / max(self.roofline_time, 1e-30)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "n_chips": self.n_chips,
            "flops_per_chip": self.flops_per_chip,
            "hbm_bytes_per_chip": self.hbm_bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "collective_breakdown": self.collective_breakdown,
            "peak_memory_per_chip": self.peak_memory_per_chip,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


def analyze(name: str, compiled, n_chips: int,
            model_flops: Optional[float] = None,
            peaks: Optional[Peaks] = None) -> RooflineRecord:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byt = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        peak = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)
        )
    except Exception:
        peak = 0.0
    coll = parse_collective_bytes(compiled.as_text())
    return RooflineRecord(
        name=name,
        n_chips=n_chips,
        flops_per_chip=flops,
        hbm_bytes_per_chip=byt,
        collective_bytes_per_chip=float(sum(coll.values())),
        collective_breakdown=coll,
        peak_memory_per_chip=peak,
        model_flops=model_flops,
        peaks=peaks,
    )
