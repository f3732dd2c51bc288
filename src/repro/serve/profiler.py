"""Measured kernel profiling + device-memory ledger (ISSUE 10 — the
measurement half of observability; ``distributed/roofline.py`` holds the
analytical model, this module closes the loop with numbers from live
dispatches).

The paper's core claim is an *efficiency* one — SDIM serves long sequences
"sizable times faster" than attention (§4.3, Table 5) — and the repo's
roofline so far is purely analytical: ``cost_analysis()`` terms divided by
datasheet peaks. Nothing measured whether the fused serve megakernel is
actually memory-bound the way the model says, and nothing accounted for
the HBM/host/disk bytes the whole tiered/quantized storage story exists to
bound. Two instruments fix that:

``KernelProfiler``
    Wraps ``SDIMEngine`` dispatch sites (encode / query / serve /
    serve_fused / update and their sharded variants — the engine routes
    every jitted call through ``profiler.profile`` when attached). Per
    dispatch it records block-until-ready wall time on an injectable
    ``clock`` (``VirtualClock`` in tests — deterministic), EXCLUDING
    jit-warmup calls: a dispatch that grew the jitted function's
    ``_cache_size()`` compiled, and compile time must never pollute the
    steady-state sample. Per *kernel* it captures ``cost_analysis()``
    flops / bytes once, from an AOT ``lower().compile()`` of the first
    call's arguments — BEFORE the call runs, so donated buffers are still
    valid — plus, on a device whose peaks ``roofline.PEAKS`` lists, the
    analytical ``roofline.analyze`` record for the same executable.
    Measured arithmetic intensity (flops/byte) and achieved-vs-peak
    fraction then sit next to the model's prediction in
    ``roofline_report()``. On any other device (the CPU among them) there
    is no prediction and ``pct_peak`` is ``None``: a host timing is never
    compared with a chip's peaks.

``MemoryLedger``
    Byte accounting keyed by ``(store, tier, dtype)`` across every grow /
    evict / promote / demote / quantize / spill / restore event in
    ``TableStore`` / ``ShardedTableStore`` / ``WarmPool`` / ``ColdStore``
    (the stores carry a ``ledger`` seam and report allocation deltas at
    each event site). Tier totals are exported as ``mem.*`` gauges
    (``serve/metrics.py`` → ``serve/export.py``), and ``verify()`` checks
    the conservation invariant — the event-accumulated total for every
    tier must equal the bytes the tier itself reports (``data.nbytes`` +
    scales for device/host tiers, live segment file sizes for disk). A
    missed or mis-sized event site shows up as a non-empty ``verify()``.

Both instruments are strictly opt-in: an engine without a profiler and a
store without a ledger pay one ``is None`` check per call site.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Callable, Optional

import jax

from repro.distributed import roofline
from repro.serve.metrics import MetricsRegistry, observe_ms
from repro.serve.tracing import Tracer, maybe_span

# ledger tier -> where the bytes physically live
TIER_LOCATION = {"hot": "device", "warm": "host", "cold": "disk"}


# ---------------------------------------------------------------------------
# kernel profiler
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class KernelRecord:
    """Measured + modeled profile of one named dispatch site."""

    name: str
    n_calls: int = 0            # timed (post-warmup) dispatches
    n_compiles: int = 0         # dispatches excluded as jit warmup
    total_s: float = 0.0
    min_s: float = math.inf
    max_s: float = 0.0
    flops: float = 0.0          # cost_analysis, captured once per kernel
    bytes: float = 0.0          # "bytes accessed", ditto
    predicted: Optional[roofline.RooflineRecord] = None

    @property
    def mean_s(self) -> float:
        return self.total_s / self.n_calls if self.n_calls else 0.0

    @property
    def time_ms(self) -> float:
        """Mean measured device time per dispatch, milliseconds."""
        return 1e3 * self.mean_s

    @property
    def ai(self) -> float:
        """Measured arithmetic intensity (flops per HBM byte)."""
        return self.flops / self.bytes if self.bytes > 0 else 0.0

    @property
    def pct_peak(self) -> Optional[float]:
        """Achieved fraction of the analytical roofline: predicted
        best-case time over measured time, clamped to [0, 1]. ``None``
        until both a timed call and a prediction exist — and there is no
        prediction off a device with known peaks."""
        if self.predicted is None or not self.n_calls:
            return None
        ideal = self.predicted.roofline_time
        if ideal <= 0.0 or self.mean_s <= 0.0:
            return 0.0
        return min(1.0, ideal / self.mean_s)

    def add(self, dt: float) -> None:
        self.n_calls += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)

    def capture_cost(self, compiled, n_chips: int,
                     peaks: Optional[roofline.Peaks]) -> None:
        cost = compiled.cost_analysis()
        # XLA reports -1 for terms it cannot attribute; clamp to 0
        self.flops = max(float(cost.get("flops", 0.0)), 0.0)
        self.bytes = max(float(cost.get("bytes accessed", 0.0)), 0.0)
        if peaks is not None:
            self.predicted = roofline.analyze(self.name, compiled, n_chips,
                                              peaks=peaks)

    def to_dict(self) -> dict:
        d = {
            "calls": self.n_calls,
            "compiles": self.n_compiles,
            "time_ms": self.time_ms,
            "min_ms": 0.0 if self.min_s is math.inf else 1e3 * self.min_s,
            "max_ms": 1e3 * self.max_s,
            "flops": self.flops,
            "bytes": self.bytes,
            "ai": self.ai,
            "pct_peak": self.pct_peak,
        }
        if self.predicted is not None:
            p = self.predicted
            d["predicted"] = {
                "t_compute_ms": 1e3 * p.t_compute,
                "t_memory_ms": 1e3 * p.t_memory,
                "t_collective_ms": 1e3 * p.t_collective,
                "roofline_ms": 1e3 * p.roofline_time,
                "bottleneck": p.bottleneck,
            }
        return d


class KernelProfiler:
    """Measured per-dispatch profiling for ``SDIMEngine``.

    Attach with ``profiler.attach(engine)`` (sets ``engine.profiler``);
    every subsequent engine dispatch routes through ``profile``. ``clock``
    is any monotonic ``() -> seconds`` (``VirtualClock`` in tests);
    ``n_chips`` feeds the analytical roofline, whose peaks are those
    ``roofline.PEAKS`` lists for this process's device kind (none on a
    device it does not list); ``metrics`` receives
    ``kernel.<name>_ms`` histograms + a ``kernel.compiles`` counter;
    ``tracer`` gets a ``kernel.<name>`` span per profiled dispatch
    carrying ``flops`` / ``bytes`` / ``ai`` attrs once known."""

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 n_chips: int = 1,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        self.clock = time.perf_counter if clock is None else clock
        self.n_chips = n_chips
        self.metrics = metrics
        self.tracer = tracer
        self.records: dict[str, KernelRecord] = {}
        self.peaks = roofline.PEAKS.get(jax.devices()[0].device_kind)

    def attach(self, engine) -> Any:
        """Wire this profiler into an ``SDIMEngine``; returns the engine."""
        engine.profiler = self
        return engine

    def profile(self, name: str, fn, args: tuple, kwargs: dict):
        """Run one jitted dispatch under measurement: AOT cost capture on
        first sight of the kernel (argument buffers are still intact —
        donation happens in the real call below), block-until-ready wall
        timing, and jit-warmup exclusion via ``fn._cache_size()`` growth.
        A dispatch that fails to compile raises here."""
        rec = self.records.get(name)
        if rec is None:
            rec = self.records[name] = KernelRecord(name)
            rec.capture_cost(fn.lower(*args, **kwargs).compile(),
                             self.n_chips, self.peaks)
        before = fn._cache_size()
        with maybe_span(self.tracer, f"kernel.{name}") as sp:
            t0 = self.clock()
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            dt = self.clock() - t0
            if fn._cache_size() > before:
                rec.n_compiles += 1
                sp.set(compile=True)
                if self.metrics is not None:
                    self.metrics.counter("kernel.compiles").inc()
            else:
                rec.add(dt)
                observe_ms(self.metrics, f"kernel.{name}_ms", dt)
            sp.set(time_ms=1e3 * dt, flops=rec.flops, bytes=rec.bytes,
                   ai=rec.ai)
        return out

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        """``{kernel: {time_ms, flops, bytes, ai, pct_peak, ...}}`` — the
        ``profile.per_kernel`` block of ``BENCH_serving.json``."""
        return {name: rec.to_dict()
                for name, rec in sorted(self.records.items())}

    def roofline_report(self) -> str:
        """Measured-vs-predicted table: per kernel, the measured mean time
        / flops / bytes / arithmetic intensity next to the analytical
        roofline's best-case time and bottleneck term."""
        hdr = (f"{'kernel':<20} {'calls':>5} {'time_ms':>9} {'flops':>10} "
               f"{'bytes':>10} {'AI':>7} {'pct_peak':>8} {'pred_ms':>9} "
               f"{'bound':<10}")
        lines = ["measured roofline (per dispatch; warmup excluded):", hdr,
                 "-" * len(hdr)]
        for name, rec in sorted(self.records.items()):
            if rec.predicted is not None:
                pred = f"{1e3 * rec.predicted.roofline_time:>9.4f}"
                bound = rec.predicted.bottleneck
            else:
                pred, bound = f"{'-':>9}", "-"
            pct = "-" if rec.pct_peak is None else f"{rec.pct_peak:.3f}"
            lines.append(
                f"{name:<20} {rec.n_calls:>5} {rec.time_ms:>9.4f} "
                f"{rec.flops:>10.3g} {rec.bytes:>10.3g} {rec.ai:>7.3f} "
                f"{pct:>8} {pred} {bound:<10}")
        if not self.records:
            lines.append("(no profiled dispatches)")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# memory ledger
# ---------------------------------------------------------------------------
class MemoryLedger:
    """Event-driven byte accounting over the serving stores.

    ``attach(store)`` registers every tier of a ``TieredTableStore`` (or
    the single device tier of a plain / sharded store) under a
    ``(store_name, tier, dtype)`` key, baselining each at its current
    allocation. From then on the stores report **allocation deltas** at
    every event site (``add``) and traffic events (``count``); tier totals
    update incrementally and are mirrored to ``mem.<tier>_bytes`` /
    ``mem.total_bytes`` gauges when a ``MetricsRegistry`` is attached.

    The conservation invariant — what the hypothesis suite sweeps — is
    that the event-accumulated bytes for every key equal the bytes the
    tier reports right now (``verify()`` returns the mismatches; an empty
    list means no event site was missed or mis-sized)."""

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics
        self._bytes: dict[tuple, int] = {}        # (store, tier, dtype) -> B
        self.events: dict[str, int] = {}          # event kind -> count
        self.moved_bytes: dict[str, int] = {}     # traffic kind -> bytes
        self._watch: list[tuple] = []             # (key, ground-truth fn)

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    @staticmethod
    def _device_bytes(store) -> int:
        n = store.data.nbytes
        if store.quantized:
            n += store.scales.nbytes
        return n

    @staticmethod
    def _cold_bytes(cold) -> int:
        total = 0
        for seg in cold._live:
            try:
                total += os.path.getsize(cold._path(seg))
            except OSError:
                pass
        return total

    def _register(self, sub, key: tuple, truth: Callable[[], int]) -> None:
        sub.ledger = self
        sub._ledger_key = key
        self._bytes[key] = int(truth())
        self._watch.append((key, truth))
        self._export()

    def attach(self, store, name: str = "bse"):
        """Register ``store`` (TableStore / ShardedTableStore /
        TieredTableStore) and all its tiers; returns the store."""
        from repro.serve.tiered_store import TieredTableStore

        if isinstance(store, TieredTableStore):
            store.ledger = self
            dt = str(store.dtype)
            self._register(store.hot, (name, "hot", dt),
                           lambda s=store.hot: self._device_bytes(s))
            self._register(
                store.warm, (name, "warm", dt),
                lambda s=store.warm: s.data.nbytes
                + (s.scales.nbytes if s.quantized else 0))
            if store.cold is not None:
                self._register(store.cold, (name, "cold", dt),
                               lambda c=store.cold: self._cold_bytes(c))
        else:
            self._register(store, (name, "hot", str(store.dtype)),
                           lambda s=store: self._device_bytes(s))
        return store

    # ------------------------------------------------------------------
    # event sinks (called by the stores)
    # ------------------------------------------------------------------
    def add(self, key: tuple, delta: int, kind: str) -> None:
        """An event at ``key`` changed its tier's allocation by ``delta``
        bytes (grow / spill / segment unlink / wholesale restore)."""
        self._bytes[key] = self._bytes.get(key, 0) + int(delta)
        self.events[kind] = self.events.get(kind, 0) + 1
        self._export()

    def set_total(self, key: tuple, nbytes: int, kind: str) -> None:
        """Wholesale replacement (restore paths): the tier now holds
        exactly ``nbytes``."""
        self._bytes[key] = int(nbytes)
        self.events[kind] = self.events.get(kind, 0) + 1
        self._export()

    def count(self, kind: str, n: int = 1, moved: int = 0) -> None:
        """A traffic event that did not change any allocation: evictions,
        quantizing writes, promote/demote row movement (``moved`` bytes
        crossed a tier boundary)."""
        self.events[kind] = self.events.get(kind, 0) + int(n)
        if moved:
            self.moved_bytes[kind] = \
                self.moved_bytes.get(kind, 0) + int(moved)

    # ------------------------------------------------------------------
    # readback
    # ------------------------------------------------------------------
    def tier_bytes(self, tier: str) -> int:
        return sum(v for (_, t, _), v in self._bytes.items() if t == tier)

    def total(self) -> int:
        return sum(self._bytes.values())

    def _export(self) -> None:
        if self.metrics is None:
            return
        for tier in ("hot", "warm", "cold"):
            self.metrics.gauge(f"mem.{tier}_bytes").set(
                self.tier_bytes(tier))
        self.metrics.gauge("mem.total_bytes").set(self.total())

    def verify(self) -> list[str]:
        """Conservation check: event-accumulated bytes vs what every
        registered tier reports right now. Empty list == conserved."""
        problems = []
        for key, truth in self._watch:
            reported = int(truth())
            if self._bytes.get(key, 0) != reported:
                problems.append(
                    f"{'/'.join(map(str, key))}: ledger "
                    f"{self._bytes.get(key, 0)} B != reported {reported} B")
        return problems

    def snapshot(self) -> dict:
        """The ``profile.mem`` block of ``BENCH_serving.json``."""
        return {
            "hot_bytes": self.tier_bytes("hot"),
            "warm_bytes": self.tier_bytes("warm"),
            "cold_bytes": self.tier_bytes("cold"),
            "total_bytes": self.total(),
            "events": dict(sorted(self.events.items())),
            "moved_bytes": dict(sorted(self.moved_bytes.items())),
            "by_key": {"/".join(map(str, k)): v
                       for k, v in sorted(self._bytes.items(),
                                          key=lambda kv: kv[0])},
        }

    def report(self) -> str:
        errs = self.verify()
        ok = "conservation OK" if not errs else f"CONSERVATION BROKEN: {errs}"
        return (f"mem ledger: hot {self.tier_bytes('hot')} B (device), "
                f"warm {self.tier_bytes('warm')} B (host), "
                f"cold {self.tier_bytes('cold')} B (disk) — {ok}")

