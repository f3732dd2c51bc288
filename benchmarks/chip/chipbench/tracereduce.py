"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` the profiler writes and keeps what the
metrics need, in plain lists that a test fixture can hold as JSON:

* ``ops``: per device, every operation on the ``XLA Ops`` line of a
  ``/device:TPU:<n>`` plane, as ``(name, start_ns, end_ns)``;
* ``host``: every host event whose name starts with ``bench.`` (the
  benchmark's own ``TraceAnnotation``s), as ``(name, start_ns, end_ns)``.

Host and device events share the profiler's clock. Busy time is the union
of the intervals in which an operation runs on a device.

Every reading of one device (``idle_share``, ``idle_gaps``, ``device_ops``,
``op_time``) reads ``READ``: the chip on one chip, the last chip on
several. On four TPU v5e chips chip 0's ``XLA Ops`` line stops within the
first second of a trace while the other chips keep every op (PERF.md).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

WINDOW = "bench.window"
BURST = "bench.handle_requests"
WAIT = "bench.wait_arrival"
READ = -1            # the device that the readers read


@dataclasses.dataclass
class Trace:
    ops: list           # per device: [(name, start_ns, end_ns), ...]
    host: list          # [(name, start_ns, end_ns), ...]

    def to_json(self) -> dict:
        return {"ops": self.ops, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls([[tuple(e) for e in dev] for dev in d["ops"]],
                   [tuple(e) for e in d["host"]])


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, host = {}, []
    for plane in pd.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if dev and line.name == "XLA Ops":
                ops.setdefault(int(dev.group(1)), []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host"):
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace([ops[k] for k in sorted(ops)], sorted(host,
                                                       key=lambda e: e[1]))


def union(intervals) -> list:
    """Merged, sorted [(start, end)] covering the same points."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(merged, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_label(name: str) -> str:
    """``%copy.13 = f32[10000000,64]{1,0:T(8,128)} copy(...)`` ->
    ``copy.13 f32[10000000,64]``: the instruction and its result shape."""
    m = re.match(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])?", name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2) or ''}".strip()


@dataclasses.dataclass
class Reduced:
    window: tuple            # (start_ns, end_ns)
    busy: list               # per device: merged busy intervals in window
    bursts: list             # merged BURST intervals in window
    waits: list              # merged WAIT intervals in window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return sum(length(b) for b in self.busy) * 1e-9 / len(self.busy)

    def idle_share(self) -> float:
        """Share of the time inside ``handle_requests`` calls in which no
        operation ran on the device ``READ`` (0 to 1)."""
        total = length(self.bursts)
        return 1.0 - length(intersect(self.busy[READ], self.bursts)) / total

    def idle_gaps(self, k: int = 10) -> list:
        """Idle gaps of the device ``READ`` in the window by what the host was doing
        (inside a ``handle_requests`` call, waiting for an arrival, or
        neither): the total of each, then the longest gaps, k in all."""
        gaps, t = [], self.window[0]
        for s, e in self.busy[READ] + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        labelled = []
        for s, e in gaps:
            mid = (s + e) / 2
            label = "other"
            for name, iv in ((BURST, self.bursts), (WAIT, self.waits)):
                if any(a <= mid < b for a, b in iv):
                    label = name
            labelled.append((label, (e - s) * 1e-9))
        totals = {}
        for label, sec in labelled:
            totals[label] = totals.get(label, 0.0) + sec
        out = [[f"{label} total", sec] for label, sec in
               sorted(totals.items(), key=lambda x: -x[1])]
        longest = sorted(labelled, key=lambda x: -x[1])
        out += [[f"{label} longest", sec]
                for label, sec in longest[:max(k - len(out), 0)]]
        return out[:k]


def reduce(trace: Trace) -> Reduced:
    """Cut the trace to the benchmark's ``bench.window`` annotation."""
    wins = [(s, e) for n, s, e in trace.host if n == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW} annotation")
    lo, hi = wins[-1]
    if not trace.ops:
        raise ValueError("trace has no device operations")
    busy = [clip(union((s, e) for _, s, e in dev), lo, hi)
            for dev in trace.ops]
    pick = lambda name: clip(union((s, e) for n, s, e in trace.host
                                   if n == name), lo, hi)
    return Reduced((lo, hi), busy, pick(BURST), pick(WAIT))


def device_ops(trace: Trace, red: Reduced, k: int = 10) -> list:
    """The k operations that took most device time in the window, summed
    over their calls, in seconds (the device ``READ``)."""
    tot = {}
    lo, hi = red.window
    for name, s, e in trace.ops[READ]:
        if e > lo and s < hi:
            lab = op_label(name)
            tot[lab] = tot.get(lab, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def op_time(trace: Trace, red: Reduced, pattern: str) -> tuple:
    """(seconds, calls) of the operations of the device ``READ`` in the
    window whose name matches ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    lo, hi = red.window
    sec, n = 0.0, 0
    for name, s, e in trace.ops[READ]:
        if s >= lo and e <= hi and rx.search(name):
            sec += (e - s) * 1e-9
            n += 1
    return sec, n


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)
