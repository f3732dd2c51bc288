"""The program's own spans and compiled programs in a JAX profiler trace.

``tracereduce`` keeps the device's ops and the benchmark's ``bench.*``
annotations. The program's span tracer (``repro.serve.tracing``) also
enters a ``jax.profiler.TraceAnnotation`` for every span it opens, so its
spans sit on the trace's host plane under their own names, on the
profiler's clock. ``load`` keeps, as ``(name, start_ns, end_ns)``:

* ``program``: every host event named ``ctr.*`` or ``bse.*`` on the
  threads that run the benchmark's ``bench.handle_requests`` calls (on
  every thread when the trace holds none);
* ``modules``: per device, every event of the ``XLA Modules`` line of a
  ``/device:TPU:<n>`` plane, one per execution of a compiled program,
  named after its module (``jit_ctr_score_tables(...)`` and the like).

A trace of a program that opens no spans, or of a run without a tracer,
has an empty ``program``; the readers built on it then find nothing.

The profiler puts the device's events on the host's clock only roughly:
on a TPU v5e the device's were up to 1.1 ms early, a fixed amount within
a run (a program starting before the host dispatched it). ``device_lead``
measures that from the program's dispatch spans, and the readers that
set device time against host spans shift the device by it first.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

from chipbench import spec, tracereduce

PREFIXES = ("ctr.", "bse.")
REQUEST = "ctr.request"
DEVICE_WAIT = "ctr.device_wait"
# span that dispatches a program -> the program's module name
DISPATCHES = {"ctr.score": r"^jit_ctr_score_(tables|interest|raw)\b",
              "ctr.embed_targets": r"^jit_ctr_embed_targets\b"}


@dataclasses.dataclass
class Programs:
    program: list = dataclasses.field(default_factory=list)
    modules: list = dataclasses.field(default_factory=list)

    @classmethod
    def from_json(cls, d: dict) -> "Programs":
        return cls([tuple(e) for e in d.get("program", [])],
                   [[tuple(e) for e in dev] for dev in d.get("modules", [])])


def load(path: str) -> Programs:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, by_line, burst_lines = {}, {}, set()
    for plane in pd.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if dev and line.name == "XLA Modules":
                modules.setdefault(int(dev.group(1)), []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host"):
                key = (plane.name, line.name)
                for e in line.events:
                    if e.name == tracereduce.BURST:
                        burst_lines.add(key)
                    elif e.name.startswith(PREFIXES):
                        by_line.setdefault(key, []).append(
                            (e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    keep = burst_lines or set(by_line)
    program = [e for key in keep for e in by_line.get(key, [])]
    return Programs(sorted(program, key=lambda e: e[1]),
                    [modules[k] for k in sorted(modules)])


def of(ctx) -> Programs:
    """The program's spans and modules of the trace a reader reads:
    ``ctx.programs`` when the context carries them, else those of the
    newest trace that ``run.py`` wrote for the cell, loaded once per
    context."""
    if getattr(ctx, "programs", None) is None:
        found = glob.glob(os.path.join(
            spec.ROOT, ".bench_trace", f"{ctx.cell.name}_*", "plugins",
            "profile", "*", "*.xplane.pb"))
        ctx.programs = (load(max(found, key=os.path.getmtime)) if found
                        else Programs())
    return ctx.programs


def spans_in(progs: Programs, red: tracereduce.Reduced, name: str) -> list:
    """``(start, end)`` of the spans called ``name`` inside the window."""
    lo, hi = red.window
    return [(s, e) for n, s, e in progs.program
            if n == name and s >= lo and e <= hi]


def covered(merged: list, lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` that a merged interval list covers."""
    i = max(bisect.bisect_right(merged, (lo,)) - 1, 0)
    out = 0
    for s, e in merged[i:]:
        if s >= hi:
            break
        out += max(min(e, hi) - max(s, lo), 0)
    return out


def module_time(progs: Programs, red: tracereduce.Reduced,
                pattern: str) -> tuple:
    """(seconds, calls) of the module executions of the device
    ``tracereduce.READ`` in the window whose name matches ``pattern`` (a
    regular expression)."""
    if not progs.modules:
        return 0.0, 0
    return tracereduce.op_time(tracereduce.Trace(progs.modules, []), red,
                               pattern)


def device_lead(progs: Programs) -> int:
    """How far (ns) the device's events lead the host's in this trace, at
    least: the most by which a program's execution starts before the span
    that dispatched it opened; 0 when none does. The n-th dispatch runs
    the n-th execution of its module, the profiler starting after the
    warm-up; a span whose counts differ is not used."""
    lead = 0
    for span, pattern in DISPATCHES.items():
        rx = re.compile(pattern)
        runs = sorted(s for n, s, _ in
                      (progs.modules or [[]])[tracereduce.READ]
                      if rx.search(n))
        calls = sorted(s for n, s, _ in progs.program if n == span)
        if calls and len(calls) == len(runs):
            lead = max(lead, max(d - r for d, r in zip(calls, runs)))
    return lead


def device_busy(red: tracereduce.Reduced, progs: Programs) -> list:
    """The merged busy intervals of the device ``tracereduce.READ``,
    shifted onto the host's clock by ``device_lead``."""
    lead = device_lead(progs)
    return [(s + lead, e + lead) for s, e in red.busy[tracereduce.READ]]


def innermost(spans: list) -> list:
    """``[(start, end, name)]``: the time the spans cover, cut where the
    innermost open span changes and labelled with it. Spans of one thread
    nest, so the innermost is the one that opened last."""
    out, stack, t = [], [], None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            if end > t:
                out.append((t, end, n))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        t = s
        stack.append((name, e))
    while stack:
        n, end = stack.pop()
        if end > t:
            out.append((t, end, n))
            t = end
    return out


def _label(intervals: list, segments: list, totals: dict) -> list:
    """Add to ``totals`` the time of ``intervals`` (merged) that each
    labelled segment ``(start, end, label)`` covers; return what none
    covers."""
    left, j = [], 0
    segments = sorted(segments)
    for s, e in intervals:
        t = s
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            a, b = max(a, s), min(b, e)
            if b > a:
                if a > t:
                    left.append((t, a))
                totals[name] = totals.get(name, 0.0) + (b - a) * 1e-9
                t = max(t, b)
            k += 1
        if e > t:
            left.append((t, e))
    return left


def idle_by_span(red: tracereduce.Reduced, progs: Programs) -> dict:
    """Seconds in the window in which the device ``tracereduce.READ`` ran
    nothing (its events
    shifted by ``device_lead``), by the innermost program span open at the
    time; where none is open, by the benchmark's annotation
    (``bench.handle_requests``, ``bench.wait_arrival``) or ``other``.
    Sorted, largest first."""
    lo, hi = red.window
    idle, t = [], lo
    busy = tracereduce.clip(device_busy(red, progs), lo, hi)
    for s, e in busy + [(hi, hi)]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    totals = {}
    program = [(n, max(s, lo), min(e, hi)) for n, s, e in progs.program
               if e > lo and s < hi]
    left = _label(idle, innermost(program), totals)
    for name, ivs in ((tracereduce.BURST, red.bursts),
                      (tracereduce.WAIT, red.waits)):
        left = _label(left, [(s, e, name) for s, e in ivs], totals)
    if left:
        totals["other"] = tracereduce.length(left) * 1e-9
    return dict(sorted(totals.items(), key=lambda x: -x[1]))
