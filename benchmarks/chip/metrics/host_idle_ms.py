"""Median over the bursts in the window of the host's own share of each
burst (ms): the time inside the program's ``ctr.request`` span in which
device 0 runs nothing and the program is not in a ``ctr.device_wait``
span (the wait for the scores). The spans come from the profiler trace,
with the device's events shifted onto the host's clock by
``device_lead`` (``chipbench/programtrace.py``). A median, since a
machine-wide freeze (PERF.md) would set a mean."""
import statistics

from chipbench import programtrace as pt


def read(ctx):
    progs = pt.of(ctx)
    red = ctx.reduced
    requests = pt.spans_in(progs, red, pt.REQUEST)
    if not requests:
        return None
    cover = ctx.tracereduce.union(
        pt.device_busy(red, progs) + pt.spans_in(progs, red, pt.DEVICE_WAIT))
    return 1e-6 * statistics.median(
        (e - s) - pt.covered(cover, s, e) for s, e in requests)
