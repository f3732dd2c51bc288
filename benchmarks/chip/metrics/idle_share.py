"""Share of the time inside the ``handle_requests`` calls in which no
operation ran on the device (%), from the profiler trace: one minus the
device-busy union intersected with the benchmark's
``bench.handle_requests`` annotations, over their total.

It reads the device ``tracereduce.READ``: chip 0 on one chip; on a cell
of several chips the last chip, which runs its shard of the fused serve,
the psum and the scorer (chip 0's op line is cut short there, PERF.md).
"""


def read(ctx):
    if not ctx.reduced.bursts:
        return None
    return 100.0 * ctx.reduced.idle_share()
