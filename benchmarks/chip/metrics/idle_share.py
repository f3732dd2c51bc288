"""Share of the time inside the ``handle_requests`` calls in which no
operation ran on the device (%), from the profiler trace: one minus the
device-busy union intersected with the benchmark's
``bench.handle_requests`` annotations, over their total."""


def read(ctx):
    if not ctx.reduced.bursts:
        return None
    return 100.0 * ctx.reduced.idle_share()
