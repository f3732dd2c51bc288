"""Share of its roofline that the ``sdim_query`` Pallas kernel reaches in
the window (%): the least time the chip could take for every call, the
larger of operations over the bf16 peak and bytes over HBM bandwidth
(``chipbench/shapes.py``, tables in the bf16 wire dtype), over the
kernel's device time in the profiler trace.

The kernel is found as the custom call named after the engine's jitted
``_query`` (``repro.core.engine``); one call per burst."""

KERNEL = r"^%_query(\.\d+)? = .*custom-call\("


def read(ctx):
    sec, calls = ctx.tracereduce.op_time(ctx.trace, ctx.reduced, KERNEL)
    if calls == 0 or calls != len(ctx.bursts):
        return None
    ideal = sum(ctx.shapes.least_time(B, C, ctx.cfg, ctx.peaks,
                                      table_itemsize=2)
                for _, _, B, C in ctx.bursts)
    return 100.0 * ideal / sec
