"""Share of its roofline that the ``sdim_fused_serve`` megakernel reaches
in the window (%): the least time for every call, the larger of
operations (with the dequantisation) over the bf16 peak and bytes (the
stored rows in their dtype and, for int8, their fp32 per-row scales) over
HBM bandwidth (``chipbench/shapes.py``), over the kernel's device time
in the profiler trace.

The kernel is found as the custom call named after the engine's jitted
``_serve_fused_impl`` (``repro.core.engine``); one call per burst."""

KERNEL = r"^%_serve_fused_impl(\.\d+)? = .*custom-call\("


def read(ctx):
    sec, calls = ctx.tracereduce.op_time(ctx.trace, ctx.reduced, KERNEL)
    if calls == 0 or calls != len(ctx.bursts):
        return None
    quantized = ctx.cfg.get("qmax") is not None
    ideal = sum(ctx.shapes.least_time(B, C, ctx.cfg, ctx.peaks,
                                      table_itemsize=1 if quantized else 4,
                                      quantized=quantized)
                for _, _, B, C in ctx.bursts)
    return 100.0 * ideal / sec
