"""Model FLOP utilisation of the served path (%): the model operations
of every burst in the window (the MLP head, the short-term attention and
the SDIM read, from ``chipbench/shapes.py``) over the summed wall time of
the ``handle_requests`` calls times the chip's bf16 peak."""


def read(ctx):
    if not ctx.bursts:
        return None
    flops = sum(B * ctx.shapes.request_flops(C, ctx.cfg)
                for _, _, B, C in ctx.bursts)
    wall = sum(e - s for s, e, _, _ in ctx.bursts)
    return 100.0 * flops / (wall * ctx.peaks.bf16_flops)
