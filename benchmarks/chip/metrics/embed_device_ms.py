"""Mean device time per burst of the fused path's candidate embedding
(ms): the executions of the module named after the CTR server's jitted
``ctr_embed_targets`` (``repro.serve.ctr_server``) on the device trace's
``XLA Modules`` line, over the bursts in the window."""
from chipbench import programtrace as pt

MODULE = pt.DISPATCHES["ctr.embed_targets"]


def read(ctx):
    sec, calls = pt.module_time(pt.of(ctx), ctx.reduced, MODULE)
    if calls == 0:
        return None
    return 1e3 * sec / len(ctx.bursts)
