"""Mean device time per burst of the CTR server's scoring program (ms):
the executions of the module named after its jitted ``ctr_score_tables``
/ ``ctr_score_interest`` / ``ctr_score_raw`` (``repro.serve.ctr_server``)
on the device trace's ``XLA Modules`` line, over the bursts in the
window, on the device ``tracereduce.READ``: on a cell of several chips
the last chip, which runs the scorer as every chip does."""
from chipbench import programtrace as pt

MODULE = pt.DISPATCHES["ctr.score"]


def read(ctx):
    sec, calls = pt.module_time(pt.of(ctx), ctx.reduced, MODULE)
    if calls == 0:
        return None
    return 1e3 * sec / len(ctx.bursts)
