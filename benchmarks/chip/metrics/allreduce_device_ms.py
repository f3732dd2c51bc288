"""Mean device time per burst of the all-reduce operations (ms): the
psum with which the sharded fused serve (``repro.core.engine``'s
``_sharded_fused_serve_fn``) assembles the (B, C, e) interest vectors
from every shard, on the ``XLA Ops`` line of the device
``tracereduce.READ`` in the profiler trace, the chip that the other
trace readers read (the last). A cell on one chip has none."""

OP = r"^%\S+ = .* all-reduce(-start|-done)?\("


def read(ctx):
    sec, calls = ctx.tracereduce.op_time(ctx.trace, ctx.reduced, OP)
    if calls == 0:
        return None
    return 1e3 * sec / len(ctx.bursts)
