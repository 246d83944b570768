"""Device milliseconds per traced period of the fused traffic program (XLA
modules named ``jit__traffic_impl...``)."""

PREFIX = "jit__traffic_impl"


def read(ctx):
    tr = ctx.trace
    hit = tr.module_s(PREFIX) if tr is not None else None
    if hit is None or not tr.steps:
        return None
    return hit[0] * 1e3 / tr.steps
