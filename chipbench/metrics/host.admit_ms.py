"""Host milliseconds per window tick of the device tick's fluid admission:
the pool's ``transport.admit`` span (per-node request counts and each
node's ``Captain.arrive_batch``)."""

SPAN = "transport.admit"


def read(ctx):
    w = ctx.window
    ms = w.get("phase_ms", {}).get(SPAN) if w else None
    if ms is None or not w.get("ticks"):
        return None
    return ms / w["ticks"]
