"""Host milliseconds per window tick of the device tick's jitter draws: the
pool's ``transport.jitter`` span (three draws on the simulator's generator
and their split into probe and frame arrays)."""

SPAN = "transport.jitter"


def read(ctx):
    w = ctx.window
    ms = w.get("phase_ms", {}).get(SPAN) if w else None
    if ms is None or not w.get("ticks"):
        return None
    return ms / w["ticks"]
