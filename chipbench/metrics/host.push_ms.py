"""Host milliseconds per window tick of the traffic program's dispatch,
host-to-device copies included: the pool's ``transport.push`` span."""

SPAN = "transport.push"


def read(ctx):
    w = ctx.window
    ms = w.get("phase_ms", {}).get(SPAN) if w else None
    if ms is None or not w.get("ticks"):
        return None
    return ms / w["ticks"]
