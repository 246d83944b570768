"""Host milliseconds per window tick of the device-to-host pulls of the tick
program's per-user outputs: the pool's ``fused_tick.pull`` span."""

SPAN = "fused_tick.pull"


def read(ctx):
    w = ctx.window
    ms = w.get("phase_ms", {}).get(SPAN) if w else None
    if ms is None or not w.get("ticks"):
        return None
    return ms / w["ticks"]
