"""Host milliseconds per window tick of the device tick's host glue: the
pool's ``transport`` phase (view refresh, static rebuild, fluid admission,
jitter draws, the traffic program's dispatch)."""


def read(ctx):
    w = ctx.window
    ms = w.get("phase_ms", {}).get("transport") if w else None
    if ms is None or not w.get("ticks"):
        return None
    return ms / w["ticks"]
