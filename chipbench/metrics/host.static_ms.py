"""Host milliseconds per window tick of the device tick's static refresh:
the pool's ``transport.static`` span (the pool's view and, on a node-epoch
change, the static rebuild with the user arrays' re-encode and upload)."""

SPAN = "transport.static"


def read(ctx):
    w = ctx.window
    ms = w.get("phase_ms", {}).get(SPAN) if w else None
    if ms is None or not w.get("ticks"):
        return None
    return ms / w["ticks"]
