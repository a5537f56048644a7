"""Peak bytes in use on the fullest chip after the window, GB."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
