"""Share of the traced window in which the device ran nothing.  In a closed
loop work is always pending, so every gap is the host's: scheduling,
sampling syncs, pushes."""


def read(ctx):
    t = ctx["trace"]
    return None if not t or "busy_s" not in t else 100.0 * (1 - t["busy_s"] / t["window_s"])
