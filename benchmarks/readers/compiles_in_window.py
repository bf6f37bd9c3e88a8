"""Backend compiles between the window's first and last request, from the
/debug/engine summary's ``compiles``.  Should read 0: every shape is warmed
during set-up."""


def read(ctx):
    a, b = ctx["engine_before"], ctx["engine_after"]
    if a is None or b is None:
        return None
    return float(b["compiles"] - a["compiles"])
