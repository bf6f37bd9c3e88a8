"""Rows per decode step as the program counts them at the call of the decode
scan (engine.decode_batch -> stepprof.note_decode): window delta of
decode.row_steps over decode.steps.

``delta`` serves the other counter readers too: the window's gain of a
lifetime sum in the /debug/engine summary (scraped at the window's start and
end), by path; None where either scrape lacks it (a program that does not
count it)."""


def delta(ctx, *path):
    ends = []
    for s in (ctx["engine_before"], ctx["engine_after"]):
        for k in path:
            s = s.get(k) if isinstance(s, dict) else None
        if s is None:
            return None
        ends.append(s)
    return ends[1] - ends[0]


def read(ctx):
    rows, steps = delta(ctx, "decode", "row_steps"), delta(ctx, "decode", "steps")
    return None if not steps or rows is None else rows / steps
