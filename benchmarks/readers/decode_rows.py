"""Rows decoding together, averaged over the traced span: what the time per
decode step is to be read against.  From the client's rows: tokens leave in
one burst when a dispatch ends, so a request was in the batch from one
dispatch (the trace's mean whole execution) before its first burst until its
last."""


def rows_and_live(ctx, n=200):
    """(mean rows, mean live prompt tokens) over the traced span, or None.
    The live tokens are counted low: a request's prompt only."""
    span, dec = ctx["trace_span"], (ctx["trace"] or {}).get("classes", {}).get("decode")
    if span is None or not dec or not dec["count"]:
        return None
    ta, tb = span
    dispatch_s = dec["dur_s"] / dec["count"]
    b_sum = live_sum = 0.0
    for i in range(n):
        t = ta + (i + 0.5) * (tb - ta) / n
        on = [r for r in ctx["all_rows"] if r["t_first"] is not None
              and r["t_first"] - dispatch_s <= t < r["t_last"]]
        b_sum += len(on)
        live_sum += sum(r["prompt_tokens"] for r in on)
    return b_sum / n, live_sum / n


def read(ctx):
    got = rows_and_live(ctx)
    return None if got is None else got[0]
