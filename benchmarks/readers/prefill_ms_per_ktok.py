"""Device milliseconds per thousand computed prompt tokens: the traced
duration of the prefill programs over executions * prefill_chunk.  Only where
every prompt length is a multiple of the chunk (else an execution's token
count is not known from the trace, and nothing is returned)."""


def prefill_tokens(ctx):
    c = (ctx["trace"] or {}).get("classes", {}).get("prefill")
    chunk = ctx["prefill_chunk"]
    if (not c or not c["count"] or ctx["traffic"].get("documents")
            or any(int(t) % chunk for t in ctx["traffic"]["tails"])):
        return None, None
    return c["dur_s"], c["count"] * chunk


def read(ctx):
    dur, toks = prefill_tokens(ctx)
    return None if dur is None else 1e3 * dur / (toks / 1e3)
