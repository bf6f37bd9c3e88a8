"""Device milliseconds per decode step: the traced duration of the decode-scan
program over executions * decode_chunk (every output length is a multiple of
decode_chunk, so every execution runs that many steps)."""


def read(ctx):
    c = (ctx["trace"] or {}).get("classes", {}).get("decode")
    if not c or not c["count"]:
        return None
    return 1e3 * c["dur_s"] / (c["count"] * ctx["traffic"]["decode_chunk"])
