"""Host milliseconds of a store load before its scatter, per thousand tokens
loaded: window delta of store.load.(desc_s + pool_copy_s + upload_s) over the
delta of store.load.tokens.  The waits for GET_DESC answers, the copies out of
the mapped pool (with the verification where integrity is on) and the
device_put calls, each timed where it happens: what a fetch taken off the
engine thread would hide.

``load_ms`` serves the sync's reader too.  None for a program without the
fields, and for a window in which nothing was loaded."""


def load_ms(ctx, *keys):
    delta = ctx["reader"]("decode_rows_counted").delta
    tokens = delta(ctx, "store", "load", "tokens")
    parts = [delta(ctx, "store", "load", k) for k in keys]
    if not tokens or None in parts:
        return None
    return 1e3 * sum(parts) / (tokens / 1e3)


def read(ctx):
    return load_ms(ctx, "desc_s", "pool_copy_s", "upload_s")
