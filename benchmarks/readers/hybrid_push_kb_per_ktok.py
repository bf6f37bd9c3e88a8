"""Where a sequence keeps pages AND a state: kilobytes handed to the store,
pages and checkpoints together, per thousand prompt tokens COMPUTED, over the
window: the gain of the transfer's ``push_totals["bytes"]`` (the /debug/engine
summary's ``store.push.bytes``) over the gain of
istpu_engine_prefix_tokens_total{source="computed"}.  By the count 4,096 KB of
pages and 128 KB of states a thousand tokens at a stride of 512.  None for a
program that does not keep both kinds, and where nothing was computed."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    if delta(ctx, "state", "store_hits") is None:
        return None
    pushed = delta(ctx, "store", "push", "bytes")
    computed = ctx["prefix_delta"].get("computed", 0)
    if pushed is None or computed <= 0:
        return None
    return pushed / 1e3 / (computed / 1e3)
