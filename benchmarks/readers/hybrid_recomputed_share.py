"""Where a sequence keeps pages AND a state: prompt tokens whose pages matched
(in HBM or in the store) and which were recomputed for want of a checkpoint at
that depth, of all the window's prompt tokens: the gain of
``state.shared_tokens_recomputed`` (engine/hybrid_engine.py) over the gains of
istpu_engine_prefix_tokens_total, every source.  0 is sound where every
document's length is a multiple of the stride.  None for a program that does
not keep both kinds (its summary's ``state`` has no ``store_hits``)."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    if delta(ctx, "state", "store_hits") is None:
        return None
    again = delta(ctx, "state", "shared_tokens_recomputed")
    d = ctx["prefix_delta"]
    total = d.get("local", 0) + d.get("store", 0) + d.get("computed", 0)
    return None if again is None or total <= 0 else 100.0 * again / total
