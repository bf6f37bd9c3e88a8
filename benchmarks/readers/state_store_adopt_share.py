"""Of the window's prompt tokens that a checkpoint covered, the share whose
checkpoint came from the store and not from a slot still resident in HBM:
store / (local + store) of the deltas of istpu_engine_prefix_tokens_total.
Near 100 where the documents do leave HBM before they are asked again.  None
for a program that keeps no state checkpoints (its /debug/engine summary has
no ``state``), and where nothing was adopted."""


def read(ctx):
    if "state" not in (ctx["engine_after"] or {}):
        return None
    d = ctx["prefix_delta"]
    covered = d.get("local", 0) + d.get("store", 0)
    return None if covered <= 0 else 100.0 * d.get("store", 0) / covered
