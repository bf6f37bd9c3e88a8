"""Share of the window's prompt tokens that the scheduler adopted from the
store: store / (local + store + computed), from the deltas of
istpu_engine_prefix_tokens_total over the window."""


def read(ctx):
    d = ctx["prefix_delta"]
    total = d.get("local", 0) + d.get("store", 0) + d.get("computed", 0)
    return None if total <= 0 else 100.0 * d.get("store", 0) / total
