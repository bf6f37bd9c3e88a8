"""Host milliseconds per thousand tokens adopted from the store: the rows'
store.load_s (lookup + fetch + scatter; the load ends in block_until_ready,
kv/transfer.py _load_pages_banded, so it is a completed copy and not an
enqueue) over store_chunks * block_tokens, summed over the window."""


def read(ctx):
    T = ctx["config"]["serve"]["block_tokens"]
    rows = [r for r in ctx["server_rows"] if r.get("store", {}).get("store_chunks")]
    toks = sum(r["store"]["store_chunks"] * T for r in rows)
    if not toks:
        return None
    return 1e3 * sum(r["store"]["load_s"] for r in rows) / (toks / 1e3)
