"""A push's round trips to the store (ALLOC_PUT and COMMIT_PUT on a mapped
pool, write_cache on a connection without one), in milliseconds per thousand
tokens pushed: store.push.(alloc_s + commit_s + wire_s) / tokens, last
scrape."""


def read(ctx):
    return ctx["reader"]("push_queue_ms_per_ktok").part(
        ctx, "alloc_s", "commit_s", "wire_s")
