"""A push's copies into the store's mapped pool (or the staging slot), in
milliseconds per thousand tokens pushed: store.push.pool_copy_s / tokens, last
scrape."""


def read(ctx):
    return ctx["reader"]("push_queue_ms_per_ktok").part(ctx, "pool_copy_s")
