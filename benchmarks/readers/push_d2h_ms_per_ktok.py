"""The worker's waits for a push's device-to-host copies (each band's
np.asarray, the chunk's own forward still running inside the first), in
milliseconds per thousand tokens pushed: store.push.d2h_s / tokens, last
scrape."""


def read(ctx):
    return ctx["reader"]("push_queue_ms_per_ktok").part(ctx, "d2h_s")
