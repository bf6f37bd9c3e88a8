"""Milliseconds of a store load's closing block_until_ready alone, per
thousand tokens loaded: window delta of store.load.sync_s over the delta of
store.load.tokens.  The uploads' arrival, the scatter and whatever the device
had queued before them: what would stay on the engine thread if the fetch were
taken off it."""


def read(ctx):
    return ctx["reader"]("load_host_ms_per_ktok").load_ms(ctx, "sync_s")
