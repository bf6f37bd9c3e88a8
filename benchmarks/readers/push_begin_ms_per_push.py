"""Engine-thread milliseconds a push in transfer.push_begin (the band slices
and the copy_to_host_async kicks): window delta of the summary's
phase_s["kv.push_begin"] over the delta of store.push.pushes."""


def read(ctx):
    return ctx["reader"]("push_gather_ms_per_push").phase_ms_per_push(
        ctx, "kv.push_begin")
