"""Engine-thread milliseconds a push in transfer.gather_pages (the block ids'
upload and the jitted gather's launch): window delta of the summary's
phase_s["kv.push_gather"] over the delta of store.push.pushes.

``phase_ms_per_push`` serves kv.push_begin's reader too.  None for a program
without the phase, and for a window without a push."""


def phase_ms_per_push(ctx, name):
    delta = ctx["reader"]("decode_rows_counted").delta
    pushes = delta(ctx, "store", "push", "pushes")
    after = (ctx["engine_after"] or {}).get("phase_s") or {}
    if not pushes or name not in after:
        return None
    before = ctx["engine_before"].get("phase_s") or {}
    return 1e3 * (after[name] - before.get(name, 0.0)) / pushes


def read(ctx):
    return phase_ms_per_push(ctx, "kv.push_gather")
