"""Share of the sliding-window layers' pages that sequences gave back to
their pool BEFORE their release, their last token having left every window
to come: returned / acquired, from the window's gain of the program's counts
(engine._acquire_window and decode growth; engine._reclaim_window_pages ->
stepprof.note_kv_pages; the /debug/engine summary's ``kv``).  None for a
program that does not count them."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    acquired = delta(ctx, "kv", "window_pages_acquired")
    returned = delta(ctx, "kv", "window_pages_returned")
    if not acquired or returned is None:
        return None
    return 100.0 * returned / acquired
