"""Share of the sliding-window layers' (layer, chunk) pages a prompt wrote that
its pushes SENT to the store: sent / (sent + not sent), from the window's gain
of the program's counts (engine._gather_push -> stepprof.note_kv_pages; the
/debug/engine summary's ``kv``: ``window_pages_pushed``,
``window_pages_push_skipped``).  A window layer sends the pages a hit at a
chunk boundary or at the prompt's end can read: with a window of 8 pages under
chunks of 32, a quarter over whole chunks and a little more with the tails.
None for a program that does not count them."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    sent = delta(ctx, "kv", "window_pages_pushed")
    skipped = delta(ctx, "kv", "window_pages_push_skipped")
    if sent is None or skipped is None or not sent + skipped:
        return None
    return 100.0 * sent / (sent + skipped)
