"""Share of an adopted store prefix's (layer, chunk) pages that were NOT
fetched because they belong to a sliding-window layer and lie wholly below
its window: skipped / (fetched + skipped), from the window's gain of the
program's counts (engine.prefill_start -> stepprof.note_kv_pages; the
/debug/engine summary's ``kv``).  None for a program that does not count
them."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    got = [delta(ctx, "kv", k) for k in ("store_pages_full", "store_pages_window",
                                          "store_pages_window_skipped")]
    if None in got or not sum(got):
        return None
    return 100.0 * got[2] / sum(got)
