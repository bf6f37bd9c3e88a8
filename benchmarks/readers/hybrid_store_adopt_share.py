"""Where a sequence keeps pages AND a state: of the window's prompts whose
pages the store matched deeper than HBM held them, the share that adopted
pages and checkpoint at that full depth (engine/hybrid_engine.py ->
stepprof.note_state: ``state.store_hits_full`` over ``state.store_hits``, the
/debug/engine summary's gains over the window).  100 is sound: a checkpoint
lies at every stride a document's prefill passed, so the store holds one
wherever it holds a document's last page.  None for a program that does not
count them, and where the store matched nothing."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    hits, full = (delta(ctx, "state", k) for k in ("store_hits", "store_hits_full"))
    return None if not hits or full is None else 100.0 * full / hits
