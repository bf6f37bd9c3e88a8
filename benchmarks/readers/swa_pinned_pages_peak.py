"""The most pages of the sliding-window layers' pool that any one sequence has
pinned at once since the server started (set-up included: the fill computes
the longest prompts): the /debug/engine summary's ``kv.window_pinned_peak`` at
the window's end, which the program notes as the peak's rises
(engine._note_pinned -> stepprof.note_kv_pages).  A sequence holds its window's
pages and the chunk it computes: 8 + 32 here, 41 with the page the engine
reserves for a tail that is not whole.  None for a program that does not
count it."""


def read(ctx):
    peak = ((ctx["engine_after"] or {}).get("kv") or {}).get("window_pinned_peak")
    return float(peak) if peak else None
