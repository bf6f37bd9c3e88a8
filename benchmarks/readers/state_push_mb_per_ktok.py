"""Megabytes of state checkpoints handed to the store per thousand prompt
tokens COMPUTED, over the window: the gain of the program's count
``state.bytes_pushed`` (engine/state_engine.py -> stepprof.note_state; the
/debug/engine summary's ``state``) over the gain of
istpu_engine_prefix_tokens_total{source="computed"}.  One checkpoint a new
document whatever its length, none for a re-ask.  None for a program that
does not count them."""


def read(ctx):
    pushed = ctx["reader"]("decode_rows_counted").delta(ctx, "state", "bytes_pushed")
    computed = ctx["prefix_delta"].get("computed", 0)
    if pushed is None or computed <= 0:
        return None
    return pushed / 1e6 / (computed / 1e3)
