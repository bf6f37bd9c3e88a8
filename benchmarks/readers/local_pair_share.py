"""Share of the decode steps' (token, expert) pairs whose expert this chip
holds: window gain of decode.expert_pairs_local (summed on the device by the
decode scan and returned with its tokens) over decode.expert_pairs (rows x
experts a token x layers x steps).  A chip that holds 16 of 128 experts
reads 12.5% in expectation.  None for a program that does not count them."""


def read(ctx):
    delta = ctx["reader"]("decode_rows_counted").delta
    local, pairs = (delta(ctx, "decode", "expert_pairs_local"),
                    delta(ctx, "decode", "expert_pairs"))
    return None if local is None or not pairs else 100.0 * local / pairs
