"""Share of its roofline the decode step reaches: the least time the chip
could take (the larger of bytes over peak bandwidth and FLOPs over peak
FLOP/s; at these sizes the bytes bound it: every weight once but the
embedding, of which B rows; K and V of every live token once) over the
measured device time per step.  B and the live tokens are those of
``decode_rows``: averaged over the traced span from the client's rows, and
counted low (a request's prompt length only)."""


def read(ctx):
    step_ms = ctx["reader"]("decode_step_ms").read(ctx)
    got = ctx["reader"]("decode_rows").rows_and_live(ctx)
    if step_ms is None or got is None:
        return None
    b, live = got
    costs = ctx["costs"]
    need_s = max(costs.decode_step_bytes(ctx["config"], b, live)
                 / ctx["peaks"]["hbm_bytes_per_s"],
                 costs.decode_step_flops(ctx["config"], b, live)
                 / ctx["peaks"]["bf16_flops_per_s"])
    return costs.share_pct(need_s, step_ms / 1e3, "kernel.decode_roofline")
