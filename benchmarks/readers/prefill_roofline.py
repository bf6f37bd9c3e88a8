"""Share of their roofline the prefill programs reach: the least time per
computed token (the larger of FLOPs over the bf16 peak and bytes over peak
bandwidth; at 512-token chunks the FLOPs bound it: layer matmuls and causal
attention, averaged over the mix's prompt lengths) over the measured device
time per token."""

def read(ctx):
    dur, toks = ctx["reader"]("prefill_ms_per_ktok").prefill_tokens(ctx)
    if dur is None:
        return None
    costs = ctx["costs"]
    grid = [(int(t), float(w)) for t, w in ctx["traffic"]["tails"].items()]
    need_s = max(costs.prefill_flops_per_token(ctx["config"], grid)
                 / ctx["peaks"]["bf16_flops_per_s"],
                 costs.prefill_bytes_per_token(ctx["config"], ctx["prefill_chunk"])
                 / ctx["peaks"]["hbm_bytes_per_s"])
    return costs.share_pct(need_s, dur / toks, "kernel.prefill_roofline")
