"""Share of its roofline the selective-scan kernel reaches: the least time the
chip could take for one token of one Mamba layer (the larger of the count
module's ``scan_bytes_per_token`` over the peak bandwidth and
``scan_flops_per_token`` over the bf16 peak), times the Mamba layers, over the
kernel's measured device time a token (``ssm_scan_ms_per_ktok``).  The kernel
is bound by the vector and transcendental units, for which ``peaks.json``
publishes no peak: against the two it does publish the share reads in single
digits, and is stated as that.  None where the family counts no scan or the
trace holds none."""


def read(ctx):
    got = ctx["reader"]("ssm_scan_ms_per_ktok").scan_time_and_tokens(ctx)
    if got is None:
        return None
    dur, tokens = got
    costs, cfg = ctx["costs"], ctx["config"]
    layers = costs.n_mamba(costs.sizes(cfg))
    need_s = layers * max(
        costs.scan_bytes_per_token(cfg, ctx["prefill_chunk"])
        / ctx["peaks"]["hbm_bytes_per_s"],
        costs.scan_flops_per_token(cfg) / ctx["peaks"]["bf16_flops_per_s"])
    return costs.share_pct(need_s, dur / tokens, "kernel.ssm_scan_roofline")
