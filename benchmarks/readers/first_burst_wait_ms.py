"""Mean time from decode-ready to the first visible tokens
(``ttft.first_burst_s``): the dispatch the first token rides."""


def read(ctx):
    return ctx["reader"]("stage_wait_ms").mean_ms(ctx, "first_burst_s")
