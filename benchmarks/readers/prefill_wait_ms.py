"""Mean time between admission and decode-ready that was not the request's
own lookup, load or chunk launches (``ttft.prefill_wait_s``): parked behind the
batch's decode dispatches and other requests' chunks."""


def read(ctx):
    return ctx["reader"]("stage_wait_ms").mean_ms(ctx, "prefill_wait_s")
