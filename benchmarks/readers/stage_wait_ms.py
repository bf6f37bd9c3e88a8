"""Mean wait between the HTTP handler staging a request and the engine thread
submitting it to the scheduler (``ttft.stage_wait_s``): the engine thread sat
in a dispatch's blocking read-back.

``mean_ms`` serves the readers of the other slices too: the mean, over the
window's requests that got a first token, of one slice of the program's own
TTFT waterfall, the /debug/requests rows' ``ttft`` block
(infinistore_tpu/ledger.py build_record), whose slices are disjoint and sum to
ttft_s + admission_wait_s.  A program whose rows carry no such block gives
None."""


def mean_ms(ctx, key):
    vals = [r["ttft"][key] for r in ctx["server_rows"]
            if r.get("ttft") and r.get("ttft_s") is not None]
    return None if not vals else 1e3 * sum(vals) / len(vals)


def read(ctx):
    return mean_ms(ctx, "stage_wait_s")
