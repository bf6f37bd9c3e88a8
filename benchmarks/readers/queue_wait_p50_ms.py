"""Median wait from submit to admission of the window's requests, from the
/debug/requests rows (waterfall.queue_s); the program's own histogram has
buckets four times apart, too coarse for a median."""


def read(ctx):
    waits = [r["waterfall"]["queue_s"] for r in ctx["server_rows"]
             if r.get("waterfall")]
    return None if not waits else ctx["stats"].nearest_rank(waits, 0.5) * 1e3
