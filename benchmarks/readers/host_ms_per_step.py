"""Engine-thread milliseconds per scheduler step spent working, not waiting:
window delta of the summary's phase_s over every phase but those that are a
wait, over the delta of ``steps``.  The waits: ``idle`` (no work),
``decode.wait`` (the read-back: the device works), ``kv.load`` (store read, H2D
and scatter through their sync), ``kv.push_wait`` (the streamer's queue and its
flush: forward, D2H and COMMIT_PUT) and ``probe`` (the sampled device drain).
What is left is Python and launches: admission, lookups, chunk and scan
launches, gathers, unpacking, retiring and streaming."""

WAITS = ("idle", "decode.wait", "kv.load", "kv.push_wait", "probe")


def read(ctx):
    a, b = ctx["engine_before"], ctx["engine_after"]
    if not a or not b or "phase_s" not in a or "phase_s" not in b:
        return None
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    host_s = sum(v - a["phase_s"].get(k, 0.0) for k, v in b["phase_s"].items()
                 if k not in WAITS)
    return 1e3 * host_s / steps
