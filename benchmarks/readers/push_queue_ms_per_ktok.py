"""A push's wait in the streamer's queue, in milliseconds per thousand tokens
pushed: store.push.queue_s / tokens, on kv.push_ms_per_ktok's own basis (the
window's last scrape: the fill is where the pushes are).  queue_s runs from the
end of push_begin on the engine thread to the worker's entry into push_commit:
behind earlier pushes.

``part`` serves the other three parts of a push too.  Each is timed once, on
the worker, where the work happens; with queue_s they sum to
submit_to_commit_s less the worker's Python between its stages.  None for a
program that keeps no queue_s (its stages are not those of one split)."""


def part(ctx, *keys):
    push = ((ctx["engine_after"] or {}).get("store") or {}).get("push")
    if not push or not push.get("tokens") or "queue_s" not in push:
        return None
    return 1e3 * sum(push[k] for k in keys) / (push["tokens"] / 1e3)


def read(ctx):
    return part(ctx, "queue_s")
