"""Milliseconds from push_begin on the engine thread to the acknowledged
COMMIT_PUT on the streamer thread, per thousand tokens pushed, over the whole
run (the window's last scrape: the fill is where the pushes are):
store.push.submit_to_commit_s / tokens."""


def read(ctx):
    push = ((ctx["engine_after"] or {}).get("store") or {}).get("push")
    if not push or not push.get("tokens"):
        return None
    return 1e3 * push["submit_to_commit_s"] / (push["tokens"] / 1e3)
