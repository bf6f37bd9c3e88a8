"""Test config: force JAX onto a virtual 8-device CPU mesh before any jax
import, so sharding tests (tp/dp/sp/pp) run without TPU hardware."""

import os

import pytest

# Tests always run on a virtual 8-device CPU mesh; set ISTPU_TEST_TPU=1 on
# a machine with a chip to run the TPU-gated tests against it instead.
if not os.environ.get("ISTPU_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # NOTE: no persistent compilation cache here (infinistore_tpu/jaxcfg.py
    # sets none while JAX_PLATFORMS pins cpu): XLA:CPU AOT reload warns
    # about machine-feature mismatches (+prefer-no-gather/scatter) with a
    # SIGILL caveat on this image — not worth the rerun speedup.


# The SLO targets a fault walk's ``ServingServer`` is built under: a walk
# that injects stalls of 0.4-2 s and compiles on the request path, on a CPU
# that five other test workers load, violates the stock 2 s TTFT target by
# design; the burn watchdog then pages, and the admission controller answers
# 429 (``reason: burn``) or the health plane ``degraded`` in the walk's
# HEALTHY phase.  These walks assert failure semantics; shedding on burn is
# tests/test_admission.py's, under the stock targets.  (The fleets of
# test_critpath / test_sessions / test_frontdoor set the same through
# ISTPU_SLO_TTFT_S / ISTPU_SLO_TPOT_S, which their worker processes read.)
WALK_SLO = {"slo_ttft_s": 60.0, "slo_tpot_s": 10.0}


@pytest.fixture
def timed_walk(tmp_path_factory):
    """One lock across the run's workers, held by a test that TIMES a fleet
    of processes (a floor, a ratio, a stage's delta): no two of them measure
    against each other's stores; every other test still runs beside them."""
    import fcntl

    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent          # the run's directory, above popen-gwN
    with open(base / "timed_walk.lock", "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


_DENSE_MEMO: dict = {}


def make_dense_greedy(params, cfg, forward=None):
    """Shared memoized dense-greedy reference (`from conftest import
    make_dense_greedy`): the full-context forward per step is the suite's
    hottest cost, so (a) the step forward is JITTED over power-of-two
    padded lengths (causal masking makes trailing pad tokens invisible to
    the last real position, so the padded argmax is exact), (b) runs are
    cached and longer cached runs over the same prompt serve shorter
    requests (greedy is prefix-stable), and (c) the whole closure is
    memoized ACROSS test modules — test_engine/test_serve/test_speculative
    all derive trajectories from the identical (params, cfg).

    ``forward``: family forward with the (params, cfg, tokens) -> (logits,
    kv) signature; defaults to the dense-Llama ``prefill_forward``
    (test_moe passes ``moe_prefill_forward``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.models import prefill_forward

    if forward is None:
        forward = prefill_forward
    # fingerprint EVERY leaf: params differing anywhere (a merged adapter,
    # quantized layers) must not share a stale reference trajectory
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    memo_key = (cfg, h.hexdigest(), getattr(forward, "__name__", repr(forward)))
    hit = _DENSE_MEMO.get(memo_key)
    if hit is not None:
        return hit

    cache = {}

    @jax.jit
    def fwd(p, toks):  # toks: [1, S_pad]; one compile per pad bucket
        logits, _ = forward(p, cfg, toks)
        return logits

    def step_argmax(toks):
        S = len(toks)
        pad = 8
        while pad < S:
            pad *= 2
        padded = jnp.asarray(toks + [0] * (pad - S), dtype=jnp.int32)[None]
        return int(jnp.argmax(fwd(params, padded)[0, S - 1]))

    def dense_greedy(tokens, n_steps):
        key = (tuple(tokens), n_steps)
        hit = cache.get(key)
        if hit is not None:
            return list(hit)
        for (t, n), out in cache.items():
            if t == key[0] and n > n_steps:
                return list(out[:n_steps])
        toks = list(tokens)
        out = []
        for _ in range(n_steps):
            nxt = step_argmax(toks)
            out.append(nxt)
            toks.append(nxt)
        cache[key] = list(out)
        return out

    _DENSE_MEMO[memo_key] = dense_greedy
    return dense_greedy
