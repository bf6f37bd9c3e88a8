"""Engine tests: paged generation correctness, PD-disagg over a live store,
and cross-engine prefix reuse."""

import os
import signal
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu.engine import InferenceEngine, StoreConnector
from infinistore_tpu.kv import PagedCacheConfig
from infinistore_tpu.models import TINY, init_params, prefill_forward, scaled


CFG = scaled(TINY, dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(7))
T = 4  # block tokens (small for tests)


def make_pc(n_blocks=64):
    return PagedCacheConfig(
        n_layers=CFG.n_layers,
        n_kv_heads=CFG.n_kv_heads,
        head_dim=CFG.head_dim,
        n_blocks=n_blocks,
        block_tokens=T,
        dtype=CFG.dtype,
    )


from conftest import make_dense_greedy

dense_greedy = make_dense_greedy(PARAMS, CFG)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(scope="module")
def server():
    port, mport = _free_port(), _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--backend", os.environ.get("ISTPU_TEST_BACKEND", "native")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 15
    while time.time() < deadline:
        if proc.poll() is not None:
            pytest.fail("server failed to start")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    yield port
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def _conn(port, conn_type=None):
    c = ist.InfinityConnection(
        ist.ClientConfig(host_addr="127.0.0.1", service_port=port,
                         connection_type=conn_type or ist.TYPE_SHM)
    )
    c.connect()
    return c


PROMPT = [11, 42, 7, 99, 5, 3, 17, 28, 64, 1, 2]  # 11 tokens: 2 full chunks + tail


def test_generate_matches_dense_no_store():
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    got = eng.generate(PROMPT, 8)
    want = dense_greedy(PROMPT, 8)
    assert got == want


def test_prefill_exact_multiple_of_chunk():
    prompt = PROMPT[:8]  # exactly 2 chunks
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    assert eng.generate(prompt, 5) == dense_greedy(prompt, 5)


def test_single_token_prompt():
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    assert eng.generate([42], 4) == dense_greedy([42], 4)


def test_chunked_prefill_matches_single_shot():
    """Chunked prefill (bounded attention memory for long prompts) must be
    bit-identical in greedy tokens to the one-shot prefill.  The 30-token
    prompt forces the bucketed prefix buffer through a growth step AND a
    slack state (prefix_len 24 < capacity 32), exercising the traced mask."""
    prompt = [int(x) for x in np.random.RandomState(3).randint(1, 500, size=30)]
    want = InferenceEngine(PARAMS, CFG, make_pc()).generate(prompt, 6)
    eng = InferenceEngine(PARAMS, CFG, make_pc(), prefill_chunk=2 * T)
    got = eng.generate(prompt, 6)
    assert got == want
    # prompt shorter than one chunk still works
    assert InferenceEngine(PARAMS, CFG, make_pc(), prefill_chunk=2 * T).generate(
        prompt[:3], 4
    ) == dense_greedy(prompt[:3], 4)


def test_batched_decode_matches_single():
    """Lockstep batched decode over different-length sequences must produce
    exactly what each sequence gets decoded alone (vLLM-style batching)."""
    prompts = [PROMPT, PROMPT[:5], [42, 7, 9]]
    solo = []
    for p in prompts:
        eng = InferenceEngine(PARAMS, CFG, make_pc())
        solo.append(eng.generate(p, 6))
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    states = [eng.prefill(p) for p in prompts]
    batched = eng.decode_batch(states, 6)
    assert batched == solo
    for st, p, got in zip(states, prompts, batched):
        assert st.tokens == list(p) + got


def test_decode_chunk_boundary():
    """n_steps spanning multiple compiled chunks stays exact."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 3
    assert eng.generate(PROMPT, 8) == dense_greedy(PROMPT, 8)


def test_categorical_sampling_device_side():
    """Sampling mode: reproducible under a fixed key, near-greedy at tiny
    temperature, and all tokens in-vocab."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    st = eng.prefill(PROMPT)
    a = eng.decode(st, 6, sample="categorical", temperature=0.8,
                   top_k=8, rng=jax.random.PRNGKey(3))
    eng2 = InferenceEngine(PARAMS, CFG, make_pc())
    st2 = eng2.prefill(PROMPT)
    b = eng2.decode(st2, 6, sample="categorical", temperature=0.8,
                    top_k=8, rng=jax.random.PRNGKey(3))
    assert a == b
    assert all(0 <= t < CFG.vocab_size for t in a)
    eng3 = InferenceEngine(PARAMS, CFG, make_pc())
    st3 = eng3.prefill(PROMPT)
    cold = eng3.decode(st3, 6, sample="categorical", temperature=1e-4,
                       rng=jax.random.PRNGKey(0))
    assert cold == dense_greedy(PROMPT, 6)


def test_prefill_batch_matches_solo():
    """Bucketed batched prefill must leave every sequence in the same state
    as solo prefill.  PROMPT (11 tok) and PROMPT[:9] share the 16-token
    bucket (one true batched forward); [42, 7, 9] is a singleton group."""
    prompts = [PROMPT, PROMPT[:9], [42, 7, 9]]
    solo = []
    for p in prompts:
        eng = InferenceEngine(PARAMS, CFG, make_pc())
        st = eng.prefill(p)
        solo.append(eng.decode(st, 6))
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    states = eng.prefill_batch(prompts)
    assert [s.tokens for s in states] == [list(p) for p in prompts]
    got = [eng.decode(st, 6) for st in states]
    assert got == solo


def test_scheduler_backpressure_on_page_exhaustion():
    """When the allocator cannot fit the whole admission wave, the newest
    requests wait in pending and run after the first batch retires."""
    from infinistore_tpu.engine import Scheduler

    # 6 usable pages: both prompts prefill (3+3) but the first decode
    # chunk needs a 4th page per sequence -> decode-time MemoryError ->
    # the newest request is shed and resumes after the first retires.
    # (Standard 64-page pool with 58 hoarded: pressure without compiling
    # a bespoke cache shape.)
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    _hoard = eng.pages.acquire(64 - 6)
    eng.decode_chunk = 4
    sched = Scheduler(eng, max_batch=4)
    a = sched.submit(PROMPT, 5)
    b = sched.submit(PROMPT[:9], 5)
    out = sched.run()
    assert out[a] == dense_greedy(PROMPT, 5)
    assert out[b] == dense_greedy(PROMPT[:9], 5)
    # everything released: fresh + APC-cached pages add back up to capacity
    assert eng.free_pages == 6


def test_scheduler_continuous_batching():
    """Requests submitted together and staggered must each match their solo
    greedy decode; finished requests leave the batch and free their pages."""
    from infinistore_tpu.engine import Scheduler

    prompts = [PROMPT, PROMPT[:5], [42, 7, 9], [11, 13]]
    budgets = [6, 9, 4, 7]
    want = {i: dense_greedy(p, n) for i, (p, n) in enumerate(zip(prompts, budgets))}

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 3  # several admission/retire boundaries per request
    sched = Scheduler(eng, max_batch=2)  # forces queueing -> staggered admission
    ids = [sched.submit(p, n) for p, n in zip(prompts, budgets)]
    got = sched.run()
    assert {ids[i]: want[i] for i in range(len(prompts))} == got
    assert not sched.active and not sched.pending
    # all pages reclaimable again (fresh + APC-retained)
    assert eng.free_pages == eng.pc.n_blocks


LONG_PROMPT = PROMPT + PROMPT + PROMPT     # 33 tokens -> 9 chunks at T=4


def _spy_sched(max_batch, admission=None):
    """A chunked-prefill scheduler whose engine logs one letter a call:
    ``p`` a prefill chunk, ``s`` a prefill start, ``d`` a decode dispatch,
    ``|`` the end of a scheduler step (the caller appends it)."""
    from infinistore_tpu.engine import Scheduler
    from infinistore_tpu.engine.stepprof import StepProfiler

    eng = InferenceEngine(PARAMS, CFG, make_pc(256), prefill_chunk=T)
    eng.decode_chunk = 2
    calls = []
    for letter, name in (("p", "prefill_step"), ("s", "prefill_start"),
                         ("d", "decode_batch")):
        def spy(*a, _f=getattr(eng, name), _l=letter, **k):
            calls.append(_l)
            return _f(*a, **k)
        setattr(eng, name, spy)
    sched = Scheduler(eng, max_batch=max_batch, admission=admission,
                      stepprof=StepProfiler())
    return eng, sched, calls


def _run_steps(sched, calls):
    results = {}
    while sched.has_work:
        for r in sched.step():
            results[r.req_id] = r.output
        calls.append("|")
    return results


def test_scheduler_interleaves_chunked_prefill_with_decode():
    """A newcomer's long prompt must NOT stall the active batch for more
    than the step's budget: its chunks run back to back, ``max_batch`` of
    them at most, and a decode dispatch follows in the SAME step; both
    requests still produce exact greedy output."""
    eng, sched, calls = _spy_sched(max_batch=4)
    first = sched.submit(PROMPT[:5], 10)      # starts decoding immediately
    sched.step()                              # wave-prefill + first chunk
    del calls[:]
    second = sched.submit(LONG_PROMPT, 4)
    out = _run_steps(sched, calls)
    assert out[first] == dense_greedy(PROMPT[:5], 10)
    assert out[second] == dense_greedy(LONG_PROMPT, 4)
    steps = "".join(calls).split("|")
    # a batch of four: four chunks a step (the first page is a local
    # prefix hit, eight remain), each burst followed by the batch's decode
    # dispatch before the step ends
    assert steps[:2] == ["sppppd", "ppppd"], steps
    assert all(st.endswith("d") for st in steps if "p" in st), steps
    assert eng.free_pages == eng.pc.n_blocks


@pytest.mark.parametrize("max_batch,n_active,want", [
    (2, 2, ""),               # a full batch: no slot, nobody is started
    (8, 1, "spspppppppd"),    # one row of eight: the budget's eight chunks
    (4, 1, "spspppd"),        # four of the older newcomer's eight
    (8, 6, "spspppppppd"),    # two slots, two started; eight chunks all the same
    (8, 7, "sppppppppd"),     # one slot: only the OLDEST newcomer is started
])
def test_scheduler_prefill_budget_and_free_slots(max_batch, n_active, want):
    """What a step prefills is read from its own state: newcomers start
    only into FREE decode slots, oldest first (the second once the first,
    which sits on a prefix hit, has run a chunk), and the burst ends after
    ``max_batch`` chunks (``_prefill_budget``), with the decode dispatch
    behind it."""
    eng, sched, calls = _spy_sched(max_batch=max_batch)
    sched.submit(PROMPT[:5], 40)
    for i in range(1, n_active):
        sched.submit([60 + i] + PROMPT[:4 + i], 40)   # no shared pages
    sched.step()                              # the wave; all decode now
    assert len(sched.active) == n_active
    assert sched._prefill_budget() == max_batch * T
    sched.submit(LONG_PROMPT, 2)              # eight chunks after its hit
    sched.submit(LONG_PROMPT[1:], 2)          # eight, no hit
    del calls[:]
    sched.step()
    assert "".join(calls) == want + "d" * (not want), calls
    rec = sched.stepprof.tail(1)[0]
    assert rec["prefill"]["granted_tokens"] == max_batch * T
    assert rec["prefill"]["spent_tokens"] == want.count("p") * T
    assert rec["dispatches"].get("prefill", 0) == want.count("p")


def _ran_unfinished(sched):
    """In-progress prefills that have run a chunk: the ones that hold a
    buffer of computed prefix KV."""
    return [r.req_id for r, pp in sched._prefilling if pp.chunks]


def test_scheduler_prefill_burst_back_to_back_deep_queue():
    """Deep queue of equally long prompts behind a decoding batch: the free
    slots take them all at once, the budget goes to ONE of them at a time,
    chunks back to back, oldest first among equals — so requests join the
    batch in submission order, at most one unfinished prefill has run (and
    holds a buffer), and every request still matches its solo greedy
    decode with all pages free at the end."""
    eng, sched, calls = _spy_sched(max_batch=8)
    first = sched.submit(PROMPT[:5], 28)   # long-running active request
    sched.step()                           # wave prefill + first chunk
    del calls[:]
    wave = sched.stepprof.summary()["dispatches"]["prefill"]
    prompts = [[70 + i] + LONG_PROMPT[:-1] for i in range(5)]   # 9 chunks each
    newcomers = [sched.submit(p, 12) for p in prompts]
    sched.step()
    calls.append("|")
    # five of the seven free slots taken; the budget's eight chunks all
    # went to the OLDEST newcomer (equal lengths), which has one left
    assert "".join(calls) == "sssssppppppppd|", calls
    assert [r.req_id for r, _pp in sched._prefilling] == newcomers
    assert _ran_unfinished(sched) == [newcomers[0]]
    joined, peak_active = [], 0
    results = {}
    while sched.has_work:
        for r in sched.step():
            results[r.req_id] = r.output
        calls.append("|")
        assert len(_ran_unfinished(sched)) <= 1
        for r in sched.active:
            if r.req_id not in joined:
                joined.append(r.req_id)
        peak_active = max(peak_active, len(sched.active))
    assert [j for j in joined if j != first] == newcomers   # oldest first
    # the batch filled past the one-admission-per-completion ceiling of 2
    assert peak_active >= 4, peak_active
    for rid, p in zip(newcomers, prompts):
        assert results[rid] == dense_greedy(p, 12)
    assert results[first] == dense_greedy(PROMPT[:5], 28)
    assert eng.free_pages == eng.pc.n_blocks
    # the counts add up: never more spent than granted, and what was spent
    # is the chunks that ran
    tot = sched.stepprof.summary()["prefill"]
    n_chunks = "".join(calls).count("p")
    assert tot["spent_tokens"] == n_chunks * T
    assert tot["spent_tokens"] <= tot["granted_tokens"]
    assert (sched.stepprof.summary()["dispatches"]["prefill"] - wave
            == n_chunks)
    for rec in sched.stepprof.tail():
        b = rec.get("prefill")
        assert b is None or b["spent_tokens"] <= b["granted_tokens"]


def test_scheduler_short_newcomer_passes_a_long_prefill():
    """Fewest chunks left first: a short prompt that arrives while a long
    one is mid-ingestion is started into a free slot, prefilled and
    decoding in its FIRST step, instead of queueing behind the long one's
    chunks (a re-ask whose prefix came from the store, behind a new
    document); the long one goes on with the rest of the budget."""
    eng, sched, calls = _spy_sched(max_batch=4)
    first = sched.submit(PROMPT[:5], 30)
    sched.step()
    long_p = [80] + LONG_PROMPT + LONG_PROMPT       # 67 tokens: 17 chunks
    big = sched.submit(long_p, 4)
    del calls[:]
    sched.step()
    assert "".join(calls) == "sppppd", calls        # four of seventeen
    short = sched.submit([81, 5, 9], 4)             # one chunk
    del calls[:]
    done = sched.step()
    # the short one first (one chunk), then three more of the long one's
    assert "".join(calls) == "sppppd", calls
    assert short in [r.req_id for r in sched.active + done]
    assert [r.req_id for r, _pp in sched._prefilling] == [big]
    assert sched._prefilling[0][1].chunks == 7
    out = _run_steps(sched, calls)
    out.update({r.req_id: r.output for r in done})
    assert out[short] == dense_greedy([81, 5, 9], 4)
    assert out[big] == dense_greedy(long_p, 4)
    assert out[first] == dense_greedy(PROMPT[:5], 30)
    assert eng.free_pages == eng.pc.n_blocks


def test_scheduler_long_prefill_gains_a_chunk_every_step():
    """A steady stream of short newcomers whose chunks alone exceed the
    budget must not starve a long prompt: the step's first chunk goes to
    the OLDEST started prefill, so the long one's chunk count grows in
    EVERY step (the progress the one-chunk-each rotation gave), and the
    short ones still pass it with the rest of the budget."""
    eng, sched, calls = _spy_sched(max_batch=4)          # four chunks a step
    first = sched.submit(PROMPT[:5], 60)
    sched.step()
    long_p = [80] + LONG_PROMPT + LONG_PROMPT            # 17 chunks
    big = sched.submit(long_p, 4)
    shorts, passed, results = {}, 0, {}
    for n in range(40):
        if not any(r.req_id == big for r, _pp in sched._prefilling) and n:
            break
        # two free slots, two short newcomers of two chunks each: four
        # chunks wanted by the short ones alone, in every step
        while len(sched.pending) < 2:
            p = [100 + len(shorts), 7, 9, 11, 13]
            shorts[sched.submit(p, 2)] = p
        before = {r.req_id: pp.chunks for r, pp in sched._prefilling}
        del calls[:]
        for r in sched.step():
            results[r.req_id] = r.output
        after = {r.req_id: pp.chunks for r, pp in sched._prefilling}
        if big in after:
            assert after[big] > before.get(big, 0), (n, before, after)
        assert "".join(calls).count("p") == 4, calls     # the budget, spent
        passed = sum(1 for rid in results if rid in shorts)
    else:
        raise AssertionError("the long prompt never finished")
    assert passed >= 8        # short ones passed it all the while
    results.update(_run_steps(sched, calls))
    assert results[big] == dense_greedy(long_p, 4)
    assert results[first] == dense_greedy(PROMPT[:5], 60)
    for rid, p in shorts.items():
        assert results[rid] == dense_greedy(p, 2)
    assert eng.free_pages == eng.pc.n_blocks


def test_scheduler_prefill_order_is_priority_then_fewest_chunks_left():
    """After the oldest's chunk the budget goes by (priority, chunks left):
    a protected-lane long prompt runs before a one-chunk prompt of a lower
    lane, and within a lane the shorter first."""
    eng, sched, calls = _spy_sched(max_batch=8)
    first = sched.submit(PROMPT[:5], 40)
    sched.step()
    a = sched.submit([80] + LONG_PROMPT + LONG_PROMPT, 8)           # 17
    sched.step()                                  # eight of them; nine left
    b = sched.submit([81] + LONG_PROMPT + PROMPT, 8, priority=1)    # 12
    c = sched.submit([82, 5, 9], 8)                                 # one
    d = sched.submit([83] + PROMPT, 8, priority=1)                  # three
    sched.step()
    # the oldest (a) one chunk; then lane 1: d's three, four of b's; c none
    ran = {r.req_id: pp.chunks for r, pp in sched._prefilling}
    assert ran == {a: 9, b: 4, c: 0}, ran
    assert d in [r.req_id for r in sched.active]
    sched.step()
    # a one more; b's eight left take the other seven; c still waits
    ran = {r.req_id: pp.chunks for r, pp in sched._prefilling}
    assert ran == {a: 10, b: 11, c: 0}, ran
    sched.step()
    # a, then b's last, then lane 0 by chunks left: c's one, five more of a
    assert c in [r.req_id for r in sched.active]
    assert b in [r.req_id for r in sched.active]
    assert [(r.req_id, pp.chunks) for r, pp in sched._prefilling] == [(a, 16)]
    out = _run_steps(sched, calls)
    assert out[c] == dense_greedy([82, 5, 9], 8)
    assert out[b] == dense_greedy([81] + LONG_PROMPT + PROMPT, 8)
    assert eng.free_pages == eng.pc.n_blocks


def test_scheduler_one_loaded_prefix_waits_at_a_time():
    """A prompt with a prefix hit takes its prefix buffer when it STARTS,
    so such prompts start one at a time, each once the one before has run
    a chunk; prompts without a hit hold nothing until they run and start
    into every free slot at once."""
    eng, sched, calls = _spy_sched(max_batch=8)
    first = sched.submit(PROMPT[:5], 40)          # registers PROMPT[:4]'s page
    sched.step()
    inner, waiting = eng.prefill_start, []

    def watch(*a, **k):
        waiting.append(sum(1 for _r, pp in sched._prefilling
                           if pp.buf is not None and not pp.chunks))
        return inner(*a, **k)

    eng.prefill_start = watch
    reasks = [PROMPT[:4] + [70 + i, 3, 5] for i in range(4)]  # a hit, one chunk
    ids = [sched.submit(p, 2) for p in reasks]
    fresh = [sched.submit([90 + i] + PROMPT, 2) for i in range(2)]  # no hit
    del calls[:]
    done = sched.step()
    # each re-ask runs before the next starts; the fresh ones start together
    assert "".join(calls) == "spspspspssppppd", calls
    assert waiting == [0] * 6
    assert not {r.req_id for r, _pp in sched._prefilling} & set(ids)
    out = _run_steps(sched, calls)
    out.update({r.req_id: r.output for r in done})
    for rid, p in zip(ids, reasks):
        assert out[rid] == dense_greedy(p, 2)
    for i, rid in enumerate(fresh):
        assert out[rid] == dense_greedy([90 + i] + PROMPT, 2)
    assert eng.free_pages == eng.pc.n_blocks


class _Throttle:
    """The admission controller's seam as the scheduler sees it."""

    def __init__(self, cap):
        self.cap = cap

    def check_submit(self, **kw):
        import types
        return types.SimpleNamespace(admitted=True)

    def prefill_token_budget(self):
        return self.cap


@pytest.mark.parametrize("cap,want_chunks", [
    (T, 1),          # degraded mode: one chunk a step, seven slots free
    (3 * T, 3),
    (1, 1),          # smaller than a chunk: the one chunk a step can run
    (100 * T, 8),    # larger than the step's own budget: that one wins
    (None, 8),       # healthy: no throttle
])
def test_scheduler_degraded_throttle_wins_when_smaller(cap, want_chunks):
    eng, sched, calls = _spy_sched(max_batch=8, admission=_Throttle(cap))
    first = sched.submit(PROMPT[:5], 12)
    sched.step()
    del calls[:]
    second = sched.submit(LONG_PROMPT, 6)
    sched.step()
    assert "".join(calls) == "s" + "p" * want_chunks + "d", calls
    out = _run_steps(sched, calls)
    assert out[second] == dense_greedy(LONG_PROMPT, 6)
    assert out[first] == dense_greedy(PROMPT[:5], 12)
    assert eng.free_pages == eng.pc.n_blocks


def test_scheduler_degraded_mode_mixed_lanes_every_started_prompt_drains():
    """Degraded mode, one chunk a step, two lanes: the chunk is the step's
    first, so it goes to the OLDEST started prefill whatever its lane (as
    it did before the budget) and a protected-lane long prompt is never
    starved by one-chunk newcomers; where both wait in ``pending`` the
    protected lane STARTS first."""
    eng, sched, calls = _spy_sched(max_batch=8, admission=_Throttle(T))
    first = sched.submit(PROMPT[:5], 60)
    sched.step()
    low = sched.submit([80] + PROMPT, 2)                     # three chunks
    prot = sched.submit([81] + LONG_PROMPT, 2, priority=1)   # nine
    order = []
    for n in range(40):
        if n < 20:   # one-chunk newcomers of either lane keep arriving
            sched.submit([100 + n, 5, 9], 2, priority=n % 2)
        before = {r.req_id: pp.chunks for r, pp in sched._prefilling}
        del calls[:]
        sched.step()
        assert "".join(calls).count("p") <= 1, calls
        for r, pp in sched._prefilling:
            if pp.chunks > before.get(r.req_id, 0):
                order.append(r.req_id)
        oldest = next(iter(before), None)
        if oldest is not None and "p" in calls:
            still = {r.req_id: pp.chunks for r, pp in sched._prefilling}
            assert oldest not in still or still[oldest] > before[oldest]
        if not sched._prefilling and not sched.pending:
            break
    # the protected prompt started first and took the first eight steps'
    # chunks (its ninth finished it); the low lane's prompt came next
    assert order[:8] == [prot] * 8, order
    assert order[8:10] == [low] * 2, order
    out = _run_steps(sched, calls)
    assert eng.free_pages == eng.pc.n_blocks


def test_scheduler_cancel_mid_burst_frees_pages():
    """A cancellation that lands while the request's chunks are running
    back to back (another thread's ``cancel``) stops the burst at the next
    chunk: its pages go back, the budget moves on to the next newcomer in
    the same step, and the batch keeps decoding."""
    eng, sched, calls = _spy_sched(max_batch=8)
    first = sched.submit(PROMPT[:5], 8)
    sched.step()
    del calls[:]
    victim = sched.submit(LONG_PROMPT, 4)           # eight chunks left
    nxt = sched.submit([90] + LONG_PROMPT, 4)       # nine
    inner = eng.prefill_step

    def cancel_after_two(pp):
        st = inner(pp)
        if "".join(calls).count("p") == 2:
            assert sched.cancel(victim)
        return st

    eng.prefill_step = cancel_after_two
    done = sched.step()
    # two chunks of the victim, then the rest of the eight went to the next
    assert "".join(calls) == "spspppppppd", calls
    assert [r.req_id for r in done] == [victim] and done[0].cancelled
    assert [(r.req_id, pp.chunks) for r, pp in sched._prefilling] == [(nxt, 6)]
    out = _run_steps(sched, calls)
    assert out[first] == dense_greedy(PROMPT[:5], 8)
    assert out[nxt] == dense_greedy([90] + LONG_PROMPT, 4)
    assert victim not in out
    assert eng.free_pages == eng.pc.n_blocks  # nothing leaked


def test_scheduler_cancel_mid_chunked_prefill():
    """Cancelling a request while its prompt is mid-ingestion frees its
    pages and the batch keeps decoding."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc(), prefill_chunk=T)
    eng.decode_chunk = 2
    sched = Scheduler(eng, max_batch=4)
    first = sched.submit(PROMPT[:5], 8)
    sched.step()
    victim = sched.submit(PROMPT + PROMPT + PROMPT, 4)
    sched.step()  # prefill_start happened; at most one chunk done
    assert sched._prefilling
    assert sched.cancel(victim)
    out = sched.run()
    assert out[first] == dense_greedy(PROMPT[:5], 8)
    assert out[victim] == []  # cancelled before producing anything
    assert eng.free_pages == eng.pc.n_blocks  # nothing leaked


def test_scheduler_mixes_sampling_params_in_one_batch():
    """Sampling params are per-row traced vectors: a greedy request, a
    temperature request, and a top-k request all share ONE lockstep batch,
    and each row's result matches the same request run solo (top_k=1 is
    deterministic — categorical truncated to the argmax — so every row here
    has a solo-verifiable answer)."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    sched = Scheduler(eng, max_batch=4)
    g = sched.submit(PROMPT, 5)  # greedy
    k1 = sched.submit(PROMPT[:5], 5, sample="categorical", temperature=0.7,
                      top_k=1)
    c = sched.submit(PROMPT[:6], 5, sample="categorical", temperature=0.9,
                     top_p=0.8)
    sched._admit()
    assert {r.req_id for r in sched.active} == {g, k1, c}  # one batch, FIFO
    out = sched.run()
    assert out[g] == dense_greedy(PROMPT, 5)
    assert out[k1] == dense_greedy(PROMPT[:5], 5)  # top_k=1 == greedy
    assert len(out[c]) == 5
    assert all(0 <= t < CFG.vocab_size for t in out[c])


def test_scheduler_eos_stops_early():
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    full = dense_greedy(PROMPT, 8)
    eos = full[2]  # a token greedy decode actually emits mid-stream
    sched = Scheduler(eng, max_batch=2)
    rid = sched.submit(PROMPT, 8, eos_id=eos)
    out = sched.run()[rid]
    assert out == full[: full.index(eos) + 1]


def test_prefill_streams_kv_per_chunk(server):
    """Chunked prefill pushes each chunk's pages to the store as soon as
    that chunk's forward finishes — one push per complete chunk riding the
    background streamer, NOT one bulk save after the loop (the reference's
    layer-by-layer prefill write, VERDICT r2 missing #2).  The store
    contents must still serve a decode-side engine byte-for-byte."""
    conn = _conn(server)
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=conn, model_id="stream-test",
        prefill_chunk=T,
    )
    pushes = []
    orig = eng.transfer.push_commit

    def spy(token):
        # the streamer hands the worker half a (bands, keys) token;
        # spying here observes exactly the per-chunk push cadence
        pushes.append(list(token[1]))
        return orig(token)

    eng.transfer.push_commit = spy
    eng.prefill(PROMPT)  # len 11, T=4 -> 2 complete chunks + tail
    assert len(pushes) == len(PROMPT) // T  # one push per complete chunk
    assert all(len(p) == 1 for p in pushes)  # each carries ONE chunk's keys

    dec_conn = _conn(server)
    dec = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=dec_conn, model_id="stream-test"
    )
    st2 = dec.prefill(PROMPT)
    assert st2.reused_chunks == len(PROMPT) // T
    assert dec.decode(st2, 8) == dense_greedy(PROMPT, 8)
    conn.close()
    dec_conn.close()


def test_relaxed_durability_prefill_returns_before_flush(server):
    """store_durability="relaxed": prefill must return as soon as the
    last chunk's pages are QUEUED — on a store slower than compute the
    return time is compute-bound, not push-bound (the reference's <=1%
    overlap design point, design.rst:57-58, without the strict
    durability barrier).  Unflushed chunks are simply not visible to a
    decode-side engine yet; ``store_flush()`` is the durability barrier
    after which prefix reuse serves them byte-for-byte."""
    import time as _time

    conn = _conn(server)
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=conn, model_id="relaxed-test",
        prefill_chunk=T, store_durability="relaxed",
    )
    # warm the compiled paths so the timed prefill is dispatch-only
    eng.release(eng.prefill(PROMPT))
    eng.store_flush()

    DELAY = 0.5
    orig = eng.transfer.push_commit
    done = []

    def slow(token):
        _time.sleep(DELAY)
        done.append(list(token[1]))
        return orig(token)

    eng.transfer.push_commit = slow
    t0 = _time.perf_counter()
    st = eng.prefill([t + 1 for t in PROMPT])  # distinct prefix
    dt = _time.perf_counter() - t0
    n_chunks = len(PROMPT) // T
    # two slow pushes (0.5 s each) were queued; a strict prefill would
    # have waited for both.  Generous bound: well under ONE push delay.
    assert dt < DELAY, f"relaxed prefill waited on the store ({dt:.2f}s)"
    eng.store_flush()
    assert len(done) == n_chunks  # the barrier drained every queued push

    dec_conn = _conn(server)
    dec = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=dec_conn, model_id="relaxed-test"
    )
    st2 = dec.prefill([t + 1 for t in PROMPT])
    assert st2.reused_chunks == n_chunks  # flushed pages serve reuse
    assert dec.decode(st2, 8) == dense_greedy([t + 1 for t in PROMPT], 8)
    eng.release(st)
    conn.close()
    dec_conn.close()


def test_relaxed_durability_push_error_surfaces_at_flush(server):
    """A push failure under relaxed durability parks and re-raises at the
    next store_flush() — never silently lost, never crashing prefill."""
    conn = _conn(server)
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=conn, model_id="relaxed-err",
        prefill_chunk=T, store_durability="relaxed",
    )

    def boom(token):
        raise RuntimeError("push failed")

    eng.transfer.push_commit = boom
    st = eng.prefill(PROMPT)  # must not raise here
    with pytest.raises(RuntimeError, match="push failed"):
        eng.store_flush()
    eng.store_flush()  # error consumed; barrier is reusable
    eng.release(st)
    conn.close()


def test_prefix_reuse_survives_partial_eviction(server):
    """The server LRU evicts per PAGE key, so a chunk can lose a middle
    layer while the layers lookup_prefix probes (first, last) survive:
    lookup reports a hit, the all-or-nothing load then 404s, and prefill
    must fall back to recomputing instead of dying (VERDICT r2 missing #4)."""
    from infinistore_tpu.kv.hashing import chunk_keys as ck_fn, layer_key

    prefill_conn, decode_conn = _conn(server), _conn(server)
    a = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=prefill_conn, model_id="evict-test"
    )
    a.prefill(PROMPT)

    # evict ONE middle-layer page of the first chunk (layer 0 and the last
    # layer — the probed ones — stay resident)
    keys = ck_fn(PROMPT, "evict-test", chunk_tokens=T)
    # the wire key carries the engine's quant-namespace suffix (int8 is
    # the store-hop default)
    victim = layer_key(keys[0], CFG.n_layers // 2) + a.transfer._key_suffix
    assert prefill_conn.delete_keys([victim]) == 1

    b = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=decode_conn, model_id="evict-test"
    )
    st = b.prefill(PROMPT)
    assert st.reused_chunks == 0  # store hit withdrawn, full recompute
    got = b.decode(st, 8)
    assert got == dense_greedy(PROMPT, 8)
    prefill_conn.close()
    decode_conn.close()


def test_scheduler_priority_admission_order():
    """Higher-priority requests jump the pending queue (FIFO within a
    level); a shed/held request re-queues AHEAD of its priority peers.
    Admission order only — in-flight requests are never preempted."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 2
    sched = Scheduler(eng, max_batch=1)  # serialize: admission order visible
    low1 = sched.submit(PROMPT[:4], 3, priority=0)
    low2 = sched.submit(PROMPT[:5], 3, priority=0)
    high = sched.submit(PROMPT[:6], 3, priority=5)
    # the high-priority request sits ahead of the earlier low ones
    assert [r.req_id for r in sched.pending] == [high, low1, low2]

    finish_order = []
    results = {}
    while sched.has_work:
        for r in sched.step():
            finish_order.append(r.req_id)
            results[r.req_id] = r.output
    assert finish_order == [high, low1, low2]
    # ordering must not change any output
    assert results[high] == dense_greedy(PROMPT[:6], 3)
    assert results[low1] == dense_greedy(PROMPT[:4], 3)
    assert results[low2] == dense_greedy(PROMPT[:5], 3)


def test_scheduler_enqueue_priority_and_requeue_front():
    """_enqueue invariants: priority-descending order with FIFO inside a
    level; front=True (a shed/held request) re-queues AHEAD of its
    priority peers but never ahead of a higher level."""
    from infinistore_tpu.engine import Scheduler
    from infinistore_tpu.engine.scheduler import Request

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    sched = Scheduler(eng)

    def req(rid, prio):
        return Request(req_id=rid, tokens=[1], max_new_tokens=1,
                       priority=prio)

    for rid, prio in ((0, 0), (1, 5), (2, 0), (3, 5), (4, 2)):
        sched._enqueue(req(rid, prio))
    assert [r.req_id for r in sched.pending] == [1, 3, 4, 0, 2]
    # shed request at priority 2 re-queues ahead of priority-2 peers...
    sched._enqueue(req(9, 2), front=True)
    assert [r.req_id for r in sched.pending] == [1, 3, 9, 4, 0, 2]
    # ...but a shed priority-0 request stays below every higher level
    sched._enqueue(req(8, 0), front=True)
    assert [r.req_id for r in sched.pending] == [1, 3, 9, 4, 8, 0, 2]
    sched.pending.clear()


def test_sampling_penalties_match_hand_reference():
    """presence/frequency (generated tokens) and repetition (prompt +
    generated) penalties applied on device inside the decode scan must
    reproduce the hand-rolled dense reference EXACTLY (greedy argmax over
    penalized logits, counts threading across chunk boundaries)."""
    P_, F_, R_ = 0.9, 0.4, 1.7
    toks = list(PROMPT)
    counts = np.zeros(CFG.vocab_size)
    pseen = np.zeros(CFG.vocab_size, bool)
    pseen[np.asarray(PROMPT)] = True
    want = []
    # jitted reference forward over pow2-padded lengths (causal masking
    # keeps pad tokens invisible to the last real position): 2 compiles
    # instead of 10 eager full forwards
    fwd = jax.jit(lambda p, t: prefill_forward(p, CFG, t)[0])
    for _ in range(10):
        S = len(toks)
        pad = 8
        while pad < S:
            pad *= 2
        logits = fwd(
            PARAMS, jnp.asarray(toks + [0] * (pad - S), jnp.int32)[None]
        )
        l = np.asarray(logits[0, S - 1], np.float32)
        seen = pseen | (counts > 0)
        l = np.where(seen, np.where(l > 0, l / R_, l * R_), l)
        l = l - F_ * counts - P_ * (counts > 0)
        nxt = int(np.argmax(l))
        want.append(nxt)
        toks.append(nxt)
        counts[nxt] += 1

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4  # counts must survive the chunk boundary
    st = eng.prefill(PROMPT)
    got = eng.decode(st, 10, presence_penalty=P_, frequency_penalty=F_,
                     repetition_penalty=R_)
    assert got == want
    assert got != dense_greedy(PROMPT, 10)  # the penalties actually bit
    eng.release(st)


def test_penalties_per_row_in_one_batch():
    """A penalized row and a plain greedy row share one lockstep batch:
    the plain row's output must be bit-identical to its solo greedy decode
    (zero penalties are exact no-ops under the penalized program)."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    sched = Scheduler(eng, max_batch=4)
    plain = sched.submit(PROMPT, 8)
    pen = sched.submit(PROMPT[:6], 8, repetition_penalty=1.8,
                       presence_penalty=0.5)
    out = sched.run()
    assert out[plain] == dense_greedy(PROMPT, 8)
    assert len(out[pen]) == 8
    # repetition-penalized greedy must differ from plain greedy here
    # (TINY greedy repeats tokens quickly at these lengths)
    solo = InferenceEngine(PARAMS, CFG, make_pc())
    st = solo.prefill(PROMPT[:6])
    assert out[pen] == solo.decode(st, 8, repetition_penalty=1.8,
                                   presence_penalty=0.5,
                                   gen_start=6)


def test_seeded_sampling_independent_of_batchmates():
    """A seeded request's tokens depend only on (seed, positions): the
    same seeded row must sample the same trajectory solo, in a mixed
    batch, and across different decode chunk sizes (the per-request-seed
    serving contract)."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    st = eng.prefill(PROMPT)
    solo = eng.decode(st, 8, sample="categorical", temperature=0.9,
                      seed=123)
    eng.release(st)

    # same seed inside a lockstep batch with an unseeded batchmate
    st_a = eng.prefill(PROMPT)
    st_b = eng.prefill(PROMPT[:5])
    outs = eng.decode_batch(
        [st_a, st_b], 8, sample="categorical", temperature=0.9,
        seed=[123, None],
    )
    assert outs[0] == solo
    eng.release(st_a)
    eng.release(st_b)

    # same seed with a DIFFERENT chunking (positions drive the stream)
    eng.decode_chunk = 2
    st = eng.prefill(PROMPT)
    assert eng.decode(st, 8, sample="categorical", temperature=0.9,
                      seed=123) == solo
    eng.release(st)

    # a different seed diverges
    st = eng.prefill(PROMPT)
    assert eng.decode(st, 8, sample="categorical", temperature=0.9,
                      seed=124) != solo
    eng.release(st)


def test_swa_reclaims_window_dead_pages():
    """Fully-windowed config (Mistral stack): a long generation's live
    pages must plateau at ~window/block_tokens instead of growing with the
    sequence, while the output still matches the dense windowed reference
    (VERDICT r3 weak #4 / next #4)."""
    wcfg = scaled(TINY, dtype=jnp.float32, sliding_window=8)
    wparams = init_params(wcfg, jax.random.PRNGKey(21))
    wdense = make_dense_greedy(wparams, wcfg)
    eng = InferenceEngine(wparams, wcfg, make_pc())
    st = eng.prefill(PROMPT)  # 11 tokens
    out, live_hist = [], []
    for _ in range(6):
        out += eng.decode(st, 8)
        live_hist.append(len(st.block_ids) - st.reclaimed_pages)
    assert out == wdense(PROMPT, 48)
    assert st.reclaimed_pages > 0
    # plateau: live pages bounded by (window + decode run + page slack)/T,
    # independent of total length (15 pages were written in all)
    assert max(live_hist[3:]) <= 6, live_hist
    # reclaimed pages really are reusable: release returns the rest and
    # the pool is whole again
    eng.release(st)
    assert eng.free_pages == eng.pc.n_blocks


def test_swa_mixed_global_layers_keep_pages():
    """Gemma-2-style alternating local/global stack: blocks span all
    layers and the global layers attend everything, so NOTHING may be
    reclaimed (reclaiming would corrupt global-layer reads)."""
    gcfg = scaled(TINY, dtype=jnp.float32, sliding_window=8,
                  window_pattern=2)
    gparams = init_params(gcfg, jax.random.PRNGKey(22))
    gdense = make_dense_greedy(gparams, gcfg)
    eng = InferenceEngine(gparams, gcfg, make_pc())
    st = eng.prefill(PROMPT)
    out = eng.decode(st, 40)
    assert out == gdense(PROMPT, 40)
    assert st.reclaimed_pages == 0
    eng.release(st)
    assert eng.free_pages == eng.pc.n_blocks


def test_swa_reclaim_under_pressure_frees_pool_for_batchmates():
    """The reclaimed pages actually relieve allocator pressure: a pool too
    small to hold the whole generation un-reclaimed still completes."""
    wcfg = scaled(TINY, dtype=jnp.float32, sliding_window=8)
    wparams = init_params(wcfg, jax.random.PRNGKey(21))
    wdense = make_dense_greedy(wparams, wcfg)
    # 48 new tokens over 11 prompt -> 15 pages unreclaimed; leave it 10
    # usable (standard pool + hoard: no bespoke cache shape to compile)
    eng = InferenceEngine(wparams, wcfg, make_pc())
    _hoard = eng.pages.acquire(64 - 10)
    st = eng.prefill(PROMPT)
    out = []
    for _ in range(6):
        out += eng.decode(st, 8)
    assert out == wdense(PROMPT, 48)
    eng.release(st)
    assert eng.free_pages == 10


def test_pd_disaggregation(server):
    """Prefill engine pushes KV to the store; a separate decode engine pulls
    it and must produce the same tokens as the dense reference."""
    prefill_conn, decode_conn = _conn(server), _conn(server)
    prefill_eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=prefill_conn, model_id="pd-test"
    )
    decode_eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=decode_conn, model_id="pd-test"
    )

    # prefill node: process the prompt, KV lands in the store
    st = prefill_eng.prefill(PROMPT)
    assert st.reused_chunks == 0

    # decode node: admits the same prompt; must reuse the stored prefix
    st2 = decode_eng.prefill(PROMPT)
    assert st2.reused_chunks == len(PROMPT) // T  # all complete chunks reused
    got = decode_eng.decode(st2, 8)
    assert got == dense_greedy(PROMPT, 8)
    prefill_conn.close()
    decode_conn.close()


def test_pd_disaggregation_over_tcp(server):
    """Same PD flow with both engines on the TCP transport — the DCN
    cross-host path (reference BASELINE config 4: 2-host PD transfer).
    Chunked prefill on the decode side exercises reuse + chunking + TCP."""
    prefill_conn = _conn(server, ist.TYPE_TCP)
    decode_conn = _conn(server, ist.TYPE_TCP)
    prefill_eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=prefill_conn, model_id="pd-tcp"
    )
    decode_eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=decode_conn, model_id="pd-tcp",
        prefill_chunk=2 * T,
    )
    prefill_eng.prefill(PROMPT)
    st = decode_eng.prefill(PROMPT)
    assert st.reused_chunks == len(PROMPT) // T
    assert decode_eng.decode(st, 8) == dense_greedy(PROMPT, 8)
    prefill_conn.close()
    decode_conn.close()


def test_pd_disaggregation_quantized(server):
    """PD flow with int8-quantized store pages: half the transfer bytes must
    still reproduce the dense greedy tokens (kv/quant.py error bound)."""
    prefill_conn, decode_conn = _conn(server), _conn(server)
    prefill_eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=prefill_conn, model_id="pd-q8",
        kv_quant="int8",
    )
    decode_eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=decode_conn, model_id="pd-q8",
        kv_quant="int8",
    )
    prefill_eng.prefill(PROMPT)
    st = decode_eng.prefill(PROMPT)
    assert st.reused_chunks == len(PROMPT) // T
    assert decode_eng.decode(st, 8) == dense_greedy(PROMPT, 8)
    prefill_conn.close()
    decode_conn.close()


def test_cross_request_prefix_reuse(server):
    """Second request sharing a long prefix reuses stored chunks."""
    conn = _conn(server)
    eng = InferenceEngine(PARAMS, CFG, make_pc(), conn=conn, model_id="reuse-test")
    prompt_a = list(range(40, 56))  # 4 chunks
    eng.prefill(prompt_a)
    prompt_b = prompt_a[:12] + [200, 201, 202, 203, 204]
    st = eng.prefill(prompt_b)
    assert st.reused_chunks == 3  # 12 shared tokens = 3 chunks
    got = eng.decode(st, 6)
    assert got == dense_greedy(prompt_b, 6)
    conn.close()


def test_connector_roundtrip(server):
    from infinistore_tpu.kv import BlockAllocator, init_cache, prefill_to_pages, write_pages

    conn = _conn(server)
    pc = make_pc()
    connector = StoreConnector(conn, pc, model_id="connector-test")
    tokens = list(range(16))  # 4 chunks
    assert connector.lookup(tokens) == 0

    cache = init_cache(pc)
    _, kv = prefill_forward(PARAMS, CFG, jnp.asarray(tokens, dtype=jnp.int32)[None])
    pages = prefill_to_pages(kv[:, :, 0], 4, T)
    cache = write_pages(cache, jnp.asarray([0, 1, 2, 3]), pages)
    connector.store_kv(tokens, cache, [0, 1, 2, 3])
    assert connector.lookup(tokens) == 16

    cache2 = init_cache(pc)
    cache2, n = connector.retrieve_kv(tokens, cache2, [8, 9, 10, 11])
    assert n == 16
    np.testing.assert_array_equal(
        np.asarray(cache2[:, :, :, 8:12]), np.asarray(cache[:, :, :, 0:4])
    )

    assert connector.invalidate(tokens) == 4 * CFG.n_layers
    assert connector.lookup(tokens) == 0
    conn.close()


# ---- automatic prefix caching (HBM page dedup) ----

def test_apc_shares_pages_across_sequences():
    """Two live sequences with a common prefix must share the complete-chunk
    pages in HBM (no recompute, no duplicate pages) and still decode the
    dense-reference tokens."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    a = eng.prefill(PROMPT)
    free_before = eng.free_pages
    b = eng.prefill(PROMPT)  # identical prompt
    # shared: both complete chunks; private: the tail page only
    assert b.reused_chunks == len(PROMPT) // T
    assert b.block_ids[: b.reused_chunks] == a.block_ids[: b.reused_chunks]
    assert free_before - eng.free_pages == 1  # one private tail page
    assert eng.decode(b, 8) == dense_greedy(PROMPT, 8)
    # the survivor keeps decoding correctly after the sharer releases
    eng.release(b)
    assert eng.decode(a, 8) == dense_greedy(PROMPT, 8)
    eng.release(a)


def test_apc_partial_prefix_and_divergence():
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    base = [9, 8, 7, 6, 5, 4, 3, 2, 1, 10, 11, 12]  # 3 full chunks
    a = eng.prefill(base)
    fork = base[:8] + [100, 101, 102, 103]  # shares 2 chunks, diverges after
    b = eng.prefill(fork)
    assert b.reused_chunks == 2
    assert b.block_ids[:2] == a.block_ids[:2]
    assert b.block_ids[2] != a.block_ids[2]  # divergent chunk is private
    assert eng.decode(b, 6) == dense_greedy(fork, 6)


def test_apc_retains_pages_after_release():
    """Released pages stay resident (reclaimable LRU): a later identical
    prefill reuses them with zero recompute."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    st = eng.prefill(PROMPT)
    eng.release(st)
    st2 = eng.prefill(PROMPT)
    assert st2.reused_chunks == len(PROMPT) // T
    assert eng.decode(st2, 8) == dense_greedy(PROMPT, 8)


def test_apc_reclaims_cached_pages_under_pressure():
    """Cached (ref-0) pages are handed back when fresh pages run out, oldest
    first; live sequences' pages are never reclaimed."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    _hoard = eng.pages.acquire(64 - 8)  # 8 usable; standard cache shape
    a = eng.prefill([1, 2, 3, 4, 5, 6, 7, 8])  # 2 pages, registered
    eng.release(a)
    assert eng.free_pages == 8  # 6 fresh + 2 cached
    b = eng.prefill([11, 12, 13, 14] * 7)  # 7 pages: reclaims the oldest cached
    assert eng.free_pages == 1  # the one surviving cached page
    # reclaim happened oldest-first: chunk 0 of the released prompt is gone,
    # so re-prefilling it cannot hit; it reclaims the last cached page
    c = eng.prefill([1, 2, 3, 4])
    assert c.reused_chunks == 0
    assert eng.free_pages == 0
    eng.release(b)
    eng.release(c)


def test_apc_never_writes_shared_pages():
    """Decode/verify append must land in private pages: grow two sharers
    past several page boundaries and check both still match the dense
    reference (a write into a shared page would corrupt the sibling)."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    a = eng.prefill(PROMPT)
    b = eng.prefill(PROMPT)
    out_a = eng.decode(a, 10)
    out_b = eng.decode(b, 10)
    want = dense_greedy(PROMPT, 10)
    assert out_a == want and out_b == want


def test_apc_pressure_error_unpins_local_hits():
    """A MemoryError mid-prefill must not leak refs on matched pages."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    _hoard = eng.pages.acquire(64 - 4)  # 4 usable; standard cache shape
    a = eng.prefill([1, 2, 3, 4, 5, 6, 7, 8])  # 2 pages
    with pytest.raises(MemoryError):
        eng.prefill([1, 2, 3, 4, 5, 6, 7, 8] + list(range(100, 112)))  # needs 5
    # the failed prefill pinned pages 0-1; ensure refs were returned:
    eng.release(a)
    assert eng.free_pages == 4  # everything reclaimable again


# ---- streaming and cancellation ----

def test_scheduler_streaming_matches_final():
    """Chunk-boundary streaming must deliver exactly the final output, in
    order, and exactly one terminal ([], True) signal."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    sched = Scheduler(eng, max_batch=2)
    got: dict = {}

    def cb_for(rid):
        got[rid] = {"toks": [], "done": 0}

        def cb(toks, done):
            if done:
                got[rid]["done"] += 1
            else:
                assert toks, "empty non-terminal stream delivery"
                got[rid]["toks"].extend(toks)
        return cb

    r1 = sched.submit(PROMPT, 9)
    sched.pending[-1].on_token = cb_for(r1)
    r2 = sched.submit(PROMPT[:5], 6)
    sched.pending[-1].on_token = cb_for(r2)
    res = sched.run()
    assert got[r1]["toks"] == res[r1] == dense_greedy(PROMPT, 9)
    assert got[r2]["toks"] == res[r2] == dense_greedy(PROMPT[:5], 6)
    assert got[r1]["done"] == got[r2]["done"] == 1


def test_scheduler_streaming_stops_at_eos():
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    full = dense_greedy(PROMPT, 12)
    eos = full[2]  # force an early eos
    sched = Scheduler(eng, max_batch=1)
    seen: list = []
    rid = sched.submit(PROMPT, 12, eos_id=eos)
    sched.pending[-1].on_token = lambda t, d: seen.extend(t)
    res = sched.run()
    assert res[rid] == full[: full.index(eos) + 1]
    assert seen == res[rid]  # nothing streamed past eos


def test_scheduler_cancel_pending_and_active():
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 2
    sched = Scheduler(eng, max_batch=1)  # b waits in pending while a runs
    a = sched.submit(PROMPT, 8)
    b = sched.submit(PROMPT[:5], 8)
    assert sched.cancel(b) is True  # pending: removed outright
    assert sched.cancel(999) is False

    # run a for one chunk, then cancel it mid-flight
    done = sched.step()
    assert not done and len(sched.active) == 1
    assert sched.cancel(a) is True
    done = sched.step()
    assert [r.req_id for r in done] == [a]
    assert done[0].output == dense_greedy(PROMPT, 2)  # partial kept
    assert not sched.has_work
    assert eng.free_pages == eng.pc.n_blocks  # everything released


def test_scheduler_cancel_leaves_batchmates_correct():
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 2
    sched = Scheduler(eng, max_batch=2)
    a = sched.submit(PROMPT, 8)
    b = sched.submit(PROMPT[:5], 8)
    sched.step()
    sched.cancel(a)
    res = {}
    while sched.has_work:
        for r in sched.step():
            res[r.req_id] = r.output
    assert res[b] == dense_greedy(PROMPT[:5], 8)  # unaffected by the cancel
    assert len(res[a]) == 2


def test_apc_batched_admission_dedups():
    """prefill_batch must reuse resident pages (per-sequence path) instead
    of recomputing in the grouped forward — including identical prompts
    inside one admission wave."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    warm = eng.prefill(PROMPT)  # registers PROMPT's 2 complete chunks
    free0 = eng.free_pages
    states = eng.prefill_batch([PROMPT, list(PROMPT)])  # same-wave duplicates
    for st in states:
        assert st.reused_chunks == len(PROMPT) // T
        assert st.block_ids[:2] == warm.block_ids[:2]
    assert free0 - eng.free_pages == 2  # one private tail page each
    got = [eng.decode(st, 5) for st in states]
    assert got == [dense_greedy(PROMPT, 5)] * 2

    # cold same-wave duplicates (nothing resident beforehand): the first
    # computes+registers via the deferral rule, the second hits it
    eng2 = InferenceEngine(PARAMS, CFG, make_pc())
    p = [5, 6, 7, 8, 9, 10, 11, 12, 13]
    sts = eng2.prefill_batch([p, list(p)])
    assert sts[1].block_ids[:2] == sts[0].block_ids[:2]
    assert [eng2.decode(s, 4) for s in sts] == [dense_greedy(p, 4)] * 2


def test_scheduler_survives_raising_callback():
    """A user on_token callback that raises must not leak pages or corrupt
    the batch — streaming is disarmed, the request still completes."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    sched = Scheduler(eng, max_batch=2)
    a = sched.submit(PROMPT, 8)

    def bomb(toks, done):
        raise RuntimeError("client went away")

    sched.pending[-1].on_token = bomb
    b = sched.submit(PROMPT[:5], 8)
    res = sched.run()
    assert res[a] == dense_greedy(PROMPT, 8)
    assert res[b] == dense_greedy(PROMPT[:5], 8)
    assert eng.free_pages == eng.pc.n_blocks


def _family_engine_roundtrip(cfg, n_steps=6, prompt=(3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5)):
    """Full serving loop (chunked prefill + paged decode) for a family
    variant must match its own dense-forward greedy reference."""
    params = init_params(cfg, jax.random.PRNGKey(11))
    dense = make_dense_greedy(params, cfg)
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=64, block_tokens=T, dtype=cfg.dtype,
    )
    eng = InferenceEngine(params, cfg, pc, prefill_chunk=2 * T)
    eng.decode_chunk = 4
    assert eng.generate(list(prompt), n_steps) == dense(prompt, n_steps)


def test_engine_serves_qwen2_style_bias_model():
    _family_engine_roundtrip(scaled(TINY, dtype=jnp.float32, attn_bias=True))


def test_engine_serves_qwen3_style_qk_norm_model():
    _family_engine_roundtrip(
        scaled(TINY, dtype=jnp.float32, qk_norm=True, head_dim_override=16)
    )


def test_engine_serves_windowed_mistral_style_model():
    # window < prompt length: chunked prefill's prefix-buffer mask and the
    # paged decode mask both genuinely drop early keys
    _family_engine_roundtrip(scaled(TINY, dtype=jnp.float32, sliding_window=8))


def test_engine_serves_gemma2_style_model():
    """Gemma-2 knobs through the full serving path: GeGLU, attention +
    final logit softcaps, sandwich (post) norms with the (1+w) RMSNorm
    convention, sqrt(dim) embed scaling, query_pre_attn_scalar, and
    alternating local/global attention — paged decode must match dense."""
    _family_engine_roundtrip(
        scaled(
            TINY, dtype=jnp.float32, act="gelu_tanh", attn_softcap=30.0,
            final_softcap=15.0, norm_offset=True, post_norms=True,
            embed_scale=True, query_pre_attn_scalar=24.0,
            sliding_window=6, window_pattern=2,
        )
    )


def test_top_p_nucleus_sampling():
    """top_p: a tiny nucleus (p→0) collapses to greedy even at temperature
    1; p=1.0 is a no-op vs plain categorical under the same key; sampled
    tokens must come from the nucleus (checked via the last-step logits)."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    st = eng.prefill(PROMPT)
    tiny = eng.decode(st, 6, sample="categorical", temperature=1.0,
                      top_p=1e-9, rng=jax.random.PRNGKey(5))
    assert tiny == dense_greedy(PROMPT, 6)

    eng_a = InferenceEngine(PARAMS, CFG, make_pc())
    a = eng_a.decode(eng_a.prefill(PROMPT), 6, sample="categorical",
                     temperature=0.9, top_p=1.0, rng=jax.random.PRNGKey(9))
    eng_b = InferenceEngine(PARAMS, CFG, make_pc())
    b = eng_b.decode(eng_b.prefill(PROMPT), 6, sample="categorical",
                     temperature=0.9, rng=jax.random.PRNGKey(9))
    assert a == b  # p=1.0 must not perturb the draw stream

    # p=0.5 nucleus membership: every sampled token's probability rank is
    # inside the smallest mass-0.5 prefix of its step distribution
    eng_c = InferenceEngine(PARAMS, CFG, make_pc())
    st_c = eng_c.prefill(PROMPT)
    toks = eng_c.decode(st_c, 8, sample="categorical", temperature=1.0,
                        top_p=0.5, rng=jax.random.PRNGKey(4))
    # replay the trajectory densely and check each sampled token is in the
    # nucleus of the distribution that produced it.  ONE padded bucket for
    # every replay length (causal masking makes the pad inert): the old
    # per-length forwards compiled 8 distinct programs and dominated the
    # test's wall time
    ctx = list(PROMPT)
    BUCKET = 32
    replay = jax.jit(lambda toks: prefill_forward(PARAMS, CFG, toks)[0])
    for t in toks:
        padded = ctx + [0] * (BUCKET - len(ctx))
        logits = replay(jnp.asarray(padded, dtype=jnp.int32)[None])
        p = np.asarray(
            jax.nn.softmax(logits[0, len(ctx) - 1].astype(jnp.float32))
        )
        order = np.argsort(-p)
        cum = np.cumsum(p[order])
        nucleus = set(order[: int(np.searchsorted(cum, 0.5)) + 1].tolist())
        assert t in nucleus, (t, sorted(nucleus))
        ctx.append(t)


def test_scheduler_batches_distinct_top_p():
    """Distinct top_p values are per-row vector entries, not batch splitters:
    both requests admit into one batch and both finish."""
    from infinistore_tpu.engine import Scheduler

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    sched = Scheduler(eng, max_batch=4)
    a = sched.submit(PROMPT, 4, sample="categorical", top_p=0.9)
    b = sched.submit(PROMPT[:5], 4, sample="categorical", top_p=0.5)
    sched._admit()
    assert {r.req_id for r in sched.active} == {a, b}
    res = sched.run()
    assert set(res) == {a, b}
    assert all(len(v) == 4 for v in res.values())


# ---- round 11: batch-dim bucketed decode programs ----


def test_decode_batch_pad_rows_are_inert():
    """A non-pow2 batch rides a padded program whose pad rows must not
    corrupt ANY real sequence: greedy decode_batch at B=3 (padded to 4)
    must equal each row's solo decode — in particular, the sequence
    owning block 0, which a zero-filled pad table row would silently
    scribble on (the pad sentinel is out-of-bounds instead: scatter
    drops, gather clamps)."""
    prompts = [
        [11, 42, 7, 99, 5, 3, 17],
        [2, 4, 6, 8, 10, 12, 14, 16, 18],
        [9, 1, 9, 2, 9, 3],
    ]
    wants = []
    for p in prompts:
        solo = InferenceEngine(PARAMS, CFG, make_pc())
        wants.append(solo.generate(p, 12))

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    states = [eng.prefill(p) for p in prompts]
    outs = eng.decode_batch(states, 12)
    assert outs == wants


def test_decode_batch_bucketed_batch_dim_never_retraces():
    """The steady-state retrace guard: batch compositions inside one
    power-of-two bucket (B=3 and B=4 both ride the Bp=4 program) must
    reuse the SAME compiled decode scan — zero new decode_many traces
    after the bucket is warm.  This is what keeps
    ``retraces_per_100_steps`` flat when continuous batching churns the
    active set."""
    from infinistore_tpu.engine import stepprof as _sp

    eng = InferenceEngine(PARAMS, CFG, make_pc())
    prompts = [
        [11, 42, 7, 99, 5, 3, 17],
        [2, 4, 6, 8, 10, 12, 14, 16],
        [9, 1, 9, 2, 9, 3],
        [5, 6, 7, 8, 9, 10, 11],
    ]
    states = [eng.prefill(p) for p in prompts]
    # warm the Bp=4 bucket (and its block-table width) at full width
    eng.decode_batch(states, 8)
    t0 = _sp.trace_counts().get("decode_many", 0)
    # composition churn INSIDE the bucket: 3 rows, then 4 again —
    # same padded program, no new traces
    eng.decode_batch(states[:3], 8)
    eng.decode_batch(states, 8)
    assert _sp.trace_counts().get("decode_many", 0) == t0, (
        "decode scan retraced inside a warm batch bucket"
    )


def test_decode_batch_seeded_rows_reproduce_across_compositions():
    """A seeded row's stream is pinned by PRNGKey(seed) + absolute
    position, so its tokens must be identical whether it decodes among
    2 batchmates or 3 (different pad widths included)."""
    seeded_prompt = [3, 1, 4, 1, 5, 9, 2, 6]

    def run(n_mates):
        eng = InferenceEngine(PARAMS, CFG, make_pc())
        sts = [eng.prefill(seeded_prompt)]
        for i in range(n_mates):
            sts.append(eng.prefill([7 + i, 8, 9, 10, 11, 12]))
        outs = eng.decode_batch(
            sts, 10, sample="categorical", temperature=1.1,
            seed=[123] + [None] * n_mates,
        )
        return outs[0]

    assert run(1) == run(2) == run(3)


def test_scheduler_zero_retraces_after_warmup_under_churn():
    """The /debug/engine acceptance criterion: with batch-dim, chunk,
    and table-width bucketing in place, a batch-composition-varying
    serving phase must run at retraces_per_100_steps == 0 once the
    bucket universe is warm — every admission/retirement recomposition
    reuses a compiled program."""
    from infinistore_tpu.engine import Scheduler
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.utils.metrics import MetricsRegistry

    eng = InferenceEngine(PARAMS, CFG, make_pc(n_blocks=256))
    sched = Scheduler(eng, max_batch=4)
    rng = np.random.RandomState(0)

    def prompt():
        return [int(x) for x in rng.randint(1, CFG.vocab_size, size=9)]

    def drive():
        # 3-wide wave + a mid-flight admission (chunked prefill), with
        # retirements staggering the batch through compositions 1..4
        for _ in range(3):
            sched.submit(prompt(), max_new_tokens=64)
        steps = 0
        while sched.has_work:
            sched.step()
            steps += 1
            if steps == 1:
                sched.submit(prompt(), max_new_tokens=64)

    drive()  # warmup: compiles every bucket the pattern touches
    prof = StepProfiler(metrics=MetricsRegistry(), sample=1000)
    sched.stepprof = prof
    drive()  # steady state: same dynamics, zero new programs
    summ = prof.snapshot(limit=0)["summary"]  # the /debug/engine payload
    assert summ["steps"] > 0
    assert summ["retraces_per_100_steps"] == 0.0, summ["retraces"]


# -- strict durability: the acknowledgement is awaited once a step, per request --

import strict_settle  # noqa: E402


@pytest.fixture
def settle_kit(server):
    """``strict_settle``'s kit over pages: prompts of 6 tokens at pages of 4
    push one page (the complete chunk) and need two chunk forwards."""
    import itertools
    import types

    from infinistore_tpu.kv.hashing import chunk_keys

    conns, rng = [], np.random.RandomState(38)
    ids = itertools.count()

    def engine(durability="strict", store=True):
        if store:
            conns.append(_conn(server))
        eng = InferenceEngine(
            PARAMS, CFG, make_pc(256), conn=conns[-1] if store else None,
            model_id=f"settle-{os.getpid()}-{time.time_ns()}-{next(ids)}",
            prefill_chunk=T, store_durability=durability)
        eng.decode_chunk = 4
        return eng

    def unnamed(eng, prompt):
        keys = chunk_keys(prompt, eng.model_id, chunk_tokens=T)
        return eng.pages.peek_prefix(keys[:1]) == 0

    yield types.SimpleNamespace(
        engine=engine, max_batch=8, first=[200, 201, 202, 203, 204],
        prompts=lambda n: [[int(x) for x in rng.randint(1, 190, size=6)]
                           for _ in range(n)],
        solo=dense_greedy, unnamed=unnamed, names_pages=True)
    for c in conns:
        c.close()


@pytest.mark.parametrize("case", strict_settle.CASES,
                         ids=lambda c: c.__name__[5:])
def test_strict_settle_over_pages(settle_kit, case):
    case(settle_kit)


@pytest.mark.parametrize("form", strict_settle.FORMS)
def test_strict_blocking_prefill_returns_after_the_acknowledgement(
        settle_kit, form):
    strict_settle.case_blocking_forms_return_after_the_acknowledgement(
        settle_kit, form)


@pytest.mark.parametrize("mode", strict_settle.MODES)
def test_strict_burst_outputs_equal_solo_runs_and_only_strict_parks(
        settle_kit, mode):
    strict_settle.case_burst_outputs_equal_solo_runs(settle_kit, mode)
