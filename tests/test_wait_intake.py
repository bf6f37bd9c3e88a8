"""The engine thread's two waits inside a step take in what is staged
(``Scheduler._under_dispatch`` and ``_settle_parked`` with an ``Intake``).

The dispatch is held by ``GatedEngine``: a real engine whose
``DecodeFlight`` reads ready only once the test has opened its gate (the
real scan has long ended on the CPU; ``ready`` and ``block`` are what the
scheduler looks at).  Arrivals are a SCRIPT: the intake's ``take_in`` is
called by the scheduler between two pieces of work, and each call runs the
script's next action (submit a request, cancel one, open the gate) on the
engine thread itself, so the order of events is the script's and no sleep
races anything.  Where the thread must really sleep (nothing staged, nothing
pending) the step runs on a thread of its own and the test waits for the
condition it is after."""

import os
import threading
import time

import numpy as np
import pytest

import strict_settle
from strict_settle import HeldCommits, in_thread, until
from test_engine import CFG, PARAMS, T, _conn, dense_greedy, make_pc
from test_engine import server  # noqa: F401 — the module's live store

from infinistore_tpu.engine import InferenceEngine, Scheduler
from infinistore_tpu.engine.scheduler import Intake
from infinistore_tpu.engine.stepprof import StepProfiler
from infinistore_tpu.utils.metrics import MetricsRegistry

WAIT_S = strict_settle.WAIT_S
FIRST = [200, 201, 202, 203, 204]


class GatedEngine(InferenceEngine):
    """A dispatch ends when the test says so: ``flights[-1].gate``."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.flights, self.log = [], []
        self.gated = True
        self.early = 0

    def decode_launch(self, states, *a, **kw):
        fl = super().decode_launch(states, *a, **kw)
        fl.gate = threading.Event()
        if not self.gated:
            fl.gate.set()
        fl.ready = fl.gate.is_set
        fl.block = lambda: fl.gate.wait(WAIT_S)
        self.flights.append(fl)
        self.log.append(("launch", len(states)))
        return fl

    def decode_collect(self, fl):
        # the real read-back stands until the dispatch has ended; ``early``
        # counts the collects that had to (the scheduler's come after
        # ``ready()``, but for a server that is stopping)
        self.early += not fl.gate.is_set()
        assert fl.gate.wait(WAIT_S), "the dispatch never ended"
        self.log.append(("collect", len(fl.states)))
        return super().decode_collect(fl)

    def prefill_start(self, tokens, **kw):
        self.log.append(("start", tuple(tokens[:3])))
        return super().prefill_start(tokens, **kw)

    def prefill_step(self, pp):
        self.log.append(("chunk", tuple(pp.tokens[:3])))
        return super().prefill_step(pp)


class Script:
    """An ``Intake`` whose arrivals are a list of actions, one a ``take_in``
    call: a callable is run (and what it returns, a count of requests, is
    what was taken in); ``"open"`` opens the newest dispatch's gate;
    ``None`` lets the scheduler do one piece.  While actions are left
    ``staged()`` holds, so the thread never sleeps on an unfinished script;
    an exhausted script opens the gate (a test that wants the thread asleep
    sets ``open_at_end = False`` and opens it from outside)."""

    def __init__(self, sched, eng, actions=()):
        self.sched, self.eng = sched, eng
        self.actions = list(actions)
        self.cv = threading.Condition()
        self.calls = 0
        self.open_at_end = True
        sched.attach_intake(Intake(self.cv, self.staged, self.take_in))

    def staged(self):
        # an exhausted script still has its gate to open, at the next call
        return bool(self.actions) or bool(
            self.open_at_end and self.eng.flights
            and not self.eng.flights[-1].gate.is_set())

    def open(self):
        self.eng.flights[-1].gate.set()

    def take_in(self):
        self.calls += 1
        if not self.actions:
            if self.open_at_end and self.eng.flights:
                self.open()
            return 0
        act = self.actions.pop(0)
        if act == "open":
            self.open()
            return 0
        if act == "stop":
            return None
        return (act() or 0) if act is not None else 0


def engine(n_blocks=256, decode_chunk=4, **kw):
    eng = GatedEngine(PARAMS, CFG, make_pc(n_blocks), prefill_chunk=T, **kw)
    eng.decode_chunk = decode_chunk
    return eng


def scheduler(eng, max_batch=8):
    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    return Scheduler(eng, max_batch=max_batch, stepprof=prof)


def decoding(eng, sched, max_new=12):
    """``FIRST`` admitted as a wave (no dispatch is gated meanwhile) and
    decoding: the next ``step()`` launches a gated dispatch."""
    eng.gated = False
    rid = sched.submit(FIRST, max_new)
    sched.step()
    eng.gated = True
    del eng.log[:]
    return rid


def submit(sched, prompt, n=6, **kw):
    def act():
        sched.submit(prompt, n, **kw)
        return 1
    return act


def run_out(sched):
    out = {}
    for _ in range(200):
        if not sched.has_work:
            return out
        for r in sched.step():
            out[r.req_id] = r.output
    raise AssertionError("the scheduler never drained")


def drained(eng, sched, n_blocks=256):
    """Every dispatch was collected after its end, nothing is held."""
    assert eng.early == 0 and eng._flight is None and sched._flight is None
    assert not sched.has_work and eng.free_pages == n_blocks


PROMPTS = [[int(x) for x in np.random.RandomState(47 + i).randint(1, 190, size=9)]
           for i in range(6)]


def test_staged_under_a_dispatch_is_admitted_and_chunked_before_the_collect():
    """A request that arrives while the dispatch is in flight is taken in,
    started and has its chunks launched BEFORE the collect; it joins the
    batch behind the rows in flight, which get the dispatch's tokens."""
    eng = engine()
    sched = scheduler(eng)
    first = decoding(eng, sched)
    p = PROMPTS[0]                     # 9 tokens: 3 chunks at T=4
    hook = Script(sched, eng, [submit(sched, p), None, None, None])
    before = sched.stepprof.summary()["prefill"]["chunks"]
    done = sched.step()
    assert not done
    kinds = [k for k, _ in eng.log]
    assert kinds == ["launch", "start", "chunk", "chunk", "chunk", "collect"]
    assert [what for k, what in eng.log if k == "chunk"] == [tuple(p[:3])] * 3
    tot = sched.stepprof.summary()["prefill"]
    assert (tot["taken_in_dispatch"], tot["started_dispatch"],
            tot["chunks_dispatch"]) == (1, 1, 3)
    assert tot["chunks"] - before == 3 and tot["chunks_settle"] == 0
    assert tot["collect_lag_s"] >= 0
    # it joined behind the row in flight and has no token yet
    assert [len(r.output) for r in sched.active] == [8, 0]
    out = run_out(sched)
    assert out[first] == dense_greedy(FIRST, 12)
    assert out[first + 1] == dense_greedy(p, 6)
    assert hook.calls >= 4
    drained(eng, sched)


@pytest.mark.parametrize("mode", ["greedy", "seeded", "mixed"])
def test_outputs_equal_the_serial_orders(mode):
    """Token for token: requests staged under dispatches produce what the
    same requests produce submitted up front to a scheduler with no intake
    (and, greedy, what each produces alone)."""
    def params(i):
        if mode == "greedy" or (mode == "mixed" and i % 2 == 0):
            return {}
        return dict(sample="categorical", temperature=0.9, top_k=20,
                    seed=1000 + i)

    # the serial order: everything queued before the first step, no intake
    eng0 = engine()
    eng0.gated = False
    ref = scheduler(eng0)
    ref.submit(FIRST, 12, **params(9))
    for i, p in enumerate(PROMPTS[:4]):
        ref.submit(p, 6, **params(i))
    want = ref.run()

    eng = engine()
    sched = scheduler(eng)
    eng.gated = False
    sched.submit(FIRST, 12, **params(9))
    sched.step()
    eng.gated = True
    Script(sched, eng, [
        submit(sched, PROMPTS[0], **params(0)), None,
        submit(sched, PROMPTS[1], **params(1)), None, None, None, "open",
        # the next dispatch: two more arrive under it
        submit(sched, PROMPTS[2], **params(2)),
        submit(sched, PROMPTS[3], **params(3)), None, None,
    ])
    got = run_out(sched)
    assert got == want
    if mode == "greedy":
        for i, p in enumerate(PROMPTS[:4]):
            assert got[i + 1] == dense_greedy(p, 6)
    assert sched.stepprof.summary()["prefill"]["chunks_dispatch"] > 0
    drained(eng, sched)


def test_at_most_max_batch_chunks_between_two_dispatches():
    """What is launched under a dispatch is spent from the NEXT step's
    budget: between two launches there are never more than ``max_batch``
    chunks, however many prompts wait."""
    eng = engine(n_blocks=512)
    sched = scheduler(eng, max_batch=4)
    decoding(eng, sched, max_new=40)
    long = [[int(x) for x in np.random.RandomState(7 + i).randint(1, 190, size=33)]
            for i in range(3)]             # 9 chunks each
    Script(sched, eng, [submit(sched, p, 4) for p in long] + [None] * 3)
    run_out(sched)
    between, worst = 0, 0
    for kind, _ in eng.log:
        if kind == "launch":
            worst, between = max(worst, between), 0
        elif kind == "chunk":
            between += 1
    assert worst == 4 == sched.max_batch
    tot = sched.stepprof.summary()["prefill"]
    assert tot["chunks_dispatch"] >= 3
    assert tot["spent_tokens"] <= tot["granted_tokens"]
    drained(eng, sched, 512)


def test_cancel_with_a_dispatch_in_flight_and_a_prefill_begun_under_it():
    """A cancel that arrives under the dispatch flags the row in flight
    (retired after the collect, with its tokens) and drops the prefill that
    was begun under the same dispatch; both leave through ``step()``."""
    eng = engine()
    sched = scheduler(eng)
    first = decoding(eng, sched)

    def cancel_both():
        assert sched.cancel(first) and sched.cancel(first + 1)

    Script(sched, eng, [submit(sched, PROMPTS[0]), None, None, cancel_both,
                        None])
    done = sched.step()
    assert sorted(r.req_id for r in done) == [first, first + 1]
    assert all(r.cancelled and r.done for r in done)
    by_id = {r.req_id: r for r in done}
    # the row in flight was read before it was released: 4 + 4 tokens
    assert by_id[first].output == dense_greedy(FIRST, 12)[:8]
    assert by_id[first + 1].output == []
    assert ("collect", 1) in eng.log
    drained(eng, sched)


def test_memory_error_under_a_dispatch_holds_admission_and_sheds_later():
    """``prefill_start`` running dry under a dispatch re-queues the newcomer
    ahead and holds admission (the dispatch is collected as if nothing had
    happened); a decode that runs dry at the NEXT launch sheds the newest
    row while the prefill begun under the dispatch goes on."""
    eng = engine()
    sched = scheduler(eng)
    first = decoding(eng, sched)
    inner = eng.prefill_start
    dry = [True]

    def prefill_start(tokens, **kw):
        if dry[0] and tokens[:3] == PROMPTS[1][:3]:
            raise MemoryError("no pages")
        return inner(tokens, **kw)

    eng.prefill_start = prefill_start
    Script(sched, eng, [submit(sched, PROMPTS[0]), None,
                        submit(sched, PROMPTS[1]), None, None, None])
    assert not sched.step()
    assert sched._admission_hold
    assert [r.req_id for r in sched.pending] == [first + 2]
    assert len(sched.active) + len(sched._prefilling) == 2
    assert [len(r.output) for r in sched.active][0] == 8 or \
        len(sched.active[0].output) == 4
    # the next launch runs dry once: the newest ROW is shed, whatever was
    # begun under the last dispatch keeps its pages and its place
    launch, fail = eng.decode_launch, [True]

    def decode_launch(states, *a, **kw):
        if fail[0] and len(states) > 1:
            fail[0] = False
            raise MemoryError("no pages to grow")
        return launch(states, *a, **kw)

    eng.decode_launch = decode_launch
    dry[0] = False
    out = run_out(sched)
    assert out[first] == dense_greedy(FIRST, 12)
    assert out[first + 1] == dense_greedy(PROMPTS[0], 6)
    assert out[first + 2] == dense_greedy(PROMPTS[1], 6)
    drained(eng, sched)


def test_fault_reset_with_a_dispatch_in_flight_drops_the_handle():
    """A fault under a dispatch (a chunk's launch raises) leaves ``step()``
    with the handle held; ``fault_reset`` drops it unread, releases the rows
    in flight and the prefill begun under it, and the engine decodes again."""
    eng = engine()
    sched = scheduler(eng)
    decoding(eng, sched)
    inner = eng.prefill_step

    def prefill_step(pp):
        if pp.chunks == 1:
            raise RuntimeError("device fault")
        return inner(pp)

    eng.prefill_step = prefill_step
    Script(sched, eng, [submit(sched, PROMPTS[0]), None, None, None])
    with pytest.raises(RuntimeError, match="device fault"):
        sched.step()
    assert sched._flight is not None and eng._flight is sched._flight
    dropped = sched.fault_reset()
    assert len(dropped) == 2 and all(r.done for r in dropped)
    assert sched._flight is None and eng._flight is None
    assert not sched.has_work and eng.free_pages == 256
    assert ("collect", 1) not in eng.log
    # nothing of the dropped dispatch is left in the way of the next one
    eng.prefill_step = inner
    rid = sched.submit(PROMPTS[2], 6)
    assert run_out(sched)[rid] == dense_greedy(PROMPTS[2], 6)


def test_stopping_under_a_dispatch_collects_and_leaves():
    """``take_in`` answering None (the serving layer is stopping): no more
    work is begun under the dispatch; it is collected and the step ends."""
    eng = engine()
    sched = scheduler(eng)
    first = decoding(eng, sched)
    hook = Script(sched, eng, [submit(sched, PROMPTS[0]), None, "stop"])
    eng.flights.clear()
    t = in_thread(sched.step)
    until(lambda: not hook.actions and eng.flights, "the script ran out")
    eng.flights[-1].gate.set()
    sched._wake()
    assert not t.result()
    kinds = [k for k, _ in eng.log]
    assert kinds == ["launch", "start", "chunk", "collect"]
    assert eng.early == 1     # it stood in the read-back, as without intake
    assert sched.active[0].req_id == first and len(sched._prefilling) == 1


def test_sleeps_with_nothing_to_do_and_wakes_at_the_dispatchs_end():
    """Nothing staged, nothing pending: the thread sleeps on the intake's
    condition (no spinning: ``take_in`` is not called again) and the
    dispatch's watcher wakes it."""
    eng = engine()
    sched = scheduler(eng)
    decoding(eng, sched)
    hook = Script(sched, eng)
    hook.open_at_end = False
    t = in_thread(sched.step)
    until(lambda: eng.flights and hook.cv._waiters, "the thread sleeps")
    calls = hook.calls
    assert t.is_alive() and ("collect", 1) not in eng.log
    hook.open()                       # the watcher's block() returns
    t.result()
    assert hook.calls == calls        # woken by the end, not by polling
    assert eng.log[-1] == ("collect", 1)
    assert eng.flights[-1].t_ready is not None
    # an arrival wakes it too
    hook.actions.append(submit(sched, PROMPTS[0]))
    t = in_thread(sched.step)
    until(lambda: ("start", tuple(PROMPTS[0][:3])) in eng.log,
          "the arrival is taken in")
    until(lambda: not hook.actions, "script done")
    hook.open()
    sched._wake()
    t.result()


def test_without_an_intake_a_step_is_the_blocking_call():
    """``Scheduler.run()`` and every caller that attaches nothing: the step
    stands in ``decode_batch``, nothing is launched or watched apart."""
    eng = engine()
    eng.gated = False
    sched = scheduler(eng)
    calls = []
    inner = eng.decode_batch

    def decode_batch(*a, **kw):
        calls.append(len(eng.flights))
        return inner(*a, **kw)

    eng.decode_batch = decode_batch
    rids = [sched.submit(p, 6) for p in PROMPTS[:3]]
    threads = set(threading.enumerate())
    out = sched.run()
    assert calls and len(eng.flights) == len(calls)
    # no thread that was not there before (one that an earlier test of this
    # process left behind may end meanwhile: the count alone flaked on that)
    assert set(threading.enumerate()) <= threads
    for rid, p in zip(rids, PROMPTS):
        assert out[rid] == dense_greedy(p, 6)
    tot = sched.stepprof.summary()["prefill"]
    assert tot["chunks_dispatch"] == tot["taken_in_dispatch"] == 0
    assert tot["collect_lag_s"] == 0


# -- strict durability: the settle wait takes in, and nobody joins early --

@pytest.fixture
def store_engine(server):  # noqa: F811
    conns = []

    def make(durability="strict"):
        conns.append(_conn(server))
        eng = GatedEngine(
            PARAMS, CFG, make_pc(256), conn=conns[-1],
            model_id=f"wait-{os.getpid()}-{time.time_ns()}",
            prefill_chunk=T, store_durability=durability)
        eng.decode_chunk = 4
        return eng

    yield make
    for c in conns:
        c.close()


def test_settle_wait_takes_in_and_nobody_joins_before_its_own_ack(store_engine):
    """Strict durability.  A's acknowledgement is held: while the thread
    waits for it, B is taken in, prefilled under the step's own budget and
    parked behind A.  A joins when A's own acknowledgement arrives, B only
    at B's; the step's ONE dispatch starts after both and holds both:
    neither was in a dispatch before its acknowledgement."""
    eng = store_engine()
    sched = scheduler(eng)
    first = decoding(eng, sched)
    eng.gated = False
    held = HeldCommits(eng)
    a, b = PROMPTS[0][:6], PROMPTS[1][:6]     # one complete page each
    held.hold(a)
    held.hold(b)
    sched.submit(a, 6)
    hook = Script(sched, eng, [None, submit(sched, b), None, None, None])
    hook.open_at_end = False
    t = in_thread(sched.step)
    until(lambda: len(sched._parked) == 2 and not hook.actions
          and hook.cv._waiters, "both parked, the thread asleep")
    assert [r.req_id for r, _ in sched._parked] == [first + 1, first + 2]
    assert not any(k == "launch" for k, _ in eng.log)
    # A's acknowledgement: A joins, B's is outstanding still, and the step
    # launches nothing before it (one worker pushes in the order submitted)
    held.release(a)
    until(lambda: len(sched.active) == 2 and hook.cv._waiters,
          "a joined, the thread asleep again")
    assert [r.req_id for r, _ in sched._parked] == [first + 2]
    assert not eng.prefill_settled(sched._parked[0][1])
    assert not any(k == "launch" for k, _ in eng.log)
    held.release(b)
    t.result()
    launches = [n for k, n in eng.log if k == "launch"]
    assert launches == [3]
    assert [k for k, _ in eng.log].index("launch") > max(
        i for i, (k, _) in enumerate(eng.log) if k == "chunk")
    tot = sched.stepprof.summary()["prefill"]
    assert (tot["taken_in_settle"], tot["started_settle"]) == (1, 1)
    assert tot["chunks_settle"] == 2
    assert tot["settle_waits"] == 2 and tot["settled_prompts"] == 3
    out = run_out(sched)
    assert out[first + 1] == dense_greedy(a, 6)
    assert out[first + 2] == dense_greedy(b, 6)
    eng.store_flush()
    assert eng.free_pages == 256


def test_strict_prompt_finished_under_a_dispatch_waits_for_its_ack(store_engine):
    """Strict durability, under a DISPATCH: a prompt whose last chunk is
    launched under it is parked, is in no dispatch while its push is
    outstanding, and joins the first dispatch after its acknowledgement."""
    eng = store_engine()
    sched = scheduler(eng)
    first = decoding(eng, sched, max_new=24)
    held = HeldCommits(eng)
    a = PROMPTS[2][:6]
    held.hold(a)
    Script(sched, eng, [submit(sched, a), None, None, None])
    assert not sched.step()
    assert [r.req_id for r, _ in sched._parked] == [first + 1]
    assert [n for k, n in eng.log if k == "launch"] == [1]
    # the next step stands in the settle wait until the push is released
    hook = sched.intake
    t = in_thread(sched.step)
    until(lambda: hook.cv._waiters, "asleep in the settle wait")
    assert [n for k, n in eng.log if k == "launch"] == [1]
    held.release(a)
    t.result()
    assert [n for k, n in eng.log if k == "launch"] == [1, 2]
    out = run_out(sched)
    assert out[first + 1] == dense_greedy(a, 6)
    eng.store_flush()


def test_a_load_begun_under_a_dispatch_is_not_charged_its_remainder(store_engine):
    """A store load's landing under a dispatch stands through the dispatch
    first (``transfer.before_sync``): the seconds stood there are kept apart
    (``held_s``) and taken out of the load's ``scatter_s`` and of the
    request's ``store_load_s``."""
    eng = store_engine("relaxed")
    eng.gated = False
    p = PROMPTS[3] + PROMPTS[4]                # 18 tokens: 4 complete pages
    eng.release(eng.prefill(p))
    eng.store_flush()
    eng2 = store_engine("relaxed")
    eng2.model_id = eng.model_id
    sched = scheduler(eng2)
    decoding(eng2, sched)
    stood = []
    inner = eng2._await_flight

    def await_flight():
        # the dispatch ends 0.3 s after the landing began to wait for it
        threading.Timer(0.3, eng2.flights[-1].gate.set).start()
        stood.append(inner())
        return stood[-1]

    eng2.transfer.before_sync = await_flight
    Script(sched, eng2, [submit(sched, p), None])
    sched.step()
    assert stood and stood[0] >= 0.25
    assert eng2.transfer.held_s == pytest.approx(stood[0])
    pp = sched._prefilling[0][1]
    assert pp.store_chunks == 4
    # the flat phases keep the two apart, and the request's seconds are the
    # load's phases, to the switches' own cost
    ph = sched.stepprof.summary()["phase_s"]
    assert ph["decode.wait"] >= stood[0] - 0.01
    assert pp.store_load_s == pytest.approx(
        ph["kv.load"] + ph["kv.lookup"], abs=0.02)
    tot = eng2.transfer.load_totals
    assert tot["scatter_s"] + tot["fetch_s"] == pytest.approx(
        ph["kv.load"], abs=0.05)


def test_serve_close_with_a_dispatch_in_flight_and_a_prefill_begun_under_it():
    """``ServingServer.close()`` while the engine thread is under a
    dispatch: the thread collects, leaves, and the waiting clients are told
    (abort), with a request's prefill begun under that dispatch."""
    from infinistore_tpu.serve import ServingServer

    old = os.environ.get("ISTPU_ADMISSION")
    os.environ["ISTPU_ADMISSION"] = "0"
    try:
        eng = engine(n_blocks=64)
        eng.gated = False
        srv = ServingServer(eng, port=0, max_batch=4, model_id="wait-close")
    finally:
        if old is None:
            os.environ.pop("ISTPU_ADMISSION", None)
        else:
            os.environ["ISTPU_ADMISSION"] = old
    srv.start()
    assert srv.sched.intake is not None
    eng.gated = True
    q1 = srv.submit({"prompt": FIRST, "max_tokens": 12, "temperature": 0})
    until(lambda: eng.flights, "a dispatch is in flight")
    q2 = srv.submit({"prompt": PROMPTS[0], "max_tokens": 6, "temperature": 0})
    until(lambda: ("chunk", tuple(PROMPTS[0][:3])) in eng.log,
          "the second request's prefill began under the dispatch")
    assert not eng.flights[-1].gate.is_set()
    threading.Timer(0.2, lambda: eng.flights[-1].gate.set()).start()
    srv.close()
    assert not srv._engine_thread.is_alive()
    for q in (q1, q2):
        kinds = []
        while not q.empty():
            kinds.append(q.get()[0])
        assert "abort" in kinds
