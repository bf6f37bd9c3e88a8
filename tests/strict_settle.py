"""Strict durability's per-request acknowledgement, as cases that any engine
kind runs (not collected by itself: ``test_engine.py`` runs them over pages,
``test_retention.py`` over state slots).

A case takes a ``kit``:

* ``kit.engine(durability=, store=)`` builds an engine with chunked prefill,
  over the module's live store unless ``store=False``, under a model id of its
  own (nothing an earlier test pushed is found);
* ``kit.first`` is a short prompt that gets a batch decoding, and
  ``kit.prompts(n)`` are ``n`` unshared prompts that each push to the store
  exactly ONCE and need two or more chunks (so a held push holds nobody in
  the streamer's bounded queue, and a burst of three fits ``kit.max_batch``
  chunks);
* ``kit.solo(prompt, n)`` is the prompt's greedy continuation run alone;
* ``kit.unnamed(eng, prompt)`` says that no page of the prompt is named for
  sharing; ``kit.names_pages`` is False for a kind that names none when a
  prefill is settled.

The store's acknowledgement is held by ``HeldCommits``: the streamer's worker
half (``transfer.push_commit``: materialize, pool copy, COMMIT_PUT) of the
chosen prompts waits for ``release``, or fails."""

import threading
import time
import types

import pytest

from infinistore_tpu.engine import Scheduler
from infinistore_tpu.engine.stepprof import StepProfiler
from infinistore_tpu.kv.hashing import chunk_keys
from infinistore_tpu.utils import tracing
from infinistore_tpu.utils.metrics import MetricsRegistry

WAIT_S = 30.0


class HeldCommits:
    """``eng.transfer.push_commit`` behind a gate a prompt: the pushes whose
    first key is one of a prompt in ``hold`` wait for ``release(prompt)``;
    those of a prompt in ``fail`` raise.  A prompt is known by its first
    chunk's key (``keys_of(prompt)[0]``): ``seen`` lists the prompts whose
    pushes the worker took, in that order."""

    def __init__(self, eng):
        self.eng = eng
        self.inner = eng.transfer.push_commit
        self.gates = {}
        self.failing = set()
        self.owner = {}
        self.seen = []
        eng.transfer.push_commit = self

    def keys_of(self, prompt):
        return chunk_keys(list(prompt), self.eng.model_id,
                          chunk_tokens=self.eng.pc.block_tokens)

    def _known(self, prompt):
        keys = self.keys_of(prompt)
        self.owner.update(dict.fromkeys(keys, keys[0]))
        return keys[0]

    def hold(self, prompt):
        self.gates[self._known(prompt)] = threading.Event()

    def fail(self, prompt):
        self.failing.add(self._known(prompt))

    def release(self, prompt):
        self.gates[self.keys_of(prompt)[0]].set()

    def __call__(self, token):
        # (bands, keys, t_begin): transfer.push_begin
        who = self.owner.get(token[1][0], token[1][0])
        self.seen.append(who)
        if who in self.gates:
            assert self.gates[who].wait(WAIT_S), "a held push was never released"
        if who in self.failing:
            raise RuntimeError(f"commit refused for {who}")
        return self.inner(token)


def until(cond, what):
    deadline = time.time() + WAIT_S
    while not cond():
        assert time.time() < deadline, f"never happened: {what}"
        time.sleep(0.005)


def in_thread(fn):
    """Run ``fn`` on a thread of its own; ``.result()`` joins and re-raises."""
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — handed to the caller
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)

    def result():
        t.join(WAIT_S)
        assert not t.is_alive(), "the thread never returned"
        if "err" in box:
            raise box["err"]
        return box["out"]

    t.result = result
    t.start()
    return t


def decoding_batch(kit, n_new, durability="strict", store=True, max_new=6):
    """A scheduler whose first request decodes, and ``n_new`` prompts
    submitted behind it: the next ``step()`` runs all their chunks as ONE
    burst.  ``log`` gains an entry a call: ``("p", i)`` a prefill chunk of
    new prompt ``i``, ``("d", [i, ..])`` a decode dispatch and the new
    prompts among its rows; ``markers[i]`` is prompt ``i``'s push marker."""
    eng = kit.engine(durability=durability, store=store)
    full = eng.free_pages
    prof = StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    sched = Scheduler(eng, max_batch=kit.max_batch, stepprof=prof)
    sched.submit(kit.first, 64)
    sched.step()
    assert len(sched.active) == 1
    prompts = kit.prompts(n_new)
    rids = [sched.submit(p, max_new) for p in prompts]
    reqs = {r.req_id: r for r in sched.pending}
    log, markers = [], {}
    step_inner, decode_inner = eng.prefill_step, eng.decode_batch

    def which(tokens):
        return next((i for i, p in enumerate(prompts)
                     if tokens[:len(p)] == p), None)

    def prefill_step(pp):
        log.append(("p", which(pp.tokens)))
        markers[which(pp.tokens)] = pp.marker
        return step_inner(pp)

    def decode_batch(states, *a, **kw):
        log.append(("d", sorted(i for i in (which(st.tokens) for st in states)
                                if i is not None)))
        return decode_inner(states, *a, **kw)

    eng.prefill_step, eng.decode_batch = prefill_step, decode_batch
    return types.SimpleNamespace(
        eng=eng, sched=sched, prompts=prompts, reqs=[reqs[r] for r in rids],
        log=log, markers=markers, full=full)


def joined(b, req):
    return req in b.sched.active or (req.state is not None
                                     and req.state.seq_id in b.eng.seqs)


def run_out(b):
    out = {}
    while b.sched.has_work:
        for r in b.sched.step():
            out[r.req_id] = r.output
    return out


# -- the cases ---------------------------------------------------------------------

def case_unsettled_until_acknowledged_then_joins_the_same_step(kit):
    """(1) While a prompt's last push is unacknowledged the request is in
    neither ``active`` nor ``seqs``, has no output and no page named; once
    released it joins in the SAME ``step()``, before that step's dispatch."""
    b = decoding_batch(kit, 1)
    held = HeldCommits(b.eng)
    held.hold(b.prompts[0])
    n_seqs = len(b.eng.seqs)
    step = in_thread(b.sched.step)
    until(lambda: b.sched._parked, "the finished prompt is parked")
    time.sleep(0.1)              # the engine thread stands in its one wait
    assert step.is_alive() and b.sched._parked[0][0] is b.reqs[0]
    assert not joined(b, b.reqs[0]) and len(b.eng.seqs) == n_seqs
    assert b.reqs[0].output == [] and b.reqs[0].state is None
    assert kit.unnamed(b.eng, b.prompts[0])
    assert not any(k == "d" for k, _ in b.log)    # no dispatch was built
    held.release(b.prompts[0])
    step.result()
    assert not b.sched._parked and b.reqs[0] in b.sched.active
    assert b.log[-1] == ("d", [0]) and b.reqs[0].output   # decoded in that step
    assert not (kit.names_pages and kit.unnamed(b.eng, b.prompts[0]))


def case_burst_goes_on_past_a_held_acknowledgement(kit):
    """(2) One burst over three prompts: the second prompt's first chunk is
    launched while the first prompt's acknowledgement is held (a join of the
    whole streamer after the first prompt's last chunk never launches it),
    and the step waits ONCE, for all three."""
    b = decoding_batch(kit, 3)
    held = HeldCommits(b.eng)
    held.hold(b.prompts[0])
    step = in_thread(b.sched.step)
    until(lambda: len(b.sched._parked) == 3, "three finished prompts parked")
    assert [r for r, _pp in b.sched._parked] == b.reqs    # in finishing order
    assert {i for k, i in b.log if k == "p"} == {0, 1, 2}
    assert held.seen == [held.keys_of(b.prompts[0])[0]]   # in the store's hands
    assert not any(joined(b, r) for r in b.reqs)
    held.release(b.prompts[0])
    step.result()
    assert b.log[-1] == ("d", [0, 1, 2])
    rec = b.sched.stepprof.tail(1)[0]["prefill"]
    assert rec["settle_waits"] == 1 and rec["settled_prompts"] == 3
    assert rec["settle_wait_s"] > 0 and rec["push_queue_full_waits"] == 0


def case_a_held_push_delays_only_its_own_request(kit):
    """(3) Request B's pushes are done, request A's last is held: B is
    settled and joins while A waits, though neither carries a trace id (they
    no longer share the marker ``None``)."""
    b = decoding_batch(kit, 2)
    held = HeldCommits(b.eng)
    held.hold(b.prompts[1])      # A: the one that finishes second
    assert tracing.current_trace_id() is None
    step = in_thread(b.sched.step)
    until(lambda: b.reqs[0] in b.sched.active, "B joined while A's push is held")
    time.sleep(0.05)
    assert step.is_alive() and [r for r, _ in b.sched._parked] == [b.reqs[1]]
    assert not joined(b, b.reqs[1])
    assert len({b.markers[0], b.markers[1], None}) == 3
    held.release(b.prompts[1])
    step.result()
    assert b.log[-1] == ("d", [0, 1])


def case_a_push_error_leaves_the_step_that_would_have_settled_it(kit):
    """(4) and (5, ``fault_reset``): the failed request's own error leaves
    the ``step()`` that would have settled it; the prompt settled before it
    has joined, its pages named; the failed one never joins, however often
    it is asked; ``fault_reset`` gives every page and slot back."""
    b = decoding_batch(kit, 3)
    held = HeldCommits(b.eng)
    held.fail(b.prompts[1])
    refused = f"commit refused for {held.keys_of(b.prompts[1])[0]}"
    with pytest.raises(RuntimeError, match=refused):
        b.sched.step()
    assert b.reqs[0] in b.sched.active
    assert not (kit.names_pages and kit.unnamed(b.eng, b.prompts[0]))
    assert [r for r, _ in b.sched._parked] == b.reqs[1:]
    pp = b.sched._parked[0][1]
    assert pp.marker == b.markers[1] and refused in str(pp.push_error)
    assert not any(k == "d" for k, _ in b.log)
    with pytest.raises(RuntimeError, match=refused):
        b.sched.step()           # asked again it raises again: it never joins
    assert not joined(b, b.reqs[1]) and kit.unnamed(b.eng, b.prompts[1])
    dropped = b.sched.fault_reset()
    assert {id(r) for r in b.reqs} <= {id(r) for r in dropped}
    assert not b.sched._parked and not b.sched.has_work
    assert b.eng.free_pages == b.full
    b.eng.store_flush()          # the parked error went with the raise


def case_a_cancelled_parked_prefill_gives_its_pages_and_slot_back(kit):
    """(5, cancel): a request cancelled while it is parked behind another's
    held acknowledgement never joins; its pages and slot come back."""
    b = decoding_batch(kit, 2)
    held = HeldCommits(b.eng)
    held.hold(b.prompts[0])
    step = in_thread(b.sched.step)
    until(lambda: len(b.sched._parked) == 2, "both parked")
    assert b.sched.cancel(b.reqs[1].req_id)
    held.release(b.prompts[0])
    out = step.result()
    assert b.reqs[1] in out and b.reqs[1].done and b.reqs[1].state is None
    assert b.reqs[1].output == [] and b.log[-1] == ("d", [0])
    assert not b.sched._parked and not joined(b, b.reqs[1])
    run_out(b)
    assert b.eng.free_pages == b.full


def case_blocking_forms_return_after_the_acknowledgement(kit, form):
    """(6) ``engine.prefill()`` / ``prefill_batch()`` return only once the
    store has acknowledged: their callers see the contract as it was."""
    eng = kit.engine(durability="strict", store=True)
    prompt = kit.prompts(1)[0]
    held = HeldCommits(eng)
    held.hold(prompt)
    call = in_thread((lambda: eng.prefill(prompt)) if form == "prefill"
                     else (lambda: eng.prefill_batch([prompt])[0]))
    until(lambda: held.seen, "the push reached the store")
    time.sleep(0.2)
    assert call.is_alive() and not eng.seqs and kit.unnamed(eng, prompt)
    held.release(prompt)
    st = call.result()
    assert eng.seqs[st.seq_id] is st and st.tokens == prompt
    eng.release(st)


def case_burst_outputs_equal_solo_runs(kit, mode):
    """(7) Greedy outputs of a three-prompt burst equal the prompts' solo
    runs; only strict durability with a store parks anything."""
    b = decoding_batch(
        kit, 3, durability="relaxed" if mode == "relaxed" else "strict",
        store=mode != "no-store")
    parked = []
    inner = b.sched._settle_parked

    def settle(*a):
        parked.append(len(b.sched._parked))
        return inner(*a)

    b.sched._settle_parked = settle
    out = run_out(b)
    for p, r in zip(b.prompts, b.reqs):
        assert out[r.req_id] == kit.solo(p, 6), mode
    tot = b.sched.stepprof.summary()["prefill"]
    if mode == "strict":
        # the wave's blocking prefill waited for itself, the burst once for three
        assert max(parked) == 3
        assert (tot["settle_waits"], tot["settled_prompts"]) == (2, 4)
    else:
        assert max(parked) == 0
        assert tot["settle_waits"] == tot["settled_prompts"] == 0
        assert tot["settle_wait_s"] == 0
    if mode != "no-store":
        b.eng.store_flush()
    assert b.eng.free_pages == b.full


CASES = [
    case_unsettled_until_acknowledged_then_joins_the_same_step,
    case_burst_goes_on_past_a_held_acknowledgement,
    case_a_held_push_delays_only_its_own_request,
    case_a_push_error_leaves_the_step_that_would_have_settled_it,
    case_a_cancelled_parked_prefill_gives_its_pages_and_slot_back,
]
FORMS = ["prefill", "prefill_batch"]
MODES = ["strict", "relaxed", "no-store"]
