"""The step loop's timeline from inside the program (`engine/stepprof.py`
``enter`` / ``note_decode``, the ledger's ``ttft`` block,
the names of the jitted programs) and the benchmark readers that read them.

Pure halves run on injected clocks; the live halves drive the tiny preset on
the CPU: a real ``jax.profiler`` capture that must hold the ``istpu.*`` phases
in its host plane, real schedulers whose ledger rows must sum, and a real
store for the store-hit row.  Nothing here is a device number.
"""

import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from infinistore_tpu.engine import stepprof
from infinistore_tpu.utils.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _prof(**kw):
    kw.setdefault("metrics", MetricsRegistry())
    kw.setdefault("sample", 10**9)
    return stepprof.StepProfiler(**kw)


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# A. flat phases: an exact partition, never nested
# ---------------------------------------------------------------------------

class _Tick:
    """A clock that advances by a scripted amount per read (whole numbers:
    the sums below are exact in floating point)."""

    def __init__(self, steps):
        self.now, self.steps = 100.0, list(steps)

    def __call__(self):
        self.now += self.steps.pop(0) if self.steps else 1.0
        return self.now


class _Annot:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annot.log.append(("open", self.name))

    def __exit__(self, *exc):
        _Annot.log.append(("close", self.name))


SCRIPTS = {
    "loop": ["idle", "intake", "admit", "sched", "decode.launch",
             "decode.wait", "decode.unpack", "retire_stream", "idle"],
    "repeats": ["admit", "kv.lookup", "admit", "kv.load", "admit", "admit",
                "sched"],
    "closes": ["intake", None, "admit", "sched", None],
    # a pushed chunk: the gather, push_begin, the submit's rest, a full queue
    "push": ["prefill.launch", "kv.push_gather", "kv.push_begin",
             "kv.push_submit", "kv.push_wait", "kv.push_submit",
             "prefill.launch", "sched"],
}


@pytest.mark.parametrize("script", sorted(SCRIPTS))
def test_phases_partition_the_threads_time_exactly(script, monkeypatch):
    monkeypatch.setattr(stepprof, "_annotation", _Annot)
    _Annot.log = []
    names = SCRIPTS[script]
    clock = _Tick([3, 5, 7, 11, 13, 17, 19, 23, 29, 31])
    prof = _prof(clock=clock)
    stamps = [prof.enter(n) for n in names]
    t_end = prof.enter(None)
    s = prof.summary()
    # the seconds between two switches belong to the phase the first began:
    # summed, they are the thread's time less the stretches with none open
    want = {}
    for n, a, b in zip(names, stamps, stamps[1:] + [t_end]):
        if n is not None:
            want[n] = want.get(n, 0.0) + (b - a)
    assert s["phase_s"] == want
    closed = sum(b - a for n, a, b in zip(names, stamps, stamps[1:] + [t_end])
                 if n is None)
    assert sum(s["phase_s"].values()) == (t_end - stamps[0]) - closed
    assert s["phase_wall_s"] == (t_end - stamps[0]) - closed   # its own reads
    # flat in the profiler's trace too: an annotation is closed before the
    # next opens, whatever the names
    depth = 0
    for what, _name in _Annot.log:
        depth += 1 if what == "open" else -1
        assert depth in (0, 1), _Annot.log
    assert [n for w, n in _Annot.log if w == "open"] == \
        ["istpu." + n for n in names if n]


def test_phase_context_reenters_the_outer_phase_and_steps_split(monkeypatch):
    """``with phase(x)`` inside a phase closes the outer one and reopens it
    (never nests); a step's record holds exactly its own share; a scrape
    counts the open phase up to the moment it is read."""
    monkeypatch.setattr(stepprof, "_annotation", _Annot)
    _Annot.log = []
    clock = _Tick([])            # every read advances one second
    prof = _prof(clock=clock)
    prof.enter("intake")                         # t=101
    with prof.step() as rec:                     # t0=102
        stepprof.enter("admit")                  # 103
        with stepprof.phase("kv.load") as ph:    # 104
            pass                                 # 105: back to admit
        assert ph.s == 1.0 and prof.phase == "admit"
        stepprof.enter("sched")                  # 106
    # t1=107; the step began inside "intake" and that phase stays the loop's
    assert rec["phases"] == {"intake": 1.0, "admit": 2.0, "kv.load": 1.0,
                             "sched": 1.0}
    assert sum(rec["phases"].values()) == rec["dur_s"] == 5.0
    assert prof.phase == "sched"                 # the loop goes on from here
    mid = prof.summary()["phase_s"]              # read at 108: sched open
    assert mid["sched"] == 2.0 and mid["intake"] == 2.0
    assert sum(mid.values()) == 7.0      # 101 .. 108, nothing in between
    opens = [n for w, n in _Annot.log if w == "open"]
    assert opens == ["istpu.intake", "istpu.admit", "istpu.kv.load",
                     "istpu.admit", "istpu.sched"]
    # a step that begins with no phase open leaves none open
    lone = _prof(clock=_Tick([]))
    with lone.step():
        stepprof.enter("decode.launch")
    assert lone.phase is None
    # and with no profiler on the thread the context still times the site
    with stepprof.phase("kv.load") as ph:
        time.sleep(0.001)
    assert ph.s >= 0.001


def test_push_queue_full_counts_only_a_submit_whose_put_blocked():
    """``push_queue_full_*`` is place (a) of ``kv.push_wait`` alone: a
    ``submit`` that found the bounded queue full, timed by its own phase.  A
    submit that found room counts nothing, and neither does a wait for
    acknowledgements (``await_prefill`` / ``flush``: the engine counts those
    as ``settle_*``), though both stand in the same phase."""
    from infinistore_tpu.engine.engine import _StoreStreamer

    class Slow:
        class breaker:
            allow = staticmethod(lambda: True)
            record_success = record_failure = staticmethod(lambda: None)

        def push_begin(self, pages, keys):
            return ("tok", list(keys))

        def push_commit(self, token):
            time.sleep(0.15)

    prof = _prof()
    st = _StoreStreamer(Slow(), maxsize=1, durability="strict")
    with prof.step() as rec:
        stepprof.enter("admit")
        st.submit(None, ["k1"], marker="m")      # taken by the worker
        time.sleep(0.05)
        st.submit(None, ["k2"], marker="m")      # the queue's one place
        assert "prefill" not in rec
        st.submit(None, ["k3"], marker="m")      # full: waits for k1's commit
        full = dict(rec["prefill"])
        with stepprof.phase("kv.push_wait"):
            st.await_prefill("m")                # k2 and k3: not the queue's
    assert full["push_queue_full_waits"] == 1
    assert 0.02 < full["push_queue_full_s"] < 0.15
    assert rec["prefill"] == full and full["settle_waits"] == 0
    assert rec["phases"]["kv.push_wait"] > full["push_queue_full_s"] + 0.25
    assert prof.summary()["prefill"]["push_queue_full_waits"] == 1


def test_fifteen_enters_and_a_count_cost_under_50_microseconds():
    """What a scheduler step pays for the timeline with the profiler off:
    about fifteen ``enter`` calls and one ``note_decode``, inside a step
    record and a bound trace (the serving loop's state).  Best of several
    batches: the guard is on the code, not on the machine's other tenants."""
    from infinistore_tpu.utils import tracing

    prof = _prof()
    names = ["admit", "kv.lookup", "admit", "kv.load", "sched",
             "prefill.launch", "kv.push_gather", "kv.push_begin",
             "kv.push_submit", "prefill.launch", "decode.launch",
             "decode.wait", "decode.unpack", "retire_stream", "idle"]
    assert len(names) == 15
    best = float("inf")
    prof.enter("intake")
    with tracing.trace("engine.step"), prof.step():
        for _ in range(40):     # until one batch ran undisturbed
            t0 = time.perf_counter()
            for _ in range(200):
                for n in names:
                    stepprof.enter(n)
                stepprof.note_decode(32, 3, 4, 256, 16, 9000)
            best = min(best, (time.perf_counter() - t0) / 200)
            if best < 50e-6:
                break
    assert best < 50e-6, f"{best * 1e6:.1f} us per step"


def test_a_pushs_ten_stages_cost_under_50_microseconds_with_no_capture():
    """The twin of the guard above for ``stepprof.stage``, the bracket of a
    thread that is not the engine's: what the streamer's worker pays a push
    of four bands (ALLOC_PUT, four waits for a band's D2H and four pool
    copies, COMMIT_PUT) while no capture runs, when each annotation is a
    flag test."""
    names = ["alloc"] + ["d2h", "pool_copy"] * 4 + ["commit"]
    assert len(names) == 10
    best, total, t_first = float("inf"), 0.0, time.perf_counter()
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(200):
            for n in names:
                with stepprof.stage("istpu.stream." + n) as st:
                    pass
                total += st.s
        best = min(best, (time.perf_counter() - t0) / 200)
    # each stage's own seconds: inside the bracket, so inside the loop's
    assert 0 < total < time.perf_counter() - t_first
    assert best < 50e-6, f"{best * 1e6:.1f} us per push"


# ---------------------------------------------------------------------------
# B/C. names of the programs; counts at the dispatch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    import jax

    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params

    params = init_params(TINY, jax.random.PRNGKey(0))

    def make_engine(n_blocks=128, **kw):
        from infinistore_tpu.engine import InferenceEngine

        pc = PagedCacheConfig(
            n_layers=TINY.n_layers, n_kv_heads=TINY.n_kv_heads,
            head_dim=TINY.head_dim, n_blocks=n_blocks, block_tokens=4)
        kw.setdefault("decode_chunk", 4)
        return InferenceEngine(params, TINY, pc, **kw)

    return TINY, params, make_engine


@pytest.mark.parametrize("program,cls", [
    ("prefill_forward", "prefill"), ("decode_many", "decode"),
    ("verify_forward", "other")])
def test_programs_are_named_and_the_committed_table_classes_them(
        tiny, program, cls):
    import jax
    import jax.numpy as jnp

    cfg, params, make_engine = tiny
    eng = make_engine()
    if program == "decode_many":
        st = eng.prefill(list(range(1, 10)))
        Bp = 1
        lowered = eng._decode_many(4, "greedy").lower(
            eng.params, jnp.stack([st.last_logits]),
            jnp.asarray([len(st.tokens)], jnp.int32), eng.cache,
            eng._block_table([st], pad_to=Bp), jax.random.PRNGKey(0), None,
            None, jnp.ones((Bp,), bool), jnp.ones((Bp,), jnp.float32),
            jnp.zeros((Bp,), jnp.int32), jnp.ones((Bp,), jnp.float32),
            None, None, None)
    elif program == "prefill_forward":
        lowered = eng._prefill_jit.lower(
            eng.params, tokens=jnp.zeros((1, 8), jnp.int32))
    else:
        assert eng._verify_jit.__name__ == "verify_forward"
        lowered = None
    if lowered is not None:
        assert f"module @jit_{program} " in lowered.as_text()[:200]
    reduce = _load(os.path.join(BENCH, "trace", "reduce.py"))
    with open(os.path.join(BENCH, "trace", "programs.json")) as f:
        table = json.load(f)
    # a device trace names a program jit_<function>(<fingerprint>)
    names = [f"jit_{program}(1234567)", "jit__write_prefill_pages(99)"]
    assert reduce.program_class(names, 0, table) == cls
    # and the ten helpers are no longer lambdas nobody can tell apart
    from infinistore_tpu.engine import engine as E

    for fn in (E._KV_APPEND, E._SPLIT2, E._STACK_ROWS, E._UNSTACK_ROWS,
               E._ROW0, E._ARGMAX_I32, E._Q_COL0, E._SPLIT3,
               E._PICK_LAST):
        assert "lambda" not in fn.__name__, fn


def test_decode_counts_equal_the_tokens_and_never_exceed_the_table(tiny):
    from infinistore_tpu.engine.scheduler import Scheduler

    cfg, params, make_engine = tiny
    eng = make_engine(prefill_chunk=8)
    prof = _prof()
    sched = Scheduler(eng, max_batch=4, stepprof=prof)
    for i, n in enumerate((5, 11, 18)):
        sched.submit(list(range(1 + i, 1 + i + n)), max_new_tokens=8)
    sched.run()
    s = prof.summary()
    d = s["decode"]
    # no speculation here: every token the profiler counted is a decode
    # row-step, which is the check that the count at the dispatch is sound
    assert d["row_steps"] == s["tokens"] > 0
    assert d["steps"] == 4 * s["dispatches"]["decode"]
    assert 0 < d["live_token_steps"] <= d["table_token_steps"]
    # only what a metric reads is summed: the benchmark's engine.decode_*,
    # and the steps' prefill budgets beside them
    assert sorted(d) == sorted(stepprof.DECODE_COUNTS)
    assert sorted(s["prefill"]) == sorted(stepprof.PREFILL_COUNTS)
    assert s["dispatches"]["prefill"] >= 3
    recs = [r for r in prof.tail() if "decode" in r]
    assert recs and all(r["decode"]["row_steps"] == r["tokens"] for r in recs)
    assert sum(s["phase_s"].values()) > 0 and "decode.wait" in s["phase_s"]


@pytest.mark.parametrize("profiler", ["none", "disabled", "enabled"])
def test_decode_histogram_is_fed_with_or_without_a_profiler(tiny, profiler):
    """``istpu_serve_decode_step_seconds`` is the scheduler's metric: one
    observation per decode dispatch, timed by the two phase switches around
    it, which fall back to ``perf_counter`` when no profiler drives the step
    (a library ``Scheduler(stepprof=None)``, ``ISTPU_STEPPROF=0``)."""
    from infinistore_tpu.engine.scheduler import Scheduler

    cfg, params, make_engine = tiny
    reg = MetricsRegistry()
    prof = None if profiler == "none" else _prof()
    if profiler == "disabled":
        prof.enabled = False               # what ISTPU_STEPPROF=0 sets
    sched = Scheduler(make_engine(), max_batch=2, stepprof=prof, metrics=reg)
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=8)
    sched.run()
    h = sched._h_decode_step._default_child()
    assert h.count == 2 and 0 < h.sum < 60     # 8 tokens, chunks of 4
    if profiler == "enabled":
        waits = prof.summary()["phase_s"]
        assert h.sum <= sum(v for k, v in waits.items()
                            if k.startswith("decode.")) + 1e-6


@pytest.mark.parametrize("rows,padded,ctx,width,pad_pct", [
    (4, 4, 8 * 16, 8, 0.0),      # rows and width fill their buckets
    (3, 4, 8 * 16, 8, 25.0),     # one pad row of four
    (4, 4, 4 * 16, 8, 50.0),     # half the table width is unused
])
def test_pad_share_from_the_dispatch_counts(rows, padded, ctx, width, pad_pct):
    prof = _prof()
    with prof.step():
        stepprof.note_decode(steps=32, rows=rows, padded_rows=padded,
                             width_pages=width, block_tokens=16,
                             live_tokens=rows * ctx)
    d = prof.summary()["decode"]
    assert d["row_steps"] == 32 * rows and d["steps"] == 32
    assert d["live_token_steps"] <= d["table_token_steps"]
    reader = _load(os.path.join(BENCH, "readers", "decode_pad_pct.py"))
    ctx_ = {"engine_before": _prof().summary(),
            "engine_after": prof.summary(), "reader": _reader}
    assert reader.read(ctx_) == pytest.approx(pad_pct)


# ---------------------------------------------------------------------------
# B. a real capture: the phases lie in the host plane of the profiler's trace
# ---------------------------------------------------------------------------

def test_a_profiler_capture_holds_the_phases_in_its_host_plane(tiny, tmp_path):
    import jax
    from jax.profiler import ProfileData

    from infinistore_tpu.engine.scheduler import Scheduler

    cfg, params, make_engine = tiny
    eng = make_engine()
    prof = _prof()
    sched = Scheduler(eng, max_batch=2, stepprof=prof)
    sched.submit(list(range(1, 12)), max_new_tokens=12)
    sched.step()                       # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.step()
        sched.step()
    finally:
        jax.profiler.stop_trace()
    reduce = _load(os.path.join(BENCH, "trace", "reduce.py"))
    data = ProfileData.from_file(reduce.find_xplane(str(tmp_path)))
    host = {e.name for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events}
    assert {"istpu.decode.wait", "istpu.admit"} <= host, sorted(
        n for n in host if n.startswith("istpu."))


# ---------------------------------------------------------------------------
# D. the TTFT waterfall sums
# ---------------------------------------------------------------------------

def _slices(ttft):
    return sum(v for k, v in ttft.items()
               if k.endswith("_s") and k != "total_s")


def _check_row(row):
    t = row["ttft"]
    assert _slices(t) == pytest.approx(t["total_s"], abs=1e-9)
    end_s = row["ttft_s"] if row["ttft_s"] is not None else row["e2e_s"]
    assert t["total_s"] == pytest.approx(
        end_s + row["admission_wait_s"], abs=5e-6)   # the slices' rounding
    assert all(v >= 0 for k, v in t.items() if k.endswith("_s")), t
    return t


def _run(tiny, prompts, *, blocker=False, store_conn=None, **eng_kw):
    from infinistore_tpu.engine.scheduler import Scheduler
    from infinistore_tpu.ledger import RequestLedger

    cfg, params, make_engine = tiny
    eng = make_engine(conn=store_conn, kv_quant=None, **eng_kw)
    ledger = RequestLedger(capacity=64, log=False)
    sched = Scheduler(eng, max_batch=4, stepprof=_prof(), ledger=ledger)
    if blocker:       # a batch is decoding: newcomers take the chunked path
        sched.submit([7, 7, 7], max_new_tokens=24)
        sched.step()
    ids = [sched.submit(p, max_new_tokens=8, t_stage=time.perf_counter())
           for p in prompts]
    sched.run()
    rows = {r["req_id"]: r for r in ledger.tail()}
    return [rows[i] for i in ids], eng


@pytest.mark.parametrize("case,chunks", [
    ("wave", None), ("chunked-1", 1), ("chunked-3", 3), ("chunked-6", 6)])
def test_ttft_slices_sum_for_wave_and_chunked_admission(tiny, case, chunks):
    prompt = list(range(3, 3 + 44))          # 44 tokens, chunks of 8: six
    if case == "wave":
        rows, _ = _run(tiny, [prompt[:20], prompt[:9]])
    else:
        rows, _ = _run(tiny, [prompt[:8 * chunks - 4]],
                       blocker=True, prefill_chunk=8)
    for row in rows:
        t = _check_row(row)
        assert row["ttft_s"] is not None and t["first_burst_s"] > 0
        assert t["prefill_own_s"] > 0 and t["steps_to_first"] >= 1
        assert t["lookup_s"] == t["load_s"] == 0.0
    if chunks:
        t = rows[0]["ttft"]
        assert t["prefill_chunks"] == chunks
        # max_batch = 4 chunks a scheduler step, back to back, the first
        # token one dispatch later
        assert t["steps_to_first"] == -(-chunks // 4)
        if chunks > 4:   # parked behind the blocker's dispatch in between
            assert t["prefill_wait_s"] > 0


def test_ttft_slices_sum_for_a_request_cancelled_before_admission(tiny):
    from infinistore_tpu.engine.scheduler import Scheduler
    from infinistore_tpu.ledger import RequestLedger

    cfg, params, make_engine = tiny
    ledger = RequestLedger(capacity=8, log=False)
    sched = Scheduler(make_engine(), max_batch=1, stepprof=_prof(),
                      ledger=ledger)
    rid = sched.submit([1, 2, 3], max_new_tokens=4,
                       t_stage=time.perf_counter() - 0.25)
    time.sleep(0.01)
    assert sched.cancel(rid)
    (row,) = ledger.tail()
    t = _check_row(row)
    assert row["outcome"] == "cancelled" and row["ttft_s"] is None
    assert t["stage_wait_s"] == pytest.approx(0.25, abs=0.05)
    # the remainder of slices rounded to the microsecond: zero to two of them
    assert t["queue_s"] >= 0.01 and 0.0 <= t["prefill_wait_s"] <= 2e-6
    assert t["first_burst_s"] == t["prefill_own_s"] == 0.0


@pytest.fixture(scope="module")
def store_port():
    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    port, mport = free_port(), free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server", "--service-port",
         str(port), "--manage-port", str(mport), "--prealloc-size", "1",
         "--minimal-allocate-size", "16", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    deadline = time.time() + 20
    while time.time() < deadline:
        if proc.poll() is not None:
            pytest.fail("store server failed to start")
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


def test_ttft_slices_sum_for_a_store_hit_and_the_store_totals(tiny, store_port):
    import infinistore_tpu as ist

    def conn():
        c = ist.InfinityConnection(ist.ClientConfig(
            host_addr="127.0.0.1", service_port=store_port,
            connection_type=ist.TYPE_SHM))
        c.connect()
        return c

    prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1, 11, 12, 13, 14, 15, 16, 17, 18]
    (first,), producer = _run(tiny, [prompt], store_conn=conn())
    assert first["store"]["store_chunks"] == 0
    push = producer.transfer.push_totals
    assert push["pushes"] >= 1 and push["tokens"] == 16   # four whole pages
    assert push["submit_to_commit_s"] > 0 and push["bytes"] > 0
    # another engine on the same store: the prompt's pages come from it
    (row,), consumer = _run(tiny, [prompt], store_conn=conn())
    t = _check_row(row)
    assert row["store"]["store_chunks"] == 4 and row["store"]["hit"]
    assert t["lookup_s"] > 0 and t["load_s"] > 0
    # the ledger's older store slice is the same two phases, timed once
    assert t["lookup_s"] + t["load_s"] == pytest.approx(
        row["store"]["load_s"], abs=2e-6)
    load = consumer.transfer.load_totals
    assert load["loads"] == 1 and load["tokens"] == 16
    assert load["fetch_s"] + load["scatter_s"] <= t["load_s"] + 1e-6


# ---------------------------------------------------------------------------
# D2. a push and a load timed where they happen (the python client: the
# native one keeps its stage timings in C)
# ---------------------------------------------------------------------------

@pytest.fixture
def py_conn(store_port, monkeypatch):
    import infinistore_tpu as ist

    monkeypatch.setenv("ISTPU_CLIENT", "python")
    conns = []

    def conn():
        c = ist.InfinityConnection(ist.ClientConfig(
            host_addr="127.0.0.1", service_port=store_port,
            connection_type=ist.TYPE_SHM))
        c.connect()
        conns.append(c)
        return c

    yield conn
    for c in conns:
        c.close()


def test_a_capture_holds_the_workers_stages_on_a_line_of_their_own(
        tiny, py_conn, tmp_path):
    """A store-attached prefill under a real capture: the streamer's worker
    writes ``istpu.stream.*`` on a line of the host plane that holds none of
    the engine thread's phases, and the engine's line holds the two phases
    cut out of ``kv.push_submit``."""
    import jax
    from jax.profiler import ProfileData

    from infinistore_tpu.engine.scheduler import Scheduler

    cfg, params, make_engine = tiny
    eng = make_engine(conn=py_conn(), kv_quant=None, prefill_chunk=8)
    sched = Scheduler(eng, max_batch=2, stepprof=_prof())
    sched.submit(list(range(1, 30)), max_new_tokens=4)
    sched.run()                        # compile outside the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.submit(list(range(40, 70)), max_new_tokens=4)
        sched.run()
    finally:
        jax.profiler.stop_trace()
    reduce = _load(os.path.join(BENCH, "trace", "reduce.py"))
    data = ProfileData.from_file(reduce.find_xplane(str(tmp_path)))
    lines = [{e.name for e in ln.events if e.name.startswith("istpu.")}
             for p in data.planes if p.name.startswith("/host:")
             for ln in p.lines]
    engine = [ln for ln in lines if "istpu.prefill.launch" in ln]
    worker = [ln for ln in lines if any(n.startswith("istpu.stream.")
                                        for n in ln)]
    assert len(engine) == 1 and len(worker) == 1, lines
    assert {"istpu.kv.push_gather", "istpu.kv.push_begin",
            "istpu.kv.push_submit"} <= engine[0], sorted(engine[0])
    # a mapped pool: no wire stage; and the worker never enters a phase
    assert worker[0] == {"istpu.stream.d2h", "istpu.stream.pool_copy",
                         "istpu.stream.alloc", "istpu.stream.commit"}


PUSH_STAGES = ("d2h_s", "pool_copy_s", "alloc_s", "commit_s", "wire_s")


def test_a_pushs_queue_and_commit_wall_sum_to_submit_to_commit(py_conn):
    """Over a slowed store (every ``write_cache_into`` sleeps first): a lone
    push waits about nothing in the queue, one submitted behind another
    waits for it; ``queue_s + commit_wall_s`` is ``submit_to_commit_s`` and
    the stages, timed inside ``push_commit``, never exceed its wall.  Both
    ride on the step record with the other totals."""
    from types import SimpleNamespace

    from infinistore_tpu.engine.engine import _StoreStreamer
    from infinistore_tpu.kv import KVTransferEngine, PagedCacheConfig
    from infinistore_tpu.kv.cache import init_cache

    pc = PagedCacheConfig(n_layers=4, n_kv_heads=2, head_dim=8, n_blocks=16,
                          block_tokens=4)
    tr = KVTransferEngine(py_conn(), pc)
    cache = init_cache(pc) + 1.0
    into = tr._src.write_cache_into

    def slowed(bands, stage=None):
        time.sleep(0.12)
        return into(bands, stage)

    tr._src.write_cache_into = slowed
    st = _StoreStreamer(tr, maxsize=2, durability="strict")

    def check(t):
        assert t["queue_s"] + t["commit_wall_s"] == pytest.approx(
            t["submit_to_commit_s"], abs=1e-6)
        assert sum(t[k] for k in PUSH_STAGES) <= t["commit_wall_s"]
        assert t["d2h_s"] > 0 and t["alloc_s"] > 0 and t["commit_s"] > 0

    prof = _prof()
    sched = SimpleNamespace(engine=SimpleNamespace(transfer=tr, cache=None))
    with prof.step(sched) as rec:
        st.submit(tr.gather_pages(cache, [1, 2]), ["lone-a", "lone-b"])
        st.flush()
    lone = tr.push_totals
    check(lone)
    assert lone["pushes"] == 1 and lone["queue_s"] < 0.05
    assert lone["commit_wall_s"] >= 0.12
    assert rec["store"]["push"]["queue_s"] == round(lone["queue_s"], 6)
    assert rec["store"]["push"]["commit_wall_s"] == round(
        lone["commit_wall_s"], 6)
    st.submit(tr.gather_pages(cache, [3]), ["first"])
    st.submit(tr.gather_pages(cache, [4]), ["behind-it"])
    st.flush()
    both = tr.push_totals
    check(both)
    assert both["pushes"] == 3
    assert both["queue_s"] - lone["queue_s"] >= 0.1     # the second's wait
    assert prof.summary()["store"]["push"]["queue_s"] == both["queue_s"]


def _random_cache(pc, seed=0):
    """``init_cache(pc)``'s arrays filled with values that differ page by
    page (a value is its own index, folded into the type's exact range)."""
    import jax.numpy as jnp

    from infinistore_tpu.kv.cache import init_cache

    def fill(a, salt):
        n = int(jnp.size(a))
        return ((jnp.arange(n, dtype=jnp.float32) * 7 + salt) % 251 - 125
                ).reshape(a.shape).astype(a.dtype)

    zero = init_cache(pc)
    if isinstance(zero, tuple):
        return tuple(fill(a, seed + 13 * i) for i, a in enumerate(zero))
    return fill(zero, seed)


@pytest.mark.parametrize("kind", ["dense", "latent", "two-pools", "int8",
                                  "state"])
def test_the_pushs_one_program_holds_what_the_gather_and_the_slices_held(kind):
    """``gather_pages`` is one program that returns the layer bands: byte for
    byte the gather by ids, the transpose to ``[L, n, planes, H, T, D]``, the
    int8 quantize and the ``pipeline_groups`` slices that were a launch each
    (here in numpy, and ``quantize_pages`` alone), for a dense page, a latent
    page, a cache of two pools (the bands in stack order, each gathered from
    its own pool under its own ids, so cut again where the layer kind
    changes), int8 pages and a state slot; five layers in bands of 2 + 2 + 1."""
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.kv import KVTransferEngine, PagedCacheConfig
    from infinistore_tpu.kv.cache import StateCacheConfig
    from infinistore_tpu.kv.quant import quantize_pages
    from infinistore_tpu.kv.transfer import StateTransferEngine

    L = 5
    if kind == "state":
        pc = StateCacheConfig(n_layers=L, n_kv_heads=2, state_dim=8,
                              head_dim=4, n_blocks=16, stride=16, max_rows=1,
                              block_tokens=4)
        tr = StateTransferEngine(None, pc)
        S, z = cache = _random_cache(pc)
        bands = tr.gather_pages(cache, 2)
        s = np.asarray(S)[2].reshape(L, 2, 8 * 4)
        want = np.concatenate([s, np.asarray(z)[2]], axis=-1)[:, None]
    else:
        pc = PagedCacheConfig(
            n_layers=L, n_kv_heads=1 if kind == "latent" else 2, head_dim=8,
            n_blocks=16, block_tokens=4,
            planes=1 if kind == "latent" else 2,
            window_layers=(0, 1, 3) if kind == "two-pools" else (),
            window_blocks=8 if kind == "two-pools" else 0)
        tr = KVTransferEngine(None, pc,
                              quant="int8" if kind == "int8" else None)
        cache = _random_cache(pc)
        if kind == "two-pools":
            ids = ([9, 3, 12], [5, 0, 2])          # full pool, window pool
            by_layer = {li: np.asarray(a, np.float32)[i][:, :, pool_ids]
                        for (layers, _), a, pool_ids in zip(pc.pools, cache,
                                                            ids)
                        for i, li in enumerate(layers)}
            gathered = np.stack([by_layer[li] for li in range(L)])
        else:
            ids = [9, 3, 12]
            gathered = np.asarray(cache, np.float32)[:, :, :, ids]
        bands = tr.gather_pages(cache, ids)
        want = jnp.asarray(gathered.transpose(0, 3, 1, 2, 4, 5), pc.dtype)
        if kind == "int8":
            want = quantize_pages(want)
        want = np.asarray(want)
    # a band is gathered from ONE pool: layers 2 (full) and 3 (window) part
    assert [b.shape[0] for b in bands] == (
        [2, 1, 1, 1] if kind == "two-pools" else [2, 2, 1])
    got = np.concatenate([np.asarray(b) for b in bands])
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    if kind != "state":
        assert got.shape[:2] == (L, 3)
        assert got[0, 0].nbytes == tr.wire_page_bytes


@pytest.mark.parametrize("quant", [None, "int8"])
def test_pages_written_after_push_begin_do_not_change_what_is_pushed(
        py_conn, quant):
    """The bands are a snapshot: pages overwritten in the (donated) cache
    after ``push_begin`` and before ``push_commit`` reach the store as they
    were when gathered.  The engine leans on it twice: the next chunk's
    page write and the window pool's reclaim both follow the submit."""
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.kv import KVTransferEngine, PagedCacheConfig
    from infinistore_tpu.kv.cache import init_cache
    from infinistore_tpu.kv.transfer import _scatter_stacked

    pc = PagedCacheConfig(n_layers=4, n_kv_heads=2, head_dim=8, n_blocks=16,
                          block_tokens=4)
    tr = KVTransferEngine(py_conn(), pc, quant=quant)
    cache = _random_cache(pc)
    ids, keys = [1, 2, 3], [f"snap-{quant}-{i}" for i in range(3)]
    before = np.asarray(cache, np.float32)[:, :, :, ids]
    token = tr.push_begin(tr.gather_pages(cache, ids), keys)
    cache = _scatter_stacked(cache, jnp.asarray(ids, jnp.int32),
                             jnp.full((4, 3) + pc.page_shape, 99.0, pc.dtype))
    assert float(cache[0, 0, 0, 2, 0, 0]) == 99.0
    tr.push_commit(token)
    back = tr.load_pages(init_cache(pc), [7, 8, 9], keys)
    got = np.asarray(back, np.float32)[:, :, :, [7, 8, 9]]
    if quant:       # one int8 step of a page whose largest value is 125
        np.testing.assert_allclose(got, before, atol=0.5)
    else:
        assert np.array_equal(got, before)


@pytest.mark.parametrize("path", ["banded", "layer-groups", "state"])
def test_a_loads_stages_lie_inside_its_fetch_and_its_sync_inside_its_scatter(
        py_conn, path):
    """Each of the three load paths counts ``desc_s`` (the waits for GET_DESC
    answers), ``pool_copy_s`` (pool to staging) and ``upload_s`` (the
    ``device_put`` calls) where they happen, inside ``fetch_s``, and the
    closing ``block_until_ready`` alone as ``sync_s``, inside ``scatter_s``."""
    import numpy as np

    from infinistore_tpu.kv import KVTransferEngine, PagedCacheConfig
    from infinistore_tpu.kv.cache import StateCacheConfig, init_cache
    from infinistore_tpu.kv.transfer import LOAD_STAGES, StateTransferEngine

    if path == "state":
        pc = StateCacheConfig(n_layers=4, n_kv_heads=2, state_dim=8,
                              head_dim=4, n_blocks=8, stride=16, max_rows=1,
                              block_tokens=4)
        tr = StateTransferEngine(py_conn(), pc)
        S, z = init_cache(pc)
        cache = (S.at[0].set(1.5), z.at[0].set(2.5))
        key = f"ckpt-{path}"
        tr.covers(key, 16)
        tr.push_pages(tr.gather_pages(cache, 0), [key])
        S, z = tr.load_pages(cache, [1], [key], tokens=16)
        assert np.array_equal(S[1], S[0]) and np.array_equal(z[1], z[0])
        tokens = 16
    else:
        pc = PagedCacheConfig(n_layers=4, n_kv_heads=2, head_dim=8,
                              n_blocks=16, block_tokens=4)
        tr = KVTransferEngine(py_conn(), pc)
        cache = init_cache(pc) + 1.0
        keys = [f"{path}-{i}" for i in range(3)]
        tr.save_pages(cache, [1, 2, 3], keys)
        ids = [5, 6, 7]
        if path == "banded":
            out = tr.load_pages(init_cache(pc), ids, keys)
        else:     # layers 2 and 3 need the last two chunks only
            out = tr.load_pages(
                init_cache(pc), ids, keys,
                layer_chunks=[([0, 1], [0, 1, 2], ids), ([2, 3], [1, 2], ids)])
        assert np.array_equal(out[:2, :, :, 5:8], cache[:2, :, :, 1:4])
        assert np.array_equal(out[2:, :, :, 6:8], cache[2:, :, :, 2:4])
        tokens = 12
    t = tr.load_totals
    assert t["loads"] == 1 and t["tokens"] == tokens
    assert all(t[k] > 0 for k in LOAD_STAGES), t
    assert sum(t[k] for k in LOAD_STAGES) <= t["fetch_s"]
    assert 0 < t["sync_s"] <= t["scatter_s"]


# ---------------------------------------------------------------------------
# E. the benchmark's new readers
# ---------------------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(BENCH, "readers", f"{name}.py"))


def _summary(steps, phase_s, decode, push, load):
    return {"steps": steps, "compiles": 0, "phase_s": phase_s,
            "decode": decode, "store": {"push": push, "load": load}}


FULL = {
    "reader": _reader,
    "server_rows": [
        {"ttft_s": 2.0, "ttft": {"stage_wait_s": 0.5, "prefill_wait_s": 1.0,
                                 "first_burst_s": 1.5}},
        {"ttft_s": 1.0, "ttft": {"stage_wait_s": 0.25, "prefill_wait_s": 0.0,
                                 "first_burst_s": 0.5}},
        {"ttft_s": None, "ttft": {"stage_wait_s": 9.0, "prefill_wait_s": 9.0,
                                  "first_burst_s": 9.0}},   # never a token
    ],
    "engine_before": _summary(
        10, {"idle": 5.0, "decode.wait": 10.0, "admit": 1.0, "sched": 0.5},
        {"steps": 320, "row_steps": 400, "live_token_steps": 1000,
         "table_token_steps": 4000},
        {"pushes": 10, "tokens": 1000, "submit_to_commit_s": 0.05,
         "queue_s": 0.01, "d2h_s": 0.01, "pool_copy_s": 0.01,
         "alloc_s": 0.005, "commit_s": 0.005, "wire_s": 0.0},
        {"tokens": 2000, "desc_s": 0.01, "pool_copy_s": 0.02,
         "upload_s": 0.01, "sync_s": 0.03}),
    "engine_after": _summary(
        20, {"idle": 6.0, "decode.wait": 23.0, "admit": 1.25, "sched": 0.75,
             # work: counted.  One phase until the gather and push_begin
             # were cut out of it: the three sum to what it was
             "kv.push_gather": 0.25, "kv.push_begin": 0.125,
             "kv.push_submit": 0.125,
             "kv.load": 0.5, "kv.push_wait": 0.3, "probe": 0.1},   # waits
        {"steps": 640, "row_steps": 880, "live_token_steps": 3000,
         "table_token_steps": 12000},
        {"pushes": 60, "tokens": 4000, "submit_to_commit_s": 0.5,
         "queue_s": 0.2, "d2h_s": 0.1, "pool_copy_s": 0.06,
         "alloc_s": 0.04, "commit_s": 0.05, "wire_s": 0.01},
        {"tokens": 10000, "desc_s": 0.05, "pool_copy_s": 0.1,
         "upload_s": 0.05, "sync_s": 0.11}),
}
# a program that has none of it: the parent commit's rows and summaries
BARE = {
    "reader": _reader,
    "server_rows": [{"ttft_s": 2.0, "waterfall": {"queue_s": 0.1}}],
    "engine_before": {"steps": 10, "compiles": 0},
    "engine_after": {"steps": 20, "compiles": 0},
}
WANT = {
    "stage_wait_ms": 375.0, "prefill_wait_ms": 500.0,
    "first_burst_wait_ms": 1000.0,
    "decode_rows_counted": 480 / 320,
    "decode_pad_pct": 75.0,
    "host_ms_per_step": 1e3 * (0.25 + 0.25 + 0.5) / 10,
    "push_ms_per_ktok": 1e3 * 0.5 / 4.0,
    # the four parts of it, on its own basis (the last scrape) ...
    "push_queue_ms_per_ktok": 1e3 * 0.2 / 4.0,
    "push_d2h_ms_per_ktok": 1e3 * 0.1 / 4.0,
    "push_copy_ms_per_ktok": 1e3 * 0.06 / 4.0,
    "push_store_ms_per_ktok": 1e3 * (0.04 + 0.05 + 0.01) / 4.0,
    # ... and the window's gains: two phases over 50 pushes, a load's stages
    # over 8,000 tokens
    "push_gather_ms_per_push": 1e3 * 0.25 / 50,
    "push_begin_ms_per_push": 1e3 * 0.125 / 50,
    "load_host_ms_per_ktok": 1e3 * (0.04 + 0.08 + 0.04) / 8.0,
    "load_sync_ms_per_ktok": 1e3 * 0.08 / 8.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_new_reader_on_a_synthetic_ctx(name):
    assert _reader(name).read(FULL) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_new_reader_finds_nothing_in_a_program_without_its_fields(name):
    assert _reader(name).read(BARE) is None
    assert _reader(name).read(dict(BARE, engine_before=None,
                                   engine_after=None, server_rows=[])) is None
    # the parent commit's summaries: phases and store totals, but not the
    # two phases, the queue or a load's stages
    old = {"phase_s": {"kv.push_submit": 1.0}, "store": {
        "push": {"pushes": 9, "tokens": 144, "submit_to_commit_s": 0.5,
                 "d2h_s": 0.1, "pool_copy_s": 0.1, "alloc_s": 0.1,
                 "commit_s": 0.1, "wire_s": 0.0},
        "load": {"loads": 2, "tokens": 64, "fetch_s": 0.1, "scatter_s": 0.1}}}
    if name.startswith(("push_", "load_")) and name != "push_ms_per_ktok":
        assert _reader(name).read(dict(
            BARE, engine_before=dict(BARE["engine_before"]),
            engine_after=dict(BARE["engine_after"], **old))) is None


def test_every_new_metric_has_its_file_its_reader_and_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    new = ["sched.stage_wait_ms", "sched.prefill_wait_ms",
           "sched.first_burst_wait_ms", "engine.decode_rows_counted",
           "engine.decode_rows_counted.batch", "engine.decode_pad_pct",
           "engine.decode_pad_pct.batch", "engine.host_ms_per_step",
           "engine.host_ms_per_step.batch", "kv.push_ms_per_ktok",
           "kv.push_queue_ms_per_ktok", "kv.push_d2h_ms_per_ktok",
           "kv.push_copy_ms_per_ktok", "kv.push_store_ms_per_ktok",
           "engine.push_gather_ms_per_push", "engine.push_begin_ms_per_push",
           "kv.load_host_ms_per_ktok", "kv.load_sync_ms_per_ktok"]
    # the cells in which a window loads from the store: the two list them
    listed = {n for n in new if n.startswith("kv.load_")}
    for name in new:
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        reader = spec.pop("reader")
        assert spec == entries[name]
        assert ("workloads" in spec) == (name in listed)
        assert callable(_reader(reader).read)
        assert reader in WANT
    twins = [n for n in new if n.endswith(".batch")]
    for n in twins:       # twins share a reader and move the other metric
        assert entries[n]["moves"] == "out_tok_per_s"
        assert entries[n[:-6]]["moves"] == "tpot_p50_ms"
