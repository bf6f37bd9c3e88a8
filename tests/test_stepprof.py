"""The engine/device attribution plane (`engine/stepprof.py`).

Unit half: record shape and sampling math with injected clock/block/mem
(no device, no wall clock), the per-function retrace counter driven by a
deliberately shape-polymorphic jit, ring overflow + ``?limit=``
semantics, speculation/store-stage delta attachment against fake
schedulers.

Live half: a serving stack proves the ledger ``step_ids`` ↔
``/debug/engine`` join end to end, and — with a store attached — that
ONE stitched Perfetto export shows ``http.request`` → ``engine.step`` →
``kv.load_pages`` plus the device sub-track under a single trace id
(the PR's acceptance criterion, loaded and asserted from the JSON).
"""

import json
import http.client
import os
import signal
import socket
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from infinistore_tpu.utils.metrics import MetricsRegistry, \
    parse_prometheus_text


def _prof(**kw):
    from infinistore_tpu.engine.stepprof import StepProfiler

    kw.setdefault("metrics", MetricsRegistry())
    return StepProfiler(**kw)


class _Clock:
    """Scripted clock: returns the next stamp per call (appends a big
    tail so stray extra reads fail loudly in assertions, not IndexError)."""

    def __init__(self, stamps):
        self.stamps = list(stamps)
        self.calls = 0

    def __call__(self):
        self.calls += 1
        if self.stamps:
            return self.stamps.pop(0)
        return 1e9


# ---------------------------------------------------------------------------
# record shape + sampling math (pure, injected everything)
# ---------------------------------------------------------------------------


def test_record_shape_with_injected_clock():
    from infinistore_tpu.engine import stepprof

    # calls: t0 (begin), t1 (end), tb (the probe phase's start, before the
    # block), after block, and the switch that ends the probe phase
    clock = _Clock([10.0, 11.0, 11.0, 11.25, 11.25])
    prof = _prof(sample=1, clock=clock, block=lambda x: None,
                 sentinel=lambda: object(),
                 mem_reader=lambda: {"live_bytes": 10, "peak_bytes": 20})
    with prof.step(kind_hint=None) as rec:
        stepprof.note_dispatch("decode")
        stepprof.note_dispatch("decode")
        stepprof.note_dispatch("prefill")
        stepprof.note_tokens(16)
    assert rec["step"] == 1 and rec["sampled"] is True
    assert rec["dur_s"] == pytest.approx(1.0)
    assert rec["host_stall_s"] == pytest.approx(0.25)
    assert rec["dispatches"] == {"decode": 2, "prefill": 1}
    assert rec["tokens"] == 16
    assert rec["kind"] == "mixed"  # prefill + decode in one step
    assert rec["mem"] == {"live_bytes": 10, "peak_bytes": 20}
    s = prof.summary()
    assert s["steps"] == 1 and s["dispatch_total"] == 3
    assert s["host_stall_frac"] == pytest.approx(0.25 / 1.0)
    # hooks outside a step are no-ops, not errors
    stepprof.note_dispatch("decode")
    stepprof.note_tokens(1)
    assert stepprof.current_step() is None


def test_kind_classification():
    prof = _prof(sample=10**9)
    from infinistore_tpu.engine import stepprof

    for notes, kind in (
        ((), "idle"),
        ((("prefill", 1),), "prefill"),
        ((("decode", 1),), "decode"),
        ((("spec_round", 1),), "spec"),
        ((("spec_round", 1), ("decode", 1)), "mixed"),
    ):
        with prof.step() as rec:
            for k, n in notes:
                stepprof.note_dispatch(k, n)
        assert rec["kind"] == kind, (notes, rec)


def test_sampling_math_and_env_knobs(monkeypatch):
    from infinistore_tpu.engine.stepprof import StepProfiler

    prof = _prof(sample=4, block=lambda x: None, sentinel=lambda: object(),
                 mem_reader=lambda: None)
    sampled = []
    for _ in range(8):
        with prof.step() as rec:
            pass
        sampled.append(rec["sampled"])
    assert sampled == [False, False, False, True] * 2
    assert prof.summary()["sampled_steps"] == 2
    # env defaults honored at construction
    monkeypatch.setenv("ISTPU_STEPPROF_SAMPLE", "7")
    monkeypatch.setenv("ISTPU_STEPPROF_RING", "3")
    p2 = StepProfiler(metrics=MetricsRegistry())
    assert p2.sample == 7 and p2._ring.maxlen == 3
    # the kill switch: disabled profilers yield None and report so
    monkeypatch.setenv("ISTPU_STEPPROF", "0")
    p3 = StepProfiler(metrics=MetricsRegistry())
    assert not p3.enabled
    with p3.step() as rec:
        assert rec is None
    assert p3.snapshot() == {"enabled": False}


def test_retrace_counter_via_shape_polymorphic_jit():
    """A deliberately shape-polymorphic jit must count one trace per
    distinct shape — per FUNCTION NAME, on the step record AND the
    labeled metric family."""
    import jax.numpy as jnp

    from infinistore_tpu.engine.engine import _shared_jit

    # unique function object => its own _JIT_CACHE entry and trace count
    def polyprobe(params, tokens=None, cfg=None):
        return tokens * 2

    reg = MetricsRegistry()
    prof = _prof(metrics=reg, sample=10**9)
    f = _shared_jit(polyprobe, {"cfg": 1})
    with prof.step() as rec:
        f(None, tokens=jnp.ones((4,)))   # trace 1 (first compile)
        f(None, tokens=jnp.ones((4,)))   # cache hit: no trace
        f(None, tokens=jnp.ones((8,)))   # shape change: retrace
    assert rec["retraces"].get("polyprobe") == 2, rec["retraces"]
    text = reg.to_prometheus_text()
    assert 'istpu_engine_retraces_total{fn="polyprobe"} 2' in text
    assert prof.summary()["retraces"].get("polyprobe") == 2


def test_ring_overflow_and_limit():
    prof = _prof(sample=10**9, ring=4)
    for _ in range(10):
        with prof.step():
            pass
    snap = prof.snapshot()
    assert snap["summary"]["steps"] == 10
    assert snap["returned"] == 4  # ring kept the newest 4
    assert [r["step"] for r in snap["records"]] == [7, 8, 9, 10]
    snap2 = prof.snapshot(limit=2)
    assert [r["step"] for r in snap2["records"]] == [9, 10]
    assert prof.snapshot(limit=0)["records"] == []  # summary-only poll


def test_spec_and_store_stage_attribution_deltas():
    """Speculation counters and transfer stage dicts attach as PER-STEP
    deltas (fake scheduler: no device needed)."""
    spec = SimpleNamespace(rounds=10, proposed=40, accepted=30)
    transfer = SimpleNamespace(
        push_totals={"pushes": 3, "d2h_s": 1.0, "zero_copy_bands": 9},
        load_totals={"loads": 1, "fetch_s": 0.5, "scatter_s": 0.25})
    sched = SimpleNamespace(
        spec=spec, engine=SimpleNamespace(transfer=transfer, cache=None),
        active=[1, 2], _prefilling=[], pending=[3],
    )
    prof = _prof(sample=10**9)
    with prof.step(sched) as rec:
        spec.rounds += 2
        spec.proposed += 8
        spec.accepted += 5
        # the transfer replaces its running totals whole on every update
        transfer.push_totals = {"pushes": 4, "d2h_s": 1.1,
                                "zero_copy_bands": 13}
        transfer.load_totals = {"loads": 2, "fetch_s": 0.7,
                                "scatter_s": 0.3}
    assert rec["batch"] == {"active": 2, "prefilling": 0, "pending": 1}
    assert rec["spec"] == {"rounds": 2, "proposed": 8, "accepted": 5}
    # the step's share is the totals' DELTA, not the last push's stages
    assert rec["store"]["push"] == {"pushes": 1, "d2h_s": 0.1,
                                    "zero_copy_bands": 4}
    assert rec["store"]["load"]["fetch_s"] == pytest.approx(0.2)
    assert prof.summary()["store"]["push"]["pushes"] == 4
    # a step that moved nothing attaches neither block
    with prof.step(sched) as rec2:
        pass
    assert "spec" not in rec2 and "store" not in rec2


def _serving(tiny_engine_parts):
    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.serve import ServingServer

    cfg, params, make_pc = tiny_engine_parts
    eng = InferenceEngine(params, cfg, make_pc())
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=2, model_id="prof-capture")
    srv.start()
    return srv


def _wait_capture_over(tmp_path):
    """Until the endpoint's capture has stopped AND written its file: the
    export runs under the lock ``start_trace`` takes, so a capture of our
    own can start only once it is whole."""
    import jax

    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            jax.profiler.start_trace(str(tmp_path / "after"))
        except RuntimeError:
            time.sleep(0.1)
            continue
        jax.profiler.stop_trace()
        return
    pytest.fail("the endpoint's capture never stopped")


def test_profile_endpoint_captures_and_refuses_a_second(tiny_engine_parts,
                                                        tmp_path):
    """``POST /debug/profile`` (the operators' capture, in the place of the
    old ``device_trace`` helper): 200 and an ``.xplane.pb`` whose host plane
    holds the engine thread's ``istpu.*`` phases; 409 while it runs; 400 for
    a body without ``seconds`` and ``dir``."""
    import glob

    srv = _serving(tiny_engine_parts)
    try:
        _post(srv.port, {"prompt": [1, 2, 3], "max_tokens": 4,
                         "temperature": 0})          # compile first
        body = {"seconds": 3.0, "dir": str(tmp_path / "ours")}
        status, out = _post(srv.port, body, path="/debug/profile")
        assert status == 200 and out["dir"] == str(tmp_path / "ours"), out
        status, out = _post(srv.port, body, path="/debug/profile")
        assert status == 409, out
        _post(srv.port, {"prompt": [4, 5, 6], "max_tokens": 4,
                         "temperature": 0})
        for bad in ({}, {"seconds": -1, "dir": "x"}, {"seconds": 1}):
            status, _out = _post(srv.port, bad, path="/debug/profile")
            assert status == 400, bad
        # a dir that cannot be made is the caller's mistake, not a conflict
        status, out = _post(srv.port, {"seconds": 1, "dir": "/dev/null/x"},
                            path="/debug/profile")
        assert status == 400 and "dir" in out["error"], out
        _wait_capture_over(tmp_path)
        from jax.profiler import ProfileData

        pat = os.path.join(str(tmp_path / "ours"), "plugins", "profile", "*",
                           "*.xplane.pb")
        data = ProfileData.from_file(glob.glob(pat)[0])
        host = {e.name for p in data.planes if p.name.startswith("/host:")
                for ln in p.lines for e in ln.events}
        assert "istpu.decode.wait" in host, sorted(
            n for n in host if n.startswith("istpu."))
    finally:
        srv.close()


def test_profile_endpoint_refuses_while_another_capture_runs(
        tiny_engine_parts, tmp_path):
    """One profile per process: a capture started by anyone else (the
    benchmark's own side thread calls ``jax.profiler.start_trace`` itself)
    makes the endpoint answer 409, and it works again afterwards."""
    import jax

    srv = _serving(tiny_engine_parts)
    try:
        jax.profiler.start_trace(str(tmp_path / "theirs"))
        try:
            status, out = _post(
                srv.port, {"seconds": 0.2, "dir": str(tmp_path / "ours")},
                path="/debug/profile")
            assert status == 409, out
        finally:
            jax.profiler.stop_trace()
        status, out = _post(
            srv.port, {"seconds": 0.2, "dir": str(tmp_path / "ours")},
            path="/debug/profile")
        assert status == 200, out
        _wait_capture_over(tmp_path)     # leave no capture running behind
    finally:
        srv.close()


def test_transfer_records_load_stages(tmp_path):
    """kv.transfer keeps a ``last_load_stages`` twin of
    ``last_push_stages`` (the step records attach both)."""
    from infinistore_tpu.kv.transfer import KVTransferEngine

    assert hasattr(KVTransferEngine, "_load_pages_banded")
    # shape-only check (the live halves below exercise real loads):
    # a fresh engine starts with empty stage dicts
    import inspect

    # every load path ends in ``_landed``, which writes the record
    assert "_landed" in inspect.getsource(KVTransferEngine._load_pages_banded)
    assert "last_load_stages" in inspect.getsource(KVTransferEngine._landed)


# ---------------------------------------------------------------------------
# live halves
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _post(port, body, timeout=180, path="/v1/completions"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data)


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


@pytest.fixture(scope="module")
def tiny_engine_parts():
    import jax

    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params

    params = init_params(TINY, jax.random.PRNGKey(0))

    def make_pc(n_blocks=64):
        return PagedCacheConfig(
            n_layers=TINY.n_layers, n_kv_heads=TINY.n_kv_heads,
            head_dim=TINY.head_dim, n_blocks=n_blocks, block_tokens=4,
        )

    return TINY, params, make_pc


def test_engine_hooks_count_real_dispatches(tiny_engine_parts):
    from infinistore_tpu.engine import InferenceEngine

    cfg, params, make_pc = tiny_engine_parts
    eng = InferenceEngine(params, cfg, make_pc())
    eng.decode_chunk = 4
    prof = _prof(sample=1, sentinel=lambda: eng.cache)
    with prof.step() as rec:
        st = eng.prefill(list(range(1, 10)))
    assert rec["dispatches"].get("prefill", 0) >= 1
    with prof.step() as rec2:
        eng.decode(st, 8)  # two chunks of 4
    assert rec2["dispatches"].get("decode") == 2
    assert rec2["tokens"] == 8
    assert rec2["kind"] == "decode"
    assert rec2["host_stall_s"] >= 0.0  # real block on the real cache
    assert rec2.get("mem", {}).get("live_bytes", 0) > 0  # CPU fallback
    eng.release(st)


def test_ledger_step_ids_join_debug_engine_live(tiny_engine_parts,
                                                monkeypatch):
    """End to end against a live serve: every /debug/requests row's
    step_ids resolve to /debug/engine records, and the istpu_engine_*
    families ride the serving /metrics."""
    monkeypatch.setenv("ISTPU_STEPPROF_SAMPLE", "1")
    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.serve import ServingServer

    cfg, params, make_pc = tiny_engine_parts
    eng = InferenceEngine(params, cfg, make_pc())
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=2, model_id="prof-serve")
    srv.start()
    try:
        for i in range(3):
            status, body = _post(srv.port, {
                "prompt": list(range(1 + i, 10 + i)), "max_tokens": 6,
                "temperature": 0,
            })
            assert status == 200, body
        _s, data = _get(srv.port, "/debug/requests")
        recs = json.loads(data)["records"]
        assert len(recs) == 3
        _s, data = _get(srv.port, "/debug/engine")
        payload = json.loads(data)
        assert payload["enabled"] and payload["summary"]["steps"] >= 1
        # records may include an {"step": N, "in_progress": true} stub
        # for the step executing right now — that is what makes this
        # join race-free (a request retires MID-step, so its ledger row
        # can name a step whose full record lands only at step end)
        step_ids = {r["step"] for r in payload["records"]}
        for rec in recs:
            assert rec["step_ids"], rec  # every request rode >= 1 step
            assert set(rec["step_ids"]) <= step_ids
        # the engine records carry dispatch counts and the sampled probe
        assert any(r.get("dispatches") for r in payload["records"])
        assert any("host_stall_s" in r for r in payload["records"])
        # metric families on the serving exposition
        _s, data = _get(srv.port, "/metrics")
        metrics = parse_prometheus_text(data.decode())
        names = {name for name, _l in metrics}
        assert "istpu_engine_dispatches_total" in names
        assert "istpu_engine_step_seconds_count" in names
        assert "istpu_engine_host_stall_seconds_count" in names
        # ?limit= caps the tail
        _s, data = _get(srv.port, "/debug/engine?limit=1")
        assert json.loads(data)["returned"] == 1
    finally:
        srv.close()


@pytest.fixture(scope="module")
def live_store():
    port, mport = _free_port(), _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 25
    while True:
        if proc.poll() is not None:
            pytest.fail("store server failed to start")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            break
        except OSError:
            if time.time() >= deadline:
                proc.kill()
                pytest.fail("store server did not come up")
            time.sleep(0.1)
    yield port
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_single_stitched_trace_http_to_device(tiny_engine_parts, live_store,
                                              monkeypatch):
    """THE acceptance criterion: one stitched Perfetto export from a live
    serve request shows http.request → engine.step → kv.load_pages AND
    the device sub-track under ONE trace id, and the request's ledger
    row joins the engine records by step id."""
    monkeypatch.setenv("ISTPU_STEPPROF_SAMPLE", "1")  # every step probed
    import infinistore_tpu as ist
    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.serve import ServingServer

    cfg, params, make_pc = tiny_engine_parts
    prompt = list(range(1, 17))  # 4 complete chunks at block_tokens=4

    # a PRODUCER engine (same model id) seeds the store with the prefix
    # the serving engine has never seen locally — its load is a real
    # store hit (kv.load_pages), not a local prefix-cache hit
    prod_conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=live_store,
        connection_type=ist.TYPE_SHM, op_timeout_s=30.0,
        log_level="warning"))
    prod_conn.connect()
    prod = InferenceEngine(params, cfg, make_pc(), conn=prod_conn,
                           model_id="prof-stitch", kv_quant=None)
    prod.release(prod.prefill(prompt))
    prod.store_flush()

    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=live_store,
        connection_type=ist.TYPE_SHM, op_timeout_s=30.0,
        log_level="warning"))
    conn.connect()
    eng = InferenceEngine(params, cfg, make_pc(), conn=conn,
                          model_id="prof-stitch", kv_quant=None)
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=2, model_id="prof-stitch")
    srv.start()
    try:
        status, body = _post(srv.port, {
            "prompt": prompt, "max_tokens": 6, "temperature": 0,
        })
        assert status == 200, body

        _s, data = _get(srv.port, "/debug/requests")
        rec = json.loads(data)["records"][-1]
        assert rec["store"]["store_chunks"] >= 1, rec  # the store hit
        trace_id = rec["trace_id"]
        assert trace_id

        _s, data = _get(srv.port, "/debug/traces")
        export = json.loads(data)  # Perfetto-loadable Chrome JSON
        events = export["traceEvents"]
        mine = [e for e in events if e.get("ph") == "X"
                and e.get("args", {}).get("trace_id") == trace_id]
        names = {e["name"] for e in mine}
        # the acceptance chain, all under ONE trace id
        assert {"http.request", "engine.step", "kv.load_pages"} <= names, \
            sorted(names)
        # ...and the device sub-track: a thread_name metadata row names
        # a track "device", and a span of THIS trace rides it
        meta = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
                if e.get("ph") == "M" and e["name"] == "thread_name"}
        dev_tracks = {k for k, v in meta.items() if v == "device"}
        assert dev_tracks, meta
        assert any((e["pid"], e["tid"]) in dev_tracks for e in mine), \
            sorted(names)

        # the ledger ↔ engine join holds on the same request
        _s, data = _get(srv.port, "/debug/engine")
        step_ids = {r["step"] for r in json.loads(data)["records"]}
        assert rec["step_ids"] and set(rec["step_ids"]) <= step_ids
        # and the store hop's stage record rode a step record
        stores = [r.get("store") for r in json.loads(data)["records"]
                  if r.get("store")]
        assert any("load" in s for s in stores), stores
    finally:
        srv.close()
        conn.close()
        prod_conn.close()


# ---- round 11: blocking-sync accounting + dispatch economy ----


def test_note_sync_counts_and_summary_economy():
    """note_sync lands on the active record, aggregates into lifetime
    totals and the istpu_engine_syncs_total family, and the summary
    derives dispatches_per_token from dispatches over tokens."""
    from infinistore_tpu.engine import stepprof as sp

    prof = _prof(sample=1000)
    with prof.step(kind_hint="spec") as rec:
        sp.note_dispatch("spec_round")
        sp.note_tokens(24)
        sp.note_sync("spec_tokens")
    assert rec["syncs"] == {"spec_tokens": 1}
    with prof.step(kind_hint="decode") as rec2:
        sp.note_dispatch("decode", 3)
        sp.note_tokens(96)
        sp.note_sync("decode_tokens", 3)
    s = prof.summary()
    assert s["syncs"] == {"spec_tokens": 1, "decode_tokens": 3}
    assert s["syncs_total"] == 4
    assert s["dispatches_per_token"] == round(4 / 120, 4)
    text = prof.metrics.to_prometheus_text()
    assert 'istpu_engine_syncs_total{kind="spec_tokens"} 1' in text
    assert 'istpu_engine_syncs_total{kind="decode_tokens"} 3' in text
    # no sync outside an active record: silently dropped, no crash
    sp.note_sync("spec_tokens")
    assert prof.summary()["syncs_total"] == 4


def test_summary_spec_accept_per_dispatch():
    """The lifetime spec aggregates fold per-step deltas of the
    scheduler's speculator counters; accepted-per-dispatch divides by
    the fused-dispatch count (the r4 '0.53x at 0.938 acceptance'
    explainer)."""

    class _Spec:
        rounds = proposed = accepted = 0

    class _Sched:
        spec = _Spec()
        active = ()
        _prefilling = ()
        pending = ()
        engine = None

    sched = _Sched()
    prof = _prof(sample=1000)
    with prof.step(sched):
        from infinistore_tpu.engine import stepprof as sp

        sp.note_dispatch("spec_round", 2)
        _Spec.rounds, _Spec.proposed, _Spec.accepted = 16, 64, 38
    s = prof.summary()
    assert s.get("spec_accept_per_dispatch") == round(38 / 2, 3)


def test_note_prefill_budget_sums_per_step_and_lifetime():
    """The scheduler's per-step prefill budget lands on the active record
    under ``prefill`` and sums into ``summary()['prefill']``; a step with
    no budget (an idle engine admits a whole wave) carries no block, and a
    call outside a step is dropped."""
    from infinistore_tpu.engine import stepprof as sp

    prof = _prof(sample=1000)
    with prof.step(kind_hint="mixed") as rec:
        sp.note_prefill_budget(7 * 512, 5 * 512)
    assert rec["prefill"] == dict(dict.fromkeys(sp.PREFILL_COUNTS, 0),
                                  granted_tokens=3584, spent_tokens=2560)
    with prof.step(kind_hint="decode") as rec2:
        sp.note_dispatch("decode")
    assert "prefill" not in rec2
    with prof.step(kind_hint="mixed"):
        sp.note_prefill_budget(512, 512)
    sp.note_prefill_budget(512, 512)
    assert prof.summary()["prefill"] == dict(
        dict.fromkeys(sp.PREFILL_COUNTS, 0),
        granted_tokens=4096, spent_tokens=3072)
    assert prof.tail()[0]["prefill"]["spent_tokens"] == 2560


def test_note_push_wait_sums_per_step_and_lifetime():
    """The two causes of ``kv.push_wait`` and the chunks with and without an
    output head land beside the step's budget under ``prefill`` and sum into
    ``summary()['prefill']``: a burst that finishes
    two prompts reads ``settled_prompts / settle_waits`` above 1; a call
    outside a step is dropped."""
    from infinistore_tpu.engine import stepprof as sp

    prof = _prof(sample=1000)
    with prof.step(kind_hint="mixed") as rec:
        sp.note_prefill_budget(4 * 512, 3 * 512)
        sp.note_push_wait(push_queue_full_waits=1, push_queue_full_s=0.25)
        sp.note_push_wait(settle_waits=1)                # the step's one wait
        sp.note_push_wait(settled_prompts=1, settle_wait_s=0.5)
        sp.note_push_wait(settled_prompts=1, settle_wait_s=0.125)
        for head in (False, False, True):   # a prompt of three chunks
            sp.note_prefill_chunk(head=head)
        # two of them launched at the thread's waits (note_wait_work)
        sp.note_wait_work(taken_in_dispatch=2)
        sp.note_wait_work(started_dispatch=1)
        sp.note_wait_work(chunks_dispatch=1)
        sp.note_wait_work(taken_in_settle=1)
        sp.note_wait_work(chunks_settle=1)
        sp.note_wait_work(collect_lag_s=0.25)
    assert rec["prefill"] == {
        "granted_tokens": 2048, "spent_tokens": 1536, "settle_waits": 1,
        "settled_prompts": 2, "settle_wait_s": 0.625,
        "push_queue_full_waits": 1, "push_queue_full_s": 0.25,
        "chunks": 3, "head_chunks": 1, "attn_kernel_chunks": 0,
        "taken_in_dispatch": 2, "started_dispatch": 1, "chunks_dispatch": 1,
        "taken_in_settle": 1, "started_settle": 0, "chunks_settle": 1,
        "collect_lag_s": 0.25}
    with prof.step(kind_hint="prefill") as wave:    # a blocking prefill: no budget
        sp.note_push_wait(settle_waits=1)
        sp.note_push_wait(settled_prompts=1, settle_wait_s=0.25)
    assert wave["prefill"]["granted_tokens"] == 0
    assert wave["prefill"]["settled_prompts"] == 1
    sp.note_push_wait(settle_waits=1, settled_prompts=1, settle_wait_s=9.0)
    sp.note_prefill_chunk(head=True)
    tot = prof.summary()["prefill"]
    assert (tot["chunks"], tot["head_chunks"]) == (3, 1)
    assert sorted(tot) == sorted(sp.PREFILL_COUNTS)
    assert (tot["settle_waits"], tot["settled_prompts"]) == (2, 3)
    assert tot["settle_wait_s"] == 0.875 and tot["push_queue_full_s"] == 0.25
    assert tot["settled_prompts"] / tot["settle_waits"] > 1
