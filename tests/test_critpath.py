"""Fleet-wide latency attribution (`critpath.py`, `trace_diff.py`,
the mesh-stitched `/debug/trace/{id}` export).

Unit half (pure): the canonical stage decomposition (disjoint slices
summing to admission+e2e with an explicit ``unattributed`` remainder),
router-grain mesh-row merging with role remaps, the aggregate shape,
``scripts/trace_diff.py``'s regression naming over every capture shape
it accepts, the ``stage_budget`` watchdog rule, the racing ring-drop
counter, and the stitch-gather outcome counter.

Live half: a real store node (subprocess) under an in-process
2-prefill + 2-decode fleet — THE tier-1 mesh walk (a client-minted
trace id rides ``X-Istpu-Trace`` through router, workers, and store;
``GET /debug/trace/{id}`` returns ONE stitched timeline whose process
rows carry clock-offset error bounds; ``GET /debug/critpath`` merged
stage sums reproduce client-measured TTFT within 10% with the
remainder named ``unattributed``) and THE chaos walk (a FaultInjector
store-side ``GET_DESC`` delay is NAMED ``store_transfer`` by
``trace_diff``, not eyeballed from a timeline).
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from infinistore_tpu import critpath
from infinistore_tpu.utils import tracing
from infinistore_tpu.utils import trace_stitch
from infinistore_tpu.utils import metrics as m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_diff():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import trace_diff
    finally:
        sys.path.pop(0)
    return trace_diff


# ---------------------------------------------------------------------------
# the canonical decomposition (pure)
# ---------------------------------------------------------------------------


def _rec(e2e=0.15, **over):
    rec = {
        "trace_id": "tr-1", "req_id": 7, "lane": "gold",
        "outcome": "done", "admission_wait_s": 0.010,
        "ttft_s": 0.100, "e2e_s": e2e,
        "token_stamps": [[0.105, 1]],
        "waterfall": {"queue_s": 0.020, "store_s": 0.030,
                      "prefill_s": 0.050, "decode_s": 0.040,
                      "stream_s": 0.010},
    }
    rec.update(over)
    return rec


def test_decompose_stages_sum_to_admission_plus_e2e():
    stages = critpath.decompose(_rec())
    assert set(stages) == set(critpath.STAGES)
    assert stages["admission_wait"] == pytest.approx(0.010)
    assert stages["queue_wait"] == pytest.approx(0.020)
    assert stages["store_transfer"] == pytest.approx(0.030)
    assert stages["prefill_compute"] == pytest.approx(0.050)
    # first-token delivery gap: first chunk stamp minus ttft
    assert stages["first_token"] == pytest.approx(0.005)
    assert stages["per_token_decode"] == pytest.approx(0.045)
    # the waterfall covers e2e exactly -> nothing unattributed
    assert stages["unattributed"] == pytest.approx(0.0)
    assert sum(stages.values()) == pytest.approx(0.010 + 0.15)


def test_decompose_reports_unclaimed_wall_clock_explicitly():
    # e2e larger than the waterfall covers: the gap is NAMED, not
    # silently absorbed into a compute stage
    stages = critpath.decompose(_rec(e2e=0.20))
    assert stages["unattributed"] == pytest.approx(0.05)
    assert sum(stages.values()) == pytest.approx(0.010 + 0.20)
    # degenerate record (failed before any stamp): all zeros, no raise
    empty = critpath.decompose({"outcome": "error"})
    assert sum(empty.values()) == 0.0


def test_merge_mesh_rows_remaps_roles_and_names_remainder():
    prefill_row = {
        "trace_id": "tr-m", "lane": None, "role": "prefill",
        "stages": {"admission_wait": 0.002, "queue_wait": 0.010,
                   "prefill_compute": 0.050, "kv_flush": 0.004,
                   "store_transfer": 0.006, "first_token": 0.003,
                   "per_token_decode": 0.002},
    }
    decode_row = {
        "trace_id": "tr-m", "lane": "-", "role": "decode",
        "stages": {"admission_wait": 0.001, "queue_wait": 0.002,
                   "prefill_compute": 0.020, "first_token": 0.004,
                   "store_transfer": 0.012, "per_token_decode": 0.030},
    }
    note = {"ttft_s": 0.150, "e2e_s": 0.200, "lane": "tenant-a"}
    merged = critpath.merge_mesh_rows([prefill_row, decode_row],
                                      note=note)
    st = merged["stages"]
    # the prefill worker's throwaway decode folds into prefill_compute
    assert st["prefill_compute"] == pytest.approx(0.055)
    # the decode worker's own admission/queue is the fleet decode_queue
    assert st["decode_queue"] == pytest.approx(0.003)
    # its adoption+compute-to-first-token is the fleet first_token
    assert st["first_token"] == pytest.approx(0.024)
    assert st["store_transfer"] == pytest.approx(0.018)
    assert st["per_token_decode"] == pytest.approx(0.030)
    # router-measured TTFT minus the claimed stage sum is the named
    # remainder (0.150 - 0.116)
    assert st["unattributed"] == pytest.approx(0.034)
    assert merged["ttft_s"] == pytest.approx(0.150)
    assert merged["lane"] == "tenant-a"
    assert merged["roles"] == ["prefill", "decode"]
    claimed = sum(st[s] for s in critpath.TTFT_STAGES)
    assert claimed == pytest.approx(0.150)


def test_aggregate_shape_dominant_and_worst():
    def row(tid, ttft, queue):
        stages = {s: 0.0 for s in critpath.STAGES}
        stages["queue_wait"] = queue
        stages["prefill_compute"] = ttft - queue
        return {"trace_id": tid, "ttft_s": ttft, "stages": stages}

    rows = [row("a", 0.10, 0.08), row("b", 0.05, 0.04),
            row("c", 0.30, 0.29)]
    agg = critpath.aggregate(rows)
    assert agg["count"] == 3
    assert agg["ttft_p99_ms"] == pytest.approx(300.0)
    assert agg["dominant_stage"] == "queue_wait"
    assert set(agg["stage_share_p99"]) == set(critpath.TTFT_STAGES)
    assert agg["stage_share_p99"]["queue_wait"] == pytest.approx(
        290.0 / 300.0, rel=1e-3)
    # worst offenders: slowest first, each naming its own dominant stage
    assert [w["trace_id"] for w in agg["worst"]] == ["c", "a", "b"]
    assert agg["worst"][0]["dominant_stage"] == "queue_wait"
    # empty ring answers a well-formed zero shape
    assert critpath.aggregate([])["count"] == 0


def test_stage_ledger_fold_annotate_and_snapshot():
    led = critpath.StageLedger(capacity=4, role="prefill")
    row = led.fold(_rec())
    assert row["ttft_s"] == pytest.approx(0.110)  # admission + ttft
    # post-retirement kv_flush annotation lands by trace id and bumps
    # the client-facing TTFT (the flush barrier is on the TTFT path)
    assert led.annotate("tr-1", "kv_flush", 0.020)
    assert not led.annotate("nope", "kv_flush", 0.020)
    got = led.rows()[-1]
    assert got["stages"]["kv_flush"] == pytest.approx(0.020)
    assert got["ttft_s"] == pytest.approx(0.130)
    snap = led.snapshot()
    assert snap["enabled"] and snap["role"] == "prefill"
    assert snap["overall"]["count"] == 1
    assert "gold" in snap["lanes"]
    # the ring is bounded: overflow drops the oldest row's trace join
    for i in range(6):
        led.fold(_rec(trace_id=f"tr-x{i}"))
    assert len(led.rows()) == 4
    assert not led.annotate("tr-1", "kv_flush", 0.1)


# ---------------------------------------------------------------------------
# automated regression naming (scripts/trace_diff.py)
# ---------------------------------------------------------------------------


def test_trace_diff_stage_names_match_package():
    td = _load_trace_diff()
    assert tuple(td.STAGES) == tuple(critpath.STAGES)


def test_trace_diff_load_stages_accepts_every_capture_shape():
    td = _load_trace_diff()
    per_stage = {s: 1.0 for s in td.STAGES}
    per_stage["store_transfer"] = 42.0
    live = {"overall": {"stage_p99_ms": per_stage}}
    bench = {"critpath": {"overall": {"stage_p99_ms": per_stage}}}
    flat_mirrors = {f"stage_p99_{s}_ms": v for s, v in per_stage.items()}
    flat = dict(per_stage)
    for obj in (live, bench, flat_mirrors, flat):
        got = td.load_stages(obj, "p99")
        assert got["store_transfer"] == 42.0
        assert set(got) == set(td.STAGES)
    with pytest.raises(ValueError):
        td.load_stages({"unrelated": 1}, "p99")


def test_trace_diff_names_dominant_regressed_stage():
    td = _load_trace_diff()
    base = {s: 10.0 for s in td.STAGES}
    cand = dict(base, store_transfer=60.0, queue_wait=14.0,
                prefill_compute=8.0)
    v = td.diff_stages(base, cand, threshold_ms=5.0)
    assert v["regressed"] and v["stage"] == "store_transfer"
    assert v["delta_ms"] == pytest.approx(50.0)
    assert v["ratio"] == pytest.approx(6.0)
    assert v["share_of_regression"] == pytest.approx(50.0 / 54.0,
                                                     rel=1e-3)
    # noise-level jitter names nothing
    calm = td.diff_stages(base, dict(base, queue_wait=12.0),
                          threshold_ms=5.0)
    assert not calm["regressed"]


def test_trace_diff_cli_exit_codes(tmp_path):
    td = _load_trace_diff()
    base = {s: 10.0 for s in td.STAGES}
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(dict(base, kv_flush=80.0)))
    assert td.main([str(a), str(b), "--json"]) == 2
    assert td.main([str(a), str(a)]) == 0
    assert td.main([str(a), str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------------------------------
# the stage_budget watchdog rule
# ---------------------------------------------------------------------------


def test_stage_budget_rule_fires_on_breach_and_names_the_stage():
    from infinistore_tpu.health import (TimeSeriesRing, stage_budget_rule,
                                        default_serve_rules)

    rule = stage_budget_rule()
    r = TimeSeriesRing(step_s=1.0, clock=lambda: 0.0)
    r.observe("critpath.count", 10.0, t=0.0)
    r.observe("critpath.share.store_transfer", 0.61, t=0.0)
    res = rule.check(r, 0.0)
    assert res is not None and "store_transfer" in res["reason"]
    assert "61%" in res["reason"]
    # under min_count rows the rule stays silent (one slow request is
    # an offender trace id, not a regression)
    r2 = TimeSeriesRing(step_s=1.0, clock=lambda: 0.0)
    r2.observe("critpath.count", 3.0, t=0.0)
    r2.observe("critpath.share.store_transfer", 0.9, t=0.0)
    assert rule.check(r2, 0.0) is None
    # compute stages are unbudgeted by default: prefill legitimately
    # dominating TTFT never pages
    r3 = TimeSeriesRing(step_s=1.0, clock=lambda: 0.0)
    r3.observe("critpath.count", 10.0, t=0.0)
    r3.observe("critpath.share.prefill_compute", 0.95, t=0.0)
    assert rule.check(r3, 0.0) is None
    assert "stage_budget" in [x.name for x in default_serve_rules()]


def test_stage_budget_env_forms(monkeypatch):
    from infinistore_tpu.health import TimeSeriesRing, stage_budget_rule

    r = TimeSeriesRing(step_s=1.0, clock=lambda: 0.0)
    r.observe("critpath.count", 10.0, t=0.0)
    r.observe("critpath.share.store_transfer", 0.61, t=0.0)
    # stage=frac loosens one stage's budget past the observed share
    monkeypatch.setenv("ISTPU_STAGE_BUDGET", "store_transfer=0.7")
    assert stage_budget_rule().check(r, 0.0) is None
    # a bare float rebudgets every default-budgeted stage
    monkeypatch.setenv("ISTPU_STAGE_BUDGET", "0.9")
    assert stage_budget_rule().check(r, 0.0) is None
    monkeypatch.setenv("ISTPU_STAGE_BUDGET", "0.25")
    res = stage_budget_rule().check(r, 0.0)
    assert res is not None and "budget 25%" in res["reason"]


# ---------------------------------------------------------------------------
# ring-drop race + stitch-gather outcome counting
# ---------------------------------------------------------------------------


def test_ring_drop_counter_is_race_exact():
    """Two threads hammering a ring of ONE: every append past the first
    displaces a completed trace, and the counter says exactly that —
    2N−1 drops for 2N appends — under real contention."""
    tracer = tracing.Tracer(ring=1)
    n = 200
    before = tracing.ring_dropped_total()

    def worker(tag):
        for i in range(n):
            with tracer.trace(f"{tag}-{i}"):
                pass

    threads = [threading.Thread(target=worker, args=(t,))
               for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tracer.dropped == 2 * n - 1
    assert tracing.ring_dropped_total() - before >= 2 * n - 1


def _stitch_counts():
    parsed = m.parse_prometheus_text(
        m.default_registry().to_prometheus_text())
    return {res: parsed.get(("istpu_trace_stitch_total",
                             (("result", res),))) or 0.0
            for res in ("ok", "unnegotiated", "error")}


def test_gather_remote_counts_every_outcome():
    class _Unnegotiated:
        trace_ctx = False

    class _Dead:
        trace_ctx = True

        def trace_dump(self):
            raise OSError("peer gone")

    class _Ok:
        trace_ctx = True
        clock_offset = 1.5
        clock_offset_err = 0.25

        def trace_dump(self):
            return {"pid": 1, "clock": 0.0, "traces": []}

    before = _stitch_counts()
    assert trace_stitch.gather_remote(_Unnegotiated()) is None
    assert trace_stitch.gather_remote(_Dead()) is None
    dump, offset, err = trace_stitch.gather_remote(_Ok())
    assert offset == 1.5 and err == 0.25
    after = _stitch_counts()
    for res in ("ok", "unnegotiated", "error"):
        assert after[res] - before[res] == 1.0, res


# ---------------------------------------------------------------------------
# live mesh: store subprocess + 2-prefill/2-decode in-process fleet
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


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
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.5).close()
            break
        except OSError:
            if time.time() >= deadline:
                proc.kill()
                pytest.fail("store server did not come up")
            time.sleep(0.1)
    yield port, mport
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


@pytest.fixture(scope="module")
def mesh(live_store):
    """2 prefill + 2 decode behind a front door over the live store.
    SLO targets loosened for the module so the CPU jit-compile storm
    can never trip the burn watchdogs into shedding — these tests
    assert attribution, not latency."""
    from infinistore_tpu.frontdoor import local_fleet

    saved = {k: os.environ.get(k)
             for k in ("ISTPU_SLO_TTFT_S", "ISTPU_SLO_TPOT_S")}
    os.environ["ISTPU_SLO_TTFT_S"] = "60"
    os.environ["ISTPU_SLO_TPOT_S"] = "10"
    fd, workers, close = local_fleet(live_store[0], 2, 2, poll_s=0.3)
    # warm every leg (compiles) so no test measures a compile storm
    for w in workers["prefill"]:
        status, _ = _post(w.port, "/v1/prefill",
                          {"prompt": [7, 7, 7, 7, 7]})
        assert status == 200
    for _ in range(2):
        status, _ = _post(fd.port, "/v1/completions",
                          {"prompt": [7, 7, 7, 7, 7], "max_tokens": 2,
                           "temperature": 0})
        assert status == 200
    yield fd, workers
    close()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _post(port, path, body, headers=None, timeout=120.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json",
                      **(headers or {})})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _post_stream(port, body, trace_id, timeout=120.0):
    """Stream one completion, measuring client TTFT (first SSE chunk)
    under a client-minted trace id — the loadgen contract in one call."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        t0 = time.perf_counter()
        conn.request("POST", "/v1/completions",
                     json.dumps(dict(body, stream=True)),
                     {"Content-Type": "application/json",
                      "X-Istpu-Trace": trace_id})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        ttft = None
        while True:
            line = resp.fp.readline()
            if not line:
                break
            if line.startswith(b"data:") and ttft is None:
                ttft = time.perf_counter() - t0
        return ttft
    finally:
        conn.close()


def _get_json(port, path, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_clock_offset_error_bound_reestimated_on_reconnect(live_store):
    """Satellite: every HELLO estimates BOTH the clock offset and its
    error bound (½ RTT), and a reconnect builds a fresh transport that
    re-estimates rather than carrying a stale pre-restart offset."""
    from infinistore_tpu import lib as ist

    c = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=live_store[0],
        connection_type=ist.TYPE_SHM, op_timeout_s=30.0,
        log_level="warning"))
    c.connect()
    try:
        raw = c.conn
        assert raw.trace_ctx
        assert raw.clock_offset_err is not None
        assert raw.clock_offset_err >= 0.0
        c.reconnect()
        assert c.conn is not raw  # a FRESH transport...
        assert c.conn.clock_offset_err is not None  # ...re-estimated
        assert c.conn.clock_offset_err >= 0.0
    finally:
        c.close()


def test_mesh_stitched_single_request_export(mesh):
    """THE tentpole walk: one client-minted trace id in, ONE
    Perfetto-loadable mesh timeline out — router spans, worker spans,
    and the store server's own op spans (carried transitively through
    the worker's pre-mapped gather), every process row self-describing
    its clock-offset error bound."""
    fd, workers = mesh
    tid = "mesh-trace-%d" % int(time.time() * 1e3)
    ttft = _post_stream(fd.port, {"prompt": list(range(3, 19)),
                                  "max_tokens": 4, "temperature": 0},
                        tid)
    assert ttft is not None
    status, export = _get_json(fd.port, f"/debug/trace/{tid}")
    assert status == 200
    spans = [e for e in export["traceEvents"] if e.get("ph") == "X"]
    assert spans, export
    # every span in the export belongs to THIS request
    assert {e["args"]["trace_id"] for e in spans} == {tid}
    names = {e["name"] for e in spans}
    assert {"http.request", "fd.prefill_handoff",
            "engine.prefill"} <= names, sorted(names)
    # the store server's spans arrived on their OWN pid row (a real
    # subprocess), clock-mapped through the worker's offset
    local_pid = os.getpid()
    store_spans = [e for e in spans if e["pid"] != local_pid]
    assert store_spans, sorted(names)
    procs = {e["pid"]: e["args"] for e in export["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert procs[local_pid]["name"] == "router"
    remote = [a for p, a in procs.items() if p != local_pid]
    assert remote and all(a["name"].startswith("store@") for a in remote)
    # satellite: the stitched export carries the offset AND its error
    # bound per remote process
    for a in remote:
        assert "clock_offset_s" in a and "clock_offset_err_s" in a
        assert a["clock_offset_err_s"] >= 0.0
    # empty trace id 400s
    conn = http.client.HTTPConnection("127.0.0.1", fd.port, timeout=10)
    try:
        conn.request("GET", "/debug/trace/")
        assert conn.getresponse().status == 400
    finally:
        conn.close()


def test_mesh_critpath_stage_sums_reproduce_client_ttft(mesh):
    """THE acceptance criterion: the router's merged stage decomposition
    sums to the client-measured TTFT within 10% per request, with the
    unclaimed remainder named ``unattributed`` — and the majority of
    TTFT is genuinely claimed by real stages, not dumped there."""
    from infinistore_tpu.loadgen import LoadConfig, run_load

    fd, workers = mesh
    url = f"http://127.0.0.1:{fd.port}"
    results, _makespan = run_load(url, LoadConfig(
        rate=4.0, n_requests=8, vocab=256,
        mix=[(1.0, 16, 4)], timeout_s=300.0))
    ok = [r for r in results if r.get("ok") and r.get("ttft_s")]
    assert len(ok) == 8, results
    # the loadgen minted the trace ids the mesh continued
    assert all(r.get("trace_id") for r in ok)

    status, report = _get_json(fd.port, "/debug/critpath")
    assert status == 200 and report["enabled"]
    assert report["role"] == "router"
    assert report["stages"] == list(critpath.STAGES)
    # every worker answered the gather
    assert len(report["workers"]) == 4
    assert all(w["reachable"] for w in report["workers"])
    rows = {r["trace_id"]: r for r in report["rows"]}

    joined = claimed_shares = 0
    for r in ok:
        row = rows.get(r["trace_id"])
        if row is None:
            continue
        joined += 1
        st = row["stages"]
        assert st["unattributed"] >= 0.0
        ttft_sum = sum(st[s] for s in critpath.TTFT_STAGES)
        # stage sum reproduces the CLIENT's TTFT within 10% (+ a small
        # absolute slack for the localhost client<->router hop)
        tol = max(0.10 * r["ttft_s"], 0.025)
        assert abs(ttft_sum - r["ttft_s"]) <= tol, (r, row)
        if ttft_sum > 0 and st["unattributed"] <= 0.5 * ttft_sum:
            claimed_shares += 1
    # every loadgen request must be joinable by its minted trace id
    assert joined == len(ok), (joined, sorted(rows))
    # ...and for the majority, real stages own most of TTFT
    assert claimed_shares * 2 >= joined, report["overall"]
    # aggregate view answers per lane too, and names a dominant stage
    assert report["overall"]["dominant_stage"] in critpath.STAGES
    assert report["lanes"]
    # the worker-grain endpoint answers the same shape locally
    status, wsnap = _get_json(workers["decode"][0].port,
                              "/debug/critpath")
    assert status == 200 and wsnap["enabled"]
    assert wsnap["role"] == "decode" and wsnap["overall"]["count"] > 0


def test_chaos_store_delay_named_by_trace_diff(mesh, live_store,
                                               tmp_path, timed_walk):
    """THE chaos walk (FaultInjector action first, house rule): a
    store-side ``GET_DESC`` delay — the in-flight shape of a dragging
    store tier — must be NAMED ``store_transfer`` by trace_diff from
    two /debug/critpath captures, with exit code 2 as the perf gate."""
    td = _load_trace_diff()
    fd, workers = mesh
    _port, mport = live_store

    def drive(n, base, tag):
        # FRESH prompts each round: a repeated prompt adopts from the
        # decode worker's LOCAL prefix cache and never touches the
        # store, which would hide the armed fault entirely.  Client-
        # minted trace ids, so a round's rows can be told from the ring's
        tids = [f"chaos-{tag}-{i}" for i in range(n)]
        for i, tid in enumerate(tids):
            status, _ = _post(fd.port, "/v1/completions",
                              {"prompt": list(range(base + 20 * i,
                                                    base + 20 * i + 16)),
                               "max_tokens": 2, "temperature": 0},
                              headers={"X-Istpu-Trace": tid})
            assert status == 200
        return set(tids)

    def capture(tids):
        """The /debug/critpath payload over ONE ROUND's requests: the
        live payload aggregates the whole ring, whose p99 of a stage over
        under a hundred rows is its maximum, and the ring holds the
        module's warm-up and every earlier test's rows; on a loaded host
        one of those had a store hop slower than the armed delay and the
        candidate's maximum did not move (``delta_ms: 0.007``).  Baseline
        and candidate are each their own window, the same four prompts'
        shapes in both."""
        _s, snap = _get_json(fd.port, "/debug/critpath")
        rows = [r for r in snap["rows"] if r.get("trace_id") in tids]
        assert len(rows) == len(tids), (sorted(tids), snap["workers"])
        return dict(snap, rows=rows, returned=len(rows),
                    overall=critpath.aggregate(rows))

    def arm(rules):
        req = urllib.request.Request(
            f"http://127.0.0.1:{mport}/faults", method="POST",
            data=json.dumps(rules).encode())
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.load(r)

    # token ids stay under the TINY vocab (512)
    baseline = capture(drive(4, base=100, tag="base"))
    try:
        out = arm([{"op": "GET_DESC", "action": "delay",
                    "delay_s": 0.4}])
        assert out["armed"] == 1
        tids = drive(4, base=300, tag="cand")
    finally:
        arm([])
    candidate = capture(tids)

    a = tmp_path / "baseline.json"
    b = tmp_path / "candidate.json"
    a.write_text(json.dumps(baseline))
    b.write_text(json.dumps(candidate))
    v = td.diff_stages(td.load_stages(baseline, "p99"),
                       td.load_stages(candidate, "p99"),
                       threshold_ms=50.0)
    assert v["regressed"], v
    assert v["stage"] == "store_transfer", v
    assert v["delta_ms"] >= 200.0, v
    assert v["share_of_regression"] >= 0.5, v
    # the CLI gate agrees, from the same capture files
    assert td.main([str(a), str(b), "--threshold-ms", "50"]) == 2
