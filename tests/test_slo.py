"""Serving SLO observability plane: open-loop arrival-process math
(deterministic clock injection), the per-request lifecycle ledger
(waterfall attribution, ring overflow, ?limit=, trace-id-stamped log
lines), per-lane percentile correctness, the istpu-top serving view
(offline Console.frame fixture), and a live mini load run asserting the
acceptance surface end to end: /debug/requests records joinable by
trace id, per-lane TTFT/TPOT families on /metrics, goodput summary."""

import io
import json
import logging
import types
import urllib.request

import pytest

from infinistore_tpu.engine.scheduler import Request
from infinistore_tpu.ledger import RequestLedger, build_record
from infinistore_tpu.loadgen import (
    LoadConfig,
    arrival_offsets,
    make_requests,
    meets_slo,
    run_load,
    summarize,
)

# ---------------------------------------------------------------------------
# arrival-process timing math (pure; injected clocks for the pacer)
# ---------------------------------------------------------------------------


def test_arrival_offsets_math():
    det = arrival_offsets(4.0, 5, "deterministic")
    assert det == [0.0, 0.25, 0.5, 0.75, 1.0]
    import random

    p1 = arrival_offsets(10.0, 200, "poisson", random.Random(7))
    p2 = arrival_offsets(10.0, 200, "poisson", random.Random(7))
    assert p1 == p2  # seeded => reproducible schedule
    assert all(b > a for a, b in zip(p1, p2[1:]))  # strictly increasing
    # mean inter-arrival ~ 1/rate (law of large numbers, loose bound)
    mean_gap = p1[-1] / len(p1)
    assert 0.05 < mean_gap < 0.2
    with pytest.raises(ValueError):
        arrival_offsets(0.0, 3)
    with pytest.raises(ValueError):
        arrival_offsets(1.0, 3, "uniform")


def test_open_loop_pacer_with_injected_clock():
    """The pacer fires at the schedule, not at completions: with a
    virtual clock that only advances through sleep(), every request is
    on time and the sleeps are exactly the schedule gaps."""

    class VClock:
        def __init__(self):
            self.t = 0.0
            self.slept = []

        def __call__(self):
            return self.t

        def sleep(self, d):
            self.slept.append(round(d, 9))
            self.t += d

    vc = VClock()
    fired = []

    def post(body):
        fired.append(body["priority"])
        return {"ok": True, "status": 200, "error": None, "tokens": 2,
                "lane": body["priority"], "ttft_s": 0.1, "tpot_s": 0.01,
                "e2e_s": 0.2}

    cfg = LoadConfig(rate=2.0, n_requests=4, process="deterministic",
                     seed=0, lanes=((3, 1.0),))
    results, makespan = run_load("http://x", cfg, clock=vc, sleep=vc.sleep,
                                 post=post)
    assert vc.slept == [0.5, 0.5, 0.5]  # exactly the schedule gaps
    assert makespan == pytest.approx(1.5)
    assert len(results) == 4 and all(r["ok"] for r in results)
    assert [r["sched_off_s"] for r in results] == [0.0, 0.5, 1.0, 1.5]
    assert all(r["late_s"] == 0.0 for r in results)  # open-loop: on time
    assert fired == [3, 3, 3, 3]


def test_make_requests_population():
    cfg = LoadConfig(rate=1, n_requests=64, seed=3, vocab=50,
                     mix=((3.0, 8, 4), (1.0, 24, 12)),
                     lanes=((0, 1.0), (10, 1.0)),
                     n_prefixes=2, prefix_len=6, prefix_frac=1.0)
    reqs = make_requests(cfg)
    assert reqs == make_requests(cfg)  # deterministic in the seed
    assert len(reqs) == 64
    assert {r["priority"] for r in reqs} == {0, 10}
    assert all(0 <= t < 50 for r in reqs for t in r["prompt"])
    # prefix_frac=1: every prompt starts with one of the 2 shared prefixes
    heads = {tuple(r["prompt"][:6]) for r in reqs}
    assert len(heads) == 2
    assert {r["max_tokens"] for r in reqs} == {4, 12}


# ---------------------------------------------------------------------------
# per-lane percentile correctness (nearest-rank over synthetic samples)
# ---------------------------------------------------------------------------


def _res(lane, ttft, tpot=0.01, ok=True):
    return {"ok": ok, "status": 200 if ok else 0, "error": None,
            "tokens": 4, "lane": lane, "ttft_s": ttft, "tpot_s": tpot,
            "e2e_s": ttft + 0.1}


def test_summarize_per_lane_percentiles():
    # lane 0: ttfts 0.1..1.0 — nearest-rank p50 = 5th smallest (0.5),
    # p99 = ceil(.99*10)=10th (1.0).  lane 9: single sample.
    results = [_res(0, i / 10) for i in range(1, 11)] + [_res(9, 0.3)]
    s = summarize(results, makespan_s=10.0, slo_ttft_s=0.55,
                  slo_tpot_s=0.05, rate=2.0)
    assert s["n"] == 11 and s["completed"] == 11 and s["errors"] == 0
    lane0 = s["lanes"]["0"]
    assert lane0["ttft"] == {"p50_ms": 500.0, "p99_ms": 1000.0}
    assert lane0["slo_met"] == 5  # ttfts 0.1..0.5 meet the 0.55 SLO
    assert s["lanes"]["9"]["ttft"] == {"p50_ms": 300.0, "p99_ms": 300.0}
    # goodput = met/makespan; attainment = met/offered
    assert s["goodput_rps"] == pytest.approx(6 / 10.0)
    assert s["slo_attainment"] == pytest.approx(6 / 11, abs=1e-4)
    # failures can't meet SLO; short requests are judged on TTFT alone
    assert not meets_slo(_res(0, 0.1, ok=False), 1.0, 1.0)
    assert meets_slo({**_res(0, 0.1), "tpot_s": None}, 1.0, 0.001)


# ---------------------------------------------------------------------------
# ledger: waterfall attribution, ring overflow, ?limit=
# ---------------------------------------------------------------------------


def _fake_req(req_id=1, lane=5, trace_id="tid-1"):
    req = Request(req_id=req_id, tokens=[1, 2, 3], max_new_tokens=8,
                  priority=lane, trace_id=trace_id)
    req.t_submit, req.t_admit, req.t_first, req.t_done = (
        100.0, 100.5, 101.0, 103.0)
    req.t_stream_s = 0.2
    req.output = [7] * 5
    req.stamps = [(1.0, 4), (3.0, 5)]
    req.state = types.SimpleNamespace(
        reused_chunks=2, local_chunks=1, store_chunks=1, store_load_s=0.05)
    return req


def test_build_record_waterfall_sums_to_e2e():
    rec = build_record(_fake_req(), "done", wall=1234.5)
    assert rec["lane"] == "5" and rec["trace_id"] == "tid-1"
    assert rec["ttft_s"] == pytest.approx(1.0)
    assert rec["tpot_s"] == pytest.approx(2.0 / 4)
    assert rec["e2e_s"] == pytest.approx(3.0)
    wf = rec["waterfall"]
    assert wf["queue_s"] == pytest.approx(0.5)
    assert wf["store_s"] == pytest.approx(0.05)
    assert wf["prefill_s"] == pytest.approx(0.45)
    assert wf["stream_s"] == pytest.approx(0.2)
    assert wf["decode_s"] == pytest.approx(1.8)
    # the waterfall is DISJOINT: slices sum to e2e, shares to ~1
    assert sum(wf.values()) == pytest.approx(rec["e2e_s"])
    assert sum(rec["shares"].values()) == pytest.approx(1.0, abs=0.01)
    assert rec["store"] == {"reused_chunks": 2, "local_chunks": 1,
                            "store_chunks": 1, "hit": True, "load_s": 0.05}
    assert ("first_token", 1.0) in [tuple(e) for e in rec["events"]]
    assert rec["token_stamps"] == [(1.0, 4), (3.0, 5)]
    # a request cancelled while still queued: all time is queue
    req = _fake_req()
    req.t_admit = req.t_first = 0.0
    req.t_done = 102.0
    req.output = []
    req.state = None
    rec = build_record(req, "cancelled")
    assert rec["outcome"] == "cancelled"
    assert rec["waterfall"]["queue_s"] == pytest.approx(2.0)
    assert rec["ttft_s"] is None and rec["store"]["hit"] is False


def test_ledger_ring_overflow_and_limit():
    led = RequestLedger(capacity=4, log=False)
    for i in range(10):
        led.record(_fake_req(req_id=i), "done")
    assert led.recorded == 10
    tail = led.tail()
    assert len(tail) == 4  # ring holds the newest 4
    assert [r["req_id"] for r in tail] == [6, 7, 8, 9]
    assert [r["req_id"] for r in led.tail(limit=2)] == [8, 9]
    assert led.tail(limit=0) == []
    snap = led.snapshot(limit=3)
    assert snap["capacity"] == 4 and snap["recorded"] == 10
    assert snap["returned"] == 3
    assert [r["req_id"] for r in snap["records"]] == [7, 8, 9]


def test_ledger_log_line_carries_request_trace_id():
    """Ledger events flow through the SHARED logger and the line carries
    the REQUEST's trace id — even when a different trace (the engine
    step) is active on the recording thread."""
    from infinistore_tpu.utils import tracing
    from infinistore_tpu.utils.logging import _TraceFormatter

    logger = logging.getLogger("infinistore_tpu")
    stream = io.StringIO()
    h = logging.StreamHandler(stream)
    h.setFormatter(_TraceFormatter("[%(levelname)s] %(message)s"))
    old_level = logger.level
    logger.addHandler(h)
    logger.setLevel(logging.INFO)
    try:
        led = RequestLedger(capacity=8)
        with tracing.trace("engine.step"):  # the ambient (WRONG) trace
            led.record(_fake_req(trace_id="req-trace-42"), "done")
    finally:
        logger.removeHandler(h)
        logger.setLevel(old_level)
    line = [ln for ln in stream.getvalue().splitlines() if "ledger" in ln][0]
    assert "req=1" in line and "lane=5" in line and "outcome=done" in line
    assert line.endswith("trace_id=req-trace-42")


# ---------------------------------------------------------------------------
# istpu-top serving view (offline Console.frame fixture)
# ---------------------------------------------------------------------------


def test_console_serving_view_fixture():
    from infinistore_tpu.top import Console, Snapshot
    from infinistore_tpu.utils.metrics import (
        MetricsRegistry,
        parse_prometheus_text,
    )

    def metrics_text(n_done):
        reg = MetricsRegistry()
        reg.counter("istpu_serve_requests_total", "").inc(8 + n_done)
        reg.counter("istpu_serve_completed_total", "").inc(n_done)
        reg.gauge("istpu_serve_inflight", "").set(3)
        reg.gauge("istpu_serve_queue_depth", "").set(5)
        h = reg.histogram("istpu_serve_ttft_seconds", "",
                          labelnames=("lane",))
        t = reg.histogram("istpu_serve_tpot_seconds", "",
                          labelnames=("lane",))
        for _ in range(n_done):
            h.labels("0").observe(0.4)
            t.labels("0").observe(0.05)
            h.labels("10").observe(0.1)
        reg.counter("istpu_serve_slo_violations_total", "",
                    labelnames=("slo", "lane")).labels("ttft", "0").inc(2)
        return reg.to_prometheus_text()

    ledger_payload = {
        "capacity": 256, "recorded": 2, "returned": 2,
        "records": [
            {"req_id": 7, "lane": "0", "outcome": "done", "ttft_s": 0.41,
             "tpot_s": 0.05, "e2e_s": 0.9, "trace_id": "ab-1",
             "shares": {"queue": 0.1, "store": 0.02, "prefill": 0.38,
                        "decode": 0.48, "stream": 0.02}},
            {"req_id": 8, "lane": "10", "outcome": "cancelled",
             "ttft_s": 0.1, "tpot_s": None, "e2e_s": 0.2,
             "trace_id": "ab-2",
             "shares": {"queue": 0.9, "store": 0.0, "prefill": 0.1,
                        "decode": 0.0, "stream": 0.0}},
        ],
    }

    def snap(n_done):
        return Snapshot(
            serve_metrics=parse_prometheus_text(metrics_text(n_done)),
            serve_health={"status": "ok"},
            requests=ledger_payload,
        )

    console = Console()
    console.frame(snap(2))       # primes the delta/rate trackers
    out = console.frame(snap(5))  # second frame has interval deltas
    assert "serving load" in out
    assert "arrivals     3/frame" in out
    assert "completions     3/frame" in out
    assert "inflight    3" in out and "queued    5" in out
    assert "slo-viol     2" in out
    # per-lane table, numeric lane order, interval-mean TTFT rendered
    lines = out.splitlines()
    lane_rows = [ln for ln in lines if ln.strip().startswith(("0 ", "10 "))]
    assert len(lane_rows) == 2
    assert lane_rows[0].strip().startswith("0")
    assert "400.0m" in lane_rows[0]  # 0.4 s interval mean, fmt_dur ms
    # recent-request ledger rows with waterfall shares and trace ids
    assert "recent requests" in out
    assert "req     8" in out and "cancelled" in out
    assert "trace ab-1" in out and "trace ab-2" in out
    assert "q90%" in out  # lane-10 row's queue share
    # lanes() discovery is numeric-ordered
    assert snap(1).lanes() == ["0", "10"]
    # an empty snapshot must not render the section (or crash)
    from infinistore_tpu.top import Snapshot as S

    assert "serving load" not in Console().frame(S())


# ---------------------------------------------------------------------------
# live: a mini open-loop run against a real server — the acceptance
# surface (per-lane /metrics families, waterfall'd /debug/requests
# joinable by trace id, goodput summary) in one pass
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def live_server():
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params, scaled
    from infinistore_tpu.serve import ServingServer

    cfg = scaled(TINY, dtype=jnp.float32)
    eng = InferenceEngine(
        init_params(cfg, jax.random.PRNGKey(1)), cfg,
        PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, n_blocks=96, block_tokens=4,
            dtype=cfg.dtype,
        ),
    )
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=4, model_id="tiny-slo",
                        slo_ttft_s=30.0, slo_tpot_s=5.0, ledger_ring=64)
    srv.start()
    yield srv, cfg.vocab_size
    srv.close()


def test_live_load_ledger_and_lane_metrics(live_server):
    srv, vocab = live_server
    url = f"http://127.0.0.1:{srv.port}"
    cfg = LoadConfig(rate=8.0, n_requests=10, process="poisson", seed=2,
                     mix=((1.0, 12, 4),), lanes=((0, 2.0), (7, 1.0)),
                     n_prefixes=2, prefix_len=8, prefix_frac=0.5,
                     vocab=vocab, timeout_s=180.0)
    results, makespan = run_load(url, cfg)
    s = summarize(results, makespan, slo_ttft_s=30.0, slo_tpot_s=5.0,
                  rate=8.0)
    assert s["completed"] == 10 and s["errors"] == 0
    assert s["goodput_rps"] > 0 and s["slo_attainment"] == 1.0
    assert set(s["lanes"]) == {"0", "7"}
    for lane in s["lanes"].values():
        assert lane["ttft"]["p99_ms"] >= lane["ttft"]["p50_ms"] > 0

    # /debug/requests: every request has a waterfall'd record with a
    # trace id, and ?limit= caps the tail
    snap = json.loads(urllib.request.urlopen(
        url + "/debug/requests").read())
    assert snap["recorded"] >= 10
    recs = snap["records"]
    done = [r for r in recs if r["outcome"] == "done"]
    assert len(done) >= 10
    for r in done:
        assert r["trace_id"]  # joinable to /debug/traces and log lines
        assert r["ttft_s"] > 0 and r["e2e_s"] >= r["ttft_s"]
        total = sum(v for v in r["waterfall"].values() if v)
        assert total == pytest.approx(r["e2e_s"], rel=0.05)
        assert r["events"][0][0] == "submit"
    lim = json.loads(urllib.request.urlopen(
        url + "/debug/requests?limit=3").read())
    assert lim["returned"] == 3 and len(lim["records"]) == 3

    # /metrics: per-lane families + load gauges
    text = urllib.request.urlopen(url + "/metrics").read().decode()
    from infinistore_tpu.utils.metrics import parse_prometheus_text

    parsed = parse_prometheus_text(text)
    for lane in ("0", "7"):
        key = ("istpu_serve_ttft_seconds_count", (("lane", lane),))
        assert parsed.get(key, 0) > 0, f"lane {lane} missing from /metrics"
    assert ("istpu_serve_inflight", ()) in parsed
    assert ("istpu_serve_queue_depth", ()) in parsed
    # generous SLOs => no violations counted on this run
    viol = sum(v for (name, _l), v in parsed.items()
               if name == "istpu_serve_slo_violations_total")
    assert viol == 0
