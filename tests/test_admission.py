"""SLO-aware admission control (infinistore_tpu/admission.py).

Pure halves first — quota-spec parsing, ``QuotaLedger`` refill/burst/
isolation math under an injected clock, the controller decision table
(burn state × lane × pool pressure) over stubs, Retry-After bounds, the
shed-lane escalation ladder, degraded-mode prefill budgets — no jax, no
sockets.  Then the live halves: shed-on-burn answers 429 + Retry-After
on the lowest lane while the protected lane keeps serving, the
shed-never-cancels-admitted invariant, per-tenant quota throttling with
the loadgen client honoring one Retry-After, `/debug/admission` +
`/healthz` admission block + the `istpu_admission_*` families, and THE
chaos acceptance walk from ROADMAP item 3: FaultInjector-induced
overload → `ttft_burn` fires page → the lowest lane sheds with 429 +
Retry-After while the protected lane's SLO attainment holds → the burn
clears with zero operator action — every transition asserted from
scraped ``/metrics`` (field-level `/healthz` asserts only; the payload
grows).
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

from infinistore_tpu.admission import (
    RETRY_AFTER_MAX_S,
    RETRY_AFTER_MIN_S,
    AdmissionController,
    AdmissionShed,
    QuotaLedger,
    parse_quotas,
    retry_after_header,
)

# ---------------------------------------------------------------------------
# quota spec parsing (pure)
# ---------------------------------------------------------------------------


def test_parse_quotas_formats():
    assert parse_quotas(None) == {}
    assert parse_quotas("") == {}
    assert parse_quotas("0:500") == {"0": (500.0, 2.0)}
    assert parse_quotas("0:500,10:2000:5") == {
        "0": (500.0, 2.0), "10": (2000.0, 5.0)}
    # the repeatable --quota flag hands a LIST of (possibly comma'd)
    # entries
    assert parse_quotas(["0:500", "10:2000,3:50"]) == {
        "0": (500.0, 2.0), "10": (2000.0, 2.0), "3": (50.0, 2.0)}
    assert parse_quotas({"7": 100}) == {"7": (100.0, 2.0)}
    for bad in ("0", "0:500:2:9", "0:0", "0:-5", "0:100:0"):
        with pytest.raises(ValueError):
            parse_quotas(bad)


def test_retry_after_header_is_integer_seconds():
    assert retry_after_header(None) is None
    assert retry_after_header(0.2) == "1"  # floor at 1
    assert retry_after_header(2.1) == "3"  # ceil
    assert retry_after_header(30.0) == "30"


# ---------------------------------------------------------------------------
# QuotaLedger (pure, injected clock)
# ---------------------------------------------------------------------------


def test_quota_refill_math_and_debt():
    """Debt model: a charge is allowed while the bucket is positive and
    takes the full cost (the bucket may go negative), so the long-run
    admitted rate equals the configured rate regardless of request
    size."""
    now = [0.0]
    led = QuotaLedger({"a": (100.0, 2.0)}, clock=lambda: now[0])
    assert led.available("a") == 200.0  # starts full (rate * burst_s)
    assert led.try_charge("a", 150)
    assert led.available("a") == 50.0
    assert led.try_charge("a", 120)  # positive bucket: allowed into debt
    assert led.available("a") == -70.0
    assert not led.try_charge("a", 1)  # drained: denied, nothing charged
    assert led.available("a") == -70.0
    assert led.throttled["a"] == 1
    now[0] = 1.0  # +100 tokens refill
    assert led.available("a") == pytest.approx(30.0)
    assert led.try_charge("a", 10)


def test_quota_burst_cap_and_multi_tenant_isolation():
    now = [0.0]
    led = QuotaLedger({"a": (100.0, 2.0), "b": (10.0, 1.0)},
                      clock=lambda: now[0])
    # tenant a drains; tenant b is untouched (isolation)
    assert led.try_charge("a", 500) and not led.try_charge("a", 1)
    assert led.available("b") == 10.0
    assert led.try_charge("b", 5)
    # a long idle refills to the burst cap, never past it
    now[0] = 1000.0
    assert led.available("a") == 200.0
    assert led.available("b") == 10.0
    # unlimited tenants: always allowed, no state
    assert led.try_charge("zz", 10 ** 9)
    assert led.available("zz") is None
    assert led.throttled_total() == 1


def test_quota_retry_after_is_own_refill_time_clamped():
    now = [0.0]
    led = QuotaLedger({"a": (100.0, 2.0), "slow": (1.0, 2.0)},
                      clock=lambda: now[0])
    led.try_charge("a", 250)  # bucket at -50
    assert not led.try_charge("a", 1)
    # (1 + 50) / 100 = 0.51 s -> clamped to the 1 s floor
    assert led.retry_after("a") == RETRY_AFTER_MIN_S
    led.try_charge("slow", 100)  # -98 at 1 tok/s = 99 s -> clamp 30
    assert led.retry_after("slow") == RETRY_AFTER_MAX_S
    snap = led.snapshot()
    assert snap["a"]["throttled"] == 1
    assert snap["a"]["used_frac"] == 1.0
    assert snap["slow"]["rate_toks_per_s"] == 1.0


# ---------------------------------------------------------------------------
# controller decision table (pure, stubbed collaborators)
# ---------------------------------------------------------------------------


class StubRing:
    def __init__(self, completed_delta=0.0):
        self.completed_delta = completed_delta

    def delta(self, name, window_s, now=None):
        return self.completed_delta


class StubSampler:
    def __init__(self, ring=None):
        self.enabled = True
        self.ring = ring
        self.rules = []

    def fire_burn(self, value, rule="ttft_burn", severity="page"):
        self.rules = [{"rule": rule, "severity": severity,
                       "value": value, "since": 0.0, "reason": "stub"}]

    def clear(self):
        self.rules = []

    def firing(self):
        return list(self.rules)


class StubEngine:
    def __init__(self, n_blocks=100, free=100, prefill_chunk=None):
        import types

        self.pc = types.SimpleNamespace(n_blocks=n_blocks)
        self.free_pages = free
        self.prefill_chunk = prefill_chunk


class StubSched:
    def __init__(self, pending=0):
        self.pending = [None] * pending
        self.active = []
        self._prefilling = []


def _ctrl(**kw):
    kw.setdefault("sampler", StubSampler(StubRing(completed_delta=60.0)))
    kw.setdefault("engine", StubEngine())
    kw.setdefault("sched", StubSched())
    kw.setdefault("enabled", True)
    kw.setdefault("quotas", {})
    return AdmissionController(clock=lambda: 1000.0, **kw)


def test_decision_table_burn_sheds_lowest_lane_first():
    c = _ctrl()
    for lane in (0, 5, 10):
        assert c.check_submit(lane, 10).admitted  # healthy: all admit
    c.sampler.fire_burn(2.5)
    assert c.shed_lanes() == [0]
    d = c.check_submit(0, 10)
    assert (d.action, d.reason) == ("shed", "burn")
    assert c.check_submit(5, 10).admitted
    assert c.check_submit(10, 10).admitted
    # escalation: one more lane per 4x of burn; the top lane NEVER
    # sheds while >1 lane exists
    c.sampler.fire_burn(4.5)
    assert c.shed_lanes() == [0, 5]
    assert not c.check_submit(5, 10).admitted
    assert c.check_submit(10, 10).admitted
    c.sampler.fire_burn(400.0)
    assert c.shed_lanes() == [0, 5]  # capped below the protected lane
    assert c.check_submit(10, 10).admitted
    # recovery: verdicts flip back with the sampler state, no reset call
    c.sampler.clear()
    assert c.shed_lanes() == []
    assert c.check_submit(0, 10).admitted
    assert c.mode() == "normal"


def test_decision_table_burn_requires_page_severity_and_burn_rule():
    c = _ctrl()
    c.check_submit(0, 1)
    c.check_submit(10, 1)
    c.sampler.fire_burn(5.0, severity="warn")  # warn never sheds
    assert c.check_submit(0, 1).admitted
    c.sampler.fire_burn(5.0, rule="circuit_flap")  # non-burn page rule
    assert c.check_submit(0, 1).admitted
    c.sampler.fire_burn(5.0, rule="tpot_burn")  # the other burn rule
    assert not c.check_submit(0, 1).admitted


def test_decision_table_single_lane_duty_cycles():
    """With one lane there is nothing to protect relative to: the lane
    itself sheds while burning (duty-cycling is what turns collapse
    into a plateau)."""
    c = _ctrl()
    c.check_submit(3, 1)
    c.sampler.fire_burn(2.1)
    assert c.shed_lanes() == [3]
    assert not c.check_submit(3, 1).admitted
    c.sampler.clear()
    assert c.check_submit(3, 1).admitted


def test_decision_table_pool_pressure_sheds_non_protected():
    c = _ctrl(engine=StubEngine(n_blocks=100, free=2),  # 2% free
              sched=StubSched(pending=10))
    c.check_submit(0, 1)
    d = c.check_submit(10, 1)
    assert d.admitted  # top lane protected from pressure sheds too
    d = c.check_submit(0, 1)
    assert (d.action, d.reason) == ("shed", "pressure")
    # shallow queue: pressure shed needs BOTH conditions
    c2 = _ctrl(engine=StubEngine(n_blocks=100, free=2),
               sched=StubSched(pending=2))
    c2.check_submit(0, 1)
    assert c2.check_submit(0, 1).admitted


def test_decision_table_quota_throttles_before_global_shed():
    """A drained tenant answers its OWN refill Retry-After (throttle)
    even while its lane is being burn-shed, and refused work never
    charges the bucket."""
    c = _ctrl(quotas={"0": (100.0, 2.0)})
    c.check_submit(10, 1)
    assert c.check_submit(0, 250).admitted  # charges into debt
    d = c.check_submit(0, 10)
    assert (d.action, d.reason) == ("throttle", "quota")
    assert d.retry_after_s is not None
    # burn-shed requests do NOT charge: the bucket is unchanged after
    # an over-quota tenant's lane sheds
    c.sampler.fire_burn(3.0)
    before = c.quota.available("0")
    d = c.check_submit(0, 50)
    assert d.reason == "quota"  # tenant verdict first: own retry time
    assert c.quota.available("0") == before
    # an in-quota tenant on a shed lane sheds WITHOUT being charged
    c2 = _ctrl(quotas={"0": (100.0, 2.0)})
    c2.check_submit(0, 1)
    c2.check_submit(10, 1)
    c2.sampler.fire_burn(3.0)
    before = c2.quota.available("0")
    d = c2.check_submit(0, 50)
    assert (d.action, d.reason) == ("shed", "burn")
    assert c2.quota.available("0") == pytest.approx(before)


def test_retry_after_bounds_and_drain_scaling():
    # dead drain (nothing completing): honest worst case, the max
    c = _ctrl(sampler=StubSampler(StubRing(completed_delta=0.0)),
              sched=StubSched(pending=5))
    assert c._retry_after(3.0) == RETRY_AFTER_MAX_S
    # fast drain, shallow queue: the floor
    c = _ctrl(sampler=StubSampler(StubRing(completed_delta=6000.0)),
              sched=StubSched(pending=0))
    assert c._retry_after(2.0) == RETRY_AFTER_MIN_S
    # deep queue, slow drain: clamped at the max, never beyond
    c = _ctrl(sampler=StubSampler(StubRing(completed_delta=6.0)),
              sched=StubSched(pending=500))
    assert c._retry_after(8.0) == RETRY_AFTER_MAX_S
    # in between: scales with depth/drain and burn, inside the bounds
    c = _ctrl(sampler=StubSampler(StubRing(completed_delta=60.0)),
              sched=StubSched(pending=3))
    ra = c._retry_after(4.0)
    assert RETRY_AFTER_MIN_S <= ra <= RETRY_AFTER_MAX_S
    assert ra == pytest.approx((3 + 1) / 1.0 * 2.0)


def test_prefill_budget_degraded_mode():
    c = _ctrl(engine=StubEngine(prefill_chunk=64))
    c.check_submit(0, 1)
    c.check_submit(10, 1)
    assert c.prefill_token_budget() is None  # healthy: no throttle
    # a TTFT burn does NOT arm the throttle: prefill IS the path to
    # first token there — pacing it would worsen the burning SLO
    c.sampler.fire_burn(2.5, rule="ttft_burn")
    assert c.prefill_token_budget() is None
    c.sampler.fire_burn(2.5, rule="tpot_burn")
    assert c.prefill_token_budget() == 64  # one chunk per step
    # no chunked prefill configured: budget degrades to "one advance"
    c2 = _ctrl(engine=StubEngine(prefill_chunk=None))
    c2.check_submit(0, 1)
    c2.sampler.fire_burn(2.5, rule="tpot_burn")
    assert c2.prefill_token_budget() == 1
    # explicit cap wins
    c3 = _ctrl(engine=StubEngine(prefill_chunk=64),
               prefill_cap_tokens=256)
    c3.check_submit(0, 1)
    c3.sampler.fire_burn(2.5, rule="tpot_burn")
    assert c3.prefill_token_budget() == 256


@pytest.mark.parametrize("active,burn,cap,want", [
    (1, None, None, 8 * 64),          # healthy: max_batch chunks a step
    (1, "ttft_burn", None, 8 * 64),   # a TTFT burn does not arm the throttle
    (1, "tpot_burn", None, 64),       # degraded: one chunk, the smaller wins
    (1, "tpot_burn", 256, 256),       # the explicit cap, still the smaller
    (1, "tpot_burn", 4096, 8 * 64),   # a cap over the step's budget: the step's
    (8, "tpot_burn", 256, 256),       # occupancy bounds WHO starts, not this
    (1, "tpot_burn", 16, 64),         # under one chunk: the one a step can run
])
def test_scheduler_budget_is_the_smaller_of_state_and_throttle(
        active, burn, cap, want):
    """The scheduler's per-step prefill budget against the degraded-mode
    throttle: the seam is ``prefill_token_budget()`` and the smaller of
    the two rules."""
    from infinistore_tpu.engine.scheduler import Scheduler

    c = _ctrl(engine=StubEngine(prefill_chunk=64), prefill_cap_tokens=cap)
    c.check_submit(0, 1)
    if burn:
        c.sampler.fire_burn(2.5, rule=burn)
    sched = StubSched()
    sched.active = [None] * active
    sched.max_batch, sched.engine, sched.admission = 8, c.engine, c
    assert Scheduler._prefill_budget(sched) == want


def test_kill_switch_and_snapshot_shape():
    c = _ctrl(enabled=False)
    c.sampler.fire_burn(99.0)
    assert c.check_submit(0, 10 ** 9).admitted  # everything admits
    assert c.mode() == "off" and c.mode_code() == 0.0
    assert c.snapshot() == {"enabled": False, "mode": "off"}
    # env spelling of the same switch
    os.environ["ISTPU_ADMISSION"] = "0"
    try:
        c2 = AdmissionController(clock=lambda: 0.0, quotas={})
        assert not c2.enabled
    finally:
        del os.environ["ISTPU_ADMISSION"]
    # enabled snapshot carries the control-loop state
    c3 = _ctrl(quotas={"0": (100.0, 2.0)})
    c3.check_submit(0, 250)
    c3.check_submit(0, 10)  # throttled
    c3.sampler.fire_burn(2.5)
    c3.check_submit(0, 10)  # quota verdict (drained tenant)
    snap = c3.snapshot()
    assert snap["enabled"] and snap["mode"] == "shed"
    assert snap["burn"]["value"] == 2.5
    assert snap["burn"]["shed_lanes"] == ["0"]
    assert snap["decisions"]["admit"]["0"] == 1
    assert snap["decisions"]["throttle"]["0"] == 2
    assert snap["shed_by_reason"]["quota"]["0"] == 2
    assert snap["quota"]["tenants"]["0"]["throttled"] == 2
    assert snap["prefill_throttle"]["active"] is False  # ttft burn
    hb = c3.health_block()
    assert hb["mode"] == "shed" and hb["shed_lanes"] == ["0"]


# ---------------------------------------------------------------------------
# loadgen accounting: a shed is `rejected`, never an error (pure)
# ---------------------------------------------------------------------------


def test_summarize_counts_rejected_separately():
    from infinistore_tpu.loadgen import summarize

    def res(lane, ok=True, rejected=False, ttft=0.1):
        return {"ok": ok, "status": 429 if rejected else (200 if ok else 0),
                "error": None if ok else "x", "tokens": 4 if ok else 0,
                "lane": lane, "rejected": rejected,
                "ttft_s": ttft if ok else None,
                "tpot_s": 0.01 if ok else None,
                "e2e_s": 0.2 if ok else None}

    results = ([res(0) for _ in range(4)]
               + [res(0, ok=False, rejected=True) for _ in range(3)]
               + [res(0, ok=False)]              # a real failure
               + [res(10), res(10)])
    s = summarize(results, makespan_s=10.0, slo_ttft_s=1.0,
                  slo_tpot_s=1.0, rate=1.0)
    assert s["n"] == 10 and s["completed"] == 6
    assert s["rejected"] == 3 and s["errors"] == 1  # disjoint counts
    assert s["lanes"]["0"]["rejected"] == 3
    assert s["lanes"]["10"]["rejected"] == 0
    # goodput counts only completed+met; sheds don't poison it
    assert s["goodput_rps"] == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# live halves: a tiny server whose controller sees a stubbed burn
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import infinistore_tpu as ist  # noqa: E402
from infinistore_tpu.engine import InferenceEngine  # noqa: E402
from infinistore_tpu.kv import PagedCacheConfig  # noqa: E402
from infinistore_tpu.models import TINY, init_params, scaled  # noqa: E402
from infinistore_tpu.serve import ServingServer  # noqa: E402
from infinistore_tpu.utils.metrics import parse_prometheus_text  # noqa: E402

CFG = scaled(TINY, dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(3))


def _post(port, body, timeout=180):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    retry = resp.getheader("Retry-After")
    conn.close()
    return resp.status, json.loads(data), retry


def _get_json(port, path):
    return json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30).read())


def _metrics(port):
    raw = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
    return parse_prometheus_text(raw)


@pytest.fixture(scope="module")
def shed_server():
    """A tiny serving server whose ADMISSION controller reads a stub
    sampler (deterministic burn on demand); the real health sampler
    keeps feeding the flight recorder.  Lane 3 carries a tight
    token quota (40 tok/s, burst 40) for the quota/honor-Retry-After
    tests."""
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=160, block_tokens=4,
            dtype=CFG.dtype,
        ),
    )
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=4, model_id="tiny-adm",
                        slo_ttft_s=30.0, slo_tpot_s=5.0,
                        quotas="3:40:1")
    fake = StubSampler(ring=srv.health_sampler.ring)
    srv.admission.sampler = fake
    srv.start()
    yield srv, fake
    srv.close()


def _prime_lanes(srv, lanes=(0, 10)):
    for lane in lanes:
        st, body, _ = _post(srv.port, {
            "prompt": [17 + lane, 5, 9, 2], "max_tokens": 2,
            "temperature": 0, "priority": lane})
        assert st == 200, body


def test_live_shed_on_burn_429_with_retry_after(shed_server):
    srv, fake = shed_server
    fake.clear()
    _prime_lanes(srv)
    try:
        fake.fire_burn(3.0)
        st, body, retry = _post(srv.port, {
            "prompt": [1, 2, 3, 4], "max_tokens": 2, "temperature": 0,
            "priority": 0})
        assert st == 429, body
        assert body["reason"] == "burn" and "retry" in body["error"]
        assert retry is not None and int(retry) >= 1
        assert body["retry_after_s"] is not None
        # the protected lane keeps serving through the same burn
        st, body, _ = _post(srv.port, {
            "prompt": [9, 8, 7, 6], "max_tokens": 2, "temperature": 0,
            "priority": 10})
        assert st == 200, body
        # every transition is on /metrics and /debug/admission
        parsed = _metrics(srv.port)
        assert parsed.get(("istpu_admission_mode", ())) == 2.0
        assert parsed.get(("istpu_admission_shed_total",
                           (("lane", "0"), ("reason", "burn")))) >= 1.0
        assert parsed.get(("istpu_admission_decisions_total",
                           (("action", "admit"), ("lane", "10")))) >= 1.0
        adm = _get_json(srv.port, "/debug/admission")
        assert adm["mode"] == "shed"
        assert "0" in adm["burn"]["shed_lanes"]
        assert "10" not in adm["burn"]["shed_lanes"]
        # a ttft burn sheds but does NOT throttle prefill (prefill is
        # the path to first token); a tpot burn arms the throttle
        assert adm["prefill_throttle"]["active"] is False
        fake.fire_burn(3.0, rule="tpot_burn")
        adm2 = _get_json(srv.port, "/debug/admission")
        assert adm2["prefill_throttle"]["active"] is True
        fake.fire_burn(3.0)
        # /healthz: FIELD asserts only — the payload grows
        hz = _get_json(srv.port, "/healthz")
        assert hz["admission"]["mode"] == "shed"
        assert "0" in hz["admission"]["shed_lanes"]
    finally:
        fake.clear()
    # burn gone: the shed lane admits again, zero operator action
    st, body, _ = _post(srv.port, {
        "prompt": [4, 3, 2, 1], "max_tokens": 2, "temperature": 0,
        "priority": 0})
    assert st == 200, body
    assert _metrics(srv.port).get(("istpu_admission_mode", ())) == 1.0


def test_live_shed_never_cancels_admitted(shed_server):
    """The invariant: a request ADMITTED before the burn keeps decoding
    to completion while new submissions on its lane shed."""
    srv, fake = shed_server
    fake.clear()
    _prime_lanes(srv)
    out = {}

    def long_req():
        out["resp"] = _post(srv.port, {
            "prompt": [41, 42, 43, 44], "max_tokens": 48,
            "temperature": 0, "priority": 0})

    t = threading.Thread(target=long_req, daemon=True)
    t.start()
    # wait until it holds engine resources (admitted)
    deadline = time.time() + 20
    while time.time() < deadline:
        if (_metrics(srv.port).get(("istpu_serve_inflight", ()))
                or 0) >= 1:
            break
        time.sleep(0.02)
    try:
        fake.fire_burn(5.0)
        st, body, retry = _post(srv.port, {
            "prompt": [1, 2, 3], "max_tokens": 2, "temperature": 0,
            "priority": 0})
        assert st == 429 and retry is not None  # new work sheds...
        t.join(timeout=120)
        assert not t.is_alive()
        st, body, _ = out["resp"]
        assert st == 200, body  # ...the admitted request finished whole
        assert len(body["choices"][0]["token_ids"]) == 48
        assert body["choices"][0]["finish_reason"] == "length"
    finally:
        fake.clear()


def test_live_quota_throttle_and_honor_retry_after(shed_server):
    """Lane 3 carries a 40 tok/s (burst 40) quota: a large charge
    drains it deep into debt, the next submission answers 429 with the
    tenant's own refill Retry-After, and the loadgen client's single
    honor-Retry-After re-attempt lands after the refill."""
    from infinistore_tpu.loadgen import _http_post

    srv, fake = shed_server
    fake.clear()
    url = f"http://127.0.0.1:{srv.port}"
    body = {"prompt": [3] * 200, "max_tokens": 2, "temperature": 0,
            "priority": 3, "stream": False}
    st, resp, _ = _post(srv.port, body)  # charges 202 -> deep debt
    assert st == 200, resp
    r = _http_post(url, body, timeout_s=60)
    assert r["rejected"] and not r["ok"] and r["status"] == 429
    assert r["retry_after_s"] is not None and r["retry_after_s"] >= 1.0
    parsed = _metrics(srv.port)
    assert parsed.get(("istpu_admission_shed_total",
                       (("lane", "3"), ("reason", "quota")))) >= 1.0
    assert ("istpu_quota_tokens", (("tenant", "3"),)) in parsed
    # honor-Retry-After: one polite sleep, then the re-attempt admits
    r2 = _http_post(url, body, timeout_s=60, honor_retry_after=True,
                    retry_cap_s=15.0)
    assert r2.get("reattempted") is True
    assert r2["ok"] and not r2["rejected"], r2


def test_live_run_load_counts_rejected(shed_server):
    """An open-loop run against a shedding server: 429s land in
    `rejected` (per run and per lane), never in `errors`."""
    from infinistore_tpu.loadgen import LoadConfig, run_load, summarize

    srv, fake = shed_server
    fake.clear()
    _prime_lanes(srv)
    fake.fire_burn(3.0)
    try:
        cfg = LoadConfig(rate=20.0, n_requests=12, process="deterministic",
                         seed=5, mix=((1.0, 8, 2),),
                         lanes=((0, 2.0), (10, 1.0)),
                         n_prefixes=0, vocab=64, timeout_s=120.0)
        results, makespan = run_load(f"http://127.0.0.1:{srv.port}", cfg)
        s = summarize(results, makespan, slo_ttft_s=30.0, slo_tpot_s=5.0,
                      rate=20.0)
    finally:
        fake.clear()
    assert s["errors"] == 0, s
    assert s["rejected"] > 0  # lane 0 shed
    assert s["rejected"] == s["lanes"]["0"]["rejected"]
    assert s["lanes"]["10"]["rejected"] == 0
    assert s["lanes"]["10"]["completed"] == s["lanes"]["10"]["n"]
    assert s["completed"] + s["rejected"] == s["n"]


# ---------------------------------------------------------------------------
# THE chaos acceptance walk (ROADMAP item 3): FaultInjector overload ->
# burn pages -> lowest lane sheds 429+Retry-After while the protected
# lane's SLO holds -> burn clears with zero operator action
# ---------------------------------------------------------------------------

T = 4
ADM_ENV = {
    # tight windows so the walk fires and clears in test time
    "ISTPU_HEALTH_STEP_S": "0.2",
    "ISTPU_BURN_FAST_S": "3",
    "ISTPU_BURN_SLOW_S": "15",
}


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _boot_store(port, mport):
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **ADM_ENV},
    )
    deadline = time.time() + 25
    for p in (port, mport):
        while True:
            if proc.poll() is not None:
                pytest.fail("store process failed to start")
            try:
                socket.create_connection(("127.0.0.1", p),
                                         timeout=0.5).close()
                break
            except OSError:
                if time.time() >= deadline:
                    proc.kill()
                    pytest.fail(f"store port {p} did not come up")
                time.sleep(0.1)
    return proc


def _arm(mport, rules):
    req = urllib.request.Request(
        f"http://127.0.0.1:{mport}/faults", method="POST",
        data=json.dumps(rules).encode(),
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.load(r)


@pytest.fixture(scope="module")
def chaos_stack():
    """A serving server (1 s TTFT SLO, fast health windows) attached to
    a dedicated store whose FaultInjector cuts serving capacity on
    demand — the stack the overload chaos walk runs against."""
    old = {k: os.environ.get(k) for k in ADM_ENV}
    os.environ.update(ADM_ENV)
    port, mport = _free_port(), _free_port()
    proc = _boot_store(port, mport)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port,
        connection_type=ist.TYPE_SHM, op_timeout_s=5.0,
        log_level="error",
    ))
    conn.connect()
    eng = InferenceEngine(
        PARAMS, CFG,
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, n_blocks=192, block_tokens=T,
            dtype=CFG.dtype,
        ),
        conn=conn, model_id="adm-chaos", store_durability="relaxed",
    )
    eng.decode_chunk = 4
    srv = ServingServer(
        eng, port=0, max_batch=4, model_id="adm-chaos",
        slo_ttft_s=1.0,
        store_manage_endpoints=[f"127.0.0.1:{mport}"],
    )
    srv.start()
    yield srv, proc, port, mport
    srv.close()
    conn.close()
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _unique_prompt(counter, lane, n=9):
    i = counter[0]
    counter[0] += 1
    return [(37 * i + 11 + lane) % 250 + 1 for _ in range(1)] + [
        (i + j) % 250 + 1 for j in range(n - 1)]


def _wait(pred, deadline_s, tick=None, interval=0.15):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if pred():
            return True
        if tick is not None:
            tick()
        time.sleep(interval)
    return pred()


def test_chaos_overload_sheds_lowest_lane_then_recovers(chaos_stack):
    """THE acceptance walk, every transition scraped from /metrics:

    1. healthy two-lane traffic — admission mode 1, no burn;
    2. FaultInjector cuts capacity (store lookups answer late) and an
       open-loop lane-0 flood overloads the server → TTFT violations →
       ``ttft_burn`` fires page → ``istpu_admission_mode`` walks to 2;
    3. while shedding: lane-0 submissions answer 429 + Retry-After,
       the protected lane 10 keeps completing AND holds its TTFT SLO;
    4. flood ends, faults cleared (the outage ending — not an operator
       touching the admission plane): the backlog drains, the burn
       clears, mode walks back to 1, lane 0 admits again, /healthz ok.
    """
    from infinistore_tpu.loadgen import _http_post

    srv, _proc, _port, mport = chaos_stack
    url = f"http://127.0.0.1:{srv.port}"
    counter = [0]

    def ask(lane, max_tokens=2, timeout=120):
        return _post(srv.port, {
            "prompt": _unique_prompt(counter, lane),
            "max_tokens": max_tokens, "temperature": 0,
            "priority": lane}, timeout=timeout)

    # -- phase 0: healthy baseline on both lanes.  The stack is COLD: the
    # first request of a shape compiles on the request path, and on a CPU
    # that five other test workers load that alone overruns the 1 s target
    # (read: 2.04 s, then 0.87 s for the next program); in these windows
    # one violation of two finished requests is ``ttft_burn`` at 5x, and
    # the next lane-0 ask was shed (429 ``reason: burn``) before the walk
    # had begun.  So an ask rides out a shed (a wait on the condition
    # "the lane admits", not on a clock), and phase 0 ends as it always
    # did: burn clear, mode 1, /healthz ok.
    def admitted(lane):
        return _wait(lambda: ask(lane)[0] == 200, deadline_s=60)

    for _ in range(3):
        assert admitted(0)
        assert admitted(10)
    assert _wait(lambda: _metrics(srv.port).get(
        ("istpu_health_alert_active", (("rule", "ttft_burn"),))) == 0.0,
        deadline_s=10)
    parsed = _metrics(srv.port)
    assert parsed.get(("istpu_admission_mode", ())) == 1.0
    hz = _get_json(srv.port, "/healthz")
    assert hz["status"] == "ok" and hz["admission"]["mode"] == "normal"

    # -- phase 1: FaultInjector-induced overload.  Every admission's
    # store prefix lookup now takes 0.35 s of engine-thread time, so
    # capacity drops under the flood's offered rate and the queue grows
    _arm(mport, [{"op": "MATCH_LAST_IDX", "action": "delay",
                  "delay_s": 0.35}])
    flood_results: list = []
    flood_threads: list = []
    stop_flood = threading.Event()

    def flood_one():
        st, body, retry = ask(0, timeout=300)
        flood_results.append((st, retry))

    def flood_pacer():
        # an initial concurrent burst puts real queue depth on the
        # server at once, then a steady over-capacity trickle keeps the
        # violations coming until shedding is observed
        for _ in range(10):
            t = threading.Thread(target=flood_one, daemon=True)
            t.start()
            flood_threads.append(t)
        while not stop_flood.is_set() and len(flood_threads) < 60:
            t = threading.Thread(target=flood_one, daemon=True)
            t.start()
            flood_threads.append(t)
            time.sleep(0.25)

    pacer = threading.Thread(target=flood_pacer, daemon=True)
    pacer.start()
    try:
        # burn fires and the controller walks to shedding — scraped
        fired = _wait(lambda: (
            _metrics(srv.port).get(
                ("istpu_health_alert_active",
                 (("rule", "ttft_burn"),))) == 1.0
            and _metrics(srv.port).get(
                ("istpu_admission_mode", ())) == 2.0
        ), deadline_s=40)
        assert fired, _get_json(srv.port, "/debug/health")["alerts"]

        # -- phase 2: shedding.  Lane 0 answers 429 + Retry-After...
        def saw_shed():
            return any(st == 429 for st, _r in flood_results)

        assert _wait(saw_shed, deadline_s=20)
        st, body, retry = ask(0)
        if st == 429:  # the direct probe (burn may clear mid-probe)
            assert retry is not None and int(retry) >= 1
            assert body["reason"] in ("burn", "pressure")
        sheds = [r for s, r in flood_results if s == 429]
        assert sheds and all(r is not None for r in sheds)

        # ...while the protected lane keeps completing AND holds its
        # TTFT SLO (client-observed, streaming first-token stamps)
        stop_flood.set()
        protected = []
        for _ in range(6):
            r = _http_post(url, {
                "prompt": _unique_prompt(counter, 10),
                "max_tokens": 2, "temperature": 0, "priority": 10,
                "stream": True}, timeout_s=120)
            protected.append(r)
        assert all(r["ok"] for r in protected), protected
        met = [r for r in protected
               if r["ttft_s"] is not None and r["ttft_s"] <= 1.0]
        assert len(met) >= 4, [r["ttft_s"] for r in protected]

        parsed = _metrics(srv.port)
        assert parsed.get(("istpu_admission_shed_total",
                           (("lane", "0"), ("reason", "burn")))) >= 1.0
        # the protected lane was never burn-shed
        assert parsed.get(("istpu_admission_shed_total",
                           (("lane", "10"), ("reason", "burn")))) is None
        assert parsed.get(("istpu_health_alerts_total",
                           (("rule", "ttft_burn"),
                            ("severity", "page")))) >= 1.0
    finally:
        stop_flood.set()
        _arm(mport, [])

    # -- phase 3: recovery with ZERO operator action on the admission
    # plane (only the injected outage ended).  The held backlog drains,
    # the burn clears, the mode walks back to normal.
    for t in flood_threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in flood_threads)
    # the never-cancel invariant, fleet-wide: every flooded request was
    # either completed (200) or shed at the door (429) — never dropped
    assert len(flood_results) == len(flood_threads)
    assert all(st in (200, 429) for st, _r in flood_results), \
        sorted({st for st, _r in flood_results})

    def healthy_tick():
        ask(10)

    cleared = _wait(lambda: (
        _metrics(srv.port).get(
            ("istpu_health_alert_active",
             (("rule", "ttft_burn"),))) == 0.0
        and _metrics(srv.port).get(("istpu_admission_mode", ())) == 1.0
    ), deadline_s=60, tick=healthy_tick)
    assert cleared, _get_json(srv.port, "/debug/health")["alerts"]
    st, body, _ = ask(0)
    assert st == 200, body  # the shed lane admits again
    # fired AND cleared are on the health record; /healthz is ok again
    h = _get_json(srv.port, "/debug/health")
    tos = {(t["rule"], t["to"]) for t in h["transitions"]}
    assert ("ttft_burn", "firing") in tos
    assert ("ttft_burn", "cleared") in tos
    assert _wait(lambda: _get_json(srv.port, "/healthz")["status"] == "ok",
                 deadline_s=20)
    hz = _get_json(srv.port, "/healthz")
    assert hz["admission"]["mode"] == "normal"
    assert hz["admission"]["shed_total"] >= 1


# ---------------------------------------------------------------------------
# the goodput plateau (slow): bench_serve sweep past saturation with
# admission ON plateaus where OFF collapses
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_goodput_plateau_with_admission_on_vs_collapse_off(tmp_path,
                                                           monkeypatch):
    """The proof artifact behind ROADMAP item 3: the same overload
    sweep (two lanes, rates far past the tiny model's capacity) run
    twice.  With ISTPU_ADMISSION=0 the goodput curve collapses past
    saturation; with admission ON the low lane sheds, the protected
    lane keeps meeting its SLO, and the curve plateaus — captured in
    the --json-out `admission` block and its `plateau` flag."""
    import bench_serve

    for k, v in ADM_ENV.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("ISTPU_SLO_TPOT_S", "5.0")

    def run(out, admission_on):
        monkeypatch.setenv("ISTPU_ADMISSION", "1" if admission_on else "0")
        rc = bench_serve.main([
            "--self-serve", "--self-serve-batch", "2",
            "--rates", "2,8,24", "--n", "24",
            "--mix", "1:12:16", "--lanes", "0:3,10:1",
            "--prefixes", "0", "--slo-ttft", "1.0", "--slo-tpot", "5.0",
            "--timeout", "300", "--cooldown", "6",
            "--json-out", str(out),
        ])
        assert rc == 0
        return json.loads(out.read_text())

    off = run(tmp_path / "off.json", admission_on=False)
    on = run(tmp_path / "on.json", admission_on=True)
    # admission ON shed load (the low lane) and kept a plateau
    assert on["admission"]["rejected_total"] > 0, on["admission"]
    assert on["admission"]["plateau"] is True, on["admission"]
    assert on["goodput_plateau"] == 1
    # OFF queued without bound: no sheds, and goodput at the overload
    # point collapsed relative to ON's
    assert off["admission"]["rejected_total"] == 0
    on_last = on["curve"][-1]["goodput_rps"]
    off_last = off["curve"][-1]["goodput_rps"]
    assert on_last > off_last, (on_last, off_last)
