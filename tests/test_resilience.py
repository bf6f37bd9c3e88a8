"""Resilient store data plane: per-op deadlines, circuit-breaker degraded
serving, and the deterministic fault-injection harness.

The contract under test (docs/robustness.md): a store-tier failure — dead
server, hung server, flapping server, mid-op connection kill — degrades
serving to recompute, never to a user-visible error or an unbounded hang.
Every scenario here is driven deterministically through the python
server's ``FaultInjector`` (manage-plane ``POST /faults``), not through
sleep-and-hope races.
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

import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu.utils import metrics as m
from infinistore_tpu.utils.resilience import (
    CircuitBreaker,
    Deadline,
    RetryPolicy,
)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _boot(port, mport, extra_env=None):
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})},
    )
    deadline = time.time() + 25
    for p in (port, mport):
        while True:
            if proc.poll() is not None:
                pytest.fail("server process failed to start")
            try:
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                break
            except OSError:
                if time.time() >= deadline:
                    proc.kill()
                    pytest.fail(f"server port {p} did not come up")
                time.sleep(0.1)
    return proc


def _stop(proc):
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def _arm(mport, rules):
    req = urllib.request.Request(
        f"http://127.0.0.1:{mport}/faults", method="POST",
        data=json.dumps(rules).encode(),
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.load(r)


def _healthz(mport):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{mport}/healthz", timeout=10
    ) as r:
        return json.load(r)


def _store_metrics(mport):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{mport}/metrics", timeout=10
    ) as r:
        return m.parse_prometheus_text(r.read().decode())


@pytest.fixture(scope="module")
def server():
    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    yield port, mport
    _stop(proc)


@pytest.fixture(autouse=True)
def _clear_faults(server):
    yield
    try:
        _arm(server[1], [])
    except OSError:
        pass


def _conn(port, op_timeout_s=None, **kw):
    c = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port,
        connection_type=ist.TYPE_SHM, op_timeout_s=op_timeout_s,
        log_level="error", **kw,
    ))
    c.connect()
    return c


# ---- resilience primitives (no server) ----


def test_deadline_and_retry_policy_budget():
    now = [0.0]
    dl = Deadline(5.0, time_fn=lambda: now[0])
    assert not dl.expired and dl.remaining() == 5.0
    now[0] = 4.0
    assert dl.remaining(cap=10.0) == pytest.approx(1.0)
    now[0] = 5.0
    assert dl.expired and dl.remaining() == 0.0
    assert Deadline(None).remaining() is None

    # attempts bound: max_attempts=3 -> 2 sleeps between 3 tries
    p = RetryPolicy(max_attempts=3, base_delay_s=0.01, budget_s=100.0,
                    jitter=False, time_fn=lambda: 0.0)
    assert list(p.backoff()) == [0.01, 0.02]
    # budget bound: the clock advances past the budget -> generator ends
    t = [0.0]
    p = RetryPolicy(max_attempts=0, base_delay_s=0.01, budget_s=1.0,
                    jitter=False, time_fn=lambda: t[0])
    it = p.backoff()
    assert next(it) == 0.01
    t[0] = 2.0
    assert next(it, None) is None
    # full jitter stays within (0, delay]
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, jitter=True,
                    rng=lambda: 0.5, time_fn=lambda: 0.0)
    assert list(p.backoff())[:2] == [0.05, 0.1]

    # run(): retries then surfaces the last error
    calls = []

    def flaky():
        calls.append(1)
        raise ValueError("nope")

    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=3, base_delay_s=0.001).run(
            flaky, retry_on=(ValueError,), sleep=lambda _s: None
        )
    assert len(calls) == 3


def test_circuit_breaker_transitions_and_metrics():
    now = [0.0]
    reg = m.MetricsRegistry()
    cb = CircuitBreaker(name="t", failure_threshold=2, cooldown_s=10.0,
                        registry=reg, time_fn=lambda: now[0])
    assert cb.state == "closed" and cb.allow()
    cb.record_failure()
    assert cb.state == "closed"  # below threshold
    cb.record_failure()
    assert cb.state == "open" and not cb.allow()
    # a success between failures resets the consecutive count
    cb2 = CircuitBreaker(name="t2", failure_threshold=2, registry=reg)
    cb2.record_failure()
    cb2.record_success()
    cb2.record_failure()
    assert cb2.state == "closed"
    # cooldown elapses -> half-open, exactly ONE probe
    now[0] = 10.0
    assert cb.allow() and cb.state == "half-open"
    assert not cb.allow()  # second caller: probe already in flight
    # probe failure reopens with a fresh cooldown
    cb.record_failure()
    assert cb.state == "open"
    now[0] = 15.0
    assert not cb.allow()  # fresh cooldown from t=10
    now[0] = 20.0
    assert cb.allow()
    cb.record_success()
    assert cb.state == "closed" and cb.allow()
    # the transition history is scrapeable
    parsed = m.parse_prometheus_text(reg.to_prometheus_text())
    trans = {
        labels: v for (name, labels), v in parsed.items()
        if name == "istpu_store_circuit_transitions_total"
        and ("name", "t") in labels
    }
    by_to = {dict(k)["to"]: v for k, v in trans.items()}
    assert by_to == {"open": 2.0, "half-open": 2.0, "closed": 1.0}


def test_prometheus_text_parser_roundtrip():
    reg = m.MetricsRegistry()
    reg.counter("a_total", "help", labelnames=("x",)).labels("v 1").inc(3)
    reg.gauge("b").set(2.5)
    parsed = m.parse_prometheus_text(reg.to_prometheus_text())
    assert parsed[("a_total", (("x", "v 1"),))] == 3.0
    assert parsed[("b", ())] == 2.5


# ---- fault injection + client deadlines over the wire ----


def test_hung_op_fails_within_deadline_then_recovers(server):
    """The acceptance hang: a stalled GET_DESC must fail within
    op_timeout_s (never block unboundedly), kill the channel so FIFO
    matching stays sound, and recover through the normal reconnect path
    once the stall clears."""
    port, mport = server
    conn = _conn(port, op_timeout_s=1.0)
    src = np.arange(4096, dtype=np.float32)
    dst = np.zeros_like(src)
    conn.register_mr(src)
    conn.register_mr(dst)
    conn.write_cache([("hang-k", 0)], 4096 * 4, src.ctypes.data)

    assert _arm(mport, [{"op": "GET_DESC", "action": "stall"}])["armed"] == 1
    assert _healthz(mport)["status"] == "degraded"

    t0 = time.perf_counter()
    with pytest.raises(ist.InfiniStoreConnectionError):
        # reconnect retries once (the stall persists), so the op costs at
        # most ~2 deadlines — bounded either way
        conn.read_cache([("hang-k", 0)], 4096 * 4, dst.ctypes.data)
    dt = time.perf_counter() - t0
    assert dt < 5.0, f"hung op took {dt:.1f}s — deadline did not bound it"

    _arm(mport, [])
    assert _healthz(mport)["status"] == "ok"
    conn.read_cache([("hang-k", 0)], 4096 * 4, dst.ctypes.data)
    np.testing.assert_array_equal(src, dst)
    conn.close()


def test_injected_error_is_absorbed_by_reconnect(server):
    """A single injected SYSTEM_ERROR is a transport failure the client's
    reconnect-and-retry absorbs transparently; the injection is visible in
    the store's fault counter."""
    port, mport = server
    conn = _conn(port, op_timeout_s=5.0)
    before = _store_metrics(mport).get(
        ("istpu_store_faults_injected_total",
         (("action", "error"), ("op", "EXIST"))), 0.0)
    _arm(mport, [{"op": "EXIST", "action": "error", "times": 1}])
    assert conn.check_exist("whatever") is False  # retried, then answered
    after = _store_metrics(mport)[
        ("istpu_store_faults_injected_total",
         (("action", "error"), ("op", "EXIST")))]
    assert after == before + 1
    conn.close()


def test_injected_delay_slows_only_matching_ops(server):
    port, mport = server
    conn = _conn(port, op_timeout_s=5.0)
    _arm(mport, [{"op": "EXIST", "action": "delay", "delay_s": 0.4}])
    t0 = time.perf_counter()
    conn.check_exist("delayed")
    assert time.perf_counter() - t0 >= 0.4
    # non-matching op is unaffected
    t0 = time.perf_counter()
    with pytest.raises(ist.InfiniStoreException):
        conn.get_match_last_index(["zz-nomatch"])
    assert time.perf_counter() - t0 < 0.3
    conn.close()


def test_drop_conn_after_skips_then_kills(server):
    """``after`` makes mid-batch kills deterministic: the first N matching
    ops pass, the N+1st dies mid-op."""
    port, mport = server
    conn = _conn(port, op_timeout_s=5.0)
    _arm(mport, [{"op": "EXIST", "action": "drop_conn", "after": 1,
                  "times": 1}])
    assert conn.check_exist("nope-1") is False  # the free pass
    # second EXIST: connection killed mid-op -> reconnect retries -> rule
    # exhausted (times=1) -> succeeds transparently
    assert conn.check_exist("nope-2") is False
    conn.close()


def test_concurrent_pipelined_ops_survive_server_restart():
    """Two threads mid pipelined write/read while the server is killed and
    restarted: every op either completes or raises a connection-class
    error — never hangs, never interleaves corrupt data.  Byte parity is
    re-verified end to end after recovery."""
    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    nb, blk = 16, 16 << 10
    stop = threading.Event()
    errs = []

    def worker(wid):
        conn = _conn(port, op_timeout_s=2.0, auto_reconnect=True)
        src = (np.arange(nb * blk, dtype=np.uint8) + wid).astype(np.uint8)
        dst = np.zeros_like(src)
        conn.register_mr(src)
        conn.register_mr(dst)
        it = 0
        try:
            while not stop.is_set():
                it += 1
                blocks = [(f"cw{wid}-{it}-{i}", i * blk) for i in range(nb)]
                try:
                    conn.write_cache_pipelined([(blocks, blk, src.ctypes.data)])
                    dst[:] = 0
                    conn.read_cache_pipelined(
                        [(blocks, blk, dst.ctypes.data)]
                    )
                    if not np.array_equal(src, dst):
                        errs.append((wid, "corrupt data after read"))
                        return
                except (ist.InfiniStoreException, OSError):
                    # outage window: connection-class failures are the
                    # contract; anything else (hang, corruption) is not
                    time.sleep(0.05)
        except BaseException as e:  # noqa: BLE001
            errs.append((wid, repr(e)))

    threads = [threading.Thread(target=worker, args=(w,)) for w in (1, 2)]
    for t in threads:
        t.start()
    try:
        time.sleep(1.0)          # both threads mid-traffic
        proc.kill()              # hard kill, no goodbye
        proc.wait(timeout=10)
        time.sleep(1.0)          # threads churn against the dead server
        proc = _boot(port, mport)
        time.sleep(2.0)          # threads recover and keep verifying parity
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), "worker hung"
    assert not errs, errs

    # post-recovery parity through a fresh connection
    conn = _conn(port, op_timeout_s=2.0)
    src = np.random.randint(0, 256, nb * blk, dtype=np.uint8)
    dst = np.zeros_like(src)
    conn.register_mr(src)
    conn.register_mr(dst)
    blocks = [(f"post-{i}", i * blk) for i in range(nb)]
    conn.write_cache_pipelined([(blocks, blk, src.ctypes.data)])
    conn.read_cache_pipelined([(blocks, blk, dst.ctypes.data)])
    np.testing.assert_array_equal(src, dst)
    conn.close()
    _stop(proc)


# ---- periodic-evict loop resilience (in-process) ----


def test_periodic_evict_survives_store_errors():
    """The evict task must survive a raising ``Store.evict`` — before this
    fix it died permanently and silently, ending in a full pool."""
    import asyncio

    from infinistore_tpu.config import ServerConfig
    from infinistore_tpu.pyserver import StoreServer

    config = ServerConfig(
        service_port=_free_port(), manage_port=_free_port(),
        prealloc_size=1, minimal_allocate_size=64, backend="python",
        evict_interval=0.01,
    )
    srv = StoreServer(config)
    calls = []

    def boom(mn, mx):
        calls.append(1)
        if len(calls) <= 2:
            raise RuntimeError("evict blew up")
        return 0

    srv.store.evict = boom

    async def run():
        srv.start_periodic_evict()
        while len(calls) < 4:  # survived the 2 failures and kept running
            await asyncio.sleep(0.01)
        assert not srv._evict_task.done()
        srv._evict_task.cancel()

    try:
        asyncio.run(asyncio.wait_for(run(), timeout=10))
    finally:
        srv.store.evict = lambda mn, mx: 0
        srv.store.close()
    assert srv._c_evict_err.value == 2
    assert srv.degraded()  # evict errors flip the store health signal


# ---- engine + serving degradation (the chaos acceptance test) ----


jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from infinistore_tpu.engine import InferenceEngine, StoreConnector  # noqa: E402
from infinistore_tpu.kv import PagedCacheConfig  # noqa: E402
from infinistore_tpu.models import TINY, init_params, scaled  # noqa: E402
from infinistore_tpu.serve import ServingServer  # noqa: E402

from conftest import WALK_SLO, make_dense_greedy  # noqa: E402

CFG = scaled(TINY, dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(7))
T = 4
PROMPT = [11, 42, 7, 99, 5, 3, 17, 28, 64, 1, 2]

dense_greedy = make_dense_greedy(PARAMS, CFG)


def make_pc(n_blocks=64):
    return PagedCacheConfig(
        n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
        head_dim=CFG.head_dim, n_blocks=n_blocks, block_tokens=T,
        dtype=CFG.dtype,
    )


def test_streamer_counts_drops_and_reports_them_at_flush(server):
    """Satellite: a parked push error must not silently eat the queued
    pushes behind it — they are counted, and the flush-time re-raise
    names the blast radius."""
    port, _ = server
    conn = _conn(port, op_timeout_s=5.0)
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=conn, model_id="drop-count",
        prefill_chunk=T, store_durability="relaxed",
    )

    def boom(token):
        raise RuntimeError("push failed hard")

    # the streamer's worker half is push_commit (push_begin runs on the
    # submitting thread and must stay cheap/unfailing)
    eng.transfer.push_commit = boom
    before = m.parse_prometheus_text(
        m.default_registry().to_prometheus_text()
    ).get(("istpu_store_push_dropped_total", (("reason", "push_error"),)), 0.0)
    st = eng.prefill(PROMPT)  # 2 complete chunks -> 2 failed pushes
    with pytest.raises(RuntimeError, match=r"push failed hard.*2 queued"):
        eng.store_flush()
    eng.store_flush()  # parked state cleared; barrier reusable
    after = m.parse_prometheus_text(
        m.default_registry().to_prometheus_text()
    )[("istpu_store_push_dropped_total", (("reason", "push_error"),))]
    assert after >= before + 1
    eng.release(st)
    conn.close()


def _prompt(i):
    """Distinct 11-token prompts (same length -> same compiled shapes; the
    first token varies, so chunk keys never collide across prompts —
    repeated prompts would hit the engine's LOCAL prefix cache and make
    no store hop at all).  Keep i < 450: TINY's vocab is 512."""
    assert i < 450, i
    return [50 + i] + PROMPT[1:]


def test_engine_degrades_to_recompute_and_circuit_opens(server):
    """Store dying mid-load: lookup says hit, the load's connection is
    killed mid-op — prefill must fall back to recompute (correct greedy
    tokens).  Then a full outage (every op answered with SYSTEM_ERROR)
    opens the circuit, after which prefills skip the store outright."""
    port, mport = server
    # producer: make one prefix store-resident
    prod = _conn(port, op_timeout_s=5.0)
    a = InferenceEngine(PARAMS, CFG, make_pc(), conn=prod,
                        model_id="chaos-eng")
    a.release(a.prefill(_prompt(0)))
    a.store_flush()

    cons = _conn(port, op_timeout_s=1.0)
    b = InferenceEngine(PARAMS, CFG, make_pc(), conn=cons,
                        model_id="chaos-eng", store_durability="relaxed")
    b.breaker.failure_threshold = 2
    b.breaker.cooldown_s = 30.0
    # warmup: compile the prefill/decode shapes against a healthy store so
    # the open-circuit timing assertion below measures hops, not XLA
    st = b.prefill(_prompt(1))
    assert b.decode(st, 8) == dense_greedy(_prompt(1), 8)
    b.release(st)
    b.store_flush()

    # kill every GET_DESC mid-op: lookup (MATCH/EXIST) still answers, the
    # LOAD dies — the deterministic "store killed mid-load" failure
    _arm(mport, [{"op": "GET_DESC", "action": "drop_conn"}])
    st = b.prefill(_prompt(0))  # store-resident prefix from the producer
    assert st.reused_chunks == 0  # hit withdrawn -> full recompute
    assert b.decode(st, 8) == dense_greedy(_prompt(0), 8)
    b.release(st)
    assert b.breaker.state == "closed"  # one load failure < threshold

    # full outage: every op (HELLO included, so reconnects fail too)
    # answers SYSTEM_ERROR — fast deterministic transport failures
    _arm(mport, [{"op": "*", "action": "error"}])
    for i in (2, 3):
        st = b.prefill(_prompt(i))
        assert st.reused_chunks == 0
        assert b.decode(st, 8) == dense_greedy(_prompt(i), 8)
        b.release(st)
    deadline = time.time() + 5  # relaxed pushes fail asynchronously
    while b.breaker.state != "open" and time.time() < deadline:
        time.sleep(0.02)
    assert b.breaker.state == "open"

    # circuit open: the store is skipped outright — no timeout tax
    t0 = time.perf_counter()
    st = b.prefill(_prompt(4))
    skip_dt = time.perf_counter() - t0
    assert st.reused_chunks == 0
    assert skip_dt < 0.9, f"open circuit still paid a store hop ({skip_dt:.2f}s)"
    b.release(st)
    _arm(mport, [])
    prod.close()
    cons.close()


def test_connector_degrades_instead_of_raising(server):
    """The LMCache-style connector surface: lookup/retrieve report miss
    and store_kv reports 0 bytes when the store hop dies."""
    from infinistore_tpu.kv.cache import init_cache

    port, mport = server
    conn = _conn(port, op_timeout_s=1.0)
    sc = StoreConnector(conn, make_pc(), model_id="conn-degrade")
    sc.breaker.failure_threshold = 1
    cache = init_cache(make_pc())
    _arm(mport, [{"op": "MATCH_LAST_IDX", "action": "drop_conn"}])
    assert sc.lookup(PROMPT) == 0
    assert sc.breaker.state == "open"
    _cache2, got = sc.retrieve_kv(PROMPT, cache, [0, 1])
    assert got == 0  # circuit open: skipped, not raised
    # store_kv under an open circuit is a counted drop, not an exception
    assert sc.store_kv(PROMPT[:T], cache, [0]) == 0
    _arm(mport, [])
    conn.close()


@pytest.fixture(scope="module")
def chaos_stack():
    """A serving server attached to a dedicated store subprocess, tuned
    for fast breaker transitions."""
    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    conn = _conn(port, op_timeout_s=1.0)
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(n_blocks=128), conn=conn,
        model_id="chaos-serve", store_durability="relaxed",
    )
    eng.decode_chunk = 4
    eng.breaker.failure_threshold = 2
    eng.breaker.cooldown_s = 0.5
    srv = ServingServer(eng, port=0, max_batch=4, model_id="chaos-serve",
                        **WALK_SLO)
    srv.start()
    yield srv, proc, port, mport
    srv.close()
    conn.close()
    _stop(proc)


def _post(port, body, timeout=180, path="/v1/completions"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data)


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_chaos_serving_completes_every_request(chaos_stack):
    """THE acceptance chaos test: with the store killed mid-load and then
    stalled, a multi-request workload completes EVERY request via
    recompute with zero error deliveries; the circuit walks open ->
    half-open -> closed across recovery, observable in /metrics; and
    /healthz flips degraded <-> ok.

    Every request uses a DISTINCT prompt (same length, first token
    varies): a repeated prompt would be served by the engine's local
    prefix cache with no store hop at all."""
    srv, proc, port, mport = chaos_stack
    n = [100]

    def ask(prompt=None):
        p = prompt if prompt is not None else _prompt(n[0])
        if prompt is None:
            n[0] += 1
        status, body = _post(srv.port, {
            "prompt": p, "max_tokens": 6, "temperature": 0,
        })
        assert status == 200, body
        assert body["choices"][0]["token_ids"] == dense_greedy(p, 6), body
        return body

    # phase 0: healthy — requests complete, pages land in the store; a
    # producer engine seeds a prefix the SERVING engine has never seen
    # locally (the mid-load-kill victim below)
    ask()
    prod_conn = _conn(port, op_timeout_s=5.0)
    prod = InferenceEngine(PARAMS, CFG, make_pc(), conn=prod_conn,
                           model_id="chaos-serve")
    victim = _prompt(200)
    prod.release(prod.prefill(victim))
    prod.store_flush()
    st, data = _get(srv.port, "/healthz")
    assert st == 200 and json.loads(data)["status"] == "ok"

    # phase 1a: the store dies MID-LOAD — lookup still answers, every
    # GET_DESC connection is killed, so the store-resident prefix is
    # found and then its load dies mid-op.  The request must complete
    # via recompute.
    _arm(mport, [{"op": "GET_DESC", "action": "drop_conn", "times": 8}])
    ask(victim)
    parsed = _store_metrics(mport)
    assert parsed.get(("istpu_store_faults_injected_total",
                       (("action", "drop_conn"), ("op", "GET_DESC"))), 0) >= 1

    # phase 1b: then the store HANGS (stall on everything — HELLO too, so
    # reconnect probes hang as well): requests keep completing, failures
    # accumulate, the circuit opens
    _arm(mport, [{"op": "*", "action": "stall"}])
    for _ in range(3):  # multi-request workload through the outage
        ask()  # every request completes via recompute — zero errors
    deadline = time.time() + 10  # relaxed pushes fail asynchronously
    while srv.engine.breaker.state != "open" and time.time() < deadline:
        time.sleep(0.05)
    assert srv.engine.breaker.state == "open"
    st, data = _get(srv.port, "/healthz")
    health = json.loads(data)
    assert health["status"] == "degraded" and health["store_circuit"] == "open"

    # while open: store hops are skipped outright — no per-request
    # timeout tax (each hop would otherwise pay >= op_timeout_s)
    t0 = time.perf_counter()
    ask()
    assert time.perf_counter() - t0 < 0.9

    # phase 2: recovery — faults cleared, cooldown elapses, the next
    # request's lookup is the half-open probe and closes the circuit
    _arm(mport, [])
    time.sleep(srv.engine.breaker.cooldown_s + 0.1)
    deadline = time.time() + 30
    while srv.engine.breaker.state != "closed" and time.time() < deadline:
        ask()
        time.sleep(0.05)
    assert srv.engine.breaker.state == "closed"
    deadline = time.time() + 10  # a clean idle flush clears the flag
    while time.time() < deadline:
        st, data = _get(srv.port, "/healthz")
        if json.loads(data)["status"] == "ok":
            break
        time.sleep(0.1)
    assert json.loads(data)["status"] == "ok", data

    # the full walk is in the serving /metrics exposition
    st, data = _get(srv.port, "/metrics")
    parsed = m.parse_prometheus_text(data.decode())
    trans = {
        dict(labels).get("to"): v for (name, labels), v in parsed.items()
        if name == "istpu_store_circuit_transitions_total"
        and dict(labels).get("name") == "store"
    }
    assert trans.get("open", 0) >= 1, trans
    assert trans.get("half-open", 0) >= 1, trans
    assert trans.get("closed", 0) >= 1, trans
    degraded = sum(
        v for (name, labels), v in parsed.items()
        if name == "istpu_store_degraded_ops_total"
    )
    assert degraded >= 1
    # circuit state gauge is exported and currently closed
    assert parsed.get(
        ("istpu_store_circuit_state", (("name", "store"),))) == 0.0
    prod_conn.close()


def test_serve_healthz_without_store():
    """A storeless server is simply ok — no circuit field, no degraded."""
    eng = InferenceEngine(PARAMS, CFG, make_pc())
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=2, model_id="no-store")
    srv.start()
    try:
        st, data = _get(srv.port, "/healthz")
        body = json.loads(data)
        assert st == 200 and body["status"] == "ok"
        # no store -> no circuit field, nothing degraded (the health
        # plane's alerts block rides along with zero firing)
        assert "store_circuit" not in body and "reason" not in body
        assert body.get("alerts", {}).get("firing", 0) == 0
        # where it runs, as JAX reports it: a launcher that must stay
        # off JAX (one process per chip) learns the device from here
        import jax

        assert body["device"] == {
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }
    finally:
        srv.close()
