"""Live ring membership: join/drain with background migration under
traffic, the per-request streamer flush marker, and THE 3→4→3 node walk.

The unit half drives ``RoutedStorePool`` membership over fake in-memory
connections (migration routing is pure bookkeeping + two wire verbs);
the live half runs a serving server over real store subprocesses, walks
the fleet 3→4→3 through ``POST /debug/cluster`` WHILE an open-loop
loadgen flood runs, and asserts zero failed requests with store-hit
provenance recovering after each transition — ROADMAP item 4's
acceptance."""

import ctypes
import http.client
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from infinistore_tpu.cluster import HashRing, RoutedStorePool
from infinistore_tpu.utils import metrics as m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# membership units over fake connections
# ---------------------------------------------------------------------------


STORES = {}


class FakeConn:
    """The four verbs migration needs, over an in-memory dict per
    endpoint — mimics the public ``InfinityConnection`` surface the
    pool's nodes hold."""

    def __init__(self, ep):
        self.ep = ep

    def connect(self):
        if STORES.get(self.ep) is None:
            raise ConnectionError(f"{self.ep} unreachable")

    def close(self):
        pass

    def list_keys(self, limit=0):
        return list(STORES[self.ep])

    def check_exist(self, key):
        return key in STORES[self.ep]

    def tcp_read_cache(self, key):
        from infinistore_tpu.lib import InfiniStoreKeyNotFound

        if key not in STORES[self.ep]:
            raise InfiniStoreKeyNotFound(key)
        return np.frombuffer(STORES[self.ep][key], dtype=np.uint8).copy()

    def tcp_write_cache(self, key, ptr, size):
        STORES[self.ep][key] = bytes(
            (ctypes.c_ubyte * size).from_address(ptr))


def _fake_pool(n=3, **kw):
    eps = [f"10.9.0.{i}:5000" for i in range(1, n + 1)]
    for ep in eps:
        STORES[ep] = {}
    return RoutedStorePool(eps, conn_factory=FakeConn, **kw), eps


def _seed(pool, n=200):
    keys = [f"mem:k{i}#L0" for i in range(n)]
    for k in keys:
        STORES[pool.ring.owner(k)][k] = f"payload-{k}".encode()
    return keys


def _wait_idle(pool, timeout=10.0):
    deadline = time.time() + timeout
    while not pool.migration_idle():
        assert time.time() < deadline, "migration did not finish"
        time.sleep(0.02)


def test_join_migrates_exactly_the_new_nodes_range():
    pool, eps = _fake_pool()
    keys = _seed(pool)
    old_ring = pool.ring.clone()
    new_ep = "10.9.0.9:5000"
    STORES[new_ep] = {}
    pool.join_node(new_ep)
    _wait_idle(pool)
    rep = pool.migration_report()
    assert rep["state"] == "done" and rep["mode"] == "join"
    assert rep["errors"] == 0
    moved = [k for k in keys if pool.ring.owner(k) == new_ep]
    assert moved, "a joined node must own a share"
    # exactly the ~1/N range: every key the new ring assigns it arrived,
    # and nothing else did
    assert set(STORES[new_ep]) == set(moved)
    assert rep["copied"] == len(moved)
    # the consistent-hashing contract held: no key shuffled among the
    # OLD nodes
    for k in keys:
        if k not in moved:
            assert pool.ring.owner(k) == old_ring.owner(k)
    assert pool.membership(new_ep) == "active"
    pool.close()


def test_candidates_ride_old_owner_during_transition():
    """While a migration runs, the PRE-change owner rides the end of the
    candidate walk — reads stay correct before the copy lands."""
    pool, eps = _fake_pool()
    keys = _seed(pool, 50)
    new_ep = "10.9.0.9:5000"
    STORES[new_ep] = {}
    # stall the migrator so the transition window stays open
    real_copy = pool._copy_key
    gate = threading.Event()

    def slow_copy(key, src, dst):
        gate.wait(5)
        return real_copy(key, src, dst)

    pool._copy_key = slow_copy
    pool.join_node(new_ep)
    try:
        assert pool.membership(new_ep) == "joining"
        moved = [k for k in keys if pool.ring.owner(k) == new_ep]
        assert moved
        k = moved[0]
        cands = pool.candidates(k)
        assert cands[0] == new_ep
        old_owner = HashRing(eps, vnodes=pool.ring.vnodes).owner(k)
        assert old_owner in cands, \
            "migration reads must fail over to the pre-change owner"
        rep = pool.report()
        by_ep = {n["endpoint"]: n for n in rep["nodes"]}
        assert by_ep[new_ep]["membership"] == "joining"
        assert rep["migration"]["state"] == "running"
    finally:
        gate.set()
        _wait_idle(pool)
    # transition over: the old owner drops off the walk
    k = [k for k in keys if pool.ring.owner(k) == new_ep][0]
    assert len(pool.candidates(k)) == pool.replicas
    pool.close()


def test_drain_copies_range_out_then_forgets_the_node():
    pool, eps = _fake_pool()
    keys = _seed(pool)
    victim = eps[1]
    owned = [k for k in keys if pool.ring.owner(k) == victim]
    assert owned
    pool.drain_node(victim)
    assert pool.membership(victim) == "draining"
    # writes already exclude the draining node (it left the ring)
    for k in keys:
        assert victim not in pool.write_targets(k)
    _wait_idle(pool)
    rep = pool.migration_report()
    assert rep["state"] == "done" and rep["mode"] == "drain"
    assert rep["errors"] == 0
    assert victim not in pool.endpoints and victim not in pool._nodes
    # every key the victim owned is now retrievable from its new owner
    for k in owned:
        assert k in STORES[pool.ring.owner(k)]
    pool.close()


def test_one_membership_change_at_a_time():
    pool, eps = _fake_pool()
    _seed(pool, 500)
    real_copy = pool._copy_key
    gate = threading.Event()

    def slow_copy(key, src, dst):
        gate.wait(5)
        return real_copy(key, src, dst)

    pool._copy_key = slow_copy
    STORES["10.9.0.8:5000"] = {}
    STORES["10.9.0.9:5000"] = {}
    pool.join_node("10.9.0.8:5000")
    with pytest.raises(RuntimeError):
        pool.join_node("10.9.0.9:5000")
    with pytest.raises(RuntimeError):
        pool.drain_node(eps[0])
    gate.set()
    _wait_idle(pool)
    # and sanity rails: unknown drains / dup joins / last-node drains
    with pytest.raises(ValueError):
        pool.drain_node("10.9.9.9:1")
    with pytest.raises(ValueError):
        pool.join_node(eps[0])
    pool.close()


def test_join_refuses_unreachable_node():
    pool, eps = _fake_pool()
    STORES["10.9.0.7:5000"] = None  # FakeConn.connect raises
    with pytest.raises(RuntimeError):
        pool.join_node("10.9.0.7:5000")
    assert "10.9.0.7:5000" not in pool.endpoints
    assert pool.migration_idle()
    pool.close()


def test_console_cluster_membership_and_migration_rows():
    """istpu-top's cluster view shouts transition states and renders the
    live migration progress line."""
    from infinistore_tpu.top import Console, Snapshot

    cl = {
        "enabled": True, "replicas": 2, "vnodes": 64,
        "hot": {"hot_after": 3, "tracked": 2, "hot": 1, "pinned": 0},
        "replica_reads": {"hit": 0, "miss": 0},
        "migration": {"state": "running", "mode": "join",
                      "endpoint": "10.0.0.4:5000", "copied": 17,
                      "skipped": 2, "errors": 0, "total": 40},
        "nodes": [
            {"endpoint": "10.0.0.1:5000", "state": "closed",
             "membership": "active", "connected": True, "epoch": 1,
             "ownership": 0.4,
             "requests": {"ok": 10, "error": 0, "skipped": 0, "miss": 0}},
            {"endpoint": "10.0.0.4:5000", "state": "closed",
             "membership": "joining", "connected": True, "epoch": 2,
             "ownership": 0.2,
             "requests": {"ok": 1, "error": 0, "skipped": 0, "miss": 0}},
        ],
    }
    frame = Console().frame(Snapshot(cluster=cl))
    assert "JOINING" in frame
    assert "migration join 10.0.0.4:5000: 17/40 copied" in frame
    cl["nodes"][1]["membership"] = "draining"
    cl["migration"] = {"state": "done"}
    frame2 = Console().frame(Snapshot(cluster=cl))
    assert "DRAINING" in frame2 and "migration join" not in frame2


# ---------------------------------------------------------------------------
# live half: engines, serving, the walk
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _boot(port, mport):
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 25
    for p in (port, mport):
        while True:
            if proc.poll() is not None:
                pytest.fail("store node failed to start")
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


jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from infinistore_tpu.engine import InferenceEngine  # noqa: E402
from infinistore_tpu.kv import PagedCacheConfig  # noqa: E402
from infinistore_tpu.kv.hashing import chunk_keys  # noqa: E402
from infinistore_tpu.models import TINY, init_params, scaled  # noqa: E402
from infinistore_tpu.serve import ServingServer  # noqa: E402

from conftest import make_dense_greedy  # noqa: E402

CFG = scaled(TINY, dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(7))
T = 4
PROMPT = [11, 42, 7, 99, 5, 3, 17, 28, 64, 1, 2]
dense_greedy = make_dense_greedy(PARAMS, CFG)


def make_pc(n_blocks=128):
    return PagedCacheConfig(
        n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
        head_dim=CFG.head_dim, n_blocks=n_blocks, block_tokens=T,
        dtype=CFG.dtype,
    )


def _prompt(i):
    assert i < 450, i
    return [50 + i] + PROMPT[1:]


def _owned_prompt(ring, model_id, owner_ep, start=100):
    for i in range(start, 450):
        p = _prompt(i)
        keys = chunk_keys(p, model_id, chunk_tokens=T)
        if {ring.owner(k) for k in keys} == {owner_ep}:
            return p
    raise AssertionError("no prompt found with the wanted ownership")


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


class _Fleet:
    def __init__(self, n=4):
        self.ports = [(_free_port(), _free_port()) for _ in range(n)]
        self.procs = [_boot(p, mp) for p, mp in self.ports]

    @property
    def endpoints(self):
        return [f"127.0.0.1:{p}" for p, _ in self.ports]

    def stop(self):
        for proc in self.procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self.procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


@pytest.fixture(scope="module")
def walk_fleet():
    f = _Fleet(4)  # three members + one spare to join
    yield f
    f.stop()


def test_three_four_three_walk_under_load(walk_fleet):
    """THE membership acceptance walk: a serving server over 3 store
    nodes, an open-loop flood running the whole time; join the 4th node
    (background migration) → store hits recover on the grown ring;
    drain it back out → store hits recover on the shrunk ring; ZERO
    failed requests end to end, all membership state read over HTTP."""
    from infinistore_tpu.loadgen import LoadConfig, run_load, summarize

    f = walk_fleet
    members, spare = f.endpoints[:3], f.endpoints[3]
    pool = RoutedStorePool(members, op_timeout_s=5.0, replicas=2)
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=pool, model_id="walk-serve",
        store_durability="relaxed", kv_quant=None,
    )
    eng.decode_chunk = 4
    # this walk tests MEMBERSHIP routing under load, not the admission
    # plane: the CPU host's compile storms under the flood would trip
    # the burn shed into 429s and change what the walk observes (same
    # isolation rule PR 12 set for the health chaos fixture)
    prev_adm = os.environ.get("ISTPU_ADMISSION")
    os.environ["ISTPU_ADMISSION"] = "0"
    try:
        srv = ServingServer(eng, port=0, max_batch=4,
                            model_id="walk-serve")
    finally:
        if prev_adm is None:
            os.environ.pop("ISTPU_ADMISSION", None)
        else:
            os.environ["ISTPU_ADMISSION"] = prev_adm
    srv.start()
    prod_pools = []
    try:
        _post(srv.port, {"prompt": _prompt(0), "max_tokens": 4,
                         "temperature": 0})  # warm the compile caches

        def serve_metrics():
            st, data = _get(srv.port, "/metrics")
            assert st == 200
            return m.parse_prometheus_text(data.decode())

        def store_tokens():
            return serve_metrics().get(
                ("istpu_engine_prefix_tokens_total",
                 (("source", "store"),)), 0.0)

        def cluster_post(action, endpoint):
            return _post(srv.port, {"action": action,
                                    "endpoint": endpoint},
                        path="/debug/cluster")

        def wait_migration_done(deadline_s=60):
            deadline = time.time() + deadline_s
            while time.time() < deadline:
                st, data = _get(srv.port, "/debug/cluster")
                rep = json.loads(data)
                if rep["migration"].get("state") in ("done", "idle"):
                    return rep
                time.sleep(0.1)
            pytest.fail("migration did not finish")

        def seed_and_hit(endpoints, owner_ep, start):
            """Seed a store-only prefix owned by ``owner_ep`` via a
            FRESH producer pool on the CURRENT membership, then ask the
            serving stack: byte-exact tokens + a store-hit delta."""
            ring = HashRing(endpoints, vnodes=pool.ring.vnodes)
            p = _owned_prompt(ring, "walk-serve", owner_ep, start=start)
            prod_pool = RoutedStorePool(endpoints, op_timeout_s=5.0,
                                        replicas=1)
            prod_pools.append(prod_pool)
            prod = InferenceEngine(PARAMS, CFG, make_pc(64),
                                   conn=prod_pool, model_id="walk-serve",
                                   kv_quant=None)
            prod.release(prod.prefill(p))
            prod.store_flush()
            before = store_tokens()
            status, body = _post(srv.port, {
                "prompt": p, "max_tokens": 4, "temperature": 0})
            assert status == 200, body
            assert body["choices"][0]["token_ids"] == dense_greedy(p, 4)
            assert store_tokens() > before, \
                "store-hit provenance must recover after the transition"

        # open-loop flood across the WHOLE walk, in a thread
        load_out = {}

        def flood():
            results, makespan = run_load(
                f"http://127.0.0.1:{srv.port}", LoadConfig(
                    rate=3.0, n_requests=40, vocab=256, seed=5,
                    mix=((1.0, 11, 4),), timeout_s=120.0,
                    n_prefixes=2, prefix_len=8, prefix_frac=0.3,
                ))
            load_out["point"] = summarize(results, makespan, 60.0, 10.0,
                                          rate=3.0)

        flood_t = threading.Thread(target=flood, daemon=True)
        flood_t.start()
        time.sleep(0.5)  # the flood is live

        # ---- 3 → 4: join the spare under traffic ----
        status, rep = cluster_post("join", spare)
        assert status == 200, rep
        by_ep = {n["endpoint"]: n for n in rep["nodes"]}
        assert by_ep[spare]["membership"] in ("joining", "active")
        rep = wait_migration_done()
        assert len(rep["nodes"]) == 4
        assert all(n["membership"] == "active" for n in rep["nodes"])
        # membership rides /metrics and the health rollup too
        parsed = serve_metrics()
        assert parsed.get(("istpu_cluster_membership",
                           (("endpoint", spare),))) == 0.0
        st, data = _get(srv.port, "/debug/health")
        ring_view = json.loads(data)["cluster"]["ring"]
        assert {n["endpoint"] for n in ring_view} == set(f.endpoints)
        seed_and_hit(f.endpoints, spare, start=100)

        # ---- 4 → 3: drain it back out, still under traffic ----
        status, rep = cluster_post("drain", spare)
        assert status == 200, rep
        rep = wait_migration_done()
        assert {n["endpoint"] for n in rep["nodes"]} == set(members)
        seed_and_hit(members, members[0], start=250)

        flood_t.join(timeout=120)
        assert not flood_t.is_alive(), "flood did not drain"
        point = load_out["point"]
        # THE acceptance bar: zero failed requests across the 3→4→3 walk
        assert point["errors"] == 0 and point.get("rejected", 0) == 0, point
        assert point["completed"] == 40, point
    finally:
        srv.close()
        pool.close()
        for p in prod_pools:
            p.close()


# ---------------------------------------------------------------------------
# per-request flush marker (PR-13 handoff barrier follow-up)
# ---------------------------------------------------------------------------


def test_streamer_marker_flush_skips_other_requests():
    """Unit shape: a request's barrier waits for ITS pushes, not for
    another request's push still in flight."""
    from infinistore_tpu.engine.engine import _StoreStreamer
    from infinistore_tpu.utils import tracing

    class FakeBreaker:
        def allow(self):
            return True

        def record_success(self):
            pass

        def record_failure(self):
            pass

    class FakeTransfer:
        breaker = FakeBreaker()

        def push_begin(self, pages, keys):
            return ("tok", list(keys))

        def push_commit(self, token):
            if token[1][0].startswith("slow"):
                time.sleep(1.0)
            return 1

    st = _StoreStreamer(FakeTransfer(), maxsize=8, durability="relaxed")
    with tracing.TRACER.trace("req-B"):
        b = tracing.current_trace_id()
        st.submit(None, ["fast:1"])
    deadline = time.time() + 5
    while st._pending and time.time() < deadline:
        time.sleep(0.01)  # B's push lands
    with tracing.TRACER.trace("req-A"):
        a = tracing.current_trace_id()
        st.submit(None, ["slow:1"])  # worker busy ~1 s with A now
    time.sleep(0.05)
    t0 = time.perf_counter()
    st.flush(marker=b)
    dt_b = time.perf_counter() - t0
    t0 = time.perf_counter()
    st.flush(marker=a)
    dt_a = time.perf_counter() - t0
    assert dt_b < 0.3, f"B's barrier joined A's push ({dt_b:.2f}s)"
    assert dt_a > 0.3, dt_a
    st.flush()  # full join still clean


def test_streamer_marker_flush_surfaces_own_error():
    """A request whose pushes failed (or were skipped behind a parked
    error) must see the failure at ITS barrier — 'flushed: true' means
    durable."""
    from infinistore_tpu.engine.engine import _StoreStreamer
    from infinistore_tpu.utils import tracing

    class FakeBreaker:
        def allow(self):
            return True

        def record_success(self):
            pass

        def record_failure(self):
            pass

    class BoomTransfer:
        breaker = FakeBreaker()

        def push_begin(self, pages, keys):
            return ("tok", list(keys))

        def push_commit(self, token):
            raise RuntimeError("store died")

    st = _StoreStreamer(BoomTransfer(), maxsize=8, durability="relaxed")
    with tracing.TRACER.trace("req-X"):
        x = tracing.current_trace_id()
        st.submit(None, ["k1"])
    with pytest.raises(RuntimeError):
        st.flush(marker=x)
    # the parked state is NOT consumed by a marker flush: the full
    # flush (the idle join) still reports and clears it
    with pytest.raises(RuntimeError):
        st.flush()
    st.flush()


class _FakePushes:
    """A transfer's two push halves; a key that starts with ``slow`` takes a
    second to commit, one that starts with ``boom`` fails."""

    class breaker:
        allow = staticmethod(lambda: True)
        record_success = record_failure = staticmethod(lambda: None)

    def push_begin(self, pages, keys):
        return ("tok", list(keys))

    def push_commit(self, token):
        if token[1][0].startswith("slow"):
            time.sleep(1.0)
        if token[1][0].startswith("boom"):
            raise RuntimeError("store died")
        return 1


def test_streamer_await_prefill_waits_on_its_own_marker_only():
    """A prefill's own marker: untraced submits (trace id ``None``, which a
    trace's barrier treats as everyone's) are told apart by it, so awaiting
    B's pushes, which have landed, does not wait for A's push in flight."""
    from infinistore_tpu.engine.engine import _StoreStreamer
    from infinistore_tpu.utils import tracing

    assert tracing.current_trace_id() is None
    st = _StoreStreamer(_FakePushes(), maxsize=8, durability="strict")
    st.submit(None, ["fast:1"], marker="prefill-b")
    deadline = time.time() + 5
    while st._pending and time.time() < deadline:
        time.sleep(0.01)         # B's push lands
    st.submit(None, ["slow:1"], marker="prefill-a")
    time.sleep(0.05)
    assert st._pending == {None: 1, "prefill-a": 1}
    t0 = time.perf_counter()
    st.await_prefill("prefill-b")
    dt_b = time.perf_counter() - t0
    st.await_prefill("prefill-a")
    dt_a = time.perf_counter() - t0
    assert dt_b < 0.3, f"B's wait joined A's push ({dt_b:.2f}s)"
    assert dt_a > 0.3 and not st._pending
    # a TRACE's barrier still covers the pushes bound to no trace
    st.submit(None, ["slow:2"], marker="prefill-c")
    t0 = time.perf_counter()
    st.flush(marker="some-request's-trace")
    assert time.perf_counter() - t0 > 0.3
    st.flush()


def test_streamer_await_prefill_raises_its_error_and_pushes_resume():
    """Strict durability's wait raises the error of ITS prefill's pushes
    (those skipped behind a parked error too) and, where that error is the
    parked one, consumes it as the whole-queue flush it replaced did: the
    next prefill's pushes are tried again."""
    from infinistore_tpu.engine.engine import _StoreStreamer

    st = _StoreStreamer(_FakePushes(), maxsize=8, durability="relaxed")
    st.submit(None, ["boom:1"], marker="prefill-x")
    st.submit(None, ["fast:skipped"], marker="prefill-y")    # behind the parked error
    st.submit(None, ["fast:1"], marker="prefill-y")
    with pytest.raises(RuntimeError, match="store died"):
        st.await_prefill("prefill-y")
    with pytest.raises(RuntimeError, match="store died"):
        st.await_prefill("prefill-x")
    st.await_prefill("prefill-x")      # taken once
    st.submit(None, ["fast:2"], marker="prefill-z")
    st.await_prefill("prefill-z")      # pushed, not skipped
    st.flush()                         # nothing parked is left


@pytest.fixture(scope="module")
def handoff_stack():
    """A serving server with a single-node store, relaxed durability,
    chunked prefill — the PD prefill-worker shape two concurrent
    ``POST /v1/prefill`` handoffs exercise."""
    import infinistore_tpu as ist

    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port,
        connection_type=ist.TYPE_TCP, log_level="warning",
        op_timeout_s=15,
    ))
    conn.connect()
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(), conn=conn, model_id="handoff-serve",
        store_durability="relaxed", kv_quant=None, prefill_chunk=T,
    )
    # admission off: the deliberately-slowed pushes inflate TTFT far
    # past any SLO — the burn shed would 429 the very handoffs whose
    # barrier timing this fixture exists to measure
    prev_adm = os.environ.get("ISTPU_ADMISSION")
    os.environ["ISTPU_ADMISSION"] = "0"
    try:
        srv = ServingServer(eng, port=0, max_batch=4,
                            model_id="handoff-serve")
    finally:
        if prev_adm is None:
            os.environ.pop("ISTPU_ADMISSION", None)
        else:
            os.environ["ISTPU_ADMISSION"] = prev_adm
    srv.start()
    yield srv, eng
    srv.close()
    proc.terminate()
    proc.wait(timeout=10)


def test_concurrent_handoffs_no_cross_request_wait(handoff_stack):
    """THE regression (ROADMAP item 1b): two concurrent /v1/prefill
    handoffs — request A (short, fast pushes) must complete its flush
    barrier while request B's SLOW pushes are still draining.  The old
    whole-queue join made A wait for B's tail.  Patches push_commit
    (house rule: never push_pages)."""
    srv, eng = handoff_stack
    slow_prompt = [(7 * i) % 200 + 1 for i in range(24)]  # 5 complete chunks
    fast_prompt = [99, 3, 5, 7, 11, 13, 17, 19]           # 1 complete chunk
    slow_stems = set(chunk_keys(slow_prompt, "handoff-serve",
                                chunk_tokens=T))

    real_commit = eng.transfer.push_commit

    def gated_commit(token):
        if any(k in slow_stems for k in token[1]):
            time.sleep(0.7)
        return real_commit(token)

    eng.transfer.push_commit = gated_commit
    try:
        # warm both shapes first (compile storms must not pollute timing)
        _post(srv.port, {"prompt": [1] * 24, "max_tokens": 1,
                         "temperature": 0}, path="/v1/prefill")
        _post(srv.port, {"prompt": [1] * 8, "max_tokens": 1,
                         "temperature": 0}, path="/v1/prefill")

        done = {}

        def handoff(name, prompt):
            t0 = time.perf_counter()
            status, body = _post(srv.port, {
                "prompt": prompt, "max_tokens": 1, "temperature": 0,
            }, path="/v1/prefill")
            done[name] = (time.perf_counter() - t0, status, body)

        # A (fast) first: under the OLD whole-queue join its barrier
        # would absorb B's slow pushes arriving right behind it
        ta = threading.Thread(target=handoff,
                              args=("fast", fast_prompt))
        tb = threading.Thread(target=handoff,
                              args=("slow", slow_prompt))
        ta.start()
        time.sleep(0.05)
        tb.start()
        ta.join(timeout=60)
        tb.join(timeout=60)
        assert not ta.is_alive() and not tb.is_alive()
        fast_dt, fast_status, fast_body = done["fast"]
        slow_dt, slow_status, slow_body = done["slow"]
        assert fast_status == 200 and fast_body["flushed"], fast_body
        assert slow_status == 200 and slow_body["flushed"], slow_body
        # B's tail is ≥ 4 slow commits ≈ 2.8 s; A must NOT have waited
        # for it (old behavior: A's join ≈ B's, both > 2 s)
        assert slow_dt > 1.5, (slow_dt, fast_dt)
        assert fast_dt < slow_dt - 1.0, \
            f"fast handoff waited on slow pushes ({fast_dt:.2f}s " \
            f"vs {slow_dt:.2f}s)"
    finally:
        eng.transfer.push_commit = real_commit
        eng.store_flush()
