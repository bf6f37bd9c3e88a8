"""Multi-node store cluster: consistent-hash sharding, routed
connection pool, hot-prefix replication, and the 1-of-N outage chaos
walk.

Ring math is pure (no sockets); the live half drives THREE python store
subprocesses through ``RoutedStorePool``/``ClusterTransferEngine`` and
the serving stack, with the outage injected by killing a real node
process (the deterministic cluster-scale fault)."""

import json
import http.client
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from infinistore_tpu.cluster import (
    DEFAULT_REPLICAS,
    HashRing,
    HotKeyTracker,
    RoutedStorePool,
    parse_endpoints,
    ring_hash,
    route_stem,
)
from infinistore_tpu.utils import metrics as m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# ring math (pure, no sockets)
# ---------------------------------------------------------------------------


EPS = [f"10.0.0.{i}:5000" for i in range(1, 9)]


def test_ring_deterministic_across_processes():
    """Routing must agree between independent processes (a fleet is
    sharded by MANY clients): the owner map computed here must match
    one computed by a fresh interpreter — blake2b, never hash()."""
    ring = HashRing(EPS[:4])
    keys = [f"model:prefix{i:04x}" for i in range(50)]
    local = {k: ring.owner(k) for k in keys}
    script = (
        "import json,sys\n"
        "from infinistore_tpu.cluster import HashRing\n"
        f"ring = HashRing({EPS[:4]!r})\n"
        f"keys = {keys!r}\n"
        "print(json.dumps({k: ring.owner(k) for k in keys}))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "PYTHONHASHSEED": "12345"},
    )
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout) == local


def test_ring_ownership_spread():
    """1000 keys over 3..8 nodes: every node owns a meaningful share
    (virtual nodes keep the spread within ~2x of even), and the
    ownership gauge arcs sum to the whole ring."""
    keys = [f"model:k{i}" for i in range(1000)]
    for n in range(3, 9):
        ring = HashRing(EPS[:n])
        counts = {ep: 0 for ep in EPS[:n]}
        for k in keys:
            counts[ring.owner(k)] += 1
        mean = 1000 / n
        assert max(counts.values()) <= 2.0 * mean, (n, counts)
        assert min(counts.values()) >= 0.4 * mean, (n, counts)
        own = ring.ownership()
        assert abs(sum(own.values()) - 1.0) < 1e-9
        assert set(own) == set(EPS[:n])


def test_ring_minimal_movement_on_add_and_remove():
    """The consistent-hashing contract: adding a node moves ~1/(N+1) of
    the keys — every moved key moves TO the new node, none shuffle
    among the old ones — and removing it restores the exact map."""
    keys = [f"model:k{i}" for i in range(1000)]
    ring = HashRing(EPS[:4])
    before = {k: ring.owner(k) for k in keys}
    new = "10.9.9.9:5000"
    ring.add(new)
    after = {k: ring.owner(k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    assert len(moved) <= 1.6 * (1000 / 5), len(moved)
    assert len(moved) >= 0.4 * (1000 / 5), len(moved)
    assert all(after[k] == new for k in moved)
    ring.remove(new)
    assert {k: ring.owner(k) for k in keys} == before
    # removing an original node moves ONLY its keys
    ring.remove(EPS[0])
    reowned = {k: ring.owner(k) for k in keys}
    for k in keys:
        if before[k] == EPS[0]:
            assert reowned[k] != EPS[0]
        else:
            assert reowned[k] == before[k], k


def test_ring_replica_successors_distinct_and_stable():
    ring = HashRing(EPS[:5])
    for i in range(100):
        key = f"model:r{i}"
        succ = ring.successors(key, 3)
        assert len(succ) == 3 and len(set(succ)) == 3
        assert succ[0] == ring.owner(key)
        assert succ == ring.successors(key, 3)  # stable
    # n capped at the endpoint count
    assert len(ring.successors("model:x", 99)) == 5


def test_route_stem_colocates_layers():
    """All layers of a chunk (and its quantized twin) route together:
    the stem strips #L{layer} and the trailing :q8."""
    ring = HashRing(EPS[:6])
    stem = "llama8b#a2:deadbeefcafe"
    owners = {
        ring.owner(f"{stem}#L{layer}{sfx}")
        for layer in range(32) for sfx in ("", ":q8")
    }
    assert owners == {ring.owner(stem)}
    assert route_stem(f"{stem}#L31:q8") == stem
    assert route_stem(stem) == stem
    assert ring_hash("x") == ring_hash(b"x")


def test_parse_endpoints():
    assert parse_endpoints("a:1, b:2,a:1") == ["a:1", "b:2"]
    assert parse_endpoints(["h:80"]) == ["h:80"]
    with pytest.raises(ValueError):
        parse_endpoints("nohost")
    with pytest.raises(ValueError):
        parse_endpoints("")


def test_client_config_endpoints_template():
    """ClientConfig grew an ``endpoints`` field: the cluster-membership
    template RoutedStorePool.from_config builds a pool from.  Malformed
    entries fail verify() with the specific error, not the masked
    'Host address is empty'."""
    from infinistore_tpu.config import ClientConfig, TYPE_SHM

    c = ClientConfig(endpoints="h1:1, h2:2", connection_type=TYPE_SHM)
    c.verify()
    assert c.endpoints == ["h1:1", "h2:2"]
    assert (c.host_addr, c.service_port) == ("h1", 1)  # derived template
    with pytest.raises(Exception, match="host:port"):
        ClientConfig(endpoints=["bad"], connection_type=TYPE_SHM).verify()

    class _FakeConn:
        def connect(self):
            pass

        def close(self):
            pass

    pool = RoutedStorePool.from_config(
        c, conn_factory=lambda ep: _FakeConn(), connect=False
    )
    assert pool.endpoints == ["h1:1", "h2:2"]
    pool.close()


def test_hot_tracker_threshold_and_pin():
    t = HotKeyTracker(hot_after=3, capacity=8)
    k = "model:sys#L0"
    assert not t.is_hot(k)
    t.record(k); t.record(k)
    assert not t.is_hot(k)
    t.record(k)
    assert t.is_hot(k)  # threshold reached
    # pin: hot immediately, across layer spellings of the same stem
    assert t.pin(["model:pinned#L7:q8"]) == 1
    assert t.is_hot("model:pinned#L0")
    t.unpin(["model:pinned"])
    assert not t.is_hot("model:pinned#L0")
    # bounded: old cold stems age out of the counting window
    for i in range(20):
        t.record(f"model:x{i}")
    snap = t.snapshot()
    assert snap["tracked"] <= 8 and snap["hot_after"] == 3


# ---------------------------------------------------------------------------
# live cluster: 3 python store nodes
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
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                break
            except OSError:
                if time.time() >= deadline:
                    proc.kill()
                    pytest.fail(f"store port {p} did not come up")
                time.sleep(0.1)
    return proc


jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from infinistore_tpu.cluster import ClusterTransferEngine  # noqa: E402
from infinistore_tpu.engine import InferenceEngine  # noqa: E402
from infinistore_tpu.kv import PagedCacheConfig  # noqa: E402
from infinistore_tpu.kv.cache import init_cache  # noqa: E402
from infinistore_tpu.kv.hashing import chunk_keys  # noqa: E402
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


def small_pc():
    return PagedCacheConfig(
        n_layers=4, n_kv_heads=2, head_dim=8, n_blocks=32,
        block_tokens=4, dtype=jnp.float32,
    )


class _Fleet:
    """Three store node subprocesses, restartable by index on their
    original ports (the epoch-fence rejoin needs the SAME address)."""

    def __init__(self):
        self.ports = [(_free_port(), _free_port()) for _ in range(3)]
        self.procs = [_boot(p, mp) for p, mp in self.ports]

    @property
    def endpoints(self):
        return [f"127.0.0.1:{p}" for p, _ in self.ports]

    def kill(self, i):
        self.procs[i].kill()
        self.procs[i].wait()

    def restart(self, i):
        assert self.procs[i].poll() is not None, "kill before restart"
        # the freed port may linger in TIME_WAIT; _boot retries until up
        self.procs[i] = _boot(*self.ports[i])

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
def fleet():
    f = _Fleet()
    yield f
    f.stop()


def _pool(fleet, **kw):
    kw.setdefault("op_timeout_s", 5.0)
    return RoutedStorePool(fleet.endpoints, **kw)


def test_cluster_routes_push_load_lookup(fleet):
    """Pages land on their ring owners, a sharded lookup answers the
    longest global prefix, and a sharded load is byte-exact."""
    pool = _pool(fleet)
    pc = small_pc()
    eng = ClusterTransferEngine(pool, pc)
    cache = jax.random.normal(
        jax.random.PRNGKey(0), init_cache(pc).shape, dtype=pc.dtype
    )
    keys = [f"route:chunk{i}" for i in range(8)]
    ids = list(range(8))
    assert eng.save_pages(cache, ids, keys) == 8 * pc.n_layers * pc.page_bytes
    # batches split across >1 endpoint (8 stems over 3 nodes)
    parts = pool.partition(keys)
    assert len(parts) >= 2
    # every page key exists on its owner — and the routing is exhaustive
    for k in keys:
        owner = pool.ring.owner(k)
        node_eng = eng._engine(owner)
        for layer in range(pc.n_layers):
            assert node_eng._call("check_exist", f"{k}#L{layer}") == 0
    assert eng.lookup_prefix(keys) == 8
    # evicting a tail of the sequence cuts the global prefix at the
    # shard level: delete chunks 3..7 on their respective owners
    for k in keys[3:]:
        page_keys = [f"{k}#L{layer}" for layer in range(pc.n_layers)]
        eng._engine(pool.ring.owner(k))._call("delete_keys", page_keys)
    assert eng.lookup_prefix(keys) == 3
    fresh = init_cache(pc)
    out, ok = eng.guarded_load(fresh, ids[:3], keys[:3])
    assert ok
    np.testing.assert_array_equal(
        np.asarray(out[:, :, :, :3]), np.asarray(cache[:, :, :, :3])
    )
    pool.close()


def test_hot_prefix_replication_and_failover(fleet):
    """Pinned stems fan out to every ring successor on push; killing
    the owner mid-fleet leaves reads served by the replica (counted in
    istpu_cluster_replica_reads_total{result="hit"}), and only the dead
    node's circuit accumulates failures."""
    pool = _pool(fleet, replicas=2)
    pc = small_pc()
    eng = ClusterTransferEngine(pool, pc)
    cache = jax.random.normal(
        jax.random.PRNGKey(1), init_cache(pc).shape, dtype=pc.dtype
    )
    keys = [f"hotrep:chunk{i}" for i in range(4)]
    pool.pin(keys)
    eng.save_pages(cache, list(range(4)), keys)
    # every chunk's pages exist on BOTH candidates
    for k in keys:
        cands = pool.candidates(k)
        assert len(cands) == 2
        for ep in cands:
            assert eng._engine(ep)._call("check_exist", f"{k}#L0") == 0
    # kill the owner of keys[0]; its replica must serve the read
    victim = pool.ring.owner(keys[0])
    vi = fleet.endpoints.index(victim)
    fleet.kill(vi)
    served = [k for k in keys if pool.ring.owner(k) == victim]
    assert served, "expected at least one chunk owned by the victim"
    fresh = init_cache(pc)
    out, ok = eng.guarded_load(
        fresh, list(range(4)), keys
    )
    assert ok, "replica failover must serve pinned chunks"
    np.testing.assert_array_equal(
        np.asarray(out[:, :, :, :4]), np.asarray(cache[:, :, :, :4])
    )
    rep = pool.report()
    assert rep["replica_reads"].get("hit", 0) >= 1, rep["replica_reads"]
    by_ep = {n["endpoint"]: n for n in rep["nodes"]}
    assert by_ep[victim]["requests"]["error"] >= 1
    for ep in fleet.endpoints:
        if ep != victim:
            assert by_ep[ep]["requests"]["error"] == 0, by_ep[ep]
    # prometheus families carry the same story
    text = m.default_registry().to_prometheus_text()
    parsed = m.parse_prometheus_text(text)
    assert parsed.get(("istpu_cluster_replica_reads_total",
                       (("result", "hit"),)), 0) >= 1
    assert ("istpu_cluster_node_state",
            (("endpoint", victim),)) in parsed
    pool.close()
    fleet.restart(vi)


def test_single_endpoint_keeps_single_connection_path(fleet):
    """One endpoint is NOT a cluster: the engine keeps the classic
    KVTransferEngine over a plain connection (no ring, no routing
    layer), and a RoutedStorePool engine is only built for fleets."""
    import infinistore_tpu as ist
    from infinistore_tpu.kv.transfer import KVTransferEngine

    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1",
        service_port=int(fleet.endpoints[0].rsplit(":", 1)[1]),
        connection_type=ist.TYPE_SHM, op_timeout_s=5.0,
        log_level="warning",
    ))
    conn.connect()
    eng = InferenceEngine(PARAMS, CFG, make_pc(), conn=conn,
                          model_id="single-path")
    assert type(eng.transfer) is KVTransferEngine
    assert eng.pin_prefix(PROMPT) == 0  # nowhere to replicate
    conn.close()

    pool = _pool(fleet)
    eng2 = InferenceEngine(PARAMS, CFG, make_pc(), conn=pool,
                           model_id="cluster-path")
    assert type(eng2.transfer) is ClusterTransferEngine
    assert eng2.pin_prefix(PROMPT) >= 1
    pool.close()


def test_cluster_report_shape(fleet):
    pool = _pool(fleet)
    rep = pool.report()
    assert rep["enabled"] is True
    assert rep["replicas"] == min(DEFAULT_REPLICAS, 3)
    assert len(rep["nodes"]) == 3
    total_own = sum(n["ownership"] for n in rep["nodes"])
    assert 0.99 <= total_own <= 1.01
    for n in rep["nodes"]:
        assert {"endpoint", "state", "connected", "epoch", "ownership",
                "requests"} <= set(n)
        assert n["state"] == "closed" and n["connected"]
    assert {"hot_after", "tracked", "hot", "pinned"} <= set(rep["hot"])
    pool.close()


# ---------------------------------------------------------------------------
# THE chaos test: 1-of-3 node outage under the serving stack
# ---------------------------------------------------------------------------


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


def _prompt(i):
    """Distinct 11-token prompts (same compiled shapes, distinct chunk
    keys).  Keep i < 450: TINY's vocab is 512."""
    assert i < 450, i
    return [50 + i] + PROMPT[1:]


def _owned_prompt(pool, model_id, owner_ep, start=100, invert=False):
    """A prompt whose complete chunks are ALL owned by ``owner_ep`` (or,
    with ``invert``, all owned by OTHER nodes) — how the chaos test
    pins 'this prefix lives in the dead node's key range'."""
    for i in range(start, 450):
        p = _prompt(i)
        keys = chunk_keys(p, model_id, chunk_tokens=T)
        owners = {pool.ring.owner(k) for k in keys}
        if not invert and owners == {owner_ep}:
            return p
        if invert and owner_ep not in owners:
            return p
    raise AssertionError("no prompt found with the wanted ownership")


@pytest.fixture(scope="module")
def chaos_cluster():
    """A serving server over a 3-node store fleet, with per-node
    breakers tuned for fast transitions, plus a producer engine on its
    own pool (seeding store-resident prefixes the serving engine has
    never computed locally)."""
    f = _Fleet()
    pool = RoutedStorePool(f.endpoints, op_timeout_s=2.0, replicas=2)
    # kv_quant=None: the test asserts BYTE-EXACT greedy tokens on
    # store-HIT paths too (survivor + rejoin phases), so the store hop
    # must be lossless — int8's ~0.4% noise can flip a late greedy
    # argmax and has nothing to do with the failure semantics under test
    eng = InferenceEngine(
        PARAMS, CFG, make_pc(n_blocks=128), conn=pool,
        model_id="cluster-serve", store_durability="relaxed",
        kv_quant=None,
    )
    eng.decode_chunk = 4
    for node in pool.nodes():
        node.breaker.failure_threshold = 2
        node.breaker.cooldown_s = 0.5
    prod_pool = RoutedStorePool(f.endpoints, op_timeout_s=5.0, replicas=2)
    prod = InferenceEngine(PARAMS, CFG, make_pc(), conn=prod_pool,
                           model_id="cluster-serve", kv_quant=None)
    srv = ServingServer(eng, port=0, max_batch=4, model_id="cluster-serve",
                        **WALK_SLO)
    srv.start()
    yield srv, f, pool, prod
    srv.close()
    pool.close()
    prod_pool.close()
    f.stop()


def test_chaos_one_node_outage_degrades_only_its_range(chaos_cluster):
    """THE cluster acceptance walk: kill 1 of 3 store nodes mid-load →
    every request still answers 200 with byte-exact greedy tokens; ONLY
    the dead node's circuit opens (asserted from /metrics and
    /debug/cluster); the survivors' key ranges keep serving store hits;
    restart → the epoch fence fires on reconnect and the node rejoins
    (circuit closes, its range serves again)."""
    srv, f, pool, prod = chaos_cluster
    victim_ep = f.endpoints[1]
    vi = 1
    live_ep = [ep for ep in f.endpoints if ep != victim_ep]

    def ask(p):
        status, body = _post(srv.port, {
            "prompt": p, "max_tokens": 6, "temperature": 0,
        })
        assert status == 200, body
        assert body["choices"][0]["token_ids"] == dense_greedy(p, 6), body
        return body

    def serve_metrics():
        st, data = _get(srv.port, "/metrics")
        assert st == 200
        return m.parse_prometheus_text(data.decode())

    def cluster_report():
        st, data = _get(srv.port, "/debug/cluster")
        assert st == 200
        return json.loads(data)

    def store_tokens():
        return serve_metrics().get(
            ("istpu_engine_prefix_tokens_total", (("source", "store"),)),
            0.0)

    # phase 0: healthy fleet — prompts whose prefixes we control:
    # "victim" lives entirely in the to-be-killed node's key range,
    # "survivor" entirely outside it.  The PRODUCER computes and pushes
    # them; the serving engine has never seen either locally.
    victim_prompt = _owned_prompt(pool, "cluster-serve", victim_ep)
    survivor_prompt = _owned_prompt(pool, "cluster-serve", victim_ep,
                                    start=200, invert=True)
    prod.release(prod.prefill(victim_prompt))
    prod.release(prod.prefill(survivor_prompt))
    prod.store_flush()
    ask(_prompt(0))  # warm the serving path end to end
    rep = cluster_report()
    assert rep["enabled"] and len(rep["nodes"]) == 3
    assert all(n["state"] == "closed" for n in rep["nodes"])
    st, data = _get(srv.port, "/healthz")
    assert json.loads(data)["status"] == "ok"

    # phase 1: kill the node.  The victim-range request completes via
    # recompute (byte-exact), and repeated hits on the dead range open
    # ONLY that node's circuit.  Long cooldown so the OPEN state holds
    # still for the assertions below (restored before the rejoin).
    pool.node(victim_ep).breaker.cooldown_s = 60.0
    f.kill(vi)
    ask(victim_prompt)
    deadline = time.time() + 10
    while (pool.node(victim_ep).breaker.state != "open"
           and time.time() < deadline):
        ask(_owned_prompt(pool, "cluster-serve", victim_ep,
                          start=300 + int(time.time() * 7) % 100))
        time.sleep(0.05)
    assert pool.node(victim_ep).breaker.state == "open"
    for ep in live_ep:
        assert pool.node(ep).breaker.state == "closed"
    # the survivors' key range still serves STORE hits: the producer-
    # seeded survivor prefix loads from the store (provenance counter)
    before_store = store_tokens()
    ask(survivor_prompt)
    assert store_tokens() > before_store, \
        "live nodes' key range must keep serving store hits"
    # observable from /debug/cluster and /metrics: only the victim OPEN
    rep = cluster_report()
    by_ep = {n["endpoint"]: n for n in rep["nodes"]}
    assert by_ep[victim_ep]["state"] == "open"
    assert by_ep[victim_ep]["requests"]["error"] >= 2
    for ep in live_ep:
        assert by_ep[ep]["state"] == "closed"
        assert by_ep[ep]["requests"]["error"] == 0
    # the live half of the fleet kept answering (which specific node
    # depends on where the few prompts' chunks hash)
    assert sum(by_ep[ep]["requests"]["ok"] for ep in live_ep) >= 1
    parsed = serve_metrics()
    assert parsed.get(("istpu_cluster_node_state",
                       (("endpoint", victim_ep),))) == 1.0
    for ep in live_ep:
        assert parsed.get(("istpu_cluster_node_state",
                           (("endpoint", ep),))) == 0.0
    # per-node circuit walk rides the classic family too
    assert parsed.get(("istpu_store_circuit_state",
                       (("name", f"store@{victim_ep}"),))) == 1.0
    st, data = _get(srv.port, "/healthz")
    health = json.loads(data)
    assert health["status"] == "degraded"
    assert health["store_circuit"] == "partial"

    # while the victim's circuit is open its range is SKIPPED outright
    # (no per-request timeout tax): a victim-range prompt completes fast
    t0 = time.perf_counter()
    ask(_owned_prompt(pool, "cluster-serve", victim_ep, start=420))
    assert time.perf_counter() - t0 < 1.5

    # phase 2: restart on the SAME port — reconnect fences the epoch
    # (the restarted store published a new boot epoch + fresh pools)
    # and the node rejoins: circuit closes, its range serves again.
    epoch_before = serve_metrics().get(
        ("istpu_integrity_failures_total", (("cause", "epoch"),)), 0.0)
    f.restart(vi)
    pool.node(victim_ep).breaker.cooldown_s = 0.5
    time.sleep(pool.node(victim_ep).breaker.cooldown_s + 0.1)
    deadline = time.time() + 30
    while (pool.node(victim_ep).breaker.state != "closed"
           and time.time() < deadline):
        ask(_owned_prompt(pool, "cluster-serve", victim_ep,
                          start=340 + int(time.time() * 3) % 60))
        time.sleep(0.05)
    assert pool.node(victim_ep).breaker.state == "closed"
    assert serve_metrics().get(
        ("istpu_integrity_failures_total", (("cause", "epoch"),)), 0.0
    ) > epoch_before, "reconnect across the restart must fence the epoch"
    # the rejoined node's range works end to end again: a fresh prefix
    # pushed by the producer into the victim range loads store-side
    rejoin_prompt = _owned_prompt(pool, "cluster-serve", victim_ep,
                                  start=240)
    prod.release(prod.prefill(rejoin_prompt))
    prod.store_flush()
    before_store = store_tokens()
    ask(rejoin_prompt)
    assert store_tokens() > before_store
    rep = cluster_report()
    assert {n["endpoint"]: n["state"] for n in rep["nodes"]} == {
        ep: "closed" for ep in f.endpoints
    }
    st, data = _get(srv.port, "/healthz")
    deadline = time.time() + 10  # a clean idle flush clears the flag
    while time.time() < deadline:
        st, data = _get(srv.port, "/healthz")
        if json.loads(data)["status"] == "ok":
            break
        time.sleep(0.1)
    assert json.loads(data)["status"] == "ok", data


# ---------------------------------------------------------------------------
# istpu-top cluster view (pure frame)
# ---------------------------------------------------------------------------


def test_console_cluster_view():
    from infinistore_tpu.top import Console, Snapshot

    cl = {
        "enabled": True, "replicas": 2, "vnodes": 64,
        "hot": {"hot_after": 3, "tracked": 12, "hot": 4, "pinned": 2},
        "replica_reads": {"hit": 7, "miss": 1},
        "nodes": [
            {"endpoint": "10.0.0.1:5000", "state": "closed",
             "connected": True, "epoch": 1, "ownership": 0.35,
             "requests": {"ok": 120, "error": 0, "skipped": 0, "miss": 2}},
            {"endpoint": "10.0.0.2:5000", "state": "open",
             "connected": True, "epoch": 2, "ownership": 0.31,
             "requests": {"ok": 80, "error": 9, "skipped": 4, "miss": 0}},
        ],
    }
    console = Console()
    frame = console.frame(Snapshot(cluster=cl))
    assert "cluster  nodes 2  replicas 2  hot 4  pinned 2" in frame
    assert "repl-reads hit 7 / miss 1" in frame
    assert "10.0.0.1:5000" in frame and "10.0.0.2:5000" in frame
    assert "OPEN" in frame  # the dead node shouts
    assert "35.0%" in frame
    # second frame renders the per-frame ok delta
    cl2 = json.loads(json.dumps(cl))
    cl2["nodes"][0]["requests"]["ok"] = 135
    frame2 = console.frame(Snapshot(cluster=cl2))
    assert "+15" in frame2
    # no cluster -> no section
    assert "cluster  nodes" not in console.frame(Snapshot())
