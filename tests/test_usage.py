"""Tenant-resolved capacity attribution: the usage ledger end to end.

The contract under test (docs/observability.md §usage attribution):
every byte the store fleet holds and every prompt token the engine
serves is attributable to a tenant — occupancy as byte·seconds per
account per tier with shared-prefix bytes SPLIT across the sharer set,
reads/evictions/DOA per account, per-tenant store-vs-recomputed token
counts — and legacy peers stay byte-identical with the accounting
capability unnegotiated (fail-closed, the TRAC/EPOC/ALOC rule).
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from infinistore_tpu import protocol as P
from infinistore_tpu import usage as U
from infinistore_tpu.utils import metrics as m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- meter units (fake clock, no store) ----


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_usage_meter_accrues_byte_seconds_per_tier():
    clk = _Clock()
    mtr = U.UsageMeter(clock=clk)
    mtr.on_commit("acme", 1000)
    clk.t += 10.0
    mtr.add(["bob"], 500, "disk")
    clk.t += 4.0
    rep = mtr.report()
    a = rep["accounts"]["acme"]
    b = rep["accounts"]["bob"]
    # acme held 1000 B of dram for 14 s; bob 500 B of disk for 4 s
    assert a["byte_seconds"]["dram"] == pytest.approx(14000.0)
    assert a["resident_bytes"]["dram"] == 1000
    assert a["bytes_written"] == 1000
    assert b["byte_seconds"]["disk"] == pytest.approx(2000.0)
    # removal stops accrual
    mtr.sub(["acme"], 1000, "dram")
    clk.t += 100.0
    rep = mtr.report()
    assert rep["accounts"]["acme"]["byte_seconds"]["dram"] == \
        pytest.approx(14000.0)
    assert rep["accounts"]["acme"]["resident_bytes"]["dram"] == 0


def test_usage_meter_sharer_split_and_evict_attribution():
    clk = _Clock()
    mtr = U.UsageMeter(clock=clk)
    mtr.on_commit("acme", 800)
    clk.t += 5.0  # 800 B·5 s accrue to acme alone
    mtr.reshare(["acme"], ["acme", "bob"], 800)
    clk.t += 6.0  # 400 B·6 s each
    rep = mtr.report()
    assert rep["accounts"]["acme"]["byte_seconds"]["dram"] == \
        pytest.approx(800 * 5 + 400 * 6)
    assert rep["accounts"]["bob"]["byte_seconds"]["dram"] == \
        pytest.approx(400 * 6)
    # eviction: both sharers lose residency, the OWNER eats the
    # eviction + DOA counters
    mtr.on_evict(["acme", "bob"], "acme", 800, never_read=True)
    rep = mtr.report()
    assert rep["accounts"]["acme"]["evictions"] == 1
    assert rep["accounts"]["acme"]["dead_on_arrival"] == 1
    assert rep["accounts"]["bob"]["evictions"] == 0
    assert rep["accounts"]["acme"]["resident_bytes"]["dram"] == 0
    assert rep["accounts"]["bob"]["resident_bytes"]["dram"] == 0


def test_usage_meter_bounds_hostile_account_churn():
    mtr = U.UsageMeter(clock=_Clock(), max_accounts=4)
    for i in range(10):
        mtr.on_commit(f"t{i}", 10)
    rep = mtr.report()
    # past the cap, new labels fold into "other" instead of growing
    assert len(rep["accounts"]) <= 5
    assert "other" in rep["accounts"]
    total = sum(a["resident_bytes"]["dram"]
                for a in rep["accounts"].values())
    assert total == pytest.approx(100)


# ---- wire protocol: ACCT trailer + account blob (fail-closed) ----


def test_protocol_acct_trailer_roundtrip_and_fail_closed():
    pools = [("istpu_pool_0", 1 << 20, 16 << 10)]
    legacy = P.pack_pool_table(pools)
    # trailer-less body (old server): negotiation fails closed
    assert P.unpack_hello_acct(memoryview(legacy)) is None
    # ACCT alone, and ACCT behind the other capability trailers, both
    # resolve; the legacy pool-table parser ignores every trailer byte
    for body in (
        legacy + P.pack_acct_trailer(),
        legacy + P.pack_hello_trailer(P.HELLO_FLAG_TRACE_CTX, 1.5)
        + P.pack_epoch_trailer(1, 9) + P.pack_acct_trailer(32),
    ):
        assert P.unpack_pool_table(memoryview(body)) == pools
        assert P.unpack_hello_acct(memoryview(body)) in (
            P.MAX_ACCOUNT_LABEL, 32)
    # a body with only the OTHER trailers answers None (scan skips them)
    other = legacy + P.pack_epoch_trailer(1, 9)
    assert P.unpack_hello_acct(memoryview(other)) is None


def test_protocol_account_blob_roundtrip_and_truncation():
    blob = P.pack_account("acme")
    label, consumed = P.unpack_account(memoryview(blob + b"rest"))
    assert (label, consumed) == ("acme", len(blob))
    # labels past the cap truncate on pack
    long = P.pack_account("x" * 500)
    label, _ = P.unpack_account(memoryview(long))
    assert label == "x" * P.MAX_ACCOUNT_LABEL
    with pytest.raises(ValueError):
        P.unpack_account(memoryview(b"\xff\xff" + b"a"))  # length > body


# ---- store units (hand-built store, injectable clock) ----


def _unit_store():
    from test_store_unit import make_store

    s = make_store()
    clk = _Clock()
    s._clock = clk
    # the meter reads the store's clock indirectly — rebind works
    return s, clk


def test_store_attributes_owner_sharers_and_evictions():
    s, clk = _unit_store()
    try:
        st, descs = s.alloc_put([b"shared"], 16 << 10, account="acme")
        assert st == P.FINISH and len(descs) == 1
        s.commit_put([b"shared"])
        e = s.kv[b"shared"]
        assert e.account == "acme"
        clk.t += 10.0
        # a DIFFERENT account reads: it joins the sharer set and the
        # split rebalances; the owner's own read never does
        st, _ = s.get_desc([b"shared"], 16 << 10, account="bob")
        assert st == P.FINISH
        assert e.sharers == ["bob"]
        st, _ = s.get_desc([b"shared"], 16 << 10, account="acme")
        assert e.sharers == ["bob"]  # owner read: no self-share
        clk.t += 10.0
        rep = s.usage_meter.report()
        size = e.size
        assert rep["accounts"]["acme"]["byte_seconds"]["dram"] == \
            pytest.approx(size * 10 + size / 2 * 10)
        assert rep["accounts"]["bob"]["byte_seconds"]["dram"] == \
            pytest.approx(size / 2 * 10)
        assert rep["accounts"]["bob"]["hits"] == 1
        assert rep["accounts"]["acme"]["hits"] == 1
        # an UNTAGGED commit bills the unattributed bucket, then its
        # never-read eviction lands on the owner "-"
        s.put_inline(b"legacy", b"z" * 1024)
        clk.t += 20.0
        assert s.delete_keys([b"shared"]) == 1
        s.evict(0.0, 0.0)  # kv holds only "legacy" now; force it out
        s._pressure_evict(n=8)
        rep = s.usage_meter.report()
        assert rep["accounts"][U.UNATTRIBUTED]["dead_on_arrival"] == 1
        assert rep["accounts"][U.UNATTRIBUTED]["evictions"] == 1
        # every removal path drained residency back to zero
        for acct in ("acme", "bob", U.UNATTRIBUTED):
            assert rep["accounts"][acct]["resident_bytes"]["dram"] == \
                pytest.approx(0.0)
    finally:
        s.mm.close()


def test_spill_tier_carries_accounts_and_slab_fill(tmp_path):
    from test_store_unit import make_tiered_store

    s = make_tiered_store(tmp_path)
    clk = _Clock()
    s._clock = clk
    s.disk._clock = clk
    s.disk.usage_sink = s._disk_usage
    s.demote_watermark = 0.0  # demote regardless of pool pressure
    try:
        s.put_inline(b"cold", b"c" * 2048, account="acme")
        e = s.kv[b"cold"]
        e.hits = 1  # disk admission gate: read entries always earn a slot
        size = e.size
        clk.t += 30.0  # past demote_after_s (20 s)
        assert s.demote_step(now=clk.t) == 1
        rep = s.usage_meter.report()
        # residency MOVED dram -> disk, attribution intact
        assert rep["accounts"]["acme"]["resident_bytes"]["dram"] == \
            pytest.approx(0.0)
        assert rep["accounts"]["acme"]["resident_bytes"]["disk"] == \
            pytest.approx(size)
        assert s.disk.index[b"cold"].account == "acme"
        # per-slab occupancy is reported (ROADMAP 4c groundwork)
        disk_rep = s.disk.report()
        (cls, slab), = disk_rep["sizeclasses"].items()
        assert slab["used"] == 1 and 0 < slab["fill"] <= 1.0
        # promote back: disk residency returns to dram, same owner
        assert s.get_inline(b"cold", account="acme") is not None
        rep = s.usage_meter.report()
        assert rep["accounts"]["acme"]["resident_bytes"]["disk"] == \
            pytest.approx(0.0)
        assert rep["accounts"]["acme"]["resident_bytes"]["dram"] == \
            pytest.approx(size)
        assert s.kv[b"cold"].account == "acme"
    finally:
        s.close()


def test_spill_manifest_persists_accounts_across_restart(tmp_path):
    from infinistore_tpu.store import DiskTier

    tier = DiskTier(str(tmp_path), 1 << 20, 16 << 10)
    assert tier.put(b"k1", b"a" * 100, account="acme")
    assert tier.put(b"k2", b"b" * 100)  # untagged stays untagged
    tier.save_manifest()
    tier.close()
    warm = DiskTier(str(tmp_path), 1 << 20, 16 << 10)
    assert warm.index[b"k1"].account == "acme"
    assert warm.index[b"k2"].account is None
    # pre-accounting manifests (5-field entries) still load
    doc = json.load(open(warm.manifest_path))
    doc["entries"] = [e[:5] for e in doc["entries"]]
    json.dump(doc, open(warm.manifest_path, "w"))
    warm.close()
    old = DiskTier(str(tmp_path), 1 << 20, 16 << 10)
    assert old.index[b"k1"].account is None  # tolerated, unattributed
    old.close()


# ---- the pure fleet join ----


def _node(accounts):
    return {"enabled": True, "accounts": accounts, "sharer_overflow": 0}


def test_usage_report_joins_nodes_and_token_provenance():
    n1 = _node({
        "acme": {"resident_bytes": {"dram": 1000, "disk": 0},
                 "byte_seconds": {"dram": 2e9, "disk": 0},
                 "hits": 5, "evictions": 1, "dead_on_arrival": 0,
                 "bytes_written": 4000},
    })
    n2 = _node({
        "acme": {"resident_bytes": {"dram": 500, "disk": 200},
                 "byte_seconds": {"dram": 1e9, "disk": 1e9},
                 "hits": 2, "evictions": 0, "dead_on_arrival": 0,
                 "bytes_written": 1000},
        "bob": {"resident_bytes": {"dram": 100, "disk": 0},
                "byte_seconds": {"dram": 5e8, "disk": 0},
                "hits": 1, "evictions": 3, "dead_on_arrival": 3,
                "bytes_written": 100},
    })
    rep = U.usage_report(
        [n1, n2],
        tenant_tokens={"acme": {"store": 4000, "local": 0,
                                "computed": 1000},
                       "bob": {"store": 0, "computed": 500}},
    )
    acme = rep["tenants"]["acme"]
    assert acme["byte_seconds"]["dram"] == pytest.approx(3e9)
    assert acme["byte_seconds"]["disk"] == pytest.approx(1e9)
    assert acme["hits"] == 7 and acme["bytes_written"] == 5000
    assert acme["reuse_ratio"] == pytest.approx(0.8)
    # 4000 store tokens over 4 GB·s held = 1000 tok/GB·s
    assert acme["store_tokens_per_gb_s"] == pytest.approx(1000.0)
    bob = rep["tenants"]["bob"]
    assert bob["reuse_ratio"] == 0.0
    assert rep["nodes"] == 2
    assert rep["top_occupants"][0]["tenant"] == "acme"
    assert rep["top_savers"][0]["tenant"] == "acme"
    assert rep["doa_offenders"][0]["tenant"] == "bob"


def test_merge_usage_reports_router_rollup():
    base = U.usage_report(
        [_node({"acme": {"resident_bytes": {"dram": 10, "disk": 0},
                         "byte_seconds": {"dram": 1e9, "disk": 0},
                         "hits": 1, "evictions": 0,
                         "dead_on_arrival": 0, "bytes_written": 10}})],
        tenant_tokens={"acme": {"store": 100, "computed": 100}},
    )
    # two workers saw the SAME store fleet (byte·seconds dedupe by max)
    # but served DISTINCT requests (tokens sum)
    merged = U.merge_usage_reports([base, base])
    acme = merged["tenants"]["acme"]
    assert acme["byte_seconds"]["dram"] == pytest.approx(1e9)
    assert acme["tokens"]["store"] == pytest.approx(200)
    assert acme["reuse_ratio"] == pytest.approx(0.5)


# ---- satellite lints / trends ----


def test_runbook_lint_green():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "runbook_lint.py")],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_console_usage_view_fixture():
    from infinistore_tpu.top import Console, Snapshot

    usage = {
        "enabled": True,
        "tenants": {
            "acme": {"resident_bytes": {"dram": 5e6, "disk": 0},
                     "byte_seconds": {"dram": 3e9, "disk": 0},
                     "hits": 12, "evictions": 2, "dead_on_arrival": 1,
                     "bytes_written": 1000,
                     "tokens": {"store": 400, "local": 0,
                                "computed": 100},
                     "reuse_ratio": 0.8},
        },
        "top_occupants": [{"tenant": "acme", "value": 3e9}],
        "top_savers": [{"tenant": "acme", "value": 400}],
        "doa_offenders": [],
    }
    c = Console()
    frame = c.frame(Snapshot(usage=usage))
    assert "usage (tenant)" in frame
    assert "acme" in frame
    assert "top occupant: acme" in frame
    # absent payload -> no section
    assert "usage (tenant)" not in Console().frame(Snapshot())


def test_doctor_summary_answers_cache_economics():
    from infinistore_tpu.doctor import summarize_capture

    usage = {
        "enabled": True,
        "tenants": {
            "acme": {"byte_seconds": {"dram": 2e9, "disk": 0},
                     "tokens": {"store": 900, "local": 0,
                                "computed": 100},
                     "reuse_ratio": 0.9, "store_tokens_per_gb_s": 450.0,
                     "evictions": 0, "dead_on_arrival": 0},
            "bob": {"byte_seconds": {"dram": 1e9, "disk": 0},
                    "tokens": {"store": 0, "computed": 100},
                    "reuse_ratio": 0.0, "evictions": 9,
                    "dead_on_arrival": 9},
        },
        "top_occupants": [{"tenant": "acme", "value": 2e9}],
        "top_savers": [{"tenant": "acme", "value": 900}],
        "doa_offenders": [{"tenant": "bob", "value": 9}],
    }
    cap = {
        "fetched_at": 0, "stores": [],
        "serve": {
            "url": "http://s", **{
                name: {"path": p, "file": f, "ok": False, "error": "x",
                       "bytes": 0, "data": None}
                for name, p, f in __import__(
                    "infinistore_tpu.doctor", fromlist=["SERVE_ENDPOINTS"]
                ).SERVE_ENDPOINTS
            },
        },
    }
    cap["serve"]["usage"] = {"path": "/debug/usage",
                            "file": "debug_usage.json", "ok": True,
                            "error": None, "bytes": 1,
                            "data": json.dumps(usage).encode()}
    text = summarize_capture(cap)
    assert "Usage / cache economics" in text
    assert "top occupants" in text and "**acme**" in text
    assert "DOA offenders" in text and "**bob**" in text
    assert "450.0 store-tok/GB·s" in text


# ---- live walks: server subprocess + serving stack ----

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from infinistore_tpu import ClientConfig, InfinityConnection, TYPE_SHM  # noqa: E402
from infinistore_tpu.engine import InferenceEngine  # noqa: E402
from infinistore_tpu.kv import PagedCacheConfig  # noqa: E402
from infinistore_tpu.models import TINY, init_params, scaled  # noqa: E402
from infinistore_tpu.serve import ServingServer  # noqa: E402

CFG = scaled(TINY, dtype=jnp.float32)
PARAMS = init_params(CFG, jax.random.PRNGKey(7))
T = 4


def make_pc(n_blocks=128):
    return PagedCacheConfig(
        n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
        head_dim=CFG.head_dim, n_blocks=n_blocks, block_tokens=T,
        dtype=CFG.dtype,
    )


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
                pytest.fail("store server failed to start")
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


def _stop(proc):
    import signal

    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def _conn(port, **kw):
    c = InfinityConnection(ClientConfig(
        host_addr="127.0.0.1", service_port=port, connection_type=TYPE_SHM,
        log_level="error", op_timeout_s=5.0, **kw,
    ))
    c.connect()
    return c


def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return json.load(r)


def _post(port, body, path="/v1/completions"):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data)


def _metrics_at(port, path="/metrics"):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return m.parse_prometheus_text(r.read().decode())


def test_account_unnegotiated_fails_closed_and_bills_unattributed():
    """Legacy parity: a client that never negotiates the accounting
    capability (ISTPU_ACCOUNT=0) sends byte-identical legacy frames —
    `_account()` answers None even with an account bound — and the
    store bills everything to the unattributed bucket."""
    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    old = os.environ.get("ISTPU_ACCOUNT")
    try:
        os.environ["ISTPU_ACCOUNT"] = "0"
        c = _conn(port)
        raw = c.conn
        assert raw.account_ctx is False  # fail-closed: never negotiated
        with U.bind_account("acme"):
            assert raw._account() is None  # frames stay legacy
            import numpy as np

            payload = np.arange(16 << 10, dtype=np.uint8)
            c.write_cache([("k0", 0)], 16 << 10, payload.ctypes.data)
        c.close()
        rep = _get_json(mport, "/debug/usage")
        assert list(rep["accounts"]) == [U.UNATTRIBUTED]
        del os.environ["ISTPU_ACCOUNT"]
        # negotiated client: the SAME write bills the bound account
        c2 = _conn(port)
        assert c2.conn.account_ctx is True
        with U.bind_account("acme"):
            c2.write_cache([("k1", 0)], 16 << 10, payload.ctypes.data)
        c2.close()
        rep = _get_json(mport, "/debug/usage")
        assert rep["accounts"]["acme"]["bytes_written"] == 16 << 10
    finally:
        if old is None:
            os.environ.pop("ISTPU_ACCOUNT", None)
        else:
            os.environ["ISTPU_ACCOUNT"] = old
        _stop(proc)


@pytest.fixture(scope="module")
def two_tenant_stack():
    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    conn = _conn(port)
    eng = InferenceEngine(PARAMS, CFG, make_pc(), conn=conn,
                          model_id="usage-serve",
                          store_durability="strict")
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=4, model_id="usage-serve",
                        store_manage_endpoints=[f"127.0.0.1:{mport}"])
    srv.start()
    yield srv, proc, port, mport
    srv.close()
    conn.close()
    _stop(proc)


def test_two_tenant_attribution_end_to_end(two_tenant_stack):
    """THE acceptance walk: tenants A (acme) and B (bob) share a
    system-prefix; A also writes private chunks.  /debug/usage and
    /metrics show A's byte·seconds > B's, the shared-prefix bytes split
    across both sharer sets, and per-tenant store-vs-recomputed token
    counts matching the requests actually sent — all asserted
    field-level from scraped Prometheus text."""
    srv, proc, port, mport = two_tenant_stack
    shared = [11, 42, 7, 99, 5, 3, 17, 28]          # 2 complete chunks
    prompt_a = shared + [60 + i for i in range(16)]  # + 4 private chunks
    prompt_b = shared + [90, 91, 92, 93]             # + 1 private chunk

    # a producer engine (tenant acme) seeds the store with A's full
    # prefix — the store-resident state the serving engine adopts
    prod_conn = _conn(port)
    prod = InferenceEngine(PARAMS, CFG, make_pc(), conn=prod_conn,
                           model_id="usage-serve",
                           store_durability="strict")
    with U.bind_account("acme"):
        prod.release(prod.prefill(prompt_a))
        prod.store_flush()
    # provenance baseline AFTER seeding: the producer runs in-process,
    # so its own (computed) tokens sit in the same process-global
    # counter — the request assertions below are deltas
    vm0 = _metrics_at(srv.port)

    # B first (string-lane spelling: priority carries the tenant id):
    # the shared chunks are NOT yet in the serving engine's local
    # cache, so B's prefill reads them from the store tagged "bob" —
    # the cross-tenant read that grows the sharer set
    status, body = _post(srv.port, {
        "prompt": prompt_b, "max_tokens": 4, "temperature": 0,
        "priority": "bob",
    })
    assert status == 200, body
    # A second (explicit tenant field + integer priority): shared
    # chunks now serve LOCALLY (B's prefill registered them), the
    # private chunks come from the store tagged "acme"
    status, body = _post(srv.port, {
        "prompt": prompt_a, "max_tokens": 4, "temperature": 0,
        "priority": 1, "tenant": "acme",
    })
    assert status == 200, body
    srv.engine.store_flush()
    time.sleep(0.4)  # byte·seconds need wall time to accrue

    # -- the store ledger: occupancy, split, hits --
    rep = _get_json(mport, "/debug/usage")
    acme = rep["accounts"]["acme"]
    bob = rep["accounts"]["bob"]
    pb = srv.engine.transfer.wire_page_bytes
    L = CFG.n_layers
    # committed pages: A's 6 chunks (producer) owned by acme, B's 1
    # private chunk owned by bob; the 2 shared chunks split acme/bob
    # after B's read — so bob holds his chunk + half the shared bytes
    assert bob["resident_bytes"]["dram"] == pytest.approx(2 * L * pb)
    assert acme["resident_bytes"]["dram"] == pytest.approx(5 * L * pb)
    assert acme["byte_seconds"]["dram"] > bob["byte_seconds"]["dram"] > 0
    assert bob["hits"] >= 2 * L  # B read the 2 shared chunks
    assert rep["sharer_overflow"] == 0

    # -- the same state from scraped Prometheus text (store /metrics) --
    sm = _metrics_at(mport)

    def usage_metric(name, **labels):
        return sm.get((name, tuple(sorted(labels.items()))))

    assert usage_metric("istpu_store_usage_resident_bytes",
                        account="bob", tier="dram") == \
        pytest.approx(2 * L * pb)
    bs_acme = usage_metric("istpu_store_usage_byte_seconds_total",
                           account="acme", tier="dram")
    bs_bob = usage_metric("istpu_store_usage_byte_seconds_total",
                          account="bob", tier="dram")
    assert bs_acme is not None and bs_bob is not None
    assert bs_acme > bs_bob > 0
    assert usage_metric("istpu_store_usage_hits_total",
                        account="bob") >= 2 * L

    # -- per-tenant token provenance (serve /metrics), matching the
    #    requests actually sent --
    vm = _metrics_at(srv.port)

    def tok(tenant, source):
        key = ("istpu_engine_tenant_prefix_tokens_total",
               (("source", source), ("tenant", tenant)))
        return vm.get(key, 0.0) - vm0.get(key, 0.0)

    # B: 12-token prompt, 2 shared chunks adopted from the store
    assert tok("bob", "store") == 8.0
    assert tok("bob", "computed") == 4.0
    # A: 24-token prompt; shared 2 chunks local (B registered them),
    # private chunks 2..4 from the store, tail computed
    assert tok("acme", "local") == 8.0
    assert tok("acme", "store") == 12.0
    assert tok("acme", "computed") == 4.0

    # -- the joined ledger on the serve plane --
    joined = _get_json(srv.port, "/debug/usage")
    assert joined["enabled"] and joined["nodes"] == 1
    ja = joined["tenants"]["acme"]
    jb = joined["tenants"]["bob"]
    assert ja["tokens"]["store"] == 12.0 and jb["tokens"]["store"] == 8.0
    assert ja["byte_seconds"]["dram"] > jb["byte_seconds"]["dram"]
    assert jb["reuse_ratio"] == pytest.approx(8 / 12, abs=1e-3)
    occupants = [r["tenant"] for r in joined["top_occupants"]]
    assert occupants and occupants[0] == "acme"
    savers = [r["tenant"] for r in joined["top_savers"]]
    assert "acme" in savers and "bob" in savers

    # -- the ledger rows carry the tenant label --
    recs = _get_json(srv.port, "/debug/requests")["records"]
    lanes = {r["lane"] for r in recs}
    assert {"acme", "bob"} <= lanes
