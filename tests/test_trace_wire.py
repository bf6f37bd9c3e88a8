"""Cross-process trace propagation over the wire.

The contract under test (docs/observability.md §cross-process trace
propagation):

* HELLO negotiation is flag-gated and byte-compatible in BOTH legacy
  directions (old-client↔new-server, new-client↔old-server);
* with a negotiated connection, ops issued inside an active trace carry
  the trace id, the python server records REAL spans under that id, and
  the stitcher merges the two rings into one Chrome trace with correct
  parent/child nesting across the wire (clock-skew corrected);
* faults injected server-side show up as long *server* spans (the
  debugging story the whole feature exists for), and a dropped
  connection leaves the client ring consistent — no orphan open spans;
* the ring is configurable (ISTPU_TRACE_RING) and overflow is counted.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu import protocol as P
from infinistore_tpu.utils import metrics as m
from infinistore_tpu.utils import tracing, trace_stitch


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
                pytest.fail("store server failed to start")
            try:
                socket.create_connection(("127.0.0.1", p), timeout=0.5).close()
                break
            except OSError:
                if time.time() >= deadline:
                    proc.kill()
                    pytest.fail(f"port {p} did not come up")
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


@pytest.fixture(scope="module")
def server():
    port, mport = _free_port(), _free_port()
    proc = _boot(port, mport)
    yield port, mport
    _stop(proc)


@pytest.fixture(autouse=True)
def _python_client_and_clean_faults(server, monkeypatch):
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    yield
    try:
        _arm(server[1], [])
    except OSError:
        pass


def _conn(port, **kw):
    c = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port,
        connection_type=ist.TYPE_SHM, log_level="error", **kw,
    ))
    c.connect()
    return c


def _rw(conn, tag, n=4, blk=16 << 10):
    buf = np.random.randint(0, 256, n * blk, dtype=np.uint8)
    conn.register_mr(buf)
    dst = np.zeros_like(buf)
    conn.register_mr(dst)
    blocks = [(f"{tag}-{i}", i * blk) for i in range(n)]
    conn.write_cache(blocks, blk, buf.ctypes.data)
    conn.read_cache(blocks, blk, dst.ctypes.data)
    assert np.array_equal(buf, dst)
    return blocks


def _x_events(chrome):
    return [e for e in chrome["traceEvents"] if e.get("ph") == "X"]


def _contained(child, parent, slack_us=2000.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


# ---------------------------------------------------------------------------
# negotiation + byte parity
# ---------------------------------------------------------------------------


def test_hello_negotiates_trace_ctx_and_clock_offset(server):
    conn = _conn(server[0])
    raw = conn.conn
    assert raw.trace_ctx is True
    # same host, same perf_counter domain: the midpoint estimate must be
    # tiny (seconds of skew would mean the math is wrong, not the clock)
    assert raw.clock_offset is not None and abs(raw.clock_offset) < 1.0
    conn.close()


def test_env_opt_out_disables_negotiation(server, monkeypatch):
    monkeypatch.setenv("ISTPU_TRACE_CTX", "0")
    conn = _conn(server[0])
    raw = conn.conn
    assert raw.trace_ctx is False
    with tracing.trace("optout.request"):
        # even inside an active trace: no negotiation -> no flagged frames
        assert raw._trace_id() is None
        _rw(conn, "optout")
    with pytest.raises(ist.InfiniStoreException):
        raw.trace_dump()
    conn.close()


def test_no_active_trace_means_legacy_frames(server):
    """Flag-gating is per FRAME: a negotiated connection with no active
    trace injects nothing (the perf floor's no-tracing case)."""
    conn = _conn(server[0])
    raw = conn.conn
    assert raw.trace_ctx is True
    assert raw._trace_id() is None  # no trace bound -> legacy bytes
    with tracing.trace("flagged"):
        assert raw._trace_id() is not None
    conn.close()


def test_wire_byte_parity_both_directions():
    """Pure protocol-level parity: the exact byte shapes each side of a
    mixed-version pair exchanges."""
    pools = [("istpu_pool_0", 1 << 20, 16 << 10)]
    legacy_body = P.pack_pool_table(pools)
    # old client <-> new server: the old client's HELLO carries flags 0,
    # so the new server appends NO trailer — and even a trailer-bearing
    # body parses identically through the legacy pool-table parser
    # (length-prefixed: trailing bytes are ignored)
    pid, flags = P.unpack_hello(memoryview(P.pack_hello(1234)))
    assert (pid, flags) == (1234, 0)
    with_trailer = legacy_body + P.pack_hello_trailer(
        P.HELLO_FLAG_TRACE_CTX, 123.456)
    assert P.unpack_pool_table(memoryview(with_trailer)) == pools
    assert P.unpack_pool_table(memoryview(legacy_body)) == pools
    # new client <-> old server: no trailer -> negotiation fails closed
    got_pools, srv_flags, t_server = P.unpack_hello_resp(
        memoryview(legacy_body))
    assert got_pools == pools and srv_flags == 0 and t_server == 0.0
    # and the trailer round-trips when present
    got_pools, srv_flags, t_server = P.unpack_hello_resp(
        memoryview(with_trailer))
    assert srv_flags == P.HELLO_FLAG_TRACE_CTX
    assert t_server == pytest.approx(123.456)
    # the per-op ctx blob round-trips and reports its exact size
    blob = P.pack_trace_ctx("abc-12f")
    tid, consumed = P.unpack_trace_ctx(memoryview(blob + b"rest"))
    assert tid == "abc-12f" and consumed == len(blob)


# ---------------------------------------------------------------------------
# server-side spans + stitching
# ---------------------------------------------------------------------------


def test_server_spans_land_under_client_trace_and_stitch(server):
    conn = _conn(server[0])
    raw = conn.conn
    with tracing.trace("wire.request") as tr:
        trace_id = tr.trace_id
        _rw(conn, "stitch")
    dump = raw.trace_dump()
    assert dump["pid"] != os.getpid()
    mine = [t for t in dump["traces"] if t["trace_id"] == trace_id]
    names = {ev[0] for t in mine for ev in t["events"]}
    # recv → alloc → pool state → commit / desc build, per the issue
    assert {"store.ALLOC_PUT", "store.alloc", "store.COMMIT_PUT",
            "store.commit", "store.GET_DESC", "store.desc_build",
            "store.recv"} <= names, names

    chrome = trace_stitch.stitch_chrome(
        tracing.TRACER, [(dump, raw.clock_offset)])
    evs = [e for e in _x_events(chrome)
           if e["args"].get("trace_id") == trace_id]
    pids = {e["pid"] for e in evs}
    assert len(pids) == 2, "client AND server events under one trace id"
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], e)
    # nesting across the wire, clock-skew corrected: the server's
    # GET_DESC processing sits inside the client's desc round-trip span,
    # and desc_build inside GET_DESC
    # ...to within what the correction itself claims: the offset was
    # estimated at HELLO with an error bound of half that round trip
    # (``clock_offset_err``), and on a host that five other test workers
    # load one round trip can take longer than the 2 ms of slack (a 0.08 ms
    # GET_DESC read as outside its 0.31 ms round trip)
    across = max(2000.0, (raw.clock_offset_err or 0.0) * 1e6)
    assert _contained(by_name["store.GET_DESC"], by_name["read_cache.desc"],
                      slack_us=across)
    assert _contained(by_name["store.desc_build"], by_name["store.GET_DESC"])
    assert _contained(by_name["read_cache.desc"], by_name["wire.request"])
    conn.close()


def test_delayed_op_shows_as_long_server_side_span(server):
    """Fault + trace: an injected GET_DESC delay must be attributable to
    the SERVER in the stitched trace — the 'why was this request slow'
    answer the feature exists to give."""
    port, mport = server
    conn = _conn(port)
    raw = conn.conn
    _arm(mport, [{"op": "GET_DESC", "action": "delay", "delay_s": 0.4,
                  "times": 1}])
    with tracing.trace("slow.request") as tr:
        trace_id = tr.trace_id
        _rw(conn, "delay")
    _arm(mport, [])
    dump = raw.trace_dump()
    chrome = trace_stitch.stitch_chrome(
        tracing.TRACER, [(dump, raw.clock_offset)])
    evs = [e for e in _x_events(chrome)
           if e["args"].get("trace_id") == trace_id]
    srv_desc = [e for e in evs if e["name"] == "store.GET_DESC"]
    assert srv_desc, [e["name"] for e in evs]
    assert max(e["dur"] for e in srv_desc) >= 0.3e6, (
        "the injected 0.4s delay must be visible as server-side time"
    )
    # ...and the inner desc_build stayed fast: the stall was NOT the store
    # data structures, which is exactly the attribution that matters
    build = [e for e in evs if e["name"] == "store.desc_build"]
    assert build and max(e["dur"] for e in build) < 0.2e6
    conn.close()


def test_dropped_conn_leaves_client_ring_consistent(server):
    """A connection the server kills mid-op reconnects (PR 3 machinery);
    the trace ring must come out consistent: the request trace completes,
    every span is closed, and no trace is left bound to the context."""
    port, mport = server
    conn = _conn(port)
    _arm(mport, [{"op": "GET_DESC", "action": "drop_conn", "times": 1}])
    with tracing.trace("dropped.request") as tr:
        trace_id = tr.trace_id
        _rw(conn, "dropped")  # absorbed by auto-reconnect
    _arm(mport, [])
    assert tracing.TRACER.current() is None, "no trace left bound"
    done = [t for t in tracing.TRACER.recent() if t.trace_id == trace_id]
    assert len(done) == 1, "the request trace completed into the ring"
    tr = done[0]
    assert tr.t_end is not None
    for name, t0, t1, _tid, _args in tr.events:
        assert t1 >= t0, f"orphan open span {name}"
    # the op itself succeeded over the fresh connection
    conn.close()


def test_trace_dump_over_reconnect(server):
    """After a reconnect the FRESH connection renegotiates: trace context
    survives the PR 3 recovery machinery instead of silently degrading."""
    conn = _conn(server[0])
    assert conn.conn.trace_ctx
    conn.reconnect()
    assert conn.conn.trace_ctx, "renegotiated on the replacement transport"
    with tracing.trace("post.reconnect") as tr:
        trace_id = tr.trace_id
        _rw(conn, "postrec")
    ids = {t["trace_id"] for t in conn.trace_dump()["traces"]}
    assert trace_id in ids
    conn.close()


# ---------------------------------------------------------------------------
# the acceptance shape: one serve request against a live python store,
# /debug/traces exports a STITCHED timeline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_with_store(server):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params, scaled
    from infinistore_tpu.serve import ServingServer

    prev = os.environ.get("ISTPU_CLIENT")
    os.environ["ISTPU_CLIENT"] = "python"
    try:
        cfg = scaled(TINY, dtype=jnp.float32)
        params = init_params(cfg, jax.random.PRNGKey(7))
        T = 4

        def pc():
            return PagedCacheConfig(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, n_blocks=64, block_tokens=T,
                dtype=cfg.dtype)

        port, _ = server
        prompt = [21, 3, 7, 1, 5, 2, 8, 6, 4, 11, 13]
        # a producer seeds the prompt's prefix in the STORE, so the
        # serving engine's prefill takes the store-load path (GET_DESC
        # under its engine.step trace — the wire hop we want stitched)
        prod_conn = _conn(port, op_timeout_s=10.0)
        producer = InferenceEngine(params, cfg, pc(), conn=prod_conn,
                                   model_id="stitch-serve")
        producer.release(producer.prefill(prompt))
        producer.store_flush()

        conn = _conn(port, op_timeout_s=10.0)
        eng = InferenceEngine(params, cfg, pc(), conn=conn,
                              model_id="stitch-serve")
        eng.decode_chunk = 4
        srv = ServingServer(eng, port=0, max_batch=2,
                            model_id="stitch-serve")
        srv.start()
        yield srv, prompt
        srv.close()
        conn.close()
        prod_conn.close()
    finally:
        if prev is None:
            os.environ.pop("ISTPU_CLIENT", None)
        else:
            os.environ["ISTPU_CLIENT"] = prev


def test_serve_debug_traces_is_stitched_end_to_end(serving_with_store):
    srv, prompt = serving_with_store
    body = json.dumps({"prompt": prompt, "max_tokens": 4,
                       "temperature": 0}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        json.load(r)

    with urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/debug/traces", timeout=30
    ) as r:
        chrome = json.load(r)
    evs = _x_events(chrome)
    assert evs, "empty stitched export"
    my_pid = os.getpid()
    names = {e["name"] for e in evs}
    assert "http.request" in names  # handler-thread trace rides along

    # the acceptance claim: client AND server spans under ONE trace id
    by_trace = {}
    for e in evs:
        by_trace.setdefault(e["args"].get("trace_id"), []).append(e)
    stitched = {
        tid: grp for tid, grp in by_trace.items()
        if {e["pid"] for e in grp} - {my_pid}
        and my_pid in {e["pid"] for e in grp}
    }
    assert stitched, "no trace id carries spans from BOTH processes"
    # find the store-load hop: server GET_DESC nested inside the client's
    # kv.load_pages (itself inside the engine-side trace root)
    for tid, grp in stitched.items():
        srv_desc = [e for e in grp if e["name"] == "store.GET_DESC"
                    and e["pid"] != my_pid]
        cli_load = [e for e in grp if e["name"] == "kv.load_pages"
                    and e["pid"] == my_pid]
        if srv_desc and cli_load:
            assert any(_contained(s, c)
                       for s in srv_desc for c in cli_load), (
                "server GET_DESC span not nested inside the client's "
                "kv.load_pages window"
            )
            break
    else:
        pytest.fail(
            f"no stitched trace pairs store.GET_DESC with kv.load_pages: "
            f"{ {t: sorted({e['name'] for e in g}) for t, g in stitched.items()} }"
        )
    # server events carry their own process row with a readable name
    meta = [e for e in chrome["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert any(e["args"]["name"] == "store-server" for e in meta)


# ---------------------------------------------------------------------------
# ring configurability + overflow accounting
# ---------------------------------------------------------------------------


def test_ring_size_env_and_dropped_counter(monkeypatch):
    monkeypatch.setenv("ISTPU_TRACE_RING", "3")
    tracer = tracing.Tracer()  # picks the env up per instance
    for i in range(5):
        with tracer.trace(f"t{i}"):
            pass
    assert [t.name for t in tracer.recent()] == ["t2", "t3", "t4"]
    assert tracer.dropped == 2
    # the process-wide overflow counter is a registered family
    text = m.default_registry().to_prometheus_text()
    assert "istpu_trace_ring_dropped_total" in text
    # explicit ring argument wins over the env
    assert tracing.Tracer(ring=7)._done.maxlen == 7
    monkeypatch.setenv("ISTPU_TRACE_RING", "not-a-number")
    assert tracing.Tracer()._done.maxlen == tracing.TRACE_RING_DEFAULT

    # dump() round-trips through JSON (the wire shape)
    with tracer.trace("dumpme", tag=1):
        pass
    dump = json.loads(json.dumps(tracer.dump(limit=1)))
    assert dump["traces"][0]["name"] == "dumpme"
    assert dump["pid"] == os.getpid() and dump["clock"] > 0
