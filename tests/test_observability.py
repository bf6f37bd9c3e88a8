"""The unified observability plane: metrics registry, Prometheus
exposition on BOTH /metrics endpoints (serving front-end and store manage
plane), request-scoped tracing with Chrome trace export, and the
/debug/traces ring.

The Prometheus checks go through one strict text-format parser
(``parse_prometheus``): a TYPE line per series, histogram buckets monotone
in ``le``, and the ``+Inf`` bucket equal to ``_count`` — the invariants a
real scraper depends on and hand-formatted exposition tends to break.
"""

import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from infinistore_tpu.utils import tracing
from infinistore_tpu.utils.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    nearest_rank,
)

# ---------------------------------------------------------------------------
# strict Prometheus text-format parser (the scrape contract, not a regex
# sniff): used below against both servers' /metrics bodies
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>[^ ]+)$"
)
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str):
    """Parse exposition text, enforcing the format invariants.

    Returns ``{family: {"type": kind, "samples": [(name, labels, value)]}}``
    where ``labels`` is a dict.  Raises AssertionError on: a sample with no
    preceding TYPE for its family, duplicate TYPE lines, an unparseable
    line, non-monotone histogram buckets, or ``+Inf`` != ``_count``.
    """
    families = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            parts = line.split(None, 3)
            assert len(parts) == 4, f"bad TYPE line {lineno}: {line!r}"
            _, _, name, kind = parts
            assert kind in ("counter", "gauge", "histogram", "untyped"), line
            assert name not in families, f"duplicate TYPE for {name}"
            families[name] = {"type": kind, "samples": []}
            continue
        assert not line.startswith("#"), f"unknown comment line: {line!r}"
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line {lineno}: {line!r}"
        name = m.group("name")
        labels = dict(
            (k, v) for k, v in _LABEL.findall(m.group("labels") or "")
        )
        value = float(m.group("value"))
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and base in families and families[base]["type"] == "histogram":
                family = base
                break
        assert family in families, f"sample {name} has no TYPE line"
        families[family]["samples"].append((name, labels, value))
    _check_histograms(families)
    return families


def _check_histograms(families):
    for fam, rec in families.items():
        if rec["type"] != "histogram":
            continue
        series = {}  # label-set minus le -> {le_value: count}
        sums, counts = {}, {}
        for name, labels, value in rec["samples"]:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            if name == f"{fam}_bucket":
                le = labels.get("le")
                assert le is not None, f"{fam} bucket without le"
                bound = math.inf if le == "+Inf" else float(le)
                series.setdefault(key, {})[bound] = value
            elif name == f"{fam}_sum":
                sums[key] = value
            elif name == f"{fam}_count":
                counts[key] = value
        # a labeled family with no children yet legally emits only its
        # TYPE line; invariants apply per materialized child
        for key, buckets in series.items():
            bounds = sorted(buckets)
            assert bounds[-1] == math.inf, f"{fam}{key} missing +Inf bucket"
            cum = [buckets[b] for b in bounds]
            assert all(a <= b for a, b in zip(cum, cum[1:])), (
                f"{fam}{key} buckets not monotone: {cum}"
            )
            assert key in counts and key in sums, f"{fam}{key} missing sum/count"
            assert buckets[math.inf] == counts[key], (
                f"{fam}{key}: +Inf bucket {buckets[math.inf]} != "
                f"count {counts[key]}"
            )


# ---------------------------------------------------------------------------
# registry unit behavior
# ---------------------------------------------------------------------------

def test_registry_exposition_is_strictly_valid():
    reg = MetricsRegistry()
    c = reg.counter("obs_total", "a counter")
    c.inc()
    c.inc(2)
    g = reg.gauge("obs_depth", "a gauge")
    g.set(3)
    g.dec()
    h = reg.histogram("obs_seconds", "a histogram", labelnames=("op",))
    for v in (1e-6, 1e-3, 0.5, 100.0):  # below first bucket / mid / above last
        h.labels("put").observe(v)
    h.labels(op="get").observe(0.25)
    fams = parse_prometheus(reg.to_prometheus_text())
    assert fams["obs_total"]["type"] == "counter"
    assert fams["obs_total"]["samples"][0][2] == 3
    assert fams["obs_depth"]["samples"][0][2] == 2
    # the out-of-range 100.0 lands only in +Inf
    buckets = {
        (labels["op"], labels["le"]): v
        for name, labels, v in fams["obs_seconds"]["samples"]
        if name.endswith("_bucket")
    }
    assert buckets[("put", "+Inf")] == 4
    top = f"{DEFAULT_BUCKETS[-1]:.10g}"
    assert buckets[("put", top)] == 3


def test_registry_get_or_create_and_type_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("same_total", "x")
    assert reg.counter("same_total") is a  # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("same_total")
    with pytest.raises(ValueError):
        reg.counter("same_total", labelnames=("op",))
    with pytest.raises(ValueError):
        a.inc(-1)  # counters only go up
    # fn rebinding: a re-created server takes over its metric names
    reg.gauge("live", "x", fn=lambda: 1)
    reg.gauge("live", "x", fn=lambda: 2)
    assert "live 2" in reg.to_prometheus_text()


def test_registry_multithreaded_hammer():
    """N threads hammer one counter, one gauge, and one labeled histogram;
    totals must be exact (no lost updates) and exposition valid while
    being scraped concurrently."""
    reg = MetricsRegistry()
    c = reg.counter("hammer_total", "")
    h = reg.histogram("hammer_seconds", "", labelnames=("op",))
    n_threads, per = 8, 2000
    scrapes = []

    def work(i):
        child = h.labels(f"op{i % 2}")
        for k in range(per):
            c.inc()
            child.observe(k * 1e-5)

    def scrape():
        for _ in range(50):
            scrapes.append(reg.to_prometheus_text())

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)] + [threading.Thread(target=scrape)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fams = parse_prometheus(reg.to_prometheus_text())
    assert fams["hammer_total"]["samples"][0][2] == n_threads * per
    counts = {
        labels["op"]: v
        for name, labels, v in fams["hammer_seconds"]["samples"]
        if name.endswith("_count")
    }
    assert counts == {"op0": 4 * per, "op1": 4 * per}
    for text in scrapes:  # every mid-flight scrape was internally valid
        parse_prometheus(text)


def test_nearest_rank_semantics():
    """ceil(q*n)-1 nearest-rank on sorted samples — the ONE shared
    percentile definition (was two disagreeing copies)."""
    xs = [1.0, 2.0, 3.0, 4.0]
    assert nearest_rank(xs, 0.50) == 2.0  # ceil(2)-1 = idx 1
    assert nearest_rank(xs, 0.51) == 3.0
    assert nearest_rank(xs, 0.99) == 4.0
    assert nearest_rank(xs, 0.0) == 1.0
    assert nearest_rank([7.0], 0.99) == 7.0
    assert nearest_rank([], 0.5) == 0.0


# ---------------------------------------------------------------------------
# tracing: nesting, propagation, Chrome export round-trip
# ---------------------------------------------------------------------------

def test_chrome_trace_round_trip():
    tracer = tracing.Tracer(ring=8)
    with tracer.trace("request", req=1) as tr:
        trace_id = tr.trace_id
        with tracer.span("transfer"):
            with tracer.span("pool_copy", bytes=4096):
                time.sleep(0.002)
        tracer.add_stage("commit", 0.001)
    out = json.loads(tracer.export_chrome_json())
    events = [e for e in out["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in events} == {
        "request", "transfer", "pool_copy", "commit"
    }
    for e in out["traceEvents"]:  # required Chrome trace-event keys
        assert {"ph", "pid", "tid", "name"} <= set(e)
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e and e["dur"] >= 0
    by = {e["name"]: e for e in events}
    # spans nest: child interval inside parent interval, one trace id
    for child, parent in (("pool_copy", "transfer"), ("transfer", "request")):
        c, p = by[child], by[parent]
        assert p["ts"] <= c["ts"] + 1e-6
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6
    assert {e["args"]["trace_id"] for e in events} == {trace_id}
    assert by["pool_copy"]["args"]["bytes"] == 4096


def test_span_without_trace_is_noop_and_ring_is_bounded():
    tracer = tracing.Tracer(ring=4)
    with tracer.span("orphan"):
        assert tracer.current_trace_id() is None
    assert tracer.recent() == []
    for i in range(10):
        with tracer.trace(f"t{i}"):
            pass
    assert [t.name for t in tracer.recent()] == [f"t{i}" for i in range(6, 10)]


def test_trace_id_propagates_through_nested_calls():
    tracer = tracing.Tracer()
    seen = []

    def library_layer():  # no plumbing: reads the contextvar
        seen.append(tracer.current_trace_id())
        with tracer.span("inner"):
            pass

    with tracer.trace("outer") as tr:
        library_layer()
        assert seen == [tr.trace_id]
        # a nested trace() degrades to a span of the SAME trace
        with tracer.trace("not-a-new-root"):
            assert tracer.current_trace_id() == tr.trace_id
    assert len(tracer.recent()) == 1  # one request = one trace


# ---------------------------------------------------------------------------
# store manage plane over HTTP (subprocess server, real wire traffic)
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def store_server():
    sport, mport = _free_port(), _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(sport), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 25
    for port in (sport, mport):
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail("store server died during startup")
            try:
                socket.create_connection(
                    ("127.0.0.1", port), timeout=0.5).close()
                break
            except OSError:
                time.sleep(0.1)
    yield sport, mport
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_store_manage_plane_prometheus(store_server):
    """/metrics on the store's manage plane is valid exposition carrying
    occupancy, fragmentation, leases, eviction, contig_batches, and
    per-op latency histograms; /healthz answers ok."""
    import numpy as np

    import infinistore_tpu as ist

    sport, mport = store_server
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=sport,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    blk = 16 << 10
    buf = np.random.randint(0, 256, 8 * blk, dtype=np.uint8)
    conn.register_mr(buf)
    blocks = [(f"obs-{i}", i * blk) for i in range(8)]
    conn.write_cache(blocks, blk, buf.ctypes.data)
    dst = np.zeros_like(buf)
    conn.register_mr(dst)
    conn.read_cache(blocks, blk, dst.ctypes.data)
    assert np.array_equal(buf, dst)

    with urllib.request.urlopen(
        f"http://127.0.0.1:{mport}/healthz", timeout=10
    ) as r:
        assert json.load(r)["status"] == "ok"
    with urllib.request.urlopen(
        f"http://127.0.0.1:{mport}/metrics", timeout=10
    ) as r:
        assert r.headers["Content-Type"] == "text/plain; version=0.0.4"
        text = r.read().decode()
    fams = parse_prometheus(text)
    for name in ("istpu_store_pool_usage", "istpu_store_fragmentation",
                 "istpu_store_active_read_leases",
                 "istpu_store_evicted_total",
                 "istpu_store_contig_batches_total",
                 "infinistore_tpu_usage", "infinistore_tpu_puts"):
        assert name in fams, f"missing {name}"
    # the batch above was served as a contiguous run on a fresh pool
    assert fams["istpu_store_contig_batches_total"]["samples"][0][2] >= 1
    # the GET_DESC read leases the entries; scraped within the 5 s window
    assert fams["istpu_store_active_read_leases"]["samples"][0][2] >= 1
    # per-op latency histograms saw the ops this client just issued
    ops = {
        labels["op"]
        for name, labels, _ in fams["istpu_store_op_seconds"]["samples"]
        if name.endswith("_count")
    }
    assert {"ALLOC_PUT", "COMMIT_PUT", "GET_DESC"} <= ops, ops
    conn.close()


def test_trace_nests_request_through_transfer_to_pool_copy(
        store_server, monkeypatch):
    """The acceptance shape: one trace id from the request root through
    the transfer layer (``kv.push_pages``) down to the client's pool
    memcpy stage (``write_cache.copy``), spans properly contained.
    Python client: the native client keeps its stage timings in C."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    import jax
    import jax.numpy as jnp

    import infinistore_tpu as ist
    from infinistore_tpu.kv import (
        KVTransferEngine,
        PagedCacheConfig,
        chunk_keys,
        init_cache,
        write_pages,
    )

    sport, _ = store_server
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=sport,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    pc = PagedCacheConfig(n_layers=2, n_kv_heads=2, head_dim=16,
                          n_blocks=8, block_tokens=16, dtype=jnp.float32)
    eng = KVTransferEngine(conn, pc)
    cache = init_cache(pc)
    pages = jax.random.normal(
        jax.random.PRNGKey(1), (2, 2, 2, 2, 16, 16), jnp.float32)
    cache = write_pages(cache, jnp.asarray([0, 1]), pages)
    keys = chunk_keys(list(range(32)), "tracemodel")

    tracer = tracing.TRACER
    with tracer.trace("request") as tr:
        trace_id = tr.trace_id
        eng.save_pages(cache, [0, 1], keys)
    conn.close()

    done = next(t for t in reversed(tracer.recent())
                if t.trace_id == trace_id)
    out = tracer.export_chrome([done])
    events = [e for e in out["traceEvents"] if e.get("ph") == "X"]
    assert all(e["args"]["trace_id"] == trace_id for e in events)
    by = {e["name"]: e for e in events}
    # the alloc-first push records its fused D2H+pool stage as
    # write_cache.fill (pre-alloc-first clients recorded write_cache.copy)
    assert {"request", "kv.push_pages", "write_cache.fill"} <= set(by), (
        sorted(by)
    )

    def contained(child, parent):
        c, p = by[child], by[parent]
        return (p["ts"] <= c["ts"] + 1e-6
                and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6)

    assert contained("kv.push_pages", "request")
    assert contained("write_cache.fill", "kv.push_pages")


# ---------------------------------------------------------------------------
# serving front-end /metrics + /debug/traces (in-process tiny engine)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving():
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params, scaled
    from infinistore_tpu.serve import ServingServer

    cfg = scaled(TINY, dtype=jnp.float32)
    eng = InferenceEngine(
        init_params(cfg, jax.random.PRNGKey(3)), cfg,
        PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, n_blocks=64, block_tokens=4,
            dtype=cfg.dtype,
        ),
    )
    eng.decode_chunk = 4
    srv = ServingServer(eng, port=0, max_batch=4, model_id="obs-test")
    srv.start()
    yield srv
    srv.close()


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=60
    ) as r:
        return r.headers, r.read().decode()


def test_serve_metrics_prometheus(serving):
    body = json.dumps({
        "prompt": [5, 9, 2, 14, 3], "max_tokens": 4, "temperature": 0,
    }).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{serving.port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        json.load(r)

    # "completed" increments on the engine thread right AFTER the final
    # token event is streamed, so give the counter a moment to land
    deadline = time.time() + 10
    while True:
        headers, text = _get(serving.port, "/metrics")
        fams = parse_prometheus(text)
        if (fams["istpu_serve_completed_total"]["samples"][0][2] >= 1
                or time.time() > deadline):
            break
        time.sleep(0.05)
    assert headers["Content-Type"] == "text/plain; version=0.0.4"
    # pre-registry names preserved
    for name in ("istpu_serve_requests_total", "istpu_serve_completed_total",
                 "istpu_serve_tokens_total", "istpu_serve_free_kv_pages",
                 "istpu_serve_queue_wait_p50_ms", "istpu_serve_prefill_p99_ms"):
        assert name in fams, f"missing {name}"
    assert fams["istpu_serve_requests_total"]["samples"][0][2] >= 1
    assert fams["istpu_serve_completed_total"]["samples"][0][2] >= 1
    # the rate()-able histograms behind the convenience p50/p99 gauges
    for name in ("istpu_serve_queue_wait_seconds",
                 "istpu_serve_prefill_seconds",
                 "istpu_serve_decode_step_seconds"):
        assert fams[name]["type"] == "histogram", name
        count = [v for n, _, v in fams[name]["samples"]
                 if n == f"{name}_count"]
        assert count and count[0] >= 1, (name, fams[name]["samples"])


def test_serve_debug_traces(serving):
    """/debug/traces returns Perfetto-loadable Chrome trace JSON with the
    scheduler's per-step spans recorded by the engine thread."""
    body = json.dumps({
        "prompt": [8, 1, 6], "max_tokens": 4, "temperature": 0,
    }).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{serving.port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        json.load(r)
    headers, text = _get(serving.port, "/debug/traces")
    assert headers["Content-Type"] == "application/json"
    out = json.loads(text)
    events = [e for e in out["traceEvents"] if e.get("ph") == "X"]
    assert events, "trace ring is empty after a served request"
    for e in events:
        assert {"ph", "ts", "pid", "tid", "name", "dur"} <= set(e)
    names = {e["name"] for e in events}
    assert "engine.step" in names
    # the engine thread's flat phases are the step's spans (one timing per
    # site: they replaced sched.decode_chunk / sched.prefill_step)
    assert "istpu.decode.wait" in names or "istpu.prefill.launch" in names
    # the http-side trace rides the same ring
    assert "http.request" in names
