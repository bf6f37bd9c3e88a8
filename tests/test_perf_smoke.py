"""Data-plane perf floor: a cheap guard against re-serializing the put path.

Three guards, each catching a different way the coalesced data plane
(contiguous-run server allocation + client run merging + bulk copies)
could silently regress to the old per-page loop:

* STRUCTURAL, server: a batch ALLOC_PUT on a fresh pool must be served
  as a contiguous run (``contig_batches`` stat increments) — guards the
  allocator fast path, whose per-region predecessor cost ~14 ms per
  2048-key batch.
* STRUCTURAL, client: a contiguous desc list must collapse to ONE copy
  run in ``_merge_runs`` — guards the client half of coalescing.
* TIMING: end-to-end shm put bandwidth (64 KB pages, 128 MB, best of 4)
  clears a floor the old per-page stack cannot reach.  Calibrated on the
  1-vCPU reference host: old stack 1.86 GB/s, coalesced stack ~4.0 GB/s,
  host memcpy wall ~5.8 GB/s; the 2.4 floor sits ~30% above old and
  ~40% below new, so it survives moderate load spikes while still
  failing on any real re-serialization.
"""

import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu.lib import _merge_runs

pytestmark = pytest.mark.perf

PUT_FLOOR_GBPS = 2.4
# store-attached prefill budget (relaxed durability, the shipping
# default): the critical-path half of a push is alloc-free and
# copy-free — kick the async D2H, enqueue — so an attached prefill may
# cost at most 20% over detached (the repo-level form of the reference's
# <=1% overhead claim; the on-chip prefill_store_overhead <= 1.2 target
# is asserted at the next live bench_tpu capture)
ATTACHED_PREFILL_BUDGET = 1.2


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    port, mport = _free_port(), _free_port()
    # the SPILL TIER is attached on purpose: every perf floor below must
    # hold with it enabled (the acceptance bar for the tiered store —
    # demotion is background-only and eviction never fires at these
    # sizes, so the tier must cost the put path nothing)
    tier_dir = str(tmp_path_factory.mktemp("perf_disk_tier"))
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python",
         "--disk-tier-path", tier_dir, "--disk-tier-size", "1"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 25
    while time.time() < deadline:
        if proc.poll() is not None:
            pytest.fail("perf server failed to start")
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    yield port
    proc.send_signal(signal.SIGINT)
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_merge_runs_collapses_contiguous_batch():
    """2048 contiguous descriptors must merge into ONE bulk-copy run, and
    a pool/client discontinuity must split exactly there."""
    bs = 64 << 10
    descs = [(0, i * bs, bs) for i in range(2048)]
    offsets = [i * bs for i in range(2048)]
    runs = _merge_runs(descs, offsets)
    assert len(runs) == 1 and runs[0] == [0, 0, 0, 2048 * bs]
    # a hole on the pool side splits the run
    descs[1024] = (0, 1025 * bs, bs)
    runs = _merge_runs(descs, offsets)
    assert len(runs) == 3
    # different pool splits too
    descs[1024] = (1, 1024 * bs, bs)
    assert len(_merge_runs(descs, offsets)) == 3


def test_put_clears_floor_old_loop_cannot(server, monkeypatch, timed_walk):
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    blk = 64 << 10
    nbytes = 128 << 20
    buf = np.random.randint(0, 256, nbytes, dtype=np.uint8)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=server,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    conn.register_mr(buf)
    n = nbytes // blk
    # best of up to twelve puts (four, beside five loaded test workers,
    # read 2.22 GB/s: every one of the four was slowed); it stops at the
    # first that clears the floor, which is what the floor asks
    best = float("inf")
    for it in range(12):
        blocks = [(f"perf-{it}-{i}", i * blk) for i in range(n)]
        t0 = time.perf_counter()
        conn.write_cache(blocks, blk, buf.ctypes.data)
        best = min(best, time.perf_counter() - t0)
        conn.delete_keys([k for k, _ in blocks])
        if it >= 3 and nbytes / 1e9 / best >= PUT_FLOOR_GBPS:
            break
    stats = conn.stats()
    stages = conn.latency_stats()
    conn.close()

    # structural: the server really served contiguous runs
    assert stats.get("contig_batches", 0) >= 1, stats
    put_gbps = nbytes / 1e9 / best
    breakdown = {
        k: v["p50_ms"] for k, v in stages.items() if k.startswith("write_cache")
    }
    assert put_gbps >= PUT_FLOOR_GBPS, (
        f"shm put {put_gbps:.2f} GB/s under the {PUT_FLOOR_GBPS} GB/s floor "
        f"(the old per-page stack measured 1.86 on the reference host) — "
        f"stage p50s: {breakdown}"
    )


def test_instrumentation_overhead_within_5pct(server, monkeypatch):
    """The observability plane must not give back the coalescing win:
    put bandwidth with tracing ACTIVE (every op/stage recorded as span
    events) and the metrics histograms fed stays within 5% of the PR 1
    floor.  Metrics are always on (the LatencyStats sink); this test
    additionally opens a live trace so the span path is exercised, then
    checks the trace and histogram actually captured the run."""
    from infinistore_tpu.utils import metrics as m
    from infinistore_tpu.utils import tracing

    from infinistore_tpu.engine.stepprof import StepProfiler

    monkeypatch.setenv("ISTPU_CLIENT", "python")
    blk = 64 << 10
    nbytes = 128 << 20
    buf = np.random.randint(0, 256, nbytes, dtype=np.uint8)
    dst = np.zeros_like(buf)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=server,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    conn.register_mr(buf)
    conn.register_mr(dst)
    n = nbytes // blk
    tracer = tracing.TRACER
    # the step profiler rides INSIDE the measured window at its default
    # sampling — the ≤5% guard now covers the whole attribution plane
    # (tracing + metrics + per-step profiling), not just tracing
    prof = StepProfiler()
    # ...and so does the HEALTH SAMPLER: a live background sampler at
    # its default cadence, scraping the registry the measured ops feed,
    # proves the fleet-health plane rides inside the same 5% envelope
    # (the acceptance criterion's "with the sampler ON" form)
    from infinistore_tpu.health import HealthSampler

    adm = None
    sampler = HealthSampler(probes={
        "client.write_count": lambda: (m.default_registry().family_hist(
            "istpu_client_op_seconds") or (0, 0))[0],
        "engine.steps": lambda: prof.steps,
        "admission.mode": lambda: (adm.mode_code()
                                   if adm is not None else None),
    })
    # ...and the ADMISSION CONTROLLER: one live submit-time verdict per
    # measured op (its real cadence — per request, not per byte), quota
    # ledger charging, watchdog read and all, INSIDE the timed window —
    # the acceptance criterion's "with the controller live" form
    from infinistore_tpu.admission import AdmissionController

    adm = AdmissionController(sampler=sampler, metrics=m.default_registry(),
                              quotas={"0": (1e9, 2.0)}, enabled=True)
    sampler.start()
    # ...and the USAGE METER: with an account bound, every measured
    # frame carries the wire account blob and the store bills per-entry
    # occupancy/sharer bookkeeping INSIDE the timed window — the
    # acceptance criterion's "with the UsageMeter live" form
    from infinistore_tpu.usage import bind_account

    assert getattr(conn.conn, "account_ctx", False), (
        "accounting capability must be negotiated so the measured frames "
        "really carry the account blob"
    )
    # ...and the SESSION LEDGER: one recorded turn per measured op pair
    # (its real cadence — the scheduler records once per finished
    # request), counters + band histogram + waste derivation live
    # INSIDE the timed window — the acceptance criterion's "with the
    # SessionLedger live" form
    from infinistore_tpu.sessions import SessionLedger

    sled = SessionLedger(capacity=64, block_tokens=16,
                         metrics=m.MetricsRegistry())

    class _SessSt:
        local_chunks = 1
        store_chunks = 2

    class _SessReq:
        priority = 0
        tenant = "perf-tenant"
        trace_id = "perf"
        state = _SessSt()

    best_put = best_get = float("inf")
    try:
        for it in range(4):
            blocks = [(f"ovh-{it}-{i}", i * blk) for i in range(n)]
            with tracer.trace("perf.request", iteration=it), \
                    bind_account("perf-tenant"):
                with prof.step(kind_hint="perf"):
                    t0 = time.perf_counter()
                    assert adm.check_submit(lane=0, tokens=blk).admitted
                    conn.write_cache(blocks, blk, buf.ctypes.data)
                    best_put = min(best_put, time.perf_counter() - t0)
                    t0 = time.perf_counter()
                    assert adm.check_submit(lane=0, tokens=blk).admitted
                    conn.read_cache(blocks, blk, dst.ctypes.data)
                    best_get = min(best_get, time.perf_counter() - t0)
                    req = _SessReq()
                    req.session = "perf-session"
                    req.req_id = it
                    req.tokens = list(range(64 * (it + 1)))
                    req.t_submit, req.t_first = t0, t0 + 0.001
                    sled.record_turn(req, "completed")
            conn.delete_keys([k for k, _ in blocks])
    finally:
        sampler.stop()
    conn.close()
    assert np.array_equal(buf, dst)
    assert prof.summary()["steps"] == 4
    # the controller really was live: every verdict recorded and charged
    assert adm.snapshot()["decisions"]["admit"]["0"] == 8
    assert adm.quota.available("0") is not None
    # the session ledger really was live: four turns folded into the
    # session, waste derivation and the TTFT band histogram exercised
    sess_snap = sled.snapshot()
    assert sess_snap["totals"]["turns"] == 4, sess_snap["totals"]
    assert sess_snap["sessions"][0]["turns"] == 4

    # instrumentation proof: the trace recorded the op and stage spans...
    last = tracer.recent()[-1]
    names = {ev[0] for ev in last.events}
    assert {"perf.request", "write_cache", "write_cache.copy"} <= names, names
    # ...and the client histogram family saw the same ops
    text = m.default_registry().to_prometheus_text()
    assert 'istpu_client_op_seconds_count{op="write_cache"}' in text

    # CI artifact hooks: dump the run's Perfetto trace and the step
    # profiler's JSON summary when asked, so the workflow uploads the
    # real stage timeline AND the attribution block next to the numbers
    out_path = os.environ.get("ISTPU_PERF_TRACE_OUT")
    if out_path:
        with open(out_path, "w") as f:
            f.write(tracer.export_chrome_json())
    prof_path = os.environ.get("ISTPU_PERF_STEPPROF_OUT")
    if prof_path:
        import json

        summary = prof.summary()
        # host load at capture time (docs/robustness.md §host-load):
        # a flaked perf guard on the 1-vCPU runner is triaged from this
        # one artifact read instead of re-running under a profiler
        summary["loadavg"] = list(os.getloadavg())
        summary["health_ticks"] = sampler.ticks
        with open(prof_path, "w") as f:
            json.dump(summary, f, indent=2)

    floor = PUT_FLOOR_GBPS * 0.95
    put_gbps = nbytes / 1e9 / best_put
    get_gbps = nbytes / 1e9 / best_get
    assert put_gbps >= floor, (
        f"instrumented shm put {put_gbps:.2f} GB/s fell below 95% of the "
        f"{PUT_FLOOR_GBPS} GB/s floor — observability overhead regression "
        f"(get measured {get_gbps:.2f})"
    )


def test_shm_push_performs_zero_intermediate_host_copies(server,
                                                         monkeypatch):
    """STRUCTURAL: the alloc-first shm push must hand its fill the
    MAPPED POOL itself — ``zero_copy_bands`` counts every band that did,
    ``staged_bands`` every band that went through a scratch copy.  A
    regression that silently reintroduces client-side staging (losing
    the tentpole's one-copy property) flips these counters long before
    it shows up as bandwidth."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    blk = 64 << 10
    n = 64
    payload = np.random.randint(0, 256, n * blk, dtype=np.uint8)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=server,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    assert conn.conn.alloc_first, "alloc-first did not negotiate"
    # four bands, like a real banded push
    per = n // 4
    bands = []
    for b in range(4):
        blocks = [(f"zcg-{b}-{i}", i * blk) for i in range(per)]
        view = payload[b * per * blk : (b + 1) * per * blk]
        bands.append((blocks, blk,
                      lambda dst, _v=view: np.copyto(dst, _v)))
    info = conn.write_cache_into(bands)
    assert info["zero_copy_bands"] == 4 and info["staged_bands"] == 0, info
    # and the bytes are byte-identical on the way back
    dst = np.zeros(per * blk, dtype=np.uint8)
    for b in range(4):
        blocks = [(f"zcg-{b}-{i}", i * blk) for i in range(per)]
        conn.read_cache(blocks, blk, dst.ctypes.data)
        assert np.array_equal(dst,
                              payload[b * per * blk : (b + 1) * per * blk])
    conn.close()


def test_fused_spec_chunk_single_sync_structural():
    """STRUCTURAL: one fused-speculation chunk at full acceptance must
    cost exactly ONE compiled dispatch, ONE blocking host sync, and
    ZERO host-side reconcile dispatches (verify/draft) — the
    single-sync contract of the device-resident reconcile
    (engine/speculative.py).  A regression that reintroduces the
    host-side trim (a ``_resync_draft`` or tail-refresh ``verify``
    after the fused program) flips these counters long before it shows
    up as tokens/s."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.engine.speculative import SpeculativeDecoder
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.kv import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params, scaled

    cfg = scaled(TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(7))

    def eng():
        pc = PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, n_blocks=64, block_tokens=4,
            dtype=cfg.dtype,
        )
        return InferenceEngine(params, cfg, pc)

    # self-draft: acceptance 1, so the adaptive controller's first
    # dispatch covers the whole chunk — the single-sync fast path
    spec = SpeculativeDecoder(eng(), eng(), k=3)
    prompt = [11, 42, 7, 99, 5, 3, 17, 28, 64, 1, 2]
    st_t, st_d = spec.prefill(prompt)
    spec.decode(st_t, st_d, 24)  # warm: compile outside the guard
    st_t2, st_d2 = spec.prefill(prompt + [29, 31])
    prof = StepProfiler(sample=1)
    with prof.step(kind_hint="spec") as rec:
        out = spec.decode(st_t2, st_d2, 24)
    assert len(out) == 24
    assert rec["dispatches"] == {"spec_round": 1}, (
        f"one fused chunk must be ONE dispatch with zero reconcile "
        f"(verify/draft) dispatches — got {rec['dispatches']}"
    )
    assert rec["syncs"] == {"spec_tokens": 1}, (
        f"one fused chunk must block on the host exactly once — got "
        f"{rec['syncs']}"
    )


def test_store_attached_prefill_within_budget(server, monkeypatch):
    """The commit-after-respond contract, measured: with relaxed
    durability the prefill critical path carries only the cheap half of
    each push (gather dispatch + async D2H kick + queue put), so a
    store-ATTACHED prefill must stay within ``ATTACHED_PREFILL_BUDGET``
    of detached.  This is the CPU-host form of the acceptance target;
    the on-chip ratio is asserted from the next live bench capture."""
    import jax

    from infinistore_tpu.engine.engine import InferenceEngine
    from infinistore_tpu.engine.stepprof import StepProfiler
    from infinistore_tpu.kv.cache import PagedCacheConfig
    from infinistore_tpu.models import TINY, init_params

    monkeypatch.setenv("ISTPU_CLIENT", "python")
    # profiler ON at DEFAULT sampling for both sides of the ratio: the
    # attached/detached budget is measured with the engine-path hooks
    # (prefill dispatch notes, sampled stall probe) live — the
    # acceptance criterion's "with the StepProfiler ON" form
    prof = StepProfiler()
    cfg = TINY
    params = init_params(cfg, jax.random.PRNGKey(0))
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, block_tokens=16, n_blocks=128,
    )
    S, C = 256, 64  # 4 chunks: 3 stream while later chunks compute
    rng = np.random.RandomState(3)

    def med7(conn, tag):
        # median-of-7 (was 5, was 3): the docs/robustness.md §host-load
        # flake — occasional runs landing ~1 ms over budget under 1-vCPU
        # scheduler jitter — is sample noise, and the documented remedy
        # is MORE samples, never a looser budget (the reshape twin below
        # already runs at 7)
        eng = InferenceEngine(
            params, cfg, pc, conn=conn, model_id=f"psmoke-{tag}",
            prefill_chunk=C, store_durability="relaxed",
        )
        prompt = [int(x) for x in rng.randint(1, cfg.vocab_size, size=S)]
        st = eng.prefill(prompt)  # compile warmup
        np.asarray(st.last_logits)
        eng.store_flush()
        eng.release(st)
        times = []
        for _ in range(7):
            p = [int(x) for x in rng.randint(1, cfg.vocab_size, size=S)]
            t0 = time.perf_counter()
            with prof.step(kind_hint=None):
                st = eng.prefill(p)
                np.asarray(st.last_logits)  # ground-truth completion
            times.append(time.perf_counter() - t0)
            eng.store_flush()
            eng.release(st)
        times.sort()
        return times[3]

    t_detached = med7(None, "detached")
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=server,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    try:
        t_attached = med7(conn, "attached")
    finally:
        conn.close()
    # +10 ms absolute slack: TINY prefills are tens of ms on this host,
    # and scheduler jitter on a 1-vCPU runner must not flake the ratio
    budget = t_detached * ATTACHED_PREFILL_BUDGET + 0.010
    assert t_attached <= budget, (
        f"store-attached prefill {t_attached * 1e3:.1f} ms exceeded "
        f"{ATTACHED_PREFILL_BUDGET}x the detached {t_detached * 1e3:.1f} ms "
        f"(+10 ms slack) — the push critical path grew "
        f"(loadavg at failure: {os.getloadavg()})"
    )


# ---------------------------------------------------------------------------
# reshape interference guards: the floors above must hold WHILE the
# fleet reshapes — a live node-to-node migration AND a paced slab
# compaction grinding in the background.  Same budgets, never loosened
# (docs/robustness.md §host-load: the remedy for jitter is more
# samples); what changes is only the load around the measurement.
# ---------------------------------------------------------------------------

RESHAPE_BLK = 16 << 10
RESHAPE_SEED_KEYS = 1200  # ~19 MB of 16 KB entries on the source node


def _manage(mport, method, path, body=None):
    import http.client
    import json as _json

    conn = http.client.HTTPConnection("127.0.0.1", mport, timeout=30)
    conn.request(method, path,
                 _json.dumps(body) if body is not None else None,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, _json.loads(data)


def _compaction_stats(mport):
    status, rep = _manage(mport, "GET", "/debug/cache")
    assert status == 200, rep
    return rep["disk"]["compaction"]


def _boot_store(port, mport, extra=(), env=None):
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python", *extra],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )
    deadline = time.time() + 25
    for p in (port, mport):
        while True:
            if proc.poll() is not None:
                pytest.fail("reshape store node failed to start")
            try:
                socket.create_connection(("127.0.0.1", p),
                                         timeout=0.5).close()
                break
            except OSError:
                if time.time() >= deadline:
                    proc.kill()
                    pytest.fail(f"reshape store port {p} did not come up")
                time.sleep(0.1)
    return proc


@pytest.fixture(scope="class")
def reshape_fleet(tmp_path_factory):
    """Two store nodes mid-reshape: node A carries a spill tier whose
    biggest slab has been churned to ~20% fill, with the background
    compactor paced SLOW (64 KB/s) so its slide spans every measurement
    window below; node B is the plain receiver migrations move ranges
    to.  The guards point their traffic at A — the node paying for both
    halves of the reshape at once."""
    a_port, a_mport = _free_port(), _free_port()
    b_port, b_mport = _free_port(), _free_port()
    tier_dir = str(tmp_path_factory.mktemp("reshape_disk_tier"))
    procs = [
        _boot_store(a_port, a_mport,
                    extra=("--disk-tier-path", tier_dir,
                           "--disk-tier-size", "1"),
                    env={"ISTPU_COMPACT_RATE": "65536"}),
        _boot_store(b_port, b_mport),
    ]
    # seed A, spill everything to disk, then delete 80% — the low-fill
    # slab the paced compactor grinds on for the whole class
    buf = np.random.randint(0, 256, RESHAPE_SEED_KEYS * RESHAPE_BLK,
                            dtype=np.uint8)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=a_port,
        connection_type=ist.TYPE_SHM, log_level="warning"))
    conn.connect()
    conn.register_mr(buf)
    blocks = [(f"seed:{i}#L0", i * RESHAPE_BLK)
              for i in range(RESHAPE_SEED_KEYS)]
    conn.write_cache(blocks, RESHAPE_BLK, buf.ctypes.data)
    status, rep = _manage(a_mport, "POST", "/spill")
    assert status == 200 and rep["demoted"] >= RESHAPE_SEED_KEYS, rep
    conn.delete_keys([k for i, (k, _) in enumerate(blocks) if i % 5])
    conn.close()
    # don't yield until the paced compactor has PICKED UP the slide —
    # the guards assert against a live pass, not a pending one
    deadline = time.time() + 20
    while True:
        comp = _compaction_stats(a_mport)
        if comp["active_cls"] is not None and comp["moved_bytes"] > 0:
            break
        assert time.time() < deadline, (
            f"compactor never started on the churned slab: {comp}")
        time.sleep(0.25)
    yield {"a": f"127.0.0.1:{a_port}", "b": f"127.0.0.1:{b_port}",
           "a_port": a_port, "a_mport": a_mport, "b_port": b_port}
    for proc in procs:
        if proc.poll() is None:
            proc.terminate()
    for proc in procs:
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


class TestReshapeInterference:
    """PR-1/PR-9 floors re-asserted with the reshape plane LIVE."""

    @staticmethod
    def _stretched_pool(fleet, monkeypatch, keys=0):
        """A pool over node A with migration pacing stretched (small
        batched runs, long breaths) so a join/drain of B stays running
        across a whole med5 window; optionally seed fresh copy traffic
        so every re-armed window moves real bytes."""
        from infinistore_tpu import cluster as cl

        monkeypatch.setattr(cl, "MIGRATE_BATCH", 16)
        monkeypatch.setattr(cl, "MIGRATE_SLEEP_S", 0.25)
        # replicas=1: the floors compare single-copy routing against
        # single-copy routing (replication doubling every push is the
        # replica feature's own cost, not reshape interference)
        pool = cl.RoutedStorePool([fleet["a"]], op_timeout_s=10.0,
                                  replicas=1)
        if keys:
            data = np.random.randint(0, 256, keys * RESHAPE_BLK,
                                     dtype=np.uint8)
            conn = ist.InfinityConnection(ist.ClientConfig(
                host_addr="127.0.0.1", service_port=fleet["a_port"],
                connection_type=ist.TYPE_SHM, log_level="warning"))
            conn.connect()
            conn.register_mr(data)
            tag = int(time.time() * 1e3)
            conn.write_cache(
                [(f"mig:{tag}:{i}#L0", i * RESHAPE_BLK)
                 for i in range(keys)],
                RESHAPE_BLK, data.ctypes.data)
            conn.close()
        return pool

    @staticmethod
    def _ensure_reshaping(pool, ep_b):
        """Keep the fleet mid-reshape: (re)arm a join of B, or — once B
        is a member — the drain back out.  Every toggle is a full
        background migration, so callers sampling inside the window
        always measure against live copy traffic."""
        if not pool.migration_idle():
            return
        if ep_b in pool.endpoints:
            pool.drain_node(ep_b)
        else:
            pool.join_node(ep_b)
        assert not pool.migration_idle()

    @staticmethod
    def _settle(pool, timeout=120):
        deadline = time.time() + timeout
        while not pool.migration_idle():
            assert time.time() < deadline, "reshape never settled"
            time.sleep(0.1)

    def test_put_floor_holds_while_fleet_reshapes(self, reshape_fleet,
                                                  monkeypatch, timed_walk):
        """The 2.4 GB/s shm put floor, median-of-5, with a batched
        migration streaming ranges OFF the measured node and the paced
        compactor sliding its spill slab at the same time.  Structural
        asserts pin both interference sources live across the window —
        a guard that silently measured a quiet fleet would pass for the
        wrong reason."""
        monkeypatch.setenv("ISTPU_CLIENT", "python")
        fleet = reshape_fleet
        pool = self._stretched_pool(fleet, monkeypatch, keys=300)
        blk = 64 << 10
        nbytes = 64 << 20
        buf = np.random.randint(0, 256, nbytes, dtype=np.uint8)
        conn = ist.InfinityConnection(ist.ClientConfig(
            host_addr="127.0.0.1", service_port=fleet["a_port"],
            connection_type=ist.TYPE_SHM, log_level="warning"))
        conn.connect()
        conn.register_mr(buf)
        n = nbytes // blk
        try:
            comp0 = _compaction_stats(fleet["a_mport"])
            # the paced compactor is MID-SLIDE: a pass is active and far
            # from done (64 KB/s against a ~3 MB tail spans every
            # window this class opens)
            assert comp0["active_cls"] is not None, comp0
            # up to twelve med5 windows (0.15 s each on an idle host), each
            # wholly inside the reshape; the floor is read off the best
            # window's median.  Host load (five other test workers here)
            # only ever subtracts from a put, in bursts as long as a
            # window: with four windows a whole run at load 14 read 2.29
            # GB/s (samples 21.8-42.2 ms, two of five under the 28 ms the
            # floor allows).  The floor stays what it is and the sample
            # count is raised (house rule)
            windows = []
            while len(windows) < 12 and not (
                    windows and nbytes / 1e9 / sorted(windows[-1])[2]
                    >= PUT_FLOOR_GBPS):
                samples = []
                for it in range(5):
                    # re-arm instead of flake: the window must be OPEN for
                    # every sample (join toggles into drain and back)
                    self._ensure_reshaping(pool, fleet["b"])
                    assert pool.migration_report()["state"] == "running"
                    blocks = [(f"rif-{len(windows)}-{it}-{i}", i * blk)
                              for i in range(n)]
                    t0 = time.perf_counter()
                    conn.write_cache(blocks, blk, buf.ctypes.data)
                    samples.append(time.perf_counter() - t0)
                    conn.delete_keys([k for k, _ in blocks])
                assert pool.migration_report()["state"] == "running", (
                    "the last sample must close inside the reshape window")
                windows.append(samples)
            samples = min(windows, key=lambda w: sorted(w)[2])
            comp1 = _compaction_stats(fleet["a_mport"])
            assert comp1["active_cls"] is not None, (
                f"the compaction pass finished before the window closed "
                f"— pace it slower: {comp0} -> {comp1}")
        finally:
            conn.close()
        # ...and it really is sliding, not wedged: the worker shares the
        # node's single-threaded loop, so its next tick may land just
        # AFTER the saturated window — poll briefly for the delta
        deadline = time.time() + 20
        progress = 0
        while progress <= 0 and time.time() < deadline:
            cur = _compaction_stats(fleet["a_mport"])
            progress = (cur["moved_bytes"] + cur["bytes"]) - \
                (comp0["moved_bytes"] + comp0["bytes"])
            if progress <= 0:
                time.sleep(0.25)
        assert progress > 0, (
            f"the compactor never advanced: {comp0} -> {cur}")
        med = sorted(samples)[2]
        put_gbps = nbytes / 1e9 / med
        out = os.environ.get("ISTPU_RESHAPE_STEPPROF_OUT")
        if out:
            import json

            with open(out, "w") as f:
                json.dump({
                    "samples_s": samples,
                    "windows": len(windows),
                    "put_gbps_med5": round(put_gbps, 3),
                    "floor_gbps": PUT_FLOOR_GBPS,
                    "migration": pool.migration_report(),
                    "compaction_progress_bytes": progress,
                    "loadavg": list(os.getloadavg()),
                }, f, indent=2)
        assert put_gbps >= PUT_FLOOR_GBPS, (
            f"shm put {put_gbps:.2f} GB/s fell under the "
            f"{PUT_FLOOR_GBPS} GB/s floor WITH the fleet reshaping "
            f"(samples {[f'{s * 1e3:.1f}ms' for s in sorted(samples)]}, "
            f"compaction moved {progress} B, loadavg {os.getloadavg()})"
        )
        self._settle(pool)
        pool.close()

    def test_attached_prefill_budget_holds_while_fleet_reshapes(
            self, reshape_fleet, monkeypatch):
        """The 1.2x store-attached prefill budget with the engine
        attached to the SAME node a live migration is streaming ranges
        off and the compactor is sliding underneath — the exact PR-9
        guard shape (direct attach, same budget, same +10 ms slack).
        BOTH sides of the ratio are sampled INSIDE live reshape windows,
        interleaved window by window, so ambient reshape CPU steal on
        the 1-vCPU runner lands on detached and attached alike and the
        budget isolates what it always isolated: the cost of the
        attach, now under reshape.  Median-of-7 matched pairs — more
        samples, never a looser budget (docs/robustness.md
        §host-load)."""
        import jax

        from infinistore_tpu.engine.engine import InferenceEngine
        from infinistore_tpu.kv.cache import PagedCacheConfig
        from infinistore_tpu.models import TINY, init_params

        monkeypatch.setenv("ISTPU_CLIENT", "python")
        fleet = reshape_fleet
        cfg = TINY
        params = init_params(cfg, jax.random.PRNGKey(0))
        pc = PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, block_tokens=16, n_blocks=128,
        )
        S, C = 256, 64
        rng = np.random.RandomState(3)
        conn = ist.InfinityConnection(ist.ClientConfig(
            host_addr="127.0.0.1", service_port=fleet["a_port"],
            connection_type=ist.TYPE_SHM, log_level="warning"))
        conn.connect()

        def make_eng(c, tag):
            eng = InferenceEngine(
                params, cfg, pc, conn=c, model_id=f"rsmoke-{tag}",
                prefill_chunk=C, store_durability="relaxed",
            )
            prompt = [int(x) for x in rng.randint(1, cfg.vocab_size,
                                                  size=S)]
            st = eng.prefill(prompt)  # compile warmup, outside windows
            np.asarray(st.last_logits)
            eng.store_flush()
            eng.release(st)
            return eng

        def sample(eng):
            p = [int(x) for x in rng.randint(1, cfg.vocab_size, size=S)]
            t0 = time.perf_counter()
            st = eng.prefill(p)
            np.asarray(st.last_logits)
            dt = time.perf_counter() - t0
            eng.store_flush()
            eng.release(st)
            return dt

        e_det = make_eng(None, "detached")
        e_att = make_eng(conn, "attached")
        pool = self._stretched_pool(fleet, monkeypatch, keys=300)

        def arm():
            self._ensure_reshaping(pool, fleet["b"])
            assert pool.migration_report()["state"] == "running"

        det, att = [], []
        try:
            for _ in range(7):
                arm()
                det.append(sample(e_det))
                arm()
                att.append(sample(e_att))
        finally:
            conn.close()
            self._settle(pool)
            pool.close()
        det.sort()
        att.sort()
        t_detached, t_attached = det[3], att[3]
        budget = t_detached * ATTACHED_PREFILL_BUDGET + 0.010
        assert t_attached <= budget, (
            f"store-attached prefill {t_attached * 1e3:.1f} ms exceeded "
            f"{ATTACHED_PREFILL_BUDGET}x the detached "
            f"{t_detached * 1e3:.1f} ms (+10 ms slack), both medians "
            f"sampled inside live reshape windows (det "
            f"{[f'{t * 1e3:.1f}' for t in det]}, att "
            f"{[f'{t * 1e3:.1f}' for t in att]}, loadavg "
            f"{os.getloadavg()})"
        )
