"""Pure-Python asyncio data-plane server.

Portable fallback for the C++ native runtime (``src/store_server.cpp``);
speaks the same wire protocol (``protocol.py``).  Mirrors the reference's
single-threaded event-loop server (reference: src/infinistore.cpp:887-1029 --
libuv READ_HEADER/READ_BODY state machine); asyncio's ``readexactly`` plays
the role of the state machine, and inline payloads are streamed directly
into pool memory just as the reference streams TCP values into the slab
(src/infinistore.cpp:942-960).
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
from typing import List, Optional, Sequence

from . import protocol as P
from .store import Store
from .utils import tracing
from .utils.logging import Logger
from .utils.metrics import AGE_BUCKETS, MetricsRegistry, stats_to_prometheus

MAX_INLINE_BODY = 1 << 30

# a stalled connection un-stalls when its rule is cleared; this cap is the
# backstop so a forgotten rule can never wedge a CI run past its timeout
_STALL_CAP_S = 120.0

_FAULT_ACTIONS = ("drop_conn", "delay", "error", "stall", "corrupt",
                  "disk_error", "disk_slow")
# disk actions target the SPILL TIER's I/O, not a wire op: they match
# under the pseudo-op name "DISK" (or "*") and are evaluated by the
# DiskTier fault hook, never by the per-frame dispatch path
_DISK_ACTIONS = ("disk_error", "disk_slow")


def _fault_keys(op: int, body: memoryview):
    """Keys named by a request frame, for targeted fault actions
    (``corrupt`` flips bytes in exactly the entries the caller is talking
    about, which is what makes corruption chaos tests deterministic)."""
    try:
        if op in (P.OP_ALLOC_PUT, P.OP_GET_DESC, P.OP_PUT_INLINE_BATCH,
                  P.OP_GET_INLINE_BATCH):
            keys, _bs = P.unpack_alloc_put(body)
            return keys
        if op in (P.OP_EXIST, P.OP_MATCH_LAST_IDX, P.OP_DELETE_KEYS,
                  P.OP_COMMIT_PUT, P.OP_GET_INLINE, P.OP_RELEASE_DESC):
            keys, _ = P.unpack_keys(body)
            return keys
        if op == P.OP_PUT_INLINE:
            key, _vlen, _n = P.unpack_put_inline_head(body)
            return [key]
    except (ValueError, IndexError):
        pass
    return []


class FaultInjector:
    """Deterministic fault injection for the store data plane.

    Every failure mode the resilience layer claims to survive must be
    reproducible on demand: rules armed here make the server kill a
    connection mid-op (``drop_conn``), answer late (``delay``), answer a
    chosen error status (``error``), or simply never answer (``stall`` —
    the hang that no socket error surfaces, which is what the client's
    per-op deadline exists for).  Armed via the manage plane's ``POST
    /faults`` or the ``ISTPU_FAULTS`` env (JSON list of rules).

    ``corrupt`` is the integrity plane's fault: it XOR-flips one byte in
    the COMMITTED pool region of every key the matched request names
    (the entry's stamped checksum is untouched, so verification — client
    read-side or the background scrubber — must catch it).

    A rule: ``{"op": "GET_DESC" | "*", "action": one of drop_conn/delay/
    error/stall/corrupt, "delay_s": float, "error_status": int, "times":
    int (-1 = until cleared), "after": int (skip the first N matching
    ops)}``.
    Rules are evaluated first-match in arm order.  Thread-safe: the manage
    plane arms/clears from HTTP threads while the asyncio loop matches;
    stalled connections poll rule liveness, so ``clear()`` releases them.
    """

    # named scenarios: canned rule sets for the failure walks the docs
    # promise (docs/robustness.md §4), armed by name via POST /faults
    # {"scenario": ...} so a chaos driver or an operator drill never
    # re-derives the op list.  ``migration_receiver_slow`` is the
    # reshape plane's slow_op rule: it delays every op a batched
    # migration lands on the RECEIVING store (the alloc reservation,
    # the atomic inline frame, the shm commit), stretching the copy
    # window the receiver-death chaos walk kills into.
    # ``compaction_disk_fault`` fails spill-tier I/O under a running
    # compaction until the tier degrades DRAM-only.
    # ``decode_death_mid_stream`` is the SERVE-plane resumption walk's
    # trigger: the pseudo-op "STREAM" is matched by the SSE streamer at
    # every chunk boundary (serve.py _stream), so drop_conn with
    # ``after`` kills the stream only AFTER tokens already reached the
    # client — the exact window the pre-first-byte failover cannot
    # cover and store-checkpointed resumption must.
    # ``router_death`` is armed on a FRONTDOOR's injector: every client
    # connection is dropped at accept, which is what a dead router
    # looks like to a client holding a replica list (the failover the
    # replicated-router walk exercises).
    SCENARIOS = {
        "migration_receiver_slow": [
            {"op": "ALLOC_PUT", "action": "delay", "delay_s": 0.25},
            {"op": "PUT_INLINE_BATCH", "action": "delay", "delay_s": 0.25},
            {"op": "COMMIT_PUT", "action": "delay", "delay_s": 0.25},
        ],
        "compaction_disk_fault": [
            {"op": "DISK", "action": "disk_error", "times": 8},
        ],
        "decode_death_mid_stream": [
            {"op": "STREAM", "action": "drop_conn", "after": 2,
             "times": 1},
        ],
        "router_death": [
            {"op": "*", "action": "drop_conn", "times": -1},
        ],
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._rules: List[dict] = []
        self._next_id = 1

    def arm_scenario(self, name: str) -> int:
        """Arm a named canned rule set (replaces the active rules, like
        ``arm``)."""
        rules = self.SCENARIOS.get(name)
        if rules is None:
            raise ValueError(
                f"unknown fault scenario {name!r}; have "
                f"{sorted(self.SCENARIOS)}"
            )
        return self.arm([dict(r) for r in rules])

    def arm(self, rules) -> int:
        """Replace the active rule set; returns how many rules are armed.
        An empty list clears (and releases any stalled connections)."""
        norm = []
        for r in rules or []:
            if not isinstance(r, dict):
                raise ValueError(f"fault rule must be an object: {r!r}")
            action = r.get("action")
            if action not in _FAULT_ACTIONS:
                raise ValueError(
                    f"fault action must be one of {_FAULT_ACTIONS}, "
                    f"got {action!r}"
                )
            norm.append({
                "id": 0,  # assigned under the lock below
                "op": str(r.get("op", "*")).upper(),
                "action": action,
                "delay_s": float(r.get("delay_s", 0.1)),
                "error_status": int(r.get("error_status", P.SYSTEM_ERROR)),
                "times": int(r.get("times", -1)),
                "after": int(r.get("after", 0)),
            })
        with self._lock:
            for r in norm:
                r["id"] = self._next_id
                self._next_id += 1
            self._rules = norm
            return len(norm)

    def clear(self) -> None:
        self.arm([])

    def snapshot(self) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._rules]

    @property
    def armed(self) -> bool:
        with self._lock:
            return bool(self._rules)

    def match(self, op_name: str,
              actions: Optional[Sequence[str]] = None) -> Optional[dict]:
        """First active rule matching ``op_name``; consumes one ``after``
        skip or one ``times`` charge.  Returns a copy (the caller acts on
        it outside the lock).  ``actions`` selects WHICH action families
        this call site evaluates: the wire dispatch path passes None
        (everything except the disk actions), the DiskTier fault hook
        passes ``_DISK_ACTIONS`` — so a ``{"op": "*"}`` disk rule can
        never fire on a wire frame, and vice versa."""
        with self._lock:
            for r in self._rules:
                if r["op"] not in ("*", op_name) or r["times"] == 0:
                    continue
                if actions is None:
                    if r["action"] in _DISK_ACTIONS:
                        continue
                elif r["action"] not in actions:
                    continue
                if r["after"] > 0:
                    r["after"] -= 1
                    return None
                if r["times"] > 0:
                    r["times"] -= 1
                return dict(r)
            return None

    def active(self, rule_id: int) -> bool:
        """Is the rule still armed?  Stalled connections poll this, so a
        ``clear()`` (or re-arm) releases them."""
        with self._lock:
            return any(r["id"] == rule_id and r["times"] != 0
                       for r in self._rules)


def _merge_desc_runs(descs):
    """Merge adjacent descriptors (same pool, contiguous offsets) into
    ``(pool_idx, offset, length)`` runs.  With the store's contiguous-run
    batch allocation a whole inline batch streams through ONE pool view
    instead of one per block (fewer Python-level iterations and larger
    socket writes); order is preserved, so payload layout is unchanged."""
    runs = []
    for pool_idx, offset, size in descs:
        if runs and runs[-1][0] == pool_idx and runs[-1][1] + runs[-1][2] == offset:
            runs[-1][2] += size
        else:
            runs.append([pool_idx, offset, size])
    return runs


class StoreServer:
    def __init__(self, config, store: Optional[Store] = None):
        self.config = config
        self.store = store or Store(config)
        self._server: Optional[asyncio.AbstractServer] = None
        self._evict_task = None
        # per-op latency accumulators: op -> [count, total_s, max_s].
        # Locked: the manage plane reads from HTTP handler threads while
        # the asyncio loop updates (native parity: mu_ in stats_json_full)
        self._op_lat: dict = {}
        self._lat_lock = threading.Lock()
        # the store half of the unified observability plane: per-instance
        # registry (tests run several servers per process) exposed by the
        # manage plane's /metrics.  Gauges are exposition-time callbacks
        # into live store state; the op histogram is fed by the dispatch
        # loop next to the legacy avg/max accumulators.
        self.metrics = MetricsRegistry()
        self._h_op = self.metrics.histogram(
            "istpu_store_op_seconds",
            "Server-side latency per wire op (dispatch to response built)",
            labelnames=("op",),
        )
        st = self.store
        reg = self.metrics
        reg.gauge("istpu_store_pool_usage",
                  "Fraction of pool capacity allocated (occupancy)",
                  fn=st.usage)
        reg.gauge("istpu_store_fragmentation",
                  "1 - largest_free_run/free_blocks: how shattered the "
                  "free space is (0 = one contiguous run)",
                  fn=lambda: st.mm.frag_stats()["fragmentation"])
        reg.gauge("istpu_store_active_read_leases",
                  "Committed entries under a live GET_DESC read lease",
                  fn=st.active_leases)
        reg.gauge("istpu_store_kvmap_len", "Committed entries",
                  fn=st.kvmap_len)
        reg.gauge("istpu_store_pending_puts",
                  "Allocated-but-uncommitted put regions",
                  fn=lambda: len(st.pending))
        reg.counter("istpu_store_evicted_total", "Entries evicted by LRU",
                    fn=lambda: st.stats.evicted)
        # who paid for them: the on-demand drain's slices run between
        # requests, an allocation's own path is a request held up (what is
        # in neither is an evict() pass: the manage plane's, the periodic)
        reg.counter("istpu_store_evicted_drain_total",
                    "Entries evicted by the on-demand drain's slices, "
                    "between requests",
                    fn=lambda: st.stats.evicted_drain)
        reg.counter("istpu_store_evicted_inline_total",
                    "Entries evicted on an allocation's own path: what it "
                    "lacked while the drain was behind, or class pressure",
                    fn=lambda: st.stats.evicted_inline)
        reg.counter("istpu_store_drain_slices_total",
                    "Slices of the on-demand drain run by the server's task",
                    fn=lambda: st.stats.drain_slices)
        st.evict_stall_sink = reg.histogram(
            "istpu_store_evict_stall_seconds",
            "Seconds an allocation spent evicting on its own path (one "
            "sample per allocation that had to)").observe
        reg.counter("istpu_store_contig_batches_total",
                    "Batch allocs served as one contiguous run",
                    fn=lambda: st.stats.contig_batches)
        reg.counter("istpu_store_reservations_reaped_total",
                    "Allocated-but-uncommitted reservations freed past the "
                    "TTL (an alloc-first writer died without disconnecting)",
                    fn=lambda: st.stats.reservations_reaped)
        # resilience plane: the periodic-evict loop counts its failures
        # here instead of dying silently, and the fault injector counts
        # every injected fault so chaos tests can assert determinism
        self._c_evict_err = reg.counter(
            "istpu_store_evict_errors_total",
            "Periodic-evict iterations that raised (loop keeps running)")
        self._c_faults = reg.counter(
            "istpu_store_faults_injected_total",
            "Faults injected into the data plane, by op and action",
            labelnames=("op", "action"))
        # server half of cross-process trace propagation: per-instance
        # ring of completed op traces (one per FLAG_TRACE_CTX frame),
        # recorded under the CALLER's trace id and exported raw over
        # OP_TRACE_DUMP for the client-side stitcher.  ISTPU_TRACE_CTX=0
        # opts the server out: HELLO stops advertising the capability, so
        # well-behaved clients never set the flag.
        self.tracer = tracing.Tracer()
        self.trace_ctx_enabled = os.environ.get("ISTPU_TRACE_CTX", "1") != "0"
        # usage-attribution capability (HELLO_FLAG_ACCOUNT): clients may
        # tag data-plane frames with an account label and the store's
        # UsageMeter bills occupancy/reads per account.  ISTPU_ACCOUNT=0
        # opts the server out: HELLO stops answering the capability, so
        # well-behaved clients never set FLAG_ACCOUNT.
        self.account_enabled = os.environ.get("ISTPU_ACCOUNT", "1") != "0"
        # cache-efficiency analytics: the store attributes every hit/miss/
        # evict (reuse distance, eviction age, dead-on-arrival); the
        # histograms live on this registry, wired in as plain observe sinks
        reg = self.metrics
        self._h_reuse = reg.histogram(
            "istpu_cache_reuse_distance_seconds",
            "Seconds between consecutive reads of the same committed key "
            "(first read measures commit -> read)",
            buckets=AGE_BUCKETS)
        self._h_evict_age = reg.histogram(
            "istpu_cache_evicted_age_seconds",
            "Seconds since last access when an entry was LRU-evicted",
            buckets=AGE_BUCKETS)
        reg.counter(
            "istpu_cache_dead_on_arrival_total",
            "Entries evicted without ever being read (wasted store writes)",
            fn=lambda: st.analytics.dead_on_arrival)
        st.analytics.reuse_sink = self._h_reuse.observe
        st.analytics.evict_age_sink = self._h_evict_age.observe
        # integrity plane: stamping backlog + scrubber counters, fed by
        # the integrity worker task (start() launches it; level "off"
        # skips it entirely)
        reg.counter(
            "istpu_store_scrub_pages_total",
            "Committed entries re-verified (or first-stamped) by the "
            "background scrubber",
            fn=lambda: st.stats.scrub_pages)
        reg.counter(
            "istpu_store_scrub_corrupt_total",
            "Corrupt entries found by checksum re-verification and "
            "quarantined (key dropped, blocks deferred-freed)",
            fn=lambda: st.stats.scrub_corrupt)
        # usage-attribution families, synced from the store's UsageMeter
        # at scrape time (the meter is the single source of truth; the
        # registry children mirror it so /metrics carries per-account
        # series without double bookkeeping on the data path)
        self._c_usage_bs = reg.counter(
            "istpu_store_usage_byte_seconds_total",
            "Byte-seconds of store occupancy per account per tier "
            "(shared-prefix bytes split across the sharer set)",
            labelnames=("account", "tier"))
        self._g_usage_res = reg.gauge(
            "istpu_store_usage_resident_bytes",
            "Bytes currently resident per account per tier (split "
            "shares of shared entries)",
            labelnames=("account", "tier"))
        self._c_usage_hits = reg.counter(
            "istpu_store_usage_hits_total",
            "Store reads attributed per account (reader when tagged, "
            "owner otherwise)",
            labelnames=("account",))
        self._c_usage_evict = reg.counter(
            "istpu_store_usage_evictions_total",
            "Entries evicted per owning account",
            labelnames=("account",))
        self._c_usage_doa = reg.counter(
            "istpu_store_usage_doa_total",
            "Entries evicted never-read (dead on arrival) per owning "
            "account — store writes that bought nothing",
            labelnames=("account",))
        self._usage_emitted: dict = {}
        self._integrity_task = None
        self._tier_task = None
        self._drain_task = None
        self.faults = FaultInjector()
        # spill tier, server half: the DiskTier's fault hook rides the
        # injector (actions disk_error / disk_slow under op "DISK"), a
        # corrupt spill page found at promote counts as an integrity
        # failure with its own cause, and the tier's occupancy/flow
        # counters join the registry.  All conditional — a DRAM-only
        # store's /metrics is unchanged.
        if st.disk is not None:
            self._c_spill_integrity = reg.counter(
                "istpu_integrity_failures_total",
                "KV integrity failures detected by this store, by cause "
                "(spill = a corrupt spill page caught by its checksum at "
                "promote; quarantined, served as a miss)",
                labelnames=("cause",))
            st.disk.fault = self._disk_fault
            st.disk.corrupt_sink = (
                lambda _key: self._c_spill_integrity.labels("spill").inc()
            )
            reg.gauge("istpu_store_disk_entries",
                      "Entries resident in the spill tier",
                      fn=lambda: float(len(st.disk.index)))
            reg.gauge("istpu_store_disk_bytes",
                      "Payload bytes resident in the spill tier",
                      fn=lambda: float(st.disk.used_bytes()))
            reg.counter("istpu_store_spills_total",
                        "Entries spilled to disk at eviction (pressure)",
                        fn=lambda: st.stats.spilled)
            reg.counter("istpu_store_demotions_total",
                        "Cold entries demoted to disk by the background "
                        "tier worker (never on the put critical path)",
                        fn=lambda: st.stats.demoted)
            reg.counter("istpu_store_promotions_total",
                        "Spilled entries promoted back to DRAM on access "
                        "(checksum verified)",
                        fn=lambda: st.stats.promoted)
            reg.counter("istpu_store_disk_errors_total",
                        "Spill-tier I/O failures (enough consecutive ones "
                        "degrade the tier to DRAM-only for a cooldown)",
                        fn=lambda: st.disk.io_errors)
            reg.counter("istpu_store_spill_verify_failures_total",
                        "Corrupt spill pages caught by checksum at promote "
                        "and dropped (a counted miss, never served bytes)",
                        fn=lambda: st.disk.verify_failures)
            reg.counter("istpu_store_compaction_slabs_total",
                        "Low-fill spill slabs compacted and truncated by "
                        "the background tier worker",
                        fn=lambda: st.disk.compacted_slabs)
            reg.counter("istpu_store_compaction_bytes_total",
                        "Spill-file bytes released to the filesystem by "
                        "background slab compaction",
                        fn=lambda: st.disk.compacted_bytes)
            # per-slab occupancy: fill fraction per sizeclass spill
            # slab — the signal the compaction pass above acts on.
            # Synced at scrape time next to the usage families.
            self._g_slab_fill = reg.gauge(
                "istpu_store_spill_slab_fill",
                "Used/allocated slot fraction per sizeclass spill slab "
                "(low fill on a grown slab = reclaimable file space)",
                labelnames=("sizeclass",))
        # fleet health plane, store half: the sampler feeds the flight
        # recorder from cheap Store reads every ISTPU_HEALTH_STEP_S and
        # evaluates the store watchdogs (scrub-corrupt spike, failing
        # evict loop, pool pressure, reservation-reap spike); exported
        # at the manage plane's GET /debug/health.  Built here, started
        # by start() (ISTPU_HEALTH=0 kills it).
        from .health import HealthSampler, default_store_rules, store_probes

        self.health_sampler = HealthSampler(
            probes=store_probes(self), rules=default_store_rules(),
            metrics=self.metrics,
        )
        env_faults = os.environ.get("ISTPU_FAULTS")
        if env_faults:
            try:
                self.faults.arm(json.loads(env_faults))
                Logger.warn(
                    f"ISTPU_FAULTS armed {len(self.faults.snapshot())} "
                    f"fault rule(s)"
                )
            except (ValueError, TypeError) as e:
                raise ValueError(f"bad ISTPU_FAULTS: {e}") from e

    def _disk_fault(self, kind: str) -> None:
        """The DiskTier's injectable fault hook: evaluated on every
        spill-tier I/O.  ``disk_error`` raises (the tier counts it and
        degrades to DRAM-only after enough in a row); ``disk_slow``
        sleeps the rule's delay — a dying-not-dead disk."""
        if not self.faults.armed:
            return
        act = self.faults.match("DISK", actions=_DISK_ACTIONS)
        if act is None:
            return
        self._c_faults.labels("DISK", act["action"]).inc()
        Logger.warn(f"fault injected: {act['action']} on DISK {kind}")
        if act["action"] == "disk_slow":
            time.sleep(min(act["delay_s"], 5.0))
            return
        raise OSError(5, f"injected spill-tier fault ({kind})")

    def degraded(self) -> bool:
        """The store manage plane's /healthz degraded signal: armed fault
        rules (the server is deliberately misbehaving) or a failing
        eviction loop both mean operators should not trust this instance
        to behave normally."""
        return self.faults.armed or self._c_evict_err.value > 0

    def stats_dict(self) -> dict:
        """Store stats + the server-side per-op latency section (native
        parity: store_server.cpp stats_json_full)."""
        stats = self.store.stats_dict()
        with self._lat_lock:
            snap = {o: list(rec) for o, rec in self._op_lat.items()}
        stats["op_latency"] = {
            P.op_name(o): {
                "count": c,
                "avg_ms": round(total / c * 1e3, 3) if c else 0.0,
                "max_ms": round(mx * 1e3, 3),
            }
            for o, (c, total, mx) in snap.items()
        }
        return stats

    def _sync_usage_metrics(self) -> None:
        """Mirror the UsageMeter (and spill-slab fill) into the labeled
        registry families.  Called at scrape/report time — counter
        children advance by the delta since the last sync, so the
        exposed series stay monotone while the meter remains the single
        source of truth."""
        m = self.store.usage_meter
        with self.metrics.lock:
            m._accrue()
            for (a, t), v in m.byte_seconds.items():
                key = ("bs", a, t)
                prev = self._usage_emitted.get(key, 0.0)
                if v > prev:
                    self._c_usage_bs.labels(a, t).inc(v - prev)
                    self._usage_emitted[key] = v
            for (a, t), v in m.resident.items():
                self._g_usage_res.labels(a, t).set(round(v, 1))
            for counter, attr in ((self._c_usage_hits, "hits"),
                                  (self._c_usage_evict, "evictions"),
                                  (self._c_usage_doa, "doa")):
                for a, v in getattr(m, attr).items():
                    key = (attr, a)
                    prev = self._usage_emitted.get(key, 0)
                    if v > prev:
                        counter.labels(a).inc(v - prev)
                        self._usage_emitted[key] = v
            if self.store.disk is not None:
                for cls, slab in self.store.disk._slabs.items():
                    fill = (slab.used() / slab.slots) if slab.slots else 0.0
                    self._g_slab_fill.labels(str(cls)).set(round(fill, 4))

    def usage_report(self) -> dict:
        """The manage plane's ``GET /debug/usage`` payload (also syncs
        the metric mirrors, so a scrape right after agrees)."""
        self._sync_usage_metrics()
        return self.store.usage_meter.report()

    def metrics_text(self) -> str:
        """Prometheus exposition for the manage plane's /metrics: the
        registry families (occupancy, fragmentation, leases, eviction,
        contig_batches, per-op latency histograms) plus the flat
        ``stats_dict`` counters under their long-standing
        ``infinistore_tpu_`` names (the /metrics.prom schema, kept so
        existing scrapes keep working)."""
        self._sync_usage_metrics()
        lines = stats_to_prometheus(
            self.store.stats_dict(), "infinistore_tpu_", Store.STATS_GAUGES
        )
        return self.metrics.to_prometheus_text() + "\n".join(lines) + "\n"

    async def start(self, host: str = "0.0.0.0") -> None:
        self._server = await asyncio.start_server(
            self._handle_conn, host, self.config.service_port, reuse_address=True
        )
        self.start_integrity_worker()
        self.start_tier_worker()
        self.start_drain_worker()
        self.health_sampler.start()
        Logger.info(f"pyserver listening on {host}:{self.config.service_port}")

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        async with self._server:
            await self._server.serve_forever()

    def start_periodic_evict(self) -> None:
        async def _loop():
            while True:
                try:
                    self.store.evict(
                        self.config.evict_min_threshold,
                        self.config.evict_max_threshold,
                    )
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — loop must survive
                    # a single bad evict pass (disk-tier IO error, a
                    # transiently inconsistent lease) must not silently
                    # kill eviction for the rest of the process — that
                    # failure mode ends in a full pool and RETRY storms
                    Logger.error(f"periodic evict failed: {e!r}")
                    self._c_evict_err.inc()
                await asyncio.sleep(self.config.evict_interval)

        self._evict_task = asyncio.get_running_loop().create_task(_loop())

    def start_integrity_worker(self) -> None:
        """Launch the background integrity task: eagerly drains the
        commit-time stamping backlog (small byte-bounded slices with a
        yield between, so data-plane ops interleave), then — at level
        ``scrub`` — walks committed, unleased entries at the configured
        rate, re-verifying checksums and quarantining mismatches."""
        if self.store.integrity == "off" or self._integrity_task is not None:
            return

        async def _loop():
            st = self.store
            # ~20 scrub ticks/s; rate is entries (pages) per second
            scrub_batch = max(1, int(st.scrub_rate / 20))
            while True:
                try:
                    if st.stamp_pending():
                        await asyncio.sleep(0)  # yield, keep draining
                        continue
                    if st.integrity == "scrub":
                        st.scrub_step(scrub_batch)
                        await asyncio.sleep(0.05)
                    else:
                        await asyncio.sleep(0.02)
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — worker must survive
                    Logger.error(f"integrity worker failed: {e!r}")
                    await asyncio.sleep(0.5)

        self._integrity_task = asyncio.get_running_loop().create_task(_loop())

    def start_tier_worker(self) -> None:
        """Launch the background spill-tier task: bounded analytics-
        driven demotion passes (cold committed entries move to disk
        while the pool is above the watermark — so pressure eviction
        finds room already made, and demotion NEVER runs on the put
        critical path), paced slab-compaction slides (low-fill spill
        files slide tight and truncate, at most ``ISTPU_COMPACT_RATE``
        bytes per second of wall clock), plus periodic manifest saves,
        so a crash loses at most a couple of seconds of spill index."""
        if self.store.disk is None or self._tier_task is not None:
            return

        async def _loop():
            st = self.store
            while True:
                try:
                    n = st.demote_step()
                    st.compact_step()
                    st.disk.maybe_save(2.0)
                    await asyncio.sleep(0.05 if n else 0.5)
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — worker must survive
                    Logger.error(f"tier worker failed: {e!r}")
                    await asyncio.sleep(1.0)

        self._tier_task = asyncio.get_running_loop().create_task(_loop())

    def start_drain_worker(self) -> None:
        """Launch the on-demand eviction's task: while an allocation has
        marked the store draining (usage reached 0.95), walk the pass down
        to 0.8 one ``drain_step`` slice at a time, so a request that
        arrives mid-drain is answered after at most one slice.

        The millisecond's pause after a slice is what keeps that promise:
        asyncio hands a readable socket to its handler over two turns of
        the loop, and with a bare yield the next slice ran in each (read
        through a real client on a 1 GiB pool of 32 KB pages held full,
        CPU, PR 44: ALLOC_PUT p99 8.4 ms and 24.9 ms at most with the
        yield, 4.1 and 12.0 with the pause, 40.6 and 68.0 with the one
        pass).  Idle, the task looks every 10 ms: the pool has 5% of
        headroom when the mark is set, and an allocation that outruns the
        drain evicts what it lacks itself."""
        if self._drain_task is not None:
            return

        async def _loop():
            st = self.store
            while True:
                try:
                    if st.draining:
                        st.drain_step()
                        await asyncio.sleep(0.001)
                    else:
                        await asyncio.sleep(0.01)
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — worker must survive
                    Logger.error(f"drain worker failed: {e!r}")
                    await asyncio.sleep(0.5)

        self._drain_task = asyncio.get_running_loop().create_task(_loop())

    def integrity_report(self) -> dict:
        rep = self.store.integrity_report()
        rep["worker_running"] = bool(
            self._integrity_task is not None
            and not self._integrity_task.done()
        )
        return rep

    async def close(self) -> None:
        self.health_sampler.stop()
        if self._evict_task:
            self._evict_task.cancel()
        if self._integrity_task:
            self._integrity_task.cancel()
        if self._tier_task:
            self._tier_task.cancel()
        if self._drain_task:
            self._drain_task.cancel()
        if self._server:
            self._server.close()
            await self._server.wait_closed()
        self.store.close()

    # ---- connection handling ----

    async def _handle_conn(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        # keys this connection has allocated but not yet committed; reclaimed
        # if the client disconnects mid-write
        conn_pending: set = set()
        # per-connection negotiated capabilities: "integrity" flips at
        # HELLO and switches GET_DESC/inline-get responses to the
        # checksummed + epoch-fenced layouts; legacy peers (who never set
        # HELLO_FLAG_INTEGRITY) keep byte-identical legacy frames
        cs = {"integrity": False}
        try:
            while True:
                try:
                    raw = await reader.readexactly(P.HEADER_SIZE)
                except (asyncio.IncompleteReadError, ConnectionResetError):
                    break
                try:
                    op, flags, body_len, req_id = P.unpack_header(raw)
                except ValueError as e:
                    Logger.error(f"bad header: {e}")
                    break
                if body_len > MAX_INLINE_BODY:
                    Logger.error(f"body too large: {body_len}")
                    break
                t_hdr = time.perf_counter()
                body = memoryview(await reader.readexactly(body_len)) if body_len else memoryview(b"")
                account = None
                if flags & P.FLAG_ACCOUNT:
                    # usage-attribution blob (always FIRST on the wire
                    # when both blobs ride one frame); clients only set
                    # the flag after HELLO negotiation
                    try:
                        account, consumed = P.unpack_account(body)
                        body = body[consumed:]
                    except ValueError as e:
                        Logger.error(f"bad account blob: {e}")
                        break
                    if not self.account_enabled or not account:
                        account = None
                trace_id = None
                if flags & P.FLAG_TRACE_CTX:
                    # the caller is propagating its trace: strip the ctx
                    # blob and record this op's spans under ITS trace id
                    # (clients only set the flag after HELLO negotiation,
                    # so a parse failure here is a broken peer)
                    try:
                        trace_id, consumed = P.unpack_trace_ctx(body)
                        body = body[consumed:]
                    except ValueError as e:
                        Logger.error(f"bad trace ctx: {e}")
                        break
                t_body = time.perf_counter()
                name = P.op_name(op)
                if trace_id is not None and self.trace_ctx_enabled:
                    # a REAL server-side trace, ring-kept for the stitcher
                    cm = self.tracer.trace(f"store.{name}",
                                           trace_id=trace_id, body=body_len)
                else:
                    cm = tracing.span(f"store.{name}", body=body_len)
                alive, skip, resp, dt = True, False, None, None
                with cm:
                    if body_len:
                        tracing.add_span_abs("store.recv", t_hdr, t_body,
                                             bytes=body_len)
                    act = (self.faults.match(name)
                           if self.faults.armed else None)
                    if act is not None:
                        # inside the trace ON PURPOSE: an injected delay/
                        # stall must show up as a LONG server-side span in
                        # the stitched timeline — that is the whole point
                        # of tracing a misbehaving store
                        if not await self._inject_fault(op, act, writer, body):
                            alive = False  # drop_conn: die without answering
                        elif act["action"] == "error":
                            skip = True  # error already written; next frame
                    if alive and not skip:
                        t0 = time.perf_counter()
                        resp = await self._dispatch(
                            op, body, reader, writer, conn_pending, cs,
                            account,
                        )
                        dt = time.perf_counter() - t0
                if not alive:
                    break
                if skip:
                    continue
                with self._lat_lock:
                    rec = self._op_lat.setdefault(op, [0, 0.0, 0.0])
                    rec[0] += 1
                    rec[1] += dt
                    rec[2] = max(rec[2], dt)
                self._h_op.labels(name).observe(dt)
                if resp is not None:  # streaming ops write directly
                    writer.write(resp)
                await writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            pass
        except Exception as e:  # noqa: BLE001 - keep server alive
            Logger.error(f"connection error: {e!r}")
        finally:
            if conn_pending:
                self.store.abort_put(list(conn_pending))
            writer.close()
            try:
                await writer.wait_closed()
            except Exception:
                pass

    async def _inject_fault(self, op: int, act: dict, writer, body) -> bool:
        """Apply one matched fault rule.  Returns False when the
        connection must die (``drop_conn``); True continues — after a
        ``delay``/``stall``/``corrupt`` the op proceeds normally, after
        ``error`` the caller skips dispatch (the error response is
        already written)."""
        name = P.op_name(op)
        self._c_faults.labels(name, act["action"]).inc()
        Logger.warn(f"fault injected: {act['action']} on {name}")
        if act["action"] == "corrupt":
            # deterministic bit damage: XOR-flip the first byte of every
            # named key's committed region, leaving the stamped checksum
            # stale — the exact fault the verification plane exists for
            flipped = 0
            for key in _fault_keys(op, body):
                e = self.store.kv.get(key)
                if e is None or e.size == 0:
                    continue
                view = self.store.mm.view(e.pool_idx, e.offset, e.size)
                view[0] ^= 0xFF
                flipped += 1
            Logger.warn(f"corrupt fault flipped {flipped} committed entries")
            return True
        if act["action"] == "drop_conn":
            try:
                writer.transport.abort()  # RST, mid-op — no goodbye
            except Exception:
                pass
            return False
        if act["action"] == "delay":
            await asyncio.sleep(act["delay_s"])
        elif act["action"] == "stall":
            # never answer while the rule stays armed: the hang that no
            # socket error surfaces — exactly what the client-side op
            # deadline must convert into a reconnectable failure.
            # Releasing is polling-based so the manage plane's clear()
            # (an HTTP thread) needs no cross-thread asyncio signaling.
            t0 = time.monotonic()
            while (self.faults.active(act["id"])
                   and time.monotonic() - t0 < _STALL_CAP_S):
                await asyncio.sleep(0.02)
        elif act["action"] == "error":
            writer.write(P.pack_resp(act["error_status"]))
            await writer.drain()
        return True

    async def _dispatch(
        self,
        op: int,
        body: memoryview,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        conn_pending: set,
        cs: dict,
        account: Optional[str] = None,
    ) -> bytes | None:
        st = self.store
        if op == P.OP_HELLO:
            _pid, cflags = P.unpack_hello(body)
            resp = P.pack_pool_table(st.mm.pool_table())
            if (cflags & P.HELLO_FLAG_TRACE_CTX) and self.trace_ctx_enabled:
                # capability trailer: tells the client it may set
                # FLAG_TRACE_CTX, and samples this process's clock so the
                # client can estimate the cross-process offset from the
                # HELLO round-trip.  Appended ONLY when asked — an
                # old-client HELLO gets the byte-identical legacy body.
                resp += P.pack_hello_trailer(
                    P.HELLO_FLAG_TRACE_CTX, time.perf_counter()
                )
            if (cflags & P.HELLO_FLAG_INTEGRITY) and st.integrity != "off":
                # integrity capability answer: boot epoch + checksum alg.
                # Appended only when asked, so legacy HELLOs stay
                # byte-identical; from here on THIS connection's
                # GET_DESC / inline-get responses use the checksummed,
                # epoch-fenced layouts.
                resp += P.pack_epoch_trailer(st.checksum_alg, st.epoch)
                cs["integrity"] = True
            if (cflags & P.HELLO_FLAG_ACCOUNT) and self.account_enabled:
                # usage-attribution capability answer: the max label
                # length.  Appended only when asked (legacy HELLOs stay
                # byte-identical); from here on this connection MAY tag
                # frames with FLAG_ACCOUNT blobs.
                resp += P.pack_acct_trailer()
            if cflags & P.HELLO_FLAG_ALLOC_FIRST:
                # alloc-first capability answer: promise the reservation
                # TTL, so the client may defer COMMIT_PUT to a background
                # thread knowing a crash can't leak its pool blocks.  No
                # per-connection state: ALLOC_PUT/COMMIT_PUT semantics
                # are unchanged, the trailer only advertises the reaper.
                resp += P.pack_alloc_trailer(st.pending_ttl_s)
            return P.pack_resp(P.FINISH, resp)
        if op == P.OP_TRACE_DUMP:
            return P.pack_resp(
                P.FINISH, json.dumps(self.tracer.dump()).encode()
            )
        if op == P.OP_LIST_KEYS:
            limit = P.unpack_i32(body) if len(body) >= 4 else 0
            # trailing-i32 flags extension (reshape plane): pre-flag
            # clients send 4 bytes and get the legacy names-only list
            flags = P.unpack_i32(body[4:]) if len(body) >= 8 else 0
            if flags & P.LIST_KEYS_F_SIZES:
                return P.pack_resp(
                    P.FINISH, json.dumps(st.list_keys_sizes(limit)).encode()
                )
            return P.pack_resp(
                P.FINISH, json.dumps(st.list_keys(limit)).encode()
            )
        if op == P.OP_POOLS:
            return P.pack_resp(P.FINISH, P.pack_pool_table(st.mm.pool_table()))
        if op == P.OP_PUT_INLINE:
            key, vlen, consumed = P.unpack_put_inline_head(body)
            payload = body[consumed : consumed + vlen]
            if len(payload) != vlen:
                return P.pack_resp(P.INVALID_REQ)
            return P.pack_resp(st.put_inline(key, payload, account=account))
        if op == P.OP_GET_INLINE:
            keys, _ = P.unpack_keys(body)
            if not keys:
                return P.pack_resp(P.INVALID_REQ)
            view = st.get_inline(keys[0], account=account)
            if view is None:
                return P.pack_resp(P.KEY_NOT_FOUND)
            if cs["integrity"]:
                hdr = P.pack_inline_resp_ex(st.epoch, st.kv[keys[0]].crc)
                return P.pack_resp(P.FINISH, hdr + bytes(view))
            return P.pack_resp(P.FINISH, bytes(view))
        if op == P.OP_ALLOC_PUT:
            keys, block_size = P.unpack_alloc_put(body)
            with tracing.span("store.alloc", keys=len(keys)):
                status, descs = st.alloc_put(keys, block_size,
                                             account=account)
            if status == P.FINISH:
                conn_pending.update(keys)
            return P.pack_resp(status, P.pack_descs(descs))
        if op == P.OP_COMMIT_PUT:
            keys, _ = P.unpack_keys(body)
            with tracing.span("store.commit", keys=len(keys)):
                status, count = st.commit_put(keys)
            conn_pending.difference_update(keys)
            return P.pack_resp(status, P.pack_i32(count))
        if op == P.OP_GET_DESC:
            keys, block_size = P.unpack_alloc_put(body)
            with tracing.span("store.desc_build", keys=len(keys)):
                status, descs = st.get_desc(keys, block_size,
                                            account=account)
            if cs["integrity"]:
                if status != P.FINISH:
                    return P.pack_resp(status)
                ex = [(p, o, s, st.kv[k].crc)
                      for (p, o, s), k in zip(descs, keys)]
                return P.pack_resp(
                    status, P.pack_desc_resp_ex(st.epoch, ex)
                )
            return P.pack_resp(status, P.pack_descs(descs))
        if op == P.OP_RELEASE_DESC:
            keys, _ = P.unpack_keys(body)
            return P.pack_resp(P.FINISH, P.pack_i32(st.release_desc(keys)))
        if op == P.OP_EXIST:
            keys, _ = P.unpack_keys(body)
            if not keys:
                return P.pack_resp(P.INVALID_REQ)
            return P.pack_resp(P.FINISH, P.pack_i32(0 if st.exist(keys[0]) else 1))
        if op == P.OP_MATCH_LAST_IDX:
            keys, _ = P.unpack_keys(body)
            return P.pack_resp(P.FINISH, P.pack_i32(st.match_last_index(keys)))
        if op == P.OP_DELETE_KEYS:
            keys, _ = P.unpack_keys(body)
            return P.pack_resp(P.FINISH, P.pack_i32(st.delete_keys(keys)))
        if op == P.OP_PURGE:
            return P.pack_resp(P.FINISH, P.pack_i32(st.purge()))
        if op == P.OP_STATS:
            # store stats + server-side per-op latency (the server half of
            # observability next to the client's latency_stats)
            return P.pack_resp(P.FINISH, json.dumps(self.stats_dict()).encode())
        if op == P.OP_EVICT:
            mn, mx = P.unpack_evict(body)
            st.evict(mn, mx)
            return P.pack_resp(P.FINISH)
        if op == P.OP_PUT_INLINE_BATCH:
            # body carries block_size+keys; n*block_size payload follows the frame
            keys, block_size = P.unpack_alloc_put(body)
            status, descs = st.alloc_put(keys, block_size, account=account)
            if status != P.FINISH:
                # drain the payload to keep the stream in sync
                remaining = block_size * len(keys)
                while remaining > 0:
                    chunk = await reader.read(min(remaining, 1 << 20))
                    if not chunk:
                        break
                    remaining -= len(chunk)
                return P.pack_resp(status)
            # mark busy: a concurrent purge/realloc must not free these
            # regions while we await payload chunks; track in conn_pending so
            # a mid-stream disconnect reclaims them
            conn_pending.update(keys)
            for key in keys:
                st.pending[key].busy = True
            try:
                with tracing.span("store.pool_copy",
                                  bytes=block_size * len(keys)):
                    for (pool_idx, offset, size) in _merge_desc_runs(descs):
                        dst = st.mm.view(pool_idx, offset, size)
                        got = 0
                        while got < size:
                            chunk = await reader.read(min(size - got, 1 << 20))
                            if not chunk:
                                st.abort_put(keys)
                                return P.pack_resp(P.INVALID_REQ)
                            dst[got : got + len(chunk)] = chunk
                            got += len(chunk)
            finally:
                for key in keys:
                    e = st.pending.get(key)
                    if e is not None:
                        e.busy = False
            status, count = st.commit_put(keys)
            conn_pending.difference_update(keys)
            return P.pack_resp(status, P.pack_i32(count))
        if op == P.OP_GET_INLINE_BATCH:
            keys, block_size = P.unpack_alloc_put(body)
            status, descs = st.get_desc(keys, block_size, account=account)
            if status != P.FINISH:
                return P.pack_resp(status)
            # resp body = n x size:u32 | payloads streamed straight from
            # the shm pool (no batch-sized intermediate copies); on
            # integrity-negotiated connections the size table becomes
            # epoch u64 | n x {size, csum, flags} so the client can
            # verify the received bytes end to end
            total = sum(size for (_, _, size) in descs)
            if cs["integrity"]:
                sizes = P.pack_u64(st.epoch) + b"".join(
                    P.pack_batch_item_ex(size, st.kv[k].crc)
                    for (_, _, size), k in zip(descs, keys)
                )
            else:
                sizes = b"".join(P._U32.pack(size) for (_, _, size) in descs)
            writer.write(P.RESP.pack(P.FINISH, len(sizes) + total))
            writer.write(sizes)
            with tracing.span("store.pool_copy", bytes=total):
                for (pool_idx, offset, size) in _merge_desc_runs(descs):
                    writer.write(bytes(st.mm.view(pool_idx, offset, size)))
                    await writer.drain()
            return None
        return P.pack_resp(P.INVALID_REQ)
