"""Transport-agnostic KV store core.

Mirrors the reference server's state (kv_map + lru_queue + MM, reference:
src/infinistore.cpp:26-53) and op semantics, independent of the event loop so
both the asyncio server (``pyserver.py``) and tests can drive it directly.
The C++ native runtime (``src/store_server.cpp``) implements the same logic.

Semantics preserved from the reference:

* entries become visible only at commit time (reference inserts into kv_map
  after the RDMA transfer completes, src/infinistore.cpp:405-418);
* reads touch the LRU (src/infinistore.cpp:629-634) and fail with
  KEY_NOT_FOUND if *any* requested key is missing (src/infinistore.cpp:612-617);
* stored size must fit the reader's block size (src/infinistore.cpp:620-624);
* eviction pops from the LRU head until usage < min threshold
  (src/infinistore.cpp:223-234), with the same on-demand thresholds
  (0.8/0.95, src/infinistore.cpp:52-53).  The on-demand pass is not made
  inside the allocation that crossed 0.95, as the reference makes it: that
  allocation marks the store DRAINING, and the same victims leave in the
  same order in bounded slices (``drain_step``) that the server runs
  between requests.  An allocation evicts on its own path only when it
  would fail otherwise, and then only what it lacks;
* ``get_match_last_index`` binary-searches for the last present key, which
  assumes present keys form a prefix of the list -- exactly the reference's
  algorithm (src/infinistore.cpp:786-802);
* allocation failure sets ``need_extend`` for the 10 GB auto-extend path
  (src/infinistore.cpp:437-452).

One addition over the reference: descriptor reads hand out raw pool offsets
to shm clients, so committed entries carry a short *lease* after a GET_DESC
and the evictor skips leased entries.  The reference has the same window with
in-flight RDMA reads and relies on LRU touch alone.

Second storage tier: with ``disk_tier_path`` set, cold entries live in
mmap'd spill files — one slab per power-of-two sizeclass — instead of
vanishing, and any access (read, exist, prefix match) PROMOTES them back
into DRAM — the reference design's "Historical KVCache in DRAM and SSD"
(reference docs/source/design.rst:36).  Entries reach the tier two ways:
the evictor SPILLS what it pops under pressure, and the background tier
worker DEMOTES entries the age-band analytics call cold before pressure
ever forces the choice (never on the put critical path).  Every spilled
record carries the entry's checksum and is re-verified on promote, so a
torn write from a crash or bit rot becomes a counted miss, never served
bytes.  A small manifest persists the tier's index across process death:
a restarted node boots as a WARM cache (the epoch fence already remaps
clients), which is what turns the store from a process-lifetime artifact
into fleet infrastructure that survives deploys.  The tier is
transparent to the wire protocol: clients only ever see pool
descriptors, never disk state.
"""

from __future__ import annotations

import json
import mmap
import os
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import protocol as P
from .mempool import MM
from .usage import SHARER_CAP, UsageMeter
from .utils import checksum as _checksum

ON_DEMAND_MIN_THRESHOLD = 0.8  # reference: src/infinistore.cpp:52
ON_DEMAND_MAX_THRESHOLD = 0.95  # reference: src/infinistore.cpp:53
# entries one slice of the on-demand drain examines before it yields to the
# server's loop: one ALLOC_PUT's worth of a push.  Read on a 3 GiB pool of
# 32 KB pages held full (CPU, PR 44): the one pass took 14,784 entries in
# 82-204 ms; a slice of 96 takes 1.0 ms in the median and 1.9 ms at p99, the
# usage meter 38% of an entry and the bitmap 15%.  So a request that arrives
# mid-drain waits about a millisecond.
DRAIN_SLICE_ENTRIES = 96
READ_LEASE_S = 5.0
# how long an allocated-but-uncommitted reservation may sit before the
# store reaps it.  Alloc-first clients (HELLO_FLAG_ALLOC_FIRST) learn
# descriptors before the payload exists and commit from a background
# thread, so a reservation legitimately outlives its ALLOC_PUT by a full
# push; the TTL only has to catch clients that died without disconnecting
# (disconnect already aborts via conn_pending).  Must comfortably exceed
# the slowest conceivable push — a reaped reservation makes the late
# COMMIT_PUT answer INVALID_REQ, a loud failure, never silent corruption.
RESERVE_TTL_S = float(os.environ.get("ISTPU_RESERVE_TTL_S", "60"))


@dataclass
class Entry:
    pool_idx: int
    offset: int
    size: int
    lease: float = 0.0
    # busy: an op is actively streaming payload into this pending region;
    # purge/realloc must not free the blocks out from under it
    busy: bool = False
    # cache-efficiency attribution (docs/observability.md): commit stamp,
    # last read stamp, and read count — together they answer "is the
    # store tier earning its keep" (reuse distance, eviction age,
    # dead-on-arrival) without a second bookkeeping structure
    created: float = 0.0
    last_access: float = 0.0
    hits: int = 0
    # integrity plane: content checksum stamped after commit (None while
    # the stamping backlog hasn't reached this entry — readers skip
    # verification for unstamped descs), and the live GET_DESC reader
    # count behind the lease (OP_RELEASE_DESC decrements; the lease
    # clears early when it reaches zero, while legacy clients that never
    # release keep the timed behavior)
    crc: Optional[int] = None
    readers: int = 0
    # usage-attribution plane (usage.py): the account that WROTE this
    # entry (first writer owns; None = an untagged/legacy client) and
    # the bounded set of OTHER accounts that have read it — the split
    # the UsageMeter bills shared-prefix bytes across
    account: Optional[str] = None
    sharers: Optional[List[str]] = None


@dataclass
class Stats:
    puts: int = 0
    gets: int = 0
    hits: int = 0
    misses: int = 0
    evicted: int = 0
    # of those, by who paid: the on-demand drain's slices (between
    # requests) against an allocation's own path (what it lacked, or the
    # sizeclass allocator's pressure pops); the rest are evict() passes
    evicted_drain: int = 0
    evicted_inline: int = 0
    drain_slices: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    spilled: int = 0    # DRAM -> disk tier at eviction (pressure)
    demoted: int = 0    # DRAM -> disk tier by the background tier worker
    promoted: int = 0   # disk tier -> DRAM
    contig_batches: int = 0  # batch allocs served as one contiguous run
    scrub_pages: int = 0    # entries re-verified by the background scrubber
    scrub_corrupt: int = 0  # corrupt entries found and quarantined
    # uncommitted reservations reaped past the TTL (a client that crashed
    # mid-push without disconnecting; >0 in steady state means leaked
    # alloc-first writers)
    reservations_reaped: int = 0


class CacheAnalytics:
    """Hit/miss/evict attribution for the cache-efficiency plane.

    The store calls the ``on_*`` hooks from its op paths; the serving
    layer (``pyserver.StoreServer``) wires ``reuse_sink`` /
    ``evict_age_sink`` to registry histograms
    (``istpu_cache_reuse_distance_seconds`` /
    ``istpu_cache_evicted_age_seconds``) so a scrape sees the
    distributions, and ``dead_on_arrival`` backs
    ``istpu_cache_dead_on_arrival_total`` — entries evicted having never
    been read, i.e. store writes that bought nothing.  Plain attributes,
    no lock: the store is single-threaded (the asyncio loop) and the
    exposition reads are snapshot-tolerant counters."""

    def __init__(self):
        self.dead_on_arrival = 0
        self.evicted_read = 0     # evicted entries that HAD been read
        self.reuse_count = 0
        self.reuse_total_s = 0.0
        self.reuse_sink = None       # callable(seconds) or None
        self.evict_age_sink = None   # callable(seconds) or None

    def on_hit(self, reuse_s: float) -> None:
        self.reuse_count += 1
        self.reuse_total_s += reuse_s
        if self.reuse_sink is not None:
            self.reuse_sink(reuse_s)

    def on_evict(self, age_s: float, never_read: bool) -> None:
        if never_read:
            self.dead_on_arrival += 1
        else:
            self.evicted_read += 1
        if self.evict_age_sink is not None:
            self.evict_age_sink(age_s)


# /debug/cache occupancy bands: "how much of the pool is held by entries
# this cold" — upper bounds in seconds since last access
AGE_BANDS = ((1.0, "<1s"), (10.0, "<10s"), (60.0, "<1m"),
             (600.0, "<10m"), (float("inf"), ">=10m"))


# the disk tier degrades to DRAM-only after this many CONSECUTIVE I/O
# failures, for a cooldown — a dying disk must cost spilled entries,
# never wedge the evict/promote paths in an error loop
DISK_DEGRADE_AFTER = 3
DISK_COOLDOWN_S = float(os.environ.get("ISTPU_DISK_COOLDOWN_S", "10"))
# admission gate sample floor: the dead-on-arrival ratio only refuses
# never-read entries once this many evictions have been attributed
# (a handful of early DOAs must not blind the tier)
DISK_DOA_MIN_SAMPLES = 64
MANIFEST_NAME = "spill_manifest.json"
_SPILL_PREFIX = "spill_"


@dataclass
class _SpillRec:
    cls: int   # sizeclass (slot bytes, pow2 multiple of block_size)
    slot: int  # slot index inside the sizeclass slab
    size: int  # payload bytes (<= cls)
    crc: int   # content checksum, verified on every promote
    # owning account (usage attribution; persisted in the manifest so a
    # warm restart keeps billing the right tenant).  None = untagged.
    account: Optional[str] = None


class _Slab:
    """One mmap'd spill file holding fixed-size slots of one sizeclass.

    Uniform slots per file is the point of classing: allocation is a
    free-list pop, never a run search, and the file grows in slot
    batches (``ftruncate`` + ``mmap.resize``) only when the free list is
    dry.  Existing files are reopened without truncation — the warm-
    restart path."""

    def __init__(self, path: str, slot_size: int, grow_slots: int = 16):
        self.path = path
        self.slot_size = slot_size
        self._grow = grow_slots
        exists = os.path.exists(path)
        self._f = open(path, "r+b" if exists else "w+b")
        self.slots = (os.path.getsize(path) // slot_size) if exists else 0
        self._map: Optional[mmap.mmap] = None
        if self.slots:
            self._remap()
        self.free: List[int] = []
        self._next = 0  # high-water mark (warm boot resets it)

    def _remap(self) -> None:
        if self._map is not None:
            self._map.close()
        self._map = mmap.mmap(self._f.fileno(), self.slots * self.slot_size)

    def alloc(self) -> int:
        """A free slot, growing the file when none is.  Raises OSError
        on a full disk (the ``ftruncate``) — the caller's admission
        failure, never a torn record."""
        if self.free:
            return self.free.pop()
        slot = self._next
        if slot >= self.slots:
            self._f.truncate((self.slots + max(self._grow, 1))
                             * self.slot_size)
            self.slots += max(self._grow, 1)
            self._remap()
        self._next += 1
        return slot

    def release(self, slot: int) -> None:
        self.free.append(slot)

    def write(self, slot: int, data: bytes) -> None:
        off = slot * self.slot_size
        self._map[off:off + len(data)] = data

    def read(self, slot: int, size: int) -> bytes:
        off = slot * self.slot_size
        return bytes(self._map[off:off + size])

    def used(self) -> int:
        return self._next - len(self.free)

    def reset(self) -> None:
        self.free = []
        self._next = 0
        if self._map is not None:
            self._map.close()
            self._map = None
        self._f.truncate(0)
        self.slots = 0

    def shrink(self, new_slots: int) -> None:
        """Give the file's tail back to the filesystem — compaction's
        final step.  Caller guarantees every slot >= ``new_slots`` is
        free; never grows.  Raises OSError on the truncate (the caller's
        I/O-failure path), leaving the slab usable at its old size."""
        new_slots = max(new_slots, 0)
        if new_slots >= self.slots:
            return
        if self._map is not None:
            self._map.close()
            self._map = None
        try:
            self._f.truncate(new_slots * self.slot_size)
        except OSError:
            if self.slots:
                self._remap()  # restore the old mapping; nothing changed
            raise
        self.slots = new_slots
        self.free = [s for s in self.free if s < new_slots]
        self._next = min(self._next, new_slots)
        if self.slots:
            self._remap()

    def close(self) -> None:
        if self._map is not None:
            self._map.close()
            self._map = None
        self._f.close()


class DiskTier:
    """The file-backed cold half of the cache hierarchy.

    mmap'd spill files per sizeclass (``spill_<bytes>.dat``), an
    OrderedDict doubling as the tier's own LRU — at capacity the oldest
    spilled entry is dropped for good, the reference hierarchy's
    behavior at the bottom of the stack — and a small JSON manifest that
    persists the index across process death, so a restarted node boots
    warm.  Every record carries its content checksum and is re-verified
    on promote: a torn write from a crash, bit rot, or an injected
    corruption answers a counted miss, never bad KV.  No fsync anywhere
    (a cache tier, not a database — a crash loses at most the entries
    spilled since the last manifest save, and re-computable KV at that).

    Failure containment: ``fault`` is the injectable disk-fault hook
    (pyserver wires it to the ``disk_error``/``disk_slow`` FaultInjector
    actions); after ``DISK_DEGRADE_AFTER`` consecutive I/O failures the
    tier answers DRAM-only for a cooldown instead of paying the error on
    every access."""

    def __init__(self, path: str, capacity_bytes: int, block_size: int,
                 alg: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        os.makedirs(path, exist_ok=True)
        self.path = path  # the tier DIRECTORY (slabs + manifest live here)
        self.manifest_path = os.path.join(path, MANIFEST_NAME)
        self.block_size = block_size
        self.capacity_bytes = max(block_size, capacity_bytes)
        self.alg = _checksum.alg_id("sum64") if alg is None else alg
        self._clock = clock
        # key -> record; insertion order = spill LRU (head = oldest)
        self.index: "OrderedDict[bytes, _SpillRec]" = OrderedDict()
        self._slabs: Dict[int, _Slab] = {}
        self._bytes = 0       # payload bytes resident
        self._slot_bytes = 0  # allocated slot bytes (the capacity unit)
        self.dropped = 0
        self.io_errors = 0
        self.verify_failures = 0
        self.orphans_reaped = 0
        self.warm_entries = 0
        # background compaction (the consumer of the per-slab fill
        # signal): slabs truncated, file bytes released, payload bytes
        # slid, and the sizeclass the last pass worked on
        self.compacted_slabs = 0
        self.compacted_bytes = 0
        self.compact_moved_bytes = 0
        self._compact_cls: Optional[int] = None
        self.fault: Optional[Callable[[str], None]] = None
        self.corrupt_sink: Optional[Callable[[bytes], None]] = None
        # usage attribution: fired on EVERY index insert/remove with
        # (account, payload bytes, added) — the one place spill-tier
        # residency changes, so the meter can never drift from the index
        self.usage_sink: Optional[
            Callable[[Optional[str], int, bool], None]] = None
        self._consec_errors = 0
        self._degraded_until = 0.0
        self._dirty = False
        self._last_save = 0.0
        self._load_manifest()

    # -- presence / accounting --

    def __contains__(self, key: bytes) -> bool:
        return key in self.index and not self.degraded()

    def __len__(self) -> int:
        return len(self.index)

    def used_bytes(self) -> int:
        return self._bytes

    def degraded(self) -> bool:
        return self._clock() < self._degraded_until

    def _cls(self, size: int) -> int:
        c = self.block_size
        while c < size:
            c <<= 1
        return c

    def _slab(self, cls: int) -> _Slab:
        slab = self._slabs.get(cls)
        if slab is None:
            slab = _Slab(
                os.path.join(self.path, f"{_SPILL_PREFIX}{cls}.dat"), cls
            )
            self._slabs[cls] = slab
        return slab

    # -- fault plumbing --

    def _io(self, kind: str) -> None:
        if self.fault is not None:
            self.fault(kind)  # may raise OSError or sleep (injection)

    def _io_failed(self) -> None:
        self.io_errors += 1
        self._consec_errors += 1
        if self._consec_errors >= DISK_DEGRADE_AFTER:
            # mitigation: stop touching the disk for a cooldown — the
            # hierarchy degrades to DRAM-only, requests never fail
            self._degraded_until = self._clock() + DISK_COOLDOWN_S

    def _io_ok(self) -> None:
        self._consec_errors = 0

    # -- data path --

    def _usage(self, account: Optional[str], size: int,
               added: bool) -> None:
        if self.usage_sink is not None:
            self.usage_sink(account, size, added)

    def put(self, key: bytes, data, crc: Optional[int] = None,
            account: Optional[str] = None) -> bool:
        """Admit one entry (spill or demotion).  False = not admitted
        (full beyond what dropping the cold tail frees, degraded, or the
        disk failed) — the caller's eviction simply continues and the
        entry leaves the hierarchy, exactly the DRAM-only behavior."""
        if self.degraded():
            return False
        payload = bytes(data)
        size = len(payload)
        cls = self._cls(size)
        if size == 0 or cls > self.capacity_bytes:
            return False
        self.pop(key)  # an old copy's slot goes back to the free list
        while self._slot_bytes + cls > self.capacity_bytes and self.index:
            self._drop_oldest()
        if self._slot_bytes + cls > self.capacity_bytes:
            return False
        try:
            self._io("write")
            slab = self._slab(cls)
            slot = slab.alloc()
            slab.write(slot, payload)
        except OSError:
            # disk full / IO error: the entry simply doesn't spill (a
            # truncated record must never sit in the index to promote
            # back as corrupt KV — alloc raises BEFORE write maps it)
            self._io_failed()
            return False
        self._io_ok()
        if crc is None:
            crc = _checksum.checksum(payload, self.alg)
        self.index[key] = _SpillRec(cls, slot, size, crc, account=account)
        self._bytes += size
        self._slot_bytes += cls
        self._dirty = True
        self._usage(account, size, True)
        return True

    def get(self, key: bytes) -> Optional[bytes]:
        """Read one entry back, VERIFYING its checksum.  A mismatch
        drops the record (counted, ``corrupt_sink`` fired) and answers
        None — the promote path's miss, which the engine serves by
        recompute."""
        rec = self.index.get(key)
        if rec is None or self.degraded():
            return None
        try:
            self._io("read")
            data = self._slabs[rec.cls].read(rec.slot, rec.size)
        except (OSError, KeyError):
            self._io_failed()
            return None
        self._io_ok()
        if _checksum.checksum(data, self.alg) != rec.crc:
            # torn write across a crash, bit rot, or injected damage:
            # quarantine the record — it must never promote
            self.pop(key)
            self.verify_failures += 1
            self._dirty = True
            if self.corrupt_sink is not None:
                self.corrupt_sink(key)
            return None
        self.index.move_to_end(key)  # tier-local LRU touch
        return data

    def pop(self, key: bytes) -> bool:
        """Drop an entry; True when one was present."""
        rec = self.index.pop(key, None)
        if rec is None:
            return False
        self._bytes -= rec.size
        self._slot_bytes -= rec.cls
        slab = self._slabs.get(rec.cls)
        if slab is not None:
            slab.release(rec.slot)
        self._dirty = True
        self._usage(rec.account, rec.size, False)
        return True

    def _drop_oldest(self) -> None:
        key, rec = self.index.popitem(last=False)
        self._bytes -= rec.size
        self._slot_bytes -= rec.cls
        slab = self._slabs.get(rec.cls)
        if slab is not None:
            slab.release(rec.slot)
        self.dropped += 1
        self._dirty = True
        self._usage(rec.account, rec.size, False)

    def clear(self) -> int:
        n = len(self.index)
        for rec in self.index.values():
            self._usage(rec.account, rec.size, False)
        self.index.clear()
        for slab in self._slabs.values():
            try:
                slab.reset()
            except OSError:
                self._io_failed()
        self._bytes = 0
        self._slot_bytes = 0
        self._dirty = True
        try:
            self.save_manifest()  # a purge must not resurrect at boot
        except OSError:
            self._io_failed()
        return n

    # -- persistence (the warm-restart contract) --

    def save_manifest(self) -> None:
        """Atomically persist the index.  Entries spilled after the last
        save are lost to a crash (re-computable cache, acceptable); a
        torn DATA write is caught by the per-record checksum on promote,
        and the manifest itself is tmp+rename so it is never torn."""
        doc = {
            "version": 1,
            "block_size": self.block_size,
            "alg": self.alg,
            "slabs": {str(cls): slab.slots
                      for cls, slab in self._slabs.items()},
            "entries": [
                [k.hex(), rec.cls, rec.slot, rec.size, rec.crc,
                 rec.account]
                for k, rec in self.index.items()
            ],
        }
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, self.manifest_path)
        self._dirty = False
        self._last_save = self._clock()

    def maybe_save(self, min_interval_s: float = 2.0) -> bool:
        if not self._dirty:
            return False
        if self._clock() - self._last_save < min_interval_s:
            return False
        try:
            self.save_manifest()
        except OSError:
            self._io_failed()
            return False
        return True

    # -- background compaction (the slab-fill signal's consumer) --

    def compact_step(self, fill_threshold: float = 0.5,
                     budget_bytes: int = 32 << 20) -> int:
        """One paced compaction slide: pick the lowest-fill slab under
        ``fill_threshold``, move its tail records down into free head
        slots (checksum-verified, at most ``budget_bytes`` of payload
        per call), and — once the tail is clear — truncate the file.

        Crash-safe by ordering, never by fsync: the manifest is saved
        BEFORE any slot is overwritten (so every head slot written to is
        unreferenced by the persisted index) and again before the
        truncate (so no persisted record points past the new end of
        file).  A kill anywhere in between replays to records whose
        bytes are intact — or, at worst, to entries lost since the last
        save, the tier's existing crash contract.  Torn bytes never
        promote: the per-record checksum quarantines them.

        Returns file bytes released (0 = nothing eligible, budget spent
        mid-slide — progress is kept — or the disk is degraded)."""
        if self.degraded():
            return 0
        # eligibility: a grown file whose aggregate fill dropped under
        # the threshold with at least one grow-batch of slack, so a slab
        # hovering at its high-water mark never thrashes shrink/grow
        best = None
        for cls, slab in self._slabs.items():
            if not slab.slots or slab.slots - slab.used() < slab._grow:
                continue
            fill = slab.used() / slab.slots
            if fill >= fill_threshold:
                continue
            if best is None or fill < best[0]:
                best = (fill, cls, slab)
        if best is None:
            self._compact_cls = None
            return 0
        _fill, cls, slab = best
        self._compact_cls = cls
        target = slab.used()  # every record fits below this mark
        tail = sorted(
            ((k, rec) for k, rec in self.index.items()
             if rec.cls == cls and rec.slot >= target),
            key=lambda kr: kr[1].slot,
        )
        try:
            if self._dirty:
                # persist BEFORE overwriting any free slot: every head
                # slot this pass fills is now unreferenced on disk
                self.save_manifest()
            moved = 0
            if tail:
                head_free = sorted(
                    (s for s in slab.free if s < target), reverse=True)
                for key, rec in tail:
                    if moved >= budget_bytes:
                        self.compact_moved_bytes += moved
                        return 0  # budget spent; next tick continues
                    self._io("read")
                    data = slab.read(rec.slot, rec.size)
                    if _checksum.checksum(data, self.alg) != rec.crc:
                        # quarantine exactly like a failed promote
                        self.pop(key)
                        self.verify_failures += 1
                        if self.corrupt_sink is not None:
                            self.corrupt_sink(key)
                        continue
                    new_slot = head_free.pop()
                    self._io("write")
                    slab.write(new_slot, data)
                    slab.free.remove(new_slot)
                    slab.free.append(rec.slot)
                    rec.slot = new_slot
                    self._dirty = True
                    moved += rec.size
            self.compact_moved_bytes += moved
            # tail clear: persist the slid index, THEN give the file
            # tail back
            high = max((rec.slot for rec in self.index.values()
                        if rec.cls == cls), default=-1)
            new_slots = high + 1
            freed = (slab.slots - new_slots) * cls
            if freed <= 0:
                return 0
            self.save_manifest()
            slab.shrink(new_slots)
        except OSError:
            self._io_failed()
            return 0
        self._io_ok()
        self._dirty = True
        self.compacted_slabs += 1
        self.compacted_bytes += freed
        return freed

    def _spill_files(self) -> List[str]:
        try:
            return [f for f in os.listdir(self.path)
                    if f.startswith(_SPILL_PREFIX) and f.endswith(".dat")]
        except OSError:
            return []

    def _reap_all_spill_files(self) -> None:
        for f in self._spill_files():
            try:
                os.unlink(os.path.join(self.path, f))
                self.orphans_reaped += 1
            except OSError:
                pass

    def _load_manifest(self) -> None:
        """Boot: rebuild the index from the manifest when one matches
        this tier's geometry, reaping every spill file the manifest does
        not vouch for (orphans from a crashed demotion, a geometry
        change, or a different run)."""
        try:
            with open(self.manifest_path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = None
        if (not isinstance(doc, dict) or doc.get("version") != 1
                or doc.get("block_size") != self.block_size
                or doc.get("alg") != self.alg):
            # cold boot (no/alien manifest): leftover slabs are orphans
            self._reap_all_spill_files()
            return
        known = {f"{_SPILL_PREFIX}{cls}.dat" for cls in doc["slabs"]}
        for f in self._spill_files():
            if f not in known:
                try:
                    os.unlink(os.path.join(self.path, f))
                    self.orphans_reaped += 1
                except OSError:
                    pass
        used: Dict[int, set] = {}
        for item in doc.get("entries", []):
            try:
                # pre-accounting manifests carry 5 fields; the account
                # rides as an optional 6th (warm restarts keep billing
                # the right tenant without a format break)
                k, cls, slot, size, crc = item[:5]
                account = item[5] if len(item) > 5 else None
                if account is not None:
                    account = str(account)
                key = bytes.fromhex(k)
                cls, slot, size, crc = (int(cls), int(slot), int(size),
                                        int(crc))
            except (ValueError, TypeError, IndexError):
                continue
            if (cls < self.block_size or cls & (cls - 1) or size > cls
                    or slot < 0):
                continue
            slab_path = os.path.join(self.path, f"{_SPILL_PREFIX}{cls}.dat")
            if not os.path.exists(slab_path):
                continue
            if (slot + 1) * cls > os.path.getsize(slab_path):
                continue  # the slab lost a tail (torn truncate)
            self.index[key] = _SpillRec(cls, slot, size, crc,
                                        account=account)
            self._bytes += size
            self._slot_bytes += cls
            used.setdefault(cls, set()).add(slot)
        for cls, slots in used.items():
            slab = self._slab(cls)
            top = max(slots) + 1
            slab._next = top
            slab.free = [s for s in range(top) if s not in slots]
        self.warm_entries = len(self.index)

    def report(self) -> dict:
        """The spill-tier breakdown of ``/debug/cache``."""
        return {
            "entries": len(self.index),
            "bytes": self._bytes,
            "slot_bytes": self._slot_bytes,
            "capacity_bytes": self.capacity_bytes,
            "dropped": self.dropped,
            "io_errors": self.io_errors,
            "verify_failures": self.verify_failures,
            "orphans_reaped": self.orphans_reaped,
            "warm_entries": self.warm_entries,
            "degraded": self.degraded(),
            # the compaction pass that consumes the fill signal below:
            # slabs truncated, file bytes released, payload bytes slid,
            # and the sizeclass the current/last pass worked on
            "compaction": {
                "slabs": self.compacted_slabs,
                "bytes": self.compacted_bytes,
                "moved_bytes": self.compact_moved_bytes,
                "active_cls": self._compact_cls,
            },
            # per-slab occupancy (the compaction pass's signal): slots
            # allocated in the file vs slots actually holding a record —
            # fill << 1.0 on a grown slab is reclaimable space
            "sizeclasses": {
                str(cls): {
                    "slots": slab.slots, "used": slab.used(),
                    "fill": (round(slab.used() / slab.slots, 4)
                             if slab.slots else 0.0),
                }
                for cls, slab in sorted(self._slabs.items())
            },
        }

    def close(self) -> None:
        """Persist and release — the spill files STAY on disk (the whole
        point: the next boot is warm).  ``clear()`` is the deliberate
        way to forget."""
        try:
            if self._dirty:
                self.save_manifest()
        except OSError:
            pass
        for slab in self._slabs.values():
            try:
                slab.close()
            except OSError:
                pass


class Store:
    def __init__(self, config):
        self.config = config
        self.mm = MM(
            pool_size=config.prealloc_size << 30,
            block_size=config.minimal_allocate_size << 10,
            name_prefix=getattr(config, "shm_prefix", None) or None,
            allocator=getattr(config, "allocator", "bitmap"),
        )
        # committed entries; OrderedDict doubles as the LRU queue (head = LRU)
        self.kv: "OrderedDict[bytes, Entry]" = OrderedDict()
        # uncommitted allocations: key -> Entry (not visible to reads/exist)
        self.pending: Dict[bytes, Entry] = {}
        # regions deleted/purged while leased: the key disappears at once,
        # the blocks are freed only after the lease expires (an shm client
        # may still be memcpying from them)
        self._deferred: List[Tuple[float, Entry]] = []
        self.stats = Stats()
        # injectable clock: leases, reuse distances, and eviction ages all
        # read it, so tests can drive deterministic timelines without
        # monkeypatching the global time module
        self._clock = time.monotonic
        self.analytics = CacheAnalytics()
        self._init_integrity(config)
        # second tier: cold entries spill/demote here and promote back
        # on access ("Historical KVCache in DRAM and SSD").  Same
        # checksum alg as the integrity plane so spill records reuse the
        # stamped entry checksums and every promote re-verifies.
        self.disk: Optional[DiskTier] = None
        tier_path = getattr(config, "disk_tier_path", "") or ""
        if tier_path:
            self.disk = DiskTier(
                tier_path,
                int(getattr(config, "disk_tier_size", 64)) << 30,
                self.mm.block_size,
                alg=self.checksum_alg,
                clock=self._clock,
            )
            # seed the usage meter with the warm-boot residency BEFORE
            # wiring the sink (the manifest load ran inside DiskTier's
            # constructor, where no sink existed yet)
            for rec in self.disk.index.values():
                self.usage_meter.add([rec.account], rec.size, "disk")
            self.disk.usage_sink = self._disk_usage

    def _init_integrity(self, config) -> None:
        """Integrity-plane state (also called by tests that hand-build
        stores via ``Store.__new__``).  ``epoch`` is the boot epoch every
        descriptor is fenced against: a client holding descs or pool
        mappings from a different epoch is talking through a restart."""
        level = (getattr(config, "integrity", "") or
                 os.environ.get("ISTPU_INTEGRITY", "") or "verify")
        if level not in ("off", "verify", "scrub"):
            raise ValueError(
                f"ISTPU_INTEGRITY must be off|verify|scrub, got {level!r}"
            )
        self.integrity = level
        alg = (getattr(config, "integrity_alg", "") or
               os.environ.get("ISTPU_INTEGRITY_ALG", "") or "sum64")
        self.checksum_alg = _checksum.alg_id(alg)
        self.epoch = time.time_ns() & ((1 << 63) - 1)
        self.scrub_rate = float(
            getattr(config, "scrub_rate", 0)
            or os.environ.get("ISTPU_SCRUB_RATE", 0) or 256.0
        )
        # reservation TTL for allocated-but-uncommitted regions (the
        # alloc-first contract advertised in the HELLO ALOC trailer);
        # initialized here so hand-built test stores get it too
        self.pending_ttl_s = float(
            getattr(config, "reserve_ttl", 0) or RESERVE_TTL_S
        )
        # commit-time stamping backlog: (key, entry) pairs drained by
        # stamp_pending.  Deferred on purpose — a synchronous checksum at
        # COMMIT_PUT would serialize a full extra memory pass into the
        # measured put path (the perf-smoke floor)
        self._unstamped: deque = deque()
        self._scrub_keys: List[bytes] = []  # current scrub pass snapshot
        # on-demand eviction (here so hand-built test stores get it too):
        # set by the allocation that finds usage at the high threshold,
        # cleared by the drain_step that brings it under the low one;
        # leased heads rotated past since the drain began (its one way to
        # end with the pool still full); callable(seconds) for the time an
        # allocation spent evicting on its own path
        self.draining = False
        self._drain_skipped = 0
        self.evict_stall_sink: Optional[Callable[[float], None]] = None
        # spill-tier knobs (initialized here so hand-built test stores
        # get them too): an entry is DEMOTABLE once it has sat untouched
        # this long AND the pool is at least this full; the DOA gate
        # refuses disk admission for never-read entries once the
        # eviction record says most writes here buy nothing
        self.demote_after_s = float(
            getattr(config, "demote_after_s", 0)
            or os.environ.get("ISTPU_DEMOTE_AFTER_S", 0) or 20.0
        )
        self.demote_watermark = float(
            getattr(config, "demote_watermark", 0)
            or os.environ.get("ISTPU_DEMOTE_WATERMARK", 0) or 0.5
        )
        self.disk_doa_gate = float(
            getattr(config, "disk_doa_gate", 0)
            or os.environ.get("ISTPU_DISK_DOA_GATE", 0) or 0.8
        )
        # background slab compaction: a sizeclass whose aggregate fill
        # drops under ``compact_fill`` gets its lowest-fill slab slid
        # and truncated, paced at ``compact_rate`` payload bytes/s so
        # the pass never starves foreground ops.  Rate 0 = off.
        self.compact_fill = float(
            getattr(config, "compact_fill", 0)
            or os.environ.get("ISTPU_COMPACT_FILL", 0) or 0.5
        )
        self.compact_rate = float(
            getattr(config, "compact_rate", 0)
            or os.environ.get("ISTPU_COMPACT_RATE", 0) or (32 << 20)
        )
        self._compact_last_t: Optional[float] = None
        # per-account usage ledger (usage.py): byte·seconds of occupancy
        # per tier, hits/evictions/DOA per account, shared-prefix bytes
        # split across sharer sets.  Initialized here so hand-built test
        # stores get it too; reads the store's clock INDIRECTLY so tests
        # that swap ``_clock`` after construction keep driving it.
        self.usage_meter = UsageMeter(
            clock=lambda: getattr(self, "_clock", time.monotonic)()
        )

    def _disk_usage(self, account: Optional[str], size: int,
                    added: bool) -> None:
        """The DiskTier's usage sink: every spill-index insert/remove
        moves residency on the meter's disk tier."""
        if added:
            self.usage_meter.add([account], size, "disk")
        else:
            self.usage_meter.sub([account], size, "disk")

    @staticmethod
    def _entry_accounts(e: Entry) -> List[Optional[str]]:
        """The accounts an entry's DRAM bytes are split across: the
        owner plus every recorded sharer."""
        return [e.account] + (e.sharers or [])

    # ---- helpers ----

    def _free(self, e: Entry) -> None:
        self.mm.deallocate(e.pool_idx, e.offset, e.size)

    def _free_or_defer(self, e: Entry, now: float) -> None:
        if e.lease > now:
            self._deferred.append((e.lease, e))
        else:
            self._free(e)

    def _reap_deferred(self, now: float) -> None:
        keep = []
        for expiry, e in self._deferred:
            if expiry <= now:
                self._free(e)
            else:
                keep.append((expiry, e))
        self._deferred = keep

    def reap_pending(self, now: Optional[float] = None) -> int:
        """Free uncommitted reservations whose TTL lapsed (the writer
        crashed without disconnecting — disconnect aborts them already).
        ``busy`` regions are skipped: an op is actively streaming into
        them and will commit or abort on its own.  Returns reservations
        reaped.  A late COMMIT_PUT of a reaped key answers INVALID_REQ,
        so an impossibly slow writer fails loudly, never silently."""
        if now is None:
            now = self._clock()
        expired = [k for k, e in self.pending.items()
                   if not e.busy and e.lease <= now]
        for key in expired:
            self._free(self.pending.pop(key))
        self.stats.reservations_reaped += len(expired)
        return len(expired)

    def _touch(self, key: bytes) -> None:
        self.kv.move_to_end(key)

    def usage(self) -> float:
        return self.mm.usage()

    def active_leases(self) -> int:
        """Committed entries under a live GET_DESC read lease (an shm
        client may still be memcpying from their regions).  Leased entries
        are skipped by the evictor and their frees deferred — the exact
        state behind PR 1's 'back-to-back runs fragment allocation' bench
        trap, now observable."""
        now = self._clock()
        return sum(1 for e in self.kv.values() if e.lease > now)

    def kvmap_len(self) -> int:
        return len(self.kv)

    # ---- eviction / pool growth ----

    def _evict_head(self, now: float) -> bool:
        """Evict the LRU head, or rotate past it when an shm reader holds
        its lease (False).  Every eviction path takes its victims here, so
        the order, the attribution and the spill are the same whoever asks."""
        key, e = next(iter(self.kv.items()))
        if e.lease > now:
            self.kv.move_to_end(key)
            return False
        del self.kv[key]
        self.analytics.on_evict(now - (e.last_access or now), e.hits == 0)
        self.usage_meter.on_evict(
            self._entry_accounts(e), e.account, e.size,
            never_read=e.hits == 0,
        )
        # spill before the blocks are reused: the entry is not leased
        # (checked above), so the bytes are stable
        if self._spill_entry(key, e):
            self.stats.spilled += 1
        self._free(e)
        self.stats.evicted += 1
        return True

    def _evict_lru(self, more: Callable[[int], bool]) -> int:
        """Evict LRU heads while ``more(evicted so far)``, until the map is
        empty or every entry left is leased.  Returns entries evicted."""
        now = self._clock()
        evicted = skipped = 0
        while self.kv and skipped < len(self.kv) and more(evicted):
            if self._evict_head(now):
                evicted += 1
            else:
                skipped += 1
        return evicted

    def evict(self, min_threshold: float, max_threshold: float) -> int:
        """One whole pass, as the reference makes it: the manage plane's
        and the periodic loop's call (an operator asked for it)."""
        # both reapers ride every evict pass: lapsed read leases free their
        # deferred blocks, lapsed reservations free leaked pending ones
        now = self._clock()
        self._reap_deferred(now)
        self.reap_pending(now)
        if self.mm.usage() < max_threshold:
            return 0
        return self._evict_lru(lambda _: self.mm.usage() >= min_threshold)

    def drain_step(self, max_entries: int = DRAIN_SLICE_ENTRIES) -> int:
        """One slice of the on-demand pass an allocation asked for at
        ``ON_DEMAND_MAX_THRESHOLD``: the same LRU heads ``evict`` would
        take, at most ``max_entries`` examined, so the server's loop
        answers requests between slices.  Clears ``draining`` once usage is
        under ``ON_DEMAND_MIN_THRESHOLD`` (or nothing evictable is left:
        every entry leased).  Returns entries evicted; a store that is not
        draining is left alone."""
        if not self.draining:
            return 0
        now = self._clock()
        evicted = 0
        for _ in range(max_entries):
            if not (self.mm.usage() >= ON_DEMAND_MIN_THRESHOLD and self.kv
                    and self._drain_skipped < len(self.kv)):
                self.draining = False
                break
            if self._evict_head(now):
                evicted += 1
            else:
                self._drain_skipped += 1
        self.stats.evicted_drain += evicted
        self.stats.drain_slices += 1
        return evicted

    def _evict_lacking(self, need: int) -> int:
        """An allocation's own eviction while the store drains: LRU heads
        until ``need`` bytes are free, and never past the drain's own end."""
        evicted = self._evict_lru(
            lambda _: self.mm.free_bytes() < need
            and self.mm.usage() >= ON_DEMAND_MIN_THRESHOLD)
        self.stats.evicted_inline += evicted
        return evicted

    def maybe_extend(self) -> bool:
        if self.config.auto_increase and self.mm.need_extend:
            self.mm.add_mempool()
            self.mm.need_extend = False
            return True
        return False

    def _pressure_evict(self, n: int = 8) -> int:
        """LRU pops that ignore the global usage gate.  The size-classed
        allocator can be FULL in one class while global usage looks low
        (the usage-threshold evict never fires), so allocation failure
        pops LRU entries directly — eventually reaching the full class's
        own entries — instead of answering OUT_OF_MEMORY while evictable
        data sits in the way.  Leased entries are skipped; spill-to-disk
        semantics match evict()."""
        evicted = self._evict_lru(lambda done: done < n)
        self.stats.evicted_inline += evicted
        return evicted

    # ---- spill tier: admission, demotion ----

    def _disk_admit(self, e: Entry) -> bool:
        """Disk admission gate, driven by the PR-4 eviction attribution:
        an entry that HAS been read always earns a slot; a never-read
        one is refused once the observed dead-on-arrival ratio says most
        writes here buy nothing — spilling those would just move the
        waste from DRAM to disk I/O."""
        if e.hits > 0:
            return True
        a = self.analytics
        total = a.dead_on_arrival + a.evicted_read
        if total < DISK_DOA_MIN_SAMPLES:
            return True  # not enough evidence to refuse anyone yet
        return a.dead_on_arrival / total < self.disk_doa_gate

    def _spill_entry(self, key: bytes, e: Entry) -> bool:
        """Write one committed entry's bytes to the spill tier (the
        caller frees the DRAM).  Reuses the stamped checksum when the
        integrity worker already computed it."""
        if self.disk is None or not self._disk_admit(e):
            return False
        crc = e.crc if e.crc is not None else self._checksum_entry(e)
        return self.disk.put(
            key, self.mm.view(e.pool_idx, e.offset, e.size), crc=crc,
            account=e.account,
        )

    def demote_step(self, max_entries: int = 8,
                    now: Optional[float] = None) -> int:
        """One bounded pass of ANALYTICS-DRIVEN demotion: move the
        coldest committed entries (age-band cold — untouched for
        ``demote_after_s``) to the spill tier and free their DRAM while
        the pool is above ``demote_watermark``, so pressure eviction
        finds room already made.  Runs ONLY from the background tier
        worker — never on the put critical path.  Returns entries
        demoted."""
        if self.disk is None or self.disk.degraded():
            return 0
        if now is None:
            now = self._clock()
        if self.mm.usage() < self.demote_watermark:
            return 0
        done = 0
        for key, e in list(self.kv.items()):  # LRU head first = coldest
            if done >= max_entries:
                break
            age = now - (e.last_access or e.created or now)
            if age < self.demote_after_s:
                break  # LRU order: everything behind is younger still
            if e.busy or e.lease > now:
                continue
            if not self._disk_admit(e):
                continue
            if not self._spill_entry(key, e):
                break  # tier refused (full / failing disk): stop the pass
            del self.kv[key]
            self.usage_meter.sub(self._entry_accounts(e), e.size, "dram")
            self._free(e)
            self.stats.demoted += 1
            done += 1
        return done

    def demote_all(self) -> int:
        """Demote EVERY committed, unleased entry and persist the
        manifest — the graceful pre-restart drain (``POST /spill``): a
        deploy that calls this hands its full prefix cache to the next
        boot."""
        if self.disk is None:
            return 0
        now = self._clock()
        done = 0
        for key, e in list(self.kv.items()):
            if e.busy or e.lease > now:
                continue
            crc = e.crc if e.crc is not None else self._checksum_entry(e)
            if not self.disk.put(
                key, self.mm.view(e.pool_idx, e.offset, e.size), crc=crc,
                account=e.account,
            ):
                continue
            del self.kv[key]
            self.usage_meter.sub(self._entry_accounts(e), e.size, "dram")
            self._free(e)
            self.stats.demoted += 1
            done += 1
        try:
            self.disk.save_manifest()
        except OSError:
            self.disk._io_failed()
        return done

    def compact_step(self, now: Optional[float] = None) -> int:
        """One paced background-compaction slide (tier-worker cadence):
        converts wall clock into a byte budget at ``compact_rate`` and
        hands it to the tier.  Returns spill-file bytes released."""
        if self.disk is None or self.compact_rate <= 0:
            return 0
        now = self._clock() if now is None else now
        last = self._compact_last_t
        self._compact_last_t = now
        if last is None:
            return 0  # first tick only arms the clock
        budget = int(self.compact_rate * min(max(now - last, 0.0), 1.0))
        if budget <= 0:
            return 0
        return self.disk.compact_step(self.compact_fill, budget)

    def list_keys(self, limit: int = 0) -> List[str]:
        """Every retrievable key, both tiers (wire OP_LIST_KEYS — the
        migration plane's enumeration primitive).  Bounded: 0 means the
        server-side cap."""
        cap = limit if 0 < limit < 100_000 else 100_000
        out: List[str] = []
        for k in self.kv:
            if len(out) >= cap:
                return out
            out.append(k.decode(errors="replace"))
        if self.disk is not None:
            for k in self.disk.index:
                if len(out) >= cap:
                    break
                if k not in self.kv:
                    out.append(k.decode(errors="replace"))
        return out

    def list_keys_sizes(self, limit: int = 0) -> List[list]:
        """``[[key, size], ...]`` across both tiers — the sized form of
        ``list_keys`` (LIST_KEYS_F_SIZES) that lets the migration plane
        batch descriptor reads by exact entry size.  Same cap rules."""
        cap = limit if 0 < limit < 100_000 else 100_000
        out: List[list] = []
        for k, e in self.kv.items():
            if len(out) >= cap:
                return out
            out.append([k.decode(errors="replace"), e.size])
        if self.disk is not None:
            for k, rec in self.disk.index.items():
                if len(out) >= cap:
                    break
                if k not in self.kv:
                    out.append([k.decode(errors="replace"), rec.size])
        return out

    def _allocate(self, size: int, n: int):
        """Allocate, with the on-demand eviction around it (+ auto-extend
        retry, + class-pressure eviction for the sizeclass allocator).

        At ``ON_DEMAND_MAX_THRESHOLD`` the store is marked DRAINING and the
        pass down to ``ON_DEMAND_MIN_THRESHOLD`` is left to ``drain_step``'s
        slices, between requests.  This call evicts only if it would fail
        while that drain is still under way, and then what it lacks: the
        pass itself took 82-199 ms of a 3 GiB pool inside one ALLOC_PUT.

        Batches (n > 1) first try ONE contiguous run so a batch put's
        descriptors coalesce into bulk memcpys client-side; a fragmented
        pool falls back to the per-region allocator, which only costs the
        batch its mergeability, never the allocation."""
        now = self._clock()
        self._reap_deferred(now)
        self.reap_pending(now)

        def _try_alloc():
            if n > 1:
                regions = self.mm.allocate_contiguous(size, n)
                if regions is not None:
                    self.stats.contig_batches += 1
                    return regions
            return self.mm.allocate(size, n)

        regions = _try_alloc()
        if (not self.draining
                and self.mm.usage() >= ON_DEMAND_MAX_THRESHOLD):
            # this allocation crossed the mark, or found the pool there (a
            # drain that ended on leases is asked again)
            self.draining = True
            self._drain_skipped = 0
        if regions is None and self.draining:
            # the drain has not come this far yet.  Free bytes are not a
            # free run: a round that frees what is lacking and still finds
            # no place frees one entry more each time after it
            t0 = time.perf_counter()
            need = n * self.mm.region_bytes(size)
            while regions is None and self._evict_lacking(
                    max(need, self.mm.free_bytes() + 1)):
                regions = _try_alloc()
            if self.evict_stall_sink is not None:
                self.evict_stall_sink(time.perf_counter() - t0)
        if regions is None and self.maybe_extend():
            regions = _try_alloc()
        if (regions is None and self.mm.allocator == "sizeclass"
                and self.mm.eviction_could_satisfy(size, n)):
            # the guard keeps one unsatisfiable request from draining
            # the whole cache through the loop and failing anyway
            while regions is None and self._pressure_evict() > 0:
                regions = self.mm.allocate(size, n)
        return regions

    # ---- ops ----

    def put_inline(self, key: bytes, data,
                   account: Optional[str] = None) -> int:
        size = len(data)
        regions = self._allocate(size, 1)
        if regions is None:
            return P.OUT_OF_MEMORY
        pool_idx, offset = regions[0]
        self.mm.view(pool_idx, offset, size)[:] = data
        self._insert_committed(key, Entry(pool_idx, offset, size,
                                          account=account))
        self.stats.puts += 1
        self.stats.bytes_in += size
        return P.FINISH

    def alloc_inline_dst(self, key: bytes, size: int,
                         account: Optional[str] = None) -> Optional[Entry]:
        """Allocate a region the server will stream an inline payload into."""
        regions = self._allocate(size, 1)
        if regions is None:
            return None
        pool_idx, offset = regions[0]
        # lease doubles as the reservation expiry while the entry is
        # pending (no read can lease an uncommitted key, so the field is
        # otherwise idle until commit resets it)
        e = Entry(pool_idx, offset, size,
                  lease=self._clock() + self.pending_ttl_s,
                  account=account)
        self.pending[key] = e
        return e

    def _promote(self, key: bytes) -> Optional[Entry]:
        """Pull a spilled entry back into a DRAM pool (the tier's read
        path): allocate (which may itself evict-and-spill colder keys),
        copy the bytes up, commit at the MRU end.  ``disk.get`` verifies
        the record's checksum first — a corrupt spill page is dropped
        and counted, and this answers None (a miss the engine serves by
        recompute), never bad KV.  Also None when the key isn't on disk
        or DRAM truly can't fit it."""
        if self.disk is None:
            return None
        rec = self.disk.index.get(key)
        data = self.disk.get(key)
        if data is None:
            return None
        regions = self._allocate(len(data), 1)
        if regions is None:
            return None
        pool_idx, offset = regions[0]
        self.mm.view(pool_idx, offset, len(data))[:] = data
        # the promoted entry keeps its spill record's owning account
        # (sharer sets don't persist across tiers; they rebuild on reads)
        e = Entry(pool_idx, offset, len(data),
                  account=rec.account if rec is not None else None)
        # _insert_committed drops the disk copy (its supersede rule)
        self._insert_committed(key, e)
        self.stats.promoted += 1
        return e

    def get_inline(self, key: bytes, account: Optional[str] = None):
        e = self.kv.get(key)
        if e is None:
            e = self._promote(key)
        if e is None:
            self.stats.misses += 1
            return None
        self._touch(key)
        self._record_hit(e)
        self._usage_read(e, account)
        self.stats.gets += 1
        self.stats.hits += 1
        self.stats.bytes_out += e.size
        return self.mm.view(e.pool_idx, e.offset, e.size)

    def _record_hit(self, e: Entry) -> None:
        """Reuse-distance attribution: seconds since this entry was last
        touched (commit counts as touch zero, so the first read measures
        commit -> read)."""
        now = self._clock()
        self.analytics.on_hit(now - (e.last_access or now))
        e.last_access = now
        e.hits += 1

    def _usage_read(self, e: Entry, account: Optional[str]) -> None:
        """Usage-ledger side of a read: count the hit to the reading
        account (the owner when the frame was untagged), and when a
        DIFFERENT account reads an entry, record it as a sharer — from
        then on the entry's byte·seconds split across the sharer set,
        so a shared system prompt is never double-billed."""
        m = self.usage_meter
        m.on_hit(account if account is not None else e.account)
        if account is None or account == e.account:
            return
        cur = e.sharers or []
        if account in cur:
            return
        if 1 + len(cur) >= SHARER_CAP:
            m.sharer_overflow += 1
            return
        before = self._entry_accounts(e)
        e.sharers = cur + [account]
        m.reshare(before, self._entry_accounts(e), e.size)

    def alloc_put(self, keys: Sequence[bytes], block_size: int,
                  account: Optional[str] = None):
        """Batched allocate for zero-copy writes.  Returns (status, descs)."""
        if len(set(keys)) != len(keys):
            return P.INVALID_REQ, []
        # another op is actively streaming into one of these keys: back off
        # rather than stomp its pending region
        if any((e := self.pending.get(k)) is not None and e.busy for k in keys):
            return P.RETRY, []
        regions = self._allocate(block_size, len(keys))
        if regions is None:
            return P.OUT_OF_MEMORY, []
        descs = []
        expiry = self._clock() + self.pending_ttl_s
        for key, (pool_idx, offset) in zip(keys, regions):
            old = self.pending.pop(key, None)
            if old is not None:
                self._free(old)
            # lease = reservation expiry while pending (see reap_pending);
            # the tagging account becomes the first-writer OWNER at commit
            self.pending[key] = Entry(pool_idx, offset, block_size,
                                      lease=expiry, account=account)
            descs.append((pool_idx, offset, block_size))
        return P.FINISH, descs

    def abort_put(self, keys: Sequence[bytes]) -> None:
        """Reclaim pending regions whose writer went away uncommitted."""
        for key in keys:
            e = self.pending.pop(key, None)
            if e is not None:
                self._free(e)

    def commit_put(self, keys: Sequence[bytes]) -> Tuple[int, int]:
        committed = 0
        for key in keys:
            e = self.pending.pop(key, None)
            if e is None:
                continue
            self._insert_committed(key, e)
            committed += 1
            self.stats.puts += 1
            self.stats.bytes_in += e.size
        status = P.FINISH if committed == len(keys) else P.INVALID_REQ
        return status, committed

    def _insert_committed(self, key: bytes, e: Entry) -> None:
        now = self._clock()
        e.created = e.last_access = now  # touch zero for reuse distances
        # while pending, lease held the reservation expiry; from commit on
        # it is a READ lease and must start clear (a stale reservation
        # stamp would make the evictor skip this entry for the whole TTL)
        e.lease = 0.0
        old = self.kv.pop(key, None)
        if old is not None:
            # overwrite: an shm reader may hold a live lease on the old
            # region; defer the free just like delete/purge do
            self.usage_meter.sub(self._entry_accounts(old), old.size,
                                 "dram")
            self._free_or_defer(old, now)
        self.usage_meter.on_commit(e.account, e.size)
        if self.disk is not None:
            # a fresh commit supersedes any spilled copy (stale data must
            # never promote back over it)
            self.disk.pop(key)
        self.kv[key] = e  # appended at MRU end
        if self.integrity != "off":
            # queue for checksum stamping; the integrity worker drains
            # this eagerly (stamp_pending), so commit latency never pays
            # the checksum pass
            self._unstamped.append((key, e))

    def get_desc(self, keys: Sequence[bytes], block_size: int = 0,
                 account: Optional[str] = None):
        """Batched descriptors for zero-copy reads.  404 if any key missing.

        Two passes on purpose: promoting a spilled batchmate allocates,
        which can evict — leasing each key the moment it checks out keeps
        the evictor's hands off earlier keys of the SAME batch, so the
        descriptors built in pass 2 can never go stale mid-request."""
        now = self._clock()
        for key in keys:
            e = self.kv.get(key)
            if e is None:
                # zero-copy reads hand out POOL offsets, so a spilled
                # entry must come back to DRAM before it can be served
                e = self._promote(key)
            if e is None:
                self.stats.misses += 1
                return P.KEY_NOT_FOUND, []
            if block_size and e.size > block_size:
                return P.INVALID_REQ, []
            if e.lease <= now:
                e.readers = 0  # previous lease window fully over
            e.readers += 1
            e.lease = now + READ_LEASE_S
        descs = []
        for key in keys:
            e = self.kv[key]
            self._touch(key)
            self._record_hit(e)
            self._usage_read(e, account)
            self.stats.gets += 1
            self.stats.hits += 1
            self.stats.bytes_out += e.size
            descs.append((e.pool_idx, e.offset, e.size))
        return P.FINISH, descs

    def release_desc(self, keys: Sequence[bytes]) -> int:
        """Explicit read-lease release (wire OP_RELEASE_DESC): a client
        whose copy verified has no further claim on the region.  Each
        release pays back one GET_DESC's reader count; the lease clears
        only at zero, so a LEGACY reader's concurrent timed lease is
        never cut short by a new client's release."""
        released = 0
        now = self._clock()
        for key in keys:
            e = self.kv.get(key)
            if e is None or e.lease <= now:
                continue
            if e.readers > 0:
                e.readers -= 1
            if e.readers == 0:
                e.lease = 0.0
                released += 1
        return released

    # ---- integrity: stamping, scrubbing, quarantine ----

    def _checksum_entry(self, e: Entry) -> int:
        return _checksum.checksum(
            self.mm.view(e.pool_idx, e.offset, e.size), self.checksum_alg
        )

    def stamp_pending(self, max_bytes: int = 4 << 20) -> int:
        """Drain (a bounded slice of) the commit-time stamping backlog.
        Returns entries stamped; 0 means the backlog is empty.  Bound is
        in BYTES so one call's pool pass stays small enough to interleave
        with data-plane ops.  Entries that were deleted/overwritten since
        commit are discarded by the identity re-check."""
        done = 0
        budget = max_bytes
        while self._unstamped and budget > 0:
            key, e = self._unstamped.popleft()
            if self.kv.get(key) is not e or e.crc is not None:
                continue
            crc = self._checksum_entry(e)
            if self.kv.get(key) is e:  # still bound after the pass
                e.crc = crc
                done += 1
            budget -= e.size
        return done

    def verify_entry(self, key: bytes, e: Entry) -> Optional[bool]:
        """Re-verify one committed entry.  None = unstamped (nothing to
        compare yet)."""
        if e.crc is None:
            return None
        return self._checksum_entry(e) == e.crc

    def quarantine(self, key: bytes) -> bool:
        """Corrupt entry containment: the key disappears immediately (a
        read must MISS, never serve bad bytes) and the blocks go through
        the existing deferred-release path in case an shm reader still
        holds a lease on them."""
        now = self._clock()
        e = self.kv.pop(key, None)
        if self.disk is not None:
            self.disk.pop(key)
        if e is None:
            return False
        self.usage_meter.sub(self._entry_accounts(e), e.size, "dram")
        self._free_or_defer(e, now)
        self.stats.scrub_corrupt += 1
        return True

    def scrub_step(self, max_entries: int = 32) -> Tuple[int, int]:
        """One bounded scrubber pass over committed, unleased entries:
        re-verify stamped checksums, quarantine mismatches, and stamp any
        entry the commit backlog missed (its first verification).  Walks
        a snapshot of the key space so concurrent commits/evictions
        between steps never skip or double-visit; returns
        (entries scanned, corrupt found)."""
        if not self._scrub_keys:
            self._scrub_keys = list(self.kv.keys())
        now = self._clock()
        scanned = corrupt = 0
        while self._scrub_keys and scanned < max_entries:
            key = self._scrub_keys.pop()
            e = self.kv.get(key)
            if e is None or e.busy or e.lease > now:
                continue  # gone, streaming, or under a live read lease
            scanned += 1
            if e.crc is None:
                e.crc = self._checksum_entry(e)
                continue
            if self._checksum_entry(e) != e.crc:
                self.quarantine(key)
                corrupt += 1
        self.stats.scrub_pages += scanned
        return scanned, corrupt

    def unverified_count(self) -> int:
        """Committed entries not yet stamped (the /debug/integrity view;
        O(n) — a debug read, not a data-path cost)."""
        return sum(1 for e in self.kv.values() if e.crc is None)

    def integrity_report(self) -> dict:
        """The /debug/integrity payload."""
        return {
            "level": self.integrity,
            "alg": _checksum.alg_name(self.checksum_alg),
            "epoch": self.epoch,
            "unverified": self.unverified_count(),
            "stamp_backlog": len(self._unstamped),
            "scrub_pages": self.stats.scrub_pages,
            "scrub_corrupt": self.stats.scrub_corrupt,
            "quarantined": self.stats.scrub_corrupt,
            "scrub_rate": self.scrub_rate,
        }

    def _present(self, key: bytes) -> bool:
        """Retrievable from EITHER tier — the presence notion exist and the
        prefix match advertise (a spilled entry still serves reads via
        promotion, so hiding it would break prefix reuse after pressure)."""
        return key in self.kv or (self.disk is not None and key in self.disk)

    def exist(self, key: bytes) -> bool:
        return self._present(key)

    def match_last_index(self, keys: Sequence[bytes]) -> int:
        left, right = 0, len(keys)
        while left < right:
            mid = (left + right) // 2
            if self._present(keys[mid]):
                left = mid + 1
            else:
                right = mid
        return left - 1

    def delete_keys(self, keys: Sequence[bytes]) -> int:
        count = 0
        now = self._clock()
        self._reap_deferred(now)
        for key in keys:
            e = self.kv.pop(key, None)
            on_disk = self.disk is not None and self.disk.pop(key)
            if e is not None:
                self.usage_meter.sub(self._entry_accounts(e), e.size,
                                     "dram")
                self._free_or_defer(e, now)
            if e is not None or on_disk:
                count += 1
        return count

    def purge(self) -> int:
        n = len(self.kv)
        now = self._clock()
        self._reap_deferred(now)
        for e in self.kv.values():
            self.usage_meter.sub(self._entry_accounts(e), e.size, "dram")
            self._free_or_defer(e, now)
        self.kv.clear()
        # keep regions an op is actively streaming into (their op will
        # commit or abort them); free the rest
        keep = {k: e for k, e in self.pending.items() if e.busy}
        for k, e in self.pending.items():
            if not e.busy:
                self._free(e)
        self.pending = keep
        if self.disk is not None:
            n += self.disk.clear()
        return n

    # point-in-time values in stats_dict(); everything else is monotonic.
    # Lives next to the schema so /metrics.prom's TYPE lines can't drift
    # from what stats_dict() actually returns.
    STATS_GAUGES = frozenset({
        "kvmap_len", "pending", "usage", "pools", "block_size",
        "disk_entries", "disk_bytes", "disk_degraded",
        "active_read_leases", "deferred_frees", "fragmentation",
        "free_bytes", "largest_free_run_bytes", "free_runs",
        "epoch", "stamp_backlog", "draining",
    })

    def cache_report(self, top_n: int = 10) -> dict:
        """The /debug/cache payload: hottest / coldest committed keys,
        occupancy by age band (seconds since last access), and the
        lifetime hit/miss/eviction attribution.  Built on demand by
        iterating the kv map — a debug endpoint, not a data-path cost."""
        now = self._clock()
        a = self.analytics
        entries = [(k, e) for k, e in self.kv.items()]
        bands = {label: {"entries": 0, "bytes": 0} for _, label in AGE_BANDS}
        for _k, e in entries:
            age = now - (e.last_access or now)
            for bound, label in AGE_BANDS:
                if age < bound or bound == float("inf"):
                    bands[label]["entries"] += 1
                    bands[label]["bytes"] += e.size
                    break

        def rec(k: bytes, e: Entry) -> dict:
            return {
                "key": k.decode(errors="replace"),
                "hits": e.hits,
                "size": e.size,
                "age_s": round(now - (e.last_access or now), 3),
                "since_commit_s": round(now - (e.created or now), 3),
            }

        hot = sorted(entries, key=lambda kv: kv[1].hits, reverse=True)
        cold = sorted(entries, key=lambda kv: kv[1].last_access or 0.0)
        gets = self.stats.hits + self.stats.misses
        disk = None
        if self.disk is not None:
            disk = self.disk.report()
            disk.update(spilled=self.stats.spilled,
                        demoted=self.stats.demoted,
                        promoted=self.stats.promoted)
        return {
            **({"disk": disk} if disk is not None else {}),
            "entries": len(self.kv),
            "bytes": sum(e.size for _k, e in entries),
            "usage": self.mm.usage(),
            "hits": self.stats.hits,
            "misses": self.stats.misses,
            "hit_ratio": round(self.stats.hits / gets, 4) if gets else 0.0,
            "evicted": self.stats.evicted,
            "dead_on_arrival": a.dead_on_arrival,
            "evicted_read": a.evicted_read,
            "mean_reuse_s": (round(a.reuse_total_s / a.reuse_count, 4)
                             if a.reuse_count else 0.0),
            "hot": [rec(k, e) for k, e in hot[:top_n]],
            "cold": [rec(k, e) for k, e in cold[:top_n]],
            "age_bands": bands,
        }

    def stats_dict(self) -> dict:
        s = self.stats
        d = {
            "kvmap_len": len(self.kv),
            "pending": len(self.pending),
            "usage": self.mm.usage(),
            "pools": len(self.mm.pools),
            "block_size": self.mm.block_size,
            "puts": s.puts,
            "gets": s.gets,
            "hits": s.hits,
            "misses": s.misses,
            "evicted": s.evicted,
            "evicted_drain": s.evicted_drain,
            "evicted_inline": s.evicted_inline,
            "drain_slices": s.drain_slices,
            "draining": int(self.draining),
            "bytes_in": s.bytes_in,
            "bytes_out": s.bytes_out,
            "contig_batches": s.contig_batches,
            "active_read_leases": self.active_leases(),
            "deferred_frees": len(self._deferred),
            "reservations_reaped": s.reservations_reaped,
            "dead_on_arrival": self.analytics.dead_on_arrival,
            "epoch": self.epoch,
            "stamp_backlog": len(self._unstamped),
            "scrub_pages": s.scrub_pages,
            "scrub_corrupt": s.scrub_corrupt,
        }
        d.update(self.mm.frag_stats())
        if self.disk is not None:
            d.update({
                "disk_entries": len(self.disk.index),
                "disk_bytes": self.disk.used_bytes(),
                "disk_spilled": s.spilled,
                "disk_demoted": s.demoted,
                "disk_promoted": s.promoted,
                "disk_dropped": self.disk.dropped,
                "disk_io_errors": self.disk.io_errors,
                "disk_verify_failures": self.disk.verify_failures,
                "disk_orphans_reaped": self.disk.orphans_reaped,
                "disk_warm_entries": self.disk.warm_entries,
                "disk_degraded": int(self.disk.degraded()),
            })
        return d

    def close(self) -> None:
        if self.disk is not None:
            self.disk.close()
        self.mm.close()
