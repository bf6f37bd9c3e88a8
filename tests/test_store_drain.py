"""The on-demand eviction as a drain: marked by the allocation that finds the
pool at 0.95, walked down to 0.8 in slices between requests, with the victims,
the order, the leases, the spill and the attribution of the one pass."""

import asyncio
import socket
import threading
import time

import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu import protocol as P
from infinistore_tpu.pyserver import StoreServer
from infinistore_tpu.store import (
    DRAIN_SLICE_ENTRIES,
    ON_DEMAND_MAX_THRESHOLD,
    ON_DEMAND_MIN_THRESHOLD,
)

from test_store_unit import make_store, make_tiered_store

BLOCK = 16 << 10
PUSH = 96  # one ALLOC_PUT of a push: 96 pages, one contiguous run


def push(s, i, n=PUSH):
    """One alloc_put + commit_put of ``n`` pages; returns (status, entries the
    alloc_put evicted)."""
    keys = [b"p%05d-%03d" % (i, j) for j in range(n)]
    before = s.stats.evicted
    st, descs = s.alloc_put(keys, BLOCK)
    evicted = s.stats.evicted - before
    if st == P.FINISH:
        assert len(descs) == n
        assert s.commit_put(keys) == (P.FINISH, n)
    return st, evicted


def drain(s, max_entries=DRAIN_SLICE_ENTRIES):
    slices = 0
    while s.draining:
        s.drain_step(max_entries)
        slices += 1
    return slices


def victims_of(s):
    """Record every evicted key, in order (each passes the spill test)."""
    seen = []
    spill = s._spill_entry

    def recording(key, e):
        seen.append(key)
        return spill(key, e)

    s._spill_entry = recording
    return seen


# ---- (a) no request pays for the pass ----


@pytest.mark.parametrize("drained_between", [False, True],
                         ids=["no-drain-task", "drain-between-pushes"])
def test_full_pool_no_alloc_put_evicts_more_than_it_allocates(drained_between):
    """A pool of 2048 pages held full by pushes of 96: the one pass took 15%
    of the pool (307 entries) inside one alloc_put.  Now an alloc_put takes
    what it lacks, and nothing at all where slices run between requests."""
    s = make_store(prealloc_mb=32, block_kb=16)
    worst = 0
    for i in range(120):
        st, evicted = push(s, i)
        assert st == P.FINISH
        worst = max(worst, evicted)
        if drained_between:
            drain(s)
    total = (32 << 20) // BLOCK
    assert s.stats.evicted > 0.15 * total  # the pool was full and turning over
    assert worst <= PUSH
    assert s.stats.evicted == s.stats.evicted_drain + s.stats.evicted_inline
    if drained_between:
        assert worst == 0 and s.stats.evicted_inline == 0
        assert s.stats.drain_slices >= s.stats.evicted_drain / DRAIN_SLICE_ENTRIES
        assert s.usage() < ON_DEMAND_MAX_THRESHOLD + PUSH / total
    else:
        assert s.stats.evicted_drain == 0 and s.stats.drain_slices == 0
        assert s.draining  # nobody walks it: every push frees its own room
    s.close()


def test_alloc_put_evicts_what_it_lacks_and_times_it():
    """A pool left at 100% with the drain behind: the next push frees its 96
    pages' worth, LRU head first, and the stall sink hears of it once."""
    s = make_store(prealloc_mb=8, block_kb=16)  # 512 pages
    stalls = []
    s.evict_stall_sink = stalls.append
    for i in range(5):
        assert push(s, i) == (P.FINISH, 0)
    assert push(s, 5, n=32) == (P.FINISH, 0)  # 512 of 512
    assert s.usage() == 1.0 and s.draining and not stalls
    st, evicted = push(s, 6)
    assert (st, evicted) == (P.FINISH, PUSH)
    assert len(stalls) == 1 and stalls[0] > 0
    assert not s.exist(b"p00000-095") and s.exist(b"p00001-000")
    assert s.stats.evicted_inline == PUSH
    s.close()


def test_request_larger_than_the_drain_may_free_is_refused():
    """What a request may take on its own path ends where the pass ends: at
    0.8.  (The one pass refused the same request after evicting to 0.8.)"""
    s = make_store(prealloc_mb=8, block_kb=16)
    for i in range(5):
        push(s, i)
    push(s, 5, n=32)
    st, _ = s.alloc_put([b"big-%d" % j for j in range(200)], BLOCK)
    assert st == P.OUT_OF_MEMORY
    assert s.usage() < ON_DEMAND_MIN_THRESHOLD  # it stopped at the floor
    assert s.usage() >= ON_DEMAND_MIN_THRESHOLD - 1 / 512
    s.close()


# ---- (b) the victims are the one pass's ----


def _fill_past_the_mark(s, lease=None):
    i = 0
    while s.usage() < ON_DEMAND_MAX_THRESHOLD:
        assert push(s, i, n=8) == (P.FINISH, 0)
        if i == 3:
            assert s.get_inline(b"p00001-004") is not None  # read: not DOA
        i += 1
    assert s.draining  # marked by the allocation that crossed 0.95
    if lease:
        st, _ = s.get_desc(lease)  # an shm reader holds these
        assert st == P.FINISH


@pytest.mark.parametrize("tiered", [False, True], ids=["dram", "disk-tier"])
@pytest.mark.parametrize("lease", [None, [b"p00000-000", b"p00000-005"]],
                         ids=["unleased", "leased-heads"])
@pytest.mark.parametrize("slice_entries", [1, 7, DRAIN_SLICE_ENTRIES])
def test_drain_evicts_the_one_pass_victims_in_order(tmp_path, tiered, lease,
                                                    slice_entries):
    def build(sub):
        if tiered:
            return make_tiered_store(tmp_path / sub, prealloc_mb=4,
                                     disk_slots=32)
        return make_store(prealloc_mb=4)

    one, sliced = build("one"), build("sliced")
    now = [100.0]
    for s in (one, sliced):
        s._clock = lambda: now[0]
        _fill_past_the_mark(s, lease)
    want, got = victims_of(one), victims_of(sliced)
    n = one.evict(ON_DEMAND_MIN_THRESHOLD, ON_DEMAND_MAX_THRESHOLD)
    slices = drain(sliced, slice_entries)

    assert n > 0 and got == want and len(want) == n
    assert slices >= n / slice_entries
    assert not sliced.draining
    assert sliced.usage() == one.usage() < ON_DEMAND_MIN_THRESHOLD
    assert list(sliced.kv) == list(one.kv)  # the LRU left behind, in order
    for key in lease or ():
        assert key not in want and sliced.exist(key)  # never under a reader
    assert sliced.stats.evicted == one.stats.evicted == n
    assert sliced.stats.evicted_drain == n and one.stats.evicted_drain == 0
    assert sliced.stats.spilled == one.stats.spilled
    a, b = sliced.analytics, one.analytics
    assert (a.dead_on_arrival, a.evicted_read) == (b.dead_on_arrival,
                                                   b.evicted_read)
    assert a.evicted_read == 1  # the one entry that was read
    assert sliced.usage_meter.evictions == one.usage_meter.evictions
    assert sliced.usage_meter.doa == one.usage_meter.doa
    if tiered:
        assert sliced.stats.spilled > 0
        assert list(sliced.disk.index) == list(one.disk.index)
        assert sliced.disk.dropped == one.disk.dropped
    one.close()
    sliced.close()


def test_requests_between_slices_leave_the_order_alone():
    """Pushes that arrive mid-drain go to the MRU end: the slices still take
    the oldest entries, in the order they were written."""
    s = make_store(prealloc_mb=4)  # 256 pages
    _fill_past_the_mark(s)
    written = list(s.kv)
    got = victims_of(s)
    i = 1000
    while s.draining:
        s.drain_step(5)
        assert push(s, i, n=2)[0] == P.FINISH
        i += 1
    assert got == written[:len(got)]
    assert s.stats.evicted_inline == 0
    s.close()


def test_every_entry_leased_ends_the_drain_with_nothing_freed():
    s = make_store(prealloc_mb=4)
    _fill_past_the_mark(s)
    assert s.get_desc(list(s.kv))[0] == P.FINISH
    assert drain(s, 16) >= len(s.kv) / 16
    assert s.stats.evicted == 0 and s.usage() >= ON_DEMAND_MAX_THRESHOLD
    # the next allocation asks again; the leases released, the drain frees
    s.release_desc(list(s.kv))
    assert push(s, 999, n=1) == (P.FINISH, 0)
    assert s.draining and drain(s) >= 1
    assert s.usage() < ON_DEMAND_MIN_THRESHOLD
    s.close()


# ---- (d) a pool under the mark is left alone ----


def test_pool_at_0_85_evicts_nothing():
    """The document cells: their pools fill to 0.78-0.87 and are read back."""
    s = make_store(prealloc_mb=32, block_kb=16)
    i = 0
    while s.usage() < 0.85:
        assert push(s, i, n=16) == (P.FINISH, 0)
        i += 1
    keys = list(s.kv)
    for k in range(50):  # reads and overwrites of what is there, at 0.85
        assert s.get_desc(keys[k * 8:k * 8 + 8])[0] == P.FINISH
        assert push(s, k % i, n=16) == (P.FINISH, 0)
    assert not s.draining and s.drain_step() == 0
    assert (s.stats.evicted, s.stats.evicted_drain, s.stats.evicted_inline) \
        == (0, 0, 0)
    assert all(s.exist(k) for k in keys)
    d = s.stats_dict()
    assert d["evicted"] == d["evicted_drain"] == d["evicted_inline"] == 0
    assert d["draining"] == 0
    s.close()


def test_operator_pass_is_still_one_pass():
    """evict(min, max) from the manage plane / the periodic loop: whole, now."""
    s = make_store(prealloc_mb=4)
    _fill_past_the_mark(s)
    n = s.evict(0.3, 0.4)
    assert n > 0 and s.usage() < 0.3
    assert s.stats.evicted == n and s.stats.evicted_drain == 0
    assert s.drain_step() == 0 and not s.draining  # nothing left to walk
    s.close()


# ---- (c) through the asyncio server and a real client ----


def _free_port():
    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        return sk.getsockname()[1]


class _Served:
    """A StoreServer over a small hand-built pool, on a loop of its own."""

    def __init__(self, store):
        from infinistore_tpu.config import ServerConfig

        self.port = _free_port()
        cfg = ServerConfig(service_port=self.port, manage_port=_free_port(),
                           prealloc_size=1, minimal_allocate_size=16)
        self.srv = StoreServer(cfg, store=store)
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        asyncio.run_coroutine_threadsafe(
            self.srv.start("127.0.0.1"), self.loop).result(10)

    def close(self):
        asyncio.run_coroutine_threadsafe(
            self.srv.close(), self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


def _until(cond, what, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def test_server_drains_in_slices_and_answers_between_them(monkeypatch):
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    s = make_store(prealloc_mb=8, block_kb=16)  # 512 pages
    gate = {"slices": 0}  # slices the server's task may still run
    step = s.drain_step

    def gated(max_entries=8):
        if gate["slices"] <= 0:
            return 0
        gate["slices"] -= 1
        return step(max_entries)

    s.drain_step = gated
    up = _Served(s)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=up.port,
        connection_type=ist.TYPE_SHM))
    conn.connect()
    try:
        n = 16
        src = np.arange(n * BLOCK // 4, dtype=np.float32)
        dst = np.zeros_like(src)

        def write(i):
            blocks = [(f"w{i:03d}-{j:02d}", j * BLOCK) for j in range(n)]
            conn.write_cache(blocks, BLOCK, src.ctypes.data)  # ALLOC_PUT
            return blocks

        i = 0
        while s.usage() < ON_DEMAND_MAX_THRESHOLD:
            write(i)
            i += 1
        assert s.draining and s.stats.evicted == 0  # marked, nothing paid
        assert s.usage() >= ON_DEMAND_MAX_THRESHOLD

        gate["slices"] = 3
        _until(lambda: s.stats.drain_slices == 3, "three slices")
        assert s.draining and s.stats.evicted_drain == 24
        assert s.usage() >= ON_DEMAND_MIN_THRESHOLD

        # mid-drain: an ALLOC_PUT and a GET_DESC are answered, no slice between
        blocks = write(i)
        conn.read_cache(blocks, BLOCK, dst.ctypes.data)  # GET_DESC
        np.testing.assert_array_equal(dst, src)
        assert s.draining and s.stats.drain_slices == 3
        assert s.stats.evicted_inline == 0  # there was room: nothing paid

        gate["slices"] = 10**6
        _until(lambda: not s.draining, "the drain's end")
        assert s.usage() < ON_DEMAND_MIN_THRESHOLD
        assert s.stats.drain_slices > 4
        assert s.stats.evicted == s.stats.evicted_drain > 24
        assert conn.check_exist(blocks[0][0])  # the MRU end stays
        assert not conn.check_exist("w000-00")  # the LRU head went

        text = up.srv.metrics_text()
        for line in (f"istpu_store_evicted_drain_total {s.stats.evicted}",
                     "istpu_store_evicted_inline_total 0",
                     f"istpu_store_drain_slices_total {s.stats.drain_slices}",
                     "# TYPE istpu_store_evict_stall_seconds histogram",
                     "infinistore_tpu_draining 0"):
            assert line in text, line
    finally:
        conn.close()
        up.close()


def test_server_counts_a_request_that_had_to_evict(monkeypatch):
    """The drain held back entirely: the pool runs to 100% and the ALLOC_PUT
    that lacks room frees it itself, counted and timed as a stall."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    s = make_store(prealloc_mb=8, block_kb=16)
    s.drain_step = lambda max_entries=0: 0
    up = _Served(s)
    conn = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=up.port,
        connection_type=ist.TYPE_SHM))
    conn.connect()
    try:
        n = 16
        src = np.ones(n * BLOCK // 4, dtype=np.float32)
        for i in range(40):  # 640 pages through a pool of 512
            conn.write_cache([(f"w{i:03d}-{j:02d}", j * BLOCK)
                              for j in range(n)], BLOCK, src.ctypes.data)
        assert s.stats.evicted_inline == s.stats.evicted == 8 * n
        hist = up.srv.metrics.family_hist("istpu_store_evict_stall_seconds")
        assert hist[0] == 8 and hist[1] > 0  # (count, seconds)
        assert "istpu_store_evicted_inline_total 128" in up.srv.metrics_text()
    finally:
        conn.close()
        up.close()
