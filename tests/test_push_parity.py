"""The alloc-first HBM→pool push path: byte parity vs the legacy path,
reservation-TTL semantics, negotiation fail-closed, and the staging-MR
leak fix.

The zero-copy push (descriptors learned BEFORE the payload exists, fill
straight into the mapped pool, commit off the critical path) must never
change a single byte of what lands in the store or what comes back out —
for both transports, both quant modes, with integrity verification ON
throughout (the loads below verify checksums end to end).
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
from infinistore_tpu import protocol as P

from test_store_unit import make_store  # same-rootdir import, see conftest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def server():
    port, mport = _free_port(), _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "infinistore_tpu.server",
         "--service-port", str(port), "--manage-port", str(mport),
         "--prealloc-size", "1", "--minimal-allocate-size", "16",
         "--log-level", "warning", "--backend", "python"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 25
    while time.time() < deadline:
        if proc.poll() is not None:
            pytest.fail("server failed to start")
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


def _connect(port, ctype=None):
    c = ist.InfinityConnection(ist.ClientConfig(
        host_addr="127.0.0.1", service_port=port,
        connection_type=ctype or ist.TYPE_SHM, log_level="warning"))
    c.connect()
    return c


# ---- wire negotiation ----

def test_alloc_trailer_roundtrip_and_legacy_tolerance():
    """The ALOC capability trailer parses regardless of which other
    trailers ride ahead of it, and a legacy (trailer-less) HELLO body
    answers None — negotiation fails closed."""
    pools = P.pack_pool_table([("p0", 1 << 20, 1 << 14)])
    assert P.unpack_hello_alloc(memoryview(pools)) is None
    body = pools + P.pack_alloc_trailer(42.5)
    assert P.unpack_hello_alloc(memoryview(body)) == 42.5
    # full trailer stack in server order: TRAC | EPOC | ALOC — each
    # parser finds its own block and legacy pool parsing is untouched
    body = (pools + P.pack_hello_trailer(1, 0.5)
            + P.pack_epoch_trailer(1, 99) + P.pack_alloc_trailer(7.0))
    assert P.unpack_pool_table(memoryview(body))[0][0] == "p0"
    assert P.unpack_hello_epoch(memoryview(body)) == (1, 99)
    assert P.unpack_hello_alloc(memoryview(body)) == 7.0
    # old servers answered TRAC+EPOC only: alloc negotiation fails closed
    body = pools + P.pack_hello_trailer(1, 0.5) + P.pack_epoch_trailer(1, 9)
    assert P.unpack_hello_alloc(memoryview(body)) is None


def test_hello_negotiates_alloc_first(server, monkeypatch):
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    conn = _connect(server)
    try:
        assert conn.conn.alloc_first is True
        assert conn.conn.reserve_ttl and conn.conn.reserve_ttl > 0
    finally:
        conn.close()


def test_alloc_first_env_optout(server, monkeypatch):
    """ISTPU_ALLOC_FIRST=0 keeps HELLO byte-identical to the pre-alloc-
    first client: no capability asked, none answered, pushes stage."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    monkeypatch.setenv("ISTPU_ALLOC_FIRST", "0")
    conn = _connect(server)
    try:
        assert conn.conn.alloc_first is False
        # the staged fallback still round-trips bytes correctly
        bs = 16 << 10
        payload = np.random.randint(0, 256, 4 * bs, dtype=np.uint8)
        blocks = [(f"optout-{i}", i * bs) for i in range(4)]
        info = conn.write_cache_into(
            [(blocks, bs, lambda dst: np.copyto(dst, payload))])
        assert info["zero_copy_bands"] == 0 and info["staged_bands"] == 1
        dst = np.zeros_like(payload)
        conn.read_cache(blocks, bs, dst.ctypes.data)
        np.testing.assert_array_equal(dst, payload)
    finally:
        conn.close()


# ---- write_cache_into semantics ----

def test_write_cache_into_zero_copy_and_parity(server, monkeypatch):
    """On a negotiated shm connection with a contiguous allocation, the
    fill target IS the pool (zero_copy_bands counts it) and a read gets
    the exact bytes back."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    conn = _connect(server)
    try:
        bs = 16 << 10
        n = 16
        payload = np.random.randint(0, 256, n * bs, dtype=np.uint8)
        seen = {}

        def fill(dst):
            # prove the destination is pool memory, not client scratch:
            # it must alias one of the mapped pools
            base = dst.__array_interface__["data"][0]
            seen["in_pool"] = any(
                p.arr.__array_interface__["data"][0] <= base
                < p.arr.__array_interface__["data"][0] + p.arr.nbytes
                for p in conn.conn.pools
            )
            np.copyto(dst, payload)

        blocks = [(f"zc-{i}", i * bs) for i in range(n)]
        info = conn.write_cache_into([(blocks, bs, fill)])
        assert info["zero_copy_bands"] == 1 and info["staged_bands"] == 0
        assert seen["in_pool"], "fill destination was not the mapped pool"
        dst = np.zeros_like(payload)
        conn.read_cache(blocks, bs, dst.ctypes.data)  # integrity verify on
        np.testing.assert_array_equal(dst, payload)
    finally:
        conn.close()


def test_write_cache_into_fragmented_falls_back_staged(server, monkeypatch):
    """Descs that can't merge to one run (block size under the server's
    allocation granularity leaves holes between payloads) degrade to ONE
    staged copy — correctness never depends on contiguity."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    conn = _connect(server)
    try:
        bs = 4 << 10  # below the 16 KiB min-allocate: pool offsets stride
        n = 6
        payload = np.random.randint(0, 256, n * bs, dtype=np.uint8)
        blocks = [(f"frag-{i}", i * bs) for i in range(n)]
        info = conn.write_cache_into(
            [(blocks, bs, lambda dst: np.copyto(dst, payload))])
        assert info["staged_bands"] == 1 and info["zero_copy_bands"] == 0
        # the second copy, scratch to pool, is a timed stage of its own
        assert 0 < info["copy_s"] and info["alloc_s"] > 0 < info["commit_s"]
        dst = np.zeros_like(payload)
        conn.read_cache(blocks, bs, dst.ctypes.data)
        np.testing.assert_array_equal(dst, payload)
    finally:
        conn.close()


# ---- the full KV push path: new vs legacy, both transports + quants ----

@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_push_path_parity_new_vs_legacy(server, transport, quant,
                                        monkeypatch):
    """Byte parity of the WHOLE save/load path across push strategies:
    pages pushed by the alloc-first path (zero-copy on shm, staging ring
    on TCP) and by the legacy pipelined path must restore IDENTICAL page
    bytes, with integrity verification on end to end."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.kv import (
        KVTransferEngine, PagedCacheConfig, chunk_keys, init_cache,
        read_pages, write_pages,
    )

    monkeypatch.setenv("ISTPU_CLIENT", "python")
    ctype = ist.TYPE_SHM if transport == "shm" else ist.TYPE_TCP
    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16,
        dtype=jnp.float32,
    )
    pages = jax.random.normal(
        jax.random.PRNGKey(11), (2, 2, 2, 2, 16, 16), jnp.float32
    )
    cache = init_cache(pc)
    cache = write_pages(cache, jnp.asarray([0, 1]), pages)
    restored = {}
    for mode in ("auto", "legacy"):
        wc = _connect(server, ctype)
        keys = chunk_keys(list(range(32)),
                          f"push-par-{transport}-{quant}-{mode}")
        eng = KVTransferEngine(wc, pc, quant=quant, push_mode=mode)
        eng.save_pages(cache, [0, 1], keys)
        if mode == "auto" and transport == "tcp":
            # the TCP push staged through the pinned ring, not the pool
            assert eng.last_push_stages["staged_bands"] >= 1
        cache2 = KVTransferEngine(wc, pc, quant=quant).load_pages(
            init_cache(pc), [4, 5], keys
        )
        restored[mode] = np.asarray(read_pages(cache2, jnp.asarray([4, 5])))
        wc.close()
    np.testing.assert_array_equal(restored["auto"], restored["legacy"])
    if quant is None:
        np.testing.assert_array_equal(restored["auto"], np.asarray(pages))


# ---- staging-MR leak (satellite) ----

def test_staging_growth_does_not_accumulate_mrs(server, monkeypatch):
    """Growing a staging buffer must RELEASE the replaced buffer's
    registration: N growths leave exactly the live buffers registered,
    not N dead entries replayed on every reconnect."""
    import jax.numpy as jnp

    from infinistore_tpu.kv import KVTransferEngine, PagedCacheConfig

    monkeypatch.setenv("ISTPU_CLIENT", "python")
    conn = _connect(server)
    try:
        pc = PagedCacheConfig(
            n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8,
            block_tokens=16, dtype=jnp.float32,
        )
        eng = KVTransferEngine(conn, pc)
        for nbytes in (1 << 10, 4 << 10, 16 << 10, 64 << 10, 256 << 10):
            eng._ensure_staging(nbytes)
            eng._ensure_staging(nbytes)  # both ring slots
            eng._ensure_push_staging(nbytes)
            eng._ensure_push_staging(nbytes)
        # live buffers: 2 load-staging slots + 2 push-ring slots
        assert len(conn._mrs) == 4, conn._mrs
        assert len(conn.conn._registered) == 4
        live = {buf.ctypes.data
                for buf in eng._staging + eng._push_staging}
        assert {p for p, _ in conn._mrs} == live
    finally:
        conn.close()


# ---- reservation TTL (store core) ----

def test_reservation_ttl_reaps_uncommitted(monkeypatch):
    """An allocated-but-uncommitted reservation outlives the TTL only
    until the next reap; the blocks return to the pool and a LATE commit
    answers INVALID_REQ (loud, not silent)."""
    s = make_store()
    now = [100.0]
    s._clock = lambda: now[0]
    s.pending_ttl_s = 5.0
    st, descs = s.alloc_put([b"a", b"b"], 16 << 10)
    assert st == P.FINISH and len(descs) == 2
    used0 = s.mm.usage()
    assert used0 > 0
    # inside the TTL: reap is a no-op, commit succeeds
    assert s.reap_pending() == 0
    now[0] += 6.0  # past the TTL
    assert s.reap_pending() == 2
    assert s.stats.reservations_reaped == 2
    assert not s.pending and s.mm.usage() == 0.0
    st, count = s.commit_put([b"a", b"b"])  # the late writer fails loudly
    assert st == P.INVALID_REQ and count == 0
    s.close()


def test_reservation_ttl_skips_busy_and_resets_on_commit():
    """``busy`` regions (an op is streaming into them) are never reaped,
    and commit clears the reservation stamp so the entry is immediately
    evictable/leasable like any committed entry."""
    s = make_store()
    now = [0.0]
    s._clock = lambda: now[0]
    s.pending_ttl_s = 5.0
    s.alloc_put([b"busy", b"idle"], 16 << 10)
    s.pending[b"busy"].busy = True
    now[0] += 10.0
    assert s.reap_pending() == 1  # idle reaped, busy kept
    assert b"busy" in s.pending and b"idle" not in s.pending
    s.pending[b"busy"].busy = False
    st, count = s.commit_put([b"busy"])
    assert st == P.FINISH and count == 1
    assert s.kv[b"busy"].lease == 0.0  # reservation stamp did not leak
    assert s.active_leases() == 0
    s.close()


def test_allocation_pressure_reaps_leaked_reservations():
    """A pool full of leaked reservations must still serve new puts: the
    on-demand reap inside the evict pass frees them before OOM."""
    s = make_store(prealloc_mb=1, block_kb=16)
    now = [0.0]
    s._clock = lambda: now[0]
    s.pending_ttl_s = 2.0
    # leak every block in the pool as uncommitted reservations
    n = (1 << 20) // (16 << 10)
    keys = [f"leak-{i}".encode() for i in range(n)]
    st, _ = s.alloc_put(keys, 16 << 10)
    assert st == P.FINISH
    st, _ = s.alloc_put([b"newcomer"], 16 << 10)
    assert st == P.OUT_OF_MEMORY  # pool genuinely full, TTL not lapsed
    now[0] += 3.0
    st, descs = s.alloc_put([b"newcomer"], 16 << 10)
    assert st == P.FINISH and len(descs) == 1
    assert s.stats.reservations_reaped == n
    s.close()
