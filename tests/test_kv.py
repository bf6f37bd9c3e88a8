"""KV layer: hashing, paged cache ops, and HBM<->store transfer."""

import os
import signal
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import infinistore_tpu as ist
from infinistore_tpu.kv import (
    BlockAllocator,
    KVTransferEngine,
    PagedCacheConfig,
    chunk_keys,
    init_cache,
    layer_key,
    matched_token_count,
    read_pages,
    write_pages,
)


# ---- hashing ----

def test_chunk_keys_prefix_property():
    t1 = list(range(64))
    t2 = list(range(48)) + [999] * 16
    k1 = chunk_keys(t1, "llama3-8b")
    k2 = chunk_keys(t2, "llama3-8b")
    assert len(k1) == 4
    assert k1[:3] == k2[:3]  # shared 48-token prefix -> same first 3 keys
    assert k1[3] != k2[3]


def test_chunk_keys_prefix_commitment():
    # same chunk content, different prefix -> different key
    a = chunk_keys([1] * 16 + [2] * 16, "m")
    b = chunk_keys([3] * 16 + [2] * 16, "m")
    assert a[1] != b[1]


def test_chunk_keys_incomplete_tail():
    assert len(chunk_keys(list(range(31)), "m")) == 1
    assert len(chunk_keys(list(range(15)), "m")) == 0


def test_model_id_separation():
    a = chunk_keys(list(range(16)), "model-a")
    b = chunk_keys(list(range(16)), "model-b")
    assert a[0] != b[0]


def test_layer_key_and_match_count():
    assert layer_key("m:abc", 3) == "m:abc#L3"
    assert matched_token_count(-1) == 0
    assert matched_token_count(2) == 48


# ---- paged cache ----

def test_page_roundtrip():
    pc = PagedCacheConfig(n_layers=2, n_kv_heads=2, head_dim=8, n_blocks=8, block_tokens=4, dtype=jnp.float32)
    cache = init_cache(pc)
    # pages: [L, 2, H_kv, n, T, D]
    pages = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 2, 3, 4, 8), jnp.float32)
    ids = jnp.asarray([5, 1, 7], dtype=jnp.int32)
    cache = write_pages(cache, ids, pages)
    out = read_pages(cache, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pages))
    # untouched pages remain zero
    assert float(jnp.abs(cache[:, :, :, 0]).max()) == 0.0


def test_token_writes_land_in_their_slots():
    """write_token_kv rewrites whole pages (so the cache keeps its layout
    on the chip): the result must be exactly the token rows in their
    slots, an out-of-bounds pad row dropped, everything else untouched;
    write_tokens_kv puts several tokens into ONE page."""
    from infinistore_tpu.kv import write_token_kv
    from infinistore_tpu.kv.cache import write_tokens_kv

    L, H, NB, T, D = 3, 2, 6, 4, 8
    rng = np.random.default_rng(0)
    base = rng.standard_normal((L, 2, H, NB, T, D)).astype(np.float32)
    k = rng.standard_normal((3, H, D)).astype(np.float32)
    v = rng.standard_normal((3, H, D)).astype(np.float32)
    blocks, slots = [4, 1, NB], [0, 3, 2]  # row 2: a pad row, one past the pool
    got = jax.jit(write_token_kv, static_argnums=1)(
        jnp.asarray(base), 1, jnp.asarray(blocks, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(k), jnp.asarray(v))
    want = base.copy()
    for b in range(2):
        want[1, 0, :, blocks[b], slots[b]] = k[b]
        want[1, 1, :, blocks[b], slots[b]] = v[b]
    np.testing.assert_array_equal(np.asarray(got), want)

    k2 = rng.standard_normal((1, 3, H, D)).astype(np.float32)
    v2 = rng.standard_normal((1, 3, H, D)).astype(np.float32)
    got = jax.jit(write_tokens_kv, static_argnums=1)(
        jnp.asarray(base), 2, jnp.asarray([[5, 5, 0]], jnp.int32),
        jnp.asarray([[2, 3, 0]], jnp.int32), jnp.asarray(k2), jnp.asarray(v2))
    want = base.copy()
    for s, (blk, slot) in enumerate([(5, 2), (5, 3), (0, 0)]):
        want[2, 0, :, blk, slot] = k2[0, s]
        want[2, 1, :, blk, slot] = v2[0, s]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_block_allocator():
    a = BlockAllocator(4)
    ids = a.alloc(3)
    assert len(set(ids)) == 3 and a.n_free == 1
    with pytest.raises(MemoryError):
        a.alloc(2)
    a.free(ids)
    assert a.n_free == 4


def test_page_bytes_llama8b_shape():
    pc = PagedCacheConfig(n_layers=32, n_kv_heads=8, head_dim=128, n_blocks=1, block_tokens=16)
    assert pc.page_bytes == 64 * 1024  # 2*16*8*128*2B


# ---- transfer through a live store ----

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
         "--backend", os.environ.get("ISTPU_TEST_BACKEND", "native")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.time() + 15
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


@pytest.fixture
def conn(server):
    config = ist.ClientConfig(
        host_addr="127.0.0.1", service_port=server, connection_type=ist.TYPE_SHM
    )
    c = ist.InfinityConnection(config)
    c.connect()
    yield c
    c.close()


def test_save_load_pages(conn):
    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16, dtype=jnp.float32
    )
    eng = KVTransferEngine(conn, pc)
    cache = init_cache(pc)
    pages = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 2, 2, 16, 16), jnp.float32)
    cache = write_pages(cache, jnp.asarray([0, 1]), pages)

    tokens = list(range(32))
    keys = chunk_keys(tokens, "tinymodel")
    nbytes = eng.save_pages(cache, [0, 1], keys)
    assert nbytes == 2 * 2 * pc.page_bytes  # layers x chunks

    # load into fresh pages of a fresh cache
    cache2 = init_cache(pc)
    cache2 = eng.load_pages(cache2, [4, 5], keys)
    out = read_pages(cache2, jnp.asarray([4, 5]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pages))


def test_lookup_prefix(conn):
    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16, dtype=jnp.float32
    )
    eng = KVTransferEngine(conn, pc)
    cache = init_cache(pc)
    pages = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 2, 3, 16, 16), jnp.float32)
    cache = write_pages(cache, jnp.asarray([0, 1, 2]), pages)

    tokens = list(range(77))  # 4 complete chunks... 77//16 = 4
    keys = chunk_keys(tokens, "m-lookup")
    # store only the first 3 chunks
    eng.save_pages(cache, [0, 1, 2], keys[:3])
    assert eng.lookup_prefix(keys) == 3
    assert eng.lookup_prefix(chunk_keys([9] * 32, "m-lookup")) == 0
    # a longer stored prefix than asked about
    assert eng.lookup_prefix(keys[:2]) == 2


def test_quantize_roundtrip_error():
    from infinistore_tpu.kv.quant import quantization_error

    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=4, block_tokens=8, dtype=jnp.bfloat16
    )
    pages = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 2, 2, 8, 16), jnp.bfloat16)
    abs_err, rel_err = quantization_error(pages, pc)
    # symmetric int8 vs per-head amax: worst case ~ (0.5/127 quantization
    # step) + bf16 round-off of the dequantized product
    assert rel_err < 0.02, (abs_err, rel_err)


def test_quantized_page_bytes():
    from infinistore_tpu.kv import page_quant_bytes

    pc = PagedCacheConfig(n_layers=32, n_kv_heads=8, head_dim=128, n_blocks=1, block_tokens=16)
    # 16 f32 scales + 32768 int8 values vs 65536 bf16 bytes: 2x minus epsilon
    assert page_quant_bytes(pc) == 2 * 8 * 4 + 2 * 8 * 16 * 128
    assert page_quant_bytes(pc) < pc.page_bytes // 2 + 256


def test_quantized_save_load_pages(conn):
    from infinistore_tpu.kv import dequantize_pages_jit, page_quant_bytes, quantize_pages

    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16, dtype=jnp.float32
    )
    eng = KVTransferEngine(conn, pc, quant="int8")
    cache = init_cache(pc)
    pages = jax.random.normal(jax.random.PRNGKey(4), (2, 2, 2, 2, 16, 16), jnp.float32)
    cache = write_pages(cache, jnp.asarray([0, 1]), pages)

    keys = chunk_keys(list(range(32)), "m-quant")
    nbytes = eng.save_pages(cache, [0, 1], keys)
    assert nbytes == 2 * 2 * page_quant_bytes(pc)  # half the bf16 bytes

    cache2 = init_cache(pc)
    cache2 = eng.load_pages(cache2, [4, 5], keys)
    out = read_pages(cache2, jnp.asarray([4, 5]))
    # the store hop must be exactly the local quantize round-trip...
    local = jnp.transpose(
        dequantize_pages_jit(
            quantize_pages(jnp.transpose(pages, (0, 3, 1, 2, 4, 5))), pc
        ),
        (0, 2, 3, 1, 4, 5),
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(local))
    # ...and close to the original values (per-head int8 error bound)
    np.testing.assert_allclose(np.asarray(out), np.asarray(pages), atol=0.05)


def test_quantized_namespace_isolation(conn):
    """int8 pages live under :q8 keys; a bf16 engine must never see them."""
    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16, dtype=jnp.float32
    )
    qeng = KVTransferEngine(conn, pc, quant="int8")
    feng = KVTransferEngine(conn, pc)
    cache = init_cache(pc)
    pages = jax.random.normal(jax.random.PRNGKey(5), (2, 2, 2, 1, 16, 16), jnp.float32)
    cache = write_pages(cache, jnp.asarray([0]), pages)
    keys = chunk_keys(list(range(16)), "m-qns")
    qeng.save_pages(cache, [0], keys)
    assert qeng.lookup_prefix(keys) == 1
    assert feng.lookup_prefix(keys) == 0


@pytest.mark.parametrize("transport", ["shm", "tcp"])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_coalesced_vs_legacy_page_parity(server, transport, quant, monkeypatch):
    """Byte parity of the full KV save/load path across copy strategies:
    pages saved by the coalesced (pipelined) client and by the legacy
    per-page client must restore IDENTICAL page bytes, for both
    transports and both quant modes (the coalesced path must never change
    what lands in the pool or what comes back out of it)."""
    monkeypatch.setenv("ISTPU_CLIENT", "python")
    ctype = ist.TYPE_SHM if transport == "shm" else ist.TYPE_TCP

    def connect(coalesce):
        c = ist.InfinityConnection(ist.ClientConfig(
            host_addr="127.0.0.1", service_port=server,
            connection_type=ctype))
        c.connect()
        c.conn.coalesce = coalesce
        return c

    # same shapes as the save/load tests above so the jitted gather/
    # scatter/quant programs are cache hits, not fresh compiles
    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16,
        dtype=jnp.float32,
    )
    pages = jax.random.normal(
        jax.random.PRNGKey(7), (2, 2, 2, 2, 16, 16), jnp.float32
    )
    cache = init_cache(pc)
    cache = write_pages(cache, jnp.asarray([0, 1]), pages)
    restored = {}
    for wmode in (True, False):
        wc = connect(wmode)
        keys = chunk_keys(list(range(32)), f"m-par-{transport}-{quant}-{wmode}")
        KVTransferEngine(wc, pc, quant=quant).save_pages(cache, [0, 1], keys)
        wc.close()
        for rmode in (True, False):
            rc = connect(rmode)
            cache2 = KVTransferEngine(rc, pc, quant=quant).load_pages(
                init_cache(pc), [4, 5], keys
            )
            restored[(wmode, rmode)] = np.asarray(
                read_pages(cache2, jnp.asarray([4, 5]))
            )
            rc.close()
    ref = restored[(True, True)]
    for combo, out in restored.items():
        np.testing.assert_array_equal(ref, out, err_msg=str(combo))
    if quant is None:  # unquantized pages restore the exact source bytes
        np.testing.assert_array_equal(ref, np.asarray(pages))


def test_lookup_prefix_requires_all_layers(conn):
    """A chunk whose last layer is missing must not count as a hit."""
    pc = PagedCacheConfig(
        n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8, block_tokens=16, dtype=jnp.float32
    )
    eng = KVTransferEngine(conn, pc)
    keys = chunk_keys(list(range(16)), "m-partial")
    # write only layer 0 of chunk 0 by hand
    payload = np.zeros(pc.page_bytes, dtype=np.uint8)
    conn.conn.w_tcp_bytes(layer_key(keys[0], 0), payload.tobytes())
    assert eng.lookup_prefix(keys) == 0
