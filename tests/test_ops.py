"""Pallas kernels vs XLA reference math (interpret mode on the CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.models.attention import paged_decode_attention_xla
from infinistore_tpu.ops import paged_decode_attention_pallas

# the XLA path reads layer LAYER out of a two-layer cache by index (a path
# that ignored the index would read the other layer's pages); the Pallas
# kernels take that layer's slice
LAYER = 1


def _setup(B, H, Hkv, D, T, n_blocks, max_pages, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    # serving layout [L, 2, H_kv, n_blocks, T, D]; a layer of it is the
    # kernel layout
    cache = jnp.asarray(rng.standard_normal((2, 2, Hkv, n_blocks, T, D)), dtype)
    # each sequence gets distinct pages; lengths straddle page boundaries
    table = np.zeros((B, max_pages), dtype=np.int32)
    lens = np.zeros((B,), dtype=np.int32)
    free = list(range(1, n_blocks))
    for b in range(B):
        n_tok = int(rng.integers(1, max_pages * T))
        n_pages = -(-n_tok // T)
        ids = [free.pop() for _ in range(n_pages)]
        table[b, :n_pages] = ids
        lens[b] = n_tok
    return q, cache, jnp.asarray(table), jnp.asarray(lens)


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel_matches_xla(n_rep, dtype):
    Hkv, D, T = 2, 128, 16
    B, max_pages, n_blocks = 3, 4, 16
    q, cache, table, lens = _setup(
        B, Hkv * n_rep, Hkv, D, T, n_blocks, max_pages, dtype=dtype
    )
    want = paged_decode_attention_xla(q, cache, LAYER, table, lens)
    got = paged_decode_attention_pallas(
        q, cache[LAYER], table, lens, interpret=True)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_paged_decode_kernel_single_token():
    # seq_len == 1: only the first slot of the first page is valid
    Hkv, D, T = 2, 128, 16
    q, cache, table, lens = _setup(1, 8, Hkv, D, T, 8, 2)
    lens = jnp.asarray([1], jnp.int32)
    want = paged_decode_attention_xla(q, cache, LAYER, table, lens)
    got = paged_decode_attention_pallas(
        q, cache[LAYER], table, lens, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=5e-6, atol=5e-6
    )


# ---- flash causal prefill kernel ----

from infinistore_tpu.models.attention import causal_attention  # noqa: E402
from infinistore_tpu.ops import flash_causal_attention_pallas  # noqa: E402


def _flash_setup(B, Sq, Sk, H, Hkv, D, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Sk, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Sk, Hkv, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_matches_xla(n_rep, dtype):
    B, S, Hkv, D = 2, 48, 2, 128  # S straddles block boundaries after padding
    q, k, v = _flash_setup(B, S, S, Hkv * n_rep, Hkv, D, dtype=dtype)
    want = causal_attention(q, k, v)
    got = flash_causal_attention_pallas(
        q, k, v, interpret=True, block_q=16, block_k=16
    )
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_prefill_chunked_offset():
    """Chunked prefill: queries at positions P..P+Sq-1 over prefix+self KV."""
    B, P, Sq, Hkv, D = 1, 24, 18, 2, 128
    q, k, v = _flash_setup(B, Sq, P + Sq, 4, Hkv, D, seed=3)
    want = causal_attention(q, k, v, q_offset=P)
    got = flash_causal_attention_pallas(
        q, k, v, q_offset=P, interpret=True, block_q=16, block_k=16
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_prefill_single_row():
    q, k, v = _flash_setup(1, 1, 1, 4, 2, 128, seed=5)
    want = causal_attention(q, k, v)
    got = flash_causal_attention_pallas(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_prefix_kernel_matches_xla():
    """Bucketed-prefix flash kernel vs the XLA padded-prefix mask path:
    valid prefix rows attended, slack masked, self causal."""
    B, Sq, Hkv, D = 1, 18, 2, 128
    prefix_pad = 32  # 2 k-blocks at block_k=16
    for plen in [5, 16, 31, 32]:
        rng = np.random.default_rng(plen)
        q = jnp.asarray(rng.standard_normal((B, Sq, 4, D)), jnp.float32)
        k = jnp.asarray(
            rng.standard_normal((B, prefix_pad + Sq, Hkv, D)), jnp.float32
        )
        v = jnp.asarray(
            rng.standard_normal((B, prefix_pad + Sq, Hkv, D)), jnp.float32
        )
        pl_arr = jnp.asarray(plen, jnp.int32)
        want = causal_attention(
            q, k, v, prefix_pad=prefix_pad, prefix_len=pl_arr
        )
        from infinistore_tpu.ops import flash_prefix_attention_pallas

        got = flash_prefix_attention_pallas(
            q, k, v, prefix_pad=prefix_pad, prefix_len=pl_arr,
            interpret=True, block_q=16, block_k=16,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"plen={plen}",
        )


def test_flash_prefix_kernel_bf16():
    B, Sq, Hkv, D = 2, 16, 2, 128
    prefix_pad = 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Sq, 8, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, prefix_pad + Sq, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, prefix_pad + Sq, Hkv, D)), jnp.bfloat16)
    pl_arr = jnp.asarray(9, jnp.int32)
    want = causal_attention(q, k, v, prefix_pad=prefix_pad, prefix_len=pl_arr)
    from infinistore_tpu.ops import flash_prefix_attention_pallas

    got = flash_prefix_attention_pallas(
        q, k, v, prefix_pad=prefix_pad, prefix_len=pl_arr,
        interpret=True, block_q=16, block_k=16,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


# ---- on-chip Mosaic acceptance (TPU-gated; VERDICT r2 weak #2 / next #9) ----
#
# Everything above runs the kernels in interpret mode on the CPU mesh; these
# run them through the REAL Mosaic compile path whenever hardware is
# reachable, so the shipped on-TPU default path is exercised by the suite,
# not first compiled in production.  Run with:
#   ISTPU_TEST_TPU=1 python -m pytest tests/test_ops.py -k on_tpu
# on a machine with a chip (chip_smoke.py does, and counts a skip as a
# failure).


def _on_tpu() -> bool:
    import os

    if not os.environ.get("ISTPU_TEST_TPU"):
        return False
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:  # noqa: BLE001 — no backend at all
        return False


requires_tpu = pytest.mark.skipif(
    not _on_tpu(), reason="needs real TPU (set ISTPU_TEST_TPU=1)"
)


@requires_tpu
def test_paged_decode_kernel_mosaic_on_tpu():
    """interpret=False: Mosaic must accept the paged-decode kernel and its
    output must match the XLA path at serving shapes (8B head config)."""
    Hkv, D, T = 8, 128, 16
    q, cache, table, lens = _setup(
        4, 32, Hkv, D, T, 64, 8, dtype=jnp.bfloat16
    )
    want = paged_decode_attention_xla(q, cache, LAYER, table, lens)
    got = paged_decode_attention_pallas(q, cache[LAYER], table, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


@requires_tpu
def test_flash_prefill_mosaic_on_tpu():
    B, S, Hkv, D = 1, 512, 8, 128
    q, k, v = _flash_setup(B, S, S, 32, Hkv, D, dtype=jnp.bfloat16)
    want = causal_attention(q, k, v)
    got = flash_causal_attention_pallas(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


@requires_tpu
def test_flash_prefix_kernel_mosaic_on_tpu():
    from infinistore_tpu.ops import flash_prefix_attention_pallas

    B, Sq, Hkv, D = 1, 128, 8, 128
    prefix_pad = 256
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Sq, 32, D)), jnp.bfloat16)
    k = jnp.asarray(
        rng.standard_normal((B, prefix_pad + Sq, Hkv, D)), jnp.bfloat16
    )
    v = jnp.asarray(
        rng.standard_normal((B, prefix_pad + Sq, Hkv, D)), jnp.bfloat16
    )
    pl_arr = jnp.asarray(200, jnp.int32)
    want = causal_attention(q, k, v, prefix_pad=prefix_pad, prefix_len=pl_arr)
    got = flash_prefix_attention_pallas(
        q, k, v, prefix_pad=prefix_pad, prefix_len=pl_arr
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_alllayers_decode_kernel_matches_per_layer():
    """The invocation-overhead instrument
    (paged_decode_attention_pallas_alllayers) must compute EXACTLY what L
    back-to-back single-layer kernel calls compute — it exists to vary
    only the invocation count (bench leg_invocation_overhead)."""
    from infinistore_tpu.ops.pallas_attention import (
        paged_decode_attention_pallas_alllayers,
    )

    L, Hkv, n_rep, D, T = 3, 2, 4, 128, 16
    B, max_pages, n_blocks = 2, 4, 16
    rng = np.random.default_rng(3)
    qs = jnp.asarray(rng.standard_normal((L, B, Hkv * n_rep, D)), jnp.float32)
    cache = jnp.asarray(
        rng.standard_normal((L, 2, Hkv, n_blocks, T, D)), jnp.float32
    )
    _, _, table, lens = _setup(
        B, Hkv * n_rep, Hkv, D, T, n_blocks, max_pages, seed=3
    )
    want = jnp.stack([
        paged_decode_attention_pallas(
            qs[l], cache[l], table, lens, interpret=True)
        for l in range(L)
    ])
    got = paged_decode_attention_pallas_alllayers(
        qs, cache, table, lens, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@requires_tpu
def test_alllayers_decode_kernel_mosaic_on_tpu():
    """interpret=False: Mosaic must accept the all-layers instrument
    kernel (the invocation-overhead experiment's fused side) and match
    L back-to-back single-layer kernel calls at serving shapes."""
    from infinistore_tpu.ops.pallas_attention import (
        paged_decode_attention_pallas_alllayers,
    )

    L, Hkv, D, T = 4, 8, 128, 16
    rng = np.random.default_rng(11)
    qs = jnp.asarray(
        rng.standard_normal((L, 4, 32, D)), jnp.bfloat16)
    cache = jnp.asarray(
        rng.standard_normal((L, 2, Hkv, 64, T, D)), jnp.bfloat16)
    _, _, table, lens = _setup(4, 32, Hkv, D, T, 64, 8, seed=11,
                               dtype=jnp.bfloat16)
    want = jnp.stack([
        paged_decode_attention_pallas(qs[l], cache[l], table, lens)
        for l in range(L)
    ])
    got = paged_decode_attention_pallas_alllayers(qs, cache, table, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )
