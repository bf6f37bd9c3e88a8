"""The paged readers (one-token decode, speculative verify) contract the
query BY GROUP against a layer's pages as gathered: q [.., H, D] is viewed
as [.., H_kv, G, D] and nothing of [B, S, H, D] is formed
(models/attention.py).  The reference here is the form that was removed:
keys and values repeated G times, then one einsum over all H heads.
tests/test_aot_tpu.py asks the TPU compiler that the repeat is gone from
the optimized program; these run on the CPU and hold the result.

Tolerance.  Both forms multiply the same bf16 operands and sum them in
float32; only the order of the sums may differ, and each result is then
rounded to bf16 (the scores once before the softmax, the output once).
One rounding apart is one bf16 ulp, 2**-8 to 2**-7 of the value; the
values here are O(1), so ``rtol = atol = 2**-7``.  A wrong pairing of
query and KV heads moves the result by O(1), a hundred times that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.models import attention

L, LAYER, T, D, N_BLOCKS, PAGES = 3, 1, 4, 16, 24, 5
ROWS = 4  # two sequences, the engine's pad row, a live table of length 0
TOL = dict(rtol=2.0 ** -7, atol=2.0 ** -7)

heads = pytest.mark.parametrize(
    "n_kv_heads,groups", [(8, 4), (4, 7), (2, 1), (1, 8)])
masks = pytest.mark.parametrize(
    "window,softcap", [(None, None), (6, None), (None, 30.0), (6, 30.0)])


def _pairings(n_kv_heads, groups):
    """The KV head of each query head: ``repeat_kv``'s pairing (h // G), and
    a wrong one (h % H_kv: what a [.., G, H_kv, D] view would give)."""
    h = np.arange(n_kv_heads * groups)
    return h // groups, h % n_kv_heads


def _repeated_reference(q, cache, table, key_mask, softcap, kv_head):
    """The removed form on a [B, S, H, D] query: every query head gets a
    copy of its KV head's keys and values ([B, S_max, H, D]), one einsum
    over H.  ``kv_head``: [H] ints; ``key_mask``: [B, S, S_max]."""
    k, v = attention.gather_layer_kv(cache, LAYER, table)
    k, v = k[:, :, kv_head], v[:, :, kv_head]
    logits = jnp.einsum("bshd,bkhd->bhsk", q, k).astype(jnp.float32)
    logits = logits * (1.0 / np.sqrt(D))
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(key_mask[:, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhsk,bkhd->bshd", probs.astype(v.dtype), v)


def _paged(n_kv_heads, seed):
    """A three-layer cache whose KV heads all differ, and a table of four
    rows: two sequences on pages of their own, a pad row whose every id is
    out of bounds (``engine._block_table``), and valid pages of length 0."""
    rng = np.random.default_rng(seed)
    cache = rng.standard_normal((L, 2, n_kv_heads, N_BLOCKS, T, D))
    # a head's values centre on its own index: swapping two heads shows
    cache[:, 1] += np.arange(n_kv_heads)[None, :, None, None, None]
    table = np.full((ROWS, PAGES), N_BLOCKS, np.int32)
    table[0] = rng.permutation(N_BLOCKS // 2)[:PAGES]
    table[1, :3] = N_BLOCKS // 2 + rng.permutation(N_BLOCKS // 2)[:3]
    table[1, 3:] = 0
    table[3] = rng.permutation(N_BLOCKS)[:PAGES]
    return rng, jnp.asarray(cache, jnp.bfloat16), jnp.asarray(table)


def _f32(x):
    return np.asarray(x, np.float32)


def _decode_mask(lens, window):
    pos = np.arange(PAGES * T)
    mask = pos[None, :] < lens[:, None]
    if window is not None:
        mask &= pos[None, :] >= lens[:, None] - window
    return jnp.asarray(mask[:, None, :])


def _verify_mask(positions, window):
    pos = np.arange(PAGES * T)
    mask = pos[None, None, :] <= positions[:, :, None]
    if window is not None:
        mask &= pos[None, None, :] > positions[:, :, None] - window
    return jnp.asarray(mask)


@heads
@masks
def test_decode_attention_is_the_repeated_form(n_kv_heads, groups, window,
                                               softcap):
    rng, cache, table = _paged(n_kv_heads, seed=30 + groups)
    H = n_kv_heads * groups
    q = jnp.asarray(rng.standard_normal((ROWS, H, D)), jnp.bfloat16)
    lens = np.array([PAGES * T - 1, 2 * T + 1, 0, 0], np.int32)
    got = jax.jit(
        lambda q, c: attention.paged_decode_attention(
            q, c, LAYER, table, jnp.asarray(lens), window=window,
            softcap=softcap)
    )(q, cache)
    assert got.shape == (ROWS, H, D) and got.dtype == q.dtype
    key_mask = _decode_mask(lens, window)
    by_head, interleaved = _pairings(n_kv_heads, groups)
    want = _repeated_reference(
        q[:, None], cache, table, key_mask, softcap, by_head)[:, 0]
    assert np.isfinite(_f32(got[:2])).all()
    # a row of length 0 has no key to attend to: NaN in both forms
    np.testing.assert_allclose(_f32(got), _f32(want), equal_nan=True, **TOL)
    if groups > 1 and n_kv_heads > 1:
        wrong = _repeated_reference(
            q[:, None], cache, table, key_mask, softcap, interleaved)[:, 0]
        assert not np.allclose(_f32(got[:2]), _f32(wrong[:2]), **TOL)


@heads
@masks
def test_multitoken_attention_is_the_repeated_form(n_kv_heads, groups, window,
                                                   softcap):
    rng, cache, table = _paged(n_kv_heads, seed=40 + groups)
    S, H = 3, n_kv_heads * groups
    q = jnp.asarray(rng.standard_normal((ROWS, S, H, D)), jnp.bfloat16)
    first = np.array([PAGES * T - S, 2 * T - 1, 0, 0])
    positions = (first[:, None] + np.arange(S)).astype(np.int32)
    got = jax.jit(
        lambda q, c: attention.paged_multitoken_attention_xla(
            q, c, LAYER, table, jnp.asarray(positions), window=window,
            softcap=softcap)
    )(q, cache)
    assert got.shape == (ROWS, S, H, D) and got.dtype == q.dtype
    key_mask = _verify_mask(positions, window)
    by_head, interleaved = _pairings(n_kv_heads, groups)
    want = _repeated_reference(q, cache, table, key_mask, softcap, by_head)
    # every query sees at least the key at its own position
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_allclose(_f32(got), _f32(want), **TOL)
    if groups > 1 and n_kv_heads > 1:
        wrong = _repeated_reference(
            q, cache, table, key_mask, softcap, interleaved)
        assert not np.allclose(_f32(got[:2]), _f32(wrong[:2]), **TOL)


@heads
def test_result_head_h_is_query_head_h_on_its_own_kv_head(n_kv_heads, groups):
    """Values constant per KV head (head j holds j + 1 everywhere): whatever
    the scores, query head h must read (h // G) + 1 (the probabilities sum
    to 1 to a bf16 rounding), in the decode reader and in every position of
    the verify reader."""
    rng, cache, table = _paged(n_kv_heads, seed=50 + groups)
    const = jnp.broadcast_to(
        jnp.arange(1, n_kv_heads + 1, dtype=jnp.bfloat16)[:, None, None, None],
        cache.shape[2:])
    cache = cache.at[:, 1].set(const)
    H = n_kv_heads * groups
    want = np.arange(H) // groups + 1.0
    q = jnp.asarray(rng.standard_normal((ROWS, 2, H, D)), jnp.bfloat16)
    lens = jnp.asarray([PAGES * T - 1, 2 * T + 1, 1, 1], jnp.int32)
    one = attention.paged_decode_attention(q[:, 0], cache, LAYER, table, lens)
    np.testing.assert_allclose(
        _f32(one), np.broadcast_to(want[None, :, None], one.shape), **TOL)
    positions = lens[:, None] - 1 + jnp.arange(2)[None, :]
    many = attention.paged_multitoken_attention_xla(
        q, cache, LAYER, table, positions)
    np.testing.assert_allclose(
        _f32(many), np.broadcast_to(want[None, None, :, None], many.shape),
        **TOL)
