"""The paged attention reads a layer's pages by (layer, K|V, page) index out
of the whole cache (models/attention.py:gather_layer_kv).  It must give, bit
for bit, what ``cache[layer]`` followed by the page gather gave before, and
it must never form ``cache[layer]``: on the chip that slice is a copy of
the layer's slab in every layer of every step (tests/test_aot_tpu.py asks
the TPU compiler; these run on the CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import models
from infinistore_tpu.kv import PagedCacheConfig, init_cache
from infinistore_tpu.models import attention

L, LAYER, T, D, N_BLOCKS, PAGES = 3, 1, 4, 16, 24, 5

heads = pytest.mark.parametrize(
    "n_kv_heads,groups", [(8, 4), (4, 7), (2, 1)])
masks = pytest.mark.parametrize(
    "window,softcap", [(None, None), (6, None), (None, 30.0), (6, 30.0)])


def _sliced_gather(cache, layer, block_table):
    """The gather as it was written before: slice the layer, then index."""
    layer_cache = cache[layer]
    B, max_pages = block_table.shape
    Hkv, _, T, D = layer_cache.shape[1:]
    k = layer_cache[0][:, block_table]
    v = layer_cache[1][:, block_table]
    k = jnp.moveaxis(k, 0, 3).reshape(B, max_pages * T, Hkv, D)
    v = jnp.moveaxis(v, 0, 3).reshape(B, max_pages * T, Hkv, D)
    return k, v


def _paged(n_kv_heads, seed):
    """A three-layer cache and a table of three rows: two sequences on
    pages of their own and a pad row whose every id is out of bounds, as
    ``engine._block_table`` pads the batch."""
    rng = np.random.default_rng(seed)
    cache = jnp.asarray(
        rng.standard_normal((L, 2, n_kv_heads, N_BLOCKS, T, D)), jnp.bfloat16)
    table = np.full((3, PAGES), N_BLOCKS, np.int32)
    table[0] = rng.permutation(N_BLOCKS // 2)[:PAGES]
    table[1, :3] = N_BLOCKS // 2 + rng.permutation(N_BLOCKS // 2)[:3]
    table[1, 3:] = 0
    return rng, cache, jnp.asarray(table)


def _bits(x):
    return np.asarray(x).tobytes()


@heads
def test_indexed_gather_is_the_sliced_gather(n_kv_heads, groups):
    _, cache, table = _paged(n_kv_heads, seed=groups)
    got = attention.gather_layer_kv(cache, LAYER, table)
    want = _sliced_gather(cache, LAYER, table)
    for g, w in zip(got, want):
        assert g.shape == (3, PAGES * T, n_kv_heads, D)
        assert _bits(g) == _bits(w)
    # and it is not the neighbouring layers' pages
    assert _bits(got[0]) != _bits(_sliced_gather(cache, LAYER - 1, table)[0])


@heads
@masks
def test_decode_attention_bit_for_bit(n_kv_heads, groups, window, softcap,
                                      monkeypatch):
    rng, cache, table = _paged(n_kv_heads, seed=10 + groups)
    q = jnp.asarray(
        rng.standard_normal((3, n_kv_heads * groups, D)), jnp.bfloat16)
    lens = jnp.asarray([PAGES * T - 1, 2 * T + 1, 0], jnp.int32)

    def run():
        return jax.jit(
            lambda q, c: attention.paged_decode_attention(
                q, c, LAYER, table, lens, window=window, softcap=softcap)
        )(q, cache)

    got = run()
    monkeypatch.setattr(attention, "gather_layer_kv", _sliced_gather)
    want = run()
    assert np.isfinite(np.asarray(got[:2], np.float32)).all()
    assert _bits(got) == _bits(want)


@heads
@masks
def test_multitoken_attention_bit_for_bit(n_kv_heads, groups, window, softcap,
                                          monkeypatch):
    rng, cache, table = _paged(n_kv_heads, seed=20 + groups)
    S = 3
    q = jnp.asarray(
        rng.standard_normal((3, S, n_kv_heads * groups, D)), jnp.bfloat16)
    first = np.array([PAGES * T - S, 2 * T - 1, 0])
    positions = jnp.asarray(first[:, None] + np.arange(S), jnp.int32)

    def run():
        return jax.jit(
            lambda q, c: attention.paged_multitoken_attention_xla(
                q, c, LAYER, table, positions, window=window, softcap=softcap)
        )(q, cache)

    got = run()
    monkeypatch.setattr(attention, "gather_layer_kv", _sliced_gather)
    want = run()
    assert np.isfinite(np.asarray(got[:2], np.float32)).all()
    assert _bits(got) == _bits(want)


def _leaf_equations(jaxpr):
    """Every equation that calls no jaxpr of its own (jit, scan, custom_jvp
    and the like are walked into)."""
    for eqn in jaxpr.eqns:
        subs = [
            getattr(sub, "jaxpr", sub)
            for p in eqn.params.values()
            for sub in (p if isinstance(p, (list, tuple)) else (p,))
            if hasattr(getattr(sub, "jaxpr", sub), "eqns")
        ]
        if not subs:
            yield eqn
        for sub in subs:
            yield from _leaf_equations(sub)


@pytest.mark.parametrize("forward", ["decode_forward", "verify_forward"])
def test_forwards_only_gather_from_and_scatter_into_the_cache(forward):
    """In the traced decode and verify steps the whole cache is read by
    gathers and written by scatters, nothing else: no slice, no
    dynamic_slice, no squeeze takes it."""
    cfg = models.TINY
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=8, block_tokens=T, dtype=cfg.dtype)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_cache(pc))
    B = 2
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if forward == "decode_forward":
        fn = lambda p, c, tok, tab: models.decode_forward(
            p, cfg, tok, tok, c, tab, tok + 1, tok, tok)
        args = (params, cache, ints(B), ints(B, 2))
    else:
        fn = lambda p, c, tok, tab: models.verify_forward(
            p, cfg, tok, tok, c, tab, tok, tok)
        args = (params, cache, ints(B, 3), ints(B, 2))
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    readers = {
        e.primitive.name for e in _leaf_equations(jaxpr)
        if any(getattr(v.aval, "shape", None) == cache.shape for v in e.invars)
    }
    assert readers == {"gather", "scatter"}, readers
