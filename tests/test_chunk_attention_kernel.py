"""The TPU's dense prefill-chunk attention kernel
(models/chunk_attention_kernel.py), run on the CPU in Pallas' interpret mode
against the XLA form it replaces on the chip
(``attention._causal_attention_xla``, the oracle) and against the float32
per-head reference of ``tests/test_prefill_attention.py``.

What the chip's program relies on and the CPU cannot time: the kernel reads
the prefix buffer's LIVE rows and no others (the buffer past ``prefix_len``
is NaN here, the bucket's slack included), and a row's arithmetic does not
depend on the bucket (bit-equal under two buckets)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.engine import stepprof
from infinistore_tpu.models import attention
from infinistore_tpu.models.chunk_attention_kernel import (
    BLOCK,
    chunk_attention_kernel,
)
from infinistore_tpu.utils.metrics import MetricsRegistry
from test_paged_decode_kernel import _OnTpu
from test_prefill_attention import _reference

D = 128
GROUPS = [(4, 7), (8, 4), (2, 1)]       # (H_kv, G): the dense cells', and MHA
# (bucket, prefix_len): no prefix at all; an empty buffer; inside a block;
# on a block's edge; inside the last block; equal to the bucket
PREFIXES = [(0, None), (BLOCK, 0), (BLOCK, 200), (2 * BLOCK, BLOCK),
            (4 * BLOCK, 3 * BLOCK + 17), (8 * BLOCK, 8 * BLOCK)]


def _case(h_kv, group, cap, plen, sq=BLOCK, seed=0, batch=1):
    """q, K and V of a chunk of ``sq`` rows over a prefix buffer of ``cap``
    rows, and a second K and V whose buffer rows past ``plen`` are NaN."""
    rng = np.random.default_rng(seed)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    q = bf(rng.standard_normal((batch, sq, h_kv * group, D)))
    k, v = (rng.standard_normal((batch, cap + sq, h_kv, D)).astype(np.float32)
            for _ in range(2))
    dead = slice(plen or 0, cap)
    kn, vn = k.copy(), v.copy()
    kn[:, dead], vn[:, dead] = np.nan, np.nan
    return q, bf(k), bf(v), bf(kn), bf(vn)


def _forms(cap, plen, sq=BLOCK):
    """(kwargs of ``causal_attention``, the mask [sq, cap + sq])."""
    if plen is None:
        return {}, np.tril(np.ones((sq, sq), bool))
    row = np.arange(cap + sq)[None, :]
    i = np.arange(sq)[:, None]
    mask = np.where(row < cap, row < plen, row - cap <= i)
    return {"q_offset": cap, "prefix_pad": cap,
            "prefix_len": jnp.asarray(plen, jnp.int32)}, mask


def _kernel(q, k, v, cap, plen):
    n = None if plen is None else jnp.asarray(plen, jnp.int32)
    return np.asarray(chunk_attention_kernel(q, k, v, cap, n, interpret=True),
                      np.float32)


@pytest.mark.parametrize("cap,plen", PREFIXES)
@pytest.mark.parametrize("h_kv,group", GROUPS)
def test_kernel_reads_live_rows_only_and_agrees_with_the_xla_form(
        h_kv, group, cap, plen):
    q, k, v, k_nan, v_nan = _case(h_kv, group, cap, plen)
    kw, mask = _forms(cap, plen)
    want = np.asarray(attention._causal_attention_xla(
        q, k, v, kw.pop("prefix_len", None), **kw), np.float32)
    got = _kernel(q, k_nan, v_nan, cap, plen)
    assert got.shape == want.shape and np.isfinite(got).all()
    # bf16 rounding of values of order 1: the XLA form rounds its scores to
    # bf16 before the softmax, the kernel keeps them in float32
    np.testing.assert_allclose(got, want, atol=2e-2)
    # and to the float32 arithmetic of the same bf16 rows, closer
    exact = _reference(q, k, v, mask, None)
    np.testing.assert_allclose(got, exact, atol=1e-2)
    assert np.abs(got - exact).max() <= np.abs(want - exact).max() + 4e-3


@pytest.mark.parametrize("h_kv,group", GROUPS)
def test_a_chunk_is_bit_equal_under_two_buckets(h_kv, group):
    """The same chunk over a 2,048 and over a 4,096 bucket with equal
    ``prefix_len``: the live blocks and their order are the same, so the
    output is, to the bit (the paired probes of the benchmark rest on it)."""
    plen = 3 * BLOCK + 40
    q, k, v, _, _ = _case(h_kv, group, 4 * BLOCK, plen, seed=3)
    wide = lambda x: jnp.concatenate(
        [x[:, :4 * BLOCK], jnp.full_like(x[:, :4 * BLOCK], jnp.nan),
         x[:, 4 * BLOCK:]], axis=1)
    narrow = _kernel(q, k, v, 4 * BLOCK, plen)
    assert np.isfinite(narrow).all()
    assert (narrow == _kernel(q, wide(k), wide(v), 8 * BLOCK, plen)).all()


def test_query_blocks_and_rows_of_a_batch_each_see_their_own_keys():
    """Two rows of a batch, each a chunk of two query blocks over one prefix
    length: a block's diagonal is its own, the block before it is whole, and
    each row is what it is alone."""
    cap, plen, sq = 2 * BLOCK, BLOCK + 5, 2 * BLOCK
    q, k, v, k_nan, v_nan = _case(2, 2, cap, plen, sq=sq, batch=2, seed=5)
    kw, mask = _forms(cap, plen, sq)
    got = _kernel(q, k_nan, v_nan, cap, plen)
    np.testing.assert_allclose(got, _reference(q, k, v, mask, None), atol=1e-2)
    for b in range(2):
        alone = _kernel(q[b:b + 1], k_nan[b:b + 1], v_nan[b:b + 1], cap, plen)
        assert (alone[0] == got[b]).all()
    whole = _kernel(q, k[:, cap:], v[:, cap:], 0, None)
    np.testing.assert_allclose(
        whole, _reference(q, k[:, cap:], v[:, cap:],
                          np.tril(np.ones((sq, sq), bool)), None), atol=1e-2)


def _q(dtype=jnp.bfloat16, rows=BLOCK, heads=28, width=D):
    return jax.ShapeDtypeStruct((1, rows, heads, width), dtype)


def _kv(dtype=jnp.bfloat16, rows=4 * BLOCK + BLOCK, heads=4, width=D):
    return jax.ShapeDtypeStruct((1, rows, heads, width), dtype)


_PADDED = {"q_offset": 4 * BLOCK, "prefix_pad": 4 * BLOCK,
           "prefix_len": jax.ShapeDtypeStruct((), jnp.int32)}


@pytest.mark.parametrize("q,k,v,kwargs,engages", [
    (_q(), _kv(), _kv(), _PADDED, True),
    (_q(heads=32), _kv(heads=8), _kv(heads=8), _PADDED, True),
    (_q(), _kv(rows=BLOCK), _kv(rows=BLOCK), {}, True),     # a first chunk
    (_q(rows=2 * BLOCK), _kv(rows=2 * BLOCK), _kv(rows=2 * BLOCK), {}, True),
    (_q(), _kv(), _kv(), {**_PADDED, "window": 4096}, False),   # Mistral
    (_q(), _kv(), _kv(), {**_PADDED, "softcap": 50.0}, False),  # Gemma-2
    (_q(jnp.float32), _kv(jnp.float32), _kv(jnp.float32), _PADDED, False),
    (_q(), _kv(jnp.float32), _kv(jnp.float32), _PADDED, False),
    (_q(width=64), _kv(width=64), _kv(width=64), _PADDED, False),
    # latent attention, expanded: keys of 192, values of 128
    (_q(width=192), _kv(width=192), _kv(), _PADDED, False),
    (_q(heads=30), _kv(), _kv(), _PADDED, False),
    # a re-ask's tail: 64-256 rows, and a chunk over an exact prefix
    (_q(rows=256), _kv(rows=4 * BLOCK + 256), _kv(rows=4 * BLOCK + 256),
     _PADDED, False),
    (_q(rows=256), _kv(rows=256), _kv(rows=256), {}, False),
    (_q(), _kv(), _kv(), {"q_offset": 4 * BLOCK}, False),
    (_q(), _kv(rows=BLOCK + 48), _kv(rows=BLOCK + 48),
     {**_PADDED, "q_offset": 48, "prefix_pad": 48}, False),
], ids=["qwen2.5", "qwen3", "first-chunk", "two-query-blocks", "window",
        "softcap", "float32", "float32-keys", "64-wide-head", "latent-expanded",
        "ragged-groups", "short-last-chunk", "short-prompt", "exact-prefix",
        "a-buffer-that-is-no-bucket"])
def test_the_kernel_is_offered_by_shapes_and_dtypes_alone(q, k, v, kwargs, engages):
    assert attention.chunk_kernel_engages(q, k, v, **kwargs) is engages


def test_under_a_named_mesh_the_xla_form_stays():
    mesh = lambda **axes: jax.sharding.use_abstract_mesh(
        jax.sharding.AbstractMesh(tuple(axes.values()), tuple(axes)))
    with mesh(dp=1, tp=1):
        assert attention.chunk_kernel_engages(_q(), _kv(), _kv(), **_PADDED)
    with mesh(dp=1, tp=4):
        assert not attention.chunk_kernel_engages(_q(), _kv(), _kv(), **_PADDED)
    with mesh(dp=2, tp=1):
        assert not attention.chunk_kernel_engages(_q(), _kv(), _kv(), **_PADDED)


@pytest.mark.parametrize("cap,plen", [(0, None), (2 * BLOCK, BLOCK + 9)])
def test_on_the_cpu_the_program_holds_the_xla_form_and_no_kernel(cap, plen):
    """``causal_attention`` offers the kernel to a TPU's lowering only: the
    CPU's program is the XLA form to the bit, and holds no custom call."""
    q, k, v, _, _ = _case(2, 2, cap, plen)
    kw, _ = _forms(cap, plen)
    assert attention.chunk_kernel_engages(q, k, v, **kw)
    n = kw.pop("prefix_len", None)
    fn = jax.jit(lambda q, k, v, n: attention.causal_attention(
        q, k, v, prefix_len=n, **kw))
    want = attention._causal_attention_xla(q, k, v, n, **kw)
    assert (np.asarray(fn(q, k, v, n), np.float32)
            == np.asarray(want, np.float32)).all()
    text = fn.lower(q, k, v, n).as_text()
    assert "custom_call" not in text and "dot_general" in text


@pytest.mark.parametrize("cap,plen", [(0, None), (2 * BLOCK, BLOCK + 9)])
def test_a_program_that_can_hold_the_kernel_is_differentiated_as_the_xla_form(
        cap, plen):
    """A Pallas call has no derivative, and every branch of the choice is
    differentiated wherever the program is lowered: a train step through
    ``prefill_forward`` at a chunk's shapes gets the XLA form's gradient."""
    q, k, v, _, _ = _case(2, 2, cap, plen)
    kw, _ = _forms(cap, plen)
    n = kw.pop("prefix_len", None)
    loss = lambda form: lambda q, k, v: form(q, k, v).astype(jnp.float32).sum()
    got = jax.jit(jax.grad(loss(lambda q, k, v: attention.causal_attention(
        q, k, v, prefix_len=n, **kw)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: attention._causal_attention_xla(
        q, k, v, n, **kw)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and (
            np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_a_differentiated_program_runs_the_xla_form_forward_too():
    """Under differentiation the kernel's branch IS the XLA form, forward
    pass and backward: the loss and its gradient come from one set of scores
    and the forward is not computed twice (a stand-in kernel that returns
    zeros shows which form ran)."""
    q, k, v, _, _ = _case(2, 2, 0, None, sq=128)
    xla = lambda q, k, v: attention._causal_attention_xla(q, k, v, None)
    f = attention._with_derivative_of(xla, lambda q, k, v: jnp.zeros_like(q))
    loss = lambda form: lambda q, k, v: form(q, k, v).astype(jnp.float32).sum()
    assert not np.asarray(f(q, k, v), np.float32).any()       # not differentiated
    got, g = jax.value_and_grad(loss(f), argnums=(0, 1, 2))(q, k, v)
    want, gw = jax.value_and_grad(loss(xla), argnums=(0, 1, 2))(q, k, v)
    assert float(got) == float(want) != 0.0
    for a, b in zip(g, gw):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()
    text = str(jax.make_jaxpr(jax.grad(loss(f)))(q, k, v))
    assert text.count("exp") == str(jax.make_jaxpr(jax.grad(loss(xla)))(
        q, k, v)).count("exp")


@pytest.mark.parametrize("attn_kernel,chunks", [(True, 3), (False, 0)])
def test_attn_kernel_chunks_counts_the_chunks_of_a_kernel_program(
        attn_kernel, chunks):
    registry = MetricsRegistry()
    prof = stepprof.StepProfiler(metrics=registry, sample=10**9)
    with prof.step():
        for head in (False, False, True):
            stepprof.note_prefill_chunk(head=head, attn_kernel=attn_kernel)
    p = prof.summary()["prefill"]
    assert (p["chunks"], p["head_chunks"], p["attn_kernel_chunks"]) == (
        3, 1, chunks)
    record = prof.snapshot(1)["records"][0]
    assert record["prefill"]["attn_kernel_chunks"] == chunks
    assert (registry.family_value(      # no sample before the first chunk
        "istpu_engine_prefill_attn_kernel_chunks_total") or 0) == chunks


@pytest.mark.parametrize("cfg_kwargs,dtype,rows,cap,engaged", [
    ({}, jnp.bfloat16, BLOCK, 4 * BLOCK, True),
    ({}, jnp.bfloat16, BLOCK, 0, True),                      # a first chunk
    ({}, jnp.bfloat16, 256, 4 * BLOCK, False),               # a short last chunk
    ({}, jnp.bfloat16, BLOCK, None, False),                  # an exact prefix
    ({"sliding_window": 64}, jnp.bfloat16, BLOCK, 4 * BLOCK, False),
    ({"sliding_window": 64, "window_pattern": 2}, jnp.bfloat16, BLOCK,
     4 * BLOCK, True),
    ({"attn_softcap": 30.0}, jnp.bfloat16, BLOCK, 4 * BLOCK, False),
    ({}, jnp.float32, BLOCK, 4 * BLOCK, False),
], ids=["dense", "first-chunk", "short-chunk", "exact-prefix", "mistral",
        "alternating", "softcap", "float32"])
def test_the_engine_counts_kernel_chunks_by_the_attentions_own_test(
        cfg_kwargs, dtype, rows, cap, engaged, monkeypatch):
    """``prefill.attn_kernel_chunks`` is the engine's reading of the test
    ``causal_attention`` makes at lowering, on the chunk's own shapes.  On
    the CPU it is 0 for every model; with the cache on a TPU it follows the
    model's layers (one that attends to every live key is enough), the
    chunk's rows, its prefix buffer and the dtype."""
    from infinistore_tpu import models
    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig

    cfg = models.scaled(models.TINY, head_dim_override=128, dtype=dtype,
                        **cfg_kwargs)
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=8, block_tokens=16, dtype=dtype)
    eng = InferenceEngine(models.init_params(cfg, jax.random.PRNGKey(0)), cfg, pc)
    kw = {"tokens": jax.ShapeDtypeStruct((1, rows), jnp.int32)}
    if cap is None:          # the ``single`` form: an exact, unpadded prefix
        kw["prefix_kv"] = jax.ShapeDtypeStruct(
            (cfg.n_layers, 2, 1, 48, cfg.n_kv_heads, 128), dtype)
    elif cap:
        kw["prefix_kv"] = jax.ShapeDtypeStruct(
            (cfg.n_layers, 2, 1, cap, cfg.n_kv_heads, 128), dtype)
        kw["prefix_len"] = jax.ShapeDtypeStruct((), jnp.int32)
    assert eng._chunk_attention_in_kernel(**kw) is False     # the CPU's program
    monkeypatch.setattr(eng, "cache", _OnTpu(eng.cache))
    assert eng._chunk_attention_in_kernel(**kw) is engaged


def test_a_served_prompt_of_three_chunks_counts_its_chunks_and_no_kernel_on_the_cpu():
    """Through the engine: three chunks of a chunked prefill are counted
    (``prefill.chunks``), and on the CPU none of them as a kernel's."""
    from infinistore_tpu import models
    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig

    cfg = models.TINY
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=16, block_tokens=16, dtype=cfg.dtype)
    eng = InferenceEngine(models.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                          pc, prefill_chunk=32)
    prof = stepprof.StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    with prof.step():
        eng.prefill(list(range(1, 81)))
    p = prof.summary()["prefill"]
    assert (p["chunks"], p["attn_kernel_chunks"]) == (3, 0)
