"""The TPU's dense decode-attention kernel (models/paged_decode_kernel.py),
run on the CPU in Pallas' interpret mode against the XLA form it replaces on
the chip (``attention._paged_decode_attention_xla``, the oracle).

What the chip's program relies on and the CPU cannot time: the kernel reads
a row's LIVE pages and no others (every other page of the pool is NaN here),
a pad row reads nothing, and a row's arithmetic depends on that row alone
(bit-equal alone, in a batch of 32, under a table twice as wide: the paired
probes of the benchmark rest on it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.engine import stepprof
from infinistore_tpu.models import attention
from infinistore_tpu.utils.metrics import MetricsRegistry
from infinistore_tpu.models.paged_decode_kernel import (
    PAGES_PER_BLOCK,
    paged_decode_attention_kernel,
)

T, D, L, LAYER = 16, 128, 2, 1
WIDTH = 2 * PAGES_PER_BLOCK
# 1; one under and one over a page boundary; an exact multiple of the pages
# a block; one over it; the table's full width
LENGTHS = (1, T - 1, T + 1, PAGES_PER_BLOCK * T, PAGES_PER_BLOCK * T + 1,
           WIDTH * T)
GROUPS = [(8, 4), (4, 7), (8, 16)]       # (H_kv, G) of the benchmark's cells
BLOCK = PAGES_PER_BLOCK * T              # the tokens of a whole block
# what the kernel does by a block's fill (a WHOLE block's copies start in a
# static run and are awaited in one wait, a row's last block walks its own
# count): lengths a row, a pad row where None, the table's width in blocks
BLOCK_FILLS = {
    "one-whole-block": ((BLOCK,), 2),
    "two-whole-blocks": ((2 * BLOCK,), 2),
    "five-whole-blocks": ((5 * BLOCK,), 5),
    "a-whole-block-then-a-one-page-tail": ((BLOCK + 1, BLOCK + T), 2),
    "a-short-row-beside-a-long-one": ((3 * T + 2, 3 * BLOCK + 5 * T), 4),
    # the next row's first block is started across the pad row
    "a-pad-row-between-two-long-rows": ((2 * BLOCK + 7, None, 2 * BLOCK), 3),
}


def _case(h_kv, group, lengths, width, pad_rows=2, seed=0, D=D):
    """A cache whose pages are random, a table that gives each row its own
    pages in a shuffled order, ``pad_rows`` rows whose table is ``n_blocks``
    (``engine._block_table``'s pad), and a second cache in which every page
    past a row's length and every page no row names is NaN."""
    rng = np.random.default_rng(seed)
    pads_within = [b for b, n in enumerate(lengths) if n is None]
    lengths = [n or 0 for n in lengths]
    need = [-(-n // T) for n in lengths]
    n_blocks = sum(need) + 7
    cache = rng.standard_normal((L, 2, h_kv, n_blocks, T, D)).astype(np.float32)
    order = rng.permutation(n_blocks)
    B = len(lengths) + pad_rows
    table = np.full((B, width), n_blocks, np.int32)
    named, at = [], 0
    for b, n in enumerate(need):
        ids = order[at:at + n]
        at += n
        table[b, :n] = ids
        # slots past the row's pages name a page of ANOTHER row's, as a
        # recycled table does: the XLA form gathers it and masks it
        table[b, n:] = order[0]
        named.extend(ids)
    lens = np.asarray(list(lengths) + list(range(1, pad_rows + 1)), np.int32)
    for b in pads_within:                   # a pad row keeps a length: unread
        table[b], lens[b] = n_blocks, 5
    poisoned = np.full_like(cache, np.nan)
    poisoned[:, :, :, named] = cache[:, :, :, named]
    q = rng.standard_normal((B, h_kv * group, D)).astype(np.float32)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    return bf(q), bf(cache), bf(poisoned), jnp.asarray(table), jnp.asarray(lens)


def _kernel(q, cache, table, lens):
    return np.asarray(paged_decode_attention_kernel(
        q, cache, table, lens, layer=LAYER, interpret=True), np.float32)


@pytest.mark.parametrize("h_kv,group", GROUPS)
def test_kernel_reads_live_pages_only_and_agrees_with_the_xla_form(h_kv, group):
    q, cache, poisoned, table, lens = _case(h_kv, group, LENGTHS, WIDTH)
    want = np.asarray(attention._paged_decode_attention_xla(
        q, cache, table, lens, layer=LAYER), np.float32)
    got = _kernel(q, poisoned, table, lens)
    assert np.isfinite(got).all()
    live = len(LENGTHS)
    # bf16 rounding of values of order 1: the XLA form rounds its scores to
    # bf16 before the softmax, the kernel keeps them in float32
    np.testing.assert_allclose(got[:live], want[:live], atol=2e-2)
    # and to the float32 arithmetic of the same bf16 pages, closer
    exact = _float32_reference(q, cache, table, lens)
    np.testing.assert_allclose(got[:live], exact[:live], atol=8e-3)
    assert np.abs(got[:live] - exact[:live]).max() <= np.abs(
        want[:live] - exact[:live]).max() + 4e-3
    # a pad row reads nothing
    assert (got[live:] == 0).all()


def _float32_reference(q, cache, table, lens):
    q, cache = np.asarray(q, np.float32), np.asarray(cache, np.float32)
    table, lens = np.asarray(table), np.asarray(lens)
    B, H, _ = q.shape
    h_kv = cache.shape[2]
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        if table[b, 0] >= cache.shape[3]:
            continue
        ids = table[b, :-(-lens[b] // T)]
        k = cache[LAYER, 0][:, ids].reshape(h_kv, -1, D)[:, :lens[b]]
        v = cache[LAYER, 1][:, ids].reshape(h_kv, -1, D)[:, :lens[b]]
        qg = q[b].reshape(h_kv, H // h_kv, D)
        s = np.einsum("hgd,hkd->hgk", qg, k) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hgk,hkd->hgd", p, v).reshape(H, D)
    return out


@pytest.mark.parametrize("h_kv,group", GROUPS)
def test_a_row_is_bit_equal_alone_in_a_batch_of_32_and_under_a_wider_table(
        h_kv, group):
    """The probes' ``pairs exactly 0``: what a sequence reads back does not
    depend on what it is batched with, on where it stands in the batch, or
    on the table's width bucket."""
    rng = np.random.default_rng(3)
    others = [int(n) for n in rng.integers(1, 3 * T, 31)]
    n = PAGES_PER_BLOCK * T + 5                     # two blocks, the last ragged
    lengths = others[:11] + [n] + others[11:]
    q, _, poisoned, table, lens = _case(h_kv, group, lengths, WIDTH, pad_rows=0)
    batch = _kernel(q, poisoned, table, lens)[11]
    alone = _kernel(q[11:12], poisoned, table[11:12], lens[11:12])[0]
    wide = jnp.concatenate(
        [table[11:12], jnp.full((1, WIDTH), poisoned.shape[3], jnp.int32)], axis=1)
    wider = _kernel(q[11:12], poisoned, wide, lens[11:12])[0]
    assert np.isfinite(batch).all()
    assert (batch == alone).all() and (batch == wider).all()


def _side_by_side(cache, n=2):
    """[L, 2, H_kv, blocks, T, d] -> [L, 2, H_kv / n, blocks, T, n d]: ``n``
    adjacent KV heads in one row, as models/lfm2_moe.py lays its pages out."""
    L_, P, H, nb, T_, d = cache.shape
    return cache.reshape(L_, P, H // n, n, nb, T_, d).transpose(
        0, 1, 2, 4, 5, 3, 6).reshape(L_, P, H // n, nb, T_, n * d)


def _kernel_by_pairs(q, cache, table, lens):
    import functools

    return np.asarray(attention._kernel_over_side_by_side_heads(
        functools.partial(paged_decode_attention_kernel, layer=LAYER,
                          interpret=True), q, cache, table, lens), np.float32)


def test_heads_of_64_side_by_side_in_pairs_agree_with_the_xla_form_by_head():
    """A head of 64 fills half a lane row and does not lower alone; two KV
    heads side by side do (8 heads of 64, groups of 4: 4 rows of 128).  The
    kernel over such a page, each query head laid into its own head's lanes,
    against the XLA form over the SAME values by head; the XLA form over the
    paired page is that to the bit; dead pages are NaN; a row is bit-equal
    alone and in the batch."""
    q, cache, poisoned, table, lens = _case(8, 4, LENGTHS, WIDTH, D=64)
    assert attention.decode_kernel_engages(q, _side_by_side(cache))
    assert not attention.decode_kernel_engages(q, cache)
    want = np.asarray(attention._paged_decode_attention_xla(
        q, cache, table, lens, layer=LAYER), np.float32)
    paired = np.asarray(attention._paged_decode_attention_xla(
        q, _side_by_side(cache), table, lens, layer=LAYER), np.float32)
    live = len(LENGTHS)
    assert np.array_equal(paired[:live], want[:live])
    got = _kernel_by_pairs(q, _side_by_side(poisoned), table, lens)
    assert np.isfinite(got).all() and got.shape == want.shape
    np.testing.assert_allclose(got[:live], want[:live], atol=2e-2)
    assert (got[live:] == 0).all()
    alone = _kernel_by_pairs(q[4:5], _side_by_side(poisoned), table[4:5],
                             lens[4:5])[0]
    assert (alone == got[4]).all()


@pytest.mark.parametrize("h_kv,group", [(8, 4), (4, 7), (1, 20)],
                         ids=["qwen3", "qwen2.5", "jamba"])
@pytest.mark.parametrize("fill", list(BLOCK_FILLS))
def test_whole_blocks_and_a_rows_last_block_agree_and_a_row_is_bit_equal(
        fill, h_kv, group):
    """By the fill of a row's blocks: agreement with the XLA form and the
    float32 arithmetic, dead pages NaN, and every live row bit-equal alone,
    among 32 rows and under a wider table."""
    lengths, blocks = BLOCK_FILLS[fill]
    width = blocks * PAGES_PER_BLOCK
    q, cache, poisoned, table, lens = _case(h_kv, group, lengths, width,
                                            pad_rows=0)
    live = [b for b, n in enumerate(lengths) if n is not None]
    want = np.asarray(attention._paged_decode_attention_xla(
        q, cache, table, lens, layer=LAYER), np.float32)
    exact = _float32_reference(q, cache, table, lens)
    got = _kernel(q, poisoned, table, lens)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=2e-2)
    np.testing.assert_allclose(got[live], exact[live], atol=8e-3)
    assert (np.delete(got, live, axis=0) == 0).all()
    # the rows again among short ones and pad rows, 32 in all, at other places
    pad = jnp.full((1, width), poisoned.shape[3], jnp.int32)
    short = jnp.concatenate([table[live[0]:live[0] + 1, :1], pad[:, 1:]], axis=1)
    rows, at = [], {}
    for b in live:
        rows += [(short, 9, q[live[0]]), (pad, 3, q[b])] * 2
        at[b] = len(rows)
        rows.append((table[b:b + 1], lens[b], q[b]))
    rows += [(short, 11, q[live[0]])] * (32 - len(rows))
    among = _kernel(jnp.stack([r[2] for r in rows]), poisoned,
                    jnp.concatenate([r[0] for r in rows]),
                    jnp.asarray([r[1] for r in rows], jnp.int32))
    for b in live:
        alone = _kernel(q[b:b + 1], poisoned, table[b:b + 1], lens[b:b + 1])[0]
        wider = _kernel(q[b:b + 1], poisoned,
                        jnp.concatenate([table[b:b + 1], pad, pad], axis=1),
                        lens[b:b + 1])[0]
        assert (got[b] == alone).all() and (got[b] == wider).all()
        assert (got[b] == among[at[b]]).all()


def test_a_batch_of_pad_rows_only_reads_nothing():
    q, _, poisoned, table, lens = _case(4, 7, (), WIDTH, pad_rows=3)
    assert (_kernel(q, poisoned, table, lens) == 0).all()


def _page(dtype=jnp.bfloat16, planes=2, h_kv=4, tokens=T, width=D):
    return jax.ShapeDtypeStruct((L, planes, h_kv, 64, tokens, width), dtype)


def _query(dtype=jnp.bfloat16, heads=28):
    return jax.ShapeDtypeStruct((2, heads, D), dtype)


@pytest.mark.parametrize("q,cache,kwargs,engages", [
    (_query(), _page(), {}, True),
    (_query(heads=128), _page(h_kv=8), {}, True),
    (_query(), _page(), {"window": 4096}, False),        # Mistral, Gemma-2
    (_query(), _page(), {"softcap": 50.0}, False),       # Gemma-2
    (_query(), _page(dtype=jnp.int8), {}, False),        # --kv-quant int8
    (_query(jnp.float32), _page(jnp.float32), {}, False),  # the reference's neighbour
    (_query(jnp.float32), _page(), {}, False),
    (_query(), _page(planes=1, h_kv=1, width=576), {}, False),  # a latent page
    (_query(), _page(tokens=8), {}, False),              # half a bf16 tile
    (_query(), _page(width=64), {}, False),              # half a lane row
    (_query(heads=30), _page(), {}, False),
    # heads of 64 in pairs: a query of 64 over rows of 128
    (jax.ShapeDtypeStruct((2, 32, 64), jnp.bfloat16), _page(), {}, True),
    (jax.ShapeDtypeStruct((2, 32, 96), jnp.bfloat16), _page(), {}, False),
], ids=["qwen2.5", "command-a", "window", "softcap", "int8-page", "float32",
        "float32-query", "latent-page", "8-token-page", "64-wide-head",
        "ragged-groups", "heads-of-64-in-pairs", "a-width-that-does-not-divide"])
def test_the_kernel_is_offered_by_shapes_and_dtypes_alone(q, cache, kwargs, engages):
    assert attention.decode_kernel_engages(q, cache, **kwargs) is engages


def test_under_a_named_mesh_the_kernel_is_offered_where_tp_alone_divides():
    mesh = lambda **axes: jax.sharding.use_abstract_mesh(
        jax.sharding.AbstractMesh(tuple(axes.values()), tuple(axes)))
    with mesh(dp=1, tp=4):
        assert attention.decode_kernel_engages(_query(), _page())
    with mesh(dp=1, tp=8):                              # 4 KV heads over 8
        assert not attention.decode_kernel_engages(_query(), _page())
    with mesh(pp=2, tp=2):                              # layers spread over pp
        assert not attention.decode_kernel_engages(_query(), _page())


def test_on_the_cpu_the_program_holds_the_xla_form_and_no_kernel():
    """``paged_decode_attention`` offers the kernel to a TPU's lowering only:
    the CPU's program is the XLA form to the bit, and holds no custom call."""
    q, cache, _, table, lens = _case(4, 7, (1, 40, 100), 8)
    fn = jax.jit(lambda q, c, t, n: attention.paged_decode_attention(
        q, c, LAYER, t, n))
    want = attention._paged_decode_attention_xla(q, cache, table, lens, layer=LAYER)
    assert (np.asarray(fn(q, cache, table, lens), np.float32)
            == np.asarray(want, np.float32)).all()
    text = fn.lower(q, cache, table, lens).as_text()
    assert "custom_call" not in text and "gather" in text


@pytest.mark.parametrize("attn_kernel,steps", [(True, 32), (False, 0)])
def test_attn_kernel_steps_counts_the_dispatched_steps_of_a_kernel_program(
        attn_kernel, steps):
    prof = stepprof.StepProfiler(metrics=MetricsRegistry(), sample=10**9)
    with prof.step():
        stepprof.note_decode(steps=32, rows=3, padded_rows=4, width_pages=8,
                             block_tokens=T, live_tokens=100,
                             attn_kernel=attn_kernel)
    d = prof.summary()["decode"]
    assert d["steps"] == 32 and d["attn_kernel_steps"] == steps


@pytest.mark.parametrize("pos,steps,width,pages,whole", [
    # one row at position 30: lengths 31 and 32 are 2 pages, 33 and 34 are 3
    ([30], 4, 64, 2 + 2 + 3 + 3, 0),
    # the row's 32nd page fills at length 512 = position 511, and stays whole
    ([509], 4, 64, 32 * 3 + 33, 32 * 4),
    # batch-summarize's rows: 3 of 4 blocks whole, 2 of 3, none of 1
    ([16 * 100 - 1, 16 * 70 - 1, 16 * 20 - 1], 1, 256, 190, 96 + 64),
    # the kernel reads no page past the table's width: 70 pages under 64
    ([16 * 70 - 1, 16 * 20 - 1], 1, 64, 64 + 20, 64),
], ids=["under-a-block", "across-a-block-boundary", "three-rows",
        "clamped-to-the-width"])
def test_the_engine_counts_the_kernels_pages_and_those_in_whole_blocks(
        pos, steps, width, pages, whole):
    from infinistore_tpu.engine import InferenceEngine

    counted = InferenceEngine._kernel_pages(np.asarray(pos), steps, T, width)
    assert counted == (pages, whole)
    metrics = MetricsRegistry()
    prof = stepprof.StepProfiler(metrics=metrics, sample=10**9)
    for _ in range(2):
        with prof.step():
            stepprof.note_decode(steps=steps, rows=len(pos), padded_rows=4,
                                 width_pages=width, block_tokens=T,
                                 live_tokens=1,
                                 attn_kernel=True, kernel_pages=counted)
    d = prof.summary()["decode"]
    assert (d["kernel_pages"], d["kernel_whole_block_pages"]) == (
        2 * pages, 2 * whole)
    text = metrics.to_prometheus_text()
    assert f"istpu_engine_decode_kernel_pages_total {2 * pages}" in text
    assert ("istpu_engine_decode_kernel_whole_block_pages_total "
            f"{2 * whole}") in text


class _OnTpu:
    """An array as the engine sees its cache on a chip: the shape and dtype
    of a real pool, a device whose platform is ``tpu``."""

    class _Device:
        platform = "tpu"

    def __init__(self, pool):
        self.shape, self.dtype = pool.shape, pool.dtype

    def devices(self):
        return {self._Device()}


@pytest.mark.parametrize("cfg_kwargs,block_tokens,dtype,engaged", [
    ({}, 16, jnp.bfloat16, True),
    ({"sliding_window": 64}, 16, jnp.bfloat16, False),       # every layer windowed
    ({"sliding_window": 64, "window_pattern": 2}, 16, jnp.bfloat16, True),
    ({"attn_softcap": 30.0}, 16, jnp.bfloat16, False),
    ({}, 4, jnp.bfloat16, False),                             # a page under a tile
    ({}, 16, jnp.float32, False),
], ids=["dense", "mistral", "alternating", "softcap", "4-token-page", "float32"])
def test_the_engine_counts_kernel_steps_by_the_attentions_own_test(
        cfg_kwargs, block_tokens, dtype, engaged, monkeypatch):
    """``decode.attn_kernel_steps`` is the engine's reading of the test
    ``paged_decode_attention`` makes at lowering.  On the CPU it is 0 for
    every model; with the cache on a TPU it follows the model's layers
    (one that attends to every live key is enough), the page and the dtype."""
    from infinistore_tpu import models
    from infinistore_tpu.engine import InferenceEngine
    from infinistore_tpu.kv import PagedCacheConfig

    cfg = models.scaled(models.TINY, head_dim_override=128, dtype=dtype,
                        **cfg_kwargs)
    pc = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_blocks=8, block_tokens=block_tokens,
        dtype=dtype)
    eng = InferenceEngine(models.init_params(cfg, jax.random.PRNGKey(0)), cfg, pc)
    assert eng._attn_in_kernel is False                      # the CPU's program
    monkeypatch.setattr(eng, "cache", _OnTpu(eng.cache))
    assert eng._dense_attention_in_kernel() is engaged
