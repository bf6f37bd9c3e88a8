"""Pallas TPU kernels: paged decode + flash prefill attention.

STATUS: EXPERIMENT, opt-in and OFF by default (``ISTPU_PALLAS_DECODE`` /
``ISTPU_PALLAS_PREFILL``).  Both kernels pass their Mosaic acceptance
tests on the chip (``tests/test_ops.py -k on_tpu``, run by
``chip_smoke.py``).  Whether either beats the XLA path is not measured on
a directly attached chip: ROADMAP A6 decides, with
``scripts/pallas_tune.py`` (a block-size/layout sweep against XLA) as
the entry, and flips a default only on a replicated win.

The decode hot loop reads every cached K/V page of every active sequence per
token -- purely HBM-bandwidth-bound.  The XLA version
(models/attention.py:paged_decode_attention_xla) gathers the table's pages
by (layer, page) index out of the whole cache and writes them out as K and
V ([B, S_max, H_kv, D]) before contracting the query, by group, against
them (no repeat of the KV heads); this kernel instead streams pages
HBM->VMEM by block-table lookup (PrefetchScalarGridSpec: the table is
available to BlockSpec index_maps, so the pipeline's double-buffered DMAs
chase the page table directly -- no gathered copy is ever written back).

The reference's comparable hot path is the GPUDirect RDMA read of KV blocks
into the GPU (reference: src/libinfinistore.cpp batched IBV_WR_RDMA_READ);
on TPU the cache is already in HBM and the analog is the HBM->VMEM stream.

Cache layout: [2(K|V), H_kv, n_blocks, T, D] -- a (head, page) tile
[T=16, D=128] is contiguous and exactly the bf16 min tile (16, 128).  This
is one layer of the serving layout (kv/cache.py), so no shuffle happens on
the decode path; the kernels are handed ``cache[layer]``.

Grid: (B, H_kv, max_pages); the page axis is innermost so the flash-style
online-softmax accumulators (m/l/acc in VMEM scratch, fp32) carry across
page steps and write out once on the last page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(s, v, m_scr, l_scr, acc_scr):
    """One flash-attention accumulator update: fold the masked score tile
    ``s`` [R, Tk] and value tile ``v`` [Tk, D] into the running max /
    denominator / numerator scratch.  Shared by all three kernels below so
    the numerics can never diverge between them."""
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_scr[:, :1] = m_new
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )


def _decode_kernel(
    table_ref,  # scalar prefetch: [B, max_pages] int32
    lens_ref,   # scalar prefetch: [B] int32
    q_ref,      # [..., R, D] current-token queries for this kv head group
    k_ref,      # [..., T, D] one K page
    v_ref,      # [..., T, D] one V page
    o_ref,      # [..., R, D]
    m_scr,      # [R, 128] fp32 running max (col 0 used)
    l_scr,      # [R, 128] fp32 running denominator (col 0 used)
    acc_scr,    # [R, D] fp32 numerator
    *,
    scale: float,
    b_axis: int = 0,
    c_axis: int = 2,
):
    """ONE kernel body for both grid layouts — (B, Hkv, pages) on the
    model path and (L, B, Hkv, pages) on the all-layers instrument
    (``b_axis``/``c_axis`` name the batch and page grid axes; block
    shapes differ only in leading 1s, which the reshapes below drop).
    Shared on purpose: the instrument exists to vary ONLY the invocation
    count, so its masking/guard numerics must be the model kernel's by
    construction."""
    b = pl.program_id(b_axis)
    c = pl.program_id(c_axis)
    n_chunks = pl.num_programs(c_axis)
    T, D = k_ref.shape[-2], k_ref.shape[-1]
    R = q_ref.shape[-2]

    @pl.when(c == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]

    @pl.when(c * T < seq_len)
    def _attend():
        q = q_ref[...].reshape(R, D).astype(jnp.float32)
        k = k_ref[...].reshape(T, D).astype(jnp.float32)
        v = v_ref[...].reshape(T, D).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [R, T]
        pos = c * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        _online_softmax_step(s, v, m_scr, l_scr, acc_scr)

    @pl.when(c == n_chunks - 1)
    def _finish():
        o_ref[...] = (
            (acc_scr[:] / l_scr[:, :1])
            .astype(o_ref.dtype)
            .reshape(o_ref.shape)
        )


def _flash_kernel(
    q_ref,    # [1, 1, Bq, D]
    k_ref,    # [1, 1, Bk, D]
    v_ref,    # [1, 1, Bk, D]
    o_ref,    # [1, 1, Bq, D]
    m_scr,    # [Bq, 128] fp32 running max (col 0 used)
    l_scr,    # [Bq, 128] fp32 running denominator (col 0 used)
    acc_scr,  # [Bq, D] fp32 numerator
    *,
    scale: float,
    q_offset: int,
    block_q: int,
    block_k: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # absolute positions of this tile's rows/cols; the causal test also
    # masks tail padding (padded K rows sit past every real Q position)
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    @pl.when(ik * block_k <= q_offset + (iq + 1) * block_q - 1)
    def _attend():  # block intersects the causal triangle
        q = q_ref[0, 0].astype(jnp.float32)  # [Bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [Bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Bq, Bk]
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        _online_softmax_step(s, v, m_scr, l_scr, acc_scr)

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def _flash_prefix_kernel(
    plen_ref,  # scalar prefetch: [1] int32 valid prefix length
    q_ref,     # [1, 1, Bq, D]
    k_ref,     # [1, 1, Bk, D]
    v_ref,     # [1, 1, Bk, D]
    o_ref,     # [1, 1, Bq, D]
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    prefix_pad: int,
    block_q: int,
    block_k: int,
):
    """Flash attention over ``[bucketed prefix | self]`` K/V: the first
    ``prefix_pad`` rows are a prefix buffer of which only ``plen`` are
    valid; the rest are the queries' own KV, causal by chunk-local index.
    ``prefix_pad`` is block-aligned, so each k block is entirely prefix or
    entirely self."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    plen = plen_ref[0]
    q_idx = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # boolean algebra, not jnp.where-of-bools: Mosaic can't lower select_n
    # on i1 vectors (it truncates i8->i1, unsupported on TPU)
    in_prefix = ik * block_k < prefix_pad
    live = (in_prefix & (ik * block_k < plen)) | (
        (~in_prefix)
        & (ik * block_k - prefix_pad <= iq * block_q + block_q - 1)
    )

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        kp = k_pos < prefix_pad
        valid = (kp & (k_pos < plen)) | (
            (~kp) & ((k_pos - prefix_pad) <= q_idx)
        )
        s = jnp.where(valid, s, NEG_INF)
        _online_softmax_step(s, v, m_scr, l_scr, acc_scr)

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("prefix_pad", "interpret", "block_q", "block_k"),
)
def flash_prefix_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    prefix_pad: int,
    prefix_len: jax.Array,
    interpret: bool = False,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Flash attention for bucketed chunked prefill (engine/engine.py).

    q: [B, Sq, H, D]; k/v: [B, prefix_pad + Sq, H_kv, D] where rows
    [0, prefix_len) are the valid prefix, [prefix_len, prefix_pad) are
    bucket slack, and [prefix_pad, ...) are the queries' own KV.
    ``prefix_len`` is a traced int32 scalar delivered to the kernel and its
    index maps via scalar prefetch, so every bucket capacity compiles once;
    slack and causal-dead K/V blocks are clamp-deduped out of the DMA
    stream just like the dense-causal kernel's frontier.
    Matches models/attention.py:causal_attention's padded-prefix mode
    (tests/test_ops.py).
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)
    assert prefix_pad % block_k == 0, (prefix_pad, block_k)
    assert Sk == prefix_pad + Sq, (Sk, prefix_pad, Sq)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qt = jnp.pad(jnp.transpose(q, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kt = jnp.pad(jnp.transpose(k, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vt = jnp.pad(jnp.transpose(v, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (B, H, (Sq + pad_q) // block_q, (Sk + pad_k) // block_k)
    n_prefix_blocks = prefix_pad // block_k

    def q_map(b, h, iq, ik, plen_ref):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik, plen_ref):
        # prefix region: clamp at the last valid prefix block (slack blocks
        # re-request it; duplicate fetches are skipped).  self region: clamp
        # at the causal frontier, as in the dense kernel.
        last_prefix = jnp.maximum(plen_ref[0] - 1, 0) // block_k
        frontier = (prefix_pad + (iq + 1) * block_q - 1) // block_k
        ikc = jnp.where(
            ik < n_prefix_blocks,
            jnp.minimum(ik, last_prefix),
            jnp.minimum(ik, frontier),
        )
        return (b, h // n_rep, ikc, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(
            _flash_prefix_kernel, scale=scale, prefix_pad=prefix_pad,
            block_q=block_q, block_k=block_k,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(prefix_len, dtype=jnp.int32).reshape(1), qt, kt, vt)

    return jnp.transpose(out[:, :, :Sq], (0, 2, 1, 3))


@functools.partial(
    jax.jit, static_argnames=("q_offset", "interpret", "block_q", "block_k")
)
def flash_causal_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: int = 0,
    interpret: bool = False,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Flash-style causal prefill attention (online softmax, GQA).

    q: [B, Sq, H, D]; k/v: [B, Sk, H_kv, D]; ``q_offset`` = absolute
    position of q[0] minus that of k[0] (chunked prefill attends to the
    cached prefix plus itself).  Returns [B, Sq, H, D].

    The O(S^2) score matrix never exists in HBM: K/V stream HBM->VMEM in
    [block_k, D] tiles and the m/l/acc accumulators carry across the
    innermost k-block grid axis (same structure as the paged decode kernel
    above).  This is the role flash attention plays in the reference's GPU
    serving stack; matches models/attention.py:causal_attention
    (tests/test_ops.py).
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    # [B, S, H, D] -> [B, H, S, D] tiles; padded K rows are causally masked
    # for every real Q row, padded Q rows are dropped on return
    qt = jnp.pad(jnp.transpose(q, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kt = jnp.pad(jnp.transpose(k, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vt = jnp.pad(jnp.transpose(v, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (B, H, (Sq + pad_q) // block_q, (Sk + pad_k) // block_k)

    def q_map(b, h, iq, ik):
        return (b, h, iq, 0)

    # causal frontier: the last k block that q block iq can see.  Clamping
    # the index map there makes every fully-masked step re-request the same
    # block, and the pipeline skips the duplicate fetch — no dead K/V DMA
    # above the diagonal (HBM bandwidth is the kernel's bottleneck).
    def kv_map(b, h, iq, ik):
        frontier = (q_offset + (iq + 1) * block_q - 1) // block_k
        return (b, h // n_rep, jnp.minimum(ik, frontier), 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)

    return jnp.transpose(out[:, :, :Sq], (0, 2, 1, 3))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jax.Array,
    cache_kl: jax.Array,
    block_table: jax.Array,
    seq_lens: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """One-token decode attention straight off the paged HBM cache.

    q: [B, H, D] (RoPE applied); cache_kl: [2, H_kv, n_blocks, T, D]
    (the kv/cache.py serving layout, per layer); block_table: [B, max_pages]
    int32; seq_lens: [B] int32 (valid tokens incl. current).
    Returns [B, H, D].

    Matches models/attention.py:paged_decode_attention_xla (tests/test_ops.py).
    """
    B, H, D = q.shape
    _, Hkv, _, T, Dc = cache_kl.shape
    assert Dc == D, (Dc, D)
    n_rep = H // Hkv
    # pad query groups to the dtype's native sublane tile: (8, 128) for
    # fp32, (16, 128) for bf16 -- an 8-sublane bf16 block would be below
    # the native tile and Mosaic may reject or mis-tile it
    min_sublane = 8 if q.dtype == jnp.float32 else 16
    R = max(n_rep, min_sublane)
    max_pages = block_table.shape[1]
    scale = 1.0 / np.sqrt(D)

    # [B, H, D] -> [B, Hkv, R, D]
    qg = q.reshape(B, Hkv, n_rep, D)
    if R != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - n_rep), (0, 0)))

    grid = (B, Hkv, max_pages)

    def q_map(b, h, c, table_ref, lens_ref):
        return (b, h, 0, 0)

    # clamp the page index at each sequence's last valid page: grid steps
    # past the sequence end re-request the same page and the pipeline skips
    # the duplicate fetch, so a short sequence in a long-max_pages batch
    # costs its own length in HBM traffic, not max_pages (compute for those
    # steps is already gated by the c*T < seq_len guard in the kernel)
    def _page(b, c, lens_ref):
        last = jnp.maximum(lens_ref[b] - 1, 0) // T
        return jnp.minimum(c, last)

    def k_map(b, h, c, table_ref, lens_ref):
        return (0, h, table_ref[b, _page(b, c, lens_ref)], 0, 0)

    def v_map(b, h, c, table_ref, lens_ref):
        return (1, h, table_ref[b, _page(b, c, lens_ref)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, R, D), q_map),
            pl.BlockSpec((1, 1, 1, T, D), k_map),
            pl.BlockSpec((1, 1, 1, T, D), v_map),
        ],
        out_specs=pl.BlockSpec((1, 1, R, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32), qg,
      cache_kl, cache_kl)

    return out[:, :, :n_rep].reshape(B, H, D)


def paged_decode_attention_pallas_alllayers(
    qs: jax.Array,
    cache: jax.Array,
    block_table: jax.Array,
    seq_lens: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """ALL layers' decode attention in ONE ``pallas_call``.

    qs: [L, B, H, D]; cache: [L, 2, H_kv, n_blocks, T, D] (the full
    serving cache); block_table/seq_lens as in
    ``paged_decode_attention_pallas``.  Returns [L, B, H, D].

    This is an INSTRUMENT, not a model path: inside a real forward,
    layer l's query depends on layer l-1's output, so the layers cannot
    actually run from one dispatch.  But the total HBM traffic and FLOPs
    here are IDENTICAL to L back-to-back single-layer calls — the only
    difference is 1 invocation instead of L — which is exactly the
    controlled experiment VERDICT r4 next #5 asked for: if this runs
    ~L times faster per-layer than the chained single-layer calls, the
    per-``pallas_call`` overhead hypothesis is confirmed (and quantified
    as the difference); if it doesn't, the kernels lose for some other
    reason and the overhead theory dies."""
    L, B, H, D = qs.shape
    Lc, _, Hkv, _, T, Dc = cache.shape
    assert Lc == L and Dc == D, (Lc, L, Dc, D)
    n_rep = H // Hkv
    min_sublane = 8 if qs.dtype == jnp.float32 else 16
    R = max(n_rep, min_sublane)
    max_pages = block_table.shape[1]
    scale = 1.0 / np.sqrt(D)

    qg = qs.reshape(L, B, Hkv, n_rep, D)
    if R != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, R - n_rep), (0, 0)))

    grid = (L, B, Hkv, max_pages)

    def q_map(l, b, h, c, table_ref, lens_ref):
        return (l, b, h, 0, 0)

    def _page(b, c, lens_ref):
        last = jnp.maximum(lens_ref[b] - 1, 0) // T
        return jnp.minimum(c, last)

    def k_map(l, b, h, c, table_ref, lens_ref):
        return (l, 0, h, table_ref[b, _page(b, c, lens_ref)], 0, 0)

    def v_map(l, b, h, c, table_ref, lens_ref):
        return (l, 1, h, table_ref[b, _page(b, c, lens_ref)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, R, D), q_map),
            pl.BlockSpec((1, 1, 1, 1, T, D), k_map),
            pl.BlockSpec((1, 1, 1, 1, T, D), v_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, R, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, b_axis=1, c_axis=3),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, B, Hkv, R, D), qs.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32), qg,
      cache, cache)

    return out[:, :, :, :n_rep].reshape(L, B, H, D)
