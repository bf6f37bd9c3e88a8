"""Attention ops: causal prefill attention and paged decode attention.
One implementation of each per program.  In plain ``jax.numpy`` (XLA tiles
the matmuls onto the MXU and fuses mask and softmax) but for two, chosen by
shapes and dtypes when a TPU's program is lowered: the dense decode
attention reads its pages through the kernel of ``paged_decode_kernel``
(each row's live pages, copied out of the whole cache by the block table),
and a whole prefill chunk's attention is the kernel of
``chunk_attention_kernel`` (the live rows of the prefix buffer and the
chunk's own, no score matrix written).  The XLA forms stay every other
platform's programs and the kernels' oracles.

* the XLA decode attention (every other platform, a window, a soft cap, a
  page that is not bf16 K|V by head) reads K/V straight from the paged HBM
  cache via a static-shape page-table gather: [B, max_pages] int32 ->
  [B, S_max, H_kv, D].  No dynamic shapes: padding slots are masked by
  sequence length.
* GQA in the paged readers (decode, speculative verify) views the query as
  [.., H_kv, G, D] and contracts each group against its KV head's pages as
  gathered: nothing of [B, S, H, D] exists.  The XLA prefill form
  (``_causal_attention_xla``) still calls ``repeat_kv``, which XLA:TPU
  materialises (below); the chunk kernel reads K and V as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def rope_freqs(
    head_dim: int,
    theta: float = 500000.0,
    scaling: tuple | None = None,
) -> jax.Array:
    """Base RoPE frequencies, optionally remapped by Llama-3.1-style
    context-extension scaling.

    ``scaling``: ``(factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)`` — long wavelengths (relative to the
    original context) are slowed by ``factor``, short ones are kept, and the
    band between is interpolated.  Matches transformers'
    ``rope_type="llama3"`` so imported 3.1/3.2 checkpoints reproduce HF
    logits (tests/test_hf_import.py).
    """
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling is not None:
        factor, low_f, high_f, orig_ctx = scaling
        wavelen = 2.0 * np.pi / freqs
        low_wl = orig_ctx / low_f
        high_wl = orig_ctx / high_f
        smooth = (orig_ctx / wavelen - low_f) / (high_f - low_f)
        interp = (1.0 - smooth) * freqs / factor + smooth * freqs
        freqs = jnp.where(
            wavelen > low_wl,
            freqs / factor,
            jnp.where(wavelen < high_wl, freqs, interp),
        )
    return freqs


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float = 500000.0,
    scaling: tuple | None = None,
) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_rope_leading(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    rotated: int,
) -> jax.Array:
    """A PARTIAL rotation: the first ``rotated`` dimensions of every head are
    rotated, dimension ``i`` paired with ``i + rotated / 2`` (the halves of the
    rotated part, not neighbours as ``apply_rope`` pairs them), at the
    frequencies ``theta ** (-2i / rotated)``; the rest of the head passes
    through.  x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    half = rotated // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32)
                             / rotated))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotated].astype(jnp.float32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), x[..., rotated:]], axis=-1)


def split_kv_rows(rows: jax.Array, heads: int, k_width: int):
    """A page row of ``heads`` keys side by side and then their values
    (``kv/cache.py`` ``pool_kv``) -> ``(k [..., heads, k_width], v [...,
    heads, v_width])``."""
    cut = heads * k_width
    lead = rows.shape[:-1]
    return (rows[..., :cut].reshape(lead + (heads, k_width)),
            rows[..., cut:].reshape(lead + (heads, -1)))


def softmax_with_sink(logits: jax.Array, sink: jax.Array | None) -> jax.Array:
    """Softmax over the last axis of float32 ``logits`` (masked entries
    ``-inf``); with ``sink`` (broadcastable to ``logits[..., 0]``) the
    denominator holds ``exp(sink)`` besides: ``p_j = exp(s_j) / (exp(b) +
    sum_j' exp(s_j'))``.  The sink is a key that every query sees and that
    has no value: the weights sum to less than one."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    b = jnp.broadcast_to(sink.astype(jnp.float32), logits.shape[:-1])
    return jax.nn.softmax(
        jnp.concatenate([logits, b[..., None]], axis=-1), axis=-1)[..., :-1]


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[..., S, H_kv, D] -> [..., S, H_kv*n_rep, D]: query head h reads KV
    head h // n_rep.

    A broadcast in the traced program, a COPY on the chip: XLA:TPU writes
    the ``n_rep``-fold array out and the einsums read it back (in the paged
    decode that was 235 MB per K and per V per layer at 8 rows x 256 pages
    and groups of 7, half the step: PERF.md, PR 31).  Only the XLA form of
    prefill (``_causal_attention_xla``) still calls it; the copy is
    materialised there too (``bf16[4608, 28, 128]`` of K and of V a layer at
    the largest bucket: PERF.md, PR 48)."""
    if n_rep == 1:
        return x
    shape = x.shape
    x = x[..., :, :, None, :]
    x = jnp.broadcast_to(x, shape[:-1] + (n_rep, shape[-1]))
    return x.reshape(shape[:-2] + (shape[-2] * n_rep, shape[-1]))


def chunk_kernel_engages(q, k, v, q_offset=0, prefix_pad=None,
                         prefix_len=None, window=None, softcap=None) -> bool:
    """Whether the TPU's kernel (``chunk_attention_kernel``) can run this
    call of ``causal_attention``: bf16 q, K and V with heads of whole 128-lane
    rows, whole groups of query heads, attention over every live key (a
    window or a soft cap keeps the XLA form), whole blocks of query rows (a
    chunk of 512, or several), and one of the two forms a prompt of several
    chunks runs: a padded prefix buffer of whole blocks with its
    ``prefix_len``, or no prefix at all.  A chunk at a static ``q_offset``
    over an exact prefix (a re-ask's short tail, compiled for its own length)
    keeps the XLA form, and so does any program traced under a named mesh
    with an axis larger than 1 (``--tp``, a shard_map).  Static: shapes,
    dtypes and the mesh named while tracing; arrays or their abstract values.

    A jit that partitions the model over a mesh by shardings alone (GSPMD)
    names that mesh while it traces (``use_abstract_mesh``:
    ``parallel/sharding.py:make_tp_prefill`` and ``make_tp_decode``, the
    engine's ``_traced_under``): nothing else tells the trace that its
    program will be split, and the partitioner cannot split a TPU kernel by
    itself (the lowering refuses: tests/test_aot_tpu.py)."""
    Sq, H, D = q.shape[1:]
    if (window is not None or softcap is not None
            or k.shape != v.shape or k.shape[-1] != D
            or any(x.dtype != jnp.bfloat16 for x in (q, k, v))
            or D % 128 or H % k.shape[2]
            or any(n > 1 for n in jax.sharding.get_abstract_mesh().shape.values())):
        return False
    # Pallas is a second of import: paid by the programs that can hold the kernel
    from .chunk_attention_kernel import BLOCK

    if Sq % BLOCK:
        return False
    if prefix_len is not None:
        return prefix_pad % BLOCK == 0 and k.shape[1] == prefix_pad + Sq
    return isinstance(q_offset, int) and q_offset == 0 and k.shape[1] == Sq


def prefix_attention_args(prefix_rows: int | None, prefix_len=None) -> dict:
    """What ``causal_attention`` is told of a prefill chunk's prefix: a
    buffer of ``prefix_rows`` rows in front of the chunk's own K and V (None:
    the chunk has no prefix), of which the first ``prefix_len`` are live
    (None: all of them, an exact prefix).  The models' prefill forwards and
    ``chunk_kernel_layers`` both build the call from here."""
    if prefix_rows is None:
        return {}
    return dict(q_offset=prefix_rows,
                prefix_pad=prefix_rows if prefix_len is not None else None,
                prefix_len=prefix_len)


def chunk_kernel_layers(q, kv, prefix_rows, prefix_len, windows,
                        softcap=None) -> int:
    """Of a prefill program's layers (``windows``: a layer's window or None,
    one entry a layer), those whose attention a TPU's lowering makes the
    chunk kernel: ``causal_attention``'s own test on the call a forward
    builds by ``prefix_attention_args``.  ``q`` [B, S, H, D] and ``kv`` [B,
    prefix_rows + S, H_kv, D]: arrays or abstract values."""
    return sum(
        chunk_kernel_engages(q, kv, kv, window=w, softcap=softcap,
                             **prefix_attention_args(prefix_rows, prefix_len))
        for w in windows)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array | int = 0,
    prefix_pad: int | None = None,
    prefix_len: jax.Array | None = None,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Causal SDPA.  q: [B, Sq, H, D]; k/v: [B, Sk, H_kv, D].

    ``q_offset``: absolute position of q[0] minus that of k[0] (chunked
    prefill attends to cached prefix + itself).

    Padded-prefix mode (``prefix_pad``/``prefix_len`` both given): the first
    ``prefix_pad`` K/V rows are a prefix buffer of which only the first
    ``prefix_len`` (a traced scalar) are valid, and the remaining rows are
    the queries' own KV.  Bucketing the prefix buffer to a few static
    capacities keeps chunked prefill's compile count logarithmic
    (engine/engine.py) while this mask hides the slack.

    ``window``: sliding-window attention (Mistral) — a key is visible iff
    ``q_pos - window < k_pos <= q_pos`` (HF convention).

    One attention per program, chosen by what is known when the program is
    lowered (``chunk_kernel_engages``): for a TPU, a whole chunk of a prompt
    of several (padded-prefix mode, or no prefix at all) in bf16 is ONE call
    of the kernel of ``chunk_attention_kernel``, which visits the live key
    blocks and writes no scores; anywhere else the XLA form below, which is
    the kernel's oracle in the tests.
    """
    xla = functools.partial(_causal_attention_xla, q_offset=q_offset,
                            prefix_pad=prefix_pad, window=window,
                            softcap=softcap)
    if not chunk_kernel_engages(q, k, v, q_offset, prefix_pad, prefix_len,
                                window, softcap):
        return xla(q, k, v, prefix_len)
    from .chunk_attention_kernel import chunk_attention_kernel

    # ``prefix_len`` is an operand of both forms where there is one
    n = () if prefix_len is None else (prefix_len,)
    xla_form = lambda q, k, v, *n: xla(q, k, v, *(n or (None,)))
    kernel = lambda q, k, v, *n: chunk_attention_kernel(
        q, k, v, prefix_pad or 0, *n)
    return jax.lax.platform_dependent(
        q, k, v, *n, default=xla_form,
        tpu=_with_derivative_of(xla_form, kernel))


def _with_derivative_of(xla, kernel):
    """``kernel``, which under differentiation IS the XLA form of the same
    function: a Pallas call has no derivative of its own, and every branch of
    a ``platform_dependent`` is differentiated wherever the program is lowered
    (a train step through ``prefill_forward``, on any platform).  The forward
    pass of a differentiated program is then the XLA form's too, its
    residuals kept for the backward pass: the loss and its gradient come from
    one set of scores, and nothing is computed twice.  A train step on a TPU
    therefore writes its scores out as it did before the kernel; only a
    program that is not differentiated runs the kernel."""
    f = jax.custom_vjp(kernel)
    f.defvjp(lambda *args: jax.vjp(xla, *args), lambda pull, g: pull(g))
    return f


def _causal_attention_xla(q, k, v, prefix_len, *, q_offset=0, prefix_pad=None,
                          window=None, softcap=None):
    """``causal_attention`` in plain ``jax.numpy``: K and V repeated by
    group, every head's scores over every key row, masked."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    k = repeat_kv(k, H // Hkv)
    v = repeat_kv(v, H // Hkv)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap is not None:  # Gemma-2 logit soft-capping
        logits = softcap * jnp.tanh(logits / softcap)
    k_pos = jnp.arange(k.shape[1])
    if prefix_len is not None:
        assert prefix_pad is not None
        i = jnp.arange(Sq)[:, None]  # query row within the chunk
        in_prefix = k_pos[None, :] < prefix_len  # valid prefix rows
        in_self = (k_pos[None, :] >= prefix_pad) & (
            k_pos[None, :] - prefix_pad <= i
        )
        mask = in_prefix | in_self  # [Sq, Sk]
        if window is not None:
            # absolute positions: prefix row j sits at j; self row at
            # prefix_len + (row - prefix_pad); query i at prefix_len + i
            k_abs = jnp.where(
                k_pos < prefix_pad, k_pos, prefix_len + k_pos - prefix_pad
            )
            mask &= k_abs[None, :] > prefix_len + i - window
    else:
        q_pos = jnp.arange(Sq) + q_offset
        mask = k_pos[None, :] <= q_pos[:, None]  # [Sq, Sk]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def gather_layer_kv(
    cache: jax.Array, layer: int, block_table: jax.Array,
    kv_split: tuple | None = None,
) -> tuple[jax.Array, ...]:
    """One layer's pages for a block table, one array per plane of the
    page (keys and values; or the one latent plane), gathered by index
    straight out of the whole cache.

    cache: [L, planes, H_kv, n_blocks, T, D]; layer: static; block_table:
    [B, max_pages] int32 -> planes x [B, max_pages * T, H_kv, D].

    Layer, plane and page id are all indices of the gather, as in
    kv/cache.py:write_token_rows.  A ``cache[layer]`` formed first is a
    slice, and XLA:TPU does not fuse a slice into a gather's operand: it
    copies the layer's slab, and K's and V's halves of it, in every layer
    of every step (PERF.md, PR 27).  The advanced indices are split by a
    slice, so the table's dims land in front: [B, max_pages, H_kv, T, D].
    Out-of-bounds page ids (pad rows) clamp; callers mask by length.

    ``kv_split`` ``(heads, key width)``: the page is one plane of one row a
    token, its heads' keys side by side and then their values
    (``split_kv_rows``); what comes back is ``(k [B, S, heads, key width],
    v [B, S, heads, value width])``."""
    B, max_pages = block_table.shape
    Hkv, _, T, D = cache.shape[2:]
    planes = tuple(
        jnp.moveaxis(cache[layer, plane, :, block_table], 2, 3).reshape(
            B, max_pages * T, Hkv, D)
        for plane in range(cache.shape[1])
    )
    if kv_split is None:
        return planes
    (rows,) = planes
    return split_kv_rows(rows[:, :, 0], *kv_split)


def _latent_kvb(w_kvb: jax.Array, n_heads: int, nope: int):
    """The up-projection [rank, H * (nope + v)] as its keys' and its values'
    halves by head: [rank, H, nope], [rank, H, v]."""
    w = w_kvb.reshape(w_kvb.shape[0], n_heads, -1)
    return w[..., :nope], w[..., nope:]


def latent_expanded_attention(
    q: jax.Array,
    ckr: jax.Array,
    w_kvb: jax.Array,
    rank: int,
    nope: int,
    q_offset: int = 0,
    prefix_pad: int | None = None,
    prefix_len: jax.Array | None = None,
) -> jax.Array:
    """Latent attention, EXPANDED: every row of the page is up-projected to
    its key and value by head, then plain causal attention (the prefill
    path: many queries share the up-projection's cost).

    q: [B, Sq, H, nope + rope] (rope part rotated); ckr: [B, Sk, rank +
    rope], the page's rows (normalised latent; the rotated key all heads
    share) of the prefix and of the queries' own tokens; w_kvb: [rank,
    H * (nope + v)].  k_i = [c W^K_i; k_r], v_i = c W^V_i, scores over
    sqrt(nope + rope).  Masking as ``causal_attention``.  -> [B, Sq, H, v]."""
    B, Sk, _ = ckr.shape
    H = q.shape[2]
    with jax.named_scope("istpu.mla.expand"):
        wk, wv = _latent_kvb(w_kvb, H, nope)
        c, k_r = ckr[..., :rank], ckr[..., rank:]
        k = jnp.concatenate(
            [jnp.einsum("bsr,rhn->bshn", c, wk),
             jnp.broadcast_to(k_r[:, :, None, :], (B, Sk, H, k_r.shape[-1]))],
            axis=-1)
        v = jnp.einsum("bsr,rhv->bshv", c, wv)
        return causal_attention(q, k, v, q_offset=q_offset,
                                prefix_pad=prefix_pad, prefix_len=prefix_len)


def latent_absorbed_decode_attention(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_table: jax.Array,
    seq_lens: jax.Array,
    w_kvb: jax.Array,
    rank: int,
    nope: int,
) -> jax.Array:
    """Latent attention, ABSORBED: one token's queries against the paged
    one-plane cache without expanding a single key (the decode path: one
    query per head, so the up-projection is moved onto the query and onto
    the weighted sum).

    q: [B, H, nope + rope]; cache: [L, 1, 1, n_blocks, T, rank + rope];
    q~_i = q_n,i (W^K_i)^T; scores (q~_i . c + q_r,i . k_r) / sqrt(nope +
    rope): H queries over ONE key row per token, read once for all heads;
    o_i = (sum p c) W^V_i.  The same function of the page as
    ``latent_expanded_attention``.  -> [B, H, v]."""
    B, H, D = q.shape
    with jax.named_scope("istpu.mla.absorb"):
        wk, wv = _latent_kvb(w_kvb, H, nope)
        (rows,) = gather_layer_kv(cache, layer, block_table)
        rows = rows[:, :, 0]                               # [B, S_max, W]
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :nope], wk)
        qq = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)  # [B, H, W]
        logits = jnp.einsum("bhw,bsw->bhs", qq, rows,
                            preferred_element_type=jnp.float32)
        logits = logits * (1.0 / np.sqrt(D))
        mask = jnp.arange(rows.shape[1])[None, :] < seq_lens[:, None]
        logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs, rows[..., :rank])
        return jnp.einsum("bhr,rhv->bhv", o_lat, wv)


def decode_kernel_engages(q, cache, window=None, softcap=None) -> bool:
    """Whether the TPU's kernel (``paged_decode_kernel``) can read this page
    for this query: bf16 K and V
    by head in ``[T, D]`` tiles the TPU copies whole, a bf16 query, and
    attention over every live key (a window or a soft cap keeps the XLA
    form).  The tile's 128 lanes are what the kernel needs of ``D``: a page
    whose heads are narrower holds several side by side in one row (heads
    of 64 in pairs, models/lfm2_moe.py ``kv_page``; ``paged_decode_attention``
    reads them so), and the query's width divides the page's.  Under a
    named mesh, only where ``tp`` alone divides the
    program: the KV heads divide over it; a cache whose layers are spread
    over another axis (``pp``) keeps the XLA form.  Static: shapes, dtypes
    and the mesh named while tracing; arrays or their abstract values."""
    if window is not None or softcap is not None or len(cache.shape) != 6:
        return False
    _, planes, Hkv, _, T, D = cache.shape
    axes = dict(jax.sharding.get_abstract_mesh().shape)
    tp = axes.pop("tp", 1)
    return (planes == 2 and T % 16 == 0 and D % 128 == 0
            and D % q.shape[-1] == 0
            and q.shape[-2] % Hkv == 0 and Hkv % tp == 0
            and all(n == 1 for n in axes.values())
            and cache.dtype == jnp.bfloat16 and q.dtype == jnp.bfloat16)


def paged_decode_attention(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_table: jax.Array,
    seq_lens: jax.Array,
    window: int | None = None,
    softcap: float | None = None,
    kv_split: tuple | None = None,
) -> jax.Array:
    """One-token decode attention against the paged cache.

    q: [B, H, D] (current token, RoPE already applied)
    cache: [L, 2, H_kv, n_blocks, T, D] (the whole cache); layer: the
    layer whose pages are read (static)
    block_table: [B, max_pages] int32
    seq_lens: [B] int32 -- number of valid tokens (including current)

    One reader per program, chosen by what is known when the program is
    lowered: for a TPU, where the page is bf16 K and V by head and every
    live key is attended (no window, no soft cap), the kernel of
    ``paged_decode_kernel`` copies each row's live pages out of the cache
    by the table; anywhere else the XLA form below, which gathers the
    whole padded table (``gather_layer_kv``) and masks by length.  The XLA
    form is the kernel's oracle in the tests.

    A page whose last axis is ``n x D`` holds ``n`` adjacent KV heads side by
    side in one row (cache ``[L, 2, H_kv / n, n_blocks, T, n x D]``: heads of
    64 fill a 128-lane tile in pairs).  The XLA form views the gathered rows
    as ``H_kv`` heads of ``D`` again; the kernel is handed each query head in
    its own KV head's lanes of a row of zeros (``lanes_of_own_head``).

    ``kv_split`` ``(heads, key width)``: a page of one plane whose row is the
    heads' keys and then their values, a value narrower than a key
    (``gather_layer_kv``): the XLA form, and the output is as wide as a value.
    """
    xla = functools.partial(_paged_decode_attention_xla, layer=layer,
                            window=window, softcap=softcap, kv_split=kv_split)
    if kv_split is not None or not decode_kernel_engages(
            q, cache, window, softcap):
        return xla(q, cache, block_table, seq_lens)
    # Pallas is a second of import: paid by the programs that can hold the kernel
    from . import paged_decode_kernel

    kernel = functools.partial(
        paged_decode_kernel.paged_decode_attention_kernel, layer=layer)
    if cache.shape[-1] != q.shape[-1]:
        kernel = functools.partial(_kernel_over_side_by_side_heads, kernel)
    return jax.lax.platform_dependent(
        q, cache, block_table, seq_lens, default=xla, tpu=kernel)


def lanes_of_own_head(x: jax.Array, n: int, heads: int, out: bool = False):
    """Between a query by head ``[B, H, D]`` and the same query laid into rows
    as wide as a page that holds ``n`` KV heads side by side ``[B, H, n x D]``:
    a query head whose KV head is the ``j``-th of its row has its values in
    lanes ``[j D, (j + 1) D)`` and zeros in the others, so that the row's dot
    product with the page's row is its dot product with its own head's key.
    ``heads``: the page's rows of heads (``H_kv / n``).  ``out``: the way back,
    which keeps of each weighted sum of rows the lanes of the head's own
    values."""
    B, H = x.shape[:2]
    own = jnp.eye(n, dtype=x.dtype)
    if out:
        D = x.shape[-1] // n
        return jnp.einsum("bpjgkd,jk->bpjgd",
                          x.reshape(B, heads, n, -1, n, D), own).reshape(B, H, D)
    D = x.shape[-1]
    return jnp.einsum("bpjgd,jk->bpjgkd",
                      x.reshape(B, heads, n, -1, D), own).reshape(B, H, n * D)


def _kernel_over_side_by_side_heads(kernel, q, cache, block_table, seq_lens):
    n, heads = cache.shape[-1] // q.shape[-1], cache.shape[2]
    o = kernel(lanes_of_own_head(q, n, heads), cache, block_table, seq_lens,
               scale=1.0 / np.sqrt(q.shape[-1]))
    return lanes_of_own_head(o, n, heads, out=True)


def _paged_decode_attention_xla(q, cache, block_table, seq_lens, *, layer,
                                window=None, softcap=None, kv_split=None):
    """``paged_decode_attention`` in plain ``jax.numpy``: the table's pages
    gathered, contracted against the grouped query, masked by length."""
    B, H, D = q.shape
    k, v = gather_layer_kv(cache, layer, block_table,
                           **({"kv_split": kv_split} if kv_split else {}))
    if k.shape[-1] != D:    # KV heads side by side in a row: by head again
        k, v = (x.reshape(B, x.shape[1], -1, D) for x in (k, v))
    S_max, Hkv = k.shape[1:3]
    # query head h pairs with KV head h // G: [B, H_kv, G, D] is that
    # pairing as a reshape, and the pages are contracted as gathered
    q = q.reshape(B, Hkv, H // Hkv, D)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bhgd,bkhd->bhgk", q, k).astype(jnp.float32) * scale
    if softcap is not None:  # Gemma-2 logit soft-capping
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(S_max)
    mask = pos[None, :] < seq_lens[:, None]  # [B, S_max]
    if window is not None:
        # current token sits at seq_lens-1; window covers (q - W, q]
        mask &= pos[None, :] >= seq_lens[:, None] - window
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v.dtype), v)
    return out.reshape(B, H, v.shape[-1])


def paged_multitoken_attention_xla(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_table: jax.Array,
    positions: jax.Array,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Attention for a short run of new tokens against the paged cache
    (the speculative-decode verify step: S proposal tokens attend to the
    whole paged history plus themselves, causally by absolute position).

    q: [B, S, H, D] (RoPE applied); cache: [L, 2, H_kv, n_blocks, T, D]
    and the (static) layer to read — the new tokens' K/V must already be
    scattered into the pages;
    block_table: [B, max_pages] int32; positions: [B, S] int32 absolute
    positions of the new tokens.  Masking is purely positional: a key in a
    gathered page is visible iff its absolute position <= the query's, which
    also hides stale slots past the sequence end.  Returns [B, S, H, D].
    """
    B, S, H, D = q.shape
    k, v = gather_layer_kv(cache, layer, block_table)
    S_max, Hkv = k.shape[1:3]
    # grouped as in paged_decode_attention: the pages as gathered
    q = q.reshape(B, S, Hkv, H // Hkv, D)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bshgd,bkhd->bhgsk", q, k).astype(jnp.float32) * scale
    if softcap is not None:  # Gemma-2 logit soft-capping
        logits = softcap * jnp.tanh(logits / softcap)
    k_pos = jnp.arange(S_max)
    mask = k_pos[None, None, :] <= positions[:, :, None]  # [B, S, S_max]
    if window is not None:
        mask &= k_pos[None, None, :] > positions[:, :, None] - window
    logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgsk,bkhd->bshgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, D)


def window_prefix_positions(rows: int, start) -> tuple[jax.Array, jax.Array]:
    """Of a window layer's prefix buffer, ``rows`` rows that END where the
    chunk starts (``start``, the prefix's length: static or traced): each
    row's absolute position and whether it holds a key at all (a row that
    would lie before position 0 does not).  The buffer is right-aligned so
    that keeping it is static slicing: after a chunk it is the last ``rows``
    rows of itself and the chunk's own (engine ``_prefill_chunk``)."""
    pos = start - rows + jnp.arange(rows)
    return pos, pos >= 0


def window_page_span(window: int, block_tokens: int) -> int:
    """Pages that can hold a key visible through a window of ``window``
    tokens ending at any position: the window's tokens lie in at most
    ``ceil(window / T) + 1`` consecutive pages, whatever its alignment."""
    return -(-window // block_tokens) + 1


def paged_window_decode_attention(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_table: jax.Array,
    seq_lens: jax.Array,
    window: int,
    sink: jax.Array | None = None,
    kv_split: tuple | None = None,
) -> jax.Array:
    """One-token decode attention of a sliding-window layer: the row's
    WINDOW'S pages are gathered, and no others.

    ``paged_decode_attention`` with a window gathers the whole table and
    masks what lies outside; here the table's slots are picked first, by
    index arithmetic on each row's length: the first page that can hold a
    visible key is ``max(seq_len - window, 0) // T`` and the window spans at
    most ``window_page_span`` pages from it (one static width).  A page
    below it is never read, so what it holds, or whether it was ever
    filled, cannot reach the arithmetic.  Same arguments and the same
    function of the visible keys: a key at ``j`` is visible to the token at
    ``i = seq_len - 1`` iff ``i - window < j <= i``.  ``sink`` [H], float32:
    one learned logit a query head in the softmax's denominator
    (``softmax_with_sink``); None for a family that has none, whose program
    is what it was.  ``kv_split``: as ``gather_layer_kv``."""
    B, H, D = q.shape
    T = cache.shape[4]
    width = block_table.shape[1]
    span = min(window_page_span(window, T), width)
    first = jnp.maximum(seq_lens - window, 0) // T               # [B]
    slots = first[:, None] + jnp.arange(span)[None, :]           # [B, span]
    sub = jnp.take_along_axis(block_table, jnp.minimum(slots, width - 1),
                              axis=1)
    k, v = gather_layer_kv(cache, layer, sub,
                           **({"kv_split": kv_split} if kv_split else {}))
    Hkv = k.shape[2]
    q = q.reshape(B, Hkv, H // Hkv, D)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bhgd,bkhd->bhgk", q, k).astype(jnp.float32) * scale
    # absolute position of each gathered key, from the slot it was taken
    # from (a slot past the table's end reads the last page again and lies
    # past the row's length, so it is masked)
    pos = first[:, None] * T + jnp.arange(span * T)[None, :]     # [B, span*T]
    mask = (pos < seq_lens[:, None]) & (pos >= seq_lens[:, None] - window)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    probs = softmax_with_sink(
        logits, None if sink is None else sink.reshape(Hkv, H // Hkv))
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v.dtype), v)
    return out.reshape(B, H, v.shape[-1])


def grouped_chunk_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    k_valid: jax.Array | None = None,
    window: int | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Attention of a prefill chunk by ABSOLUTE positions, one key/value
    head at a time.

    q: [B, Sq, H, D]; k: [B, Sk, H_kv, D], v: [B, Sk, H_kv, Dv] (a value may
    be narrower than a key; whatever rows the caller chose
    to hand over: a whole prefix buffer or a window's rows, then the
    chunk's own); ``sink`` [H] float32: one logit a query head in the
    softmax's denominator (``softmax_with_sink``), None for none; q_pos [Sq], k_pos [Sk] absolute positions; k_valid [Sk]
    marks rows that hold a key at all (a padded buffer's slack is not one).
    A key is visible iff valid, ``k_pos <= q_pos`` and, with ``window``,
    ``k_pos > q_pos - window``.

    The query is viewed [.., H_kv, G, D] and each group is contracted with
    its head's keys as they are (no ``repeat_kv`` copy), under ``lax.map``
    over the KV heads: the scores that exist at once are [G, Sq, Sk], an
    H_kv-th of all heads' (at 128 query heads over 8, a 512-token chunk
    over a 16k prefix: 0.55 GB in float32 where all heads' are 4.4 GB)."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if k_valid is not None:
        mask &= k_valid[None, :]

    def one_head(args):
        qh, kh, vh = args[:3]       # [B, Sq, G, D], [B, Sk, D], [B, Sk, Dv]
        logits = jnp.einsum("bsgd,bkd->bgsk", qh, kh).astype(jnp.float32) * scale
        probs = softmax_with_sink(
            jnp.where(mask[None, None], logits, -jnp.inf),
            args[3][None, :, None] if sink is not None else None)
        return jnp.einsum("bgsk,bkd->bsgd", probs.astype(vh.dtype), vh)

    heads = (jnp.moveaxis(q.reshape(B, Sq, Hkv, G, D), 2, 0),
             jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0))
    if sink is not None:
        heads += (sink.reshape(Hkv, G),)
    out = jax.lax.map(one_head, heads)                   # [Hkv, B, Sq, G, Dv]
    return jnp.moveaxis(out, 0, 2).reshape(B, Sq, H, v.shape[-1])
