"""The dense prefill chunk's attention as one TPU kernel over the LIVE rows
of the prefix buffer and the chunk's own keys.

The XLA form (``attention.causal_attention``) writes its scores out: in the
program XLA:TPU makes of a 512-token chunk over a 4,096-row prefix buffer,
every layer holds ``bf16[H, 512, 4608]`` scores, their float32 exponentials
(264 MB at 28 heads) and the ``repeat_kv`` broadcast of K and of V, over the
whole bucket, slack included (PERF.md, PR 48).  Here nothing of shape
``[H, Sq, Sk]`` exists.  The grid runs over (row of the batch, KV head, query
block, key block); a KV head's ``G = H // H_kv`` query heads are columns of
ONE query tile ``[BLOCK, G x D]`` held in VMEM while that head's key blocks
pass, so K and V are read once a query block and not once a query head; the
softmax is online, in float32, bf16 operands feed the MXU as they are, and
the probabilities are cast to V's dtype before PV as the XLA form casts them.

The scores of a block are held TRANSPOSED, ``[keys, queries]``: the running
maximum and sum of a query are then reductions down the rows (elementwise
over vector registers, one sublane fold at the end) and ``m``, ``l`` are
lane-dense ``[1, BLOCK]`` rows.  With queries down the rows the same
reductions run across the 128 lanes and ``m``, ``l`` are ``[BLOCK, 1]``
columns, one lane a register: on the chip that form cost half as much again
(PERF.md, PR 48).  The accumulator is ``[D, queries]`` and is transposed once,
when a query block's last key block is done.

Not the kernel PR 32 deleted: that one ran ONE query head and a 128 x 128
tile a grid step (4,032 steps a layer at the largest bucket, each 0.04 us of
MXU under its own overhead) and fetched K and V again for each head of a
group.  This one is 4 x 7 steps a layer there, of 0.9 GFLOP each.

Only live key blocks are visited: the prefix's blocks below
``ceil(prefix_len / BLOCK)``, then the chunk's own up to the query block's
diagonal.  A step past them skips its arithmetic (``pl.when``) and its index
map names the block already held, so the bucket's slack is never fetched.
The last live prefix block is masked by ``prefix_len`` (and its values past
it zeroed: what a masked key multiplies must be finite).  The diagonal block
is walked in two halves of its queries, each against the keys up to its own
last row, so a quarter of its products is never formed.  A row's arithmetic
depends on the live blocks and their order only, never on the bucket: one
chunk over a 2,048 and over a 4,096 bucket with equal ``prefix_len`` is
bit-equal (the rule of ``paged_decode_kernel``).

Operands are the arrays as the model holds them, viewed ``[B, S, heads x
D]`` (a reshape, no copy): a head is a block of 128-lane columns, so no
transpose or re-layout stands before or after the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# query rows and key rows a block: a whole chunk of the served cells
BLOCK = 512
_MASKED = -0.7 * float(np.finfo(np.float32).max)


def _kernel(plen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, group: int, scale: float):
    G, D = group, k_ref.shape[-1]
    i, s = pl.program_id(2), pl.program_id(3)
    live = plen_ref[0] - s * BLOCK         # rows of prefix block s that are valid
    n_prefix = pl.cdiv(plen_ref[0], BLOCK)  # live blocks of the prefix buffer
    own = s - n_prefix                     # which of the chunk's own blocks

    @pl.when(s == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(queries=slice(None), keys=BLOCK, visible=None, v=None):
        """``queries`` (columns of the block) against its first ``keys`` rows."""
        k = k_ref[:keys]
        vt = (v_ref[...] if v is None else v)[:keys].T        # [D, keys]
        # G copies of the body and not a loop over g: the compiler lays one
        # head's products beside the last one's softmax, and a loop cost a
        # third more on the chip (1.76 against 1.32 ms a chunk; PERF.md, PR 48)
        for g in range(G):
            x = lax.dot_general(
                k, q_ref[queries, g * D:(g + 1) * D], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [keys, queries]
            if visible is not None:
                x = jnp.where(visible, x, _MASKED)
            m = m_ref[g, :, queries]
            m_new = jnp.maximum(m, x.max(axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(x - m_new)
            m_ref[g, :, queries] = m_new
            l_ref[g, :, queries] = (alpha * l_ref[g, :, queries]
                                    + p.sum(axis=0, keepdims=True))
            acc_ref[g, :, queries] = alpha * acc_ref[g, :, queries] + (
                lax.dot_general(vt, p.astype(vt.dtype), (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    # a whole block: of the prefix, or one of the chunk's own before the diagonal
    pl.when(jnp.where(own < 0, live >= BLOCK, own < i))(attend)

    @pl.when((own < 0) & (live < BLOCK))
    def _():        # the prefix's last live block: its rows below prefix_len
        row = lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
        attend(visible=row < live,
               v=jnp.where(row < live, v_ref[...], jnp.zeros_like(v_ref)))

    @pl.when(own == i)
    def _():        # the diagonal: each half of the queries, the keys up to its end
        half = BLOCK // 2
        for lo in (0, half):
            key = lax.broadcasted_iota(jnp.int32, (lo + half, half), 0)
            query = lo + lax.broadcasted_iota(jnp.int32, (lo + half, half), 1)
            attend(slice(lo, lo + half), lo + half, key <= query)
        for g in range(G):
            o_ref[:, g * D:(g + 1) * D] = (
                acc_ref[g] / l_ref[g]).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("prefix_pad", "interpret"))
def _call(q, k, v, prefix_len, prefix_pad, interpret=False):
    """The kernel on one device's arrays, as ``chunk_attention_kernel`` takes
    them; prefix_len: int32[1].  A jit of its own, so that a program's layers
    share ONE traced and lowered kernel (``paged_decode_kernel._call``)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1:3]
    G = H // Hkv
    before = prefix_pad // BLOCK           # blocks of the prefix buffer

    def kv_block(b, h, i, s, plen_ref):
        # a step past the query block's live keys names the block it holds
        n_prefix = pl.cdiv(plen_ref[0], BLOCK)
        own = jnp.minimum(s - n_prefix, i)
        return b, jnp.where(s < n_prefix, s, before + own), h

    q_spec = pl.BlockSpec((None, BLOCK, G * D), lambda b, h, i, s, _: (b, i, h))
    kv_spec = pl.BlockSpec((None, BLOCK, D), kv_block)
    out = pl.pallas_call(
        functools.partial(_kernel, group=G, scale=1.0 / np.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, Sq // BLOCK, before + Sq // BLOCK),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((G, 1, BLOCK), jnp.float32),
                pltpu.VMEM((G, 1, BLOCK), jnp.float32),
                pltpu.VMEM((G, D, BLOCK), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="chunk_attention",
        interpret=interpret,
    )(prefix_len, q.reshape(B, Sq, H * D), k.reshape(B, Sk, Hkv * D),
      v.reshape(B, Sk, Hkv * D))
    return out.reshape(B, Sq, H, D)


def chunk_attention_kernel(q, k, v, prefix_pad=0, prefix_len=None, *,
                           interpret=False):
    """``attention.causal_attention`` without window or soft cap, in its two
    multi-chunk forms, on the TPU (``interpret=True``: on any backend, for
    the tests).

    q: [B, Sq, H, D]; k, v: [B, prefix_pad + Sq, H_kv, D], all bf16: the
    first ``prefix_pad`` rows a prefix buffer of which ``prefix_len`` (a
    traced scalar) are valid, then the queries' own.  ``prefix_pad`` 0 and
    ``prefix_len`` None: a prompt's first chunk, no prefix.  ``Sq`` and
    ``prefix_pad`` are multiples of ``BLOCK``.  -> [B, Sq, H, D]."""
    Sq = q.shape[1]
    assert Sq % BLOCK == 0 and prefix_pad % BLOCK == 0, (Sq, prefix_pad)
    assert k.shape[1] == prefix_pad + Sq and k.shape == v.shape, (
        q.shape, k.shape, v.shape, prefix_pad)
    plen = (jnp.zeros((1,), jnp.int32) if prefix_len is None
            else jnp.asarray(prefix_len, jnp.int32).reshape(1))
    return _call(q, k, v, plen, prefix_pad=prefix_pad, interpret=interpret)
