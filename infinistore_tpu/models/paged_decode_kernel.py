"""The dense decode attention as one TPU kernel that reads a row's LIVE
pages straight out of the whole cache by its block table.

The XLA form (``attention._paged_decode_attention_xla``) gathers every padded
row's whole padded table, lays the result out a second time and contracts
it: at 32 rows x 256 pages over a table that is half padding that was
three quarters of the decode scan (PERF.md, PR 33).  Here nothing of shape
``[B, pages, ...]`` exists: the cache stays in HBM as the program holds it,
``[L, 2, H_kv, n_blocks, T, D]``, and for each row the kernel copies
``ceil(seq_len / T)`` pages, K and V of every KV head in ONE strided copy
a page (``cache[layer, :, :, page]``: 2 x H_kv tiles of ``[T, D]``),
``PAGES_PER_BLOCK`` pages a block into one of two VMEM buffers, the next
block (of this row or of the next) in flight while this one is contracted.
A block whose pages are all live (by the row's length: every block of a row
but its last) starts its copies in one run of a static count and awaits
them in ONE wait for the buffer's bytes; a row's last block walks its own
count, once to start and once to wait.
The softmax is online, in float32, over the group's ``G = H // H_kv``
query heads; bf16 pages feed the MXU as they are.

Not the kernel PR 32 deleted: that one was handed ``cache[layer]``, a slab
XLA:TPU copies before the call.  This one is handed the whole cache and
finds layer, plane and head by index.

A row's arithmetic depends on its own length and pages only (the pages a
block are a constant, not a function of the batch or of the table's
width), so a sequence's output is bit-equal whatever it is batched with.
A page id past the pool marks a pad row (``engine._block_table``): no page
of it is read and its output is zeros.  A page past a row's length is
never read; the tail of its last page is masked by length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

# pages a block: 512 tokens of K and V for every KV head, 1-2 MB a buffer.
# Read on the chip (PERF.md, PR 36): 8 and 16 pages cost a fifth to a half
# more at 27 rows, 64 pages 11% less there and 10% more at one row.
# What the scalar core does a page sets this kernel's pace, not the memory.
# Read on the chip (PERF.md, PR 53; ms a call at 29 rows of 64-200 pages, 8
# rows of 512-1,024, one row of 190): every page in a loop walked twice a
# block, to start and to wait (the kernel before), 0.244 / 0.378 / 0.0226;
# whole blocks awaited in ONE wait, their starts still a loop: 0.226 / 0.343
# / 0.0224; their starts unrolled besides (what ``block`` does): 0.201 /
# 0.300 / 0.0224, 55 and 49 ns a page where the bytes allow 40, at a lowering
# 0.1 s dearer.  Mosaic unrolls a loop by 1 or by its whole count and refuses
# anything between.  The last block as a ladder of static runs and waits by
# the bits of its count gained nothing (a twentieth of the cells' pages) and
# cost 4% at one row
PAGES_PER_BLOCK = 32
_MASKED = -0.7 * float(np.finfo(np.float32).max)


def pages_by_fill(lens: np.ndarray, block_tokens: int, width: int) -> tuple:
    """On the host, what one call of the kernel copies for live rows of these
    lengths under a table ``width`` pages wide: (all its pages, those of them
    in a WHOLE block, ``PAGES_PER_BLOCK`` live pages: the blocks ``block``
    starts in one static run and awaits in one wait).  ``lens``: any shape."""
    pages = np.minimum(-(-np.asarray(lens) // block_tokens), width)
    whole = pages // PAGES_PER_BLOCK * PAGES_PER_BLOCK
    return int(pages.sum()), int(whole.sum())


def _kernel(table_ref, lens_ref, layer_ref, q_ref, cache_ref, o_ref, buf, sems,
            *, width: int, scale: float):
    B, Hkv, G, D = q_ref.shape
    layer = layer_ref[0]
    n_blocks, T = cache_ref.shape[3], cache_ref.shape[4]
    P = PAGES_PER_BLOCK

    def n_pages(b):
        # a pad row's table names a page past the pool: nothing of it is read
        live = table_ref[b * width] < n_blocks
        return jnp.where(live, jnp.minimum(pl.cdiv(lens_ref[b], T), width), 0)

    def n_chunks(b):
        return pl.cdiv(n_pages(b), P)

    def page_copy(b, i, slot, j):
        page = jnp.minimum(table_ref[b * width + i * P + j], n_blocks - 1)
        return pltpu.make_async_copy(
            cache_ref.at[layer, :, :, page], buf.at[slot, j], sems.at[slot])

    def block(b, i, slot, do):
        # ``do`` ("start" or "wait") to the copies of block i of row b.  The
        # scalar core sets the pace of this kernel, not the memory (see
        # PAGES_PER_BLOCK), so a WHOLE block, all P pages live (by the row's
        # length: every block of a row but its last), starts its copies in a
        # run of a static count and awaits them in ONE wait: each page's copy
        # signals the slot's semaphore, and a descriptor as large as the slot
        # stands for the P of them.  A row's last block walks its own count.
        live = n_pages(b) - i * P

        def each(j, _):
            getattr(page_copy(b, i, slot, j), do)()
            return 0

        @pl.when(live >= P)
        def _():
            if do == "wait":
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sems.at[slot]).wait()
            else:
                lax.fori_loop(0, P, each, 0, unroll=True)

        @pl.when(live < P)
        def _():
            lax.fori_loop(0, live, each, 0)

    def next_live_row(r):
        # the first row from r on that has a page to read, or B
        return lax.while_loop(
            lambda r: (r < B) & (n_chunks(jnp.minimum(r, B - 1)) == 0),
            lambda r: r + 1, r)

    def start(b, i, slot):
        @pl.when(b < B)
        def _():
            block(b, i, slot, "start")

    # the buffers' slots past a row's last page keep what an earlier block
    # left there: zeros first, so that what a masked key multiplies is finite
    buf[...] = jnp.zeros_like(buf)
    start(next_live_row(0), 0, 0)

    def row(b, slot):
        length = lens_ref[b]
        chunks = n_chunks(b)

        def chunk(i, carry):
            slot, m, l, acc = carry

            # the next block in flight while this one is contracted: this
            # row's, or behind its last the next live row's first (row b
            # itself is live, so the search from b stands still).  ONE site,
            # so that the static run is in the program twice, not four times
            last = i + 1 == chunks
            start(next_live_row(jnp.where(last, b + 1, b)),
                  jnp.where(last, 0, i + 1), 1 - slot)
            block(b, i, slot, "wait")
            pos = i * (P * T) + lax.broadcasted_iota(jnp.int32, (G, P * T), 1)
            visible = pos < length
            m_out, l_out, acc_out = [], [], []
            for h in range(Hkv):
                k = buf[slot, :, 0, h].reshape(P * T, D)
                v = buf[slot, :, 1, h].reshape(P * T, D)
                s = lax.dot_general(
                    q_ref[b, h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(visible, s, _MASKED)
                m_new = jnp.maximum(m[h], s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m[h] - m_new)
                p = jnp.exp(s - m_new)
                m_out.append(m_new)
                l_out.append(alpha * l[h] + p.sum(axis=-1, keepdims=True))
                acc_out.append(alpha * acc[h] + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            return 1 - slot, tuple(m_out), tuple(l_out), tuple(acc_out)

        init = (slot,
                (jnp.full((G, 1), _MASKED, jnp.float32),) * Hkv,
                (jnp.zeros((G, 1), jnp.float32),) * Hkv,
                (jnp.zeros((G, D), jnp.float32),) * Hkv)
        slot, _, l, acc = lax.fori_loop(0, chunks, chunk, init)
        for h in range(Hkv):
            # a row with no page (a pad row) reads 0 / 1
            o_ref[b, h] = acc[h] / jnp.where(l[h] > 0.0, l[h], 1.0)
        return slot

    lax.fori_loop(0, B, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def _call(q, cache, table, seq_lens, layer, interpret=False, scale=None):
    """The kernel on one device's arrays.  q: [B, H_kv, G, D]; table: [B,
    width]; layer: int32[1] -> [B, H_kv, G, D] float32.

    The layer is an operand and the call a jit of its own, so that a
    program's layers share ONE traced and lowered kernel: lowering a kernel
    is a second of Python that no compile cache spares, and a model's layers
    times a server's decode programs (72 in a cell's warm-up) of them made
    set-up a minute longer (PERF.md, PR 36)."""
    B, Hkv, G, D = q.shape
    T = cache.shape[4]
    return pl.pallas_call(
        functools.partial(_kernel, width=table.shape[1],
                          scale=scale or 1.0 / np.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, PAGES_PER_BLOCK, 2, Hkv, T, D), cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        name="paged_decode_attention",
        interpret=interpret,
    )(table.reshape(-1), seq_lens, layer, q, cache)


def paged_decode_attention_kernel(q, cache, block_table, seq_lens, *, layer,
                                  interpret=False, scale=None):
    """``attention.paged_decode_attention`` without window or soft cap, on
    the TPU (``interpret=True``: on any backend, for the tests).

    q: [B, H, D]; cache: [L, 2, H_kv, n_blocks, T, D] bf16, the whole
    cache, left in HBM; block_table: [B, width] int32; seq_lens: [B];
    layer: a Python int.  -> [B, H, D] in q's dtype.  ``scale``: the
    scores' scale where it is not ``1 / sqrt(D)`` (a query laid into a row
    wider than its head: attention.lanes_of_own_head)."""
    B, H, D = q.shape
    Hkv = cache.shape[2]
    args = (q.reshape(B, Hkv, H // Hkv, D), cache,
            block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
            jnp.full((1,), layer, jnp.int32))
    call = functools.partial(_call, interpret=interpret, scale=scale)
    mesh = jax.sharding.get_abstract_mesh()
    if dict(mesh.shape).get("tp", 1) > 1:
        # a program partitioned over a mesh (parallel/sharding.py, the
        # engine's ``mesh=``) cannot split a kernel by itself; whoever traces
        # the model under such a mesh names it (``use_abstract_mesh``), and
        # each device then runs the kernel on its own KV heads' share of the
        # query and of the cache, the table and the lengths whole
        heads, whole = PartitionSpec(None, "tp"), PartitionSpec()
        call = jax.shard_map(
            call, in_specs=(heads, PartitionSpec(None, None, "tp"),
                            whole, whole, whole),
            out_specs=heads, check_vma=False)
    return call(*args).reshape(B, H, D).astype(q.dtype)
