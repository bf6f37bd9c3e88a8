"""The selective scan of a Mamba-1 layer (models/jamba.py): per channel ``c``
and state ``n``

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n s_t[n, c] C_t[n]

over a chunk's tokens, continuing the state it is given; float32 inside.
The state is held ``[N, channels]`` (states on sublanes, channels on the 128
lanes: ``[channels, 16]`` as published would fill an eighth of a tile), and
``A`` comes transposed to match.  ``D x`` and the gate are the caller's: they
are elementwise over the chunk and XLA fuses them.

**A token with ``dt = 0`` is the identity** (``exp(0) = 1``, nothing added):
the caller zeroes ``dt`` at a padded position and that position enters no
state, whatever its ``x``.

Two forms, one arithmetic (the same expression in the same order, so they
agree to float32 rounding: the exponential is the platform's):

* ``selective_scan_plain``: a ``lax.scan`` over the tokens.  The oracle, the
  CPU's path, and ``selective_step`` is its one step for the decode scan.
* ``selective_scan_kernel``: one Pallas launch a layer a chunk on the TPU.
  The grid walks blocks of ``TOKENS`` tokens and, inside each, blocks of
  ``LANES`` channels; a channel block's state ``[N, LANES]`` stays in VMEM
  from the chunk's first token to its last, and a token block's ``B`` and
  ``C`` are fetched once for all its channel blocks.  ``B_t[n]`` is wanted as
  a COLUMN over the sublanes, the same for every lane: the caller hands ``B``
  and ``C`` broadcast over one tile's lanes (``[T, N, 128]``, 4 MB a chunk of
  512), so a token's are one aligned ``[N, 128]`` load by a leading index and
  no lane is ever indexed dynamically.  Tokens go eight at a time (one
  sublane tile of ``x`` and ``dt``), unrolled, each over ``LANES / 128``
  independent lane blocks whose chains interleave.

``selective_scan`` picks by the platform a program is lowered for, as
``attention.paged_decode_attention`` does: a CPU program never holds the
kernel.  A row's arithmetic depends on its own chunk and state only, so the
same chunk from the same state gives the same bits whatever else runs.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

TOKENS = 128        # tokens a grid step
LANES = 512         # channels a grid step: 4 lane blocks, 8 vregs of state
_GROUP = 8          # tokens a loop step: one sublane tile of x and dt


def selective_step(s: jax.Array, x: jax.Array, dt: jax.Array, B: jax.Array,
                   C: jax.Array, At: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence, any leading axes: s [..., N, ch], x, dt
    [..., ch], B, C [..., N], At [N, ch], all float32 -> (y [..., ch], s)."""
    s = (jnp.exp(dt[..., None, :] * At) * s
         + (dt * x)[..., None, :] * B[..., :, None])
    return jnp.sum(s * C[..., :, None], axis=-2), s


def selective_scan_plain(x, dt, B, C, At, s0):
    """x, dt [T, ch], B, C [T, N], At, s0 [N, ch], float32 -> (y [T, ch], s)."""

    def one(s, xs):
        y, s = selective_step(s, *xs, At)
        return s, y

    s, y = lax.scan(one, s0, (x, dt, B, C))
    return y, s


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, s_in_ref, y_ref, s_out_ref,
            s_scr):
    from jax.experimental import pallas as pl

    ti, ci = pl.program_id(0), pl.program_id(1)
    tokens, lanes = x_ref.shape
    blocks = lanes // 128

    @pl.when(ti == 0)
    def _():
        s_scr[ci] = s_in_ref[0]

    a = a_ref[...]
    a_k = [a[:, k * 128:(k + 1) * 128] for k in range(blocks)]

    sub = lax.broadcasted_iota(jnp.int32, (_GROUP, 128), 0)

    def group(g, s_k):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        dt8 = dt_ref[pl.ds(t0, _GROUP), :]
        dx8 = dt8 * x_ref[pl.ds(t0, _GROUP), :]
        s_k = list(s_k)
        # a token's y is one row of a sublane tile: a tile is put together
        # from its eight and stored whole (no store by a dynamic row)
        y_k = [jnp.zeros((_GROUP, 128), jnp.float32)] * blocks
        for j in range(_GROUP):
            b, c = b_ref[t0 + j], c_ref[t0 + j]             # [N, 128]
            for k in range(blocks):
                at = slice(k * 128, (k + 1) * 128)
                s = (jnp.exp(dt8[j:j + 1, at] * a_k[k]) * s_k[k]
                     + dx8[j:j + 1, at] * b)
                s_k[k] = s
                y_k[k] = jnp.where(
                    sub == j, jnp.sum(s * c, axis=0, keepdims=True), y_k[k])
        for k in range(blocks):
            y_ref[pl.ds(t0, _GROUP), k * 128:(k + 1) * 128] = y_k[k]
        return tuple(s_k)

    s = s_scr[ci]
    s_k = lax.fori_loop(
        0, tokens // _GROUP, group,
        tuple(s[:, k * 128:(k + 1) * 128] for k in range(blocks)))
    s = jnp.concatenate(s_k, axis=1)
    s_scr[ci] = s
    # every visit writes: the last one, after the chunk's last token, stays
    s_out_ref[0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(x, dt, b, c, At, s0, interpret=False):
    """A jit of its own, so that a program's layers share one traced and
    lowered kernel (as ``paged_decode_kernel._call``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, ch = x.shape
    N = At.shape[0]
    tb, cb = min(TOKENS, T), min(LANES, ch)
    nc = ch // cb
    by_block = lambda a: a.reshape(N, nc, cb).transpose(1, 0, 2)  # noqa: E731
    y, s = pl.pallas_call(
        _kernel,
        grid=(T // tb, nc),
        in_specs=[
            pl.BlockSpec((tb, cb), lambda t, c: (t, c)),
            pl.BlockSpec((tb, cb), lambda t, c: (t, c)),
            pl.BlockSpec((tb, N, 128), lambda t, c: (t, 0, 0)),
            pl.BlockSpec((tb, N, 128), lambda t, c: (t, 0, 0)),
            pl.BlockSpec((N, cb), lambda t, c: (0, c)),
            pl.BlockSpec((1, N, cb), lambda t, c: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, cb), lambda t, c: (t, c)),
            pl.BlockSpec((1, N, cb), lambda t, c: (c, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, ch), jnp.float32),
                   jax.ShapeDtypeStruct((nc, N, cb), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nc, N, cb), jnp.float32)],
        name="ssm_selective_scan",
        interpret=interpret,
    )(x, dt, b, c, At, by_block(s0))
    return y, s.transpose(1, 0, 2).reshape(N, ch)


def kernel_engages(T: int, ch: int, N: int) -> bool:
    """Whether the kernel takes a chunk of these sizes: whole sublane tiles
    of states, whole lane tiles of channels, whole groups of tokens (a toy's
    widths and a short tail keep the plain form).  Static."""
    tb, cb = min(TOKENS, T), min(LANES, ch)
    return (N % 8 == 0 and ch % cb == 0 and cb % 128 == 0
            and T % tb == 0 and tb % _GROUP == 0)


def selective_scan_kernel(x, dt, B, C, At, s0, interpret=False):
    """``selective_scan_plain`` as the TPU's kernel (``interpret=True``: on
    any backend, for the tests)."""
    T, N = B.shape
    over_lanes = lambda a: jnp.broadcast_to(a[:, :, None], (T, N, 128))  # noqa: E731
    return _call(x, dt, over_lanes(B), over_lanes(C), At, s0,
                 interpret=interpret)


def selective_scan(x, dt, B, C, At, s0):
    """The chunk's scan: the kernel where the program is lowered for a TPU
    and the sizes are whole tiles, the plain form anywhere else."""
    if not kernel_engages(x.shape[0], x.shape[1], At.shape[0]):
        return selective_scan_plain(x, dt, B, C, At, s0)
    return lax.platform_dependent(
        x, dt, B, C, At, s0, default=selective_scan_plain,
        tpu=selective_scan_kernel)
