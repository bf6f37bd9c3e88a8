"""int8 KV-cache quantization for the store path.

The reference moves KV pages at their native dtype (fp16/bf16) because RDMA
bandwidth is cheap next to PCIe (reference: infinistore/lib.py:425-542 moves
raw ``data_ptr()`` bytes).  On a TPU-VM the store hop is host memcpy (shm) or
DCN TCP — both byte-bound — so halving page bytes halves the cost of every
save, load, and cross-host prefix fetch.  This module quantizes KV pages to
int8 *on device* (one fused jit: amax-reduce + scale + round + bitcast) and
packs scales into the page payload itself, so the store sees a single opaque
key per page, the same wire protocol, and exactly half-plus-epsilon bytes.

Scheme: symmetric per-(K|V, head) scaling within each (layer, page) page —
the granularity at which attention consumes KV (one head's page tile at a
time), so quantization error never crosses heads.  Payload layout per page::

    [2*H float32 scales][2*H*T*D int8 values]      (page_quant_bytes total)

Only pages of two planes, K and V by head, have this scale: the transfer
engine refuses ``quant`` for a page of any other make (a latent page of one
plane) when it is built, so such a page never meets a wrong scale.

Accuracy: KV values are post-RMSNorm projections with small dynamic range;
per-head int8 keeps relative error ~1e-2, which leaves greedy decode tokens
unchanged on every model we test (tests/test_kv.py::test_quantized_*).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cache import PagedCacheConfig

SCALE_DTYPE = jnp.float32


def page_quant_bytes(cfg: PagedCacheConfig) -> int:
    """Bytes of one quantized (layer, chunk) page: scales + int8 data."""
    h2 = 2 * cfg.n_kv_heads
    return h2 * np.dtype(np.float32).itemsize + h2 * cfg.block_tokens * cfg.head_dim


@jax.jit
def quantize_pages(pages: jax.Array) -> jax.Array:
    """[L, n, 2, H, T, D] (any float dtype) -> packed uint8 [L, n, page_quant_bytes].

    One fused program: amax over (T, D), scale, round-to-nearest-even, pack
    scales and values into contiguous per-page byte rows (what the batched
    put writes straight into the pool).
    """
    L, n, two, H, T, D = pages.shape
    x = pages.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(4, 5))  # [L, n, 2, H]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(SCALE_DTYPE)
    q = jnp.round(x / scale[..., None, None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    scale_u8 = jax.lax.bitcast_convert_type(scale, jnp.uint8).reshape(L, n, two * H * 4)
    q_u8 = jax.lax.bitcast_convert_type(q, jnp.uint8).reshape(L, n, two * H * T * D)
    return jnp.concatenate([scale_u8, q_u8], axis=-1)


def dequantize_pages(
    packed: jax.Array, cfg: PagedCacheConfig
) -> jax.Array:
    """Packed uint8 [L, n, page_quant_bytes] -> [L, n, 2, H, T, D] cfg.dtype."""
    L, n, _ = packed.shape
    H, T, D = cfg.n_kv_heads, cfg.block_tokens, cfg.head_dim
    h2 = 2 * H
    scale_u8 = packed[:, :, : h2 * 4].reshape(L, n, 2, H, 4)
    q_u8 = packed[:, :, h2 * 4 :].reshape(L, n, 2, H, T, D)
    scale = jax.lax.bitcast_convert_type(scale_u8, SCALE_DTYPE)  # [L, n, 2, H]
    q = jax.lax.bitcast_convert_type(q_u8, jnp.int8).astype(jnp.float32)
    return (q * scale[..., None, None]).astype(cfg.dtype)


_dequantize_pages = jax.jit(dequantize_pages, static_argnums=1)


def dequantize_pages_jit(packed: jax.Array, cfg: PagedCacheConfig) -> jax.Array:
    return _dequantize_pages(packed, cfg)


def quantization_error(pages: jax.Array, cfg: PagedCacheConfig) -> Tuple[float, float]:
    """(max_abs_err, max_rel_err vs per-head amax) of a quantize round-trip —
    diagnostic for tests and capacity planning."""
    packed = quantize_pages(pages)
    back = dequantize_pages_jit(packed, cfg)
    x = pages.astype(jnp.float32)
    err = jnp.abs(back.astype(jnp.float32) - x)
    amax = jnp.max(jnp.abs(x), axis=(4, 5), keepdims=True)
    rel = jnp.where(amax > 0, err / amax, 0.0)
    return float(jnp.max(err)), float(jnp.max(rel))
