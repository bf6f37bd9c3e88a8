"""Paged KV cache in TPU HBM.

The reference's client stores KV blocks from GPU memory (GPUDirect RDMA from
``data_ptr()`` offsets); the TPU-native counterpart keeps the device cache as
one fused ``jax.Array`` of pages and moves whole pages with gather/scatter
under ``jit``:

    kv : [n_layers, 2(K|V), n_kv_heads, n_blocks, block_tokens, head_dim]

``PagedCacheConfig`` states the page once -- ``planes`` x ``n_kv_heads`` x
``block_tokens`` x ``head_dim`` -- and the cache, the token and chunk writes,
the gather, the transfer engine, the store's page size and the prefix keys all
follow from it.  The dense grouped-query families keep the two planes K and V
by head shown above.  A latent-attention family (models/mla_moe.py) keeps ONE
plane of one row per token, the normalised latent and the rotated key all
heads share: ``[n_layers, 1, 1, n_blocks, block_tokens, 576]``, a page of
``[T, 576]`` and half the bytes that "one head of 576" under K|V would take.

Heads sit OUTSIDE the block axis so a (head, page) tile [block_tokens,
head_dim] = [16, 128] is contiguous -- exactly the bf16 min tile.  The decode
step reads and writes this one array by index and never slices a layer out
of it: on a TPU the dense attention's kernel copies ``kv[layer, :, :, page]``,
2 x H_kv such tiles a page, for each row's live pages
(models/paged_decode_kernel.py); the XLA readers gather ``kv[layer, k|v, :,
block_table]`` (models/attention.py:gather_layer_kv); and ``write_token_kv``
below gathers and scatters ``kv[layer, :, :, block_ids]``, so the donated
cache is updated in place and no layer's slab is copied.

A page is ``block_tokens`` consecutive tokens of one layer's K+V (all heads)
-- the unit that maps 1:1 onto a store key (kv/hashing.chunk_keys x layer).
With Llama-3-8B shapes (8 kv-heads x 128 dim, 16-token pages, bf16) a page
is 64 KiB.

A stack that mixes sliding-window layers with layers that read everything
keeps one such array PER LAYER KIND (``PagedCacheConfig.pools``): the window
layers' pages are a pool of their own blocks, each pool with its own
allocator, residency and block table, so that a sequence holds window-layer
pages for its window only (engine.py).  Every other stack is the case "one
kind": one array, one block id across every layer.  Where the kinds write
pages of different shapes (4 key/value heads in the layers that read
everything, 8 in the window layers, a key wider than a value), the page is a
property of the POOL (``PagedCacheConfig.pool_kv``): one row a token of the
pool's heads' keys and then their values, side by side.

A family whose layers keep NO key or value per token (power retention,
models/retention.py) has no page at all: a sequence's whole past in one layer
is a STATE of fixed size.  ``StateCacheConfig`` states that unit, ``init_cache``
gives its slots, and ``StateSlots`` is their host-side bookkeeping: a running
row owns a slot and writes it; a checkpoint of a prefix is a slot that is never
written, held by the prefix's chunk key and COPIED into a row's slot when
adopted (a state summarises everything before it, so it cannot be shared the
way a read-only page is).

A stack that mixes layers that keep pages with layers that keep a state
(gated short convolutions among attention layers, models/lfm2_moe.py; Mamba
selective-scan layers, whose state is float32, models/jamba.py) holds
BOTH KINDS for one sequence: ``HybridCacheConfig`` is the paged cache over the
attention layers alone and, beside it, state slots over the others under the
same ``StateSlots`` bookkeeping; a prefix is reusable only at a position where
the pages up to it AND the state at it exist (engine/hybrid_engine.py).

Static shapes everywhere: gathers/scatters take fixed-width index vectors so
XLA compiles one program per (n_pages,) width; the host-side ``BlockAllocator``
is plain Python (never traced).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class PagedCacheConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    n_blocks: int
    block_tokens: int = 16
    dtype: jnp.dtype = jnp.bfloat16
    # planes of a page: 2 = K and V by head (the dense families); 1 = one
    # row per token and no K|V split (a latent page)
    planes: int = 2
    # PAGES BY LAYER KIND.  A stack that mixes sliding-window layers with
    # layers that read everything (a model config's ``layer_windows``) keeps
    # the window layers' pages in a POOL OF THEIR OWN, ``window_blocks``
    # blocks over ``window_layers``; the other layers' pages are the pool of
    # ``n_blocks``.  A sequence then has a block table per pool and holds
    # window-pool pages for its window only (engine.py).  Empty: one pool,
    # one block id across every layer.
    window_layers: Tuple[int, ...] = ()
    window_blocks: int = 0
    # A PAGE HAS THE SHAPE OF ITS POOL.  Where the layer kinds write pages of
    # different shapes (a model config's ``kv_pages``), per pool in ``pools``
    # order: ``(key/value heads, width of a key, width of a value)``.  Such a
    # pool's page is ONE plane of one row a token, its heads' keys side by
    # side and then their values, ``heads x (key + value)`` wide and not a
    # byte more: no key is padded to a value's width or a tile's.  (4 heads
    # of 192 are 768 lanes, 8 are 1,536, the whole rows 1,280 and 2,560: all
    # whole 128-lane tiles, where a minor axis of 192 or 320 would be padded
    # to the next one in HBM.)  Empty: every pool's page is the one
    # ``planes x n_kv_heads x head_dim`` above, which a config of this make
    # states for its first pool.
    pool_kv: Tuple[Tuple[int, int, int], ...] = ()

    @classmethod
    def for_model(cls, cfg, n_blocks: int, block_tokens: int = 16,
                  window_blocks: Optional[int] = None) -> "PagedCacheConfig":
        """The cache of a model: the page is what the model's config says
        it writes per token and layer (``cfg.kv_page`` = planes, heads,
        width), so no caller rebuilds it from head counts.  Where the
        model's layers are of two kinds (``cfg.layer_windows`` names a
        window for some and None for others) the window layers get a pool
        of ``window_blocks`` blocks; left out, as many as ``n_blocks``: a
        sequence never needs more window pages than pages of the other
        kind, so that pool can never be the one that runs out first."""
        planes, heads, width = cfg.kv_page
        windows = tuple(getattr(cfg, "layer_windows", ()) or ())
        window_layers = tuple(li for li, w in enumerate(windows)
                              if w is not None)
        if len(window_layers) == len(windows):
            window_layers = ()      # every layer windowed: one kind, one pool
        if window_blocks is not None and not window_layers:
            raise ValueError(
                "window_blocks sizes the pool of a stack's sliding-window "
                "layers beside its full ones; this model's layers are of "
                "one kind")
        pool_kv = tuple(getattr(cfg, "kv_pages", ())) if window_layers else ()
        return cls(n_layers=cfg.n_layers, n_kv_heads=heads, head_dim=width,
                   n_blocks=n_blocks, block_tokens=block_tokens,
                   dtype=cfg.dtype, planes=planes,
                   window_layers=window_layers,
                   window_blocks=((window_blocks or n_blocks)
                                  if window_layers else 0),
                   pool_kv=pool_kv)

    @property
    def pools(self) -> Tuple[Tuple[Tuple[int, ...], int], ...]:
        """``(layers, blocks)`` of each pool: the pool of ``n_blocks`` first
        (every layer, where the stack has one kind), then the window
        layers' pool."""
        if not self.window_layers:
            return ((tuple(range(self.n_layers)), self.n_blocks),)
        rest = tuple(li for li in range(self.n_layers)
                     if li not in self.window_layers)
        return ((rest, self.n_blocks),
                (self.window_layers, self.window_blocks))

    @property
    def cache_bytes(self) -> int:
        """Bytes of the cache as ``init_cache`` allocates it, every pool."""
        return sum(len(ls) * n * self.page_bytes_of(p)
                   for p, (ls, n) in enumerate(self.pools))

    @property
    def page_bytes(self) -> int:
        """Bytes of one (layer, chunk) page: every plane, all heads.  Of the
        FIRST pool where the pools' pages differ (``page_bytes_of``)."""
        return self.page_bytes_of(0)

    @property
    def page_shape(self) -> Tuple[int, ...]:
        """Shape of one (layer, chunk) page as stored: [planes, H_kv, T, D];
        the first pool's where they differ (``page_shape_of``)."""
        return self.page_shape_of(0)

    def page_shape_of(self, pool: int) -> Tuple[int, ...]:
        """``page_shape`` of pool ``pool``'s layers."""
        if not self.pool_kv:
            return (self.planes, self.n_kv_heads, self.block_tokens,
                    self.head_dim)
        heads, k_width, v_width = self.pool_kv[pool]
        return (1, 1, self.block_tokens, heads * (k_width + v_width))

    def page_bytes_of(self, pool: int) -> int:
        """``page_bytes`` of pool ``pool``'s layers."""
        return int(np.prod(self.page_shape_of(pool))) * np.dtype(
            jnp.dtype(self.dtype)).itemsize

    def pool_of(self, layer: int) -> int:
        """The pool that holds stack layer ``layer``'s pages."""
        return int(bool(self.window_layers) and layer in self.window_layers)


@dataclass(frozen=True)
class StateCacheConfig:
    """The cache of a family whose unit is a STATE, not a page: ``n_slots``
    slots of ``[n_layers, n_kv_heads, state_dim, head_dim]`` (``S``) and
    ``[n_layers, n_kv_heads, state_dim]`` (``z``), float32.  ``max_rows`` of
    them are the running rows' (each writes its own), the rest hold resident
    checkpoints (never written).  A checkpoint is kept every ``stride``
    tokens at most; ``block_tokens`` is the chunk of the prefix keys
    (kv/hashing.chunk_keys) and the unit ``n_blocks`` is counted in, so that
    ``n_blocks x block_tokens`` is the tokens the slots stand for, as for a
    paged cache: ``n_slots = n_blocks x block_tokens / stride``.

    The transfer engine moves ONE LAYER'S STATE where it moves a page
    (``page_shape`` / ``page_bytes``: ``S`` and ``z`` of one layer laid end
    to end by head), so the store, its keys and its staging need nothing
    new."""

    n_layers: int
    n_kv_heads: int
    state_dim: int
    head_dim: int
    n_blocks: int
    stride: int
    max_rows: int
    block_tokens: int = 16
    dtype: jnp.dtype = jnp.float32
    planes: int = 1             # no K|V split: int8 pages refuse it
    window_layers: Tuple[int, ...] = ()

    @classmethod
    def for_model(cls, cfg, n_blocks: int, block_tokens: int, stride: int,
                  max_rows: int) -> "StateCacheConfig":
        heads, width, head_dim = cfg.state_shape
        pc = cls(n_layers=cfg.n_layers, n_kv_heads=heads, state_dim=width,
                 head_dim=head_dim, n_blocks=n_blocks, stride=stride,
                 max_rows=max_rows, block_tokens=block_tokens)
        _check_slots(n_blocks, block_tokens, stride, max_rows)
        return pc

    @property
    def n_slots(self) -> int:
        return self.n_blocks * self.block_tokens // self.stride

    @property
    def pools(self):
        """``serve`` bounds a request by the smallest pool's blocks."""
        return ((tuple(range(self.n_layers)), self.n_blocks),)

    @property
    def page_shape(self) -> Tuple[int, ...]:
        """One layer's state as it goes to the store: by head, ``S`` then
        ``z``, ``state_dim x (head_dim + 1)`` values."""
        return (self.n_kv_heads, self.state_dim * (self.head_dim + 1))

    @property
    def page_bytes(self) -> int:
        return int(np.prod(self.page_shape)) * np.dtype(
            jnp.dtype(self.dtype)).itemsize

    @property
    def slot_bytes(self) -> int:
        """One slot: every layer's state."""
        return self.n_layers * self.page_bytes

    @property
    def cache_bytes(self) -> int:
        return self.n_slots * self.slot_bytes


def cache_kind(cfg) -> str:
    """What a sequence keeps of a model, as its config states it
    (``cfg.cache_kind``): ``"pages"`` for every layer, which a config that
    says nothing keeps, ``"state"`` (a state a layer and no pages:
    ``StateCacheConfig``) or ``"hybrid"`` (pages for its ``page_layers`` and a
    state for its ``state_layers``: ``HybridCacheConfig``)."""
    return getattr(cfg, "cache_kind", "pages")


def _check_slots(n_blocks: int, block_tokens: int, stride: int,
                 max_rows: int) -> None:
    """``ValueError``, in words, for a cache of ``n_blocks x block_tokens /
    stride`` state slots that cannot be served."""
    if stride <= 0 or stride % block_tokens:
        raise ValueError(
            f"a checkpoint lies at the end of a chunk of {block_tokens} "
            f"tokens: stride {stride} is no multiple of it")
    n_slots = n_blocks * block_tokens // stride
    if n_slots < max_rows:
        raise ValueError(
            f"{n_blocks} blocks of {block_tokens} tokens at a stride of "
            f"{stride} are {n_slots} state slots: fewer than the "
            f"{max_rows} rows that run together")


@dataclass(frozen=True)
class HybridCacheConfig(PagedCacheConfig):
    """The cache of a stack whose sequence keeps TWO KINDS: pages for its
    ``page_layers`` (the paged cache above, over those layers alone: one pool
    of ``n_blocks`` blocks, its allocator, prefix page cache and block table
    unchanged) and, for its ``state_layers``, a state of ``state_width``
    values a layer in a slot (``StateSlots``: ``max_rows`` running rows'
    slots, the rest resident checkpoints by chunk key; ``n_slots = n_blocks x
    block_tokens / stride``, one every ``stride`` tokens the pages stand
    for).  ``n_layers`` is the STACK's depth and the layer ids in the store's
    keys are the stack's, so a page and a state never share a key."""

    page_layers: Tuple[int, ...] = ()
    state_layers: Tuple[int, ...] = ()
    state_width: int = 0
    stride: int = 0
    max_rows: int = 0
    # the type a slot holds a state in and the store gets it in; None: the
    # pages' (a shift register of activations); float32 where the state is a
    # recurrence's accumulator (models/jamba.py)
    state_dtype: Optional[jnp.dtype] = None
    # 0: a slot holds a layer's state as one row ``[state_width]``.  n: as
    # ``[state_width / n, n]``, so that the LAYER axis is no tiled axis of the
    # slots: 26 layers on the sublanes would pad to 32, and the TPU's compact
    # layout then puts the layers outermost, which every program that walks
    # the slots by slot undoes with a copy of all of them, twice a launch
    # (benchmarks/aot_check_jamba.py read 3.2 GB each way)
    state_lanes: int = 0

    @classmethod
    def for_model(cls, cfg, n_blocks: int, block_tokens: int, stride: int,
                  max_rows: int) -> "HybridCacheConfig":
        """From the kind's names, which every model that keeps both gives:
        ``kv_page``, ``page_layers``, ``state_layers``, ``state_width`` and, where
        a state is not held in the model's type, ``state_dtype``."""
        planes, heads, width = cfg.kv_page
        _check_slots(n_blocks, block_tokens, stride, max_rows)
        return cls(n_layers=cfg.n_layers, n_kv_heads=heads, head_dim=width,
                   n_blocks=n_blocks, block_tokens=block_tokens,
                   dtype=cfg.dtype, planes=planes,
                   page_layers=tuple(cfg.page_layers),
                   state_layers=tuple(cfg.state_layers),
                   state_width=int(cfg.state_width),
                   stride=stride, max_rows=max_rows,
                   state_dtype=getattr(cfg, "state_dtype", None),
                   state_lanes=getattr(cfg, "state_lanes", 0))

    @property
    def pools(self) -> Tuple[Tuple[Tuple[int, ...], int], ...]:
        """One pool of pages, over the layers that keep them."""
        return ((self.page_layers, self.n_blocks),)

    @property
    def n_slots(self) -> int:
        return self.n_blocks * self.block_tokens // self.stride

    @property
    def state_bytes(self) -> int:
        """One layer's state of one sequence, as it is held and as it goes
        to the store."""
        return self.state_width * self.slot_dtype.itemsize

    @property
    def slot_shape(self) -> Tuple[int, ...]:
        """One slot as ``init_cache`` lays it out: every state layer's state."""
        n = self.state_lanes
        return (len(self.state_layers),) + (
            (self.state_width // n, n) if n else (self.state_width,))

    @property
    def slot_dtype(self) -> jnp.dtype:
        """The type the slots hold a state in."""
        return jnp.dtype(self.dtype if self.state_dtype is None
                         else self.state_dtype)

    @property
    def slot_bytes(self) -> int:
        """One slot: every state layer's state."""
        return len(self.state_layers) * self.state_bytes

    @property
    def cache_bytes(self) -> int:
        """Pages and slots, as ``init_cache`` allocates them."""
        return super().cache_bytes + self.n_slots * self.slot_bytes


class StateSlots:
    """Host-side bookkeeping of a ``StateCacheConfig``'s slots (and of a
    ``HybridCacheConfig``'s).  Slots
    ``[0, max_rows)`` are the running rows': ``take_row`` / ``free_row``,
    each slot out once.  The rest hold RESIDENT CHECKPOINTS by key, least
    recently used first out: ``keep`` gives a key a slot (evicting the
    oldest unpinned one) that ``register`` then names, ``match`` finds and
    pins one while it is copied, ``unpin`` lets it go again."""

    def __init__(self, n_slots: int, max_rows: int):
        from collections import OrderedDict

        self.n_slots, self.max_rows = n_slots, max_rows
        self._rows = list(range(max_rows - 1, -1, -1))
        self._free = list(range(n_slots - 1, max_rows - 1, -1))
        self._by_key: "OrderedDict[str, int]" = OrderedDict()   # LRU order
        self._pins: dict = {}
        self.evicted = 0

    @property
    def rows_free(self) -> int:
        return len(self._rows)

    def take_row(self) -> int:
        if not self._rows:
            raise MemoryError("out of state slots: every running row's is taken")
        return self._rows.pop()

    def free_row(self, slot: int) -> None:
        assert 0 <= slot < self.max_rows and slot not in self._rows, slot
        self._rows.append(slot)

    def __contains__(self, key: str) -> bool:
        return key in self._by_key

    def match(self, key: str) -> Optional[int]:
        """The resident checkpoint of ``key``, pinned and marked used, or
        None."""
        slot = self._by_key.get(key)
        if slot is not None:
            self._by_key.move_to_end(key)
            self._pins[slot] = self._pins.get(slot, 0) + 1
        return slot

    def unpin(self, slot: int) -> None:
        n = self._pins[slot] - 1
        if n:
            self._pins[slot] = n
        else:
            del self._pins[slot]

    def keep(self) -> Optional[int]:
        """A resident slot to copy a checkpoint into, pinned: a free one,
        else the least recently used unpinned one's (its key forgotten).
        None where every one is pinned, or there is none."""
        if self._free:
            slot = self._free.pop()
        else:
            key = next((k for k, s in self._by_key.items()
                        if s not in self._pins), None)
            if key is None:
                return None
            slot = self._by_key.pop(key)
            self.evicted += 1
        self._pins[slot] = 1
        return slot

    def register(self, key: str, slot: int) -> None:
        """Name the checkpoint ``keep``'s slot now holds; the slot stays
        pinned until ``unpin``."""
        assert key not in self._by_key and slot >= self.max_rows
        self._by_key[key] = slot


def init_cache(cfg, sharding=None):
    """Zeroed cache; with ``sharding`` it is created in its shards (a cache
    sized for a mesh need not fit one device first).  One array; for a
    stack with a pool per layer kind a tuple of one array a pool
    (``cfg.pools``), each over its own layers and blocks; for a
    ``StateCacheConfig`` the slots ``(S, z)``; for a ``HybridCacheConfig``
    ``(pages [page layers, ...], slots [n_slots] + ``cfg.slot_shape``)``.  A pool's
    array is ``[its layers, planes, heads, its blocks, T, width]`` of ITS page
    (``page_shape_of``)."""
    if isinstance(cfg, StateCacheConfig):
        lead = (cfg.n_slots, cfg.n_layers, cfg.n_kv_heads, cfg.state_dim)
        return (jnp.zeros(lead + (cfg.head_dim,), cfg.dtype, device=sharding),
                jnp.zeros(lead, cfg.dtype, device=sharding))
    arrays = []
    for p, (layers, n_blocks) in enumerate(cfg.pools):
        planes, heads, T, width = cfg.page_shape_of(p)
        arrays.append(jnp.zeros((len(layers), planes, heads, n_blocks, T, width),
                                dtype=cfg.dtype, device=sharding))
    if isinstance(cfg, HybridCacheConfig):
        return arrays[0], jnp.zeros((cfg.n_slots,) + cfg.slot_shape,
                                    cfg.slot_dtype, device=sharding)
    return tuple(arrays) if cfg.window_layers else arrays[0]


def write_pages(cache: jax.Array, block_ids: jax.Array, pages: jax.Array) -> jax.Array:
    """Scatter pages for all layers at once.

    pages: [n_layers, 2, H_kv, n, T, D]; block_ids: [n] int32
    """
    return cache.at[:, :, :, block_ids].set(pages)


def read_pages(cache: jax.Array, block_ids: jax.Array) -> jax.Array:
    """Gather pages for all layers: -> [n_layers, 2, H_kv, n, T, D]."""
    return cache[:, :, :, block_ids]


def write_token_rows(
    cache: jax.Array,
    layer: int,
    block_ids: jax.Array,
    slot_ids: jax.Array,
    rows: jax.Array,
) -> jax.Array:
    """Write one token per sequence into layer ``layer``.

    block_ids/slot_ids: [B] page id and in-page slot for each sequence's
    current position; rows: [B, planes, n_kv_heads, head_dim], the token's
    row of every plane (K and V, or the one latent row).  The page ids must
    be distinct (each sequence appends to a page of its own) or out of
    bounds (pad rows: their write is dropped).

    The write is a read-modify-write of WHOLE pages: gather the B pages,
    put each token in its slot, scatter the pages back.  A scatter of the
    bare [B, 2, H, D] token rows makes XLA:TPU re-lay the whole cache out
    with heads next to head_dim for the decode loop (the one-row update
    has no (T, D) tile), and the copy that does so is a second cache: on a
    v5e a cache of 5.6 GB next to 7 GB of weights then fails to compile
    ("Used 18.68G of 15.75G hbm").  Whole pages keep the [T, D] tile, the
    donated cache is updated in place, and a step moves 16x the bytes of
    the rows it writes — tens of MB next to GBs of weights."""
    T = cache.shape[4]
    # advanced indices (layer, block_ids) are separated by slices, so the
    # batch dim lands in FRONT: [B, planes, H, T, D]; out-of-bounds ids
    # clamp on the gather and are dropped by the scatter
    pages = cache[layer, :, :, block_ids]
    here = jnp.arange(T)[None, :] == slot_ids[:, None]  # [B, T]
    pages = jnp.where(here[:, None, None, :, None], rows[:, :, :, None, :], pages)
    return cache.at[layer, :, :, block_ids].set(pages)


def write_token_kv(
    cache: jax.Array,
    layer: int,
    block_ids: jax.Array,
    slot_ids: jax.Array,
    k: jax.Array,
    v: jax.Array,
) -> jax.Array:
    """``write_token_rows`` for the two planes K and V: k/v
    [B, n_kv_heads, head_dim]."""
    return write_token_rows(cache, layer, block_ids, slot_ids,
                            jnp.stack([k, v], axis=1))  # [B, 2, H, D]


def write_tokens_kv(
    cache: jax.Array,
    layer: int,
    block_ids: jax.Array,
    slot_ids: jax.Array,
    k: jax.Array,
    v: jax.Array,
) -> jax.Array:
    """Scatter a run of tokens per sequence into layer ``layer`` (the
    multi-token sibling of write_token_kv; used by the speculative-decode
    verify step, which only the K|V families have).

    block_ids/slot_ids: [B, S]; k/v: [B, S, n_kv_heads, head_dim].
    Distinct (page, slot) targets per token, so the flat scatter is exact.
    A run's tokens share pages, so this stays a scatter of token rows (a
    page-wise read-modify-write would lose all but one of them) and keeps
    the relayout cost write_token_rows describes: speculation at a
    deployment-sized cache is not measured (ROADMAP A5)."""
    B, S = block_ids.shape
    kv = jnp.stack([k, v], axis=2).reshape((B * S, 2) + k.shape[2:])
    # batch dim in FRONT, as above: target shape [B*S, 2, H, D]
    return cache.at[
        layer, :, :, block_ids.reshape(B * S), slot_ids.reshape(B * S)
    ].set(kv)


def prefill_to_pages(kv: jax.Array, n_pages: int, block_tokens: int) -> jax.Array:
    """Reshape prefill KV [L, planes, S, H, D] (S = n_pages*block_tokens)
    into pages [L, planes, H, n_pages, T, D]."""
    L, two, S, H, D = kv.shape
    assert S == n_pages * block_tokens, (S, n_pages, block_tokens)
    kv = kv.reshape(L, two, n_pages, block_tokens, H, D)
    return jnp.transpose(kv, (0, 1, 4, 2, 3, 5))


def pages_to_seq_kv(pages: jax.Array) -> jax.Array:
    """[L, planes, H, n, T, D] -> [L, planes, 1, n*T, H, D] (batch-1
    sequence KV)."""
    L, two, H, n, T, D = pages.shape
    return jnp.transpose(pages, (0, 1, 3, 4, 2, 5)).reshape(L, two, 1, n * T, H, D)


class BlockAllocator:
    """Host-side page allocator for the HBM cache (free-list; O(1))."""

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, -1, -1))

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(f"out of KV pages: want {n}, have {len(self._free)}")
        return [self._free.pop() for _ in range(n)]

    def free(self, ids: Sequence[int]) -> None:
        self._free.extend(ids)

    @property
    def n_free(self) -> int:
        return len(self._free)


class PrefixPageCache:
    """Refcounted, content-addressed page residency — automatic prefix
    caching for the HBM cache (the role vLLM's APC plays in the reference's
    serving stack; the *store* handles cross-host reuse, this handles
    same-engine reuse without recompute OR store traffic).

    Chunk keys (kv/hashing.py) commit to the whole token prefix, so
    ``key match == identical page content`` and pages become content-
    addressable for free.  Complete-chunk pages are registered under their
    key; sequences sharing a prefix pin the same block ids (a ref each).
    Shared pages are only ever *read* — decode/verify append into pages past
    the registered prefix, never into a registered one (slot = pos // T
    lands beyond every complete chunk).  On release, refs drop; pages at
    ref 0 with a key are RETAINED on an LRU of reclaimable pages (a later
    prefill can still hit them) and only handed back to the allocator when
    ``acquire`` runs out of fresh pages.
    """

    def __init__(self, alloc: BlockAllocator):
        self.alloc = alloc
        self._key_to_block: dict = {}
        self._block_key: dict = {}
        self._refs: dict = {}  # block_id -> live-sequence count
        from collections import OrderedDict

        self._cached: "OrderedDict[int, None]" = OrderedDict()  # ref==0, reclaimable

    @property
    def available(self) -> int:
        """Pages obtainable by ``acquire``: fresh + reclaimable."""
        return self.alloc.n_free + len(self._cached)

    def acquire(self, n: int) -> List[int]:
        """All-or-nothing allocation, reclaiming LRU cached pages on demand."""
        if n > self.available:
            raise MemoryError(
                f"out of KV pages: want {n}, have {self.available}"
            )
        fresh = min(n, self.alloc.n_free)
        ids = self.alloc.alloc(fresh) if fresh else []
        while len(ids) < n:
            bid, _ = self._cached.popitem(last=False)  # oldest first
            key = self._block_key.pop(bid)
            del self._key_to_block[key]
            ids.append(bid)
        for bid in ids:
            self._refs[bid] = 1
        return ids

    def peek_prefix(self, keys: Sequence[str]) -> int:
        """Length of the resident prefix run WITHOUT pinning — the routing
        probe for batched admission (engine.prefill_batch sends hits down
        the per-sequence reuse path)."""
        n = 0
        for k in keys:
            if k not in self._key_to_block:
                break
            n += 1
        return n

    def __contains__(self, key: str) -> bool:
        return key in self._key_to_block

    def match_each(self, keys: Sequence[str]) -> List[Optional[int]]:
        """Each key's resident page or None, every hit pinned (+1 ref): a
        pool whose sequences hold a WINDOW of their pages is hit key by
        key, not as a run from the first chunk."""
        ids: List[Optional[int]] = []
        for k in keys:
            bid = self._key_to_block.get(k)
            if bid is not None:
                self._pin(bid)
            ids.append(bid)
        return ids

    def match_prefix(self, keys: Sequence[str]) -> List[int]:
        """Longest resident run of ``keys``; pins every hit (+1 ref)."""
        ids: List[int] = []
        for k in keys:
            bid = self._key_to_block.get(k)
            if bid is None:
                break
            self._pin(bid)
            ids.append(bid)
        return ids

    def _pin(self, bid: int) -> None:
        self._refs[bid] = self._refs.get(bid, 0) + 1
        self._cached.pop(bid, None)

    def unpin(self, block_ids: Sequence[int]) -> None:
        """Drop one ref per page; ref-0 pages go to the reclaim LRU (if
        registered) or straight back to the allocator."""
        for bid in block_ids:
            r = self._refs[bid] - 1
            if r > 0:
                self._refs[bid] = r
                continue
            del self._refs[bid]
            if bid in self._block_key:
                self._cached[bid] = None
                self._cached.move_to_end(bid)
            else:
                self.alloc.free([bid])

    def register(self, keys: Sequence[str], block_ids: Sequence[int]) -> None:
        """Name complete-chunk pages so later prefills can hit them.  First
        registration wins: a key already resident keeps its page (the new
        page simply stays private to its sequence)."""
        for k, bid in zip(keys, block_ids):
            if k in self._key_to_block or bid in self._block_key:
                continue
            self._key_to_block[k] = bid
            self._block_key[bid] = k

class BlockTable:
    """Per-sequence page tables (host side), for paged attention."""

    def __init__(self, max_seqs: int, max_blocks_per_seq: int):
        self.max_seqs = max_seqs
        self.max_blocks_per_seq = max_blocks_per_seq
        self.table = np.zeros((max_seqs, max_blocks_per_seq), dtype=np.int32)
        self.seq_lens = np.zeros((max_seqs,), dtype=np.int32)

    def assign(self, seq_idx: int, block_ids: Sequence[int], seq_len: int) -> None:
        n = len(block_ids)
        if n > self.max_blocks_per_seq:
            raise ValueError("sequence exceeds max_blocks_per_seq")
        self.table[seq_idx, :n] = block_ids
        self.table[seq_idx, n:] = 0
        self.seq_lens[seq_idx] = seq_len

    def device_arrays(self) -> Tuple[jax.Array, jax.Array]:
        return jnp.asarray(self.table), jnp.asarray(self.seq_lens)
