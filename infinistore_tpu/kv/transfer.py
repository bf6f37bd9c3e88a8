"""HBM <-> store movement for paged KV.

The reference moves KV between GPU memory and the store pool with GPUDirect
RDMA against ``tensor.data_ptr()`` offsets (reference: infinistore/lib.py:425-
542, benchmark.py:163-247).  On a TPU-VM the device side is a ``jax.Array``
in HBM, so the path is: one fused gather on device -> a device-to-host
transfer a layer band -> zero-copy batched put straight from that host array
into the store's shm pool (one host copy total; the mirror image for reads lands in a
reusable staging buffer — the "registered MR": allocated once, registered
with the connection, reused).

Key layout: page (layer L, chunk c) of a sequence is stored under
``layer_key(chunk_keys(tokens)[c], L)`` so prefix reuse works per chunk while
layer-by-layer streaming (reference design.rst prefill flow) stays possible.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import resilience as _resilience
from ..utils import tracing
from .cache import PagedCacheConfig, read_pages, write_pages
from .hashing import layer_key
from .quant import dequantize_pages_jit, page_quant_bytes, quantize_pages


# a load's stages inside its ``fetch_s``, as ``load_totals`` sums them
LOAD_STAGES = ("desc_s", "pool_copy_s", "upload_s")


def _stage(name: str):
    """``stepprof.stage("istpu.stream.<name>")``: one stage of a push, on the
    thread that commits it (the streamer's worker), in the profiler's trace
    and as seconds.  Looked up at the call: the engine package imports this
    module."""
    from ..engine.stepprof import stage

    return stage("istpu.stream." + name)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_stacked(cache: jax.Array, block_ids: jax.Array,
                     stacked: jax.Array) -> jax.Array:
    """Store-layout pages [L, n, planes, H, T, D] into ``block_ids``'s slots of
    the DONATED cache: a store hit updates the cache in place.  Eager, the
    scatter needs a second whole cache, and a cache sized like a deployment
    (most of HBM) cannot exist twice."""
    return write_pages(
        cache, block_ids, jnp.transpose(stacked, (0, 2, 3, 1, 4, 5))
    )


@partial(jax.jit, donate_argnums=(0,))
def _scatter_layers(cache: jax.Array, layer_ids: jax.Array,
                    block_ids: jax.Array, stacked: jax.Array) -> jax.Array:
    """``_scatter_stacked`` for SOME layers: store-layout pages [l, n,
    planes, H, T, D] of layers ``layer_ids`` into ``block_ids``'s slots of
    the donated cache.  Layer and page id are both indices of the scatter
    (split by slices, so their dims land in front, in store layout as it
    is); no other layer's slot of those blocks is written."""
    return cache.at[layer_ids[:, None], :, :, block_ids[None, :]].set(stacked)


def _band_ranges(L: int, groups: int) -> List[Tuple[int, int]]:
    """``L`` layers as at most ``groups`` bands ``(first layer, layers)``:
    the unit a push materializes and writes, and a load reads and uploads."""
    Lg = -(-L // max(1, min(groups, L)))
    return [(l0, min(Lg, L - l0)) for l0 in range(0, L, Lg)]


@partial(jax.jit, static_argnums=(2, 3))
def _gather_bands(cache, block_ids, quant, groups):
    """The device half of a push as ONE program: ``block_ids``'s pages of
    every layer gathered (``read_pages``), laid out as the store holds them,
    [L, n, planes, H, T, D] with each (layer, chunk) page contiguous,
    quantized and packed where ``quant`` (the packed rows ARE the wire
    pages, half the bytes to move), and cut into ``groups`` layer bands.
    Eager, each of these was a launch of its own, and a band's slice the
    costliest.  A cache of one pool a layer kind: ``_gather_bands_by_pool``."""
    gathered = read_pages(cache, block_ids)  # [L, planes, H, n, T, D]
    pages = jnp.transpose(gathered, (0, 3, 1, 2, 4, 5))
    if quant:
        pages = quantize_pages(pages)  # [L, n, wire_page_bytes] uint8
    return tuple(pages[l0 : l0 + n]
                 for l0, n in _band_ranges(pages.shape[0], groups))


class KeysByPool(list):
    """The chunk keys of a push from a cache of one pool a layer kind.  The
    list itself names every chunk of the push (the first pool's layers, which
    read everything, send a page of each); ``by_pool[p]`` names the chunks of
    which pool ``p``'s layers send a page: a window layer sends only the pages
    a later hit can read (engine ``_window_sent``)."""

    def __init__(self, by_pool: Sequence[Sequence[str]]):
        super().__init__(by_pool[0])
        self.by_pool = tuple(list(keys) for keys in by_pool)


@partial(jax.jit, static_argnums=(2, 3))
def _gather_bands_by_pool(caches, block_ids, plan, quant):
    """``_gather_bands`` over a cache of one pool a layer kind, one program:
    ``block_ids`` one id array a pool (of DIFFERENT lengths where a pool
    sends fewer chunks), ``plan`` the bands as ``(pool, first layer in the
    pool's array, layers)`` in stack order (``KVTransferEngine._band_plan``).
    Each band [l, n of its pool, ...its pool's page] in store layout."""
    # every layer of a pool gathered by ids at once, the bands cut out of the
    # small result: a band's layers sliced out of the POOL first would be a
    # copy of that slab (XLA:TPU fuses no slice into a gather's operand)
    gathered = {pool: jnp.transpose(read_pages(caches[pool], block_ids[pool]),
                                    (0, 3, 1, 2, 4, 5))
                for pool in {pool for pool, _, _ in plan}}
    bands = [gathered[pool][l0 : l0 + n] for pool, l0, n in plan]
    return tuple(quantize_pages(b) if quant else b for b in bands)


class KVTransferEngine:
    """Moves pages between a paged HBM cache and an infinistore-tpu server.

    ``quant="int8"`` quantizes pages on device before the D2H hop (and
    dequantizes after H2D on load), halving every byte the store, shm pool,
    and DCN link touch; quantized pages live under a distinct key namespace
    (``...#L{i}:q8``) so they can never be misread as bf16 pages.
    """

    # ``load_pages`` takes ``layer_chunks`` and ``lookup_prefix`` a
    # ``probe_layer``: a stack whose layers need different chunks of a
    # stored prefix is loaded layer group by layer group
    loads_by_layer = True

    def __init__(
        self,
        conn,
        cfg: PagedCacheConfig,
        pipeline_groups: int = 4,
        quant: Optional[str] = None,
        breaker: Optional[_resilience.CircuitBreaker] = None,
        push_mode: str = "auto",
    ):
        # accept the public InfinityConnection or the raw wire Connection.
        # The SOURCE is kept (not unwrapped): the public wrapper owns the
        # auto-reconnect machinery, and pinning its raw connection here
        # would leave every transfer hop dead after the first transport
        # failure — the store tier could then never recover without
        # rebuilding the engine.  ``self.conn`` resolves the CURRENT raw
        # connection; ``_call`` dispatches reconnect-aware when possible.
        self._src = conn
        self.cfg = cfg
        # circuit breaker over the store transport: the guarded_* hops
        # below (and the engine's streamer) report transport failures
        # here, and skip the store outright while it is open — a dead or
        # hung store degrades to recompute instead of taxing every
        # request with a timeout.  Shared when the caller passes one
        # (serving engine + draft engine on one store, connector pools).
        self.breaker = breaker or _resilience.CircuitBreaker()
        # save_pages splits the D2H transfer into this many layer bands and
        # overlaps each band's pool write with the next band's transfer
        # (the role the reference's async RDMA WR chains play on the GPU
        # side); 1 = fully serial
        self.pipeline_groups = pipeline_groups
        if quant not in (None, "int8"):
            raise ValueError(f"unsupported quant mode: {quant!r}")
        if quant and cfg.planes != 2:
            # kv/quant.py scales per (K|V, head); a page of another make
            # (one latent plane, or keys and values of unequal widths side
            # by side in one row) has no such scale, and a wrong one would
            # be served: refuse here, at start-up
            raise ValueError(
                f"kv quant {quant!r} scales pages per (K|V, head); a page "
                f"of {cfg.planes} plane(s) goes to the store as it is "
                f"(--kv-quant none)")
        self.quant = quant
        # bytes of one page as it crosses the wire / sits in the pool; where
        # the pools' pages differ (``cfg.pool_kv``) the FIRST pool's, and
        # every path that moves another pool's asks ``_wire_bytes_of``
        self.wire_page_bytes = page_quant_bytes(cfg) if quant else cfg.page_bytes
        self._key_suffix = ":q8" if quant else ""
        # DOUBLE-buffered staging, alternated per load call: the banded
        # load hands numpy views to jax.device_put (async H2D; on the
        # CPU backend possibly a zero-copy alias), so the buffer a call
        # used must not be rewritten by the NEXT call's pool reads while
        # transfers could still be in flight — the alternation plus the
        # end-of-call block makes reuse safe
        self._staging: list = [None, None]
        self._staging_idx = 0
        # push path selector: "auto" (default) = alloc-first zero-copy on
        # negotiated shm connections, the pinned staging ring on TCP /
        # native, legacy pipelined otherwise; "legacy" pins the pre-
        # alloc-first path outright (the byte-parity reference, mirroring
        # Connection.coalesce=False one layer down)
        if push_mode not in ("auto", "legacy"):
            raise ValueError(f"unsupported push_mode: {push_mode!r}")
        self.push_mode = push_mode
        # pinned, MR-registered staging ring for pushes on transports with
        # no mappable pool (TCP / native): double-buffered per layer band,
        # so band i's slot is never rewritten while its wire copy could
        # still be in flight, and band i+1's D2H lands in the other slot
        self._push_staging: list = [None, None]
        self._push_idx = 0
        # per-stage seconds of the LAST push_commit (d2h_s / pool_copy_s /
        # alloc_s / commit_s, plus the zero-copy/staged band counters) —
        # the bench legs read this to attribute regressions on the push
        # path from bench output alone
        self.last_push_stages: dict = {}
        # load-side twin: wire/pool half (fetch_s) vs device half
        # (scatter_s, including the end-of-load block) of the LAST
        # load_pages — the engine step records attach both dicts when a
        # step moved pages (engine/stepprof.py)
        self.last_load_stages: dict = {}
        # what a load's landing stands through before its own wait (set by
        # the engine: the decode dispatch in flight), and the seconds stood
        # there so far, which the engine takes out of its ``kv.load`` times
        self.before_sync = None
        self.held_s = 0.0
        # running totals beside the two "last" dicts, for readers that take
        # deltas (engine/stepprof.py).  Each is REPLACED whole under the
        # lock, never mutated: a reader holding one holds a consistent
        # snapshot.  ``submit_to_commit_s`` runs from push_begin on the
        # submitting thread to the acknowledged COMMIT_PUT on the worker:
        # ``queue_s`` (push_begin's end to push_commit's entry: behind
        # earlier pushes) + ``commit_wall_s`` (push_commit, entry to
        # return); the five stages are timed inside ``commit_wall_s`` and
        # what they leave of it is the worker's Python between them.
        self._totals_lock = threading.Lock()
        self.push_totals: dict = dict.fromkeys(
            ("pushes", "tokens", "bytes"), 0) | dict.fromkeys(
            ("d2h_s", "pool_copy_s", "alloc_s", "wire_s", "commit_s",
             "queue_s", "commit_wall_s", "submit_to_commit_s"), 0.0)
        # ``fetch_s`` holds ``LOAD_STAGES`` and the loop's Python;
        # ``scatter_s`` the scatter's launch and ``sync_s``, the closing
        # ``block_until_ready`` alone
        self.load_totals: dict = dict.fromkeys(
            ("loads", "tokens", "bytes"), 0) | dict.fromkeys(
            ("fetch_s", "scatter_s", "sync_s") + LOAD_STAGES, 0.0)

    def _tokens_of(self, chunk_keys_: Sequence[str]) -> int:
        """Tokens a push of these keys stands for in ``push_totals``."""
        return len(chunk_keys_) * self.cfg.block_tokens

    def _wire_bytes_of(self, pool: int) -> int:
        """``wire_page_bytes`` of pool ``pool``'s page."""
        if not getattr(self.cfg, "pool_kv", ()):
            return self.wire_page_bytes
        return self.cfg.page_bytes_of(pool)

    def _add_totals(self, which: str, **add) -> None:
        with self._totals_lock:
            old = getattr(self, which)
            setattr(self, which, {k: v + add.get(k, 0) for k, v in old.items()})

    @property
    def conn(self):
        """The CURRENT raw wire connection (fresh after a wrapper
        reconnect — a cached unwrap would go permanently dead with the
        first torn-down channel)."""
        return getattr(self._src, "conn", self._src)

    def _call(self, name: str, *args):
        """Dispatch a connection op reconnect-aware: through the public
        wrapper's ``_call`` (tear down + reconnect + one retry on
        transport failure) when the source is one, directly otherwise.
        Raw-connection SEMANTICS either way (``check_exist`` returns the
        wire int, ``get_match_last_index`` returns -1 instead of
        raising)."""
        call = getattr(self._src, "_call", None)
        if call is not None:
            return call(name, *args)
        return getattr(self._src, name)(*args)

    def _release_mr(self, buf: np.ndarray) -> None:
        """Drop a replaced staging buffer's registration (connections
        without the entry point — older wrappers — just leak one record,
        the pre-fix behavior)."""
        fn = getattr(self._src, "unregister_mr", None)
        if fn is not None:
            fn(buf.ctypes.data)

    def _ensure_staging(self, nbytes: int) -> np.ndarray:
        self._staging_idx ^= 1
        buf = self._staging[self._staging_idx]
        if buf is None or buf.nbytes < nbytes:
            old = buf
            buf = np.empty(nbytes, dtype=np.uint8)
            # register on the SOURCE: the wrapper replays MRs on reconnect
            self._src.register_mr(buf.ctypes.data, buf.nbytes)
            self._staging[self._staging_idx] = buf
            if old is not None:
                # the grown-away buffer's registration must not linger in
                # the MR table (one dead entry per growth, replayed on
                # every reconnect, forever)
                self._release_mr(old)
        return buf

    def _ensure_push_staging(self, nbytes: int) -> np.ndarray:
        """Push-side twin of ``_ensure_staging``: the pinned ring slot
        the next band materializes into on TCP/native transports.  Same
        double-buffer alternation and the same unregister-on-growth
        rule."""
        self._push_idx ^= 1
        buf = self._push_staging[self._push_idx]
        if buf is None or buf.nbytes < nbytes:
            old = buf
            buf = np.empty(nbytes, dtype=np.uint8)
            self._src.register_mr(buf.ctypes.data, buf.nbytes)
            self._push_staging[self._push_idx] = buf
            if old is not None:
                self._release_mr(old)
        return buf

    def _page_blocks(
        self, chunk_keys_: Sequence[str], l0: int, l1: int
    ) -> List[Tuple[str, int]]:
        """The store layout, defined once for both directions: layer-major,
        chunk-minor ``(key, offset)`` pairs for layers [l0, l1), offsets
        relative to a buffer that starts at layer ``l0``."""
        return self._layer_blocks(chunk_keys_, range(l0, l1))

    def _layer_blocks(
        self, chunk_keys_: Sequence[str], layers: Sequence[int],
        pb: Optional[int] = None,
    ) -> List[Tuple[str, int]]:
        """``_page_blocks`` for any layers, in the order given: which
        layers own a page of each chunk is the caller's to say.  ``pb``: the
        page's bytes where it is not ``wire_page_bytes`` (a pool of another
        page shape)."""
        pb = self.wire_page_bytes if pb is None else pb
        n = len(chunk_keys_)
        return [
            (layer_key(ck, layer) + self._key_suffix, (j * n + i) * pb)
            for j, layer in enumerate(layers)
            for i, ck in enumerate(chunk_keys_)
        ]

    def _page_keys(self, chunk_keys_: Sequence[str]) -> List[str]:
        return [
            k for k, _ in self._page_blocks(chunk_keys_, 0, self.cfg.n_layers)
        ]

    def gather_pages(self, cache, block_ids) -> Tuple[jax.Array, ...]:
        """Device-side half of a save, ONE launch (``_gather_bands``): the
        gather of ``block_ids``'s pages, the store's layout, the int8
        quantize where ``self.quant`` and the cut into ``pipeline_groups``
        layer bands.  Returns the bands, small device arrays [l, n, ...]:
        a snapshot (jax arrays are immutable; the program is enqueued
        behind the writes of the pages it reads), so a caller can hand
        them to a background pusher while the next chunk computes and the
        cache's pages are written again.  A cache of one pool a layer kind
        (a tuple, ``cfg.pools``) takes one id list a pool, the pages THAT
        pool sends (``KeysByPool`` names their chunks; a pool may send fewer
        than the first); the bands are ``_band_plan``'s, in stack order."""
        if isinstance(cache, tuple):
            ids = tuple(np.asarray(i, dtype=np.int32) for i in block_ids)
            plan = tuple((p, l0, len(ls)) for p, l0, ls in self._band_plan(
                [len(i) for i in ids]))
            return _gather_bands_by_pool(cache, ids, plan, bool(self.quant))
        return _gather_bands(cache, np.asarray(block_ids, dtype=np.int32),
                             bool(self.quant), self.pipeline_groups)

    def _band_plan(self, chunks_by_pool: Sequence[int]
                   ) -> List[Tuple[int, int, Tuple[int, ...]]]:
        """The bands of a push from a cache of one pool a layer kind, in
        STACK order: the ``pipeline_groups`` bands of consecutive layers that
        one array would be cut into, each cut again where the layer kind
        changes (a band is gathered from one pool and has one page shape), as
        ``(pool, the band's first layer's place in the pool's array, its
        layers by their ids in the stack)``.  A pool that sends no chunk has
        no band.  Keys are written in this order; the layer whose page says a
        chunk is whole (``_last_page_layer``) is the first pool's last."""
        place = {li: (p, j) for p, (layers, _) in enumerate(self.cfg.pools)
                 for j, li in enumerate(layers)}
        plan: list = []
        for l0, n in _band_ranges(self.cfg.n_layers, self.pipeline_groups):
            run: list = []
            for li in range(l0, l0 + n):
                if run and place[run[-1]][0] != place[li][0]:
                    plan.append(run)
                    run = []
                run.append(li)
            plan.append(run)
        return [(place[run[0]][0], place[run[0]][1], tuple(run))
                for run in plan if chunks_by_pool[place[run[0]][0]]]

    @staticmethod
    def _band_host(p: jax.Array):
        """Just-in-time host materialization of one band: ``np.asarray``
        waits only for THIS band's D2H, and the extra
        ``ascontiguousarray`` re-copy is paid only when the runtime hands
        back a strided view (the common case is already contiguous)."""

        def mat() -> np.ndarray:
            host = np.asarray(p)
            if not host.flags["C_CONTIGUOUS"]:
                host = np.ascontiguousarray(host)
            return host

        return mat

    def _band_fill(self, p: jax.Array, stages: dict):
        """``fill(dst)`` for one band of the alloc-first push: wait for
        THIS band's D2H (``np.asarray`` — on same-host runtimes it
        aliases the device buffer) and lay the bytes into ``dst`` with
        one copy.  When ``dst`` is the mapped pool, that single copy is
        the whole HBM→pool journey."""

        def fill(dst: np.ndarray) -> None:
            with _stage("d2h") as st:
                host = np.asarray(p)
                if not host.flags["C_CONTIGUOUS"]:
                    host = np.ascontiguousarray(host)
            stages["d2h_s"] += st.s
            with _stage("pool_copy") as st:
                np.copyto(dst, host.reshape(-1).view(np.uint8))
            stages["pool_copy_s"] += st.s

        return fill

    def push_begin(self, bands: Sequence[jax.Array],
                   chunk_keys_: Sequence[str]):
        """Critical-path half of a push: KICK the device→host DMA of every
        band ``gather_pages`` returned (``copy_to_host_async`` is
        dispatch-only) and stamp the time: the only store work the prefill
        thread pays for besides the gather's one launch.  Returns an opaque
        token for ``push_commit``, the streamer-thread half."""
        for p in bands:
            p.copy_to_host_async()
        keys = (chunk_keys_ if isinstance(chunk_keys_, KeysByPool)
                else list(chunk_keys_))
        return list(bands), keys, time.perf_counter()

    def push_commit(self, token) -> int:
        """Off-critical-path half of a push: materialize each band —
        straight into the shm pool on connections that negotiated
        alloc-first descriptors, through the pinned staging ring on
        TCP/native — and COMMIT_PUT.  Per-stage seconds land in
        ``last_push_stages`` and ``push_totals``, each stage an
        ``istpu.stream.*`` annotation on the calling thread; the call
        stamps its own entry (the push's wait since ``push_begin`` is
        ``queue_s``) and return (``commit_wall_s``).  Returns bytes
        written."""
        t_in = time.perf_counter()
        parts, chunk_keys_, t_begin = token
        stages = {"d2h_s": 0.0, "pool_copy_s": 0.0, "wire_s": 0.0,
                  "alloc_s": 0.0, "commit_s": 0.0,
                  "zero_copy_bands": 0, "staged_bands": 0}
        plan = self._parts_blocks(parts, chunk_keys_)
        with tracing.span("kv.push_pages",
                          pages=sum(len(b) for b, _ in plan),
                          bytes=sum(len(b) * pb for b, pb in plan)):
            total = self._push_banded(parts, plan, stages)
        self.last_push_stages = stages
        t_out = time.perf_counter()
        self._add_totals(
            "push_totals", pushes=1, bytes=total,
            tokens=self._tokens_of(chunk_keys_),
            queue_s=t_in - t_begin, commit_wall_s=t_out - t_in,
            submit_to_commit_s=t_out - t_begin,
            **{k: v for k, v in stages.items() if k.endswith("_s")})
        return total

    def _part_blocks(self, chunk_keys_: Sequence[str], l0: int, part
                     ) -> Tuple[List[Tuple[str, int]], int]:
        """``(blocks, block size)`` of one band of a push: the band's layers'
        pages of every chunk.  A cache of two kinds pushes a band of another
        make beside them (``HybridTransferEngine``)."""
        return (self._page_blocks(chunk_keys_, l0, l0 + part.shape[0]),
                self.wire_page_bytes)

    def _parts_blocks(self, parts, chunk_keys_: Sequence[str]
                      ) -> List[Tuple[List[Tuple[str, int]], int]]:
        """``(blocks, block size)`` of every band of a push, in the bands'
        order.  One array: the bands are consecutive layers' pages of every
        chunk (``_part_blocks``).  One pool a layer kind (``KeysByPool``): the
        bands are ``_band_plan``'s, each its layers' pages of the chunks ITS
        pool sends, in its pool's page size."""
        if isinstance(chunk_keys_, KeysByPool):
            return [(self._layer_blocks(chunk_keys_.by_pool[p], layers,
                                        self._wire_bytes_of(p)),
                     self._wire_bytes_of(p))
                    for p, _, layers in self._band_plan(
                        [len(k) for k in chunk_keys_.by_pool])]
        out, l0 = [], 0
        for p in parts:
            out.append(self._part_blocks(chunk_keys_, l0, p))
            l0 += p.shape[0]
        return out

    def _push_banded(self, parts, plan, stages: dict) -> int:
        raw = self.conn
        if (self.push_mode != "legacy"
                and getattr(raw, "shm_mode", False)
                and getattr(raw, "alloc_first", False)):
            # zero-copy path: descriptors learned up front, each band's
            # fill targets the mapped pool itself (exactly one copy
            # between the device buffer and the pool)
            bands = [(*blocks, self._band_fill(p, stages))
                     for blocks, p in zip(plan, parts)]
            info = self._src.write_cache_into(bands, _stage)
            stages["alloc_s"] += info.get("alloc_s", 0.0)
            # a band whose allocation came back in pieces: scratch to pool
            stages["pool_copy_s"] += info.get("copy_s", 0.0)
            stages["commit_s"] += info.get("commit_s", 0.0)
            stages["zero_copy_bands"] += info.get("zero_copy_bands", 0)
            stages["staged_bands"] += info.get("staged_bands", 0)
            return info["bytes"]
        if (self.push_mode != "legacy"
                and not getattr(raw, "shm_mode", False)):
            # no mappable pool (TCP / cross-host): materialize each band
            # into the pinned staging ring, then the batched put — band
            # i's socket write runs while band i+1's D2H (kicked at
            # push_begin) is still in flight
            total = 0
            for (blocks, pb), p in zip(plan, parts):
                nbytes = pb * len(blocks)
                slot = self._ensure_push_staging(nbytes)
                self._band_fill(p, stages)(slot[:nbytes])
                stages["staged_bands"] += 1
                with _stage("wire") as st:
                    self._call("write_cache", blocks, pb, slot.ctypes.data)
                stages["wire_s"] += st.s
                total += nbytes
            return total
        # legacy path (push_mode="legacy", or an shm peer that did not
        # negotiate alloc-first): the pre-alloc-first banded pipelined
        # put, kept as the byte-parity reference and the old-server path
        bands = [(*blocks, self._band_host(p))
                 for blocks, p in zip(plan, parts)]
        writer = getattr(self._src, "write_cache_pipelined", None)
        if writer is not None:
            return writer(bands)
        total = 0
        for blocks, pb, mat in bands:  # bare native client: per-band
            host = mat()
            self._call("write_cache", blocks, pb, host.ctypes.data)
            total += host.nbytes
        return total

    def push_pages(self, bands: Sequence[jax.Array],
                   chunk_keys_: Sequence[str]) -> int:
        """Host-side half of a save: move the gathered bands D2H and put
        them into the store — ``push_begin`` (kick every band's D2H)
        followed immediately by ``push_commit`` (materialize + commit).
        Callers that can afford to defer the commit half off their
        critical path (the engine's ``_StoreStreamer``) call the two
        halves separately."""
        return self.push_commit(self.push_begin(bands, chunk_keys_))

    def save_pages(
        self, cache: jax.Array, block_ids: Sequence[int], chunk_keys_: Sequence[str]
    ) -> int:
        """Gather pages from HBM and put them into the store.

        ``block_ids[i]`` holds the page whose key stem is ``chunk_keys_[i]``.
        Returns bytes written.
        """
        assert len(block_ids) == len(chunk_keys_)
        if len(block_ids) == 0:
            return 0
        return self.push_pages(
            self.gather_pages(cache, block_ids), chunk_keys_
        )

    def load_pages(
        self, cache: jax.Array, block_ids: Sequence[int],
        chunk_keys_: Sequence[str],
        layer_chunks: Optional[Sequence[Tuple]] = None,
    ):
        """Get pages from the store and scatter them into HBM.

        Mirror image of ``push_pages``'s banding: the read splits into
        layer bands, and each band's H2D upload (``jax.device_put`` is
        asynchronous) overlaps the NEXT band's pool→staging read — the
        socket/pool copy rides behind the host→device DMA instead of
        serializing with it.  Bands write to DISTINCT staging offsets,
        so an in-flight upload never races the next read.

        ``layer_chunks``: which layers need which chunks, as groups
        ``(layers, indices into chunk_keys_, table)``; a (layer, chunk)
        page that no group names is not asked of the store, not fetched and
        not scattered.  ``table[i]`` is chunk ``i``'s page id in the pool
        that holds the group's layers (``cfg.pools``), in ``block_ids``'s
        place.  A stack whose sliding-window layers cannot read a
        prefix's early pages names them in no group, and lands each kind's
        pages in its own pool (engine.prefill_start).  Default: every
        layer, every chunk.

        Returns the updated cache array; ``cache`` itself is donated to
        it once every byte has landed (a failed fetch raises before that
        and leaves it usable).  Raises InfiniStoreKeyNotFound if any page
        is missing (reference read semantics).
        """
        assert len(block_ids) == len(chunk_keys_)
        n = len(block_ids)
        if n == 0:
            return cache
        pb = self.wire_page_bytes
        L = self.cfg.n_layers
        if layer_chunks is not None:
            groups = [(list(ls), list(cs), table)
                      for ls, cs, table in layer_chunks if len(ls) and len(cs)]
            pages = sum(len(ls) * len(cs) for ls, cs, _ in groups)
            nbytes = sum(len(ls) * len(cs) * self._wire_bytes_of(
                self._pool_of(ls)[0]) for ls, cs, _ in groups)
            with tracing.span("kv.load_pages", pages=pages, bytes=nbytes):
                return self._load_layer_groups(cache, chunk_keys_, groups,
                                               pages, nbytes)
        nbytes = L * n * pb
        with tracing.span("kv.load_pages", pages=L * n, bytes=nbytes):
            return self._load_pages_banded(cache, block_ids, chunk_keys_, n)

    def _pool_of(self, layers: Sequence[int]) -> Tuple[int, List[int]]:
        """``(pool, the layers' indices in that pool's array)`` for layers
        of one kind."""
        for p, (pool_layers, _) in enumerate(self.cfg.pools):
            if layers[0] in pool_layers:
                return p, [pool_layers.index(li) for li in layers]
        raise ValueError(f"layers {layers} are in no pool of the cache")

    def _load_layer_groups(self, cache, chunk_keys_, groups, pages: int,
                           nbytes: int):
        """Every group fetched (the all-or-nothing half: a missing page
        raises here, before the cache is touched), then every group
        scattered into the donated cache, or into its layers' pool of it."""
        stages = dict.fromkeys(LOAD_STAGES, 0.0)
        t0 = time.perf_counter()
        fetched = [self.fetch_pages([chunk_keys_[i] for i in cs], layers=ls,
                                    stages=stages)
                   for ls, cs, _ in groups]
        t1 = time.perf_counter()
        pools = list(cache) if isinstance(cache, tuple) else [cache]
        for (ls, cs, table), stacked in zip(groups, fetched):
            if self.quant:
                stacked = dequantize_pages_jit(stacked, self.cfg)
            p, ls = self._pool_of(ls)
            pools[p] = _scatter_layers(
                pools[p], jnp.asarray(np.asarray(ls, dtype=np.int32)),
                jnp.asarray(np.asarray([table[i] for i in cs],
                                       dtype=np.int32)), stacked)
        cache = tuple(pools) if isinstance(cache, tuple) else pools[0]
        self._landed(cache, t0, t1, stages, pages,
                     len({i for _, cs, _ in groups for i in cs})
                     * self.cfg.block_tokens, nbytes)
        return cache

    def _landed(self, out, t0: float, t1: float, stages: dict, pages: int,
                tokens: int, nbytes: Optional[int] = None) -> None:
        """The end of every load: wait until ``out`` has materialized (every
        read of this call's staging buffer must complete before a LATER
        call can rewrite it: with the double buffer, a stale optimistic
        sync would need two further loads to become dangerous), then the
        load's record.  ``t0`` .. ``t1`` was the fetch, whose ``stages``
        (``LOAD_STAGES``) were timed where they happened; the scatter's
        launch follows it and the wait here is ``sync_s``."""
        # a decode dispatch in flight holds the cache the scatter consumes:
        # its remainder is stood first and apart (``before_sync``, the
        # engine's), and is in none of this load's seconds
        held = self.before_sync() if self.before_sync is not None else 0.0
        self.held_s += held
        ts = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        if nbytes is None:
            nbytes = pages * self.wire_page_bytes
        self.last_load_stages = {
            "fetch_s": round(t1 - t0, 6),
            "scatter_s": round(t2 - t1 - held, 6),
            "pages": pages, "bytes": nbytes,
        }
        self._add_totals(
            "load_totals", loads=1, tokens=tokens, bytes=nbytes,
            fetch_s=t1 - t0, scatter_s=t2 - t1 - held, sync_s=t2 - ts,
            **stages)

    def fetch_pages(self, chunk_keys_: Sequence[str],
                    layers: Optional[Sequence[int]] = None,
                    stages: Optional[dict] = None) -> jax.Array:
        """Wire half of a load: read every (layer, chunk) page of
        ``chunk_keys_`` into this engine's staging ring and hand each
        band to an async H2D upload.  Returns the stacked device array
        in store layout (``[L, n, wire_page_bytes]`` quantized, ``[L,
        n] + page_shape`` otherwise) WITHOUT touching any cache — the
        caller scatters via ``scatter_pages``.  Split out so the
        cluster layer can fetch different chunks from different nodes
        concurrently (each node engine owns its own staging) and
        scatter once all bytes verified.  ``layers``: those layers' pages
        only, stacked in the order given (default: every layer).
        ``stages``: a dict whose ``LOAD_STAGES`` gain this fetch's seconds,
        each timed where it happens: ``desc_s`` and ``pool_copy_s`` inside
        the client (the python client on a mapped pool; 0 elsewhere),
        ``upload_s`` the ``device_put`` calls and the ``concatenate``."""
        if stages is None:
            stages = dict.fromkeys(LOAD_STAGES, 0.0)
        n = len(chunk_keys_)
        layers = list(range(self.cfg.n_layers) if layers is None else layers)
        # the page of the layers asked for: one fetch is of one pool's layers
        by_kind = bool(getattr(self.cfg, "pool_kv", ()))
        pool = self.cfg.pool_of(layers[0]) if by_kind else 0
        shape = self.cfg.page_shape_of(pool) if by_kind else self.cfg.page_shape
        pb = self._wire_bytes_of(pool)
        L = len(layers)
        nbytes = L * n * pb
        staging = self._ensure_staging(nbytes)
        bands = []
        meta = []  # (staging offset, span, n_layers) per band
        for l0, nl in _band_ranges(L, self.pipeline_groups):
            blocks = self._layer_blocks(chunk_keys_, layers[l0 : l0 + nl], pb)
            off = l0 * n * pb
            bands.append((blocks, pb, staging.ctypes.data + off))
            meta.append((off, nl * n * pb, nl))
        devs: list = [None] * len(bands)

        def upload(i: int) -> None:
            t0 = time.perf_counter()
            off, span, nl = meta[i]
            band = staging[off : off + span]
            if self.quant:
                host = band.reshape(nl, n, pb)
            else:
                host = (
                    band.view(jnp.dtype(self.cfg.dtype))
                    .reshape((nl, n) + shape)
                )
            # async H2D: returns immediately; the next band's pool copy
            # (and its prefetched GET_DESC) overlaps this band's DMA
            devs[i] = jax.device_put(host)
            stages["upload_s"] += time.perf_counter() - t0

        reader = getattr(self._src, "read_cache_pipelined", None)
        if reader is not None:
            reader(bands, upload, stages)
        else:  # bare native client: per-band reads, same upload overlap
            for i, (blocks, _pb, ptr) in enumerate(bands):
                self._call("read_cache", blocks, pb, ptr)
                upload(i)
        if len(devs) == 1:   # already [L, n, ...] — don't pay a concat copy
            return devs[0]
        t0 = time.perf_counter()
        out = jnp.concatenate(devs, axis=0)
        stages["upload_s"] += time.perf_counter() - t0
        return out

    def scatter_pages(
        self, cache: jax.Array, block_ids: Sequence[int], stacked: jax.Array
    ) -> jax.Array:
        """Device half of a load: dequantize/transpose the stacked
        pages ``fetch_pages`` returned and scatter them into
        ``block_ids``'s slots.  ``cache`` is DONATED: use the returned
        array (NOT yet materialized — callers block once after the last
        scatter)."""
        if self.quant:
            stacked = dequantize_pages_jit(stacked, self.cfg)  # [L, n, 2, H, T, D]
        ids = jnp.asarray(np.asarray(block_ids, dtype=np.int32))
        return _scatter_stacked(cache, ids, stacked)

    def _load_pages_banded(
        self, cache: jax.Array, block_ids: Sequence[int],
        chunk_keys_: Sequence[str], n: int
    ) -> jax.Array:
        stages = dict.fromkeys(LOAD_STAGES, 0.0)
        t0 = time.perf_counter()
        stacked = self.fetch_pages(chunk_keys_, stages=stages)
        t1 = time.perf_counter()
        out = self.scatter_pages(cache, block_ids, stacked)
        self._landed(out, t0, t1, stages, self.cfg.n_layers * n,
                     n * self.cfg.block_tokens)
        return out

    def lookup_prefix(self, chunk_keys_: Sequence[str],
                      probe_layer: int = 0) -> int:
        """Longest store-resident prefix, in chunks.  Probes one layer's
        keys (a chunk is only readable if every layer committed; layers are
        written in order, so verify the last layer before trusting a hit).
        ``probe_layer``: layer 0 unless the caller needs another layer's
        page of EVERY chunk and layer 0's of only some (a stack that opens
        with sliding-window layers probes its first full layer: a window
        layer's early pages may be gone from the store and are not
        needed)."""
        if not chunk_keys_:
            return 0
        with tracing.span("kv.lookup_prefix", chunks=len(chunk_keys_)):
            sfx = self._key_suffix
            probe = [layer_key(ck, probe_layer) + sfx for ck in chunk_keys_]
            idx = self._call("get_match_last_index", probe)
            while idx >= 0:
                last = layer_key(chunk_keys_[idx], self._last_page_layer) + sfx
                # 0 => exists (wire semantics)
                if self._call("check_exist", last) == 0:
                    break
                idx -= 1
            return idx + 1

    @property
    def _last_page_layer(self) -> int:
        """The layer whose page of a chunk says the chunk is whole: the last
        written of those that send a page of EVERY chunk (the first pool's
        last layer; a window layer after it sends only some chunks' pages)."""
        return self.cfg.pools[0][0][-1]

    def guarded_held(self, chunk_keys_: Sequence[str], layer: int) -> bool:
        """Whether the store holds ``layer``'s page of EVERY one of
        ``chunk_keys_`` (one round trip each: a handful, the pages of one
        window); False on a store failure or an open circuit.  A hint: the
        load that follows is all or nothing whatever this says."""
        if not self.breaker.allow():
            return False
        try:
            return all(self._call(
                "check_exist", layer_key(ck, layer) + self._key_suffix) == 0
                for ck in chunk_keys_)
        except _resilience.transport_errors():
            self.breaker.record_failure()
            return False
        except Exception:  # noqa: BLE001 — a probe is an optimization
            return False

    def _deepest_whole(self, chunk_keys_: Sequence[str], layer: int) -> int:
        """``i + 1`` for the deepest ``chunk_keys_[i]`` under which the store
        holds ``layer``'s value (the last written: what lies under the key is
        whole), 0 for none; deepest first, one round trip each."""
        for i in range(len(chunk_keys_) - 1, -1, -1):
            if self._call("check_exist", layer_key(chunk_keys_[i], layer)) == 0:
                return i + 1
        return 0

    # -- breaker-guarded hops (the degraded-serving contract) --
    #
    # A store failure must cost a cache MISS, never a request.  These
    # wrappers are the one place that rule lives; the engine's prefill
    # path and the LMCache-style connector both ride them.  Transport
    # failures (socket dead, channel torn down, op deadline fired) feed
    # the breaker; while it is open the hop is skipped outright — no
    # timeout tax per request.  KeyNotFound is a normal protocol answer
    # (eviction race) and neither trips nor counts against the circuit;
    # the same goes for integrity failures (checksum/epoch fence) — the
    # transport is healthy, the BYTES were bad, so the hop degrades to a
    # miss without touching the circuit.

    def guarded_lookup_prefix(self, chunk_keys_: Sequence[str],
                              **kw) -> int:
        """``lookup_prefix`` degraded to 0 (miss) on store failure or an
        open circuit."""
        if not self.breaker.allow():
            _resilience.count_degraded("lookup")
            return 0
        try:
            n = self.lookup_prefix(chunk_keys_, **kw)
        except _resilience.transport_errors():
            self.breaker.record_failure()
            _resilience.count_degraded("lookup")
            return 0
        except Exception:  # noqa: BLE001 — a lookup is an optimization
            _resilience.count_degraded("lookup")
            return 0
        self.breaker.record_success()
        return n

    def guarded_load(
        self, cache: jax.Array, block_ids: Sequence[int],
        chunk_keys_: Sequence[str], **kw,
    ) -> Tuple[jax.Array, bool]:
        """``load_pages`` degraded to ``(cache-unchanged, False)`` on any
        failure.  Loads are all-or-nothing (the donating scatter runs
        after every byte landed), so a mid-load transport failure leaves
        the HBM cache untouched and the caller falls back to recompute."""
        if not self.breaker.allow():
            _resilience.count_degraded("load")
            return cache, False
        from ..lib import InfiniStoreIntegrityError, InfiniStoreKeyNotFound

        try:
            out = self.load_pages(cache, block_ids, chunk_keys_, **kw)
        except InfiniStoreKeyNotFound:
            # a matched page was evicted between lookup and load (the
            # server LRU evicts per PAGE key, so a chunk can lose a
            # middle layer while the probed layers survive) — a healthy
            # miss, not a store fault
            _resilience.count_degraded("load")
            return cache, False
        except InfiniStoreIntegrityError as e:
            # verification failure IS a cache miss (the detected form of
            # the lease-expiry race / pool corruption / a restart's epoch
            # fence) — already counted per cause in
            # istpu_integrity_failures_total by the client.  The store is
            # HEALTHY, so the circuit is untouched.  Client-assisted
            # quarantine: ask the store to drop the pages that failed so
            # later requests miss cleanly instead of re-paying a failed
            # verification until the scrubber finds them.
            if e.cause in ("checksum", "lease") and e.keys:
                try:
                    self._call("delete_keys", list(e.keys))
                except Exception:  # noqa: BLE001 — best-effort hygiene
                    pass
            _resilience.count_degraded("load")
            return cache, False
        except _resilience.transport_errors():
            self.breaker.record_failure()
            _resilience.count_degraded("load")
            return cache, False
        self.breaker.record_success()
        return out, True

    # -- small-blob sidecar (stream-resume checkpoints) --
    #
    # Resumable SSE streams (docs/design.md, resumption contract)
    # checkpoint the little that KV pages don't cover — emitted tokens,
    # effective sampling seed, session id — through the SAME store fleet
    # the pages live in, as inline single-key blobs (OP_PUT_INLINE /
    # OP_GET_INLINE).  Both hops are best-effort by contract: a failed
    # checkpoint write costs replay work at resume time, a failed read
    # degrades the survivor to deterministic re-generation under the
    # watermark — never a request.

    def put_blob(self, key: str, data: bytes) -> bool:
        """Write one inline blob under ``key``.  Returns False instead of
        raising on any failure (open circuit, transport death, or a
        clustered pool whose ``_call`` routes per-chunk and refuses
        single-key inline ops)."""
        if not self.breaker.allow():
            return False
        try:
            self._call("w_tcp_bytes", key, data)
        except _resilience.transport_errors():
            self.breaker.record_failure()
            return False
        except Exception:  # noqa: BLE001 — checkpoints are best-effort
            return False
        self.breaker.record_success()
        return True

    def get_blob(self, key: str) -> Optional[bytes]:
        """Read one inline blob, or None.  A miss (KeyNotFound — normal
        after TTL/eviction or before the first checkpoint landed) never
        touches the circuit."""
        if not self.breaker.allow():
            return None
        try:
            arr = self._call("r_tcp", key)
        except _resilience.transport_errors():
            self.breaker.record_failure()
            return None
        except Exception:  # noqa: BLE001 — a miss is a normal answer
            return None
        self.breaker.record_success()
        return bytes(bytearray(arr))


# -- a cache whose unit is a state (kv/cache.py StateCacheConfig) --


@partial(jax.jit, static_argnums=(3,))
def _state_to_wire(S: jax.Array, z: jax.Array, slot: jax.Array, groups: int):
    """Slot ``slot`` in store layout ``[L, 1, H, F * (D + 1)]``: one layer's
    state a page, ``S`` then ``z`` by head; as ``groups`` layer bands, each
    laid out straight from its layers of the slot (``_gather_bands``'s twin:
    one program, and no whole slot is formed beside its bands)."""
    L, H, F, D = S.shape[1:]
    bands = []
    for l0, n in _band_ranges(L, groups):
        s = jax.lax.dynamic_slice(S, (slot, l0, 0, 0, 0), (1, n, H, F, D))
        zs = jax.lax.dynamic_slice(z, (slot, l0, 0, 0), (1, n, H, F))
        bands.append(jnp.concatenate(
            [s.reshape(n, H, F * D), zs[0]], axis=-1)[:, None])
    return tuple(bands)


@partial(jax.jit, donate_argnums=(0, 1))
def _wire_to_state(S: jax.Array, z: jax.Array, slot: jax.Array,
                   stacked: jax.Array):
    """``_state_to_wire``'s inverse into slot ``slot`` of the donated slots."""
    L, H, F, D = S.shape[1:]
    w = stacked[:, 0]
    return (S.at[slot].set(w[..., : F * D].reshape(L, H, F, D)),
            z.at[slot].set(w[..., F * D:]))


class StateTransferEngine(KVTransferEngine):
    """``KVTransferEngine`` for a cache of state slots: what crosses is a
    CHECKPOINT, every layer's state of one slot, under the chunk key of the
    position it was taken at.  One layer's state stands where a page stood
    (``StateCacheConfig.page_shape``: 34 MB at the published widths where a
    page is 64 KB), so the banded push, the staging ring, the keys by layer
    and the store itself are the parent's; bit for bit what was written is
    what is read back.  What differs:

    * ``gather_pages(cache, slot)`` snapshots one slot and ``load_pages(cache,
      [slot], [key])`` fills one;
    * ``lookup_prefix(keys)`` is handed the keys of the positions at which a
      checkpoint MAY lie (most are absent by design: one checkpoint a
      prompt), so it cannot bisect as a run of pages is bisected: it asks
      for the last layer's state of each, deepest first, and the deepest
      prompt that was asked before answers in one round trip;
    * ``push_totals["tokens"]`` counts the tokens a checkpoint stands for
      (``covers``), not a chunk's.
    """

    loads_by_layer = False

    def __init__(self, conn, cfg, **kw):
        super().__init__(conn, cfg, **kw)
        self._covers: dict = {}

    def covers(self, key: str, tokens: int) -> None:
        """Say how many tokens the checkpoint about to be pushed under
        ``key`` stands for."""
        self._covers[key] = tokens

    def _tokens_of(self, chunk_keys_: Sequence[str]) -> int:
        return sum(self._covers.pop(k, 0) for k in chunk_keys_)

    def gather_pages(self, cache, slot: int) -> Tuple[jax.Array, ...]:
        return _state_to_wire(*cache, np.int32(slot), self.pipeline_groups)

    def load_pages(self, cache, block_ids: Sequence[int],
                   chunk_keys_: Sequence[str], tokens: int = 0):
        """The checkpoint ``chunk_keys_[0]`` into slot ``block_ids[0]`` of the
        donated slots; every byte has landed when this returns."""
        (slot,), (key,) = block_ids, chunk_keys_
        nbytes = self.cfg.n_layers * self.wire_page_bytes
        with tracing.span("kv.load_pages", pages=self.cfg.n_layers,
                          bytes=nbytes):
            stages = dict.fromkeys(LOAD_STAGES, 0.0)
            t0 = time.perf_counter()
            stacked = self.fetch_pages([key], stages=stages)
            t1 = time.perf_counter()
            out = _wire_to_state(*cache, jnp.asarray(slot, jnp.int32), stacked)
            self._landed(out, t0, t1, stages, self.cfg.n_layers, tokens)
        return out

    def lookup_prefix(self, chunk_keys_: Sequence[str]) -> int:
        """``i + 1`` for the deepest ``chunk_keys_[i]`` whose checkpoint the
        store holds whole (its last layer: layers are written in order), 0
        for none."""
        with tracing.span("kv.lookup_prefix", chunks=len(chunk_keys_)):
            return self._deepest_whole(chunk_keys_, self.cfg.n_layers - 1)


# -- a cache of two kinds: pages and a state (kv/cache.py HybridCacheConfig) --


@partial(jax.jit, static_argnums=(2,))
def _gather_bands_and_state(pages, block_ids, groups, conv, slot):
    """``_gather_bands`` over the page layers and, in the same program, slot
    ``slot``'s state of every state layer ``[state layers, width]`` as one
    band more: the one launch a push costs the engine thread carries both."""
    bands = _gather_bands(pages, block_ids, False, groups)
    state = conv[slot]
    # a layer's state as one row, however the slots lay it out (slot_shape)
    return bands + (state.reshape(state.shape[0], -1),)


@partial(jax.jit, donate_argnums=(0,))
def _set_slot(conv: jax.Array, slot: jax.Array, state: jax.Array) -> jax.Array:
    """``state`` (``cfg.slot_shape``) into slot ``slot`` of the donated slots."""
    return conv.at[slot].set(state)


class HybridTransferEngine(KVTransferEngine):
    """``KVTransferEngine`` for a cache of TWO KINDS (``HybridCacheConfig``):
    under a prompt's chunk keys a push carries a page for each page layer of
    each chunk and, where the chunks end at a multiple of the stride, the
    state layers' states at that position under the LAST chunk's key; layer
    ids in the keys are the stack's, so the two never share one.  The banded
    push, the one-launch gather, the staging ring and the streamer's queue
    are the parent's: the states are one band more of a block size of their
    own (``cfg.state_bytes``), written after the pages in the same commit,
    and a state is on the wire in the type it has in HBM (``cfg.slot_dtype``:
    the activations' for a shift register, float32 for a recurrence's
    accumulator; no cast either way), so what comes back is bit for bit what
    was pushed.  What differs:

    * ``gather_pages(cache, block_ids, slot=None)`` takes the engine's pair
      ``(pages, slots)`` and, with ``slot``, snapshots that slot too;
    * ``load_pages(cache, block_ids, keys, state=(slot, key))`` fetches the
      pages not held and the checkpoint, BOTH before either is scattered: a
      key missing of either kind raises with the cache untouched
      (``guarded_load``: a miss);
    * ``lookup_prefix(keys)`` probes the page layers; with ``states=True`` the
      keys are positions at which a checkpoint MAY lie and the deepest that is
      whole answers (``StateTransferEngine.lookup_prefix``'s rule)."""

    loads_by_layer = False

    def __init__(self, conn, cfg, **kw):
        super().__init__(conn, cfg, **kw)
        self._state_staging: Optional[np.ndarray] = None

    def _page_blocks(self, chunk_keys_, l0: int, l1: int):
        # a band's layers are the page pool's: name them as the stack does
        return self._layer_blocks(chunk_keys_, self.cfg.page_layers[l0:l1])

    def _state_blocks(self, key: str) -> List[Tuple[str, int]]:
        sb = self.cfg.state_bytes
        return [(layer_key(key, li), j * sb)
                for j, li in enumerate(self.cfg.state_layers)]

    def _part_blocks(self, chunk_keys_, l0: int, part):
        if part.ndim == 2:          # the states, at the last chunk's end
            return self._state_blocks(chunk_keys_[-1]), self.cfg.state_bytes
        return super()._part_blocks(chunk_keys_, l0, part)

    def gather_pages(self, cache, block_ids, slot: Optional[int] = None):
        pages, conv = cache
        ids = np.asarray(block_ids, dtype=np.int32)
        if slot is None:
            return _gather_bands(pages, ids, False, self.pipeline_groups)
        return _gather_bands_and_state(pages, ids, self.pipeline_groups, conv,
                                       np.int32(slot))

    def _fetch_state(self, key: str, stages: dict) -> jax.Array:
        """The checkpoint under ``key``, every state layer, as a device array
        ``[state layers, width]``; a layer's state missing raises."""
        cfg = self.cfg
        nbytes = cfg.slot_bytes
        if self._state_staging is None:
            self._state_staging = np.empty(nbytes, dtype=np.uint8)
            self._src.register_mr(self._state_staging.ctypes.data, nbytes)
        buf = self._state_staging
        band = (self._state_blocks(key), cfg.state_bytes, buf.ctypes.data)
        reader = getattr(self._src, "read_cache_pipelined", None)
        if reader is not None:
            reader([band], None, stages)
        else:
            self._call("read_cache", *band)
        t0 = time.perf_counter()
        # a copy of its own: the staging buffer is the next load's too
        out = jax.device_put(np.array(buf.view(cfg.slot_dtype).reshape(
            cfg.slot_shape)))
        stages["upload_s"] += time.perf_counter() - t0
        return out

    def load_pages(self, cache, block_ids: Sequence[int],
                   chunk_keys_: Sequence[str],
                   state: Optional[Tuple[int, str]] = None):
        assert len(block_ids) == len(chunk_keys_)
        pages, conv = cache
        n, L = len(block_ids), len(self.cfg.page_layers)
        if n == 0 and state is None:
            return cache
        nbytes = L * n * self.wire_page_bytes + (
            self.cfg.slot_bytes if state else 0)
        with tracing.span("kv.load_pages", pages=L * n, bytes=nbytes):
            stages = dict.fromkeys(LOAD_STAGES, 0.0)
            t0 = time.perf_counter()
            stacked = self.fetch_pages(
                chunk_keys_, layers=self.cfg.page_layers, stages=stages
            ) if n else None
            loaded = self._fetch_state(state[1], stages) if state else None
            t1 = time.perf_counter()
            # every byte of both kinds is here: now the cache is written
            if stacked is not None:
                pages = self.scatter_pages(pages, block_ids, stacked)
            if loaded is not None:
                conv = _set_slot(conv, jnp.asarray(state[0], jnp.int32), loaded)
            self._landed((pages, conv), t0, t1, stages, L * n,
                         n * self.cfg.block_tokens)
        return pages, conv

    @property
    def _last_page_layer(self) -> int:
        return self.cfg.page_layers[-1]

    def lookup_prefix(self, chunk_keys_: Sequence[str],
                      states: bool = False) -> int:
        if not states:
            return super().lookup_prefix(chunk_keys_,
                                         probe_layer=self.cfg.page_layers[0])
        # written after the pages and in layer order: the last state layer's
        # says the checkpoint is whole
        with tracing.span("kv.lookup_prefix", chunks=len(chunk_keys_)):
            return self._deepest_whole(chunk_keys_, self.cfg.state_layers[-1])
