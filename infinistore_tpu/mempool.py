"""Slab memory pools backed by POSIX shared memory.

TPU-native counterpart of the reference's RDMA-registered pinned pool
(reference: src/mempool.{h,cpp}).  The reference pre-registers host DRAM with
``ibv_reg_mr`` and hands out fixed-size blocks via a bitmap allocator; on a
TPU-VM there is no NIC registration step, but the pool must be reachable by
local clients without copies through the server process.  We therefore back
every pool with a POSIX shm segment (``/dev/shm``): local clients map the
segment and read/write blocks directly (the "local gpu copy"/RDMA analog),
while remote clients stream payloads over TCP.

The allocator mirrors the reference design: fixed block size
(``minimal_allocate_size``), a bitmap of used blocks, first-fit with a rover,
multi-pool ``MM`` with 10 GB auto-extend (reference: src/mempool.h:12-13,
src/infinistore.cpp:437-452).  The bitmap is a Python big-int: run-of-k free
block search is done with shifted AND-chains, which executes in C at
~word-per-64-blocks speed.
"""

from __future__ import annotations

import ctypes
import mmap
import os
import re
import secrets
import threading
from typing import Dict, List, Optional, Tuple

EXTEND_POOL_SIZE = 10 << 30  # reference: src/mempool.h:12
SHM_DIR = "/dev/shm"
MADV_POPULATE_WRITE = 23  # linux >= 5.14; not in this Python's mmap module


def _prefault(mm: mmap.mmap, size: int, write: bool = True) -> None:
    """Pre-fault every page of ``mm`` so the data path never takes tmpfs
    first-touch faults (the analog of the reference's ``ibv_reg_mr`` pinning,
    src/mempool.cpp -- registration faults+pins the pool up front).  Measured
    on this host: first-touch writes run at ~0.15 GB/s vs ~5 GB/s after.

    ``write=False`` MUST be used for mappings of pools owned by someone else
    (client mappings of the server pool): the write fallback zero-fills,
    which would destroy live data there."""
    if os.environ.get("ISTPU_NO_PREFAULT"):
        return
    addr = ctypes.addressof(ctypes.c_char.from_buffer(mm))
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.madvise(ctypes.c_void_p(addr), ctypes.c_size_t(size), MADV_POPULATE_WRITE) == 0:
        return
    if write:
        step = 1 << 24  # fallback: sequential zero-fill (fresh pools only)
        zeros = bytes(step)
        for off in range(0, size, step):
            mm[off : off + min(step, size - off)] = zeros[: min(step, size - off)]
    else:
        # read-touch one byte per page; populates this process's page table
        # without modifying shared contents
        view = memoryview(mm)
        acc = 0
        for off in range(0, size, mmap.PAGESIZE):
            acc |= view[off]
        view.release()


def _round_up(x: int, align: int) -> int:
    return -(-x // align) * align


_SEGMENT_RE = re.compile(r"^istpu_(\d+)_")


def sweep_stale_segments(shm_dir: str = SHM_DIR) -> List[str]:
    """Remove ``istpu_<pid>_*`` segments whose owning pid is dead.

    A server killed with SIGKILL never reaches ``Pool.close``, so its
    segments would permanently eat host RAM; every new server reclaims them
    at startup (segment names embed the creator's pid).  Returns the paths
    removed."""
    removed = []
    try:
        names = os.listdir(shm_dir)
    except OSError:
        return removed
    for name in names:
        m = _SEGMENT_RE.match(name)
        if not m:
            continue
        pid = int(m.group(1))
        if pid == os.getpid():
            continue
        try:
            os.kill(pid, 0)
            continue  # owner alive
        except ProcessLookupError:
            pass
        except PermissionError:
            continue  # alive, different uid
        try:
            os.unlink(os.path.join(shm_dir, name))
            removed.append(os.path.join(shm_dir, name))
        except OSError:
            pass
    return removed


class Pool:
    """One shm-backed slab pool with a bitmap block allocator."""

    def __init__(self, name: str, pool_size: int, block_size: int):
        assert pool_size % block_size == 0
        self.name = name
        self.pool_size = pool_size
        self.block_size = block_size
        self.total_blocks = pool_size // block_size
        self.reclassified = False
        self.allocated_blocks = 0
        self._rover = 0
        self._occ = 0  # bitmap: bit i set => block i in use
        self._full_mask = (1 << self.total_blocks) - 1
        self.path = os.path.join(SHM_DIR, name)
        fd = os.open(self.path, os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
        try:
            os.ftruncate(fd, pool_size)
            self.mm = mmap.mmap(fd, pool_size)
        finally:
            os.close(fd)
        self.buf = memoryview(self.mm)
        # Pre-fault in the background so the server can bind/listen
        # immediately (a 16 GiB pool takes minutes to fault in).  Only the
        # madvise and read-touch strategies are concurrency-safe; the
        # zero-fill fallback in _prefault would race live writes, so it is
        # never used off-thread.
        self.prefault_done = threading.Event()
        self._closing = False
        if os.environ.get("ISTPU_NO_PREFAULT"):
            self.prefault_done.set()
            self._prefault_thread = None
        else:
            self._prefault_thread = threading.Thread(
                target=self._prefault_bg, args=(pool_size,), daemon=True
            )
            self._prefault_thread.start()

    def _prefault_bg(self, size: int) -> None:
        try:
            addr = ctypes.addressof(ctypes.c_char.from_buffer(self.mm))
            libc = ctypes.CDLL(None, use_errno=True)
            step = 1 << 28  # 256 MB chunks so close() never waits long
            for off in range(0, size, step):
                if self._closing:
                    return
                n = min(step, size - off)
                rc = libc.madvise(
                    ctypes.c_void_p(addr + off),
                    ctypes.c_size_t(n),
                    MADV_POPULATE_WRITE,
                )
                if rc != 0:  # pre-5.14 kernel: read-touch (concurrency-safe)
                    for o2 in range(off, off + n, mmap.PAGESIZE):
                        if self._closing:
                            return
                        self.buf[o2]
        except (ValueError, OSError, BufferError):
            pass  # pool closed mid-prefault; remaining pages fault on first touch
        finally:
            self.prefault_done.set()

    # -- allocation --

    def _find_run(self, k: int) -> int:
        """Return first block index of a free run of k blocks, or -1.

        Doubling AND-chain: after the loop, bit i of ``r`` is set iff
        blocks i..i+k-1 are all free — O(log k) big-int ops instead of
        O(k), which is what makes whole-batch contiguous runs (k in the
        thousands) as cheap to probe as single regions."""
        free = ~self._occ & self._full_mask
        if free == 0:
            return -1
        r = free
        span = 1
        while span < k:
            step = min(span, k - span)
            r &= r >> step
            if r == 0:
                return -1
            span += step
        # prefer positions at/after the rover to reduce fragmentation churn
        hi = r >> self._rover
        if hi:
            return self._rover + (hi & -hi).bit_length() - 1
        return (r & -r).bit_length() - 1

    def allocate(self, size: int) -> Optional[int]:
        """Allocate a contiguous region of ``size`` bytes (rounded up to
        blocks).  Returns byte offset into the pool or None."""
        k = _round_up(size, self.block_size) // self.block_size
        if k == 0 or k > self.total_blocks - self.allocated_blocks:
            return None
        idx = self._find_run(k)
        if idx < 0:
            return None
        run_mask = ((1 << k) - 1) << idx
        self._occ |= run_mask
        self.allocated_blocks += k
        self._rover = (idx + k) % self.total_blocks
        return idx * self.block_size

    def deallocate(self, offset: int, size: int) -> None:
        k = _round_up(size, self.block_size) // self.block_size
        idx = offset // self.block_size
        run_mask = ((1 << k) - 1) << idx
        assert self._occ & run_mask == run_mask, "double free"
        self._occ &= ~run_mask
        self.allocated_blocks -= k

    def largest_free_run(self) -> int:
        """Largest run of contiguous free blocks, by exponential + binary
        search over the doubling AND-chain (O(log^2 n) big-int ops — cheap
        enough for every /metrics scrape)."""
        free = ~self._occ & self._full_mask
        if free == 0:
            return 0

        def has_run(k: int) -> bool:
            r = free
            span = 1
            while span < k:
                step = min(span, k - span)
                r &= r >> step
                if r == 0:
                    return False
                span += step
            return r != 0

        lo = 1  # free != 0 guarantees a run of 1
        hi = 2
        limit = self.total_blocks - self.allocated_blocks
        while hi <= limit and has_run(hi):
            lo, hi = hi, hi * 2
        hi = min(hi, limit)
        while lo < hi:  # invariant: has_run(lo), not has_run(hi + 1)
            mid = (lo + hi + 1) // 2
            if has_run(mid):
                lo = mid
            else:
                hi = mid - 1
        return lo

    def free_run_count(self) -> int:
        """Number of maximal free runs: bits set in ``free & ~(free >> 1)``
        (each run contributes exactly its highest bit)."""
        free = ~self._occ & self._full_mask
        return bin(free & ~(free >> 1)).count("1")

    def reclassify(self, new_block_size: int) -> None:
        """Repurpose an EMPTY pool for another size class (sizeclass
        MM: carved budget never returns, so an idle class's segment must
        be reusable by a starved one).  Floor division — a segment of
        3 x 16 KB becoming a 32 KB-class pool holds 1 block and wastes
        the 16 KB tail until reclassified again."""
        assert self.allocated_blocks == 0, "reclassify of a live pool"
        assert self.pool_size >= new_block_size
        self.block_size = new_block_size
        self.total_blocks = self.pool_size // new_block_size
        self.allocated_blocks = 0
        self._rover = 0
        self._occ = 0
        self._full_mask = (1 << self.total_blocks) - 1
        self.reclassified = True

    def close(self) -> None:
        self._closing = True
        if self._prefault_thread is not None:
            self._prefault_thread.join(timeout=10.0)
        self.buf.release()
        self.mm.close()
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass


def _pow2ceil(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


class MM:
    """Multi-pool manager (reference: src/mempool.h:54-91).

    Two allocators (the reference names "bitmap or jemalloc",
    docs/source/design.rst:52):

    * ``"bitmap"`` (default): every pool uses one block size; a request
      takes a contiguous run of blocks.  Simple and fast for the
      homogeneous case (all KV pages of one model/dtype are the same
      size), but a mixed workload (int8 + bf16 namespaces, MoE + dense
      models on one store) pays up to ``block_size - 1`` bytes of
      internal fragmentation per small object and run-fragments the
      large ones.
    * ``"sizeclass"`` (the jemalloc-shaped option): requests round up to
      a power-of-two CLASS (>= the configured block size) and each class
      has its own pools, created lazily by carving the configured
      budget.  Every allocation is exactly one block of its class — no
      run search, no cross-size interleaving, internal fragmentation
      bounded by 2x worst-case instead of unbounded run churn.
      ``add_mempool`` (the auto-extend path) GROWS THE BUDGET; the next
      allocation carves the class pool it actually needs.
    """

    # lazily-carved class pools come in chunks of budget/CARVE_DIVISOR
    # (must match src/mempool.h kCarveDivisor — the two runtimes are
    # parity-tested as equivalents)
    CARVE_DIVISOR = 4
    # reject absurd wire-controlled sizes before class math (mirrors
    # src/mempool.h kMaxAllocSize)
    MAX_ALLOC_SIZE = 1 << 50

    def __init__(self, pool_size: int, block_size: int,
                 name_prefix: str = None, allocator: str = "bitmap"):
        if allocator not in ("bitmap", "sizeclass"):
            raise ValueError(f"unknown allocator: {allocator!r}")
        self.allocator = allocator
        self.block_size = block_size
        self.name_prefix = name_prefix or f"istpu_{os.getpid()}_{secrets.token_hex(4)}"
        self.pools: List[Pool] = []
        self.need_extend = False
        sweep_stale_segments()  # reclaim segments of SIGKILL'd servers
        if allocator == "bitmap":
            self.add_mempool(pool_size, block_size)
        else:
            # budget accounting: pools are carved per class on demand
            self._budget = pool_size
            self._carved = 0

    def _next_name(self) -> str:
        return f"{self.name_prefix}_p{len(self.pools)}"

    def add_mempool(self, pool_size: int = EXTEND_POOL_SIZE, block_size: int = None) -> Optional[Pool]:
        if self.allocator == "sizeclass":
            # the auto-extend contract: grant more BUDGET; the class
            # that hit the wall carves its pool on the retry
            self._budget += pool_size
            return None
        block_size = block_size or self.block_size
        pool = Pool(self._next_name(), _round_up(pool_size, block_size), block_size)
        self.pools.append(pool)
        return pool

    def _class_of(self, size: int) -> int:
        return _pow2ceil(max(size, self.block_size))

    def _carve(self, cls: int) -> Optional[int]:
        """A pool of class ``cls``: first by RECLASSIFYING an empty pool
        of another class (budget once carved never returns, so without
        reclassification one busy class could permanently starve the
        others), else by carving a chunk of budget/CARVE_DIVISOR (at
        least one block) from what is left.  Returns the pool's INDEX
        (a reclassified pool keeps its original slot — callers must not
        assume the newest pool), or None when neither works."""
        for pi, pool in enumerate(self.pools):
            if (pool.block_size != cls and pool.allocated_blocks == 0
                    and pool.pool_size >= cls):
                pool.reclassify(cls)
                return pi
        remaining = self._budget - self._carved
        # at least one block, never a many-block floor: a large class
        # would otherwise swallow the whole budget in one carve and
        # wedge every other class
        want = max(self._budget // self.CARVE_DIVISOR, cls)
        take = min(want, remaining)
        take -= take % cls  # whole blocks only
        if take < cls:
            return None
        pool = Pool(self._next_name(), take, cls)
        self.pools.append(pool)
        self._carved += take
        return len(self.pools) - 1

    def allocate(self, size: int, n: int) -> Optional[List[Tuple[int, int]]]:
        """Allocate ``n`` regions of ``size`` bytes.  Returns a list of
        (pool_idx, offset) or None (all-or-nothing, like the reference's
        callback-per-region allocate, src/mempool.cpp MM::allocate)."""
        if size == 0 or size > self.MAX_ALLOC_SIZE:  # wire-controlled
            return None
        cls = self._class_of(size) if self.allocator == "sizeclass" else None
        out: List[Tuple[int, int]] = []
        for _ in range(n):
            placed = False
            for pi, pool in enumerate(self.pools):
                if cls is not None and pool.block_size != cls:
                    continue
                off = pool.allocate(size)
                if off is not None:
                    out.append((pi, off))
                    placed = True
                    break
            if not placed and cls is not None:
                pi = self._carve(cls)
                if pi is not None:
                    # pi is the REAL index: a reclassified pool keeps
                    # its original slot, so recording the newest index
                    # here would point Store.view()/deallocate at the
                    # wrong pool's bytes (cross-class corruption)
                    off = self.pools[pi].allocate(size)
                    if off is not None:
                        out.append((pi, off))
                        placed = True
            if not placed:
                self.need_extend = True
                for pi, off in out:  # roll back
                    self.pools[pi].deallocate(off, size)
                return None
        return out

    def allocate_contiguous(self, size: int, n: int) -> Optional[List[Tuple[int, int]]]:
        """Best-effort: ``n`` regions of ``size`` bytes as ONE contiguous run
        inside one pool, so a batch put's descriptors merge into a single
        bulk memcpy client-side (the RDMA-WR-chain analog of the design).

        Region i sits at ``base + i * stride`` where stride is ``size``
        rounded up to the pool's block size — every region starts on a
        block boundary, so per-entry ``deallocate(offset, size)`` frees
        exactly its own blocks.  Returns None on failure WITHOUT setting
        ``need_extend``; callers fall back to the per-region ``allocate``.
        """
        if n <= 0 or size == 0 or size > self.MAX_ALLOC_SIZE:
            return None
        cls = self._class_of(size) if self.allocator == "sizeclass" else None
        for pi, pool in enumerate(self.pools):
            if cls is not None and pool.block_size != cls:
                continue
            stride = _round_up(size, pool.block_size)
            off = pool.allocate(stride * n)
            if off is not None:
                return [(pi, off + i * stride) for i in range(n)]
        if cls is not None:
            # carve (or reclassify) a class pool and retry the run there
            pi = self._carve(cls)
            if pi is not None:
                off = self.pools[pi].allocate(cls * n)
                if off is not None:
                    return [(pi, off + i * cls) for i in range(n)]
        return None

    def deallocate(self, pool_idx: int, offset: int, size: int) -> None:
        self.pools[pool_idx].deallocate(offset, size)

    def eviction_could_satisfy(self, size: int, n: int) -> bool:
        """sizeclass only: could freeing committed entries EVER make
        ``allocate(size, n)`` succeed?  Guards the store's pressure-
        evict loop — without it, one unsatisfiable request would drain
        the whole cache and still fail.  Counts this class's existing
        blocks, blocks reclassifiable from other classes' segments once
        they empty, and uncarved budget."""
        if self.allocator != "sizeclass":
            return False
        if size == 0 or size > self.MAX_ALLOC_SIZE:
            return False
        cls = self._class_of(size)
        have = sum(
            p.total_blocks for p in self.pools if p.block_size == cls
        )
        reclassifiable = sum(
            p.pool_size // cls
            for p in self.pools
            if p.block_size != cls and p.pool_size >= cls
        )
        budget_blocks = (self._budget - self._carved) // cls
        return n <= have + reclassifiable + budget_blocks

    def view(self, pool_idx: int, offset: int, size: int) -> memoryview:
        return self.pools[pool_idx].buf[offset : offset + size]

    def region_bytes(self, size: int) -> int:
        """What one region of ``size`` bytes takes from a pool."""
        if self.allocator == "sizeclass":
            return self._class_of(size)
        return _round_up(size, self.block_size)

    def free_bytes(self) -> int:
        free = sum((p.total_blocks - p.allocated_blocks) * p.block_size
                   for p in self.pools)
        if self.allocator == "sizeclass":
            free += self._budget - self._carved  # uncarved is capacity too
        return free

    def usage(self) -> float:
        used = sum(p.allocated_blocks * p.block_size for p in self.pools)
        if self.allocator == "sizeclass":
            # uncarved budget is still capacity: eviction thresholds must
            # not fire while whole classes remain uncarved
            total = max(self._budget, self._carved)
        else:
            total = sum(p.pool_size for p in self.pools)
        return used / total if total else 0.0

    def pool_table(self) -> List[Tuple[str, int, int]]:
        return [(p.name, p.pool_size, p.block_size) for p in self.pools]

    def frag_stats(self) -> Dict[str, float]:
        """Allocator-shape observability: how usable the free space is.
        ``fragmentation`` = 1 - largest_free_run / free_blocks (0 = one
        perfect run, -> 1 as free space shatters; 0 when nothing is free).
        This is the number that explains a batch ALLOC_PUT falling off the
        contiguous-run fast path (PR 1's read-lease bench trap) without
        attaching a debugger."""
        free_blocks = sum(
            p.total_blocks - p.allocated_blocks for p in self.pools
        )
        largest = max(
            (p.largest_free_run() for p in self.pools), default=0
        )
        runs = sum(p.free_run_count() for p in self.pools)
        frag = 1.0 - largest / free_blocks if free_blocks else 0.0
        return {
            "free_bytes": float(sum(
                (p.total_blocks - p.allocated_blocks) * p.block_size
                for p in self.pools
            )),
            "largest_free_run_bytes": float(max(
                (p.largest_free_run() * p.block_size for p in self.pools),
                default=0,
            )),
            "free_runs": float(runs),
            "fragmentation": frag,
        }

    def close(self) -> None:
        for p in self.pools:
            p.close()
        self.pools.clear()
