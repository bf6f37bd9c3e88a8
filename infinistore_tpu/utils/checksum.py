"""Content checksums for the KV integrity plane.

One definition shared by the store (stamping at commit, scrub
re-verification) and the client (verification after the bulk copy), so a
mismatch always means the BYTES changed, never that two implementations
disagree.  Two algorithms:

* ``sum64`` (default) — a vectorized 64-bit wrapping sum over
  little-endian words, avalanched and folded to 32 bits.  Runs at memory
  bandwidth through numpy (~8 GB/s measured on the 1-vCPU reference
  host), which is what lets commit-time stamping and read-time
  verification coexist with the coalesced data plane's throughput floor
  (docs/design.md §2).  Detects every single-bit flip, torn write,
  and recycled-region read; the accepted weakness is commutativity
  (swapped aligned words collide), which none of the failure modes in
  docs/robustness.md produce.
* ``crc32`` — ``zlib.crc32``, the standard answer, for operators who
  want CRC guarantees and have the cores to pay for it (~1 GB/s per core
  on the reference host — it contends with the data plane on small
  hosts, which is why it is not the default).

The algorithm is a SERVER property (``ISTPU_INTEGRITY_ALG`` /
``--integrity-alg``), advertised to clients in the HELLO epoch trailer,
so both ends always agree.
"""

from __future__ import annotations

import zlib

import numpy as np

ALG_SUM64 = 1
ALG_CRC32 = 2

_ALG_IDS = {"sum64": ALG_SUM64, "crc32": ALG_CRC32}
_ALG_NAMES = {v: k for k, v in _ALG_IDS.items()}

_M64 = (1 << 64) - 1
_GOLD = 0x9E3779B97F4A7C15  # 2^64 / golden ratio: length mixing
_MIX = 0xFF51AFD7ED558CCD   # murmur3 finalizer constant: avalanche


def alg_id(name: str) -> int:
    try:
        return _ALG_IDS[name]
    except KeyError:
        raise ValueError(
            f"integrity alg must be one of {sorted(_ALG_IDS)}, got {name!r}"
        ) from None


def alg_name(aid: int) -> str:
    return _ALG_NAMES.get(aid, f"unknown({aid})")


def _fold(s: int, nbytes: int) -> int:
    """Mix the length in, avalanche, fold to u32 — shared by the scalar
    and the row-vectorized paths (they must agree bit-for-bit)."""
    s = (s + ((nbytes * _GOLD) & _M64)) & _M64
    s = (s * _MIX) & _M64
    return ((s >> 32) ^ s) & 0xFFFFFFFF


def checksum(data, alg: int = ALG_SUM64) -> int:
    """Checksum of one bytes-like/buffer region (u32)."""
    if alg == ALG_CRC32:
        return zlib.crc32(data) & 0xFFFFFFFF
    a = np.frombuffer(data, dtype=np.uint8)
    n = a.nbytes
    n8 = n & ~7
    s = int(a[:n8].view(np.uint64).sum(dtype=np.uint64)) if n8 else 0
    if n8 != n:
        # zero-padded trailing word, little-endian — keeps the scalar
        # path defined for arbitrary (inline-put) sizes
        tail = a[n8:].tobytes() + b"\x00" * (8 - (n - n8))
        s = (s + int.from_bytes(tail, "little")) & _M64
    return _fold(s, n)


def checksum_rows(rows: "np.ndarray", alg: int = ALG_SUM64):
    """Per-row checksums of a contiguous ``(n, row_bytes)`` uint8 array —
    ONE vectorized pass over a whole coalesced run instead of a per-page
    Python loop (``row_bytes % 8 == 0`` required for sum64).  Returns a
    list of ints, row order preserved, each equal to ``checksum(row)``."""
    n, rb = rows.shape
    if alg == ALG_CRC32:
        return [zlib.crc32(rows[i]) & 0xFFFFFFFF for i in range(n)]
    assert rb % 8 == 0, rb
    sums = rows.view(np.uint64).reshape(n, rb // 8).sum(
        axis=1, dtype=np.uint64
    )
    s = (sums + np.uint64((rb * _GOLD) & _M64)) * np.uint64(_MIX)
    out = ((s >> np.uint64(32)) ^ s) & np.uint64(0xFFFFFFFF)
    return [int(v) for v in out]
