"""Token-chunk prefix hashing for KV-cache keys.

The reference stores KV blocks under variable-length string keys and leaves
key construction to the integration layer (LMCache hashes token chunks;
reference docs/source/design.rst notes keys carry "model_id, request, and
token hash").  We make that scheme first-class: a sequence of tokens is cut
into fixed-size chunks and each chunk's key commits to the *entire prefix*
up to and including that chunk, so a key match implies a full prefix match
and ``get_match_last_index`` (reference: src/infinistore.cpp:786-802) finds
the longest reusable prefix with one round-trip.

What a match lets a cache REUSE depends on what it holds.  Pages of keys and
values are reusable chunk by chunk: every matching key is 16 more tokens not
recomputed.  A model that keeps a STATE and no key or value per token
(models/retention.py) can start only from a position whose state was kept,
so the same keys name checkpoints there: the key of the chunk that ends at a
checkpoint's position names it, a match means "this exact prefix's state",
and the keys in between match nothing that is held
(engine/state_engine.py).
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence

DEFAULT_CHUNK_TOKENS = 16

# Versions the in-page byte layout ([2, H_kv, T, D] since v2); part of the
# hash seed so pages persisted under a different layout can never be
# reinterpreted silently -- they simply miss.
KV_LAYOUT_VERSION = "kv2"


def chunk_keys(
    tokens: Sequence[int],
    model_id: str,
    chunk_tokens: int = DEFAULT_CHUNK_TOKENS,
    layer: int | None = None,
    world_suffix: str = "",
) -> List[str]:
    """Keys for every *complete* chunk of ``tokens``.

    Each key is ``{model_id}[.L{layer}]{world_suffix}:{rolling prefix hash}``.
    Incomplete trailing chunks get no key (they are recomputed, same as
    LMCache's chunked prefix caching).
    """
    n_full = len(tokens) // chunk_tokens
    keys: List[str] = []
    h = hashlib.blake2b(
        f"{KV_LAYOUT_VERSION}:{model_id}".encode(), digest_size=16
    )
    for c in range(n_full):
        chunk = tokens[c * chunk_tokens : (c + 1) * chunk_tokens]
        h = h.copy()
        h.update(b"".join(int(t).to_bytes(4, "little", signed=False) for t in chunk))
        digest = h.hexdigest()
        prefix = f"{model_id}.L{layer}" if layer is not None else model_id
        keys.append(f"{prefix}{world_suffix}:{digest}")
    return keys


def layer_key(base_key: str, layer: int) -> str:
    """Derive a per-layer key from a chunk key (layer-by-layer streaming
    writes KV per layer, reference docs/source/design.rst prefill flow)."""
    return f"{base_key}#L{layer}"


def matched_token_count(match_last_index: int, chunk_tokens: int = DEFAULT_CHUNK_TOKENS) -> int:
    """Tokens covered by a store prefix match (-1 means no match)."""
    return (match_last_index + 1) * chunk_tokens
