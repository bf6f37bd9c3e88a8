"""The engine over a cache of TWO KINDS: pages for a stack's attention layers
and, beside them in the same sequence, a state of fixed size for its other
layers (gated short convolutions, models/lfm2_moe.py; Mamba selective-scan
layers, whose state is float32, models/jamba.py; kv/cache.py
``HybridCacheConfig``).

It is the paged engine (``InferenceEngine``: the allocator, the prefix page
cache, the block table, the prefix buffer, chunked prefill's loop, the decode
scan, the streamer, ``prefill_settle``) plus the slot bookkeeping of
engine/state_engine.py (``SlotBook``, ``StateSlots``, ``_copy_slot``); what is
its own is the rule that joins the two:

* ``self.cache`` is ``(pages [attention layers, 2, H_kv, n_blocks, T, D], slots
  [n_slots] + pc.slot_shape)``, both donated through the prefill chunk
  and the decode scan.  A ``SequenceState`` holds ``block_ids`` AND a ``slot``;
  the scan's block table is ``(the pages' table, the rows' slots [B, 1])``.
* **A checkpoint at every multiple of the stride** a prompt's prefill passes
  (``pc.stride``, a multiple of ``prefill_chunk``, so at a chunk's end): the
  row's state there is copied into a resident slot under that position's
  chunk key and rides to the store in the chunk's own push, behind its pages
  (kv/transfer.py ``HybridTransferEngine``), so that strict durability's one
  wait (``prefill_settle``) acknowledges both.  Decode takes none.
* **A hit is the deepest position at which BOTH exist.**  ``prefill_start``
  matches pages as the paged engine does (HBM, then the store), then takes the
  deepest multiple of the stride at or below that match whose checkpoint is
  resident or in the store; pages beyond it are not adopted, the row's slot is
  a COPY of the checkpoint (a row that adopts none starts from a zeroed slot)
  and the prefill goes on from there: chunk boundaries fall where they fell
  when the prompt was computed, so a re-ask's logits are bit for bit the
  computed prompt's.  Pages not held and a checkpoint not resident come back
  in ONE load, both or neither; a load that fails costs a shallower hit (what
  HBM holds of both) or a miss, never a request (``guarded_*``).  Tokens whose
  pages matched and which were recomputed for want of a checkpoint are
  counted (``shared_tokens_recomputed``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..kv.cache import HybridCacheConfig
from ..kv.transfer import HybridTransferEngine
from . import stepprof as _stepprof
from .engine import InferenceEngine, PartialPrefill
from .state_engine import SlotBook


class HybridEngine(SlotBook, InferenceEngine):
    cache_cls = HybridCacheConfig
    transfer_cls = HybridTransferEngine
    prefill_donates = ("conv",)

    # the two kinds, each where its helpers look for it

    @property
    def _page_pool(self) -> jax.Array:
        return self.cache[0]

    @_page_pool.setter
    def _page_pool(self, pages: jax.Array) -> None:
        self.cache = (pages, self.cache[1])

    @property
    def _slot_arrays(self) -> tuple:
        return (self.cache[1],)

    @_slot_arrays.setter
    def _slot_arrays(self, arrays: tuple) -> None:
        self.cache = (self.cache[0], arrays[0])

    # ---- prefill ----

    def _start_in_row(self, tokens: List[int], keys: List[str], row: int,
                      block_ids: List[int]) -> PartialPrefill:
        """The deepest position at which this prompt finds pages AND a
        checkpoint, both adopted, and the rest of its pages.  ``block_ids`` is
        the caller's list, filled in place: what it holds when this raises is
        what is pinned."""
        T, n = self.pc.block_tokens, len(tokens)
        per = self.pc.stride // T                 # chunks a stride
        # the pages, as the paged engine matches them: HBM, then the store;
        # capped so that a token is left to compute
        max_reuse = (n - 1) // T
        block_ids += self.pages.match_prefix(keys[:max_reuse])  # pins hits
        n_local = len(block_ids)
        lookup_s = load_s = 0.0
        n_store = 0
        if self.transfer is not None and n_local < max_reuse:
            with _stepprof.phase("kv.lookup") as ph:
                n_store = min(self.transfer.guarded_lookup_prefix(keys),
                              max_reuse)
            lookup_s = ph.s
        matched = max(n_local, n_store)

        # the checkpoints at or below the match, deepest first: resident, or
        # in the store (asked only for the positions deeper than that)
        def key_at(c: int) -> str:                # c chunks = position c x T
            return keys[c - 1]

        at_stride = range(per, matched + 1, per)
        resident = next((c for c in reversed(at_stride)
                         if key_at(c) in self.slots), 0)
        cut, stored = resident, False
        deeper = [c for c in at_stride if c > resident]
        if self.transfer is not None and deeper:
            with _stepprof.phase("kv.lookup") as ph:
                hit = self.transfer.guarded_lookup_prefix(
                    [key_at(c) for c in deeper], states=True)
            lookup_s += ph.s
            if hit:
                cut, stored = deeper[hit - 1], True

        # pages beyond the cut are other sequences' to read, not this one's
        # to write; the rest of the table is fresh
        def cut_table(c: int) -> None:
            nonlocal n_local
            beyond = block_ids[c:]
            del block_ids[c:]
            self.pages.unpin(beyond)
            n_local = len(block_ids)
            block_ids.extend(self.pages.acquire(-(-n // T) - n_local))

        cut_table(cut)
        if cut > n_local or stored:
            # one load: the pages HBM does not hold and the checkpoint where
            # it is not resident, both or neither
            ok, load_s = self._load(
                block_ids[n_local:cut], keys[n_local:cut],
                state=(row, key_at(cut)) if stored else None)
            if ok and stored:
                # a store hit becomes resident, as a computed checkpoint does
                self._keep_resident(key_at(cut), row)
            elif not ok:
                # what HBM holds of both: the pages matched there, and the
                # deepest resident checkpoint at or below them
                self.pages.unpin(block_ids[n_local:])
                del block_ids[n_local:]
                cut, stored = next(
                    (c for c in reversed(at_stride)
                     if c <= n_local and key_at(c) in self.slots), 0), False
                cut_table(cut)
        if stored:
            self._count(adopted_store=1, bytes_loaded=self.pc.slot_bytes)
        elif cut and self._adopt_resident(key_at(cut), row):
            self._count(adopted_local=1)
        else:
            assert cut == 0, cut
            self._zero_row(row)
        if matched > cut:
            self._count(shared_tokens_recomputed=(matched - cut) * T)
        if n_store > n_local and self.transfer is not None:
            # full: the deepest stride the matched pages reach was adopted
            self._count(store_hits=1,
                        store_hits_full=int(cut == matched // per * per))
        return self._begin_chunks(tokens, keys, block_ids, cut,
                                  min(n_local, cut), lookup_s, load_s, slot=row)

    def _chunk_args(self, pp: PartialPrefill, n_tokens: int) -> Dict[str, Any]:
        return {"conv": self.cache[1],
                "slot": jnp.asarray(pp.slot, jnp.int32),
                # the chunk's tokens that are the prompt's: a padded tail
                # enters no state
                "n_valid": jnp.asarray(min(n_tokens, pp.S - pp.off), jnp.int32)}

    def _chunk_landed(self, kv):
        kv, conv = kv
        self.cache = (self.cache[0], conv)
        return kv

    def _checkpoint_at(self, pp: PartialPrefill, chunks: int) -> bool:
        """Whether the row's state after ``chunks`` chunks is one to keep: a
        multiple of the stride, every token up to it the prompt's."""
        return (chunks * self.pc.block_tokens % self.pc.stride == 0
                and chunks <= pp.n_complete)

    def _gather_push(self, pp: PartialPrefill, lo: int, hi: int):
        # chunks ``[lo, hi)`` end where the chunk's program left the row's
        # state; at a multiple of the stride it rides behind their pages
        ckpt = hi == pp.done and self._checkpoint_at(pp, hi)
        if ckpt:
            self._count(checkpoints_pushed=1, bytes_pushed=self.pc.slot_bytes)
        return (self.transfer.gather_pages(
            self.cache, pp.block_ids[lo:hi], slot=pp.slot if ckpt else None),
            pp.keys[lo:hi])

    def _prefill_chunk(self, pp: PartialPrefill) -> None:
        if getattr(self.cfg, "state_update", None) == "scan":
            # the tokens the chunk's scan walks, its padding among them
            n = min(pp.C, len(pp.padded) - pp.off)
            self._count(scan_chunks=1, scan_tokens=n,
                        scan_full_chunks=int(n == self.prefill_chunk))
        super()._prefill_chunk(pp)
        if self._checkpoint_at(pp, pp.done):
            # a copy, enqueued behind the chunk and before the next one's
            # write of the row's slot
            with _stepprof.phase("kv.checkpoint"):
                if self._keep_resident(pp.keys[pp.done - 1], pp.slot):
                    self._count(checkpoints_taken=1)

    # ---- decode ----

    def _block_table(self, states, pad_to: Optional[int] = None):
        """``(the pages' table, the rows' slots [rows, 1])``; a pad row's slot
        is one past the slots, as its pages are one past the pool."""
        return (super()._block_table(states, pad_to=pad_to),
                self._slot_column(states, pad_to))

    @property
    def free_pages(self) -> int:
        """What admission compares a request's pages with: the pool's, while a
        row's slot is free."""
        return self.pages.available if self.slots.rows_free else 0
