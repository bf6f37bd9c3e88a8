"""The engine over a cache whose unit is a STATE, not a page.

A power-retention layer (models/retention.py) keeps no key or value per
token: a sequence's whole past in one layer is one array of fixed size, and
every step WRITES it.  What that forces on the cache tier, and what of
``InferenceEngine`` this subclass replaces for it:

* **Slots** (kv/cache.py ``StateCacheConfig`` / ``StateSlots``).  ``self.cache``
  is ``(S [slots, L, H_kv, F, D], z [slots, L, H_kv, F])``, float32, donated
  through the prefill chunk and the decode scan.  A running row owns one slot
  and writes it (``SequenceState.slot``); a resident checkpoint owns one and is
  never written; ``release`` returns the row's.  No pages, no block table:
  the scan's ``block_table`` is the rows' slot ids ``[B, 1]``.
* **A checkpoint a prompt.**  A state summarises everything before it, so a
  prefix is reusable only at a position at which a state was KEPT: of a
  prompt the deepest multiple of ``pc.stride`` that at least one token
  follows.  The state there is copied into a resident slot under that
  position's chunk key (kv/hashing.chunk_keys, unchanged) and pushed to the
  store, every layer, acknowledged before the prompt's state is visible
  under strict durability (``prefill_settle``).  Decode takes none.
* **A hit COPIES.**  ``prefill_start`` finds the deepest stride-aligned
  position whose key is resident in HBM, else in the store, copies or loads
  that checkpoint into the row's own slot (a loaded one is kept resident
  too, as a computed one is) and prefills from there: chunk
  boundaries then fall where they fell when the prompt was first computed,
  so a re-ask's logits are bit for bit the computed prompt's.  "The longest
  matching prefix" became "the deepest checkpoint whose key matches"; what a
  prompt shares with an earlier one beyond that is recomputed
  (``shared_tokens_recomputed`` counts it).  A store failure costs a miss and
  a recompute, never a request (``guarded_*``).  A row that adopts nothing
  starts from a slot zeroed for it: what a slot held before never reaches
  the arithmetic.

``reused_chunks`` / ``local_chunks`` / ``store_chunks`` / ``store_load_s`` and
``istpu_engine_prefix_tokens_total`` keep their meaning: 16-token chunks (and
tokens) of the prompt not recomputed, by where their checkpoint came from.
"""

from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..kv.cache import StateCacheConfig, StateSlots
from ..kv.hashing import chunk_keys
from ..kv.transfer import StateTransferEngine
from ..utils import metrics as _metrics
from . import stepprof as _stepprof
from .engine import (
    _PREFIX_TOKENS,
    _PREFIX_TOKENS_TENANT,
    InferenceEngine,
    PartialPrefill,
    SequenceState,
)
from .. import usage as _usage

_CHECKPOINTS = _metrics.default_registry().counter(
    "istpu_engine_state_checkpoints_total",
    "State checkpoints of prompts: taken into a resident slot, pushed to the "
    "store, or not pushed because the store had their key",
    labelnames=("event",),
)
_BYTES_PUSHED = _metrics.default_registry().counter(
    "istpu_engine_state_bytes_pushed_total",
    "Bytes of state checkpoints handed to the store",
)
_BYTES_LOADED = _metrics.default_registry().counter(
    "istpu_engine_state_bytes_loaded_total",
    "Bytes of state checkpoints that came back from the store into a row's "
    "slot, where a sequence keeps pages and a state",
)
_SCAN = _metrics.default_registry().counter(
    "istpu_engine_state_scan_total",
    "Where a state's update is a scan over the chunk: the prefill chunks "
    "that ran it, the whole chunks among them, and the tokens they walked, "
    "padding included",
    labelnames=("what",),
)
_ADOPTIONS = _metrics.default_registry().counter(
    "istpu_engine_state_adoptions_total",
    "Prompts that started from a checkpoint, by where it came from",
    labelnames=("source",),
)
_SHARED_RECOMPUTED = _metrics.default_registry().counter(
    "istpu_engine_state_shared_tokens_recomputed_total",
    "Prompt tokens shared with an earlier prompt but recomputed because no "
    "checkpoint was kept that deep",
)
_STORE_HITS = _metrics.default_registry().counter(
    "istpu_engine_state_store_hits_total",
    "Prompts whose pages the store matched deeper than HBM held them, where a "
    "sequence keeps pages and a state: all of them, and those that adopted "
    "pages and checkpoint at the matched depth (full)",
    labelnames=("depth",),
)
_EVICTED = _metrics.default_registry().counter(
    "istpu_engine_state_resident_evicted_total",
    "Resident checkpoints evicted from their slot for a newer one",
)


@partial(jax.jit, donate_argnums=(0,))
def _copy_slot(cache, src, dst):
    """Slot ``src`` over slot ``dst`` of the donated slots, every layer."""
    return tuple(a.at[dst].set(a[src]) for a in cache)


@partial(jax.jit, donate_argnums=(0,))
def _zero_slot(cache, dst):
    return tuple(a.at[dst].set(0.0) for a in cache)


def refuse_for_slots(kw: dict) -> None:
    """What no engine whose sequences keep a state in a slot serves, refused
    in words where the engine is built (``kw``: its keyword arguments)."""
    if kw.get("kv_quant") is not None:
        raise ValueError(
            f"kv quant {kw['kv_quant']!r} scales pages per (K|V, head); "
            f"a state has no such scale and goes to the store as it is")
    kw["kv_quant"] = None
    for name in ("mesh", "lora", "verify_fn"):
        if kw.get(name) is not None:
            raise ValueError(f"a cache of state slots is served without "
                             f"{name}")
    if kw.get("conn") is not None:
        from ..cluster import RoutedStorePool

        if isinstance(kw["conn"], RoutedStorePool):
            raise ValueError(
                "state checkpoints go to ONE store connection; the "
                "clustered transfer routes pages by chunk")


class SlotBook:
    """What the engines whose sequences keep a state share (this module's and
    engine/hybrid_engine.py's), mixed in before ``InferenceEngine``: what such
    a cache refuses, a running row's slot from ``prefill_start`` to
    ``release``, the counters' sinks and the resident checkpoints' copies.
    ``self.slots`` is the ``StateSlots``; ``_slot_arrays`` the donated arrays
    whose first axis is the slot.  An engine keeps how a hit is found
    (``_start_in_row``), what a checkpoint is and what rides in a push."""

    batched_prefill = False      # a row's slot is taken in ``prefill_start``

    def __init__(self, params, cfg, pc, **kw):
        refuse_for_slots(kw)
        kw.setdefault("max_seqs", pc.max_rows)
        super().__init__(params, cfg, pc, **kw)
        if self.prefill_chunk is None or pc.stride % self.prefill_chunk:
            raise ValueError(
                f"a checkpoint is taken at the end of a prefill chunk: the "
                f"stride {pc.stride} must be a multiple of prefill_chunk "
                f"({self.prefill_chunk})")
        self.slots = StateSlots(pc.n_slots, pc.max_rows)

    def prefill_start(self, tokens: Sequence[int],
                      adapter_id: int = 0) -> PartialPrefill:
        """Admission half of a prefill: a row's slot (``MemoryError`` where
        every one is taken), the deepest position this prompt can start from
        (the engine's ``_start_in_row``), what is kept there adopted, and the
        chunking.  ``_start_in_row`` fills ``block_ids`` in place: what the
        list holds when it raises is what is pinned."""
        assert adapter_id == 0 and len(tokens) >= 1, adapter_id
        tokens = list(tokens)
        keys = chunk_keys(tokens, self.model_id,
                          chunk_tokens=self.pc.block_tokens)
        row = self.slots.take_row()
        block_ids: List[int] = []
        try:
            return self._start_in_row(tokens, keys, row, block_ids)
        except BaseException:
            self.pages.unpin(block_ids)
            self.slots.free_row(row)
            raise

    def _make_visible(self, pp: PartialPrefill) -> SequenceState:
        """The row's slot handed over to the decode-ready state (under strict
        durability ``prefill_settle`` has awaited the checkpoint's push)."""
        state = super()._make_visible(pp)
        state.slot, pp.slot = pp.slot, -1
        return state

    def abandon_prefill(self, pp: PartialPrefill) -> None:
        super().abandon_prefill(pp)
        self._free_row(pp)

    def release(self, state: SequenceState) -> None:
        self._free_row(state)
        super().release(state)

    def _free_row(self, holder) -> None:
        """The row's slot of a prefill or a sequence, back once."""
        if holder.slot >= 0:
            self.slots.free_row(holder.slot)
            holder.slot = -1

    def _slot_column(self, states, pad_to: Optional[int] = None) -> jax.Array:
        """The rows' slots ``[rows, 1]``, as the decode scan's block table
        takes them; a pad row's is one past the slots (its read clamps, its
        write is dropped)."""
        pad = (pad_to or len(states)) - len(states)
        return jnp.asarray([[st.slot] for st in states]
                           + [[self.pc.n_slots]] * pad, dtype=jnp.int32)

    def adopt_prefill(self, tokens, kv, last_logits):
        raise ValueError("adopt_prefill lands K and V in pages; layers of "
                         "this model keep a state too (prefill it through "
                         "the engine)")

    def prompt_logprobs(self, tokens, k: int = 0, adapter_id: int = 0):
        raise ValueError("prompt scoring runs a paged family's dense "
                         "forward; this model's prefill runs through its "
                         "state slots")

    def propose(self, *a, **kw):
        raise ValueError("a sequence that keeps a state drafts nothing: a "
                         "rejected token cannot be taken out of a state")

    @property
    def _slot_arrays(self) -> tuple:
        return self.cache

    @_slot_arrays.setter
    def _slot_arrays(self, arrays: tuple) -> None:
        self.cache = arrays

    def _count(self, **counts: int) -> None:
        """One event into every sink: /debug/engine's ``summary.state`` and
        the /metrics families."""
        _stepprof.note_state(**counts)
        for k, n in counts.items():
            if k.startswith("checkpoints_"):
                _CHECKPOINTS.labels(k[len("checkpoints_"):]).inc(n)
            elif k.startswith("adopted_"):
                _ADOPTIONS.labels(k[len("adopted_"):]).inc(n)
            elif k in ("store_hits", "store_hits_full"):
                _STORE_HITS.labels("full" if k.endswith("_full") else "all").inc(n)
            elif k.startswith("scan_"):
                _SCAN.labels(k[len("scan_"):]).inc(n)
            else:
                {"bytes_pushed": _BYTES_PUSHED, "bytes_loaded": _BYTES_LOADED,
                 "shared_tokens_recomputed": _SHARED_RECOMPUTED,
                 "resident_evicted": _EVICTED}[k].inc(n)

    def _keep_resident(self, key: str, row: int) -> bool:
        """Row ``row``'s state as it is now, copied into a resident slot under
        ``key`` (the least recently used unpinned checkpoint goes); False
        where the key is resident already or every slot is pinned."""
        if key in self.slots:
            return False
        before = self.slots.evicted
        dst = self.slots.keep()
        if dst is None:
            return False
        self._slot_arrays = _copy_slot(self._slot_arrays, row, dst)
        self.slots.register(key, dst)
        self.slots.unpin(dst)
        if self.slots.evicted > before:
            self._count(resident_evicted=1)
        return True

    def _adopt_resident(self, key: str, row: int) -> bool:
        """The resident checkpoint of ``key`` copied into row ``row``'s slot
        (copied, not shared: the row will write its slot); False where none
        is resident."""
        src = self.slots.match(key)
        if src is None:
            return False
        self._slot_arrays = _copy_slot(self._slot_arrays, src, row)
        self.slots.unpin(src)
        return True

    def _zero_row(self, row: int) -> None:
        """A row that adopts nothing starts from zeros: what a slot held
        before never reaches the arithmetic."""
        self._slot_arrays = _zero_slot(self._slot_arrays, row)


class StateEngine(SlotBook, InferenceEngine):
    cache_cls = StateCacheConfig
    transfer_cls = StateTransferEngine
    prefill_donates = ("cache",)

    # chunk keys of prompts seen, to count what a prompt shares with an
    # earlier one beyond the checkpoint it could adopt; host strings only
    SEEN_KEYS = 1 << 16

    def __init__(self, params, cfg, pc: StateCacheConfig, **kw):
        super().__init__(params, cfg, pc, **kw)
        self._seen: "OrderedDict[str, None]" = OrderedDict()

    def _dense_attention_in_kernel(self) -> bool:
        return False

    # ---- prefill ----

    def _start_in_row(self, tokens: List[int], keys: List[str], row: int,
                      block_ids: List[int]) -> PartialPrefill:
        """The deepest checkpoint this prompt can start from, copied into the
        row's slot; a state keeps no pages, so ``block_ids`` stays empty."""
        T, n = self.pc.block_tokens, len(tokens)
        # where a checkpoint may lie and leave a token to compute (the last
        # token's logits start the decode): stride, 2 x stride, .. <= n - 1
        aligned = list(range(self.pc.stride, n, self.pc.stride))

        def key_at(p: int) -> str:
            return keys[p // T - 1]

        P, source = 0, None
        lookup_s = load_s = 0.0
        for p in reversed(aligned):          # deepest resident in HBM
            if self._adopt_resident(key_at(p), row):
                P, source = p, "local"
                break
        deeper = [p for p in aligned if p > P]
        if self.transfer is not None and deeper:
            # breaker-guarded: a dead store reports a miss, never a failure
            with _stepprof.phase("kv.lookup") as ph:
                hit = self.transfer.guarded_lookup_prefix(
                    [key_at(p) for p in deeper])
            lookup_s = ph.s
            if hit:
                p = deeper[hit - 1]
                ok, load_s = self._load([row], [key_at(p)], tokens=p)
                if ok:
                    P, source = p, "store"
                    # a store hit becomes resident as a computed checkpoint
                    # does (its copy leaves HBM again by the same LRU)
                    self._keep_resident(key_at(p), row)
        if source is None:
            self._zero_row(row)
        else:
            self._count(**{f"adopted_{source}": 1})
        shared = self._shared_with_earlier(keys) * T
        if shared > P:
            self._count(shared_tokens_recomputed=min(shared, n - 1) - P)

        local = P if source == "local" else 0
        store = P if source == "store" else 0
        tenant = _usage.current_account()
        for label, count in (("local", local), ("store", store),
                             ("computed", n - P)):
            if count:
                _PREFIX_TOKENS.labels(label).inc(count)
                if tenant is not None:
                    _PREFIX_TOKENS_TENANT.labels(tenant, label).inc(count)

        suffix = tokens[P:]
        S = len(suffix)
        padded = suffix + [0] * ((-S) % T)
        C = self.prefill_chunk
        return PartialPrefill(
            tokens=tokens, keys=keys, block_ids=block_ids, reused=P // T,
            done=P // T, n_complete=n // T, padded=padded, C=C,
            single=C >= len(padded), buf=None, plen=P, S=S,
            slot=row, ckpt_at=aligned[-1] if aligned and aligned[-1] > P else 0,
            local_chunks=local // T, store_chunks=store // T,
            store_load_s=lookup_s + load_s, lookup_s=lookup_s,
        )

    def _shared_with_earlier(self, keys: List[str]) -> int:
        """Leading chunks of ``keys`` that an earlier prompt had too (a key
        commits to its whole prefix); then these are remembered."""
        shared = 0
        for k in keys:
            if k not in self._seen:
                break
            shared += 1
        for k in keys:
            self._seen[k] = None
            self._seen.move_to_end(k)
        while len(self._seen) > self.SEEN_KEYS:
            self._seen.popitem(last=False)
        return shared

    def _prefill_chunk(self, pp: PartialPrefill) -> None:
        off, C = pp.off, pp.C
        chunk = pp.padded[off: off + C]
        start = pp.plen + off
        # the head on the prompt's last position alone, in its last chunk
        last = off + C >= len(pp.padded)
        rows, self.cache = self._prefill(
            [(pp.S - 1) - off] if last else None,
            tokens=jnp.asarray(chunk, dtype=jnp.int32)[None],
            cache=self.cache, slot=jnp.asarray(pp.slot, jnp.int32),
            start=jnp.asarray(start, jnp.int32),
            n_valid=jnp.asarray(min(len(chunk), pp.S - off), jnp.int32))
        pp.logits = rows[0] if last else None
        _stepprof.note_dispatch("prefill")
        pp.off = off + C
        pp.done = (start + len(chunk)) // self.pc.block_tokens
        if pp.ckpt_at and start + len(chunk) == pp.ckpt_at:
            self._checkpoint(pp)

    def _checkpoint(self, pp: PartialPrefill) -> None:
        """The row's state, now that of position ``pp.ckpt_at``, kept: a copy
        in a resident slot under that position's key, and a push of every
        layer unless the store has the key.  Both read the row's slot as it
        is NOW (a copy and a gather, enqueued before the next chunk's
        write), so the row goes on at once."""
        key = pp.keys[pp.ckpt_at // self.pc.block_tokens - 1]
        if self._keep_resident(key, pp.slot):
            self._count(checkpoints_taken=1)
        if self.transfer is None:
            return
        with _stepprof.phase("kv.lookup"):
            stored = self.transfer.guarded_lookup_prefix([key])
        if stored:
            self._count(checkpoints_skipped_stored=1)
            return
        with _stepprof.phase("kv.push_gather"):
            pages = self.transfer.gather_pages(self.cache, pp.slot)
            _stepprof.enter("kv.push_submit")
            self.transfer.covers(key, pp.ckpt_at)
            self._streamer.submit(pages, [key], marker=pp.marker)
        self._count(checkpoints_pushed=1, bytes_pushed=self.pc.slot_bytes)

    # ---- decode ----

    def _grow_tables(self, states, n_steps: int) -> None:
        pass                    # a state does not grow with the sequence

    def _live_tokens(self, lens) -> int:
        # a row's read is its state, whatever its length: one table entry of
        # ``block_tokens`` a row, so that ``live / table`` is the share of
        # the states read that are real rows' and not pad rows'
        return len(lens) * self.pc.block_tokens

    def _block_table(self, states, pad_to: Optional[int] = None) -> jax.Array:
        """The rows' slots in the block table's place."""
        return self._slot_column(states, pad_to)

    @property
    def free_pages(self) -> int:
        """What admission compares a request's pages with: any request fits
        while a row's slot is free (``serve`` bounds one by ``n_blocks``)."""
        return self.slots.rows_free * self.pc.n_blocks
