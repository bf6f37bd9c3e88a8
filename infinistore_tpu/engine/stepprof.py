"""Per-step engine/device attribution: the ``StepProfiler``.

The serving metrics say how the fleet is doing and the request ledger
says where one request's latency went — but the ENGINE STEP LOOP and the
device under it were a black box: no dispatch counts, no compile/retrace
visibility, no host-blocked vs device-busy split, no HBM watermarks.
A slow store-attached prefill or a speculation mode slower than plain
decode at high acceptance (ROADMAP A1, A5) cannot be explained without
exactly that attribution.  This module makes the step loop emit ONE
structured record per scheduler step:

* **step kind and batch composition** — prefill chunks advanced, decode
  sequences, speculative rounds, pending depth;
* **dispatch counts** — compiled STEP programs launched (decode scan
  chunks, prefill chunk forwards, verify/draft forwards, fused
  speculation rounds).  Counted per step program, not per raw XLA
  executable launch;
* **host-stall vs device time** — on SAMPLED steps (1 in
  ``ISTPU_STEPPROF_SAMPLE``, default 16) the profiler times a
  ``block_until_ready`` on the engine's cache after the step body:
  the measured wait is device work the host did NOT overlap.  High
  stall share ⇒ device-bound; ~0 stall with long steps ⇒ the host loop
  (dispatch overhead, Python) is the bottleneck — read this before
  blaming a kernel.  Sampling keeps the ≤5%
  instrumentation-overhead guard passing: a per-step block would
  serialize the async dispatch pipeline the engine exists to keep full;
* **compile/retrace events** — a ``jax.monitoring`` duration listener
  counts backend compiles process-wide, and the engine's shared-jit
  wrapper (``count_trace``) attributes trace-cache misses PER FUNCTION
  (the python body of a jitted function only runs at trace time, so
  counting body executions counts traces exactly — first compile
  included);
* **device memory watermarks** — ``device.memory_stats()`` where the
  backend provides it (TPU/GPU), falling back to summing
  ``jax.live_arrays()`` on CPU; sampled with the stall probe;
* **speculation attribution** — per-step deltas of the speculator's
  rounds/proposed/accepted counters next to the dispatch counts, so
  a slowdown at high acceptance reads as tokens-per-dispatch, not a
  mystery;
* **store-hop stages** — when a step moved pages, the delta of the
  transfer's ``push_totals`` / ``load_totals`` over the step rides along
  (pushes commit on the streamer thread, so a push may count one step
  late; the lifetime totals in ``summary()['store']`` lose nothing);
* **flat engine-thread phases** — ``enter(name)`` ends the phase before
  and begins the next, so the phases PARTITION the driving thread's time.
  Each is at once a ``jax.profiler.TraceAnnotation("istpu.<name>")`` (the
  host plane of the profiler's own trace: same clock as the device's
  operations), seconds on the step record and in ``summary()['phase_s']``,
  and a span in the bound istpu trace.  Never nested: the benchmark's
  trace reduction names a device idle gap by the host event covering most
  of it, so an enclosing annotation would name every gap;
* **counts at the dispatch** — ``note_decode`` records what the program
  knows when it launches the decode scan: steps, live rows, the context
  tokens they hold and the table tokens the attention reads;
  ``note_prefill_budget`` records, at the scheduler's call, the prefill
  chunk tokens a step was granted and the ones it spent; ``note_push_wait``
  tells the two causes of the ``kv.push_wait`` phase apart (the streamer's
  full queue, and strict durability's wait for acknowledgements);
  ``note_prefill_chunk`` counts the prefill chunks run, those whose
  program ran an output head and those whose attention is the TPU's kernel.
* **stages of other threads** — ``stage(name)`` is the same bracket for a
  thread that is not the engine's: an annotation on that thread's line of
  the profiler's trace and its seconds, which the KV transfer sums into
  ``push_totals`` (the streamer's worker: ``istpu.stream.*``).

Records live in a bounded ring (``ISTPU_STEPPROF_RING``, default 256),
exported at the serving front-end's ``GET /debug/engine`` (``?limit=``),
and feed the ``istpu_engine_*`` metric families on the owning server's
registry.  Sampled steps also add a ``device.drain`` span on a synthetic
**device track** to the engine-step trace AND to every participating
request's own ``http.request`` trace, so one stitched Perfetto file runs
HTTP handler → scheduler → engine.step → kv store hop → device dispatch
under one trace id.

Hooks (``note_dispatch`` / ``note_tokens`` / ``count_trace`` / ``enter``)
follow the tracing module's contract: with no active step record they cost one
contextvar read and nothing else.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..utils import metrics as _metrics
from ..utils import tracing
from ..utils.profiling import Timer

# -- knobs ------------------------------------------------------------------

STEPPROF_SAMPLE_DEFAULT = 16   # 1-in-N steps pay the block+mem probe
STEPPROF_RING_DEFAULT = 256    # records kept for /debug/engine

# step ids a single request accumulates for the ledger join (the newest
# window is what an investigation needs; a 100k-token request must not
# grow its ledger record without bound)
MAX_STEP_IDS = 64

# what ``note_decode`` sums, per step record and over the lifetime
DECODE_COUNTS = ("steps", "row_steps", "live_token_steps",
                 "table_token_steps", "attn_kernel_steps", "kernel_pages",
                 "kernel_whole_block_pages", "expert_pairs",
                 "experts_expected", "expert_pairs_local")

# what ``note_prefill_budget``, ``note_push_wait`` and ``note_prefill_chunk``
# sum, per step record
# and over the lifetime
PREFILL_COUNTS = ("granted_tokens", "spent_tokens",
                  "settle_waits", "settled_prompts", "settle_wait_s",
                  "push_queue_full_waits", "push_queue_full_s",
                  "chunks", "head_chunks", "attn_kernel_chunks",
                  "taken_in_dispatch", "started_dispatch", "chunks_dispatch",
                  "taken_in_settle", "started_settle", "chunks_settle",
                  "collect_lag_s")

# what ``note_kv_pages`` sums, per step record and over the lifetime
KV_COUNTS = ("store_pages_full", "store_pages_window",
             "store_pages_window_skipped", "window_pages_acquired",
             "window_pages_returned", "window_pages_pushed",
             "window_pages_push_skipped", "window_pinned_peak")

# what ``note_state`` sums, per step record and over the lifetime
STATE_COUNTS = ("checkpoints_taken", "checkpoints_pushed",
                "checkpoints_skipped_stored", "bytes_pushed",
                "adopted_local", "adopted_store", "shared_tokens_recomputed",
                "resident_evicted", "store_hits", "store_hits_full",
                "bytes_loaded", "scan_chunks", "scan_full_chunks",
                "scan_tokens")

# the key that counts operations in the transfer's running totals
_STORE_COUNT = {"push": "pushes", "load": "loads"}


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


# -- process-wide trace/compile accounting ----------------------------------

_ACTIVE: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "istpu_stepprof", default=None
)

# the profiler driving the current step: the module-level ``enter`` /
# ``phase`` reach it from engine and transfer code without plumbing
_PROFILER: contextvars.ContextVar[Optional["StepProfiler"]] = \
    contextvars.ContextVar("istpu_stepprof_owner", default=None)

_TRACE_LOCK = threading.Lock()
_TRACES: Dict[str, int] = {}     # fn name -> traces (first compile included)
_TRACES_TOTAL = 0
_COMPILES = 0                     # backend compiles (jax.monitoring)
_COMPILE_S = 0.0
_MONITOR_INSTALLED = False


def count_trace(name: str) -> None:
    """Count one trace-cache miss of ``name`` (called from inside the
    traced python body — engine._shared_jit wraps its functions with
    this).  Also lands on the active step record, so a mid-serving
    retrace shows up on the step that paid for it."""
    global _TRACES_TOTAL
    with _TRACE_LOCK:
        _TRACES[name] = _TRACES.get(name, 0) + 1
        _TRACES_TOTAL += 1
    rec = _ACTIVE.get()
    if rec is not None:
        r = rec["retraces"]
        r[name] = r.get(name, 0) + 1


def traced(fn, name: Optional[str] = None):
    """Wrap ``fn`` so every trace of the (later-jitted) function counts —
    the wrap-``jit`` fallback of the retrace tracker.  ``functools.wraps``
    keeps the signature inspectable, so ``donate_argnames`` on the
    enclosing ``jax.jit`` still resolves; the wrapper is NAMED ``name``."""
    import functools

    label = name or getattr(fn, "__name__", repr(fn))

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        count_trace(label)
        return fn(*args, **kwargs)

    # jit names its program after the function: ``jit_<label>`` is what a
    # device trace shows, and what benchmarks/trace/programs.json keys on
    counted.__name__ = counted.__qualname__ = label
    return counted


def _install_monitoring() -> None:
    """Register the process-wide ``jax.monitoring`` compile listener
    (idempotent).  Gives the global backend-compile count/seconds even
    for programs the per-function wrapper never saw."""
    global _MONITOR_INSTALLED
    with _TRACE_LOCK:
        if _MONITOR_INSTALLED:
            return
        _MONITOR_INSTALLED = True
    try:
        import jax.monitoring as mon

        def _on_duration(event: str, duration: float, **kw) -> None:
            global _COMPILES, _COMPILE_S
            if event == "/jax/core/compile/backend_compile_duration":
                with _TRACE_LOCK:
                    _COMPILES += 1
                    _COMPILE_S += float(duration)

        mon.register_event_duration_secs_listener(_on_duration)
    except Exception:  # noqa: BLE001 — monitoring is optional attribution
        pass


def trace_counts() -> Dict[str, int]:
    with _TRACE_LOCK:
        return dict(_TRACES)


def total_traces() -> int:
    """Process-lifetime trace-cache misses across every counted
    function — the cheap monotone series the health plane's
    retrace-regression watchdog samples (deltas over its windows, so
    the process-lifetime baseline cancels out)."""
    with _TRACE_LOCK:
        return _TRACES_TOTAL


# -- step-local hooks (no-ops without an active record) ---------------------

def note_dispatch(kind: str, n: int = 1) -> None:
    """Count ``n`` compiled dispatches of ``kind`` against the active
    step record (decode scan chunk, prefill chunk forward, verify,
    draft, fused spec round...).  One contextvar read when inactive."""
    rec = _ACTIVE.get()
    if rec is not None:
        d = rec["dispatches"]
        d[kind] = d.get(kind, 0) + n


def note_tokens(n: int) -> None:
    """Count ``n`` tokens emitted by the active step's dispatches."""
    rec = _ACTIVE.get()
    if rec is not None:
        rec["tokens"] += n


def note_sync(kind: str, n: int = 1) -> None:
    """Count ``n`` BLOCKING host syncs of ``kind`` against the active
    step record — device→host downloads the step loop actually waited
    on (the decode chunk's token landing, the fused-spec chunk's token
    landing).  Dispatches say how often the host talked to the device;
    syncs say how often it STOPPED for it — the single-sync speculation
    guard asserts exactly one per fused chunk.  One contextvar read
    when inactive."""
    rec = _ACTIVE.get()
    if rec is not None:
        s = rec["syncs"]
        s[kind] = s.get(kind, 0) + n


def note_decode(steps: int, rows: int, padded_rows: int, width_pages: int,
                block_tokens: int, live_tokens: int,
                expert_routing: Optional[tuple] = None,
                attn_kernel: bool = False,
                kernel_pages: tuple = (0, 0)) -> None:
    """Count ONE decode-scan dispatch with what the engine knows at the
    call: ``steps`` scan steps over ``rows`` live rows in a batch bucket
    of ``padded_rows``, a block table ``width_pages`` wide, and
    ``live_tokens`` = the rows' context lengths summed at the dispatch's
    start.  ``table_token_steps`` is the table as dispatched (every
    padded row, the whole width): what the XLA attention reads whatever
    ``seq_lens`` says, and what the TPU's kernel does NOT (it copies the
    live pages; ``attn_kernel`` says this dispatch's dense attention is
    that kernel, and ``attn_kernel_steps`` sums its steps;
    ``kernel_pages`` = (the pages one call of it copies a step, summed over
    the dispatch's steps; those of them in a block whose every page is live:
    the blocks it starts in one static run and awaits in one wait).
    ``expert_routing`` = (expert layers, experts a token, experts a layer)
    of a model with routed experts: ``expert_pairs`` counts the (token,
    expert) pairs the steps route (exact: k a row a layer, no token is
    dropped) and ``experts_expected`` the distinct experts they touch, a
    layer a step summed, as the EXPECTATION ``E * (1 - (1 - k/E) ** rows)``
    from the counted rows: the choice is made on the device and reading it
    back would be a sync in the decode loop.  Both stay 0 for a dense model.
    Summed under ``rec["decode"]``; the benchmark's ``engine.decode_*``
    readers take the window's gain of each."""
    rec = _ACTIVE.get()
    if rec is None:
        return
    d = rec["dispatches"]
    d["decode"] = d.get("decode", 0) + 1
    b = rec.setdefault("decode", dict.fromkeys(DECODE_COUNTS, 0))
    b["steps"] += steps
    b["row_steps"] += rows * steps
    b["live_token_steps"] += live_tokens * steps
    b["table_token_steps"] += padded_rows * width_pages * block_tokens * steps
    b["attn_kernel_steps"] += steps * bool(attn_kernel)
    b["kernel_pages"] += kernel_pages[0]
    b["kernel_whole_block_pages"] += kernel_pages[1]
    if expert_routing is not None:
        layers, k, n_experts = expert_routing
        b["expert_pairs"] += rows * k * layers * steps
        b["experts_expected"] += (
            n_experts * (1.0 - (1.0 - k / n_experts) ** rows) * layers * steps)


def note_expert_pairs_local(n: int) -> None:
    """Add the (token, expert) pairs of ONE decode-scan dispatch whose
    expert this chip holds, as the dispatch itself summed them on the device
    (a model that holds a share of its routed experts; exact, where
    ``experts_expected`` is an expectation).  Read after the dispatch's
    tokens have landed, so it costs no sync of its own.  Summed under
    ``rec["decode"]`` beside ``expert_pairs``."""
    rec = _ACTIVE.get()
    if rec is not None:
        b = rec.setdefault("decode", dict.fromkeys(DECODE_COUNTS, 0))
        b["expert_pairs_local"] += n


def _sum_into(block: str, names: tuple, counts: Dict[str, float]) -> None:
    """Add ``counts`` to the active step record's ``block`` (made with every
    one of ``names`` at 0 when first touched); nothing without a record."""
    rec = _ACTIVE.get()
    if rec is None:
        return
    b = rec.setdefault(block, dict.fromkeys(names, 0))
    for k, n in counts.items():
        b[k] += n


def note_kv_pages(**counts: int) -> None:
    """Count pages by layer kind (``KV_COUNTS``).  Of ONE adopted store
    prefix under a stack of mixed attention kinds, the (layer, chunk) pages
    fetched for the full layers, fetched for the sliding-window layers, and
    the window layers' pages NOT fetched because they lie wholly below the
    window (engine.prefill_start).  Of the sliding-window layers' pool, the
    pages a sequence took into its table and those it returned BEFORE its
    release, their last token having left every window to come
    (engine._reclaim_window_pages); the window layers' (layer, chunk) pages
    a push sent and those it did NOT send because no later hit can read them
    (engine._gather_push); and the most window-pool pages any one sequence
    has pinned at once, noted as its RISES so that their sum is the peak
    (engine._note_pinned).  Summed under ``rec["kv"]``."""
    _sum_into("kv", KV_COUNTS, counts)


def note_state(**counts: int) -> None:
    """Count what a cache of STATE SLOTS does (``STATE_COUNTS``;
    engine/state_engine.py): checkpoints taken into a resident slot, pushed
    to the store, and not pushed because the store had their key; the bytes
    pushed; prompts that adopted a checkpoint, by where it came from; the
    tokens a prompt shared with an earlier one BEYOND the checkpoint it could
    adopt (shared, yet recomputed: a state is reusable only where one was
    kept); resident checkpoints evicted for a newer one; where a sequence
    keeps pages AND a state (engine/hybrid_engine.py), the prompts whose
    pages the store matched deeper than HBM held them (``store_hits``) and
    those of them that adopted pages and checkpoint at the deepest stride
    that match reaches (``store_hits_full``), and the bytes of the checkpoints
    that came back from the store (``bytes_loaded``); where the state's update
    is a scan over the chunk (models/ssm_scan.py), the prefill chunks that ran
    it, those of them that were whole chunks of ``prefill_chunk`` tokens, and
    the tokens they walked, padding included (``scan_chunks``,
    ``scan_full_chunks``, ``scan_tokens``).  Summed under ``rec["state"]``."""
    _sum_into("state", STATE_COUNTS, counts)


def note_prefill_budget(granted_tokens: int, spent_tokens: int) -> None:
    """Count ONE step's prefill token budget at the scheduler's call: the
    chunk tokens the step was GRANTED (``max_batch`` chunks, or the
    degraded-mode cap where smaller) and the chunk tokens it SPENT
    (chunks run x ``prefill_chunk``; never more than granted).  Only
    steps with a batch in flight have a budget: an idle engine admits a
    whole wave and counts nothing here.  ``spent / granted`` near 1 says
    admission is held by the budget; far below it, by arrivals, slots or
    pages.  Summed under ``rec["prefill"]``."""
    _sum_into("prefill", PREFILL_COUNTS, {"granted_tokens": granted_tokens,
                                          "spent_tokens": spent_tokens})


def note_push_wait(**counts: float) -> None:
    """Count why the engine thread stood in ``kv.push_wait``
    (``PREFILL_COUNTS``), beside the step's budget under ``rec["prefill"]``.
    Strict durability's barrier: ``settle_waits`` times the thread awaited
    acknowledgements (once per blocking ``prefill``, once per scheduler step
    that finished prompts), ``settled_prompts`` the prefills those waits made
    visible and ``settle_wait_s`` their seconds; ``settled_prompts /
    settle_waits`` is how many prompts share one drain of the device.  The
    streamer's bound: ``push_queue_full_waits`` / ``push_queue_full_s``, a
    ``submit`` whose ``put`` found two chunks queued: the HOST runs ahead of
    the pusher there, the device still has chunks to run."""
    _sum_into("prefill", PREFILL_COUNTS, counts)


def note_prefill_chunk(head: bool, attn_kernel: bool = False) -> None:
    """Count ONE prefill chunk at the engine's launch of its program
    (``InferenceEngine._prefill``: a chunk of a chunked prefill, of either
    engine; a padded group's one forward): ``chunks`` run, and
    ``head_chunks``, those whose program ran an output head (a prompt's last
    chunk, on the one row it keeps; every chunk of a custom family that has
    the whole form only).  ``head_chunks / chunks`` is one over the chunks a
    prompt computes.  ``attn_kernel_chunks``: those whose dense attention is
    the TPU's kernel in some layer (models/chunk_attention_kernel.py; the
    model's reading of its own program, ``prefill_forward.kernel_layers``,
    through ``InferenceEngine._chunk_attention_in_kernel``).  Summed under
    ``rec["prefill"]``."""
    _sum_into("prefill", PREFILL_COUNTS,
              {"chunks": 1, "head_chunks": int(head),
               "attn_kernel_chunks": int(attn_kernel)})


def note_wait_work(**counts: float) -> None:
    """Count what the engine thread did at one of its two waits inside a
    step instead of standing in it (``PREFILL_COUNTS``;
    ``Scheduler._under_dispatch`` and ``_settle_parked`` with an intake
    attached), beside ``chunks`` under ``rec["prefill"]``: requests
    ``taken_in_*`` from the serving layer, prefills ``started_*``
    (``prefill_start``: lookup, load, pages) and chunks launched
    (``chunks_*``), under a decode DISPATCH in flight (``*_dispatch``: spent
    from the next step's budget) and under the SETTLE wait for the store's
    acknowledgements (``*_settle``: spent from the step's own).
    ``(chunks_dispatch + chunks_settle) / chunks`` is the share of prefill
    launches the waits hid.  ``collect_lag_s``: the seconds from a
    dispatch's end (stamped by its watcher thread) to the start of its
    collect, summed: what the rows in flight pay for a piece that was
    running, or a wake-up, when their dispatch ended; over
    ``dispatches.decode`` it is the mean a dispatch."""
    _sum_into("prefill", PREFILL_COUNTS, counts)


def enter(name: Optional[str]) -> float:
    """``StepProfiler.enter`` on the profiler driving this thread's step,
    and the switch's clock stamp: two of them time a site once.  A plain
    ``perf_counter`` read when there is no profiler."""
    prof = _PROFILER.get()
    return prof.enter(name) if prof is not None else time.perf_counter()


class phase:
    """``with phase("kv.load") as p:`` — enter the phase, and on the way
    out re-enter the one that was open (flat in every view: the outer
    phase is closed and reopened, never nested).  ``p.s`` is the seconds
    between the two switches, on the profiler's clock — the ONE timing of
    the site; with no profiler on this thread it is a plain
    ``perf_counter`` pair."""

    __slots__ = ("name", "prof", "prev", "t0", "s")

    def __init__(self, name: str):
        self.name = name
        self.s = 0.0

    def __enter__(self) -> "phase":
        prof = _PROFILER.get()
        if prof is not None and prof.enabled:
            self.prof, self.prev = prof, prof.phase
            self.t0 = prof.enter(self.name)
        else:
            self.prof = None
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self.prof is not None:
            self.s = self.prof.enter(self.prev) - self.t0
        else:
            self.s = time.perf_counter() - self.t0
        return False


class stage(Timer):
    """``with stage("istpu.stream.d2h") as st:`` — the timeline of a thread
    that is NOT the engine's (the streamer's worker: ``StepProfiler.enter``
    belongs to one thread, and no other may call it).  The block is a
    ``jax.profiler.TraceAnnotation(name)``, so a capture shows it on that
    thread's own line of the host plane, on the clock of the device's
    operations; ``st.s`` is its seconds, for whatever total the caller keeps.
    The site is timed ONCE: the annotation and the total are one bracket.
    With no capture running the annotation is a flag test."""

    __slots__ = ("ann",)

    def __init__(self, name: str):
        self.ann = _annotation(name)
        self.s = 0.0

    def __enter__(self) -> "stage":
        self.ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        self.ann.__exit__(*exc)
        return False


def current_step() -> Optional[int]:
    """The active step record's id (None outside a profiled step) — the
    scheduler stamps it onto a request at RETIREMENT, before the ledger
    record snapshots ``step_ids`` (the end-of-step attribution pass runs
    too late for a request that exits mid-step)."""
    rec = _ACTIVE.get()
    return rec["step"] if rec is not None else None


# -- device memory ----------------------------------------------------------

def default_mem_reader() -> Optional[Dict[str, int]]:
    """Device memory watermarks: ``memory_stats()`` where the backend
    provides it (TPU/GPU PJRT devices), else the CPU fallback — the sum
    of live jax array bytes (``live``) with ``peak`` tracked by the
    caller.  Every local device is read: the scalar keys are the FULLEST
    device's (the one that runs out first) and ``devices`` lists each, so
    a sharded engine whose shards all sit on device 0 shows as exactly
    that.  Returns None when nothing is measurable."""
    try:
        import jax

        per_dev = []
        for dev in jax.local_devices():
            stats = getattr(dev, "memory_stats", lambda: None)()
            if stats:
                live = int(stats.get("bytes_in_use", 0))
                per_dev.append({
                    "id": dev.id, "live_bytes": live,
                    "peak_bytes": int(stats.get("peak_bytes_in_use", live)),
                    "limit_bytes": int(stats.get("bytes_limit", 0)),
                })
        if per_dev:
            out = {k: max(d[k] for d in per_dev)
                   for k in ("live_bytes", "peak_bytes", "limit_bytes")}
            if not out["limit_bytes"]:
                del out["limit_bytes"]
            out["devices"] = per_dev
            return out
        live = sum(int(x.nbytes) for x in jax.live_arrays())
        return {"live_bytes": live, "peak_bytes": live, "cpu_fallback": 1}
    except Exception:  # noqa: BLE001 — watermarks are best-effort
        return None


def default_block(x: Any) -> None:
    import jax

    jax.block_until_ready(x)


# -- the profiler -----------------------------------------------------------

class StepProfiler:
    """One structured record per engine step; see the module docstring.

    ``metrics``: the owning server's registry (defaults to the process
    registry for library/bench use).  ``sentinel``: a no-arg callable
    returning the device value the sampled stall probe blocks on
    (typically ``lambda: engine.cache``).  ``clock`` / ``block`` /
    ``mem_reader`` / ``sample`` are injectable so the record shape and
    sampling math are unit-testable without a device or a wall clock.
    """

    def __init__(self, metrics: Optional[_metrics.MetricsRegistry] = None,
                 sentinel: Optional[Callable[[], Any]] = None,
                 sample: Optional[int] = None,
                 ring: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 block: Optional[Callable[[Any], None]] = None,
                 mem_reader: Optional[Callable[[], Optional[dict]]] = None):
        self.enabled = os.environ.get("ISTPU_STEPPROF", "1") != "0"
        self.sample = max(1, sample if sample is not None else _env_int(
            "ISTPU_STEPPROF_SAMPLE", STEPPROF_SAMPLE_DEFAULT))
        cap = max(1, ring if ring is not None else _env_int(
            "ISTPU_STEPPROF_RING", STEPPROF_RING_DEFAULT))
        self._ring: "deque" = deque(maxlen=cap)
        self._lock = threading.Lock()
        # id of the step currently executing (None between steps): a
        # ledger row written MID-step (requests retire inside the step)
        # may name this id before the full record ring-appends at step
        # end — /debug/engine exports it as an in_progress stub so the
        # /debug/requests join can never dangle
        self._current_step: Optional[int] = None
        self._clock = clock
        self._block = block if block is not None else default_block
        self._mem = mem_reader if mem_reader is not None else \
            default_mem_reader
        self._sentinel = sentinel
        self.steps = 0
        # lifetime aggregates behind summary()/the metric callbacks
        self._by_kind: Dict[str, int] = {}
        self._dispatch_totals: Dict[str, int] = {}
        self._sync_totals: Dict[str, int] = {}
        # lifetime speculation deltas (summed from per-step ``spec``
        # blocks): accepted tokens PER spec_round DISPATCH is the one
        # number that explains a sub-1x spec speedup at high acceptance
        self._spec_totals = {"rounds": 0, "proposed": 0, "accepted": 0}
        self.tokens = 0
        # lifetime sums of the decode dispatches' counts (note_decode)
        self._decode_totals = dict.fromkeys(DECODE_COUNTS, 0)
        # lifetime sums of the steps' prefill budgets and push waits
        # (note_prefill_budget, note_push_wait, note_prefill_chunk)
        self._prefill_totals = dict.fromkeys(PREFILL_COUNTS, 0)
        self._kv_totals = dict.fromkeys(KV_COUNTS, 0)
        self._state_totals = dict.fromkeys(STATE_COUNTS, 0)
        # flat phases of the driving thread (see ``enter``)
        self.phase: Optional[str] = None
        self._phase_t0 = 0.0
        self._phase_ann = None
        self._phase_s: Dict[str, float] = {}
        # the clock time with SOME phase open, from two reads per stretch:
        # what the sum of phase_s must equal if no time falls between phases
        self._phase_wall_s = 0.0
        self._phase_wall_t0 = 0.0
        # the transfer of the last scheduler stepped: summary()'s store
        # totals are read from it
        self._transfer = None
        self._wall_s = 0.0
        self._sampled_wall_s = 0.0
        self._stall_s = 0.0
        self._sampled = 0
        self._mem_last: Optional[dict] = None
        self._peak_live = 0  # running peak for the CPU fallback
        # trace/compile baselines: the summary reports deltas since THIS
        # profiler was built, not process-lifetime noise from warmup
        self._traces0 = dict(_TRACES)
        self._compiles0, self._compile_s0 = _COMPILES, _COMPILE_S
        self.metrics = metrics if metrics is not None else \
            _metrics.default_registry()
        self._register_metrics()
        _install_monitoring()

    # -- metrics --

    def _register_metrics(self) -> None:
        reg = self.metrics
        self._h_step = reg.histogram(
            "istpu_engine_step_seconds",
            "One scheduler step, by step kind; phase=wall is the step's "
            "wall time (every step), phase=stall the sampled end-of-step "
            "device drain (see istpu_engine_host_stall_seconds)",
            labelnames=("kind", "phase"),
        )
        self._c_dispatch = reg.counter(
            "istpu_engine_dispatches_total",
            "Compiled step programs launched, by kind (decode scan "
            "chunk, prefill chunk forward, verify/draft forward, fused "
            "speculation round)",
            labelnames=("kind",),
        )
        self._c_sync = reg.counter(
            "istpu_engine_syncs_total",
            "Blocking device->host downloads the step loop waited on, "
            "by kind (decode_tokens: a decode chunk's token landing; "
            "spec_tokens: a fused-spec chunk's token landing) — the "
            "single-sync speculation budget is one per fused chunk",
            labelnames=("kind",),
        )
        self._c_retrace = reg.counter(
            "istpu_engine_retraces_total",
            "jit trace-cache misses per engine function (first compile "
            "included) — a climbing series during steady serving means "
            "shape-polymorphic churn is eating steps",
            labelnames=("fn",),
        )
        self._h_stall = reg.histogram(
            "istpu_engine_host_stall_seconds",
            "Sampled end-of-step block_until_ready wait: device work "
            "the host loop did not overlap (high = device-bound, ~0 = "
            "host/dispatch-bound)",
        )
        self._g_mem = reg.gauge(
            "istpu_engine_device_mem_bytes",
            "Device memory watermarks from device.memory_stats() "
            "(live-array-sum fallback on CPU), sampled with the stall "
            "probe",
            labelnames=("kind",),
        )
        self._c_wait_work = reg.counter(
            "istpu_engine_wait_work_total",
            "What the engine thread did at a wait inside a step instead of "
            "standing in it (wait=dispatch: a decode dispatch in flight; "
            "wait=settle: strict durability's wait for acknowledgements): "
            "requests taken_in, prefills started, prefill chunks launched",
            labelnames=("wait", "what"),
        )
        self._c_collect_lag = reg.counter(
            "istpu_engine_collect_lag_seconds_total",
            "Seconds from a decode dispatch's end to the start of its "
            "collect, summed: what rows in flight pay for the work begun "
            "under their dispatch",
        )
        self._c_attn_kernel_chunks = reg.counter(
            "istpu_engine_prefill_attn_kernel_chunks_total",
            "Prefill chunks whose dense attention ran as the TPU's kernel "
            "(models/chunk_attention_kernel.py): against "
            "istpu_engine_dispatches_total{kind=prefill}, the share of chunk "
            "programs that write no score matrix",
        )
        self._c_kernel_pages = reg.counter(
            "istpu_engine_decode_kernel_pages_total",
            "Pages one call of the TPU's decode-attention kernel "
            "(models/paged_decode_kernel.py) copies, summed over the decode "
            "steps dispatched: the rows' live pages, by their lengths",
        )
        self._c_kernel_whole_block_pages = reg.counter(
            "istpu_engine_decode_kernel_whole_block_pages_total",
            "Those of istpu_engine_decode_kernel_pages_total in a block "
            "whose every page is live (every block of a row but its last): "
            "the share the kernel starts in one static run and awaits in "
            "one wait",
        )
        self._c_compiles = reg.counter(
            "istpu_engine_compiles_total",
            "Backend compiles observed process-wide via jax.monitoring "
            "(includes programs the per-fn retrace wrapper never saw)",
            fn=lambda: _COMPILES,
        )

    # -- recording --

    @staticmethod
    def _spec_counts(scheduler) -> Optional[tuple]:
        spec = getattr(scheduler, "spec", None) if scheduler else None
        if spec is None:
            return None
        return (int(spec.rounds), int(spec.proposed), int(spec.accepted))

    @staticmethod
    def _store_totals(transfer) -> Dict[str, dict]:
        """The transfer's running totals (each dict is replaced whole on
        update, so holding one IS a consistent snapshot)."""
        return {k: t for k in ("push", "load")
                if (t := getattr(transfer, k + "_totals", None))}

    # -- flat phases --

    def _account(self, now: float, rec: Optional[dict]) -> None:
        """Charge the open phase up to ``now`` (caller holds the lock)."""
        name = self.phase
        if name is not None:
            dt = now - self._phase_t0
            self._phase_s[name] = self._phase_s.get(name, 0.0) + dt
            if rec is not None:
                ph = rec["phases"]
                ph[name] = ph.get(name, 0.0) + dt
        self._phase_t0 = now

    def enter(self, name: Optional[str]) -> float:
        """End the open phase and begin ``name`` (``None``: begin none).
        Called by ONE thread, the one that drives the steps; phases never
        nest and never overlap, so from the first call on they partition
        that thread's time.  Returns the switch's clock stamp."""
        now = self._clock()
        if not self.enabled:
            return now
        prev, t0 = self.phase, self._phase_t0
        with self._lock:
            self._account(now, _ACTIVE.get())
            self.phase = name
            if prev is None:
                self._phase_wall_t0 = now
            elif name is None:
                self._phase_wall_s += now - self._phase_wall_t0
        ann = self._phase_ann
        if ann is not None:
            ann.__exit__(None, None, None)
        if name is None:
            self._phase_ann = None
        else:
            ann = self._phase_ann = _annotation("istpu." + name)
            ann.__enter__()
        if prev is not None:
            tr = tracing.TRACER.current()   # the step's, or a bound request's
            if tr is not None:
                tr.add("istpu." + prev, t0, now)
        return now

    @contextlib.contextmanager
    def step(self, scheduler=None, kind_hint: Optional[str] = None):
        """Profile one engine step.  Yields the (mutable) record dict;
        the finished record is ring-appended and metric-fed on exit.
        Usable without a scheduler (``kind_hint`` labels the step) —
        the bench legs and perf smoke wrap raw engine calls this way."""
        if not self.enabled:
            yield None
            return
        with self._lock:
            self.steps += 1
            step_id = self.steps
            self._current_step = step_id
        sampled = step_id % self.sample == 0
        rec: Dict[str, Any] = {
            "step": step_id,
            "t_wall": round(time.time(), 3),
            "trace_id": tracing.current_trace_id(),
            "dispatches": {},
            "tokens": 0,
            "syncs": {},
            "retraces": {},
            "phases": {},
            "sampled": sampled,
        }
        if scheduler is not None:
            rec["batch"] = {
                "active": len(getattr(scheduler, "active", ())),
                "prefilling": len(getattr(scheduler, "_prefilling", ())),
                "pending": len(getattr(scheduler, "pending", ())),
            }
        spec0 = self._spec_counts(scheduler)
        transfer = getattr(getattr(scheduler, "engine", None), "transfer",
                           None)
        if transfer is not None:
            self._transfer = transfer
        store0 = self._store_totals(transfer)
        compiles0, compile_s0 = _COMPILES, _COMPILE_S
        token = _ACTIVE.set(rec)
        owner = _PROFILER.set(self)
        outer = self.phase
        t0 = self._clock()
        with self._lock:
            self._account(t0, None)    # what came before is not this step's
        try:
            yield rec
        finally:
            t1 = self._clock()
            with self._lock:
                self._account(t1, rec)
            _PROFILER.reset(owner)
            _ACTIVE.reset(token)
            self._finish(rec, scheduler, kind_hint, t0, t1, sampled,
                         spec0, transfer, store0, compiles0, compile_s0)
            if outer is None:
                # nobody partitions this thread's time between steps (a
                # library caller, a bench leg): leave no phase running
                self.enter(None)

    def _finish(self, rec, scheduler, kind_hint, t0, t1, sampled,
                spec0, transfer, store0, compiles0, compile_s0) -> None:
        dur = max(0.0, t1 - t0)
        rec["dur_s"] = round(dur, 6)
        rec["kind"] = kind_hint or self._classify(rec["dispatches"])
        # sampled probe: time the device drain, then read the watermarks
        # (reading them BEFORE the block would race in-flight dispatches)
        if sampled:
            tb = self.enter("probe")
            stall = 0.0
            sentinel = self._sentinel
            target = None
            if sentinel is not None:
                target = sentinel()
            elif scheduler is not None:
                target = getattr(getattr(scheduler, "engine", None),
                                 "cache", None)
            if target is not None:
                try:
                    self._block(target)
                except Exception:  # noqa: BLE001 — probe must not fault steps
                    pass
                stall = max(0.0, self._clock() - tb)
            rec["host_stall_s"] = round(stall, 6)
            mem = self._mem()
            if mem is not None:
                if mem.get("cpu_fallback"):
                    self._peak_live = max(self._peak_live,
                                          mem["live_bytes"])
                    mem["peak_bytes"] = self._peak_live
                rec["mem"] = mem
        # speculation attribution: per-step deltas of the speculator's
        # counters next to the dispatch counts — accepted tokens PER
        # DISPATCH is the number that explains a sub-1x speedup at high
        # acceptance
        spec1 = self._spec_counts(scheduler)
        if spec0 is not None and spec1 is not None and spec1 != spec0:
            rec["spec"] = {
                "rounds": spec1[0] - spec0[0],
                "proposed": spec1[1] - spec0[1],
                "accepted": spec1[2] - spec0[2],
            }
            with self._lock:
                for key in self._spec_totals:
                    self._spec_totals[key] += rec["spec"][key]
        # store-hop stages: what the transfer's running totals gained
        # under this step (a push commits on the streamer thread, so it
        # may count towards the step after the one that submitted it)
        store = {}
        for k, tot in self._store_totals(transfer).items():
            was = store0.get(k, {})
            if tot[_STORE_COUNT[k]] != was.get(_STORE_COUNT[k], 0):
                store[k] = {f: round(v - was.get(f, 0), 6)
                            for f, v in tot.items()}
        if store:
            rec["store"] = store
        if _COMPILES != compiles0:
            rec["compiles"] = _COMPILES - compiles0
            rec["compile_s"] = round(_COMPILE_S - compile_s0, 6)
        # lifetime aggregates + metric families
        kind = rec["kind"]
        with self._lock:
            self._by_kind[kind] = self._by_kind.get(kind, 0) + 1
            for k, n in rec["dispatches"].items():
                self._dispatch_totals[k] = \
                    self._dispatch_totals.get(k, 0) + n
            for k, n in rec["syncs"].items():
                self._sync_totals[k] = self._sync_totals.get(k, 0) + n
            self.tokens += rec["tokens"]
            for k, n in rec.get("decode", {}).items():
                self._decode_totals[k] += n
            for k, n in rec.get("prefill", {}).items():
                self._prefill_totals[k] += n
            for k, n in rec.get("kv", {}).items():
                self._kv_totals[k] += n
            for k, n in rec.get("state", {}).items():
                self._state_totals[k] += n
            self._wall_s += dur
            if sampled:
                self._sampled += 1
                self._sampled_wall_s += dur
                self._stall_s += rec.get("host_stall_s", 0.0)
                if rec.get("mem"):
                    self._mem_last = rec["mem"]
            self._ring.append(rec)
            if self._current_step == rec["step"]:
                self._current_step = None
        self._h_step.labels(kind, "wall").observe(dur)
        for k, n in rec["dispatches"].items():
            self._c_dispatch.labels(k).inc(n)
        for k, n in rec["syncs"].items():
            self._c_sync.labels(k).inc(n)
        for fname, n in rec["retraces"].items():
            self._c_retrace.labels(fname).inc(n)
        kernel = rec.get("decode", {})
        if kernel.get("kernel_pages"):
            self._c_kernel_pages.inc(kernel["kernel_pages"])
            self._c_kernel_whole_block_pages.inc(
                kernel["kernel_whole_block_pages"])
        for k, n in rec.get("prefill", {}).items():
            what, _, wait = k.rpartition("_")
            if n and wait in ("dispatch", "settle"):
                self._c_wait_work.labels(wait, what).inc(n)
            elif n and k == "collect_lag_s":
                self._c_collect_lag.inc(n)
            elif n and k == "attn_kernel_chunks":
                self._c_attn_kernel_chunks.inc(n)
        if sampled:
            stall = rec.get("host_stall_s", 0.0)
            self._h_step.labels(kind, "stall").observe(stall)
            self._h_stall.observe(stall)
            mem = rec.get("mem")
            if mem:
                self._g_mem.labels("live").set(mem["live_bytes"])
                self._g_mem.labels("peak").set(mem["peak_bytes"])
        # the device sub-track: the sampled drain as a span on a
        # synthetic "device" thread of the ACTIVE trace (the engine.step
        # trace in serving; a bench.* trace in the legs) — the scheduler
        # mirrors it into each participating request's own trace
        if sampled and rec.get("host_stall_s"):
            tracing.add_span_abs(
                "device.drain", t1, t1 + rec["host_stall_s"],
                tid="device", step=rec["step"],
            )
        rec["t0"], rec["t1"] = t0, t1  # for the scheduler's span mirror

    @staticmethod
    def _classify(dispatches: Dict[str, int]) -> str:
        spec = any(k.startswith(("spec", "draft", "verify"))
                   for k in dispatches)
        prefill = "prefill" in dispatches
        decode = "decode" in dispatches
        if spec:
            return "spec" if not (prefill or decode) else "mixed"
        if prefill and decode:
            return "mixed"
        if prefill:
            return "prefill"
        if decode:
            return "decode"
        return "idle"

    # -- cheap probe reads (the health sampler polls these every tick;
    # summary() builds dicts and merges global trace state, too much for
    # a 1 Hz background thread that only needs three numbers) --

    def stall_totals(self) -> tuple:
        """``(host_stall_s, sampled_wall_s)`` lifetime totals — windowed
        deltas of the pair give the health plane an INSTANTANEOUS
        host-stall fraction (``summary()['host_stall_frac']`` is the
        lifetime aggregate, too damped to watchdog a trend)."""
        with self._lock:
            return self._stall_s, self._sampled_wall_s

    def mem_last(self) -> Optional[Dict[str, int]]:
        """The most recent sampled device-memory watermark dict."""
        with self._lock:
            return dict(self._mem_last) if self._mem_last else None

    # -- export --

    def summary(self) -> Dict[str, Any]:
        """Lifetime aggregates: the ``/debug/engine`` header and the
        bench-JSON profiler block.  ``host_stall_frac`` is the sampled
        device-drain share of sampled step wall time — the one number
        that says device-bound vs host-bound; ``retraces_per_100_steps``
        the steady-state retrace pressure."""
        with self._lock:
            steps = self.steps
            by_kind = dict(self._by_kind)
            dispatches = dict(self._dispatch_totals)
            syncs = dict(self._sync_totals)
            spec_tot = dict(self._spec_totals)
            tokens = self.tokens
            decode = dict(self._decode_totals)
            prefill = {k: round(v, 6) if isinstance(v, float) else v
                       for k, v in self._prefill_totals.items()}
            kv = dict(self._kv_totals)
            state = dict(self._state_totals)
            # the open phase counts up to this moment: a scrape in the
            # middle of a long decode.wait loses nothing
            phase_s = dict(self._phase_s)
            phase_wall = self._phase_wall_s
            if self.phase is not None:
                now = self._clock()
                phase_s[self.phase] = phase_s.get(self.phase, 0.0) + max(
                    0.0, now - self._phase_t0)
                phase_wall += now - self._phase_wall_t0
            wall = self._wall_s
            s_wall, stall, sampled = (self._sampled_wall_s, self._stall_s,
                                      self._sampled)
            mem = dict(self._mem_last) if self._mem_last else None
        with _TRACE_LOCK:
            retraces = {
                k: v - self._traces0.get(k, 0) for k, v in _TRACES.items()
                if v - self._traces0.get(k, 0) > 0
            }
            compiles = _COMPILES - self._compiles0
            compile_s = _COMPILE_S - self._compile_s0
        n_retr = sum(retraces.values())
        dispatch_total = sum(dispatches.values())
        out = {
            "steps": steps,
            "by_kind": by_kind,
            "dispatches": dispatches,
            "dispatch_total": dispatch_total,
            "syncs": syncs,
            "syncs_total": sum(syncs.values()),
            # dispatch economy: compiled programs launched per decoded
            # token — THE number the single-sync speculation work moves
            # (down is good)
            "dispatches_per_token": round(dispatch_total / tokens, 4)
            if tokens else 0.0,
            "tokens": tokens,
            "wall_s": round(wall, 4),
            "sampled_steps": sampled,
            "host_stall_s": round(stall, 4),
            "host_stall_frac": round(stall / s_wall, 4) if s_wall else 0.0,
            "retraces": retraces,
            "retraces_total": n_retr,
            "retraces_per_100_steps": round(100.0 * n_retr / steps, 3)
            if steps else 0.0,
            "compiles": compiles,
            "compile_s": round(compile_s, 4),
            "mem": mem,
            "phase_s": {k: round(v, 6) for k, v in phase_s.items()},
            "phase_wall_s": round(phase_wall, 6),
            # the decode dispatches' counts, summed (note_decode)
            "decode": decode,
            # the steps' prefill token budgets, what their push waits were
            # for and their chunks with and without an output head, summed
            # (note_prefill_budget, note_push_wait, note_prefill_chunk)
            "prefill": prefill,
            # adopted store prefixes' pages by layer kind (note_kv_pages)
            "kv": kv,
            # a cache of state slots: checkpoints and adoptions (note_state)
            "state": state,
            "store": {k: dict(t) for k, t in
                      self._store_totals(self._transfer).items()},
        }
        # speculation economy: accepted tokens per fused dispatch, the
        # read that explains a slowdown at high acceptance (up is
        # good; absent when no spec step ever ran)
        n_spec_disp = dispatches.get("spec_round", 0)
        if n_spec_disp and spec_tot["proposed"]:
            out["spec_accept_per_dispatch"] = round(
                spec_tot["accepted"] / n_spec_disp, 3
            )
        return out

    def tail(self, limit: Optional[int] = None) -> List[dict]:
        with self._lock:
            recs = [
                {k: v for k, v in r.items() if k not in ("t0", "t1")}
                | {"phases": {k: round(v, 6) for k, v in r["phases"].items()}}
                for r in self._ring
            ]
        if limit is not None and limit >= 0:
            recs = recs[len(recs) - min(limit, len(recs)):]
        return recs

    def snapshot(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """The ``GET /debug/engine`` payload."""
        if not self.enabled:
            return {"enabled": False}
        # current BEFORE tail: a step completing in between then shows in
        # the ring snapshot, so a step id a reader learned earlier (from
        # /debug/requests) always resolves one way or the other
        with self._lock:
            current = self._current_step
        recs = self.tail(limit)
        if current is not None and not any(
            r["step"] == current for r in recs
        ):
            # the step EXECUTING right now: a ledger row may already name
            # it (requests retire mid-step), so the join must resolve —
            # the full record replaces this stub when the step ends
            recs.append({"step": current, "in_progress": True})
        return {
            "enabled": True,
            "sample": self.sample,
            "ring": self._ring.maxlen,
            "summary": self.summary(),
            "returned": len(recs),
            "records": recs,
        }


# -- the profiler's own trace ------------------------------------------------

_TRACE_ANNOTATION = None


def _annotation(name: str):
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:      # jax stays a lazy import here
        import jax

        _TRACE_ANNOTATION = jax.profiler.TraceAnnotation
    return _TRACE_ANNOTATION(name)


def start_capture(log_dir: str, seconds: float) -> bool:
    """Start ``jax.profiler`` into ``log_dir`` and stop it ``seconds``
    later from a side thread (``POST /debug/profile``).  Host tracer level
    2 and the Python tracer off: what the benchmark's own capture uses —
    the engine's Python frames would swamp the trace and slow the host.
    False when a capture is already running, whoever started it; a
    ``log_dir`` that cannot be made or written raises ``OSError`` here
    (the profiler itself would only find out when it stops)."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    if not os.access(log_dir, os.W_OK | os.X_OK):
        raise PermissionError(f"cannot write to {log_dir!r}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    except RuntimeError as e:
        if "Only one profile" in str(e):
            return False
        raise

    def stop() -> None:
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — a Timer thread has no caller
            # someone else stopped it, or the export failed
            logging.getLogger("infinistore_tpu").exception(
                "profiler capture into %s did not stop cleanly", log_dir)

    timer = threading.Timer(seconds, stop)
    timer.daemon = True
    timer.start()
    return True
