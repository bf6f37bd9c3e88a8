"""Lightweight observability helpers.

``LatencyStats`` backs the client-side per-op latency counters
(lib.py Connection.latency_stats — the client's side of the story next to
the server's ``/metrics``).  (A ``jax.profiler`` capture of a running
server is ``POST /debug/profile``, ``engine.stepprof.start_capture``.)

``LatencyStats`` is one leg of the unified observability plane: every
sample it takes is simultaneously (a) accumulated into its own
count/avg/percentile snapshot, (b) forwarded to an optional ``sink``
(lib.py feeds the ``istpu_client_op_seconds`` Prometheus histogram this
way), and (c) recorded as a span in the active request trace
(``utils.tracing``) — so one ``timed()`` block shows up in
``latency_stats()``, ``/metrics``, and ``/debug/traces`` without being
timed three times.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, Optional

from . import tracing
from .metrics import nearest_rank


class Timer:
    """``with Timer() as t:`` — ``t.s`` is the block's seconds: the plain
    form of a stage's bracket (``engine.stepprof.stage`` is the one that is
    also an annotation in the profiler's trace)."""

    __slots__ = ("t0", "s")

    def __init__(self, name: str = ""):
        self.s = 0.0

    def __enter__(self) -> "Timer":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.s = time.perf_counter() - self.t0
        return False


class LatencyStats:
    """Per-op latency accumulator: count / total / max plus a bounded
    ring of recent samples for percentiles (thread-safe, cheap enough for
    the data path — two perf_counter calls and a dict update).  p50 backs
    the driver metric's latency half (BASELINE.json: "p50 read latency")."""

    SAMPLES = 512  # recent-sample ring per op (percentile window)

    def __init__(self, sink: Optional[Callable[[str, float], None]] = None):
        self._lock = threading.Lock()
        # name -> [count, total_s, max_s, ring list, ring cursor]
        self._ops: Dict[str, list] = {}
        # called (name, seconds) per sample OUTSIDE the lock; lib.py wires
        # the shared Prometheus histogram here
        self._sink = sink

    @contextlib.contextmanager
    def timed(self, name: str):
        t0 = time.perf_counter()
        with tracing.span(name):
            try:
                yield
            finally:
                self._record(name, time.perf_counter() - t0)

    @contextlib.contextmanager
    def staged(self, name: str, st):
        """``timed`` for a block that ``st`` times (a context manager with
        the block's seconds as ``.s`` on the way out: a ``Timer``, or a
        caller's own bracket): the block is timed ONCE, the sample lands
        here whether or not the block raised, and the caller reads
        ``st.s`` for the totals it keeps."""
        try:
            with st:
                yield st
        finally:
            self.record(name, st.s)

    def record(self, name: str, seconds: float) -> None:
        """Accumulate one externally-timed sample (the data plane's
        per-stage alloc/copy/commit breakdown records sub-spans this way
        where a context manager doesn't fit).  Also lands in the active
        trace as a stage that ended now."""
        tracing.add_stage(name, seconds)
        self._record(name, seconds)

    def _record(self, name: str, seconds: float) -> None:
        with self._lock:
            rec = self._ops.setdefault(name, [0, 0.0, 0.0, [], 0])
            rec[0] += 1
            rec[1] += seconds
            rec[2] = max(rec[2], seconds)
            ring = rec[3]
            if len(ring) < self.SAMPLES:
                ring.append(seconds)
            else:  # write at cursor, then advance: oldest-first overwrite
                ring[rec[4]] = seconds
                rec[4] = (rec[4] + 1) % self.SAMPLES
        if self._sink is not None:
            self._sink(name, seconds)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            out = {}
            for name, (c, total, mx, ring, _) in self._ops.items():
                s = sorted(ring)
                out[name] = {
                    "count": c,
                    "total_ms": round(total * 1e3, 3),
                    "avg_ms": round(total / c * 1e3, 3) if c else 0.0,
                    "p50_ms": round(nearest_rank(s, 0.50) * 1e3, 3) if s else 0.0,
                    "p99_ms": round(nearest_rank(s, 0.99) * 1e3, 3) if s else 0.0,
                    "max_ms": round(mx * 1e3, 3),
                }
            return out
