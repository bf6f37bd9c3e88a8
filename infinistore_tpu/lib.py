"""Client API (reference parity: infinistore/lib.py).

``InfinityConnection`` exposes the same surface as the reference client:
``connect``/``connect_async``, batched zero-copy ``write_cache_async`` /
``read_cache_async`` (aliased as ``rdma_write_cache_async`` /
``rdma_read_cache_async`` for drop-in compatibility), single-key
``tcp_write_cache``/``tcp_read_cache``, ``check_exist``,
``get_match_last_index``, ``delete_keys``, ``register_mr``.

Transport: instead of RDMA verbs, the zero-copy path maps the server's
POSIX-shm pools (same host -- the TPU-VM case, where the store and the
inference engine share the host) and memcpys blocks directly; the server only
does bookkeeping (ALLOC/COMMIT/DESC round-trips).  Cross-host clients use the
inline-batch TCP ops (the DCN path).  JAX arrays enter via
``infinistore_tpu.kv.transfer`` which stages HBM<->host through these calls.
"""

from __future__ import annotations

import asyncio
import ctypes
import functools
import json
import mmap
import os
import socket
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import protocol as P
from .config import (  # noqa: F401 - re-exported for parity
    ClientConfig,
    ServerConfig,
    TYPE_SHM,
    TYPE_TCP,
    TYPE_RDMA,
    LINK_ICI,
    LINK_DCN,
    LINK_ETHERNET,
    LINK_IB,
)
from .mempool import SHM_DIR, _prefault
from .store import READ_LEASE_S
from .utils import checksum as _checksum
from .utils import metrics as _metrics
from .utils import resilience as _resilience
from .utils import tracing as _tracing
from .utils.logging import Logger
from .utils.profiling import LatencyStats, Timer

# one shared client-side histogram for every connection in the process:
# the op label carries both whole ops (write_cache, read_cache, w_tcp ...)
# and their stages (write_cache.alloc/.copy/.commit, read_cache.desc/.copy),
# so /metrics can answer "is the put slow because of the allocator round-
# trip or the pool memcpy" with rate()-able series instead of the
# point-in-time p50s in latency_stats()
_CLIENT_OPS = _metrics.default_registry().histogram(
    "istpu_client_op_seconds",
    "Client-side latency of store data-plane ops and their stages",
    labelnames=("op",),
)


def _observe_client_op(name: str, seconds: float) -> None:
    _CLIENT_OPS.labels(name).observe(seconds)


# end-to-end KV integrity failures detected CLIENT-side, by cause:
# checksum — the bytes that landed do not match the entry's stamped
# checksum (pool corruption, or a region recycled mid-copy);
# lease — same mismatch, but the copy outlasted the server's read lease,
# so the root cause is almost certainly the lease-expiry race;
# epoch — descriptors or pool mappings predate a server restart (the
# epoch fence fired).  Every cause is handled as a cache MISS by the
# serving stack (guarded_load -> recompute), never a failed request.
_INTEGRITY_FAILURES = _metrics.default_registry().counter(
    "istpu_integrity_failures_total",
    "Client-detected KV integrity failures, by cause "
    "(checksum / lease / epoch); each one is served as a cache miss",
    labelnames=("cause",),
)


def _timed_op(name: str):
    """Record the wrapped data-path method in the connection's client-side
    latency counters (the client half of observability; server half is
    /metrics)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            with self.latency.timed(name):
                return fn(self, *args, **kwargs)

        return wrapper

    return deco


class InfiniStoreException(Exception):
    pass


class InfiniStoreKeyNotFound(InfiniStoreException):
    pass


class InfiniStoreConnectionError(InfiniStoreException):
    """The transport itself failed (socket died, channel torn down, server
    unreachable) — the only class of error worth a reconnect."""


class InfiniStoreTimeoutError(InfiniStoreConnectionError):
    """No response within ``ClientConfig.op_timeout_s``: the server is hung
    (alive but not answering), which no socket error would ever surface.
    Subclasses the connection error because the remedy is the same — the
    channel is torn down and the op rides the reconnect machinery."""


class InfiniStoreIntegrityError(InfiniStoreException):
    """The bytes a read delivered failed end-to-end verification (or the
    epoch fence fired).  NOT a connection error on purpose: the transport
    is healthy and a reconnect-retry would re-read the same bad bytes —
    the correct remedy is to treat the read as a cache MISS and recompute
    (``kv.transfer.guarded_load`` does exactly that)."""

    def __init__(self, msg: str, cause: str = "checksum", keys=()):
        super().__init__(msg)
        self.cause = cause
        self.keys = list(keys)


_STATUS_EXC = {
    P.KEY_NOT_FOUND: InfiniStoreKeyNotFound,
    # the server never answers SYSTEM_ERROR over the wire; this status
    # surfaces client-side when a channel is dead
    P.SYSTEM_ERROR: InfiniStoreConnectionError,
}


def _raise_for_status(status: int, what: str):
    if status == P.FINISH or status == P.TASK_ACCEPTED:
        return
    exc = _STATUS_EXC.get(status, InfiniStoreException)
    raise exc(f"{what} failed, ret = {status}")


def _ptr_view(ptr: int, size: int) -> memoryview:
    """A writable memoryview over raw memory at ``ptr`` (the moral equivalent
    of the reference handing ``data_ptr()`` to ibverbs)."""
    return memoryview((ctypes.c_char * size).from_address(ptr)).cast("B")


# data-plane knobs (shm zero-copy path).  ISTPU_NO_COALESCE=1 pins the
# legacy per-page copy loop — kept as the byte-parity reference and as an
# escape hatch; the coalesced path is the default.
_COALESCE = not os.environ.get("ISTPU_NO_COALESCE")


def _trace_ctx_enabled() -> bool:
    """Cross-process trace propagation opt-out (ISTPU_TRACE_CTX=0): when
    off, HELLO advertises nothing and every frame is byte-identical to the
    pre-trace-context wire format.  Read per connection so tests can flip
    it without reimporting."""
    return os.environ.get("ISTPU_TRACE_CTX", "1") != "0"


def _integrity_enabled() -> bool:
    """Client half of the integrity opt-out (ISTPU_INTEGRITY=off): when
    off, HELLO never asks for the capability and every read stays on the
    legacy wire format.  Read per connection, like the trace gate."""
    return os.environ.get("ISTPU_INTEGRITY", "verify") != "off"


def _account_enabled() -> bool:
    """Usage-attribution opt-out (ISTPU_ACCOUNT=0): when off, HELLO
    never asks for the capability and no frame ever carries an account
    blob — byte-identical to the pre-accounting wire format.  Read per
    connection, like the trace/integrity gates."""
    return os.environ.get("ISTPU_ACCOUNT", "1") != "0"


def _alloc_first_enabled() -> bool:
    """Alloc-first put opt-out (ISTPU_ALLOC_FIRST=0): when off, HELLO
    never asks for the capability and ``write_cache_into`` stays on the
    staged fallback — the byte-parity escape hatch for the zero-copy
    push path, mirroring ISTPU_NO_COALESCE for the copy loop."""
    return os.environ.get("ISTPU_ALLOC_FIRST", "1") != "0"
# total time write_cache keeps re-asking after RETRY (another writer is
# actively streaming one of these keys) before giving up with a clear error
_RETRY_DEADLINE_S = float(os.environ.get("ISTPU_RETRY_DEADLINE_S", "10"))
# stripe run copies across a few workers once the batch is large enough to
# amortize the handoff (one core's memcpy tops out below DRAM bandwidth;
# np.copyto releases the GIL, so the workers genuinely overlap)
_COPY_WORKERS = int(os.environ.get("ISTPU_COPY_WORKERS", "0")) or max(
    1, min(4, (os.cpu_count() or 1) - 1)
)
_PAR_MIN_BYTES = 8 << 20
# runs below this copy via buffer-protocol slice assignment (memoryview →
# plain memcpy, no ufunc dispatch); at/above it np.copyto wins AND releases
# the GIL, which is what lets the worker striping overlap
_VEC_MIN_BYTES = 1 << 20


def _merge_runs(
    descs: Sequence[Tuple[int, int, int]], offsets: Sequence[int]
) -> List[list]:
    """Merge adjacent descriptors — same pool, contiguous pool offsets AND
    contiguous client offsets — into copy runs ``[pool_idx, pool_off,
    client_off, nbytes]`` (order-preserving single pass).  With the
    server's contiguous-run allocation a whole batch collapses into one
    run; a fragmented desc list degrades gracefully toward per-page."""
    runs: List[list] = []
    for (pool_idx, pool_off, size), cli_off in zip(descs, offsets):
        if runs:
            r = runs[-1]
            if (
                r[0] == pool_idx
                and r[1] + r[3] == pool_off
                and r[2] + r[3] == cli_off
            ):
                r[3] += size
                continue
        runs.append([pool_idx, pool_off, cli_off, size])
    return runs


class _MappedPool:
    def __init__(self, name: str, size: int):
        self.name = name
        path = os.path.join(SHM_DIR, name)
        fd = os.open(path, os.O_RDWR)
        try:
            self.mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        # server already populated the pages; this maps them into our page
        # table up front so the data path takes no minor faults.  write=False:
        # this is the server's pool -- the write fallback would zero it.
        _prefault(self.mm, size, write=False)
        self.buf = memoryview(self.mm)
        # ndarray alias of the same mapping: run copies go through
        # np.copyto, which is one GIL-releasing memcpy per run
        self.arr = np.frombuffer(self.mm, dtype=np.uint8)

    def close(self):
        self.arr = None
        self.buf.release()
        try:
            self.mm.close()
        except BufferError:
            # a stray numpy view still pins the mapping; dropping our refs
            # above is what matters — the OS unmaps at process exit
            pass


class _Slot:
    """One in-flight request: resolved by the channel's reader thread."""

    __slots__ = ("ev", "consumer", "status", "result", "error")

    def __init__(self, consumer: Optional[Callable] = None):
        self.ev = threading.Event()
        self.consumer = consumer
        self.status = 0
        self.result: Optional[bytes] = None
        self.error: Optional[Exception] = None


class _Channel:
    """One pipelined socket: many requests may be in flight at once.

    Sends are serialized by ``_send_lock`` (a frame must hit the wire
    contiguously); responses are read by a dedicated reader thread and
    matched FIFO -- both servers process a connection's frames strictly in
    order, so no response tag is needed.  This plays the role of the
    reference's CQ-polling thread + batched WR chains
    (reference: src/libinfinistore.cpp:103 cq_handler, :596 w_rdma_async).
    """

    def __init__(self, host: str, port: int,
                 op_timeout: Optional[float] = None):
        self.sock = socket.create_connection((host, port), timeout=30)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.op_timeout = op_timeout
        if op_timeout:
            # bound the synchronous bootstrap (HELLO) too: a server that
            # hangs mid-handshake must fail within the op deadline, not
            # the 30s connect default.  start_reader() lifts this back to
            # blocking mode for the pipelined phase.
            self.sock.settimeout(op_timeout)
            # kernel-level SEND timeout: a stalled server with full socket
            # buffers must not wedge sendall forever.  SO_SNDTIMEO (not
            # settimeout) because the Python-level timeout is per-socket
            # and would make the reader thread's idle recv spuriously
            # expire; the kernel option bounds sends alone.
            import struct

            sec = int(op_timeout)
            usec = int((op_timeout - sec) * 1e6)
            self.sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDTIMEO,
                struct.pack("ll", sec, usec),
            )
        self._send_lock = threading.Lock()
        self._pending: deque = deque()
        self._pending_lock = threading.Lock()
        self._err: Optional[Exception] = None
        self._reader: Optional[threading.Thread] = None

    def start_reader(self) -> None:
        """Switch from synchronous request/response to pipelined mode."""
        self.sock.settimeout(None)  # reader blocks until data or close
        self._reader = threading.Thread(target=self._read_loop, daemon=True)
        self._reader.start()

    # -- synchronous exchange (pre-pipeline bootstrap: HELLO) --

    def exchange(self, op: int, body: bytes) -> Tuple[int, bytes]:
        self.sock.sendall(P.pack_header(op, len(body)) + body)
        hdr = bytearray(P.RESP_SIZE)
        self._recv_exact_into(memoryview(hdr))
        status, body_len = P.RESP.unpack(bytes(hdr))
        resp = bytearray(body_len)
        if body_len:
            self._recv_exact_into(memoryview(resp))
        return status, bytes(resp)

    # -- pipelined exchange --

    def submit(
        self,
        op: int,
        body: bytes,
        payload: Sequence[memoryview] = (),
        consumer: Optional[Callable] = None,
        trace_id: Optional[str] = None,
        account: Optional[str] = None,
    ) -> _Slot:
        """Put one request on the wire without waiting (the pipelined
        banded ops overlap the next band's round-trip with this band's
        pool copy).  FIFO response matching holds because the send lock
        orders the frame and the pending-queue append together.

        ``trace_id`` (only ever passed after HELLO negotiation proved the
        server speaks trace context) prepends the ctx blob and sets
        FLAG_TRACE_CTX, so the server records its op spans under the
        caller's trace.  ``account`` (same negotiation rule, via
        HELLO_FLAG_ACCOUNT) prepends the account blob — it rides FIRST
        on the wire when both are present — so the store's usage ledger
        attributes this op to the tenant that paid for it."""
        flags = 0
        if trace_id is not None:
            flags = P.FLAG_TRACE_CTX
            body = P.pack_trace_ctx(trace_id) + body
        if account is not None:
            flags |= P.FLAG_ACCOUNT
            body = P.pack_account(account) + body
        slot = _Slot(consumer)
        with self._send_lock:
            if self._err is not None:
                raise InfiniStoreConnectionError(f"connection dead: {self._err!r}")
            with self._pending_lock:
                self._pending.append(slot)
            # sendall per buffer: sendmsg can partially send under
            # backpressure and is capped at IOV_MAX vectors
            self.sock.sendall(P.pack_header(op, len(body), flags=flags) + body)
            for view in payload:
                self.sock.sendall(view)
        return slot

    def wait(self, slot: _Slot,
             timeout: Optional[float] = None) -> Tuple[int, object]:
        """Block for a slot's response, bounded by ``timeout`` (default:
        the channel's ``op_timeout``).  A fired deadline KILLS the whole
        channel — every in-flight slot fails, so FIFO response matching
        can never desynchronize — and surfaces a timeout error that rides
        the reconnect machinery like any other transport failure."""
        t = self.op_timeout if timeout is None else timeout
        if not slot.ev.wait(t if t and t > 0 else None):
            self.kill(InfiniStoreTimeoutError(
                f"no response within {t:.3g}s (op deadline); "
                f"channel torn down"
            ))
            slot.ev.wait()  # kill() resolves every in-flight slot
        if slot.error is not None:
            if isinstance(slot.error, InfiniStoreConnectionError):
                raise slot.error
            raise InfiniStoreConnectionError(f"request failed: {slot.error!r}")
        return slot.status, slot.result

    def kill(self, exc: Exception) -> None:
        """Tear the channel down: future submits fail fast, the socket is
        shut (unblocking the reader), and every in-flight slot resolves
        with ``exc``.  Idempotent; safe from any thread."""
        if self._err is None:
            self._err = exc
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._fail_pending(exc)

    def _fail_pending(self, exc: Exception,
                      current: Optional[_Slot] = None) -> None:
        with self._pending_lock:
            pending = list(self._pending)
            self._pending.clear()
        if current is not None:
            pending.insert(0, current)
        for slot in pending:
            if not slot.ev.is_set():
                if slot.error is None:
                    slot.error = exc
                slot.ev.set()

    def request(
        self,
        op: int,
        body: bytes,
        payload: Sequence[memoryview] = (),
        consumer: Optional[Callable] = None,
        trace_id: Optional[str] = None,
        account: Optional[str] = None,
    ) -> Tuple[int, object]:
        return self.wait(self.submit(op, body, payload, consumer, trace_id,
                                     account))

    def _read_loop(self) -> None:
        slot: Optional[_Slot] = None
        try:
            while True:
                hdr = bytearray(P.RESP_SIZE)
                self._recv_exact_into(memoryview(hdr))
                status, body_len = P.RESP.unpack(bytes(hdr))
                with self._pending_lock:
                    slot = self._pending.popleft()
                slot.status = status
                if slot.consumer is not None:
                    slot.result = slot.consumer(self, status, body_len)
                else:
                    body = bytearray(body_len)
                    if body_len:
                        self._recv_exact_into(memoryview(body))
                    slot.result = bytes(body)
                slot.ev.set()
                slot = None
        except Exception as e:  # noqa: BLE001 - fail all in-flight requests
            if self._err is None:  # a kill()'s deadline error wins the race
                self._err = e
            # the popped slot (mid-body when the socket died) must fail
            # too, or its waiter hangs forever — it left the pending queue
            # before the failure
            self._fail_pending(self._err, current=slot)

    def _recv_exact_into(self, view: memoryview) -> None:
        got = 0
        size = len(view)
        while got < size:
            n = self.sock.recv_into(view[got:], size - got)
            if n == 0:
                raise InfiniStoreConnectionError("connection closed by server")
            got += n

    def close(self) -> None:
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()
        if self._reader is not None:
            self._reader.join(timeout=5)


class Connection:
    """Python wire client: pipelined requests over striped TCP sockets.

    The native C++ client (src/store_client.cpp) implements the same calls
    with GIL-free IO; this Python implementation is the portable fallback
    and the spec for the protocol.  ``num_streams`` sockets are opened for
    TCP (DCN) connections and batched inline ops stripe blocks across them;
    SHM connections need only the control stream (payload moves through the
    mapped pool, not the socket).
    """

    def __init__(self, config: ClientConfig):
        self.config = config
        self.channels: List[_Channel] = []
        self.pools: List[_MappedPool] = []
        self.pool_meta: List[Tuple[str, int, int]] = []
        self.shm_mode = False
        self._registered: Dict[int, int] = {}  # base ptr -> size
        self._pool_lock = threading.Lock()
        self._stripe_pool: Optional[ThreadPoolExecutor] = None
        self._copy_pool: Optional[ThreadPoolExecutor] = None
        # coalesced bulk copies by default; tests pin the legacy per-page
        # loop here (or via ISTPU_NO_COALESCE) for byte-parity checks
        self.coalesce = _COALESCE
        self.op_timeout = getattr(config, "op_timeout_s", None)
        self.latency = LatencyStats(sink=_observe_client_op)
        # wire trace-context state (negotiated at HELLO; see connect()):
        # trace_ctx — the server accepts FLAG_TRACE_CTX frames;
        # clock_offset — server perf_counter minus client perf_counter
        # (midpoint estimate from the HELLO round-trip), used by the
        # stitcher to map server span stamps into this process's timeline;
        # server_pid — rendering hint for the stitched Perfetto rows.
        self.trace_ctx = False
        self.clock_offset: Optional[float] = None
        # half the HELLO RTT: the offset estimate's error bound, carried
        # into stitched exports so timeline skew is self-describing.
        # Re-estimated whenever connect() runs again (reconnect/failover
        # builds a fresh Connection), never a stale one-shot value.
        self.clock_offset_err: Optional[float] = None
        self.server_pid: Optional[int] = None
        # integrity state (negotiated at HELLO): when the server answers
        # the EPOC capability trailer, every GET_DESC / inline-get on
        # this connection carries checksums + the server's boot epoch,
        # reads verify AFTER the bulk copy completes, and read leases are
        # released explicitly (OP_RELEASE_DESC) the moment a copy checks
        # out
        self.integrity = False
        self.epoch: Optional[int] = None
        self.checksum_alg = _checksum.ALG_SUM64
        # alloc-first state (negotiated at HELLO): when the server answers
        # the ALOC capability trailer, write_cache_into may learn pool
        # descriptors BEFORE the payload exists and commit from another
        # thread — the server's reservation TTL (reserve_ttl) bounds the
        # leak if this process dies mid-push.  Fails closed: an old server
        # or native runtime leaves alloc_first False and pushes staged.
        self.alloc_first = False
        self.reserve_ttl: Optional[float] = None
        # usage-attribution state (negotiated at HELLO via
        # HELLO_FLAG_ACCOUNT): when the server answers the ACCT trailer,
        # data-plane frames carry the account label bound in the ambient
        # usage context (usage.bind_account) — the serving layer binds
        # each request's tenant around its store hops.  Fails closed:
        # legacy peers leave account_ctx False and every frame stays
        # byte-identical.
        self.account_ctx = False
        self.account_max = P.MAX_ACCOUNT_LABEL
        # grow-only scratch for write_cache_into's staged fallback (a
        # fragmented allocation, a non-shm transport, or no negotiation)
        self._scratch: Optional[np.ndarray] = None

    def latency_stats(self) -> Dict[str, Dict[str, float]]:
        """Client-side per-op latency counters (count/avg/max ms)."""
        return self.latency.snapshot()

    @property
    def sock(self):  # backwards-compat probe: "is connected"
        return self.channels[0].sock if self.channels else None

    # -- plumbing --

    def connect(self) -> None:
        if self.channels:
            raise InfiniStoreException("Already connected to remote instance")
        ch0 = _Channel(self.config.host_addr, self.config.service_port,
                       op_timeout=self.op_timeout)
        hello_flags = P.HELLO_FLAG_TRACE_CTX if _trace_ctx_enabled() else 0
        if _integrity_enabled():
            hello_flags |= P.HELLO_FLAG_INTEGRITY
        if _alloc_first_enabled():
            hello_flags |= P.HELLO_FLAG_ALLOC_FIRST
        if _account_enabled():
            hello_flags |= P.HELLO_FLAG_ACCOUNT
        t0 = time.perf_counter()
        status, body = ch0.exchange(
            P.OP_HELLO, P.pack_hello(os.getpid(), hello_flags)
        )
        t1 = time.perf_counter()
        _raise_for_status(status, "hello")
        ch0.start_reader()
        self.channels.append(ch0)
        pools, srv_flags, t_server = P.unpack_hello_resp(memoryview(body))
        self.pool_meta = pools
        if hello_flags & P.HELLO_FLAG_INTEGRITY:
            # integrity capability answer: an EPOC trailer with the boot
            # epoch (the fence every later response is checked against)
            # and the server's checksum algorithm.  Absent (old server /
            # native runtime / ISTPU_INTEGRITY=off server-side) ->
            # negotiation fails closed, legacy wire format throughout.
            got = P.unpack_hello_epoch(memoryview(body))
            if got is not None:
                self.checksum_alg, self.epoch = got
                self.integrity = True
        if hello_flags & P.HELLO_FLAG_ALLOC_FIRST:
            # alloc-first capability answer: the server's reservation TTL.
            # Absent (old server / native runtime) -> negotiation fails
            # closed and write_cache_into stages through scratch instead.
            ttl = P.unpack_hello_alloc(memoryview(body))
            if ttl is not None:
                self.alloc_first = True
                self.reserve_ttl = ttl
        if hello_flags & P.HELLO_FLAG_ACCOUNT:
            # usage-attribution capability answer.  Absent (old server /
            # native runtime / ISTPU_ACCOUNT=0 server-side) ->
            # negotiation fails closed, no frame ever carries the blob.
            max_label = P.unpack_hello_acct(memoryview(body))
            if max_label is not None:
                self.account_ctx = True
                self.account_max = max(1, min(max_label,
                                              P.MAX_ACCOUNT_LABEL))
        if (hello_flags & P.HELLO_FLAG_TRACE_CTX) and (
                srv_flags & P.HELLO_FLAG_TRACE_CTX):
            # clock-skew correction: the server stamped t_server while the
            # request was in flight; assume it fired at the round-trip
            # midpoint, so server_clock ≈ client_clock + offset.  The
            # error bound is half the HELLO RTT — microseconds on the
            # same-host shm topology this estimate matters for.
            self.trace_ctx = True
            self.clock_offset = t_server - (t0 + t1) / 2
            self.clock_offset_err = (t1 - t0) / 2
        if self.config.connection_type == TYPE_SHM:
            try:
                self._map_pools()
                self.shm_mode = True
            except OSError as e:
                raise InfiniStoreException(
                    f"SHM transport requested but server pools are not mappable "
                    f"(different host?): {e}"
                )
        else:
            # cross-host: stripe data ops over extra sockets (the role the
            # reference's batched RDMA WR chains play for throughput)
            for _ in range(int(self.config.num_streams) - 1):
                ch = _Channel(self.config.host_addr, self.config.service_port,
                              op_timeout=self.op_timeout)
                # the integrity capability is per-CONNECTION server-side:
                # every striped data channel must negotiate it too, or the
                # server would answer batched gets in the legacy layout
                st, _b = ch.exchange(P.OP_HELLO, P.pack_hello(
                    os.getpid(),
                    (P.HELLO_FLAG_INTEGRITY if self.integrity else 0)
                    | (P.HELLO_FLAG_ACCOUNT if self.account_ctx else 0),
                ))
                _raise_for_status(st, "hello")
                ch.start_reader()
                self.channels.append(ch)
            if len(self.channels) > 1:
                self._stripe_pool = ThreadPoolExecutor(
                    max_workers=len(self.channels),
                    thread_name_prefix="istpu-stripe",
                )

    def _map_pools(self) -> None:
        for name, pool_size, _bs in self.pool_meta[len(self.pools) :]:
            self.pools.append(_MappedPool(name, pool_size))

    def _refresh_pools(self) -> None:
        status, body = self._request(P.OP_POOLS, b"")
        _raise_for_status(status, "pools")
        self.pool_meta = P.unpack_pool_table(memoryview(body))
        if self.shm_mode:
            self._map_pools()

    def close(self) -> None:
        if self._stripe_pool is not None:
            self._stripe_pool.shutdown(wait=False)
            self._stripe_pool = None
        if self._copy_pool is not None:
            self._copy_pool.shutdown(wait=True)  # copies touch the pools
            self._copy_pool = None
        for ch in self.channels:
            ch.close()
        self.channels.clear()
        for p in self.pools:
            p.close()
        self.pools.clear()

    def _trace_id(self) -> Optional[str]:
        """Trace id to propagate on the next frame: the active trace's id
        when the server negotiated trace context, else None (frame stays
        byte-identical to the legacy format)."""
        if not self.trace_ctx:
            return None
        return _tracing.current_trace_id()

    def _account(self) -> Optional[str]:
        """Account label to tag the next frame with: the ambient bound
        account (usage.bind_account) when the server negotiated the
        capability, else None (frame stays byte-identical)."""
        if not self.account_ctx:
            return None
        from .usage import current_account

        acct = current_account()
        return acct[: self.account_max] if acct else None

    def _request(self, op: int, body: bytes, payload: Sequence[memoryview] = ()) -> Tuple[int, bytes]:
        if not self.channels:
            raise InfiniStoreException("not connected")
        return self.channels[0].request(
            op, body, payload, trace_id=self._trace_id(),
            account=self._account(),
        )

    # -- zero-copy batched ops (reference: rdma_write_cache/rdma_read_cache) --

    def _pool_view(self, pool_idx: int, offset: int, size: int) -> memoryview:
        if pool_idx >= len(self.pools):
            with self._pool_lock:
                if pool_idx >= len(self.pools):
                    self._refresh_pools()
        return self.pools[pool_idx].buf[offset : offset + size]

    def _pool_arr(self, pool_idx: int) -> np.ndarray:
        if pool_idx >= len(self.pools):
            with self._pool_lock:
                if pool_idx >= len(self.pools):
                    self._refresh_pools()
        return self.pools[pool_idx].arr

    def _copy_exec(self) -> ThreadPoolExecutor:
        if self._copy_pool is None:
            self._copy_pool = ThreadPoolExecutor(
                max_workers=_COPY_WORKERS, thread_name_prefix="istpu-copy"
            )
        return self._copy_pool

    def _copy_descs(
        self,
        descs: Sequence[Tuple[int, int, int]],
        offsets: Sequence[int],
        client_view: memoryview,
        to_pool: bool,
    ) -> None:
        """Move descriptor payloads between the client buffer and the
        mapped pools.  Coalesced mode merges adjacent descriptors into
        runs and issues one GIL-releasing ``np.copyto`` per run, striped
        across a small worker pool when the batch is large; legacy mode
        (``coalesce=False``) is the per-page loop, kept as the
        byte-parity reference."""
        if not self.coalesce:
            for (pool_idx, pool_off, size), off in zip(descs, offsets):
                if to_pool:
                    dst = self._pool_view(pool_idx, pool_off, size)
                    dst[:] = client_view[off : off + size]
                else:
                    src = self._pool_view(pool_idx, pool_off, size)
                    client_view[off : off + size] = src
            return
        runs = _merge_runs(descs, offsets)
        cli = np.frombuffer(client_view, dtype=np.uint8)

        def copy_one(run):
            pool_idx, pool_off, cli_off, length = run
            if length < _VEC_MIN_BYTES:
                # small run: buffer-protocol memcpy beats ufunc dispatch
                if to_pool:
                    dst = self._pool_view(pool_idx, pool_off, length)
                    dst[:] = client_view[cli_off : cli_off + length]
                else:
                    client_view[cli_off : cli_off + length] = self._pool_view(
                        pool_idx, pool_off, length
                    )
                return
            pool = self._pool_arr(pool_idx)
            if to_pool:
                np.copyto(
                    pool[pool_off : pool_off + length],
                    cli[cli_off : cli_off + length],
                )
            else:
                np.copyto(
                    cli[cli_off : cli_off + length],
                    pool[pool_off : pool_off + length],
                )

        total = sum(r[3] for r in runs)
        if len(runs) > 1 and total >= _PAR_MIN_BYTES and _COPY_WORKERS > 1:
            list(self._copy_exec().map(copy_one, runs))
        else:
            for run in runs:
                copy_one(run)

    # -- integrity plane: epoch fence, post-copy verification, release --

    def _epoch_fence(self, server_epoch: int) -> None:
        """Compare a response's epoch against the one captured at HELLO.
        A mismatch means this connection's descriptors and shm mappings
        predate a server restart: drop the stale attach, re-map the
        CURRENT server's pools, and invalidate this read — copying from a
        recycled pool is the one failure the lease machinery can never
        see."""
        if server_epoch == self.epoch:
            return
        old, self.epoch = self.epoch, server_epoch
        _INTEGRITY_FAILURES.labels("epoch").inc()
        Logger.warn(
            f"store epoch changed ({old} -> {server_epoch}): dropping "
            f"stale pool attach and invalidating the in-flight read"
        )
        if self.shm_mode:
            with self._pool_lock:
                stale, self.pools = self.pools, []
                self.pool_meta = []
                try:
                    self._refresh_pools()
                except Exception as e:  # noqa: BLE001 — fence still fires
                    Logger.warn(f"pool remap after epoch change failed: {e!r}")
                for p in stale:
                    try:
                        p.close()
                    except Exception:  # noqa: BLE001 — a pinned view is fine
                        pass
        raise InfiniStoreIntegrityError(
            f"store epoch changed ({old} -> {server_epoch}); descriptors "
            f"predate a server restart", cause="epoch",
        )

    def _verify_descs(self, descs_ex, offsets, client_view, keys,
                      t_desc: float) -> None:
        """Verify delivered bytes against the entries' stamped checksums,
        AFTER the bulk copy completed — this is what converts the
        unfixable lease-expiry race (region recycled mid-copy) into a
        detected, retryable miss.  Vectorized over coalesced runs of
        equal-size descs (one numpy pass per run, not a per-page loop);
        descs the server hasn't stamped yet (csum None) are skipped."""
        arr = np.frombuffer(client_view, dtype=np.uint8)
        bad: List[bytes] = []
        n = len(descs_ex)
        i = 0
        while i < n:
            csum = descs_ex[i][3]
            if csum is None:
                i += 1
                continue
            size = descs_ex[i][2]
            j = i + 1
            if self.checksum_alg == _checksum.ALG_SUM64 and size % 8 == 0:
                # grow a client-contiguous, same-size, stamped run
                while (j < n and descs_ex[j][3] is not None
                       and descs_ex[j][2] == size
                       and offsets[j] == offsets[i] + (j - i) * size):
                    j += 1
            if j - i > 1:
                rows = arr[offsets[i]: offsets[i] + (j - i) * size]
                got = _checksum.checksum_rows(
                    rows.reshape(j - i, size), self.checksum_alg
                )
            else:
                got = [_checksum.checksum(
                    arr[offsets[i]: offsets[i] + size], self.checksum_alg
                )]
            for k in range(i, j):
                if descs_ex[k][3] != got[k - i]:
                    bad.append(keys[k])
            i = j
        if not bad:
            return
        # the copy outlasting the server's read lease makes the recycled-
        # region race the overwhelmingly likely root cause
        cause = ("lease" if time.monotonic() - t_desc > READ_LEASE_S
                 else "checksum")
        _INTEGRITY_FAILURES.labels(cause).inc()
        shown = b", ".join(bad[:4]).decode(errors="replace")
        raise InfiniStoreIntegrityError(
            f"{len(bad)}/{n} pages failed checksum verification "
            f"(cause={cause}): {shown}{'...' if len(bad) > 4 else ''}",
            cause=cause,
            keys=[k.decode(errors="replace") for k in bad],
        )

    def _release_descs(self, keys: Sequence[bytes]) -> None:
        """Fire-and-forget OP_RELEASE_DESC: the copy verified, so the
        read lease has nothing left to protect — releasing now (instead
        of waiting out the 5 s lease) keeps back-to-back runs from
        fragmenting allocation behind lingering leases.  Advisory: a lost
        release just falls back to the timed lease."""
        try:
            self.channels[0].submit(P.OP_RELEASE_DESC, P.pack_keys(keys))
        except Exception:  # noqa: BLE001 — lease expiry covers us
            pass

    def _alloc_put_retrying(self, keys: Sequence[bytes], block_size: int) -> bytes:
        """ALLOC_PUT with exponential backoff on RETRY (another writer is
        actively streaming one of these keys) and a hard deadline that
        turns a wedged peer into a clear error instead of an unbounded
        fixed-interval spin."""
        req = P.pack_alloc_put(keys, block_size)
        status, body = self._request(P.OP_ALLOC_PUT, req)
        if status == P.RETRY:
            # full jitter so many writers contending on one key set don't
            # re-collide in lockstep; unlimited attempts under the budget
            policy = _resilience.RetryPolicy(
                max_attempts=0, base_delay_s=0.002, max_delay_s=0.256,
                budget_s=_RETRY_DEADLINE_S,
            )
            for delay in policy.backoff():
                time.sleep(delay)
                status, body = self._request(P.OP_ALLOC_PUT, req)
                if status != P.RETRY:
                    break
            if status == P.RETRY:
                raise InfiniStoreException(
                    f"alloc_put: server kept answering RETRY for "
                    f"{_RETRY_DEADLINE_S:.0f}s (a concurrent writer is "
                    f"streaming these keys); giving up"
                )
        _raise_for_status(status, "alloc_put")
        return body

    def _stripe(self, blocks: Sequence[Tuple[str, int]]) -> List[Tuple[int, List]]:
        """Partition a batch across channels: [(channel_idx, sub_blocks)]."""
        n = len(self.channels)
        if n == 1 or len(blocks) == 1:
            return [(0, list(blocks))]
        per = -(-len(blocks) // n)
        return [
            (i, list(blocks[i * per : (i + 1) * per]))
            for i in range(n)
            if blocks[i * per : (i + 1) * per]
        ]

    @_timed_op("write_cache")
    def write_cache(self, blocks: Sequence[Tuple[str, int]], block_size: int, ptr: int) -> int:
        """Batched put: key i's payload is ``block_size`` bytes at
        ``ptr + offset_i`` (reference: lib.py:425-481)."""
        if not blocks:
            return P.FINISH  # nothing to allocate, copy, or commit
        keys = P.encode_keys([k for k, _ in blocks])
        offsets = [off for _, off in blocks]
        src = _ptr_view(ptr, max(offsets) + block_size)
        if self.shm_mode:
            with self.latency.timed("write_cache.alloc"):
                body = self._alloc_put_retrying(keys, block_size)
            descs = P.unpack_descs(memoryview(body))
            with self.latency.timed("write_cache.copy"):
                self._copy_descs(descs, offsets, src, to_pool=True)
            with self.latency.timed("write_cache.commit"):
                status, _ = self._request(P.OP_COMMIT_PUT, P.pack_keys(keys))
                _raise_for_status(status, "commit_put")
        else:
            # captured HERE: the stripe workers run off-thread, where the
            # contextvar-bound trace (and account) is not visible
            tid = self._trace_id()
            acct = self._account()

            def _put(chunk):
                ch_idx, sub = chunk
                sub_keys = P.encode_keys([k for k, _ in sub])
                payload = [src[off : off + block_size] for _, off in sub]
                st, _ = self.channels[ch_idx].request(
                    P.OP_PUT_INLINE_BATCH,
                    P.pack_put_inline_batch(sub_keys, block_size),
                    payload,
                    trace_id=tid,
                    account=acct,
                )
                return st

            chunks = self._stripe(blocks)
            if len(chunks) == 1:
                statuses = [_put(chunks[0])]
            else:
                statuses = list(self._stripe_pool.map(_put, chunks))
            for st in statuses:
                _raise_for_status(st, "put_inline_batch")
        return P.FINISH

    @_timed_op("read_cache")
    def read_cache(self, blocks: Sequence[Tuple[str, int]], block_size: int, ptr: int) -> int:
        """Batched get into ``ptr + offset_i`` (reference: lib.py:483-542)."""
        if not blocks:
            return P.FINISH  # nothing to fetch
        offsets = [off for _, off in blocks]
        dst = _ptr_view(ptr, max(offsets) + block_size)
        if self.shm_mode:
            keys = P.encode_keys([k for k, _ in blocks])
            with self.latency.timed("read_cache.desc"):
                status, body = self._request(
                    P.OP_GET_DESC, P.pack_alloc_put(keys, block_size)
                )
                _raise_for_status(status, "get_desc")
            t_desc = time.monotonic()
            if self.integrity:
                epoch, descs_ex = P.unpack_desc_resp_ex(memoryview(body))
                self._epoch_fence(epoch)
                descs = [(p, o, s) for p, o, s, _c in descs_ex]
            else:
                descs_ex = None
                descs = P.unpack_descs(memoryview(body))
            with self.latency.timed("read_cache.copy"):
                self._copy_descs(descs, offsets, dst, to_pool=False)
            if self.integrity:
                # verify AFTER the copy (the lease-expiry race detector),
                # then hand the leases back immediately either way
                try:
                    with self.latency.timed("read_cache.verify"):
                        self._verify_descs(descs_ex, offsets, dst, keys,
                                           t_desc)
                finally:
                    self._release_descs(keys)
        else:
            tid = self._trace_id()  # stripe workers lack the contextvar
            acct = self._account()

            def _get(chunk):
                ch_idx, sub = chunk
                sub_keys = P.encode_keys([k for k, _ in sub])
                sub_offs = [off for _, off in sub]

                def consumer(ch: _Channel, status: int, body_len: int):
                    # runs on the channel's reader thread: stream payloads
                    # straight into the destination buffer
                    if status != P.FINISH:
                        if body_len:
                            ch._recv_exact_into(memoryview(bytearray(body_len)))
                        return None
                    if self.integrity:
                        hdr = bytearray(8)
                        ch._recv_exact_into(memoryview(hdr))
                        (epoch,) = P._U64.unpack(bytes(hdr))
                        items_buf = bytearray(
                            P.BATCH_ITEM_EX_SIZE * len(sub_keys))
                        ch._recv_exact_into(memoryview(items_buf))
                        items = P.unpack_batch_items_ex(
                            memoryview(items_buf), len(sub_keys))
                        for (size, _c), dst_off in zip(items, sub_offs):
                            ch._recv_exact_into(dst[dst_off:dst_off + size])
                        # verification happens on the CALLING thread (an
                        # exception here would be misclassified as a
                        # transport failure by _Channel.wait)
                        return epoch, items
                    sizes_buf = bytearray(4 * len(sub_keys))
                    ch._recv_exact_into(memoryview(sizes_buf))
                    sizes = np.frombuffer(sizes_buf, dtype="<u4")
                    for size, dst_off in zip(sizes, sub_offs):
                        ch._recv_exact_into(dst[dst_off : dst_off + int(size)])
                    return True

                st, res = self.channels[ch_idx].request(
                    P.OP_GET_INLINE_BATCH,
                    P.pack_get_inline_batch(sub_keys, block_size),
                    consumer=consumer,
                    trace_id=tid,
                    account=acct,
                )
                return st, res, sub_keys, sub_offs

            t_desc = time.monotonic()
            chunks = self._stripe(blocks)
            if len(chunks) == 1:
                results = [_get(chunks[0])]
            else:
                results = list(self._stripe_pool.map(_get, chunks))
            for st, _res, _k, _o in results:
                _raise_for_status(st, "get_inline_batch")
            if self.integrity:
                for _st, res, sub_keys, sub_offs in results:
                    if not res:
                        continue
                    epoch, items = res
                    self._epoch_fence(epoch)
                    descs_ex = [(0, 0, size, csum) for size, csum in items]
                    self._verify_descs(descs_ex, sub_offs, dst, sub_keys,
                                       t_desc)
        return P.FINISH

    # -- pipelined banded ops (the prefill-save / restore hot path) --

    @staticmethod
    def _band_ptr(src):
        """Materialize a band's host buffer: an int pointer, a numpy
        array, or a zero-arg callable returning either (called
        just-in-time so a band's D2H can complete while earlier bands
        copy).  Returns (ptr, keepalive)."""
        obj = src() if callable(src) else src
        if isinstance(obj, (int, np.integer)):
            return int(obj), None
        return obj.ctypes.data, obj

    @_timed_op("write_cache_pipelined")
    def write_cache_pipelined(self, bands) -> int:
        """Pipelined multi-band put (shm fast path): band i+1's ALLOC_PUT
        round-trip is already in flight while band i's pool copy runs,
        and ONE COMMIT_PUT publishes the whole save (vs one per band).

        ``bands``: sequence of ``(blocks, block_size, src)`` with ``src``
        an int pointer, numpy array, or zero-arg callable returning
        either.  Off the shm path this degrades to sequential per-band
        ``write_cache``.  Returns bytes written."""
        bands = [b for b in bands if b[0]]
        if not bands:
            return 0
        total = 0
        if not self.shm_mode:
            for blocks, block_size, src in bands:
                ptr, keep = self._band_ptr(src)
                self.write_cache(blocks, block_size, ptr)
                total += block_size * len(blocks)
                del keep
            return total
        ch = self.channels[0]
        tid = self._trace_id()
        acct = self._account()
        enc = [P.encode_keys([k for k, _ in blocks]) for blocks, _, _ in bands]
        all_keys: List[bytes] = []
        slot = ch.submit(P.OP_ALLOC_PUT, P.pack_alloc_put(enc[0], bands[0][1]),
                         trace_id=tid, account=acct)
        for i, (blocks, block_size, src) in enumerate(bands):
            with self.latency.timed("write_cache.alloc"):
                status, body = ch.wait(slot)
                if status == P.RETRY:
                    # rare contention path: synchronous backoff for THIS band
                    body = self._alloc_put_retrying(enc[i], block_size)
                else:
                    _raise_for_status(status, "alloc_put")
            if i + 1 < len(bands):
                slot = ch.submit(
                    P.OP_ALLOC_PUT, P.pack_alloc_put(enc[i + 1], bands[i + 1][1]),
                    trace_id=tid, account=acct,
                )
            descs = P.unpack_descs(memoryview(body))
            offsets = [off for _, off in blocks]
            ptr, keep = self._band_ptr(src)
            view = _ptr_view(ptr, max(offsets) + block_size)
            with self.latency.timed("write_cache.copy"):
                self._copy_descs(descs, offsets, view, to_pool=True)
            del keep
            all_keys.extend(enc[i])
            total += block_size * len(blocks)
        with self.latency.timed("write_cache.commit"):
            status, _ = self._request(P.OP_COMMIT_PUT, P.pack_keys(all_keys))
            _raise_for_status(status, "commit_put")
        return total

    def _fill_scratch(self, nbytes: int) -> np.ndarray:
        buf = self._scratch
        if buf is None or buf.nbytes < nbytes:
            buf = np.empty(nbytes, dtype=np.uint8)
            self._scratch = buf
        return buf

    @_timed_op("write_cache_into")
    def write_cache_into(self, bands, stage=None) -> dict:
        """Alloc-first, fill-in-place put — the zero-copy half of the
        HBM→pool push path.

        ``bands``: sequence of ``(blocks, block_size, fill)`` where
        ``fill(dst)`` writes the band's ``len(blocks) * block_size``
        payload bytes into ``dst`` (a writable uint8 ndarray).  On an shm
        connection that negotiated the alloc-first capability, EVERY
        band's ALLOC_PUT goes on the wire up front — before any payload
        exists, so a device→host DMA can still be in flight — and each
        band whose descriptors merge to one contiguous run hands ``fill``
        a view of the MAPPED POOL itself: the payload's first landing in
        host memory IS the store pool, no intermediate host array, no
        second memcpy.  Fragmented allocations (and non-shm / legacy
        peers) degrade to one staging copy through a reusable scratch
        buffer.

        ``stage``: ``stage("alloc")`` / ``stage("commit")`` give the context
        manager that times the ALLOC_PUT and COMMIT_PUT round trips, and
        ``stage("pool_copy")`` a staged band's copy from the scratch buffer
        into the pool (its seconds as ``.s``); default a plain clock pair.

        Returns ``{"bytes", "zero_copy_bands", "staged_bands", "alloc_s",
        "copy_s", "commit_s"}`` — the band counters the structural perf
        guard asserts on, plus the phase seconds the bench breakdown reads
        (``copy_s``: the staged bands' second copy alone; ``fill`` times
        its own)."""
        stage = stage or Timer
        bands = [b for b in bands if b[0]]
        info = {"bytes": 0, "zero_copy_bands": 0, "staged_bands": 0,
                "alloc_s": 0.0, "copy_s": 0.0, "commit_s": 0.0}
        if not bands:
            return info
        if not (self.shm_mode and self.alloc_first):
            # no negotiated zero-copy target: stage each band, then the
            # ordinary batched put (works against any peer)
            for blocks, block_size, fill in bands:
                nbytes = block_size * len(blocks)
                scratch = self._fill_scratch(nbytes)
                fill(scratch[:nbytes])
                self.write_cache(blocks, block_size, scratch.ctypes.data)
                info["staged_bands"] += 1
                info["bytes"] += nbytes
            return info
        ch = self.channels[0]
        tid = self._trace_id()
        acct = self._account()
        enc = [P.encode_keys([k for k, _ in blocks])
               for blocks, _, _ in bands]
        with self.latency.staged("write_cache.alloc", stage("alloc")) as st:
            # all bands' ALLOC_PUTs pipelined on one channel: the
            # descriptors come back while the payload is still being
            # produced (this is what "alloc-first" buys)
            slots = [
                ch.submit(P.OP_ALLOC_PUT, P.pack_alloc_put(enc[i], b[1]),
                          trace_id=tid, account=acct)
                for i, b in enumerate(bands)
            ]
            descs_per = []
            for i, slot in enumerate(slots):
                status, body = ch.wait(slot)
                if status == P.RETRY:
                    # rare contention path: synchronous backoff this band
                    body = self._alloc_put_retrying(enc[i], bands[i][1])
                else:
                    _raise_for_status(status, "alloc_put")
                descs_per.append(P.unpack_descs(memoryview(body)))
        info["alloc_s"] = st.s
        all_keys: List[bytes] = []
        for i, (blocks, block_size, fill) in enumerate(bands):
            descs = descs_per[i]
            offsets = [off for _, off in blocks]
            nbytes = block_size * len(blocks)
            runs = _merge_runs(descs, offsets)
            with self.latency.timed("write_cache.fill"):
                if (len(runs) == 1 and runs[0][2] == 0
                        and runs[0][3] == nbytes):
                    # one contiguous pool run covering the whole band:
                    # fill writes the pool directly — zero staging copies
                    pool_idx, pool_off, _cli, length = runs[0]
                    fill(self._pool_arr(pool_idx)[
                        pool_off : pool_off + length])
                    info["zero_copy_bands"] += 1
                else:
                    scratch = self._fill_scratch(nbytes)
                    fill(scratch[:nbytes])
                    with stage("pool_copy") as st:
                        self._copy_descs(descs, offsets,
                                         memoryview(scratch)[:nbytes],
                                         to_pool=True)
                    info["copy_s"] += st.s
                    info["staged_bands"] += 1
            all_keys.extend(enc[i])
            info["bytes"] += nbytes
        with self.latency.staged("write_cache.commit", stage("commit")) as st:
            status, _ = self._request(P.OP_COMMIT_PUT, P.pack_keys(all_keys))
            _raise_for_status(status, "commit_put")
        info["commit_s"] = st.s
        return info

    @_timed_op("read_cache_pipelined")
    def read_cache_pipelined(self, bands, on_band: Optional[Callable] = None,
                             stages: Optional[dict] = None) -> int:
        """Mirror image of ``write_cache_pipelined``: band i+1's GET_DESC
        round-trip rides behind band i's pool copy.  ``bands``: sequence
        of ``(blocks, block_size, ptr)``.  ``on_band(i)`` fires once band
        i's bytes are in place (the KV load path hands each band to an
        async H2D there).  ``stages``: a dict whose ``desc_s`` (the waits
        for GET_DESC answers) and ``pool_copy_s`` (pool to ``ptr``, and the
        verification where integrity is on) gain this call's seconds, on
        the shm path (the inline path has no such stages).  Returns bytes
        read."""
        live = [(i, b) for i, b in enumerate(bands) if b[0]]
        if not live:
            return 0
        total = 0
        if not self.shm_mode:
            for i, (blocks, block_size, ptr) in live:
                self.read_cache(blocks, block_size, ptr)
                total += block_size * len(blocks)
                if on_band is not None:
                    on_band(i)
            return total
        ch = self.channels[0]
        tid = self._trace_id()
        acct = self._account()
        enc = [P.encode_keys([k for k, _ in b[0]]) for _, b in live]
        slot = ch.submit(P.OP_GET_DESC, P.pack_alloc_put(enc[0], live[0][1][1]),
                         trace_id=tid, account=acct)
        if stages is None:
            stages = {"desc_s": 0.0, "pool_copy_s": 0.0}
        for j, (i, (blocks, block_size, ptr)) in enumerate(live):
            with self.latency.staged("read_cache.desc", Timer()) as st:
                status, body = ch.wait(slot)
                _raise_for_status(status, "get_desc")
            stages["desc_s"] += st.s
            t_desc = time.monotonic()
            if j + 1 < len(live):
                slot = ch.submit(
                    P.OP_GET_DESC,
                    P.pack_alloc_put(enc[j + 1], live[j + 1][1][1]),
                    trace_id=tid, account=acct,
                )
            if self.integrity:
                epoch, descs_ex = P.unpack_desc_resp_ex(memoryview(body))
                self._epoch_fence(epoch)
                descs = [(p, o, s) for p, o, s, _c in descs_ex]
            else:
                descs_ex = None
                descs = P.unpack_descs(memoryview(body))
            offsets = [off for _, off in blocks]
            view = _ptr_view(ptr, max(offsets) + block_size)
            with self.latency.staged("read_cache.copy", Timer()) as st:
                self._copy_descs(descs, offsets, view, to_pool=False)
            stages["pool_copy_s"] += st.s
            if self.integrity:
                # verify BEFORE on_band fires: a band is only handed to
                # the H2D upload once its bytes checked out — corrupt
                # pages must never be admitted into the paged cache
                try:
                    with self.latency.staged("read_cache.verify", Timer()) as st:
                        self._verify_descs(descs_ex, offsets, view, enc[j],
                                           t_desc)
                    stages["pool_copy_s"] += st.s
                finally:
                    self._release_descs(enc[j])
            total += sum(s for _, _, s in descs)
            if on_band is not None:
                on_band(i)
        return total

    # -- inline single-key ops (reference: w_tcp/r_tcp) --

    @_timed_op("w_tcp")
    def w_tcp(self, key: str, ptr: int, size: int) -> int:
        payload = _ptr_view(ptr, size)
        body = P.pack_put_inline(key.encode(), size)
        status, _ = self._request(P.OP_PUT_INLINE, body + bytes(payload))
        _raise_for_status(status, "tcp write")
        return 0

    @_timed_op("w_tcp")
    def w_tcp_bytes(self, key: str, data: bytes) -> int:
        body = P.pack_put_inline(key.encode(), len(data))
        status, _ = self._request(P.OP_PUT_INLINE, body + data)
        _raise_for_status(status, "tcp write")
        return 0

    @_timed_op("r_tcp")
    def r_tcp(self, key: str) -> np.ndarray:
        status, body = self._request(P.OP_GET_INLINE, P.pack_keys([key.encode()]))
        _raise_for_status(status, "tcp read")
        if self.integrity:
            epoch, csum, consumed = P.unpack_inline_resp_ex(memoryview(body))
            self._epoch_fence(epoch)
            payload = np.frombuffer(body, dtype=np.uint8)[consumed:]
            if csum is not None and _checksum.checksum(
                    payload, self.checksum_alg) != csum:
                _INTEGRITY_FAILURES.labels("checksum").inc()
                raise InfiniStoreIntegrityError(
                    f"inline read of {key!r} failed checksum verification",
                    cause="checksum", keys=[key],
                )
            return payload
        return np.frombuffer(body, dtype=np.uint8)

    # -- metadata ops --

    def check_exist(self, key: str) -> int:
        status, body = self._request(P.OP_EXIST, P.pack_keys([key.encode()]))
        _raise_for_status(status, "check_exist")
        return P.unpack_i32(body)  # 0 => exists (reference: src/infinistore.cpp:771-784)

    def get_match_last_index(self, keys: Sequence[str]) -> int:
        status, body = self._request(P.OP_MATCH_LAST_IDX, P.pack_keys(P.encode_keys(keys)))
        _raise_for_status(status, "get_match_last_index")
        return P.unpack_i32(body)

    def delete_keys(self, keys: Sequence[str]) -> int:
        status, body = self._request(P.OP_DELETE_KEYS, P.pack_keys(P.encode_keys(keys)))
        _raise_for_status(status, "delete_keys")
        return P.unpack_i32(body)

    def purge(self) -> int:
        status, body = self._request(P.OP_PURGE, b"")
        _raise_for_status(status, "purge")
        return P.unpack_i32(body)

    def stats(self) -> dict:
        status, body = self._request(P.OP_STATS, b"")
        _raise_for_status(status, "stats")
        return json.loads(body.decode())

    def trace_dump(self) -> dict:
        """The server's completed-span ring, raw server-clock stamps
        (wire OP_TRACE_DUMP).  Feed it to ``utils.trace_stitch`` together
        with ``clock_offset`` to merge server spans into this process's
        trace timeline.  Requires a server that negotiated trace context
        at HELLO."""
        if not self.trace_ctx:
            raise InfiniStoreException(
                "server did not negotiate trace context at HELLO"
            )
        status, body = self._request(P.OP_TRACE_DUMP, b"")
        _raise_for_status(status, "trace_dump")
        dump = json.loads(body.decode())
        self.server_pid = dump.get("pid")
        return dump

    def evict(self, min_threshold: float, max_threshold: float) -> None:
        status, _ = self._request(P.OP_EVICT, P.pack_evict(min_threshold, max_threshold))
        _raise_for_status(status, "evict")

    def list_keys(self, limit: int = 0) -> List[str]:
        """Every retrievable key on the server, both tiers (wire
        OP_LIST_KEYS; python runtimes only) — the membership migration
        plane's enumeration primitive.  ``limit`` 0 = server-side cap."""
        status, body = self._request(P.OP_LIST_KEYS, P.pack_i32(limit))
        _raise_for_status(status, "list_keys")
        return json.loads(body.decode())

    def list_keys_sizes(self, limit: int = 0):
        """``[(key, size), ...]`` for every retrievable key, or ``None``
        when the server predates LIST_KEYS_F_SIZES (it ignores the
        trailing flags i32 and answers names-only — the caller falls
        back to the per-key path).  Sizes let the batched migration
        plane group descriptor reads by exact entry size."""
        status, body = self._request(
            P.OP_LIST_KEYS,
            P.pack_list_keys(limit, P.LIST_KEYS_F_SIZES),
        )
        _raise_for_status(status, "list_keys_sizes")
        rows = json.loads(body.decode())
        if rows and not isinstance(rows[0], list):
            return None  # pre-flag server: names-only response
        return [(k, int(sz)) for k, sz in rows]

    def register_mr(self, ptr: int, size: int) -> int:
        """Record a client buffer region for zero-copy ops.  No NIC to
        register with on a TPU-VM; kept for API parity and sanity checks
        (reference: lib.py:580-616)."""
        self._registered[ptr] = size
        return 0

    def unregister_mr(self, ptr: int) -> int:
        """Release a registration made by ``register_mr`` — a staging
        buffer that grew and was replaced must drop its old registration
        or the MR table (and the wrapper's reconnect-replay list) leaks
        one dead entry per growth."""
        self._registered.pop(ptr, None)
        return 0


def _make_connection(config: ClientConfig):
    """Native C++ client when built (GIL-free IO), Python fallback otherwise.

    ``ISTPU_CLIENT=python`` forces the fallback; ``=native`` makes a missing
    native build a hard error.  ``op_timeout_s`` pins the Python client:
    per-op deadlines live in its channel layer (the C client's calls block
    without one), and silently dropping a configured deadline would
    reintroduce exactly the unbounded hang the knob exists to kill."""
    mode = os.environ.get("ISTPU_CLIENT", "auto")
    if getattr(config, "op_timeout_s", None):
        if mode == "native":
            raise InfiniStoreException(
                "op_timeout_s is not supported by the native client "
                "(ISTPU_CLIENT=native); unset one of the two"
            )
        return Connection(config)
    if mode != "python":
        try:
            from . import _native
        except (ImportError, OSError):
            _native = None
            if mode == "native":
                raise
        # only a missing/unloadable library falls through; real errors from
        # the native client itself must surface, not mask as a silent
        # slow-path fallback
        if _native is not None and _native.available():
            return _native.NativeConnection(config)
        if mode == "native":
            raise InfiniStoreException("ISTPU_CLIENT=native but libistpu.so not built")
    return Connection(config)


class InfinityConnection:
    """Reference parity: infinistore/lib.py:288-636."""

    OP_RDMA_READ = "A"  # parity constant

    def __init__(self, config: ClientConfig):
        config.verify()
        self.conn = _make_connection(config)
        self.config = config
        self.rdma_connected = False  # parity name: true when zero-copy path is up
        self.semaphore = asyncio.BoundedSemaphore(128)
        self._connected = False
        self._mrs: list = []  # (ptr, size) replayed on reconnect
        self._gen = 0  # bumps on every successful reconnect
        self._needs_reconnect = False  # a reconnect attempt failed; retry next op
        self._reconnect_lock = threading.Lock()
        Logger.set_log_level(config.log_level)

    @staticmethod
    def resolve_hostname(hostname: str) -> str:
        try:
            socket.inet_aton(hostname)
            return hostname
        except socket.error:
            pass
        Logger.info(f"Resolving hostname: {hostname}")
        try:
            infos = socket.getaddrinfo(hostname, None, socket.AF_INET, socket.SOCK_STREAM)
            return infos[0][4][0]
        except socket.gaierror as e:
            raise InfiniStoreException(f"Failed to resolve hostname '{hostname}': {e}")

    def connect(self) -> None:
        if self._connected:
            raise InfiniStoreException("Already connected to remote instance")
        self.config.host_addr = self.resolve_hostname(self.config.host_addr)
        self.conn.connect()
        self._connected = True
        if self.config.connection_type == TYPE_SHM:
            self.rdma_connected = True

    def reconnect(self) -> None:
        """Tear down and re-establish the transport: fresh sockets, freshly
        mapped pools (a restarted server publishes new shm segments), and
        every registered MR replayed.  Reference analog: the client-side
        retry half of SURVEY §5 failure handling."""
        with self._reconnect_lock:
            self._reconnect_locked()

    def _reconnect_locked(self) -> None:
        # Build the replacement connection FULLY before swapping it in: a
        # failed attempt (server still down) must leave self.conn a dead-but-
        # recognizable transport whose ops keep raising connection errors, so
        # a later op can retry the reconnect once the server is back.
        self._needs_reconnect = True
        old_epoch = getattr(self.conn, "epoch", None)
        try:
            self.conn.close()
        except Exception:
            pass
        # rebuild the SAME implementation chosen at construction time —
        # re-reading ISTPU_CLIENT here could silently swap python<->native
        # mid-session (e.g. under a scoped env pin)
        conn = type(self.conn)(self.config)
        conn.connect()
        new_epoch = getattr(conn, "epoch", None)
        if (old_epoch is not None and new_epoch is not None
                and new_epoch != old_epoch):
            # the server behind the address RESTARTED (not just a
            # transient outage): any state derived from the old epoch —
            # descriptors, pool mappings, cached existence answers — is
            # void.  The fresh connection mapped the new pools already;
            # count the fence so operators see restarts in the failure
            # breakdown.
            _INTEGRITY_FAILURES.labels("epoch").inc()
            Logger.warn(
                f"store epoch changed across reconnect "
                f"({old_epoch} -> {new_epoch}): pre-restart descriptors "
                f"and pool mappings invalidated"
            )
        for ptr, size in self._mrs:
            conn.register_mr(ptr, size)
        self.conn = conn
        self._gen += 1
        self._needs_reconnect = False
        self._connected = True
        if self.config.connection_type == TYPE_SHM:
            self.rdma_connected = True

    def _try_reconnect(self, gen: int, why) -> None:
        with self._reconnect_lock:
            if not self._connected:
                # close() won the race — a closed connection must not revive
                raise InfiniStoreConnectionError("connection closed")
            if self._gen == gen or self._needs_reconnect:
                # first thread in does the work; losers ride the fresh conn
                Logger.warn(f"transport failure ({why}); reconnecting")
                self._reconnect_locked()

    def _call(self, name: str, *args):
        """Run a connection op; on a TRANSPORT failure (socket/channel dead
        — never a server-answered status like OOM or KEY_NOT_FOUND),
        reconnect once and retry.  Threads coordinate via a generation
        counter: whoever loses the race rides the winner's fresh
        connection."""
        if self._needs_reconnect and self.config.auto_reconnect and self._connected:
            # an earlier reconnect attempt failed mid-outage; try again
            # before the op instead of poking the known-dead transport
            self._try_reconnect(self._gen, "previous reconnect failed")
        gen = self._gen
        try:
            return getattr(self.conn, name)(*args)
        except (OSError, InfiniStoreConnectionError) as e:
            if not (self.config.auto_reconnect and self._connected):
                raise
            self._try_reconnect(gen, e)
            return getattr(self.conn, name)(*args)

    async def connect_async(self) -> None:
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.connect)

    def close(self) -> None:
        pool = getattr(self, "_async_pool", None)
        if pool is not None:
            pool.shutdown(wait=False)
            self._async_pool = None
        # under the reconnect lock so an in-flight op's failure handler
        # cannot revive the transport we are tearing down
        with self._reconnect_lock:
            self.conn.close()
            self.rdma_connected = False
            self._connected = False  # a closed connection must not auto-revive

    def latency_stats(self) -> dict:
        """Client-side per-op latency counters (count/avg/max ms); empty for
        the native client, whose timings live in the C runtime."""
        fn = getattr(self.conn, "latency_stats", None)
        return fn() if fn is not None else {}

    # -- zero-copy batched API --

    def write_cache(self, blocks: Sequence[Tuple[str, int]], block_size: int, ptr: int) -> int:
        # safe to retry across a reconnect: committed keys may be
        # overwritten (reference semantics) and a server that died
        # mid-write aborted the pending entries on disconnect
        return self._call("write_cache", blocks, block_size, ptr)

    def read_cache(self, blocks: Sequence[Tuple[str, int]], block_size: int, ptr: int) -> int:
        return self._call("read_cache", blocks, block_size, ptr)

    def write_cache_pipelined(self, bands) -> int:
        """Banded put with alloc/copy overlap and ONE commit per save
        (python shm client); clients without the entry point (native)
        fall back to sequential per-band ``write_cache``."""
        if hasattr(self.conn, "write_cache_pipelined"):
            return self._call("write_cache_pipelined", bands)
        total = 0
        for blocks, block_size, src in bands:
            if not blocks:
                continue
            obj = src() if callable(src) else src
            ptr = int(obj) if isinstance(obj, (int, np.integer)) else obj.ctypes.data
            self.write_cache(blocks, block_size, ptr)
            total += block_size * len(blocks)
        return total

    def write_cache_into(self, bands, stage=None) -> dict:
        """Alloc-first fill-in-place put (see ``Connection``): clients
        without the entry point (native) stage each band through a
        scratch buffer and ride the plain batched put (their stages are
        timed in C: ``stage`` is not called)."""
        if hasattr(self.conn, "write_cache_into"):
            return self._call("write_cache_into", bands, stage)
        info = {"bytes": 0, "zero_copy_bands": 0, "staged_bands": 0}
        for blocks, block_size, fill in bands:
            if not blocks:
                continue
            nbytes = block_size * len(blocks)
            scratch = np.empty(nbytes, dtype=np.uint8)
            fill(scratch)
            self.write_cache(blocks, block_size, scratch.ctypes.data)
            info["staged_bands"] += 1
            info["bytes"] += nbytes
        return info

    def read_cache_pipelined(self, bands, on_band=None, stages=None) -> int:
        """Banded get with desc-prefetch overlap; ``on_band(i)`` fires as
        each band's bytes land (same fallback rule as the write side, and
        there ``stages`` gains nothing)."""
        if hasattr(self.conn, "read_cache_pipelined"):
            return self._call("read_cache_pipelined", bands, on_band, stages)
        total = 0
        for i, (blocks, block_size, ptr) in enumerate(bands):
            if blocks:
                self.read_cache(blocks, block_size, ptr)
                total += block_size * len(blocks)
            if on_band is not None:
                on_band(i)
        return total

    def _io_pool(self):
        # One shared bounded executor per connection: asyncio's loop-default
        # executor is created per event loop (tests/apps often spin up many
        # short-lived loops), which churns threads and loses the pipelined
        # channels' warm state.  The sync calls below already overlap on the
        # wire via req_id pipelining + socket striping, so a handful of
        # threads is enough to keep every channel busy.
        pool = getattr(self, "_async_pool", None)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="istpu-async"
            )
            self._async_pool = pool
        return pool

    async def write_cache_async(
        self, blocks: Sequence[Tuple[str, int]], block_size: int, ptr: int
    ) -> int:
        async with self.semaphore:
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(
                self._io_pool(), self.write_cache, blocks, block_size, ptr
            )

    async def read_cache_async(
        self, blocks: Sequence[Tuple[str, int]], block_size: int, ptr: int
    ) -> int:
        async with self.semaphore:
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(
                self._io_pool(), self.read_cache, blocks, block_size, ptr
            )

    # drop-in aliases for reference callers
    rdma_write_cache_async = write_cache_async
    rdma_read_cache_async = read_cache_async

    def rdma_write_cache(self, blocks, block_size, ptr):
        return self.write_cache(blocks, block_size, ptr)

    def rdma_read_cache(self, blocks, block_size, ptr):
        return self.read_cache(blocks, block_size, ptr)

    # -- inline single-key API --

    def tcp_write_cache(self, key: str, ptr: int, size: int, **kwargs) -> None:
        if key == "":
            raise InfiniStoreException("key is empty")
        if size == 0:
            raise InfiniStoreException("size is 0")
        if ptr == 0:
            raise InfiniStoreException("ptr is 0")
        self._call("w_tcp", key, ptr, size)

    def tcp_read_cache(self, key: str, **kwargs) -> np.ndarray:
        return self._call("r_tcp", key)

    # -- metadata --

    def check_exist(self, key: str) -> bool:
        return self._call("check_exist", key) == 0

    def get_match_last_index(self, keys: Sequence[str]) -> int:
        ret = self._call("get_match_last_index", keys)
        if ret < 0:
            raise InfiniStoreException("can't find a match")
        return ret

    def delete_keys(self, keys: Sequence[str]) -> int:
        ret = self._call("delete_keys", keys)
        if ret < 0:
            raise InfiniStoreException(
                "somethings are wrong, not all the specified keys were deleted"
            )
        return ret

    def purge(self) -> int:
        """Drop every committed entry (wire OP_PURGE; manage-plane /purge
        is the HTTP spelling of the same op)."""
        return self._call("purge")

    def list_keys(self, limit: int = 0) -> List[str]:
        """Every retrievable key on the server, both tiers (wire
        OP_LIST_KEYS; python runtimes only)."""
        return self._call("list_keys", limit)

    def list_keys_sizes(self, limit: int = 0):
        """``[(key, size), ...]`` for every retrievable key, or ``None``
        from a server that predates the sizes flag (the migration plane
        then falls back to per-key copies)."""
        return self._call("list_keys_sizes", limit)

    def evict(self, min_threshold: float, max_threshold: float) -> None:
        """Run one eviction pass with explicit thresholds (wire OP_EVICT).
        With a disk tier attached, evicted entries spill instead of
        vanishing."""
        return self._call("evict", min_threshold, max_threshold)

    def stats(self) -> dict:
        """Server stats snapshot (wire OP_STATS; same payload as the
        manage plane's /metrics)."""
        return self._call("stats")

    def trace_dump(self) -> dict:
        """Server-side span ring for the trace stitcher (python client
        with negotiated trace context only)."""
        return self._call("trace_dump")

    def register_mr(self, arg: Union[int, "np.ndarray"], size: Optional[int] = None) -> int:
        if isinstance(arg, (int, np.integer)):
            if not self.rdma_connected and self.config.connection_type == TYPE_SHM:
                raise InfiniStoreException(
                    "this function is only valid for a connected zero-copy client"
                )
            if size is None:
                raise InfiniStoreException("size is required")
            return self._register_mr(int(arg), size)
        if isinstance(arg, np.ndarray):
            return self._register_mr(arg.ctypes.data, arg.size * arg.itemsize)
        raise NotImplementedError(f"not supported: {type(arg)}")

    def _register_mr(self, ptr: int, size: int) -> int:
        # under the reconnect lock: a registration racing a reconnect must
        # land on the connection that survives, and the replay list must not
        # collect duplicates from re-registration loops
        with self._reconnect_lock:
            ret = self.conn.register_mr(ptr, size)
            if (ptr, size) not in self._mrs:
                self._mrs.append((ptr, size))
            return ret

    def unregister_mr(self, ptr: int) -> int:
        """Release a registration: drops it from the live connection AND
        from the reconnect-replay list, so a grown-and-replaced staging
        buffer doesn't accumulate one dead MR per growth."""
        with self._reconnect_lock:
            self._mrs = [(p, s) for p, s in self._mrs if p != ptr]
            fn = getattr(self.conn, "unregister_mr", None)
            return fn(ptr) if fn is not None else 0
