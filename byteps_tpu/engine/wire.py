"""Pipelined wire engine for the PS client — framing codec + per-shard
I/O workers.

The reference keeps push/pull fast by never letting the wire idle:
partitions pipeline (push of part *i+1* overlaps the pull of part *i*)
and fan out across server shards concurrently, in priority order
(BytePS core_loops.cc's Run*LoopOnce threads; ByteScheduler's credit
windows).  The seed's ``RemoteStore`` did the opposite — one blocking
request→response round-trip per partition, holding the shard lock — so
a 4-shard cluster with 8 partitions still had exactly one frame in
flight cluster-wide.

This module provides the two halves that fix it:

**Framing codec** (shared with the server and the chaos proxy):
``_encode_buffers`` builds a *list* of buffers — the fixed header plus a
zero-copy ``uint8`` view of the tensor payload — which ``_send_buffers``
hands to ``sendmsg`` scatter-gather, so a multi-MB push never
concatenates into a second copy; ``_recv_exact`` reads into one
preallocated ``bytearray`` via ``recv_into`` (the seed grew a ``bytes``
quadratically).

**ShardWorker** — one per (client, shard): a send loop draining a
priority ``ScheduledQueue`` (same (priority desc, key asc) order as the
engine dispatcher, so first-needed gradients win the wire) under a
bounded in-flight window (``BYTEPS_WIRE_WINDOW``), and a receive loop
matching replies to requests **by order**.  FIFO matching is sound
because ``_Handler`` serves one connection's requests strictly in
arrival order — no protocol change, no tags; an old server and a new
client interoperate.  The failure contract:

  * any wire error (reset, garbled frame, timeout) kills the whole
    connection and fails every un-acked in-flight request — each then
    re-enters ``RemoteStore._rpc``'s retry/version-guard/failover
    machinery *individually*, so a mid-window reset neither drops nor
    double-applies any part (the OP_VERSION dedup probe stays
    per-(name, shard) exactly as in the serial client);
  * a request still queued (never sent) survives a reset untouched and
    goes out on the fresh connection;
  * a caller abandoning a SENT request (op deadline) must kill the
    connection too — selectively forgetting one in-flight frame would
    desynchronize FIFO matching for every later reply.

``BYTEPS_WIRE_WINDOW=0`` disables the workers entirely and restores the
serial blocking client — the baseline the pipelined one is compared
against.  See docs/wire.md.
"""

from __future__ import annotations

import collections
import os
import socket
import struct
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..common import logging as bps_log
from ..common.scheduler import ScheduledQueue
from ..common.types import TensorTaskEntry
from ..compression.wire import WIRE_MAGIC, WireBlob, decode_blob

_MAX_NAME = 1 << 16
_MAX_PAYLOAD = 1 << 34  # 16 GiB sanity bound

# versioned header extension (distributed tracing, docs/observability.md):
# a frame whose op byte has _EXT_FLAG set carries, between the fixed
# 5-byte head and the name, an extension block
#     u8 version | u8 length | <length bytes>
# Version 1's body is the 8-byte per-RPC trace id minted at
# push_pull/serving submit.  Forward compatibility is LOUD like the
# compression tag ``bpsc1``: this decoder raises on an unknown
# extension version rather than guessing at its length's meaning.
# Backward is NOT protected — a pre-extension server reads the
# extension bytes as the start of the name and desyncs on the shifted
# length fields (hang/garbage, not a clean "bad op"), because its
# decoder consumes the whole frame before dispatching on op.  Set
# ``BYTEPS_TRACE_RPC=0`` on the client when talking to older shards
# (the auto default only extends frames when tracing is on).
_EXT_FLAG = 0x80
_EXT_VERSION = 1
_TRACE_ID_LEN = 8


# ---------------------------------------------------------------- wire codec


def _dtype_to_wire(dt: np.dtype) -> bytes:
    """Encode a dtype by *name* (e.g. ``bfloat16``): ml_dtypes dtypes have
    ``.str`` of ``'<V2'`` (raw void) which would not round-trip."""
    return np.dtype(dt).name.encode()


def _wire_to_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    """Read exactly ``n`` bytes into ONE preallocated buffer via
    ``recv_into`` — linear, unlike the seed's quadratic ``bytes +=``
    growth.  Returns the bytearray itself (callers ``struct.unpack`` /
    ``decode`` / ``np.frombuffer`` it without another copy; each message
    owns its buffer, nothing is reused)."""
    buf = bytearray(n)
    if n:
        view = memoryview(buf)
        got = 0
        while got < n:
            r = sock.recv_into(view[got:])
            if r == 0:
                raise ConnectionError("peer closed mid-message")
            got += r
    return buf


def hard_reset(sock: socket.socket) -> None:
    """Close with an RST (SO_LINGER 0), not a FIN — the peer sees
    ECONNRESET mid-RPC, the way a crashed process looks.  Shared by
    ``PSServer.kill`` and the chaos proxy."""
    try:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


def _payload_view(arr: np.ndarray):
    """Zero-copy byte view of a (contiguous) array — what the frame
    payload slot sends via scatter-gather instead of ``tobytes()``'s
    full copy.  Works for ml_dtypes too (uint8 reinterpret, no buffer-
    protocol format string involved)."""
    if arr.size == 0:
        return b""
    return arr.reshape(-1).view(np.uint8)


def _encode_buffers(op: int, name: str, arr, raw: bytes = b"",
                    trace_id: bytes = b"") -> List:
    """Build one request/reply frame as a buffer LIST for scatter-gather
    send: ``[header, payload...]`` with the payload a zero-copy view of
    the tensor (or the WireBlob's own buffers).  ``b"".join`` of the
    result is byte-identical to the seed's single-buffer framing.
    A non-empty ``trace_id`` (8 bytes) rides the versioned header
    extension — see the module-level framing notes."""
    nb = name.encode()
    payload_bufs: Sequence
    if isinstance(arr, WireBlob):
        # compressed payload: versioned dtype tag, original shape in the
        # frame header, scheme-tagged blob as the payload
        from ..compression.wire import WIRE_TAG

        dt = WIRE_TAG.encode()
        shape = arr.shape
        payload_bufs = arr.buffers()
        plen = arr.nbytes
    elif arr is not None:
        arr = np.ascontiguousarray(arr)
        dt = _dtype_to_wire(arr.dtype)
        shape = arr.shape
        view = _payload_view(arr)
        payload_bufs = (view,)
        plen = arr.nbytes
    else:
        dt = b""
        shape = ()
        payload_bufs = (raw,) if raw else ()
        plen = len(raw)
    if trace_id:
        if len(trace_id) != _TRACE_ID_LEN:
            raise ValueError(
                f"trace id must be {_TRACE_ID_LEN} bytes, got "
                f"{len(trace_id)}")
        head = struct.pack("<BI", op | _EXT_FLAG, len(nb))
        head += struct.pack("<BB", _EXT_VERSION, _TRACE_ID_LEN) + trace_id
        head += nb
    else:
        head = struct.pack("<BI", op, len(nb)) + nb
    head += struct.pack("<I", len(dt)) + dt
    head += struct.pack("<B", len(shape)) + struct.pack(
        f"<{len(shape)}Q", *shape
    )
    head += struct.pack("<Q", plen)
    return [head, *payload_bufs]


def _encode(op: int, name: str, arr, raw: bytes = b"") -> bytes:
    """One-buffer framing for single-shot senders (heartbeat pings, the
    serving frontend) — join of ``_encode_buffers``."""
    bufs = _encode_buffers(op, name, arr, raw)
    return bufs[0] if len(bufs) == 1 else b"".join(
        bytes(b) if not isinstance(b, bytes) else b for b in bufs)


# sendmsg rejects iovecs longer than IOV_MAX (1024 on Linux) with
# EMSGSIZE; chunking here means a high partition/buffer fan-out can
# never hit it.  sysconf is authoritative where available.
try:
    _IOV_MAX = min(1024, os.sysconf("SC_IOV_MAX"))
except (AttributeError, OSError, ValueError):  # pragma: no cover
    _IOV_MAX = 1024


def _send_buffers(sock: socket.socket, buffers: Sequence) -> None:
    """``sendall`` a list of buffers with ``sendmsg`` scatter-gather —
    the kernel walks the iovec, no user-space concatenation.  Handles
    partial sends across buffer boundaries, and caps each ``sendmsg``
    at ``IOV_MAX`` buffers (beyond it the kernel fails with EMSGSIZE
    rather than sending partially)."""
    views = [memoryview(b).cast("B") for b in buffers if len(b)]
    while views:
        sent = sock.sendmsg(views[:_IOV_MAX])
        while views and sent >= len(views[0]):
            sent -= len(views[0])
            views.pop(0)
        if sent and views:
            views[0] = views[0][sent:]


def _decode_frame(sock: socket.socket):
    """Read one frame: ``(op, name, arr, payload, trace_id)``.  The
    trace id is b"" for unextended frames; an unknown extension version
    raises (loud, never a silent misread — the ``bpsc1`` discipline)."""
    op, nlen = struct.unpack("<BI", _recv_exact(sock, 5))
    if nlen > _MAX_NAME:
        raise ValueError(f"name too long: {nlen}")
    trace_id = b""
    if op & _EXT_FLAG:
        ver, elen = struct.unpack("<BB", _recv_exact(sock, 2))
        ext = bytes(_recv_exact(sock, elen))
        if ver != _EXT_VERSION:
            raise ValueError(
                f"unknown wire header extension version {ver} "
                f"(peer newer than this build?)")
        trace_id = ext[:_TRACE_ID_LEN]
        op &= ~_EXT_FLAG
    name = _recv_exact(sock, nlen).decode()
    (dlen,) = struct.unpack("<I", _recv_exact(sock, 4))
    dt = _recv_exact(sock, dlen).decode()
    (ndim,) = struct.unpack("<B", _recv_exact(sock, 1))
    shape = struct.unpack(f"<{ndim}Q", _recv_exact(sock, 8 * ndim)) if ndim else ()
    (plen,) = struct.unpack("<Q", _recv_exact(sock, 8))
    if plen > _MAX_PAYLOAD:
        raise ValueError(f"payload too large: {plen}")
    payload = _recv_exact(sock, plen) if plen else b""
    arr = None
    if dt:
        if dt.startswith(WIRE_MAGIC):
            # compressed frame: decompress here so both ends of the wire
            # (server request leg, client reply leg) see a dense array —
            # version/framing mismatches raise loudly in decode_blob
            arr = decode_blob(dt, bytes(payload), shape)
        else:
            arr = np.frombuffer(payload,
                                dtype=_wire_to_dtype(dt)).reshape(shape)
    return op, name, arr, payload, trace_id


def _decode(sock: socket.socket):
    """Legacy 4-tuple read (trace id dropped) — the shape every
    pre-extension call site expects."""
    op, name, arr, payload, _ = _decode_frame(sock)
    return op, name, arr, payload


# ----------------------------------------------------------- shard workers


class PendingRpc:
    """One submitted request: its frame buffers and the future its
    caller blocks on.  Settling (resolve/fail) is idempotent — kill
    paths and late receivers may race, first one wins.

    The three ``perf_counter`` stamps (submit/sent/reply) are the raw
    material for the client-queue and wire trace spans the store emits
    after ``wait`` (docs/observability.md) — noting times here keeps
    the I/O threads off the tracer entirely.  ``stamp=False`` (RPC
    tracing off) skips all three clock reads: they would only ever be
    read by ``_trace_part_spans``, which no-ops without a tracer."""

    __slots__ = ("buffers", "state", "done", "event", "error",
                 "status", "rname", "out", "payload", "_plock",
                 "t_submit", "t_sent", "t_reply")

    QUEUED, SENT = 0, 1

    def __init__(self, buffers: List, stamp: bool = False):
        self.buffers = buffers
        self.state = PendingRpc.QUEUED  # wire bookkeeping (worker lock)
        self.done = False               # settled flag (own lock)
        self.event = threading.Event()
        self.error: Optional[BaseException] = None
        self.status = self.rname = self.out = self.payload = None
        self._plock = threading.Lock()
        self.t_submit = time.perf_counter() if stamp else 0.0
        self.t_sent = 0.0
        self.t_reply = 0.0

    def _settle(self) -> bool:
        with self._plock:
            if self.done:
                return False
            self.done = True
            return True

    def resolve(self, status, rname, out, payload) -> None:
        if self._settle():
            if self.t_submit:
                self.t_reply = time.perf_counter()
            self.status, self.rname = status, rname
            self.out, self.payload = out, payload
            self.buffers = None  # free the request frame early
            self.event.set()

    def fail(self, err: BaseException) -> None:
        if self._settle():
            self.error = err
            self.buffers = None
            self.event.set()


class ShardWorker:
    """Per-shard I/O worker: priority send queue, bounded in-flight
    window, FIFO reply matching (module docstring has the contract).

    Threading shape — one sender + one receiver thread per shard
    connection.  A dedicated sender (rather than the submitting thread
    pumping its own frames) is load-bearing for throughput, not just
    tidiness: ``sendmsg`` of a large frame blocks at the pace the peer
    drains it, so a single caller pumping every shard's socket
    serializes the cluster's entire upload on one thread — measured as
    the whole pipelining win evaporating.  Per-shard senders stream to
    all shards concurrently (the GIL is released inside send/recv), and
    the caller's only per-frame costs are the enqueue and the reply
    event.  The receiver NEVER sends — a receiver blocked mid-
    ``sendmsg`` while the server is itself blocked sending us a large
    reply would deadlock both socket buffers.

    ``connect`` is a zero-arg callable returning a fresh connected
    socket — or anything duck-typing its blocking stream surface
    (engine/transport.py: the AF_UNIX and shared-memory-ring fast paths
    plug in here, with the window/FIFO/abort contract untouched by
    construction).  The RemoteStore supplies it so address/timeout/
    transport policy stays in one place; ``transport`` is the resolved
    transport kind, used only to label this shard's wire metrics.
    ``on_reset(exc, n_inflight)`` fires once per connection kill — the
    store bumps its reconnect/window counters there."""

    def __init__(self, connect: Callable[[], socket.socket], window: int,
                 shard: int = 0, recv_timeout: float = 30.0,
                 on_reset: Optional[Callable] = None,
                 transport: str = "tcp"):
        self._connect = connect
        self._window = max(1, int(window))
        self._shard = shard
        self._recv_timeout = recv_timeout
        self._on_reset = on_reset
        self._queue = ScheduledQueue(name=f"wire-shard{shard}")
        self._inflight: "collections.deque[PendingRpc]" = collections.deque()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)  # window-slot wakeups
        self._free = self._window  # un-acked window slots left (lock)
        self._sock: Optional[socket.socket] = None
        self._gen = 0  # connection generation; bumped on every kill
        self._closed = threading.Event()
        self._sender: Optional[threading.Thread] = None
        from ..observability.metrics import get_registry

        reg = get_registry()
        # live wire metrics (observability/metrics.py, global registry):
        # resolved once here — the send/recv loops must not pay a
        # registry lookup per frame.  All registry-only (mirror=False)
        # except window occupancy: these fire several times per frame on
        # the I/O threads, per-frame trace detail already comes from the
        # client-queue/wire spans, and mirroring every bump measurably
        # taxes the step — scrapes still see live values
        # byte/frame/reply counters carry the transport label so a
        # scrape can attribute wire volume to tcp vs the local fast
        # paths per shard (docs/wire.md "Transports")
        self._m_bytes = reg.counter("wire.bytes_sent", track="wire",
                                    instants=False, mirror=False,
                                    shard=shard, transport=transport)
        self._m_frames = reg.counter("wire.frames_sent", track="wire",
                                     instants=False, mirror=False,
                                     shard=shard, transport=transport)
        self._m_replies = reg.counter("wire.replies_received", track="wire",
                                      instants=False, mirror=False,
                                      shard=shard, transport=transport)
        self._m_inflight = reg.gauge("wire.inflight", track="wire",
                                     mirror=False, shard=shard)
        self._m_qdepth = reg.gauge("wire.queue_depth", track="wire",
                                   mirror=False, shard=shard)
        # window occupancy: in-flight / window, the live "is the wire
        # full" signal — the one wire series that stays on the chrome
        # trace (scripts/trace_report.py's window-stall histogram)
        self._m_occ = reg.gauge("wire.window_occupancy", track="wire",
                                shard=shard)
        # resolved once: whether frames get perf_counter stamps (three
        # clock reads per frame otherwise wasted — only
        # _trace_part_spans ever reads them, and it no-ops untraced)
        from ..observability.trace import rpc_tracing_enabled

        self._stamp = rpc_tracing_enabled()

    def _note_inflight_locked(self) -> None:
        """Caller holds ``_lock``; publishes the window state gauges."""
        used = self._window - self._free
        self._m_inflight.set(used)
        self._m_occ.set(used / self._window)

    # --------------------------------------------------------------- submit

    def submit(self, buffers: List, priority: int = 0,
               key: int = 0) -> PendingRpc:
        """Enqueue one request frame and pump the wire; returns its
        future.  Issue order is (priority desc, key asc) — the
        dispatcher's rule — with FIFO among equals (ScheduledQueue's
        insert is stable).  Never blocks on the window: frames beyond it
        stay queued until replies free slots."""
        if self._closed.is_set():
            raise ConnectionError(f"shard {self._shard} wire worker closed")
        pending = PendingRpc(buffers, stamp=self._stamp)
        task = TensorTaskEntry(name="", key=key, priority=priority,
                               payload=pending)
        self._ensure_sender()
        self._queue.add_task(task)
        self._m_qdepth.set(self._queue.pending())
        return pending

    def wait(self, pending: PendingRpc, timeout: Optional[float]):
        """Block on a submitted request.  A timeout ABORTS the request
        (see ``abort``) and raises ``socket.timeout`` so callers' retry
        machinery treats it like the serial client's socket timeout."""
        if not pending.event.wait(timeout):
            self.abort(pending, socket.timeout(
                f"shard {self._shard}: no reply within {timeout:.3f}s"))
            pending.event.wait()  # abort settles it synchronously
        if pending.error is not None:
            raise pending.error
        return pending.status, pending.rname, pending.out, pending.payload

    def abort(self, pending: PendingRpc, err: BaseException) -> None:
        """Give up on one request.  Queued-and-unsent: just cancel it
        (the sender skips settled pendings).  Already on the wire: the
        connection must die with it — FIFO matching cannot skip one
        reply — which fails the rest of the window into their own
        retries, exactly like a peer reset would."""
        with self._lock:
            sent = pending.state == PendingRpc.SENT
            gen = self._gen
        if sent:
            self._kill(gen, err)
        pending.fail(err)  # idempotent; no-op if the kill settled it

    # ------------------------------------------------------------ send loop

    def _ensure_sender(self) -> None:
        if self._sender is None:
            with self._lock:
                if self._sender is None and not self._closed.is_set():
                    t = threading.Thread(
                        target=self._send_loop,
                        name=f"bps-wire-send-{self._shard}", daemon=True)
                    self._sender = t
                    t.start()

    def _send_loop(self) -> None:
        """Drain the priority queue onto the wire, window-gated.  The
        window check blocks on the cv (receiver notifies per freed
        slot); the queue wait blocks on the queue's own cv — both with
        short timeouts so close() is prompt."""
        while not self._closed.is_set():
            with self._cv:
                if self._free <= 0:
                    # window full: wait for the receiver to free a slot
                    # (only this thread ever decrements _free, so the
                    # re-check after wake is race-free)
                    self._cv.wait(0.25)
                    continue
            task = self._queue.wait_task(timeout=0.25)
            if task is None:
                continue
            pending: PendingRpc = task.payload
            if pending.done:  # aborted while queued
                continue
            try:
                sock, gen = self._ensure_sock()
            except OSError as e:
                pending.fail(e)
                continue
            with self._lock:
                if gen != self._gen:
                    # connection died between connect and here; fail this
                    # request into its caller's retry loop
                    pending.fail(ConnectionError("connection reset"))
                    continue
                # snapshot the buffer list BEFORE committing to send: a
                # concurrent abort/kill fail()s the pending under its
                # own lock (not ours) and nulls .buffers — reading once
                # and checking None closes that race; sending from the
                # local reference stays valid even if the fail lands
                # just after (a doomed frame at worst raises OSError on
                # the already-closed socket below)
                bufs = pending.buffers
                if pending.done or bufs is None:
                    continue  # aborted between dequeue and here
                pending.state = PendingRpc.SENT
                if pending.t_submit:
                    pending.t_sent = time.perf_counter()
                self._inflight.append(pending)
                self._free -= 1
                self._note_inflight_locked()
            nbytes = sum(len(b) for b in bufs)
            try:
                _send_buffers(sock, bufs)
            except OSError as e:
                self._kill(gen, e)  # drains in-flight (incl. this frame)
            else:
                self._m_bytes.inc(nbytes)
                self._m_frames.inc()
                self._m_qdepth.set(self._queue.pending())
        # worker closing: everything still queued fails loudly
        for task in self._queue.drain():
            task.payload.fail(ConnectionError("wire worker closed"))

    # --------------------------------------------------------------- loops

    def _ensure_sock(self) -> Tuple[socket.socket, int]:
        """Sender-thread only: connect lazily, spawn the paired
        receiver."""
        with self._lock:
            if self._sock is not None:
                return self._sock, self._gen
        sock = self._connect()
        sock.settimeout(self._recv_timeout)
        with self._lock:
            self._sock = sock
            gen = self._gen
        threading.Thread(target=self._recv_loop, args=(sock, gen),
                         name=f"bps-wire-recv-{self._shard}",
                         daemon=True).start()
        return sock, gen

    def _recv_loop(self, sock: socket.socket, gen: int) -> None:
        while True:
            try:
                status, rname, out, payload = _decode(sock)
            except socket.timeout:
                with self._lock:
                    stale = gen != self._gen
                    hung = bool(self._inflight)
                if stale:
                    return
                if hung:
                    # un-acked requests older than the socket timeout: a
                    # hung (not crashed) shard — same poisoned-socket
                    # treatment as the serial client's settimeout
                    self._kill(gen, socket.timeout(
                        f"shard {self._shard} stalled mid-window"))
                    return
                continue  # idle connection; keep listening
            except Exception as e:
                self._kill(gen, e if isinstance(e, (OSError, ValueError,
                                                    struct.error))
                           else ConnectionError(str(e)))
                return
            with self._cv:
                if gen != self._gen:
                    return  # replaced connection; a fresh receiver owns it
                if not self._inflight:
                    break  # reply with no request: protocol violation
                pending = self._inflight.popleft()
                self._free += 1
                self._note_inflight_locked()
                self._cv.notify()  # wake a window-gated sender
            self._m_replies.inc()
            pending.resolve(status, rname, out, payload)
        self._kill(gen, ValueError(
            f"shard {self._shard}: reply with no request in flight"))

    def _kill(self, gen: int, err: BaseException) -> None:
        """Tear down one connection generation: close the socket, fail
        every un-acked in-flight request (each re-enters its caller's
        retry machinery), leave queued-but-unsent requests for the next
        connection.  Idempotent per generation."""
        with self._cv:
            if gen != self._gen:
                return
            self._gen += 1
            sock, self._sock = self._sock, None
            victims = list(self._inflight)
            self._inflight.clear()
            self._free += len(victims)
            self._note_inflight_locked()
            self._cv.notify()
        if sock is not None:
            # shutdown() BEFORE close(): closing an fd another thread is
            # blocked recv-ing on does not reliably wake it (it can sit
            # out the full socket timeout); SHUT_RDWR interrupts the
            # receiver immediately so the thread exits with the kill
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        for p in victims:
            p.fail(err)
        if self._on_reset is not None and sock is not None:
            self._on_reset(err, len(victims))
        if victims:
            bps_log.debug("wire shard %d: reset failed %d in-flight (%s)",
                          self._shard, len(victims), err)

    # --------------------------------------------------------------- admin

    def drop_connection(self, err: Optional[BaseException] = None) -> None:
        """External poison request (heartbeat declared the shard down):
        kill the current connection, failing its window."""
        with self._lock:
            gen = self._gen
            has_sock = self._sock is not None
        if has_sock:
            self._kill(gen, err or ConnectionError("shard marked down"))

    def close(self) -> None:
        self._closed.set()
        self._queue.close()
        self.drop_connection(ConnectionError("wire worker closed"))
        with self._cv:
            self._cv.notify_all()
        sender = self._sender
        if sender is not None:
            sender.join(timeout=2.0)
        # the sender drains the queue on exit; if it never started (no
        # traffic) or died, fail any stragglers here
        for task in self._queue.drain():
            task.payload.fail(ConnectionError("wire worker closed"))
