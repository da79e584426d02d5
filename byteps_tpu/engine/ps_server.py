"""TCP parameter-server tier — the ps-lite / MXNet-KVStore-server analog.

The reference's inter-machine transport is ps-lite ``ZPush``/``ZPull`` over
ZeroMQ/RDMA to CPU server processes that sum gradients (SURVEY.md §1;
core_loops.cc:430-502 on the worker side, the bytedance MXNet server on the
other end, launched by ``launcher/launch.py:62-64``).  The synchronous path
does not need this tier on TPU (DCN collectives are strictly better), but
the **asynchronous** mode is genuinely off the SPMD path and does: workers
push weight deltas and pull global state at their own cadence, which is a
client/server interaction, not a collective.

This module provides that tier natively:

  * ``serve()`` — a threaded TCP server owning one ``AsyncParameterServer``
    shard; summation runs through the native OpenMP reducer when built.
    Started by the launcher under ``DMLC_ROLE=server`` (the same role that
    started the MXNet KVStore in the reference).
  * ``RemoteStore`` — the worker-side client: same duck-typed interface as
    the in-process stores (init_tensor/push_delta/pull/push_pull/version/
    names), placing each tensor on a server with the reference's
    key->server formula (global.cc:305-334).

Wire protocol (binary, length-prefixed; one request per round-trip):

    request :=  u8 op | u32 len(name) | name
               | u32 len(dtype) | dtype-str | u8 ndim | u64*ndim shape
               | u64 len(payload) | payload-bytes
    reply   :=  u8 status | <tensor encoded as above, name "">

Ops: 0=INIT (first-push-wins), 1=PUSH_PULL (atomic add+read),
2=PULL, 3=VERSION (payload = u64), 4=NAMES (payload = '\n'.join),
5=PING, 6=PUSH (delta add, status-only reply — no tensor download),
7=SET (force-overwrite — the failover/failback re-seed op: unlike
INIT's first-push-wins it replaces a tensor a shard already holds, so
a stale leftover copy can never shadow the authoritative state).
No pickling — payloads are raw ``numpy`` buffers, like ps-lite's zero-copy
char views.  Store-level errors come back as status=1 replies with the
message in the payload; the connection survives.

Replies to the versioned mutations (INIT, SET, PUSH, PUSH_PULL) carry the
post-op version counter as a decimal string in the otherwise-unused
reply ``name`` field.  ``RemoteStore`` records it per tensor so that a
retried mutation whose first reply was lost mid-connection can ask
``OP_VERSION`` whether the server already applied it (exactly-once under
connection resets for a single writer per key — see
resilience/policy.py and docs/resilience.md).

Compressed payloads (byteps_tpu/compression — docs/compression.md) ride
the same frame under the versioned dtype tag ``"bpsc1"``: the payload is
a scheme-tagged blob (scheme name + ctx + data) instead of raw numpy
bytes, while the frame's shape field keeps the original dimensions.  The
server decompresses at decode time and sums the dense fp32 result into
the store; replies (PULL / PUSH_PULL / INIT-loser) are cast-compressed
per ``BYTEPS_COMPRESSION_REPLY``.  A peer that predates the subsystem
fails loudly on the unknown dtype name — never a silent misread.
``RemoteStore`` additionally partitions tensors larger than
``BYTEPS_PARTITION_BYTES`` into independently keyed ``name#p{i}`` parts
(reference PartitionTensor, operations.cc:95-132) so compression,
version-guarded retries and shard placement all happen per partition.

Pipelined client (byteps_tpu/engine/wire.py — docs/wire.md): with
``BYTEPS_WIRE_WINDOW`` > 0 (default 8) every shard gets a send/receive
I/O worker with a bounded in-flight request window and FIFO reply
matching, and multi-partition ops fan their parts out concurrently
across shards in ``ScheduledQueue`` priority order — the client half of
the paper's keep-the-wire-busy architecture.  ``BYTEPS_WIRE_WINDOW=0``
restores the serial one-frame-in-flight client (the A/B baseline).

Endpoint transports (byteps_tpu/engine/transport.py — docs/wire.md
"Transports"): the server listens on TCP and, unless
``BYTEPS_TRANSPORT=tcp``, additionally advertises an AF_UNIX socket and
a shared-memory-ring rendezvous keyed by its port (the
``BytePSSharedMemory`` / ``BytePSCommSocket`` analog).  ``RemoteStore``
resolves a transport per endpoint (``auto``: the local fast path for
colocated shards, TCP otherwise) and consumes it only through the
duck-socket interface, so the window/FIFO/retry/failover machinery is
transport-independent by construction.
"""

from __future__ import annotations

import json
import socket
import socketserver
import struct
import threading
import time
from contextlib import contextmanager
from typing import List, Optional

import numpy as np

from ..common import logging as bps_log
from ..common.context import name_key
from ..common.tracing import get_tracer
from ..compression.wire import WireBlob  # noqa: F401  (re-export compat)
from .async_ps import AsyncParameterServer
# framing codec + pipeline live in engine/wire.py; re-exported here
# because the chaos proxy, the serving frontend and tests import them
# from this module (one wire framing, one reader)
from . import hierarchical as hier
from .transport import (LocalEndpoints, connection_kind, maybe_nodelay,
                        parse_overrides, peer_label, resolve_transport,
                        transport_connect)
from .wire import (ShardWorker, _decode, _decode_frame,  # noqa: F401
                   _dtype_to_wire, _encode, _encode_buffers, _recv_exact,
                   _send_buffers, _wire_to_dtype, hard_reset)

(OP_INIT, OP_PUSH_PULL, OP_PULL, OP_VERSION, OP_NAMES, OP_PING, OP_PUSH,
 OP_SET, OP_STATS) = range(9)


# -------------------------------------------------------------------- server


_PROFILED_OPS = {OP_PUSH: "push", OP_PULL: "pull", OP_PUSH_PULL: "push_pull"}


class ServerProfiler:
    """Per-key request timeline on the PS tier — the reference's
    straggler-hunting tool (``BYTEPS_SERVER_ENABLE_PROFILE``,
    /root/reference/docs/timeline.md:1-30): each push/pull request emits
    chrome-trace ``B``/``E`` events spanning arrival to completion, with
    the tensor's declared key as pid/tid and the requesting peer in the
    event name — load ``server_profile.json`` in chrome://tracing and a
    slow shard or a consistently-late worker is visible per key.

    Env knobs (byteps-compatible): ``BYTEPS_SERVER_ENABLE_PROFILE=1``,
    ``BYTEPS_SERVER_PROFILE_OUTPUT_PATH=/path.json``,
    ``BYTEPS_SERVER_KEY_TO_PROFILE=<key>`` (restrict to one key).
    """

    _AUTOFLUSH = 4096  # events buffered before an automatic flush

    def __init__(self, path: str, key_filter: Optional[int] = None):
        self._path = path
        self._key_filter = key_filter
        self._events: List[dict] = []
        self._lock = threading.Lock()        # guards the event buffer
        self._io_lock = threading.Lock()     # serializes file appends
        self._written = False  # file has an opening '[' + >=1 event
        self._closed = False
        # chrome-trace ts must be monotonic: wall-clock steps (NTP) can
        # emit out-of-order or negative-duration B/E spans, so callers
        # stamp with time.perf_counter() and this fixed epoch maps the
        # values onto the wall clock once
        self._epoch = time.time() - time.perf_counter()

    def record(self, op: int, name: str, peer: str, t_begin: float,
               t_end: float, trace_id: str = "") -> None:
        opname = _PROFILED_OPS.get(op)
        if opname is None:
            return
        key = name_key(name)
        if self._key_filter is not None and key != self._key_filter:
            return
        ev = f"{opname}-{peer}"
        # the trace id (wire header extension, docs/observability.md) is
        # the join key trace_merge correlates this server span with the
        # issuing client's client-queue/wire spans on
        args = {"tensor": name}
        if trace_id:
            args["trace_id"] = trace_id
        b = {"name": ev, "ph": "B", "pid": key, "tid": key,
             "ts": int((self._epoch + t_begin) * 1e6), "args": args}
        e = {"name": ev, "ph": "E", "pid": key, "tid": key,
             "ts": int((self._epoch + t_end) * 1e6)}
        drained = None
        dropped = False
        with self._lock:
            if self._closed:
                # a record() after close() would buffer events nothing
                # will ever drain (the file's array is already
                # terminated) — drop them as loudly as _write() drops a
                # batch that raced close()
                dropped = True
            else:
                self._events.append(b)
                self._events.append(e)
                if len(self._events) >= self._AUTOFLUSH:
                    # swap the buffer out under the lock, write OUTSIDE
                    # it — the request that trips the threshold must not
                    # stall every concurrent handler behind file I/O
                    drained, self._events = self._events, []
        if dropped:
            bps_log.debug(
                "ps_server profiler: dropping 2 events recorded after "
                "close()")
            return
        if drained:
            self._write(drained)

    def _append_locked(self, events: List[dict]) -> None:
        """Append events to the JSON array on disk.  Caller must hold
        ``_io_lock`` — the '['/',' separator protocol and ``_written``
        bookkeeping live only here so every append path shares them."""
        import json

        mode = "a" if self._written else "w"
        with open(self._path, mode) as f:
            for ev in events:
                f.write(("[\n" if not self._written else ",\n")
                        + json.dumps(ev))
                self._written = True

    def _write(self, events: List[dict]) -> None:
        """Append drained events to the file (``_io_lock`` serializes
        concurrent drains so appends stay ordered).  Flushes are O(new
        events), never a rewrite of history, and the file is a
        chrome-trace JSON array kept loadable mid-run by the viewer's
        documented leniency about a missing closing bracket; ``close()``
        terminates it properly."""
        with self._io_lock:
            if self._closed:
                # a record() thread swapped its batch out just as
                # close() terminated the array — appending now would
                # write past the closing ']' and corrupt the strict
                # JSON close() promises; drop the stragglers
                bps_log.debug(
                    "ps_server profiler: dropping %d events raced "
                    "against close()", len(events))
                return
            self._append_locked(events)
        bps_log.debug("ps_server profiler: +%d events -> %s",
                      len(events), self._path)

    def flush(self) -> None:
        with self._lock:
            events, self._events = self._events, []
        if events:
            self._write(events)

    def close(self) -> None:
        """Drain and terminate the JSON array (valid strict JSON)."""
        self.flush()
        with self._io_lock:
            # last-chance drain INSIDE the io lock: a record() batch
            # appended after flush()'s swap (too small to trip the
            # autoflush) would otherwise stay buffered forever with no
            # drop log — write it before terminating the array.  The
            # _closed flag is set under BOTH locks: record() checks it
            # under _lock, so flipping it inside this _lock hold closes
            # the window where a record() racing close() passed the
            # check and buffered events AFTER the straggler swap —
            # silently burying them with no drop log (the TOCTOU the
            # lock-discipline lint flagged here); _write() still checks
            # under _io_lock, which close() also holds
            with self._lock:
                self._closed = True
                stragglers, self._events = self._events, []
            if stragglers:
                self._append_locked(stragglers)
            if self._written:
                with open(self._path, "a") as f:
                    f.write("\n]\n")
                self._written = False


class _Handler(socketserver.BaseRequestHandler):
    """One connection, many requests — strictly FIFO: each request is
    fully served and its reply sent before the next is read.  The
    pipelined client RELIES on this order to match replies to requests
    without protocol tags (docs/wire.md); a future concurrent-handler
    server must bump the protocol to tagged frames first."""

    def handle(self):  # one connection, many requests
        store: AsyncParameterServer = self.server.store  # type: ignore[attr-defined]
        profiler: Optional[ServerProfiler] = getattr(
            self.server, "profiler", None)
        # reply-leg cast compression (BYTEPS_COMPRESSION_REPLY): identity
        # unless configured; biased schemes are refused inside the helper
        reply_c = getattr(self.server, "reply_compress", lambda a: a)
        peer = peer_label(self.client_address)
        sock = self.request
        maybe_nodelay(sock)
        self.server.track_connection(sock)  # type: ignore[attr-defined]
        # live request accounting (process registry — what OP_STATS and
        # /metrics serve); metric objects resolved once per connection
        from ..observability.metrics import get_registry

        _reg = get_registry()
        # registry-only (mirror=False): per-request trace detail is the
        # profiler's job; a counter event per request would tax the
        # handler loop for a redundant series
        m_reqs = _reg.counter("ps.requests", track="ps_server",
                              instants=False, mirror=False)
        m_errs = _reg.counter("ps.request_errors", track="ps_server",
                              instants=False, mirror=False)
        # per-transport RPC attribution (tcp vs the unix/shm fast
        # paths) — the server twin of the client's labeled wire.* series
        m_treqs = _reg.counter("ps.requests_by_transport",
                               track="ps_server", instants=False,
                               mirror=False,
                               transport=connection_kind(sock))
        m_handle = _reg.histogram("ps.handle_s", track="ps_server")
        try:
            while True:
                try:
                    op, name, arr, _, tid = _decode_frame(sock)
                except ConnectionError:
                    return
                t_begin = time.perf_counter()
                failed = False
                # store-level errors (e.g. pull of an un-init'd name) reply
                # status=1 and keep the connection alive — only wire-level
                # failures tear it down
                # replies are built as buffer lists and sent with
                # sendmsg scatter-gather: a multi-MB PULL reply goes out
                # as header + a zero-copy view of the store's array
                try:
                    if op == OP_INIT:
                        # a first-push-wins LOSER gets the winning value
                        # in the reply (clients seed failover state from
                        # it); the creator gets a bare ack — its own seed
                        # IS the value, echoing the tensor back would be
                        # a pointless full-model transfer at startup
                        info = getattr(store, "init_tensor_info", None)
                        if info is not None:
                            v, created = info(name, arr)
                        else:  # duck-typed store: echo to be safe
                            v = store.init_tensor(name, arr)
                            if v is None:
                                v = store.version(name)
                            created = False
                        reply = _encode_buffers(
                            0, str(v),
                            None if created else reply_c(store.pull(name)))
                    elif op == OP_PUSH_PULL:
                        # version must be read under the same lock as the
                        # add, or a concurrent mutation's counter gets
                        # attributed to this op (dedup-baseline poison)
                        pv = getattr(store, "push_pull_versioned", None)
                        if pv is not None:
                            out, v = pv(name, arr)
                        else:
                            out = store.push_pull(name, arr)
                            v = store.version(name)
                        reply = _encode_buffers(0, str(v), reply_c(out))
                    elif op == OP_PUSH:
                        v = store.push_delta(name, arr)
                        if v is None:
                            v = store.version(name)
                        reply = _encode_buffers(0, str(v), None)
                    elif op == OP_SET:
                        v = store.set_tensor(name, arr)
                        if v is None:
                            v = store.version(name)
                        reply = _encode_buffers(0, str(v), None)
                    elif op == OP_PULL:
                        reply = _encode_buffers(0, "", reply_c(store.pull(name)))
                    elif op == OP_VERSION:
                        reply = _encode_buffers(0, "", None,
                                        struct.pack("<Q", store.version(name)))
                    elif op == OP_NAMES:
                        reply = _encode_buffers(0, "", None,
                                        "\n".join(store.names()).encode())
                    elif op == OP_PING:
                        # the reply carries this host's wall clock so
                        # clients can estimate per-shard clock offsets
                        # NTP-style (observability/trace.py); pre-PR-6
                        # clients ignore the payload
                        reply = _encode_buffers(0, "", None,
                                                struct.pack("<d", time.time()))
                    elif op == OP_STATS:
                        # live stats scrape over the existing binary
                        # protocol — the in-band twin of the HTTP
                        # /metrics endpoint (docs/observability.md)
                        payload = json.dumps(
                            self.server.stats_payload())  # type: ignore[attr-defined]
                        reply = _encode_buffers(0, "", None, payload.encode())
                    else:
                        reply = _encode_buffers(1, "", None, f"bad op {op}".encode())
                except Exception as e:
                    failed = True
                    reply = _encode_buffers(
                        1, "", None, f"{type(e).__name__}: {e}".encode()
                    )
                t_end = time.perf_counter()
                m_reqs.inc()
                m_treqs.inc()
                if failed:
                    m_errs.inc()
                if op in _PROFILED_OPS:
                    m_handle.observe(t_end - t_begin)
                if profiler is not None:
                    profiler.record(op, name, peer, t_begin, t_end,
                                    trace_id=tid.hex() if tid else "")
                _send_buffers(sock, reply)
        except Exception as e:  # pragma: no cover - connection teardown races
            bps_log.debug("ps_server handler exit: %s", e)
        finally:
            self.server.untrack_connection(sock)  # type: ignore[attr-defined]


class PSServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, addr, use_native: bool = True):
        super().__init__(addr, _Handler)
        # anything failing after the super() bind must release the
        # listening socket, or a supervised restart (launcher
        # BYTEPS_SERVER_MAX_RESTARTS) hits EADDRINUSE on the same port
        # for the rest of its budget
        try:
            self.profiler: Optional[ServerProfiler] = None
            self._t0 = time.monotonic()
            self.store = AsyncParameterServer(use_native=use_native)
            # live client connections, so kill() can sever them the way a
            # dying process would (shutdown() alone only stops the accept
            # loop; per-connection daemon threads keep serving)
            self._conns: set = set()
            self._conns_lock = threading.Lock()
            self.local_endpoints: Optional[LocalEndpoints] = None
            from ..common.config import get_config

            cfg = get_config()
            if cfg.transport != "tcp":
                # advertise the colocated fast paths (UDS + shm
                # rendezvous keyed by this TCP port); a client's
                # BYTEPS_TRANSPORT=auto finds them via the shared path
                # convention (engine/transport.py).  An overlong
                # rendezvous path raises (loud, names the path); any
                # other bind failure degrades to TCP-only with a
                # warning — a shard must not die because /tmp is odd.
                try:
                    self.local_endpoints = LocalEndpoints(
                        self.server_address[1], _Handler, self)
                except ValueError:
                    raise
                except OSError as e:
                    bps_log.warning(
                        "ps_server: local transport endpoints "
                        "unavailable (%s); serving TCP only", e)
            if cfg.compression_reply:
                from ..compression.wire import maybe_compress_reply

                self.reply_compress = (
                    lambda a, _s=cfg.compression_reply,
                    _m=cfg.compression_min_bytes:
                    maybe_compress_reply(a, _s, _m))
                bps_log.info("ps_server: reply compression -> %s",
                             cfg.compression_reply)
            if cfg.server_enable_profile:
                self.profiler = ServerProfiler(
                    cfg.server_profile_output_path, cfg.server_key_to_profile)
                bps_log.info("ps_server: per-key profiling on -> %s",
                             cfg.server_profile_output_path)
        except Exception:
            super().server_close()
            raise

    def stats_payload(self) -> dict:
        """The ``OP_STATS`` reply body: shard identity + the process
        metrics-registry snapshot (same bytes ``/metrics.json``
        serves)."""
        from ..observability.metrics import get_registry

        return {
            "role": "ps_server",
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "tensors": len(self.store.names()),
            "local_endpoints": (list(self.local_endpoints.kinds)
                                if self.local_endpoints is not None
                                else []),
            "metrics": get_registry().snapshot(),
        }

    def track_connection(self, sock) -> None:
        with self._conns_lock:
            self._conns.add(sock)

    def untrack_connection(self, sock) -> None:
        with self._conns_lock:
            self._conns.discard(sock)

    def kill(self) -> None:
        """Die like a crashed process: stop accepting AND sever every
        live client connection (clients see a reset, not a quiet stall).
        Used by chaos tests and the restart-supervision story — a plain
        ``shutdown()`` leaves per-connection threads serving, which no
        real shard death does.  Local endpoints stop accepting but
        their rendezvous FILES stay behind, exactly like a SIGKILLed
        shard's would — the next bind (supervised restart) cleans them
        up, and clients probing a dead rendezvous fall back to TCP."""
        self.shutdown()
        if self.local_endpoints is not None:
            self.local_endpoints.close(unlink=False)
        with self._conns_lock:
            conns, self._conns = set(self._conns), set()
        for c in conns:
            hard_reset(c)
        self.server_close()

    def server_close(self):
        if getattr(self, "local_endpoints", None) is not None:
            self.local_endpoints.close()  # idempotent; kill() won
        if self.profiler is not None:
            self.profiler.close()
        super().server_close()


def serve(port: int, host: str = "0.0.0.0", use_native: bool = True,
          in_thread: bool = False):
    """Run one PS shard.  ``in_thread=True`` returns (server, thread) for
    tests; otherwise blocks forever (the launcher's server role)."""
    srv = PSServer((host, port), use_native=use_native)
    bps_log.info("byteps_tpu PS server shard listening on %s:%d",
                 host, srv.server_address[1])
    # live scrape endpoint (BYTEPS_METRICS_PORT; off by default) — the
    # HTTP twin of OP_STATS for operators without a wire client handy
    from ..observability.scrape import maybe_start_metrics_server

    maybe_start_metrics_server(
        role="ps_server",
        health_fn=lambda: {"tensors": len(srv.store.names())})
    if in_thread:
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        return srv, t
    try:
        srv.serve_forever()
    except KeyboardInterrupt:  # pragma: no cover
        pass
    finally:
        srv.server_close()


# -------------------------------------------------------------------- client


# wire-level failures (vs store-level status=1 replies, which are final):
# ConnectionError ⊂ OSError; ValueError/struct.error = corrupt framing
_WIRE_ERRORS = (OSError, ValueError, struct.error)


class RemoteStore:
    """Worker-side client over >=1 PS server shards.

    Tensor -> server placement uses the declared-key formula of reference
    global.cc:305-334 so a cluster's key distribution matches the
    reference's load-balance behavior byte for byte.

    Failure semantics (byteps_tpu addition — the reference dies with
    ps-lite on any server fault; docs/resilience.md):

      * wire-level failures retry under ``RetryPolicy`` (exponential
        backoff + jitter, per-op deadline) instead of raising on the
        first ``OSError``; a retried PUSH/PUSH_PULL is version-guarded
        via ``OP_VERSION`` so a mutation whose reply was lost is not
        double-applied (exactly-once per key for a single writer);
      * with >1 shards and ``BYTEPS_FAILOVER`` on (default), a shard
        that exhausts its retries is marked down and its keys re-route
        to the deterministic next alive shard, re-initialized there from
        this client's last-seen global state (degraded mode);
      * a heartbeat ``FailureDetector`` (``BYTEPS_HEARTBEAT_INTERVAL_MS``
        or auto-started on first failover) watches the dead shard; when
        it answers ``OP_PING`` again, failed-over keys migrate back
        (pull latest from the fallback, re-init the restarted shard).

    Wire compression (byteps_tpu/compression — docs/compression.md):
    PUSH / PUSH_PULL deltas are compressed per the policy
    (``BYTEPS_COMPRESSION`` or the ``compression=`` argument); biased
    schemes run under client-side error feedback whose residual is
    committed only AFTER the version-guarded ack, so a replayed PUSH
    resends the exact same compressed bytes and never double-folds the
    residual.  Tensors above ``BYTEPS_PARTITION_BYTES`` are split into
    independently keyed ``name#p{i}`` partitions (compressed, retried
    and placed per partition; ``names()`` lists partition names).
    Partitioned tensors must be init'd or pushed through this client
    before ``pull``/``version`` can reassemble them.

    Hierarchical slicing (engine/hierarchical.py — docs/wire.md
    "Hierarchical reduction"): with ``BYTEPS_HIERARCHICAL`` (or
    ``hierarchical=True``) every eligible mutation is split into
    ``local_size`` slice keys ``name@s{r}`` *above* the partition layer
    — slices compress, version-guard, fail over and carry error-feedback
    residuals independently (they are ordinary wire names), and all
    slices of one op fan out through a single pipelined window pass.
    0-d scalars and tensors under ``BYTEPS_HIERARCHICAL_MIN_BYTES`` pass
    through unsliced.  ``push_pull_slices``/``init_slices`` expose the
    per-rank entry points the group-level exchange
    (``hierarchical.hierarchical_push_pull``) pushes single slices
    through.
    """

    def __init__(self, addrs: List[str], use_hash: bool = False,
                 timeout: float = 30.0, retry_policy=None, counters=None,
                 heartbeat: Optional[float] = None, compression=None,
                 wire_window: Optional[int] = None, transport=None,
                 hierarchical: Optional[bool] = None,
                 local_size: Optional[int] = None):
        from ..common.config import get_config
        from ..common.context import ServerSharder
        from ..compression import (CompressionPolicy, WireCompressor,
                                   get_compression_stats)
        from ..resilience import (DegradedModeRouter, RetryPolicy,
                                  get_counters)
        from ..resilience import counters as cn

        if not addrs:
            raise ValueError("RemoteStore needs at least one server address")
        cfg = get_config()
        self._addrs = list(addrs)
        # per-endpoint transport resolution (engine/transport.py):
        # ``transport=`` (str spec, or {addr: spec} dict) beats
        # BYTEPS_TRANSPORT_OVERRIDES beats BYTEPS_TRANSPORT.  ``auto``
        # resolves ONCE here (probing the rendezvous), so every
        # reconnect of a shard stays on the transport its first
        # connection chose — failover must not flip transports mid-run.
        per_addr = dict(transport) if isinstance(transport, dict) else {}
        base_spec = (transport if isinstance(transport, str) and transport
                     else cfg.transport)
        env_over = parse_overrides(cfg.transport_overrides)
        self._tspec = [
            resolve_transport(a, per_addr.get(a, env_over.get(a, base_spec)))
            for a in addrs
        ]
        self._transports = [k for k, _ in self._tspec]
        self._sharder = ServerSharder(len(addrs), use_hash=use_hash)
        self._socks: List[Optional[socket.socket]] = [None] * len(addrs)
        self._locks = [threading.Lock() for _ in addrs]
        self._timeout = timeout
        self._cn = cn
        self._policy = (retry_policy if retry_policy is not None
                        else RetryPolicy.from_config(cfg))
        self._counters = counters if counters is not None else get_counters()
        self._failover_enabled = cfg.failover and len(addrs) > 1
        # version-guarded retry dedup assumes a single writer per key;
        # with several workers pushing the same keys the counter is
        # ambiguous and suppressing a resend silently DROPS a delta —
        # worse than the at-least-once double-apply async-PS tolerates.
        # Auto: on only for single-worker clusters; BYTEPS_RETRY_VERSION_GUARD
        # overrides either way.
        self._version_guard = (cfg.retry_version_guard
                               if cfg.retry_version_guard is not None
                               else cfg.num_worker <= 1)
        self._router = DegradedModeRouter(len(addrs),
                                          counters=self._counters)
        # serializes degraded-mode ops against recovery migration (held
        # across fallback network I/O — degraded-mode correctness over
        # degraded-mode latency); healthy-shard ops never take it
        self._failover_lock = threading.RLock()
        # guards _last_global/_pushed_version — held only for dict ops,
        # never across I/O (RLock: nested paths)
        self._state_lock = threading.RLock()
        self._last_global: dict = {}      # name -> last seen global value
        # (name, shard) -> that SHARD's version counter after our last
        # acknowledged mutation there.  Keyed per shard: during a
        # failover episode the same name has independent counters on the
        # primary and the fallback, and comparing across them would
        # corrupt the retry-dedup decision.
        self._pushed_version: dict = {}
        # wire compression: explicit policy object > scheme-name string >
        # env config; stats go to the process-global track so every
        # client's bytes land on one Tracer timeline
        if isinstance(compression, CompressionPolicy):
            policy = compression
        elif compression is not None:
            policy = CompressionPolicy(
                default=compression,
                min_bytes=cfg.compression_min_bytes,
                overrides=cfg.compression_overrides,
                ratio=cfg.compression_ratio,
                seed=cfg.compression_seed)
        else:
            policy = CompressionPolicy.from_config(cfg)
        self._wire_stats = get_compression_stats()
        self._compressor = WireCompressor(policy, stats=self._wire_stats)
        # distributed per-RPC tracing (docs/observability.md): when on,
        # public ops mint an 8-byte trace id, every frame of the op
        # carries it in the wire-header extension, and the client emits
        # client-queue/wire spans stamped with it
        from ..observability.trace import rpc_tracing_enabled

        self._trace_rpc = rpc_tracing_enabled(cfg)
        self._partition_bytes = cfg.effective_partition_bytes
        self._part_meta: dict = {}  # base name -> (nparts, shape, dtype)
        # hierarchical slicing (docs/wire.md "Hierarchical reduction"):
        # eligible tensors split into local_size slice keys name@s{r}
        # above the partition layer.  local_size resolution: explicit
        # argument > launcher-injected BYTEPS_LOCAL_SIZE > the process's
        # device count (the reference's GPU-count analog).
        self._hier = (cfg.hierarchical if hierarchical is None
                      else bool(hierarchical))
        self._hier_min = max(1, cfg.hierarchical_min_bytes)
        if local_size is not None:
            self._hier_L = max(1, int(local_size))
        elif cfg.local_size is not None:
            self._hier_L = max(1, int(cfg.local_size))
        elif self._hier:
            import jax

            self._hier_L = max(1, jax.local_device_count())
        else:
            self._hier_L = 1
        self._hier_meta: dict = {}  # base name -> (nslices, shape, dtype)
        # failover/restart seed cache (_last_global).  Off when the user
        # disabled BYTEPS_FAILOVER outright: the snapshots exist purely
        # to re-seed shards, so keeping multi-MB copies of every reply
        # would be pure overhead (restart re-seeding is then off too).
        self._seed_enabled = cfg.failover
        # name -> issue priority (reference tensorflow/ops.cc:158:
        # earlier-declared = higher priority, so the first-needed tensor
        # wins the wire under the per-shard ScheduledQueue)
        self._prio: dict = {}
        # pipelined wire engine (docs/wire.md): per-shard I/O workers
        # with a bounded in-flight window; multi-part ops submit up to
        # _fanout parts ahead of the gather.  window=0 = serial legacy
        # client (the A/B baseline).
        self._window = (cfg.wire_window if wire_window is None
                        else int(wire_window))
        self._fanout = max(1, cfg.wire_fanout)
        self._workers: Optional[List[ShardWorker]] = None
        if self._window > 0:
            self._workers = [
                ShardWorker(
                    (lambda i=i: self._connect(i)), self._window, shard=i,
                    recv_timeout=self._timeout,
                    on_reset=(lambda err, n, i=i: self._on_wire_reset(i, n)),
                    transport=self._transports[i])
                for i in range(len(addrs))
            ]
        self._hb_interval = cfg.heartbeat_interval_ms / 1e3
        self._hb_timeout = cfg.heartbeat_timeout_ms / 1e3
        self._hb_threshold = cfg.heartbeat_miss_threshold
        self._detector = None
        hb = self._hb_interval if heartbeat is None else heartbeat
        if hb and hb > 0:
            self._start_detector(hb)

    # ------------------------------------------------ sockets & heartbeat

    def _connect(self, i: int) -> socket.socket:
        kind, path = self._tspec[i]
        return transport_connect(kind, path, self._addrs[i],
                                 timeout=self._timeout)

    def _sock(self, i: int) -> socket.socket:
        if self._socks[i] is None:
            self._socks[i] = self._connect(i)
        return self._socks[i]

    def _on_wire_reset(self, shard: int, n_inflight: int) -> None:
        """ShardWorker connection kill: the pipelined analog of
        ``_drop_socket_locked`` — same RECONNECT accounting, plus a
        window-abort count when a whole in-flight window died at once
        (each of those requests re-enters its own retry machinery)."""
        self._counters.bump(self._cn.RECONNECT, shard=shard)
        if n_inflight > 1:
            self._counters.bump(self._cn.WINDOW_ABORT, shard=shard,
                                n=1, inflight=n_inflight)

    # ------------------------------------------------- distributed tracing

    def _tid(self) -> bytes:
        """The trace id every frame of the current op carries (b"" when
        RPC tracing is off or no op context is active)."""
        if not self._trace_rpc:
            return b""
        from ..observability.trace import current_trace_id

        return current_trace_id()

    @contextmanager
    def _traced(self, opname: str, name: str):
        """Per-op trace scope: mint (or join) a trace id for the
        calling thread and wrap the op in a ``client`` span carrying
        it.  No-op when RPC tracing is off — the hot path pays one
        attribute check."""
        if not self._trace_rpc:
            yield b""
            return
        from ..observability.trace import trace_context

        with trace_context() as tid:
            tracer = get_tracer()
            if tracer.enabled:
                with tracer.span(f"{opname}:{name}", "client",
                                 trace_id=tid.hex()):
                    yield tid
            else:
                yield tid

    def _trace_part_spans(self, name: str, pending, shard: int = 0) -> None:
        """Emit the client-queue (submit->sent) and wire (sent->reply)
        spans of one acked frame from the stamps its ``PendingRpc``
        noted — the I/O threads never touch the tracer.  Wire spans
        carry the shard's resolved transport, so a merged timeline
        shows which frames rode the fast path."""
        if not self._trace_rpc:
            return
        tracer = get_tracer()
        if not tracer.enabled or not pending.t_sent:
            return
        tid = self._tid().hex()
        tracer.complete(name or "<frame>", "client-queue",
                        pending.t_submit, pending.t_sent - pending.t_submit,
                        trace_id=tid)
        if pending.t_reply:
            tracer.complete(name or "<frame>", "wire", pending.t_sent,
                            pending.t_reply - pending.t_sent, trace_id=tid,
                            transport=self._transports[shard])

    # -------------------------------------------------- part-level fan-out

    def _submit_part(self, shard: int, op: int, name: str, arr=None,
                     raw: bytes = b"", priority: int = 0, key: int = 0):
        """Optimistic pipelined first attempt of one part: issue the
        frame on the shard worker NOW (it rides the wire while the
        caller encodes/waits siblings) and hand the future to ``_rpc``
        as attempt #1.  Returns None when the op must start inside
        ``_rpc`` instead: serial mode, or the shard is currently routed
        away (degraded mode must hold the failover lock around I/O)."""
        if self._workers is None:
            return None
        if self._failover_enabled and self._router.route(shard) != shard:
            return None
        try:
            return self._workers[shard].submit(
                _encode_buffers(op, name, arr, raw, trace_id=self._tid()),
                priority=priority, key=key)
        except ConnectionError:
            return None

    def _pipeline_parts(self, op: int, parts, encode, prio: int):
        """Windowed fan-out over the partitions of one logical op: up to
        ``BYTEPS_WIRE_FANOUT`` parts are encoded + submitted ahead of
        the one currently being gathered, so compression of part *i+1*
        and the socket wait of part *i* overlap, and parts fan out
        across shard connections concurrently (each shard's in-flight
        window bounds the wire).  ``encode(pname, part) -> (payload,
        commit)``; each part's ``commit`` (EF residual) fires only after
        ITS ack, in gather order.  Returns the per-part ``out`` values.

        On a part failure the already-submitted siblings are still
        awaited (and their residuals committed on success) before the
        error is re-raised — their mutations may have landed server-side
        and must not leave the EF state half-updated."""
        n = len(parts)
        ahead = max(1, self._fanout)
        state: dict = {}

        def _issue(j):
            pname, part = parts[j]
            payload, commit = encode(pname, part)
            shard = self._shard_of(pname,
                                   0 if part is None else part.nbytes)
            pend = self._submit_part(shard, op, pname, payload,
                                     priority=prio, key=j)
            state[j] = (shard, pname, payload, commit, pend)

        outs = [None] * n
        j = 0
        try:
            for i in range(n):
                while j < n and j < i + ahead:
                    _issue(j)
                    j += 1
                shard, pname, payload, commit, pend = state.pop(i)
                out, _ = self._rpc(shard, op, pname, payload,
                                   priority=prio, key=i, pending=pend)
                if commit is not None:
                    commit()  # EF residual: after THIS part's own ack
                outs[i] = out
        except BaseException:
            for k in sorted(state):
                shard, pname, payload, commit, pend = state[k]
                if pend is None:
                    continue
                try:
                    status, rname, out, _ = self._workers[shard].wait(
                        pend, self._timeout)
                    if status == 0:
                        # a drained sibling's ack is still an ack: record
                        # its version baseline AND fold it into the
                        # failover seed (skipping _note_success here
                        # would falsely dedup the NEXT push of this part
                        # and let a failover re-seed erase this one)
                        self._note_success(op, pname, rname, out, payload,
                                           shard=shard)
                        if commit is not None:
                            commit()
                except Exception:
                    pass  # best-effort drain; the first error wins
            raise
        return outs

    def _priority_of(self, name: str) -> int:
        """First-touch declaration order -> issue priority (earlier =
        higher), the reference's convention for "what the next forward
        needs first"."""
        with self._state_lock:
            p = self._prio.get(name)
            if p is None:
                p = -len(self._prio)
                self._prio[name] = p
            return p

    def _drop_socket_locked(self, shard: int) -> None:
        """Drop the (possibly poisoned) cached socket so the next RPC
        reconnects instead of failing forever.  Caller holds the shard
        lock."""
        if self._socks[shard] is not None:
            try:
                self._socks[shard].close()
            except OSError:
                pass
            self._socks[shard] = None
            self._counters.bump(self._cn.RECONNECT, shard=shard)

    def ping_shard(self, shard: int) -> bool:
        """One-shot short-timeout OP_PING round-trip on a fresh
        connection — never touches the cached data sockets, so
        heartbeats cannot contend with (or poison) in-flight ops."""
        host, port = self._addrs[shard].rsplit(":", 1)
        try:
            with socket.create_connection(
                    (host, int(port)), timeout=self._hb_timeout) as s:
                s.settimeout(self._hb_timeout)
                s.sendall(_encode(OP_PING, "", None))
                status, _, _, _ = _decode(s)
                return status == 0
        except _WIRE_ERRORS:
            return False

    def _start_detector(self, interval: float) -> None:
        from ..resilience import FailureDetector

        with self._state_lock:  # two racing RPC threads -> one detector
            if self._detector is None:
                self._detector = FailureDetector(
                    len(self._addrs), self.ping_shard, interval=interval,
                    miss_threshold=self._hb_threshold,
                    on_down=self._on_shard_down, on_up=self._on_shard_up,
                    counters=self._counters).start()

    def _ensure_detector(self) -> None:
        """A failover without a heartbeat would never notice recovery —
        start one lazily the first time a shard goes down."""
        if self._detector is None:
            self._start_detector(self._hb_interval or 0.25)

    def _on_shard_down(self, shard: int) -> None:
        if self._failover_enabled and self._router.mark_down(shard):
            self._counters.bump(self._cn.FAILOVER, shard=shard)
        if self._workers is not None:
            self._workers[shard].drop_connection()
        with self._locks[shard]:
            self._drop_socket_locked(shard)

    def _on_shard_up(self, shard: int) -> None:
        """Recovery migration: move every failed-over key back onto the
        restarted shard, seeding it with the latest global state pulled
        from its fallback.  Holds the failover lock, so no degraded-mode
        op can interleave and lose an update."""
        if not self._failover_enabled:
            return
        with self._failover_lock:
            for name, fb in self._router.failed_over_names(shard):
                try:
                    _, out, _ = self._rpc_raw(fb, OP_PULL, name)
                    val = np.array(out)
                except Exception:
                    with self._state_lock:
                        val = self._last_global.get(name)
                    if val is None:
                        continue
                try:
                    # force-set: a shard that was merely partitioned (not
                    # restarted) still holds its pre-partition state,
                    # which must not shadow the fallback's newer value
                    rname, _, _ = self._rpc_raw(shard, OP_SET, name, val)
                except Exception as e:
                    bps_log.warning(
                        "failback of %r to shard %d failed (%s); staying "
                        "degraded", name, shard, e)
                    # re-arm the detector: it already moved the shard to
                    # its up set before firing on_up, so without this the
                    # next successful ping is a no-op and the migration
                    # would never be retried — permanently degraded
                    if self._detector is not None:
                        self._detector.mark_down(shard)
                    return
                self._router.clear_failover(name)
                self._counters.bump(self._cn.REINIT, name=name, shard=shard)
                self._note_success(OP_SET, name, rname, None, val,
                                   shard=shard)
            if self._router.mark_up(shard):
                self._counters.bump(self._cn.FAILBACK, shard=shard)
                bps_log.warning("shard %d restored; routing returned to "
                                "primary placement", shard)

    # --------------------------------------------------------------- RPC

    def _shard_of(self, name: str, nbytes: int = 0) -> int:
        return self._sharder.place(name_key(name), nbytes)

    def _rpc_raw(self, shard: int, op: int, name: str,
                 arr: Optional[np.ndarray] = None, raw: bytes = b"",
                 op_timeout: Optional[float] = None, priority: int = 0,
                 key: int = 0, pending=None):
        """One attempt against one shard; no retry, no routing.
        ``op_timeout`` clamps the wait for this attempt so a hung shard
        cannot stall an op past its retry deadline.

        Pipelined mode: the frame is enqueued on the shard's I/O worker
        (issue order = priority desc, key asc) and this thread blocks on
        its future — up to ``BYTEPS_WIRE_WINDOW`` requests from
        concurrent callers ride the connection un-acked.  ``pending``
        (from ``_submit_part``) is an already-issued frame: this attempt
        then only waits — how multi-part ops overlap their parts.  A
        wait timeout aborts through the worker (killing the connection —
        FIFO reply matching cannot skip one frame) and surfaces as the
        same ``socket.timeout`` the serial path produces."""
        wait = (self._timeout if op_timeout is None
                else max(0.05, min(self._timeout, op_timeout)))
        if self._workers is not None:
            worker = self._workers[shard]
            if pending is None:
                pending = worker.submit(
                    _encode_buffers(op, name, arr, raw,
                                    trace_id=self._tid()),
                    priority=priority, key=key)
            status, rname, out, payload = worker.wait(pending, wait)
            self._trace_part_spans(name, pending, shard)
        else:
            t0 = 0.0
            with self._locks[shard]:
                # stamp INSIDE the lock: waiting for another thread's
                # RPC on this shard is client-side queueing, not wire
                # time — the exact confusion the straggler workflow
                # exists to resolve
                if self._trace_rpc:
                    t0 = time.perf_counter()
                try:
                    sock = self._sock(shard)
                    sock.settimeout(wait)
                    _send_buffers(sock,
                                  _encode_buffers(op, name, arr, raw,
                                                  trace_id=self._tid()))
                    status, rname, out, payload = _decode(sock)
                except _WIRE_ERRORS:
                    self._drop_socket_locked(shard)
                    raise
            if self._trace_rpc:
                tracer = get_tracer()
                if tracer.enabled:
                    tracer.complete(name or "<frame>", "wire", t0,
                                    time.perf_counter() - t0,
                                    trace_id=self._tid().hex(),
                                    transport=self._transports[shard])
        if status != 0:
            raise RuntimeError(f"ps_server error: {bytes(payload).decode()!r}")
        return rname, out, payload

    def _rpc_once(self, shard: int, op: int, name: str,
                  arr: Optional[np.ndarray] = None, raw: bytes = b"",
                  op_timeout: Optional[float] = None, priority: int = 0,
                  key: int = 0, pending=None):
        rname, out, payload = self._rpc_raw(shard, op, name, arr, raw,
                                            op_timeout, priority, key,
                                            pending)
        if self._detector is not None:
            self._detector.report_success(shard)
        self._note_success(op, name, rname, out, arr, shard=shard)
        return out, payload

    def _note_success(self, op: int, name: str, rname: str, out, arr=None,
                      shard: int = 0):
        """Record the server-acknowledged version (reply name field,
        keyed per (name, shard)) and the last seen global value — the
        failover seed."""
        if op not in (OP_INIT, OP_SET, OP_PUSH, OP_PUSH_PULL, OP_PULL):
            return
        version = int(rname) if rname and rname.isdigit() else None
        snap = None
        if self._seed_enabled:
            if op in (OP_PULL, OP_PUSH_PULL, OP_INIT) and out is not None:
                # INIT replies carry the store's actual value, so a
                # first-push-wins loser records the WINNING value here,
                # not its own rejected seed.  Zero-copy: ``out`` is a
                # view over this reply's private buffer (nothing else
                # writes it, and user-facing returns are separate
                # copies), so the seed is a reference, not a multi-MB
                # copy per RPC — under contention the latest reply per
                # name simply wins the dict slot.
                snap = out
            elif op == OP_SET and arr is not None:
                # force-set: our value IS the store's value now; the
                # caller owns (and may reuse) ``arr``, so this one copies
                snap = np.array(arr)
            elif op == OP_PUSH and arr is not None:
                # status-only ack: fold the mutation into the seed
                # ourselves.  Without this, a later failover re-seed (or
                # failback SET) built from _last_global would silently
                # ERASE every acked push since the last pulled value —
                # the single-element drift the partitioned chaos smoke
                # caught.  Exact for a single writer: the fold applies
                # the same dense delta, cast and elementwise add the
                # server itself performs.
                snap = self._fold_seed(name, arr)
            elif op == OP_INIT and arr is not None and version == 0:
                # duck-typed store without a value in the init reply:
                # fall back to our seed (exact only pre-push)
                snap = np.array(arr)
        with self._state_lock:
            if version is not None:
                self._pushed_version[(name, shard)] = version
            if snap is not None:
                self._last_global[name] = snap

    @staticmethod
    def _dense_delta(payload):
        """The dense array the server ADDS for this mutation payload:
        ``decode_blob``'s reconstruction for a compressed frame (exactly
        what the server-side frame decode produces), the raw array
        otherwise."""
        if isinstance(payload, WireBlob):
            from ..compression.wire import WIRE_TAG, decode_blob

            return decode_blob(WIRE_TAG, payload.data, payload.shape)
        return payload

    def _fold_seed(self, name: str, payload):
        """``last_global[name] + dense(payload)`` — the post-mutation
        global state, computed client-side.  Bit-exact vs the server for
        a single writer: both sides do the same elementwise add of the
        same dense delta in the store dtype (no reassociation).  None
        when there is no seed yet to fold into (the name was never
        pulled — failover re-seeding then skips it, as before)."""
        with self._state_lock:
            last = self._last_global.get(name)
        if last is None:
            return None
        last = np.asarray(last)
        dense = np.asarray(self._dense_delta(payload))
        return last + dense.astype(last.dtype, copy=False)

    def _rpc(self, shard: int, op: int, name: str,
             arr: Optional[np.ndarray] = None, raw: bytes = b"",
             priority: int = 0, key: int = 0, pending=None):
        """Routed, retried RPC — the resilience front door.
        ``priority``/``key`` order the frame on the shard worker's send
        queue in pipelined mode (no effect on the serial path).

        ``pending`` is an optimistic already-submitted first attempt
        (``_submit_part``): it is consumed as attempt #1 under the SAME
        policy/deadline/version-guard machinery as a fresh send, so a
        pipelined part that dies mid-window gets exactly the serial
        client's retry semantics."""
        primary = shard
        policy = self._policy
        deadline = policy.start()
        attempt = 0
        reseeded = False
        while True:
            # target of THIS attempt: primary, or the fallback when the
            # router has the primary excluded.  The lock-free route peek
            # keeps healthy-shard ops off the failover lock entirely; the
            # re-check under the lock makes fallback ops atomic against
            # recovery migration.
            target = primary
            if (self._failover_enabled
                    and self._router.route(primary) != primary):
                with self._failover_lock:
                    routed = self._router.route(primary)
                    if routed != primary:
                        if pending is not None:
                            # the optimistic frame went to the (now
                            # excluded) primary; abort it so a stray
                            # mutation cannot land there while the
                            # fallback applies ours (failback's OP_SET
                            # overwrite heals the narrow race where it
                            # was already applied)
                            self._workers[primary].abort(
                                pending,
                                ConnectionError("re-routed to fallback"))
                            pending = None
                        try:
                            return self._rpc_on_fallback(
                                primary, routed, op, name, arr, raw)
                        except _WIRE_ERRORS as e:
                            err = e
                            target = routed
            if target == primary:
                try:
                    # clamp this attempt's socket timeout to the time
                    # left on the op deadline: a hung (not crashed)
                    # shard must not stall the op past the documented
                    # BYTEPS_RETRY_DEADLINE_MS bound
                    remaining = (None if deadline == float("inf")
                                 else deadline - time.monotonic())
                    first, pending = pending, None
                    return self._rpc_once(primary, op, name, arr, raw,
                                          op_timeout=remaining,
                                          priority=priority, key=key,
                                          pending=first)
                except _WIRE_ERRORS as e:
                    err = e
                except RuntimeError as e:
                    # store-level errors are final — EXCEPT the one a
                    # supervised restart manufactures: a shard brought
                    # back with a fresh store answers ops for tensors it
                    # no longer holds with KeyError.  Re-seed once from
                    # the last-seen global state and retry (the recovery
                    # path for single-shard clusters, where failover can
                    # never kick in).
                    if (not reseeded and name and "KeyError" in str(e)
                            and self._reseed_shard(primary, name)):
                        reseeded = True
                        continue
                    raise
            attempt += 1
            if self._detector is not None and target == primary:
                self._detector.report_failure(primary)
            if policy.should_retry(attempt, deadline):
                self._counters.bump(self._cn.RETRY, op=op, name=name,
                                    shard=target, attempt=attempt)
                policy.sleep(attempt + 1)
                if op in (OP_PUSH, OP_PUSH_PULL):
                    # probe the shard the lost attempt actually hit
                    resolved = self._resolve_lost_mutation(target, op, name,
                                                           arr)
                    if resolved is not None:
                        return resolved
                continue
            # retries exhausted: exclude the shard we kept failing
            # against — the primary, or a fallback that died too
            # (cascading failure) — and re-route if that moves the op
            # anywhere new.  mark_down refuses to exclude the last
            # alive shard, so this terminates.
            if self._failover_enabled:
                if self._router.mark_down(target):
                    self._counters.bump(self._cn.FAILOVER, shard=target)
                    self._ensure_detector()
                    if self._detector is not None:
                        self._detector.mark_down(target)
                if self._router.route(primary) != target:
                    # routing changed (we excluded the target, or the
                    # heartbeat beat us to it) — try the new home with a
                    # fresh retry budget: carrying the exhausted counter
                    # over would give every subsequent shard exactly one
                    # blip of tolerance and cascade healthy shards out
                    attempt = 0
                    continue
            self._counters.bump(self._cn.GIVE_UP, op=op, name=name,
                                shard=target)
            raise err

    def _reseed_shard(self, shard: int, name: str) -> bool:
        """Force-SET a tensor a shard lost (restart with a fresh store)
        from this client's last-seen global state.  False when there is
        nothing to seed from — the KeyError then surfaces unchanged
        (e.g. a genuinely never-declared name)."""
        with self._state_lock:
            seed = self._last_global.get(name)
        if seed is None:
            return False
        try:
            rname, _, _ = self._rpc_raw(shard, OP_SET, name, seed)
        except Exception:
            return False
        self._counters.bump(self._cn.REINIT, name=name, shard=shard)
        self._note_success(OP_SET, name, rname, None, seed, shard=shard)
        bps_log.warning("shard %d lost %r (restarted with a fresh "
                        "store?); re-seeded from last-seen state",
                        shard, name)
        return True

    def _resolve_lost_mutation(self, shard: int, op: int, name: str,
                               arr=None):
        """After a wire failure on PUSH/PUSH_PULL, decide whether the
        lost attempt was applied (reply lost) or not (request lost): if
        the server's version advanced past the last version it
        acknowledged to us, the mutation landed — resending would
        double-apply.  Assumes a single writer per key (concurrent
        writers make the counter ambiguous; see docs/resilience.md).
        Returns the op's result when known-applied, else None (resend).

        ``arr`` is the mutation payload: a deduplicated (applied, reply
        lost) mutation is folded into ``_last_global`` locally — exact
        for a single writer — so the failover seed can never lose an
        acked mutation, and a PUSH_PULL's lost reply is reconstructed
        without a second routed round-trip (a recovery PULL that itself
        failed over used to adopt — and then failback-SET — a state
        PREDATING the acked mutation: the exactly-once violation the
        partitioned chaos smoke exposed).
        """
        if not self._version_guard:
            # multiple writers: the counter cannot attribute the advance
            # to OUR lost push — suppressing would silently drop a delta,
            # so fall back to at-least-once resend
            return None
        with self._state_lock:
            expected = self._pushed_version.get((name, shard))
        if expected is None:
            return None  # no baseline ON THIS SHARD: at-least-once resend
        # the probe is idempotent, so retry it under the policy itself: a
        # single-shot probe that happened to hit its own transient fault
        # would wrongly resend an applied mutation
        payload = None
        for probe_attempt in range(self._policy.max_attempts):
            try:
                _, _, payload = self._rpc_raw(shard, OP_VERSION, name)
                break
            except RuntimeError:
                return None  # store-level: tensor unknown there
            except _WIRE_ERRORS:
                self._policy.sleep(probe_attempt + 2)
        if payload is None:
            return None  # probe never got through; resend (at-least-once)
        v = struct.unpack("<Q", payload)[0]
        if v <= expected:
            return None  # not applied; safe to resend
        with self._state_lock:
            self._pushed_version[(name, shard)] = v
        self._counters.bump(self._cn.DEDUP, op=op, name=name, shard=shard)
        bps_log.debug("retry of %s on %r suppressed: server already at "
                      "version %d (> %d)", op, name, v, expected)
        post = self._fold_seed(name, arr) if arr is not None else None
        if post is not None:
            # the applied-but-unacked value now lives in the seed: a
            # failover re-seed (or failback SET) built from it carries
            # this mutation instead of erasing it
            with self._state_lock:
                self._last_global[name] = post
        if op == OP_PUSH_PULL:
            if post is not None:
                # lost reply reconstructed locally (exact, single
                # writer) — no second routed round-trip that could
                # itself fail over to a shard without the mutation
                return post, b""
            # no seed to fold into: a plain idempotent PULL recovers it
            return self._rpc(shard, OP_PULL, name)
        return None, b""

    def _rpc_on_fallback(self, primary: int, fallback: int, op: int,
                         name: str, arr, raw):
        """Degraded mode: serve an op for a key whose primary shard is
        down.  First touch of a name re-initializes it on the fallback
        shard from this worker's last-seen global state (the
        restore-from-worker-state leg of failover).  Caller holds the
        failover lock (held across the I/O: degraded-mode ops must not
        interleave with recovery migration, or its final
        pull-from-fallback could miss an in-flight update)."""
        if op in (OP_NAMES, OP_PING):
            return self._rpc_once(fallback, op, name, arr, raw)
        # re-seed when the name is not yet re-homed OR its ledgered
        # fallback differs from where routing points now (a cascading
        # second failure moved the fallback — the new shard has no copy)
        if self._router.fallback_for(name) != fallback:
            with self._state_lock:
                seed = self._last_global.get(name)
            if seed is not None:
                # force-set, not init: the fallback may hold a stale
                # leftover copy from an earlier failover episode, which
                # first-push-wins INIT would silently keep
                rname, _, _ = self._rpc_raw(fallback, OP_SET, name, seed)
                self._counters.bump(self._cn.REINIT, name=name,
                                    shard=fallback)
                # adopt the fallback's version counter as the dedup
                # baseline for this (name, shard) pair
                self._note_success(OP_SET, name, rname, None, seed,
                                   shard=fallback)
            self._router.note_failover(name, primary, fallback)
            bps_log.warning("shard %d down: %r re-homed to shard %d",
                            primary, name, fallback)
        return self._rpc_once(fallback, op, name, arr, raw)

    # ------------------------------------------------- store interface

    def _partition(self, name: str, arr: np.ndarray):
        """Split ``arr`` into the wire partitions of ``name`` (reference
        PartitionTensor, operations.cc:95-132): ``[(wire_name, part)]``,
        flat slices for multi-part tensors, and record the reassembly
        meta so ``pull``/``version`` can find the parts later.  Each
        partition is compressed, version-guarded and shard-placed
        independently — priority interleaving on the wire happens at
        partition granularity, like the scheduler's."""
        from ..common.partition import partition_offsets

        arr = np.ascontiguousarray(arr)
        parts = partition_offsets(arr.nbytes, self._partition_bytes)
        with self._state_lock:
            self._part_meta[name] = (max(1, len(parts)), arr.shape,
                                     arr.dtype)
        if len(parts) <= 1:
            return [(name, arr)]
        flat = arr.reshape(-1)
        itemsize = arr.dtype.itemsize
        return [(f"{name}#p{i}",
                 flat[off // itemsize:(off + length) // itemsize])
                for i, (off, length) in enumerate(parts)]

    def _part_names(self, name: str):
        """Reassembly meta, or None for an unpartitioned/unknown name."""
        with self._state_lock:
            meta = self._part_meta.get(name)
        if meta is None or meta[0] == 1:
            return None
        return meta

    def _discover_parts(self, name: str):
        """A client that never pushed ``name`` has no reassembly meta; a
        tensor partitioned by ANOTHER client still lives on the servers
        as ``name#p{i}`` keys.  Discover them via ``names()`` and cache a
        flat-shaped meta (the original shape is client-local knowledge —
        callers reshape against their own template).  Returns the meta or
        None when the name genuinely does not exist partitioned."""
        prefix = f"{name}#p"
        idx = []
        for n in self.names():
            if n.startswith(prefix) and n[len(prefix):].isdigit():
                idx.append(int(n[len(prefix):]))
        if not idx or sorted(idx) != list(range(len(idx))):
            return None
        out, _ = self._rpc(self._shard_of(f"{name}#p0"), OP_PULL,
                           f"{name}#p0")
        part0 = np.asarray(out)
        bps_log.warning(
            "%r was partitioned by another client; reassembling %d parts "
            "as a flat [n] array (original shape is client-local — "
            "reshape against your template)", name, len(idx))
        meta = (len(idx), None, part0.dtype)
        with self._state_lock:
            self._part_meta[name] = meta
        return meta

    # ------------------------------------------- hierarchical slices

    def _hier_slices(self, name: str, arr: np.ndarray):
        """``[(slice_key, flat_view)]`` when ``arr`` falls under the
        hierarchical contract (docs/wire.md "Hierarchical reduction"),
        else None.  Slices are zero-copy views of the flat tensor —
        contiguous spans per ``hier.slice_spans`` — and reassembly meta
        is recorded like ``_partition``'s."""
        if not self._hier or self._hier_L <= 1:
            return None
        if hier.is_sliced_name(name):
            return None  # slice/partition keys are never re-sliced
        if not hier.eligible(arr, self._hier_L, self._hier_min):
            return None
        arr = np.ascontiguousarray(arr)
        spans = hier.slice_spans(arr.size, self._hier_L)
        flat = arr.reshape(-1)
        with self._state_lock:
            self._hier_meta[name] = (len(spans), arr.shape, arr.dtype)
        return [(hier.slice_name(name, r), flat[a:b])
                for r, (a, b) in enumerate(spans)]

    def _hier_meta_of(self, name: str):
        with self._state_lock:
            return self._hier_meta.get(name)

    def _mutate_parts(self, op: int, name: str, arr: np.ndarray, encode,
                      prio: int):
        """Slice (when hierarchical) and partition one mutation, fanning
        every resulting part through a single pipelined pass; outs come
        back in span order, so ``_assemble_flat`` reassembles them
        directly."""
        sl = self._hier_slices(name, arr)
        if sl is None:
            parts = self._partition(name, arr)
        else:
            parts = [p for sname, sarr in sl
                     for p in self._partition(sname, sarr)]
        return self._pipeline_parts(op, parts, encode, prio)

    def _discover_slices(self, name: str):
        """A tensor sliced by ANOTHER client lives on the servers only
        as ``name@s{r}`` keys (each possibly partitioned).  Discover the
        rank set via ``names()``; reassembly is flat ``[n]`` (the
        original shape is client-local knowledge), mirroring
        ``_discover_parts``."""
        ranks = set()
        for n in self.names():
            r = hier.parse_slice_rank(n, name)
            if r is not None:
                ranks.add(r)
        if not ranks or sorted(ranks) != list(range(len(ranks))):
            return None
        bps_log.warning(
            "%r was sliced hierarchically by another client; "
            "reassembling %d slices as a flat [n] array (reshape "
            "against your template)", name, len(ranks))
        meta = (len(ranks), None, None)
        with self._state_lock:
            self._hier_meta[name] = meta
        return meta

    def _pull_sliced(self, name: str, hm, prio: int) -> np.ndarray:
        """Pull every slice of ``name`` (one windowed fan-out pass over
        all slice-parts) into one preallocated flat destination."""
        nsl, shape, dtype = hm
        if shape is None:
            # discovery pull (sliced by another client): per-slice plain
            # pulls own their partition discovery
            chunks = [np.asarray(
                self._pull_traced(hier.slice_name(name, r))).reshape(-1)
                for r in range(nsl)]
            return self._assemble_flat(chunks, dtype or chunks[0].dtype)
        parts = []
        for r in range(nsl):
            sname = hier.slice_name(name, r)
            pmeta = self._part_names(sname)
            if pmeta is None:
                parts.append((sname, None))
            else:
                parts.extend((f"{sname}#p{i}", None)
                             for i in range(pmeta[0]))
        chunks = [np.asarray(o).reshape(-1) for o in
                  self._pipeline_parts(OP_PULL, parts, self._encode_raw,
                                       prio)]
        return self._assemble_flat(chunks, dtype).reshape(shape)

    def _note_slice_meta(self, name: str, total: int, items) -> None:
        """Record pull-side reassembly meta for a slice-API op — only
        when the caller covers the WHOLE group (a multi-process caller
        pushing just its rank owns its own reassembly; the shape is
        flat because the slice API never sees the original one)."""
        if len(items) != int(total) or not items:
            return
        n = sum(int(a.size) for _, a in items)
        with self._state_lock:
            self._hier_meta[name] = (int(total), (n,), items[0][1].dtype)

    def init_slices(self, name: str, slices: dict, total: int) -> None:
        """INIT the given rank slices of ``name`` (flat arrays keyed
        ``name@s{r}``, first-push-wins per slice).  ``total`` is the
        group's local_size."""
        prio = self._priority_of(name)
        items = [(r, np.ascontiguousarray(np.asarray(a).reshape(-1)))
                 for r, a in sorted(slices.items())]
        self._note_slice_meta(name, total, items)
        parts = [p for r, arr in items
                 for p in self._partition(hier.slice_name(name, r), arr)]
        with self._traced("init", name):
            self._pipeline_parts(OP_INIT, parts, self._encode_raw, prio)

    def push_pull_slices(self, name: str, slices: dict,
                         total: int) -> dict:
        """Per-rank hierarchical exchange: push each given flat slice as
        ``name@s{r}`` — every part of every slice rides ONE windowed
        fan-out pass — and return the pulled global slices
        ``{rank: flat array}``.  This is the entry point the group-level
        ``hierarchical.hierarchical_push_pull`` ships single ranks
        through (the 1/local_size wire contract)."""
        prio = self._priority_of(name)
        items = [(r, np.ascontiguousarray(np.asarray(a).reshape(-1)))
                 for r, a in sorted(slices.items())]
        self._note_slice_meta(name, total, items)
        parts, counts = [], []
        for r, arr in items:
            p = self._partition(hier.slice_name(name, r), arr)
            parts.extend(p)
            counts.append(len(p))
        with self._traced("push_pull", name):
            outs = [np.asarray(o).reshape(-1) for o in
                    self._pipeline_parts(OP_PUSH_PULL, parts,
                                         self._compressor.encode_mutation,
                                         prio)]
        result = {}
        off = 0
        for (r, _), k in zip(items, counts):
            result[r] = (np.array(outs[off]) if k == 1 else
                         self._assemble_flat(outs[off:off + k],
                                             outs[off].dtype))
            off += k
        return result

    @staticmethod
    def _encode_raw(pname, part):
        # identity "encode" for uncompressed legs (INIT / PULL)
        return part, None

    @staticmethod
    def _assemble_flat(chunks, dtype) -> np.ndarray:
        """Reassemble part arrays into ONE preallocated flat destination
        — each part is cast + placed into its slice in a single pass
        (the seed's ``concatenate().astype()`` made two full copies)."""
        flat = np.empty(sum(c.size for c in chunks), dtype)
        off = 0
        for c in chunks:
            flat[off:off + c.size] = c
            off += c.size
        return flat

    def init_tensor(self, name: str, value: np.ndarray) -> None:
        # INIT stays raw: it seeds the authoritative global state, which
        # must not start life quantized
        prio = self._priority_of(name)
        with self._traced("init", name):
            self._mutate_parts(OP_INIT, name, np.asarray(value),
                               self._encode_raw, prio)

    def push_delta(self, name: str, delta: np.ndarray,
                   priority: Optional[int] = None) -> None:
        # OP_PUSH replies status-only: no pointless global-tensor download
        prio = self._priority_of(name) if priority is None else priority
        with self._traced("push", name):
            self._mutate_parts(OP_PUSH, name, np.asarray(delta),
                               self._compressor.encode_mutation, prio)

    def pull(self, name: str) -> np.ndarray:
        with self._traced("pull", name):
            return self._pull_traced(name)

    def pull_many(self, names) -> dict:
        """Pull several tensors through ONE windowed fan-out pass:
        ``{name: array}``.  The ZeRO pull-params phase
        (training/zero.py) pulls ``world - 1`` span keys per step; a
        serial loop pays one wire round trip each, while this rides
        every part of every name down the same pipelined window the
        partition fan-out uses (docs/wire.md).  Names this client holds
        no meta for (sliced elsewhere, never touched) fall back to the
        discovery path of :meth:`pull` individually."""
        names = list(names)
        parts, counts, fast = [], [], []
        for name in names:
            if self._hier_meta_of(name) is not None:
                fast.append(False)
                continue
            meta = self._part_names(name)
            with self._state_lock:
                known = name in self._part_meta
            if meta is None and not known:
                fast.append(False)  # never seen: needs discovery
                continue
            fast.append(True)
            if meta is None:
                parts.append((name, None))
                counts.append((1, None, None))
            else:
                nparts, shape, dtype = meta
                parts.extend((f"{name}#p{i}", None) for i in range(nparts))
                counts.append((nparts, shape, dtype))
        with self._traced("pull", f"pull_many[{len(names)}]"):
            outs = (self._pipeline_parts(OP_PULL, parts, self._encode_raw,
                                         0)
                    if parts else [])
        result, off, ci = {}, 0, 0
        for name, is_fast in zip(names, fast):
            if not is_fast:
                result[name] = self.pull(name)
                continue
            k, shape, dtype = counts[ci]
            ci += 1
            if k == 1 and shape is None:
                result[name] = np.array(outs[off])
            else:
                chunks = [np.asarray(o).reshape(-1)
                          for o in outs[off:off + k]]
                flat = self._assemble_flat(chunks, dtype or chunks[0].dtype)
                result[name] = (flat if shape is None
                                else flat.reshape(shape))
            off += k
        return result

    def _pull_traced(self, name: str) -> np.ndarray:
        prio = self._priority_of(name)
        hm = self._hier_meta_of(name)
        if hm is not None:
            return self._pull_sliced(name, hm, prio)
        meta = self._part_names(name)
        if meta is None:
            try:
                out, _ = self._rpc(self._shard_of(name), OP_PULL, name,
                                   priority=prio)
                return np.array(out)  # own the buffer
            except RuntimeError as e:
                # possibly a tensor partitioned (or sliced) by another
                # client (this one holds no meta): the store only knows
                # name#p{i} / name@s{r}
                if "KeyError" not in str(e):
                    raise
                meta = self._discover_parts(name)
                if meta is None:
                    hm = self._discover_slices(name)
                    if hm is None:
                        raise
                    return self._pull_sliced(name, hm, prio)
        nparts, shape, dtype = meta
        parts = [(f"{name}#p{i}", None) for i in range(nparts)]
        chunks = [np.asarray(o).reshape(-1) for o in
                  self._pipeline_parts(OP_PULL, parts, self._encode_raw,
                                       prio)]
        flat = self._assemble_flat(chunks, dtype)
        return flat if shape is None else flat.reshape(shape)

    def push_pull(self, name: str, delta: np.ndarray,
                  priority: Optional[int] = None) -> np.ndarray:
        d = np.asarray(delta)
        prio = self._priority_of(name) if priority is None else priority
        with self._traced("push_pull", name):
            outs = [np.asarray(o).reshape(-1) for o in
                    self._mutate_parts(OP_PUSH_PULL, name, d,
                                       self._compressor.encode_mutation,
                                       prio)]
        if len(outs) == 1:
            return np.array(outs[0]).reshape(d.shape)
        return self._assemble_flat(outs, outs[0].dtype).reshape(d.shape)

    def version(self, name: str) -> int:
        hm = self._hier_meta_of(name)
        if hm is not None:
            # a sliced tensor's version question means slice 0's (each
            # slice carries an independent counter, like partitions)
            return self.version(hier.slice_name(name, 0))
        meta = self._part_names(name)
        qname = name if meta is None else f"{name}#p0"
        try:
            _, payload = self._rpc(self._shard_of(qname), OP_VERSION, qname)
        except RuntimeError as e:
            if meta is not None or "KeyError" not in str(e):
                raise
            if self._discover_parts(name) is not None:
                qname = f"{name}#p0"
            elif self._discover_slices(name) is not None:
                return self.version(hier.slice_name(name, 0))
            else:
                raise
            _, payload = self._rpc(self._shard_of(qname), OP_VERSION, qname)
        return struct.unpack("<Q", payload)[0]

    def names(self) -> List[str]:
        """Union of tensor names across shards, queried CONCURRENTLY
        (this sits on the recovery/``_discover_parts`` path, where a
        serial per-shard scan added a full round-trip per shard).  Down
        shards are skipped (their reachable names live on fallbacks and
        appear in those listings); the union is deduplicated in shard
        order because a failed-over name exists on both its fallback
        and, after recovery, its primary."""
        alive = [i for i in range(len(self._addrs))
                 if not (self._failover_enabled and self._router.is_down(i))]
        pend = {i: self._submit_part(i, OP_NAMES, "") for i in alive}
        payloads = [self._rpc(i, OP_NAMES, "", pending=pend[i])[1]
                    for i in alive]
        out: List[str] = []
        seen: set = set()
        for payload in payloads:
            for n in (bytes(payload).decode().split("\n") if payload else []):
                if n and n not in seen:
                    seen.add(n)
                    out.append(n)
        return out

    def ping(self) -> bool:
        """True iff every shard ADDRESS answers — deliberately not
        routed through the failover layer (a fallback answering for a
        dead primary must not make the cluster look healthy)."""
        return all(self.ping_shard(i) for i in range(len(self._addrs)))

    def health(self) -> List[bool]:
        """Per-shard routing health (True = primary placement active)."""
        return [not self._router.is_down(i) for i in range(len(self._addrs))]

    def shard_stats(self, shard: int) -> dict:
        """Live ``OP_STATS`` scrape of one shard: its identity plus the
        shard process's metrics-registry snapshot — the in-band twin of
        the shard's HTTP ``/metrics.json`` (docs/observability.md)."""
        _, payload = self._rpc(shard, OP_STATS, "")
        return json.loads(bytes(payload).decode())

    def record_clock_offsets(self, samples: int = 5) -> List:
        """Estimate every shard's wall-clock offset (NTP-style midpoint
        over ``OP_PING`` — observability/trace.py) and drop each
        estimate into the client trace as a ``clock_offset`` instant
        event.  That event is the in-band channel
        ``scripts/trace_merge.py`` reads per-host offsets from, so a
        merge needs no side-file.  Unreachable shards are skipped with
        a warning (their spans stay unaligned rather than failing the
        run).  Returns the :class:`ClockOffset` list."""
        from ..observability.trace import estimate_clock_offset

        tracer = get_tracer()
        out = []
        for addr in self._addrs:
            try:
                off = estimate_clock_offset(addr, n=samples)
            except (ConnectionError, OSError) as e:
                bps_log.warning("clock offset for %s unavailable: %s",
                                addr, e)
                continue
            out.append(off)
            if tracer.enabled:
                tracer.instant("clock_offset", "client", **off.as_dict())
        return out

    def close(self) -> None:
        if self._detector is not None:
            self._detector.stop()
            self._detector = None
        if self._workers is not None:
            for w in self._workers:
                w.close()
        for i, s in enumerate(self._socks):
            if s is not None:
                try:
                    s.close()
                finally:
                    self._socks[i] = None
        try:
            # run-end wire summary (one line; silent when nothing was sent)
            self._wire_stats.log_summary()
        except Exception:  # pragma: no cover - logging must never mask close
            pass
