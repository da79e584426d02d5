"""Pluggable endpoint transports — the ps-lite *van* analog.

BytePS splits its communication layer in two: the inter-machine path is
a pluggable ps-lite van (ZeroMQ / RDMA), while intra-machine traffic
goes through a dedicated local layer (``BytePSSharedMemory`` POSIX shm,
``BytePSCommSocket`` AF_UNIX) that never touches the NIC (PAPER.md
layer map).  Our wire engine was TCP-only, and on the colocated
topology every test and single-host serve runs, per-frame TCP overhead
is a large part of the round trip.

This module is the transport seam extracted from that socket plumbing.
A *transport* is anything that duck-types the blocking stream-socket
surface the framing codec already consumes:

    recv_into(view) -> int      # 0 = clean EOF
    sendmsg(views) -> int       # partial writes allowed
    sendall(bytes)              # single-shot senders
    settimeout(t) / setsockopt(...) / shutdown(how) / close() / fileno()

Three implementations:

  * **tcp** — ``socket.create_connection`` + TCP_NODELAY, bit-identical
    to the pre-transport client; the only choice for cross-host
    endpoints.
  * **unix** — the same stream framing over an AF_UNIX socket: one
    kernel round trip fewer per frame, no TCP/IP stack, no Nagle.
  * **shm** — a pair of mmap'd SPSC byte rings (one per direction)
    over an anonymous ``memfd`` passed via SCM_RIGHTS, with a
    futex-free doorbell (empty->non-empty poke on the rendezvous
    socket; spin-then-select on the reader).  The zero-copy
    buffer-list framing writes scatter-gather straight into the ring,
    so a multi-MB push never coalesces into an intermediate ``bytes``.

**Addressing.**  Endpoints keep their one identity — ``host:port`` —
on every transport.  A server that listens on TCP port *P* *advertises*
local endpoints by also binding ``ps-P.sock`` (UDS) and ``ps-P.shm``
(shm rendezvous) under a short per-uid tmpdir
(``BYTEPS_TRANSPORT_DIR``).  ``resolve_transport(addr, "auto")`` picks
the fast path iff the host resolves to this machine AND the rendezvous
answers a probe connect (a stale socket file left by a crashed shard
therefore falls back to TCP instead of wedging the client); non-local
addresses always resolve to TCP.  Resolution happens once per client
construction, so reconnects never flip transports mid-run.

**Semantics.**  All three transports surface failures inside the same
``OSError``/``ConnectionError``/``socket.timeout`` taxonomy the retry /
version-guard / failover machinery already speaks, so the in-flight
window, FIFO reply matching and exactly-once contracts are transport-
independent by construction (chaos-proven on the UDS path —
``scripts/chaos_smoke.py --transport unix``).  One honest difference:
a UDS/shm peer death looks like a clean EOF rather than an ECONNRESET,
both of which are wire errors to the client.

The shm ring relies on x86-TSO store ordering (payload bytes are
written before the position counter that publishes them; both sides
are CPython, whose eval loop adds no reordering).  The rendezvous
socket doubles as the doorbell: an idle reader blocks in ``select``
(zero CPU), a writer taking the ring from empty to non-empty pokes one
byte, and mid-stream chunks skip the kernel entirely — see
:class:`ShmConnection`.

Heartbeats (``ping_shard``) and clock-offset probes deliberately stay
on TCP: they answer "is the shard process alive at its address", which
must not depend on the fast path's rendezvous state.
"""

from __future__ import annotations

import errno
import mmap
import os
import socket
import socketserver
import struct
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

from ..common import logging as bps_log

__all__ = [
    "KINDS", "RegisteredBufferPool", "ShmConnection", "LocalEndpoints",
    "connection_kind", "endpoint_path", "is_local_host", "maybe_nodelay",
    "parse_overrides", "peer_label", "rdma_available", "resolve_transport",
    "transport_connect", "transport_dir",
]

KINDS = ("tcp", "unix", "shm")

_SUFFIX = {"unix": ".sock", "shm": ".shm"}
# AF_UNIX sun_path is 108 bytes including NUL; leave margin for the
# file name so the loud failure names the *derived* path
_UDS_PATH_MAX = 100
_HANDSHAKE_MAGIC = b"BPSHM1"
_RING_HDR = 64
_MAX_RING = 1 << 30  # 1 GiB/direction sanity bound on the handshake


# ------------------------------------------------------------- addressing


def transport_dir() -> str:
    """Rendezvous directory: ``BYTEPS_TRANSPORT_DIR`` or a short
    per-uid dir under the system tmpdir (created 0700 on first use —
    endpoints must not be spoofable by other users)."""
    from ..common.config import get_config

    d = get_config().transport_dir
    if not d:
        d = os.path.join(tempfile.gettempdir(), f"byteps-{os.getuid()}")
    os.makedirs(d, mode=0o700, exist_ok=True)
    return d


def endpoint_path(port: int, kind: str) -> str:
    """The rendezvous path a server on TCP port ``port`` advertises for
    ``kind`` — the shared client/server naming convention.  Raises
    (loudly, naming the path) when it would exceed the AF_UNIX
    ``sun_path`` limit: a silent truncation would rendezvous nowhere."""
    path = os.path.join(transport_dir(), f"ps-{port}{_SUFFIX[kind]}")
    if len(path.encode()) > _UDS_PATH_MAX:
        raise ValueError(
            f"transport rendezvous path {path!r} exceeds the AF_UNIX "
            f"path limit (~108 bytes incl. NUL); point "
            f"BYTEPS_TRANSPORT_DIR at a shorter directory")
    return path


_local_host_cache: Dict[str, bool] = {}


def is_local_host(host: str) -> bool:
    """True iff ``host`` names THIS machine — the gate for the auto
    fast path (a rendezvous file proves nothing about a remote host
    that happens to share a port number)."""
    cached = _local_host_cache.get(host)
    if cached is not None:
        return cached
    local = False
    if host in ("", "localhost", "127.0.0.1", "::1", "0.0.0.0"):
        local = True
    else:
        try:
            if host == socket.gethostname():
                local = True
            else:
                resolved = socket.gethostbyname(host)
                if resolved.startswith("127."):
                    local = True
                else:
                    try:
                        own = socket.gethostbyname_ex(
                            socket.gethostname())[2]
                    except OSError:
                        own = []
                    local = resolved in own
        except OSError:
            local = False
    _local_host_cache[host] = local
    return local


def parse_overrides(spec: str) -> Dict[str, str]:
    """``BYTEPS_TRANSPORT_OVERRIDES`` = ``"host:port=spec,..."``; spec
    may itself contain ``:`` (``unix:/path``), so split on the LAST
    ``=``."""
    out: Dict[str, str] = {}
    for pair in spec.split(","):
        pair = pair.strip()
        if not pair:
            continue
        addr, sep, tspec = pair.rpartition("=")
        if not sep or not addr:
            raise ValueError(
                f"bad BYTEPS_TRANSPORT_OVERRIDES entry {pair!r} "
                f"(want host:port=transport)")
        out[addr] = tspec.strip()
    return out


def _endpoint_alive(path: str, timeout: float = 0.25) -> bool:
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(timeout)
    try:
        s.connect(path)
        return True
    except OSError:
        return False
    finally:
        s.close()


def resolve_transport(addr: str, spec: str,
                      probe: bool = True) -> Tuple[str, Optional[str]]:
    """Map one ``host:port`` endpoint + transport spec to a concrete
    ``(kind, rendezvous_path)``.  Specs: ``auto`` (unix, then shm, when
    the host is local and the rendezvous answers a probe; TCP
    otherwise), a kind name (path derived from the port), or
    ``unix:/path`` / ``shm:/path`` explicit rendezvous."""
    spec = (spec or "auto").strip()
    host, _, port_s = addr.rpartition(":")
    if spec == "tcp":
        return "tcp", None
    if spec.startswith(("unix:", "shm:")):
        kind, _, path = spec.partition(":")
        return kind, path
    if spec in ("unix", "shm"):
        return spec, endpoint_path(int(port_s), spec)
    if spec != "auto":
        raise ValueError(
            f"unknown transport spec {spec!r} (want auto|tcp|unix|shm"
            f"|unix:/path|shm:/path)")
    if is_local_host(host):
        for kind in ("unix", "shm"):
            try:
                path = endpoint_path(int(port_s), kind)
            except ValueError:
                break  # overlong dir: auto quietly stays on TCP
            if os.path.exists(path) and (not probe
                                         or _endpoint_alive(path)):
                return kind, path
    return "tcp", None


# ------------------------------------------------------------- connecting


# AF_UNIX sockets start at net.core.*mem_default (~208 KB) and never
# autotune the way TCP loopback does — at multi-MB frames that means a
# wakeup per fifth of a frame; size them like the shm rings instead
_UDS_BUF = 4 * 1024 * 1024


def free_port() -> int:
    """Grab an ephemeral loopback TCP port (bind-and-release).  The
    one implementation behind every test and chaos harness that
    spawns endpoints on fresh ports."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def maybe_nodelay(sock) -> None:
    """Per-family socket tuning: TCP_NODELAY on TCP (a UDS/shm endpoint
    has no Nagle to disable), big send/recv buffers on AF_UNIX (no
    autotuning there — see ``_UDS_BUF``)."""
    fam = getattr(sock, "family", None)
    try:
        if fam in (socket.AF_INET, socket.AF_INET6):
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        elif fam == socket.AF_UNIX:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, _UDS_BUF)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, _UDS_BUF)
    except OSError:
        pass


def peer_label(client_address) -> str:
    """Human label for a connection's peer across transports (TCP
    tuples, the empty string a UDS accept yields, shm pseudo-addrs)."""
    if isinstance(client_address, tuple) and len(client_address) >= 2:
        return "%s:%s" % client_address[:2]
    return str(client_address) or "local"


def connection_kind(sock) -> str:
    if isinstance(sock, ShmConnection):
        return "shm"
    if getattr(sock, "family", None) == socket.AF_UNIX:
        return "unix"
    return "tcp"


def transport_connect(kind: str, path: Optional[str], addr: str,
                      timeout: float = 30.0):
    """Open one connection to ``addr`` over a resolved transport.
    Failures raise ``OSError`` exactly like a refused TCP connect, so
    every retry/failover caller treats the fast path uniformly."""
    if kind == "tcp":
        host, _, port_s = addr.rpartition(":")
        s = socket.create_connection((host, int(port_s)), timeout=timeout)
        maybe_nodelay(s)
        return s
    if kind == "unix":
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(timeout)
        maybe_nodelay(s)  # sizes the buffers (set before connect)
        try:
            s.connect(path)
        except OSError:
            s.close()
            raise
        return s
    if kind == "shm":
        return _connect_shm(path, addr, timeout)
    raise ValueError(f"unknown transport kind {kind!r}")


def _kick_listener(path: str) -> None:
    """Self-connect once to cycle a thread blocked in ``accept(2)`` —
    on AF_UNIX, neither ``shutdown`` nor ``close`` reliably wakes it,
    and while it blocks it holds the listener's file description open
    (still accepting!).  The kick connection reaches the loop's
    post-accept closed-guard, which drops it and exits the thread."""
    try:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(0.2)
        s.connect(path)
        s.close()
    except OSError:
        pass


def _cleanup_stale_uds(path: str) -> None:
    """Pre-bind hygiene: a socket file whose listener answers is a LIVE
    collision (loud); one that refuses is the corpse of a crashed/killed
    server — unlink it so the supervised-restart path can rebind."""
    if not os.path.exists(path):
        return
    if _endpoint_alive(path):
        raise OSError(
            errno.EADDRINUSE,
            f"transport endpoint {path} is already served by a live "
            f"process")
    try:
        os.unlink(path)
        bps_log.debug("transport: removed stale socket file %s", path)
    except OSError:
        pass


# ---------------------------------------------------------- shm transport


class _Ring:
    """One SPSC byte ring inside a shared mapping.

    Header (64-byte slot): ``u64 wpos | u64 rpos | u8 writer_closed |
    u8 reader_closed`` — positions are monotonically increasing byte
    counts (offset = pos % cap), so full/empty never ambiguate.  The
    producer owns ``wpos``, the consumer ``rpos``; payload bytes are
    stored before the position that publishes them (x86-TSO — see the
    module docstring)."""

    __slots__ = ("_mv", "_base", "_cap", "_data")

    # per-call transfer cap: positions publish every _CHUNK bytes, so
    # the producer refills space the consumer frees WHILE the consumer
    # is still copying the rest out — without it each side moves a
    # whole ring's worth per call and the two memcpys strictly
    # alternate (measured: the cap roughly doubles large-transfer
    # throughput on the 2-vCPU host)
    _CHUNK = 256 * 1024

    def __init__(self, mv: memoryview, base: int, cap: int):
        self._mv = mv
        self._base = base
        self._cap = cap
        self._data = base + _RING_HDR

    def _wpos(self) -> int:
        return struct.unpack_from("<Q", self._mv, self._base)[0]

    def _rpos(self) -> int:
        return struct.unpack_from("<Q", self._mv, self._base + 8)[0]

    def empty(self) -> bool:
        return self._wpos() == self._rpos()

    def writer_closed(self) -> bool:
        return self._mv[self._base + 16] != 0

    def reader_closed(self) -> bool:
        return self._mv[self._base + 17] != 0

    def close_writer(self) -> None:
        self._mv[self._base + 16] = 1

    def close_reader(self) -> None:
        self._mv[self._base + 17] = 1

    def write(self, src: memoryview) -> int:
        """Copy what fits (possibly 0) from ``src`` into the ring —
        never blocks; the connection's doorbell loop owns the waiting."""
        w, r = self._wpos(), self._rpos()
        n = min(self._cap - (w - r), len(src), self._CHUNK)
        if n <= 0:
            return 0
        off = w % self._cap
        first = min(n, self._cap - off)
        base = self._data
        self._mv[base + off:base + off + first] = src[:first]
        if n > first:
            self._mv[base:base + n - first] = src[first:n]
        struct.pack_into("<Q", self._mv, self._base, w + n)
        return n

    def read_into(self, dst: memoryview) -> int:
        w, r = self._wpos(), self._rpos()
        n = min(w - r, len(dst), self._CHUNK)
        if n <= 0:
            return 0
        off = r % self._cap
        first = min(n, self._cap - off)
        base = self._data
        dst[:first] = self._mv[base + off:base + off + first]
        if n > first:
            dst[first:n] = self._mv[base:base + n - first]
        struct.pack_into("<Q", self._mv, self._base + 8, r + n)
        return n


def _anon_fd(nbytes: int) -> int:
    """An anonymous shared-memory fd: ``memfd_create`` when the kernel
    allows it, else an immediately-unlinked temp file in the transport
    dir — either way nothing to leak on crash (the mapping dies with
    the last process holding it)."""
    try:
        fd = os.memfd_create("byteps-shm-ring")
    except (AttributeError, OSError):
        fd, name = tempfile.mkstemp(prefix="byteps-ring-",
                                    dir=transport_dir())
        os.unlink(name)
    os.ftruncate(fd, nbytes)
    return fd


class ShmConnection:
    """Socket-duck over two shm rings + the rendezvous UDS socket.

    The UDS socket doubles as the **doorbell**: a writer that takes its
    ring from empty to non-empty pokes one byte at the peer, and an
    idle reader blocks in ``select`` on the socket instead of polling —
    so an idle connection costs zero CPU, a fresh frame wakes the peer
    at kernel-wakeup latency (~50 us, not a poll backoff), and BULK
    data never touches the kernel (mid-stream chunks find the ring
    non-empty and skip both syscalls).  The select also doubles as the
    liveness backstop: a peer that exits without setting its closed
    flags (SIGKILL) surfaces as EOF on the socket, so neither side can
    wedge watching a dead ring.  The short yield-spin before the
    select keeps mid-transfer chunk handoffs (<= _CHUNK apart) off the
    kernel entirely.

    Thread shape matches a stream socket: one reader plus one writer
    thread may use the connection concurrently (distinct rings); the
    framing codec's ``_recv_exact``/``_send_buffers`` loops handle the
    partial reads/writes a bounded ring produces, which is exactly how
    frames larger than the ring stream through it."""

    _SLEEP_CAP = 0.001
    _SPIN = 64           # yield-spins before blocking on the doorbell
    _DOORBELL_WAIT = 0.05  # select backstop (doorbell loss, flag close)

    def __init__(self, mm: mmap.mmap, in_ring: _Ring, out_ring: _Ring,
                 uds: socket.socket, label: str):
        self._mm = mm
        self._in = in_ring
        self._out = out_ring
        self._uds = uds
        self._label = label
        self._timeout: Optional[float] = None
        self._closed = False

    # socket-surface admin ------------------------------------------------
    def settimeout(self, t) -> None:
        self._timeout = t

    def setsockopt(self, *a, **k) -> None:  # no-op (nodelay/linger)
        pass

    def fileno(self) -> int:
        try:
            return self._uds.fileno()
        except OSError:
            return -1

    def _peer_dead(self) -> bool:
        try:
            return self._uds.recv(1) == b""
        except (BlockingIOError, InterruptedError):
            return False
        except OSError:
            return True

    def _ring_doorbell(self) -> None:
        """One byte at the peer — only called on an empty->non-empty
        ring transition, so bulk streams ring at most once per drain.
        A full socket buffer (EAGAIN) is safe to ignore: bytes already
        queued there will wake the reader just the same."""
        try:
            self._uds.send(b"\x01")
        except (BlockingIOError, InterruptedError):
            pass
        except OSError:
            pass  # peer teardown races; flags/EOF surface it

    def _wait_doorbell(self, wait_s: float) -> None:
        """Idle-reader block: select on the doorbell socket, drain any
        rung bytes; EOF = peer died without flags (SIGKILL)."""
        import select as _select

        try:
            r, _, _ = _select.select([self._uds], [], [], wait_s)
        except (OSError, ValueError):
            raise ConnectionResetError(f"{self._label}: shm peer vanished")
        if r:
            try:
                if self._uds.recv(64) == b"":
                    raise ConnectionResetError(
                        f"{self._label}: shm peer vanished")
            except (BlockingIOError, InterruptedError):
                pass

    # data path -----------------------------------------------------------
    def recv_into(self, buf, nbytes: int = 0) -> int:
        view = memoryview(buf).cast("B")
        if nbytes:
            view = view[:nbytes]
        deadline = (None if self._timeout is None
                    else time.monotonic() + self._timeout)
        spins = 0
        while True:
            if self._closed:
                raise OSError(errno.EBADF, f"{self._label}: closed")
            n = self._in.read_into(view)
            if n:
                return n
            if self._in.writer_closed():
                return 0  # clean EOF, the FIN analog
            if deadline is not None and time.monotonic() >= deadline:
                raise socket.timeout(f"{self._label}: shm recv timed out")
            # brief yield-spin first: mid-transfer the peer publishes
            # the next chunk within microseconds, and a kernel block
            # would quantize the stream to wakeup latency
            spins += 1
            if spins <= self._SPIN:
                time.sleep(0)
                continue
            self._wait_doorbell(self._DOORBELL_WAIT)

    def sendmsg(self, buffers) -> int:
        views = [memoryview(b).cast("B") for b in buffers if len(b)]
        if not views:
            return 0
        deadline = (None if self._timeout is None
                    else time.monotonic() + self._timeout)
        sleep = 0.0
        spins = 0
        while True:
            if self._closed:
                raise OSError(errno.EBADF, f"{self._label}: closed")
            was_empty = self._out.empty()
            total = 0
            for v in views:
                n = self._out.write(v)
                total += n
                if n < len(v):
                    break
            if total:
                if was_empty:
                    self._ring_doorbell()
                return total
            if self._out.reader_closed() or self._peer_dead():
                raise BrokenPipeError(
                    f"{self._label}: shm peer closed")
            if deadline is not None and time.monotonic() >= deadline:
                raise socket.timeout(f"{self._label}: shm send timed out")
            # ring full: the reader is actively draining — poll with a
            # short backoff (it frees space every _CHUNK, no doorbell
            # exists in this direction)
            spins += 1
            if spins <= self._SPIN:
                time.sleep(0)
                continue
            time.sleep(sleep)
            sleep = min(self._SLEEP_CAP, sleep * 2.0 + 1e-6)

    def sendall(self, data) -> None:
        view = memoryview(data).cast("B")
        while len(view):
            view = view[self.sendmsg([view]):]

    # teardown ------------------------------------------------------------
    def shutdown(self, how=None) -> None:
        try:
            self._out.close_writer()
            self._in.close_reader()
        except (ValueError, IndexError):  # mapping already released
            pass

    def close(self) -> None:
        if self._closed:
            return
        self.shutdown()
        self._closed = True
        try:
            self._uds.close()
        except OSError:
            pass
        # the mmap itself is freed by refcount once the last thread
        # blocked in recv/send observes _closed and drops its views —
        # an eager munmap here would race them


def _ring_bytes() -> int:
    from ..common.config import get_config

    return max(64 * 1024, get_config().transport_shm_mb << 20)


def _connect_shm(path: str, addr: str, timeout: float) -> ShmConnection:
    """Client half of the shm rendezvous: create the anonymous mapping,
    pass its fd over the UDS socket (SCM_RIGHTS), wait for the
    server's ack."""
    uds = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    uds.settimeout(timeout if timeout else 10.0)
    try:
        uds.connect(path)
        cap = _ring_bytes()
        total = 2 * (_RING_HDR + cap)
        fd = _anon_fd(total)
        try:
            mm = mmap.mmap(fd, total)
            socket.send_fds(
                uds, [_HANDSHAKE_MAGIC + struct.pack("<QQ", cap, cap)],
                [fd])
        finally:
            os.close(fd)
        ack = uds.recv(2)
        while len(ack) == 1:  # stream socket: the two bytes may split
            more = uds.recv(1)
            if not more:
                break
            ack += more
        if ack != b"OK":
            raise ConnectionError(
                f"shm handshake with {addr} rejected: {ack!r}")
    except OSError:
        uds.close()
        raise
    uds.setblocking(False)
    mv = memoryview(mm)
    c2s = _Ring(mv, 0, cap)
    s2c = _Ring(mv, _RING_HDR + cap, cap)
    return ShmConnection(mm, in_ring=s2c, out_ring=c2s, uds=uds,
                         label=f"shm->{addr}")


def _accept_shm(conn: socket.socket) -> ShmConnection:
    """Server half: receive the mapping fd + ring sizes, ack."""
    conn.settimeout(10.0)
    want = len(_HANDSHAKE_MAGIC) + 16
    msg, fds, _, _ = socket.recv_fds(conn, want, 4)
    while len(msg) < want:
        more = conn.recv(want - len(msg))
        if not more:
            break
        msg += more
    try:
        if len(msg) < want or not msg.startswith(_HANDSHAKE_MAGIC):
            raise ConnectionError(f"bad shm handshake: {msg[:16]!r}")
        if not fds:
            raise ConnectionError("shm handshake carried no fd")
        cap_c2s, cap_s2c = struct.unpack_from(
            "<QQ", msg, len(_HANDSHAKE_MAGIC))
        if not (0 < cap_c2s <= _MAX_RING and 0 < cap_s2c <= _MAX_RING):
            raise ConnectionError(
                f"shm handshake ring sizes out of range: "
                f"{cap_c2s}/{cap_s2c}")
        mm = mmap.mmap(fds[0], 2 * _RING_HDR + cap_c2s + cap_s2c)
    finally:
        for fd in fds:
            os.close(fd)
    conn.sendall(b"OK")
    conn.setblocking(False)
    mv = memoryview(mm)
    c2s = _Ring(mv, 0, cap_c2s)
    s2c = _Ring(mv, _RING_HDR + cap_c2s, cap_s2c)
    return ShmConnection(mm, in_ring=c2s, out_ring=s2c, uds=conn,
                         label="shm-peer")


# ------------------------------------------------------- server-side bind


class _DelegatingUnixServer(socketserver.ThreadingUnixStreamServer):
    """UDS listener sharing one primary server's state: the handler
    class reads ``self.server.store`` / ``.engine`` / connection
    tracking — all resolved on the PRIMARY via ``__getattr__``, so the
    TCP and local listeners serve literally the same objects."""

    daemon_threads = True

    def __init__(self, path: str, handler_cls, primary):
        self.primary = primary
        super().__init__(path, handler_cls)

    def __getattr__(self, name):
        if name == "primary":
            raise AttributeError(name)
        return getattr(self.primary, name)

    def get_request(self):
        request, client_address = super().get_request()
        maybe_nodelay(request)  # size the UDS buffers server-side too
        return request, client_address


class LocalEndpoints:
    """The server half of endpoint advertisement: bind the UDS and shm
    rendezvous for one TCP port and serve accepted connections through
    the SAME handler class (and primary server state) as the TCP
    listener.  ``close(unlink=False)`` is the crash-shaped teardown
    ``PSServer.kill`` uses — accepts stop, but the stale rendezvous
    files stay behind exactly like a SIGKILLed shard's would (the next
    bind cleans them up)."""

    def __init__(self, port: int, handler_cls, primary):
        self._closed = False
        self._unix_srv = None
        self._shm_sock = None
        self._paths = []
        self.kinds = []
        self._spath = None
        try:
            upath = endpoint_path(port, "unix")
            _cleanup_stale_uds(upath)
            self._unix_srv = _DelegatingUnixServer(upath, handler_cls,
                                                   primary)
            self._paths.append(upath)
            self.kinds.append("unix")
            threading.Thread(
                target=self._unix_srv.serve_forever,
                kwargs={"poll_interval": 0.05},
                name=f"bps-uds-{port}", daemon=True).start()

            spath = endpoint_path(port, "shm")
            _cleanup_stale_uds(spath)
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.bind(spath)
            s.listen(16)
            self._shm_sock = s
            self._spath = spath
            self._paths.append(spath)
            self.kinds.append("shm")
            threading.Thread(
                target=self._shm_accept_loop,
                args=(handler_cls, primary),
                name=f"bps-shm-{port}", daemon=True).start()
        except BaseException:
            self.close(unlink=True)
            raise

    def _shm_accept_loop(self, handler_cls, primary) -> None:
        while not self._closed:
            try:
                conn, _ = self._shm_sock.accept()
            except OSError:
                return
            if self._closed:
                # the accept raced close(): a thread blocked in
                # accept(2) keeps the listening socket's file
                # description alive past close(), so one late connect
                # can still be handed out — refuse it, a killed server
                # must not serve
                try:
                    conn.close()
                except OSError:
                    pass
                return

            def _serve(conn=conn):
                try:
                    shm_conn = _accept_shm(conn)
                except Exception as e:
                    bps_log.debug("shm handshake failed: %s", e)
                    try:
                        conn.close()
                    except OSError:
                        pass
                    return
                # BaseRequestHandler.__init__ runs handle() inline —
                # this thread IS the connection's handler thread.
                # socketserver closes its own requests after handle();
                # this manual path must too, or the rendezvous socket
                # fd and the peer's EOF linger per dead connection
                try:
                    handler_cls(shm_conn, ("shm", peer_label("")), primary)
                finally:
                    shm_conn.close()

            threading.Thread(target=_serve, daemon=True,
                             name="bps-shm-conn").start()

    def close(self, unlink: bool = True) -> None:
        if self._closed:
            return
        self._closed = True
        if self._unix_srv is not None:
            try:
                self._unix_srv.shutdown()
                self._unix_srv.server_close()
            except OSError:
                pass
        if self._shm_sock is not None:
            try:
                self._shm_sock.close()
            except OSError:
                pass
            # a thread blocked in accept(2) holds the listener's file
            # description past close() (and AF_UNIX shutdown() does
            # not wake it) — kick it through the closed-guard so the
            # rendezvous actually stops answering
            # ... and do not return while it still does: the woken
            # thread drops the listener a scheduling quantum later, and
            # a supervised restart that probes the path in that window
            # (a loaded host) reads the corpse as a live collision
            if self._spath is not None:
                for _ in range(20):
                    _kick_listener(self._spath)
                    if not _endpoint_alive(self._spath, timeout=0.05):
                        break
        if unlink:
            for p in self._paths:
                try:
                    os.unlink(p)
                except OSError:
                    pass


# ------------------------------------------------- registered buffers


def rdma_available() -> bool:
    """True when an RDMA verbs stack is importable — the gate for the
    hardware half of the registered-buffer experiment (ps-lite's RDMA
    van registers its buffers with the NIC so the HCA can DMA without
    page-pinning per message).  This container has no verbs stack, so
    the software half below is what runs; the gate keeps the seam
    honest instead of stubbing verbs calls that could never execute."""
    try:  # pragma: no cover - hardware-specific
        import pyverbs  # noqa: F401
        return True
    except ImportError:
        return False


class RegisteredBufferPool:
    """Preallocated, recycled receive buffers — the software half of
    ps-lite's registered-memory idea (RDMAVan pins each buffer once and
    reuses it for every message; ours cannot pin without verbs, but the
    allocator-pressure half of the win is hardware-independent).

    The wire codec's ``_recv_exact`` allocates a fresh ``bytearray`` per
    frame; at disagg KV-ship rates (one multi-KB frame per block) that
    is an allocation per block on the receive path.  A pool caller does

        buf = pool.acquire(n)      # recycled when a fit exists
        ... sock.recv_into(memoryview(buf)[...]) ...
        pool.release(buf)          # back to the free list

    Buffers are bucketed by power-of-two capacity and handed out
    oversized (callers slice to ``n``); the pool holds at most
    ``max_buffers`` free buffers per bucket and ``max_bytes`` total —
    beyond that, release drops the buffer to the allocator (bounded
    memory, no leak on bursty frame sizes).  Thread-safe; acquisition
    never blocks (a miss just allocates)."""

    def __init__(self, max_buffers: int = 8,
                 max_bytes: int = 64 * 1024 * 1024):
        self.max_buffers = int(max_buffers)
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._free: Dict[int, list] = {}
        self._held_bytes = 0
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _bucket(n: int) -> int:
        b = 4096
        while b < n:
            b <<= 1
        return b

    def acquire(self, n: int) -> bytearray:
        """A buffer of capacity >= ``n`` (callers slice their view)."""
        b = self._bucket(n)
        with self._lock:
            lst = self._free.get(b)
            if lst:
                self.hits += 1
                self._held_bytes -= b
                return lst.pop()
            self.misses += 1
        return bytearray(b)

    def release(self, buf: bytearray) -> None:
        b = len(buf)
        with self._lock:
            lst = self._free.setdefault(b, [])
            if (len(lst) < self.max_buffers
                    and self._held_bytes + b <= self.max_bytes):
                lst.append(buf)
                self._held_bytes += b
            # else: drop to the allocator — bounded pool

    def recv_exact(self, sock, n: int) -> memoryview:
        """``_recv_exact`` against a pooled buffer: a length-``n``
        memoryview whose backing buffer came from (and must go back
        to) this pool via :meth:`recycle`."""
        buf = self.acquire(n)
        view = memoryview(buf)[:n]
        got = 0
        while got < n:
            r = sock.recv_into(view[got:])
            if r == 0:
                self.release(buf)
                raise ConnectionError(
                    f"peer closed mid-frame ({got}/{n} bytes)")
            got += r
        return view

    def recycle(self, view: memoryview) -> None:
        """Return a :meth:`recv_exact` view's backing buffer."""
        self.release(view.obj)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "held_bytes": self._held_bytes,
                    "free_buffers": sum(len(v)
                                        for v in self._free.values())}
