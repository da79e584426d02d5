"""Serving frontends: in-process ``ServeClient`` and a thin TCP server.

The TCP layer reuses the length-prefixed wire helpers of
``engine/ps_server.py`` (``_encode``/``_decode`` — the same u8-op,
raw-numpy-payload framing the PS tier speaks), so a serve process slots
into the launcher the way a PS shard does: ``DMLC_ROLE=serve`` runs
:func:`serve_from_env`.

Wire ops (request := the ps_server frame; one request per round trip,
except STREAM whose reply is a frame *sequence*):

    0 = SUBMIT  name = JSON {"max_new_tokens", "seed", "priority",
                             "resume"}
                arr  = int32 prompt tokens [T] (with ``resume`` = k > 0
                the trailing k entries are tokens another replica
                already emitted — the router's failover re-dispatch;
                the engine resumes the stream bit-exactly)
                reply: status=0, name = request id, arr = int32 tokens;
                rejections (queue full, infeasible request) come back
                as status=1 with the typed error's message — the
                connection survives, clients can back off and retry.
    1 = STATS   reply payload = JSON engine metrics summary
    2 = PING    liveness
    3 = STREAM  same request frame as SUBMIT; the reply is one frame
                per emitted token (status=0, name="t", arr=[tok]) and
                a terminal frame (status=0, name="end", arr = the full
                token sequence).  A status=1 frame at any point carries
                a typed error message and ends the stream.  This is
                what lets the router record how far a stream got before
                a replica died — the failover re-dispatch resumes from
                exactly the tokens that crossed the wire.
    4 = CANCEL  name = JSON {"rid"}: eagerly cancel the in-flight
                request that was submitted with that caller-chosen
                ``rid`` param — from a SECOND connection, since the
                streaming one is busy relaying tokens.  The engine's
                eager cancel reclaims the slot (and paged KV blocks)
                same-tick; the cancelled stream ends with its normal
                "end" frame carrying whatever was emitted.  A rid that
                has not arrived yet is tombstoned so a cancel racing
                its own submit still lands (bounded set).  Reply
                payload = JSON {"cancelled": bool}.
    5 = JOURNAL name = JSON list of router-HA journal entries (routers
                only — serving/router.py streams active-router state to
                standbys through it; a plain serve frontend answers
                "bad op").  Reply payload = JSON {"epoch": receiver's
                epoch} — how a deposed active discovers a takeover
                happened (split-brain guard on the journal path).

SUBMIT/STREAM params may also carry ``epoch`` (the dispatching
router's fencing token — the engine refuses values below its
high-water with the typed ``EpochFencedError``, the split-brain guard
of docs/serving.md "Router HA"), ``rid`` (caller-chosen request id for
OP_CANCEL) and ``tenant`` (fair-share accounting at the router tier;
replicas ignore it).

SUBMIT blocks the *connection* until the request finishes — per-request
streaming rides OP_STREAM (or stays in-process via
``Request.__iter__``); concurrency across the wire comes from
concurrent connections, which the engine batches into one decode pool
(that is the whole point of continuous batching).

A client socket that disappears mid-STREAM triggers the engine's eager
``cancel()`` path: the slot (and on paged engines the non-shared KV
blocks and prefix references) returns to the pool the same tick the
broken pipe is noticed, not when the abandoned request would have
finished.
"""

from __future__ import annotations

import collections
import json
import socketserver
import threading
import time
from typing import List, Optional

import numpy as np

from ..common import logging as bps_log
from ..engine.ps_server import _decode, _encode
from ..engine.transport import (LocalEndpoints, maybe_nodelay,
                                resolve_transport, transport_connect)
from ..engine.wire import hard_reset
from .engine import Request, ServingEngine
from .scheduler import AdmissionError

OP_SUBMIT, OP_STATS, OP_PING, OP_STREAM, OP_CANCEL, OP_JOURNAL = range(6)
# disaggregated prefill/decode (serving/disagg, docs/serving.md
# "Disaggregated tiers"): one frame per shipped KV block — name = JSON
# {"key","i","n","pos","geom","digest"}, payload = the block's raw K/V
# bytes.  Replies: status=0 JSON ack, or status=1 with a typed
# KVShip* error name the sender maps to retry/abort.
OP_KV_BLOCKS = 6

__all__ = ["ServeClient", "ServeFrontend", "RemoteServeClient",
           "ServeConnectionError", "ServeReplyError", "serve",
           "serve_from_env", "build_engine_from_env", "OP_SUBMIT",
           "OP_STATS", "OP_PING",
           "OP_STREAM", "OP_CANCEL", "OP_JOURNAL", "OP_KV_BLOCKS"]


class ServeConnectionError(ConnectionError):
    """The serve frontend (or router) went away mid-conversation — the
    connection died or stalled past the client timeout.  Typed so
    callers can distinguish a dead endpoint (retry elsewhere / fail
    over) from a replica-side error reply (status=1 ``RuntimeError``,
    which would recur on retry)."""


# status=1 error names a multi-router client may safely re-issue to
# ANOTHER router: the refusal says "this router cannot serve you", not
# "your request is wrong".  Everything else is non-retryable by default
# — a typed refusal that would recur (WeightsMismatchError, ValueError,
# QueueFullError backpressure, a tier-wide ReplicaLostError) must
# surface to the caller, never be retried as if the router were dead.
_RETRYABLE_REPLY_NAMES = frozenset({"RouterStandbyError"})

# status=1 names that mean "shed under overload" (the router's
# SLO-class admission door — docs/serving.md "Elastic capacity & SLO
# classes"): NOT router-rotation-retryable (every router fronts the
# same saturated tier; rotating would just burn the deadline), but
# safe for the CALLER to retry with backoff — the request was never
# placed.  ``ServeReplyError.shed`` flags them.
_SHED_REPLY_NAMES = frozenset({"OverloadShedError"})


class ServeReplyError(RuntimeError):
    """A status=1 reply frame: the endpoint is alive and answered with
    a typed error.  ``name`` is the server-side error class name parsed
    off the payload; ``retryable`` tells the multi-router failover loop
    whether re-issuing the request to the NEXT router can possibly
    help (a standby refusal) or the refusal would recur anywhere
    (weights mismatch, infeasible request, tier failure) — retrying
    those as if the router were dead would burn the deadline repeating
    a deterministic error.  ``shed`` marks an SLO-class overload shed:
    back off and resubmit later (``retryable`` stays False — a
    DIFFERENT router cannot help, only time can)."""

    def __init__(self, msg: str, name: str = ""):
        self.name = name
        self.retryable = name in _RETRYABLE_REPLY_NAMES
        self.shed = name in _SHED_REPLY_NAMES
        super().__init__(msg)


class ServeClient:
    """In-process client: submit -> stream tokens, cancel, drain.

    A thin convenience veneer over :class:`ServingEngine` that starts
    the background tick thread on first use and owns its shutdown."""

    def __init__(self, engine: ServingEngine):
        self.engine = engine

    def submit(self, prompt, max_new_tokens: int, *, seed: int = 0,
               priority: int = 0) -> Request:
        self.engine.start()
        return self.engine.submit(prompt, max_new_tokens, seed=seed,
                                  priority=priority)

    def stream(self, prompt, max_new_tokens: int, *, seed: int = 0,
               priority: int = 0):
        """Iterator of tokens as the engine emits them."""
        return iter(self.submit(prompt, max_new_tokens, seed=seed,
                                priority=priority))

    def generate(self, prompt, max_new_tokens: int, *, seed: int = 0,
                 priority: int = 0,
                 timeout: Optional[float] = None) -> np.ndarray:
        """Blocking submit -> full token array."""
        return self.submit(prompt, max_new_tokens, seed=seed,
                           priority=priority).result(timeout)

    def cancel(self, req: Request) -> None:
        self.engine.cancel(req)

    def drain(self, timeout: Optional[float] = None) -> None:
        self.engine.drain(timeout)

    def close(self) -> None:
        self.engine.stop()


# ------------------------------------------------------------------ TCP tier


def _split_resume(params: dict, arr):
    """THE wire contract for SUBMIT/STREAM request arrays: ``resume`` =
    k > 0 marks the trailing k entries as already-emitted tokens (a
    failover re-dispatch or client retry); the rest is the prompt.
    Shared by the serve frontend and the router so the two tiers can
    never silently disagree on the frame layout."""
    toks = np.asarray(arr, np.int32).reshape(-1)
    k = int(params.get("resume", 0))
    return (toks[:-k], toks[-k:]) if k > 0 else (toks, None)


def _wire_cancel(addr: str, params: dict, timeout: Optional[float],
                 transport_pref: Optional[str] = None) -> bool:
    """One OP_CANCEL round-trip on a fresh short-lived connection —
    the single wire implementation behind ``RemoteServeClient.cancel``
    and the router's replica-side forward (which would otherwise pay a
    second, unused connection just to construct a client)."""
    kind, path = resolve_transport(addr, transport_pref)
    try:
        s = transport_connect(kind, path, addr, timeout=timeout)
    except OSError as e:
        raise ServeConnectionError(
            f"serve frontend {addr} unreachable for cancel: "
            f"{e}") from e
    try:
        s.sendall(_encode(OP_CANCEL, json.dumps(params), None))
        status, _, _, payload = _decode(s)
    except (ConnectionError, OSError, ValueError) as e:
        raise ServeConnectionError(
            f"serve frontend {addr} died mid-cancel: "
            f"{e}") from e
    finally:
        try:
            s.close()
        except OSError:
            pass
    if status != 0:
        msg = payload.decode()
        raise ServeReplyError(f"serve error: {msg!r}",
                              name=msg.split(":", 1)[0].strip())
    return bool(json.loads(payload.decode()).get("cancelled"))


def _parse_submit(engine: ServingEngine, name: str, arr, stager=None):
    """Decode a SUBMIT/STREAM frame into an engine submit.

    Disagg params (docs/serving.md "Disaggregated tiers"): a PREFILL
    dispatch carries ``ship_to`` (the decode replica's address) +
    ``kv_ship`` (the ship id) — the engine parks the finished KV for
    the post-reply ship.  A DECODE dispatch carries ``kv_ship`` alone:
    the staged blocks are claimed from the stager here and adopted at
    admission in place of re-prefill; a missing/partial staging just
    means normal (re-)prefill — never a wrong answer."""
    params = json.loads(name) if name else {}
    prompt, resumed = _split_resume(params, arr)
    kv = None
    if (stager is not None and params.get("kv_ship")
            and not params.get("ship_to")):
        staged = stager.take(str(params["kv_ship"]))
        if staged is not None:
            if staged["pos"] == int(prompt.shape[0]):
                kv = staged["ids"]
            else:
                engine.release_kv_ids(staged["ids"])
    # the router-epoch fence rides INTO the submit so check and
    # admission are atomic: a deposed router's dispatch must be refused
    # typed, never admitted (the split-brain guard — docs/serving.md
    # "Router HA")
    try:
        req = engine.submit(
            prompt, int(params.get("max_new_tokens", 16)),
            seed=int(params.get("seed", 0)),
            priority=int(params.get("priority", 0)),
            resume_tokens=resumed,
            epoch=params.get("epoch"),
            keep_kv=bool(params.get("ship_to")),
            kv_blocks=kv)
    except Exception:
        # the engine takes block ownership only on a successful return
        engine.release_kv_ids(kv)
        raise
    return req, params


class _ServeHandler(socketserver.BaseRequestHandler):
    def setup(self):
        track = getattr(self.server, "_track_conn", None)
        if track is not None:
            track(self.request)

    def _stream(self, engine: ServingEngine, sock, req: Request) -> bool:
        """Relay ``req``'s tokens as one frame each, then the terminal
        frame.  Returns False when the CLIENT went away — the caller
        must stop serving this connection; the request is eagerly
        cancelled so its slot (and paged KV blocks) free this tick."""
        try:
            for tok, t_emit in req.stamped():
                sock.sendall(_encode(0, "t", np.asarray([tok], np.int32)))
                # the hand-off a client's token gap contains: the tick
                # thread's _emit to this thread's frame on the wire
                engine.metrics.observe("emit_to_wire",
                                       time.monotonic() - t_emit)
            sock.sendall(_encode(0, "end",
                                 np.asarray(req.tokens, np.int32)))
            return True
        except RuntimeError as e:
            # engine died mid-stream: a typed status=1 frame ends the
            # stream loudly (the iterator already drained to _END)
            try:
                sock.sendall(_encode(1, "", None,
                                     f"{type(e).__name__}: {e}".encode()))
            except OSError:
                pass
            return True
        except OSError:
            # client disconnected mid-stream: eager-cancel so the slot
            # and non-shared blocks are reclaimed same-tick, not when
            # the abandoned stream would have finished
            engine.cancel(req)
            return False

    def handle(self):  # one connection, many requests
        engine: ServingEngine = self.server.engine  # type: ignore
        sock = self.request
        maybe_nodelay(sock)
        try:
            while True:
                try:
                    op, name, arr, payload_in = _decode(sock)
                except (ConnectionError, OSError):
                    return
                try:
                    if op in (OP_SUBMIT, OP_STREAM):
                        req, params = _parse_submit(
                            engine, name, arr,
                            stager=self.server.kv_stager(create=False))
                        rid = params.get("rid")
                        if rid and self.server.register_rid(str(rid),
                                                            req):
                            # an OP_CANCEL for this rid raced ahead of
                            # the submit (tombstoned): honor it now
                            engine.cancel(req)
                        try:
                            if op == OP_SUBMIT:
                                toks = req.result(timeout=float(
                                    params.get("timeout", 300.0)))
                                if params.get("ship_to"):
                                    # disagg prefill leg: ship the
                                    # parked KV AFTER the request
                                    # finished, report the outcome in
                                    # the reply name (the router's
                                    # prefill_ship reads it; plain
                                    # clients never set ship_to)
                                    info = self.server.ship_kv(
                                        req, params)
                                    reply = _encode(
                                        0, json.dumps(info), toks)
                                else:
                                    reply = _encode(0, str(req.id),
                                                    toks)
                            else:
                                if not self._stream(engine, sock, req):
                                    return
                                continue
                        finally:
                            if rid:
                                self.server.unregister_rid(str(rid),
                                                           req)
                    elif op == OP_CANCEL:
                        params = json.loads(name) if name else {}
                        if "epoch" in params:
                            # a deposed router must not cancel work the
                            # takeover epoch re-dispatched; the fence
                            # stays held across the cancel so a newer
                            # epoch's re-dispatch cannot interleave
                            # between check and cancel
                            with engine.epoch_fence(
                                    int(params["epoch"])):
                                ok = self.server.cancel_rid(
                                    str(params.get("rid", "")))
                        else:
                            ok = self.server.cancel_rid(
                                str(params.get("rid", "")))
                        reply = _encode(
                            0, "", None,
                            json.dumps({"cancelled": ok}).encode())
                    elif op == OP_STATS:
                        payload = json.dumps(
                            {**engine.metrics.summary(),
                             # engine identity: the weights fingerprint
                             # the router's registration handshake
                             # compares before trusting this replica
                             # with resumes (serving/router.py)
                             "weights_fingerprint": engine.weights_fp,
                             # what ran, not what was asked for: the
                             # device JAX reports and the attention
                             # path the engine resolved to
                             "device": engine.device,
                             "attention_path": engine.attention_path,
                             "compile_counts": engine.compile_counts(),
                             "occupancy": engine.pool.occupancy(),
                             "queue_depth": engine.scheduler.depth,
                             "prefix_cache": (engine.prefix.stats()
                                              if engine.prefix is not None
                                              else None),
                             # paged KV pool accounting (None on dense
                             # engines) — free/used/shared block counts
                             # next to the prefix stats they interact
                             # with (docs/serving.md "Paged KV cache")
                             "kv_blocks": (engine.pool.block_stats()
                                           if engine.paged else None),
                             # the same registry snapshot /metrics.json
                             # serves — one stats surface, two transports
                             # (docs/observability.md)
                             "metrics": engine.metrics.registry.snapshot()})
                        reply = _encode(0, "", None, payload.encode())
                    elif op == OP_KV_BLOCKS:
                        # disagg decode leg: one shipped KV block into
                        # the stager (serving/disagg/ship.py owns the
                        # sequence/digest/geometry verification)
                        reply = self.server.kv_stager().handle(
                            name, payload_in)
                    elif op == OP_PING:
                        reply = _encode(0, "", None)
                    else:
                        reply = _encode(1, "", None,
                                        f"bad op {op}".encode())
                except AdmissionError as e:
                    # typed backpressure: status=1 + reason, socket lives
                    reply = _encode(1, "", None,
                                    f"{type(e).__name__}: {e}".encode())
                except Exception as e:
                    reply = _encode(
                        1, "", None, f"{type(e).__name__}: {e}".encode())
                sock.sendall(reply)
        except Exception as e:  # pragma: no cover - teardown races
            bps_log.debug("serve handler exit: %s", e)


class ServeFrontend(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, addr, engine: ServingEngine):
        super().__init__(addr, _ServeHandler)
        self.engine = engine
        # live client sockets, so kill() can die like a crashed process
        # (sever mid-stream connections, not just stop accepting)
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._killing = False
        # OP_CANCEL bookkeeping: caller-chosen rid -> in-flight Request,
        # plus a bounded tombstone set for cancels that raced ahead of
        # their own submit (the registering handler then cancels
        # immediately instead of the cancel being silently lost)
        self._rids: dict = {}
        self._rid_lock = threading.Lock()
        self._rid_tombs: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        # recently-FINISHED rids (bounded): a cancel arriving after its
        # request completed is "too late", not "too early" — without
        # this it would be tombstoned and silently cancel the next
        # request reusing the rid at admission
        self._rid_done: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        # disagg KV stager (decode replicas; serving/disagg/ship.py) —
        # built lazily on the first OP_KV_BLOCKS frame, because only
        # paged engines can stage and most frontends never receive one
        self._kv_stager = None
        self._kv_stager_lock = threading.Lock()
        # colocated fast path (docs/wire.md "Transports"): advertise a
        # UDS + shm rendezvous next to the TCP port, served by the SAME
        # handler over the same engine, unless pinned to TCP
        self.local_endpoints = None
        from ..common.config import get_config

        if get_config().transport != "tcp":
            try:
                self.local_endpoints = LocalEndpoints(
                    self.server_address[1], _ServeHandler, self)
            except ValueError:
                super().server_close()
                raise
            except OSError as e:
                bps_log.warning(
                    "serve frontend: local transport endpoints "
                    "unavailable (%s); serving TCP only", e)
        engine.start()

    # ------------------------------------------------- disagg KV ship

    def kv_stager(self, create: bool = True):
        """The engine's KV stager (decode side of a disagg ship).
        ``create=False`` returns None until the first OP_KV_BLOCKS
        frame built it — the submit path's claim probe must not pay a
        stager on frontends that never receive ships.  Raises typed on
        a dense engine: there is no block pool to stage into."""
        with self._kv_stager_lock:
            if self._kv_stager is None and create:
                from .disagg.ship import KVShipGeometryError, KVStager

                if not self.engine.paged:
                    raise KVShipGeometryError(
                        "this replica's engine is dense (paged=False) "
                        "— it cannot stage shipped KV blocks")
                self._kv_stager = KVStager(self.engine)
            return self._kv_stager

    def ship_kv(self, req: Request, params: dict) -> dict:
        """Prefill leg: ship ``req``'s parked KV to the decode replica
        named by ``ship_to``.  Never raises — every failure downgrades
        to ``{"shipped": False, "error": ...}`` alongside the (valid)
        token reply, and the router re-prefills decode-side."""
        from .disagg.ship import KVShipError, ship_parked

        parked = self.engine.take_parked_kv(req.id)
        if parked is None:
            return {"shipped": False,
                    "error": "no parked KV (dense engine, non-DONE "
                             "finish, or parked-cap eviction)"}
        try:
            return ship_parked(
                self.engine, str(params["ship_to"]),
                str(params.get("kv_ship", req.id)), parked,
                metrics=self.engine.metrics)
        except KVShipError as e:
            bps_log.warning("disagg ship for request %d failed: %s",
                            req.id, e)
            return {"shipped": False,
                    "error": f"{type(e).__name__}: {e}"}
        finally:
            self.engine.release_kv_ids(parked["ids"])

    # ------------------------------------------------ OP_CANCEL registry

    def register_rid(self, rid: str, req: Request) -> bool:
        """Associate a caller-chosen request id with its in-flight
        engine request.  Returns True when an OP_CANCEL for this rid
        already arrived (tombstoned) — the caller must cancel the
        request immediately."""
        with self._rid_lock:
            self._rids[rid] = req
            self._rid_done.pop(rid, None)  # the rid is live again
            tombed = rid in self._rid_tombs
            if tombed:
                del self._rid_tombs[rid]
            return tombed

    def unregister_rid(self, rid: str, req: Optional[Request] = None
                       ) -> None:
        """Drop the registration — only while it still points at
        ``req`` (a stalled old leg finishing late must not clobber a
        re-dispatch's newer registration of the same rid), and record
        the rid as recently finished."""
        with self._rid_lock:
            if req is not None and self._rids.get(rid) is not req:
                return
            self._rids.pop(rid, None)
            self._rid_done[rid] = None
            while len(self._rid_done) > 1024:
                self._rid_done.popitem(last=False)

    def cancel_rid(self, rid: str) -> bool:
        """Cancel the in-flight request registered under ``rid`` (the
        engine's eager cancel: slot + non-shared paged blocks reclaimed
        same-tick).  An unknown rid is tombstoned (bounded) so a cancel
        racing AHEAD of its own submit still lands — unless the rid
        recently FINISHED here, in which case the cancel is simply too
        late (tombstoning it would cancel the next request reusing the
        rid).  Returns whether a live request was cancelled."""
        with self._rid_lock:
            req = self._rids.get(rid)
            if req is None:
                if rid not in self._rid_done:
                    self._rid_tombs[rid] = None
                    while len(self._rid_tombs) > 1024:
                        self._rid_tombs.popitem(last=False)
                return False
        self.engine.cancel(req)
        return True

    def _track_conn(self, sock) -> None:
        with self._conns_lock:
            # the _killing check must share kill()'s critical section:
            # checked outside it, a handler could pass the check, block
            # on the lock while kill() swaps the set, and then register
            # a connection nobody will ever reset
            if not self._killing:
                self._conns.add(sock)
                # drop references the handlers already finished with
                self._conns = {s for s in self._conns
                               if s.fileno() != -1}
                return
        # a connection that slipped through between kill() and the
        # listener actually closing (socketserver's shutdown can lag a
        # poll interval): a dead process serves nobody
        hard_reset(sock)

    def kill(self) -> None:
        """Die like a crashed replica (the PSServer.kill discipline):
        hard-reset every live client connection AND stop accepting, so
        in-flight streams see ECONNRESET mid-frame — what the router's
        failover path (and RemoteServeClient's typed
        ``ServeConnectionError``) must absorb.  Connections are severed
        FIRST: ``shutdown()`` can wait up to the serve_forever poll
        interval, and a fast engine would stream a whole request's
        remaining tokens into the socket in that window — a crash cuts
        the wire mid-token, so the kill must too (and ``_killing``
        makes any connection accepted inside that window die
        unserved).  Chaos/test only."""
        self._killing = True
        with self._conns_lock:
            conns, self._conns = set(self._conns), set()
        for c in conns:
            hard_reset(c)
        self.shutdown()
        if self.local_endpoints is not None:
            self.local_endpoints.close(unlink=False)
        self.server_close()

    def server_close(self):
        if self.local_endpoints is not None:
            self.local_endpoints.close()
        self.engine.stop()
        super().server_close()


def serve(engine: ServingEngine, port: int, host: str = "0.0.0.0",
          in_thread: bool = False):
    """Run the TCP frontend over ``engine``.  ``in_thread=True`` returns
    ``(server, thread)`` for tests; otherwise blocks (launcher mode)."""
    srv = ServeFrontend((host, port), engine)
    bps_log.info("byteps_tpu serve frontend listening on %s:%d",
                 host, srv.server_address[1])
    # live scrape endpoint (BYTEPS_METRICS_PORT; off by default) — the
    # HTTP twin of the TCP STATS op (docs/observability.md)
    from ..observability.scrape import maybe_start_metrics_server

    maybe_start_metrics_server(
        role="serve",
        health_fn=lambda: {"occupancy": engine.pool.occupancy(),
                           "queue_depth": engine.scheduler.depth})
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


def _submit_frame(op: int, prompt, max_new_tokens: int, seed: int,
                  priority: int, resume, extra: Optional[dict] = None
                  ) -> bytes:
    """Encode a SUBMIT/STREAM request: the resume tokens (if any) ride
    the tail of the token array, counted by the ``resume`` param.
    ``extra`` carries the optional wire params (``epoch``/``rid``/
    ``tenant``) — omitted entirely when unused, so frames stay
    bit-identical to the pre-HA wire for plain clients."""
    resume = ([] if resume is None
              else [int(t) for t in resume])
    p = {"max_new_tokens": max_new_tokens, "seed": seed,
         "priority": priority, "resume": len(resume)}
    if extra:
        p.update({k: v for k, v in extra.items() if v is not None})
    toks = np.concatenate([np.asarray(prompt, np.int32).reshape(-1),
                           np.asarray(resume, np.int32)])
    return _encode(op, json.dumps(p), toks)


class RemoteServeClient:
    """Client for the serve frontend (same framing as ``RemoteStore``).
    ``transport`` is resolved per endpoint exactly like the PS
    client's (``auto`` default: UDS/shm for a colocated frontend, TCP
    otherwise — docs/wire.md "Transports").

    Every wire read is bounded by ``timeout`` (default: the
    ``BYTEPS_SERVE_CLIENT_TIMEOUT_MS`` knob), and a dead or stalled
    frontend surfaces as the typed :class:`ServeConnectionError` on
    ``generate()``/``stream()`` — promptly, never an indefinite hang.
    One in-flight ``stream()`` per client (it holds the connection).

    **Multi-router failover** (docs/serving.md "Router HA"): ``addr``
    may be a comma-separated router list.  A ``ServeConnectionError``
    mid-call (dead router) or a *retryable* typed refusal (a standby
    router answering before takeover) rotates to the next address and
    re-issues the request — mid-stream with ``resume=`` the tokens
    already received, which the PR 10 resume argument makes
    token-identical, one tier higher.  Non-retryable typed errors
    (``ServeReplyError.retryable`` False — e.g. a
    ``WeightsMismatchError`` surfaced through a router) propagate
    immediately: re-issuing a deterministic refusal elsewhere would
    only burn the deadline.  The whole failover loop is bounded by
    ``timeout``."""

    def __init__(self, addr: str, timeout: Optional[float] = None,
                 transport: Optional[str] = None):
        from ..common.config import get_config

        cfg = get_config()
        self._addrs = [a.strip() for a in str(addr).split(",")
                       if a.strip()]
        if not self._addrs:
            raise ValueError("RemoteServeClient needs at least one "
                             "address")
        self._transport_pref = (transport if transport
                                else cfg.transport)
        self.timeout = (timeout if timeout is not None
                        else cfg.serve_client_timeout_ms / 1e3)
        self._lock = threading.Lock()
        self._cur = 0
        self._sock = None
        # set when a stream() was abandoned mid-flight: the server
        # keeps sending that stream's frames, so the connection can no
        # longer pair requests with replies — every later op would
        # silently read the orphaned frames as its reply.  A
        # single-address client stays poisoned (the historical
        # contract); a multi-router client clears it by reconnecting.
        self._poisoned = False
        if len(self._addrs) == 1:
            self._connect(0)  # eager — the single-endpoint contract
        else:
            self._connect_any()

    # ------------------------------------------------------- connections

    def _connect(self, idx: int) -> None:
        a = self._addrs[idx]
        kind, path = resolve_transport(a, self._transport_pref)
        self.addr = a
        self.transport = kind
        self._sock = transport_connect(kind, path, a,
                                       timeout=self.timeout)
        self._poisoned = False
        self._cur = idx

    def _connect_any(self) -> None:
        """Connect to the first reachable address starting at the
        current cursor (lock held or single-threaded init)."""
        errs = []
        for j in range(len(self._addrs)):
            idx = (self._cur + j) % len(self._addrs)
            try:
                self._connect(idx)
                return
            except OSError as e:
                errs.append(f"{self._addrs[idx]}: {e}")
        raise ServeConnectionError(
            f"no serve endpoint reachable: {'; '.join(errs)}")

    def _rotate_locked(self) -> None:
        """Drop the current connection and point the cursor at the
        next address (lock held); the next call reconnects."""
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self._sock = None
        self._poisoned = False
        self._cur = (self._cur + 1) % len(self._addrs)

    def _check_usable(self) -> None:
        """Call with ``self._lock`` held: the poison flag is written
        under the same lock (a check outside it could pass while the
        abandoning thread is still inside the stream's critical
        section).  A multi-router client reconnects out of a poisoned
        or dropped connection instead of failing — the failover loop
        owns bounding that."""
        if (self._poisoned or self._sock is None) \
                and len(self._addrs) > 1:
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None
            self._connect_any()
            return
        if self._poisoned:
            raise ServeConnectionError(
                f"client for {self.addr} abandoned an in-flight "
                f"stream(); the connection is desynced — open a new "
                f"RemoteServeClient")

    def _send(self, frame: bytes) -> None:
        """One frame out, with wire-level death typed (lock held)."""
        try:
            self._sock.sendall(frame)
        except (ConnectionError, OSError) as e:
            raise ServeConnectionError(
                f"serve frontend {self.addr} unreachable: {e}") from e

    def _read_frame(self):
        """One reply frame, with wire-level death typed and status=1
        replies raised as :class:`ServeReplyError` (its ``retryable``
        flag drives the multi-router failover loop)."""
        try:
            status, rname, out, payload = _decode(self._sock)
        except (ConnectionError, OSError, ValueError) as e:
            raise ServeConnectionError(
                f"serve frontend {self.addr} died or stalled "
                f"mid-conversation ({type(e).__name__}: {e}); "
                f"timeout={self.timeout}s") from e
        if status != 0:
            msg = payload.decode()
            raise ServeReplyError(f"serve error: {msg!r}",
                                  name=msg.split(":", 1)[0].strip())
        return rname, out, payload

    def _rpc(self, op: int, name: str = "", arr=None):
        with self._lock:
            self._check_usable()
            self._send(_encode(op, name, arr))
            return self._read_frame()

    @staticmethod
    def _extra(epoch, rid, tenant, extra=None,
               slo=None) -> Optional[dict]:
        out = dict(extra) if extra else {}
        if epoch is not None:
            out["epoch"] = epoch
        if rid is not None:
            out["rid"] = rid
        if tenant is not None:
            out["tenant"] = tenant
        if slo is not None:
            out["slo"] = slo
        return out or None

    def generate(self, prompt, max_new_tokens: int, *, seed: int = 0,
                 priority: int = 0, resume=None, epoch=None, rid=None,
                 tenant=None, slo=None, extra=None) -> np.ndarray:
        """Blocking submit -> the full token array.  Raises the typed
        :class:`ServeConnectionError` when the frontend dies first
        (after the deadline-bounded failover loop, on a multi-router
        client).  ``slo`` = the request's SLO class wire param
        (``guaranteed``/``standard``/``best-effort`` — a router may
        shed it typed, ``ServeReplyError.shed``).  ``extra`` =
        additional wire params merged into the submit frame (the
        router's disagg ``kv_ship`` hand-off rides here —
        docs/serving.md "Disaggregated tiers")."""
        if len(self._addrs) == 1:
            return self._generate_once(prompt, max_new_tokens,
                                       seed=seed, priority=priority,
                                       resume=resume, epoch=epoch,
                                       rid=rid, tenant=tenant,
                                       slo=slo, extra=extra)
        deadline = time.monotonic() + self.timeout
        while True:
            try:
                return self._generate_once(
                    prompt, max_new_tokens, seed=seed,
                    priority=priority, resume=resume, epoch=epoch,
                    rid=rid, tenant=tenant, slo=slo, extra=extra)
            except (ServeConnectionError, ServeReplyError) as e:
                self._note_failover(e, deadline)

    def _generate_once(self, prompt, max_new_tokens: int, *, seed, priority,
                       resume, epoch, rid, tenant, slo=None,
                       extra=None) -> np.ndarray:
        with self._lock:
            self._check_usable()
            self._send(_submit_frame(OP_SUBMIT, prompt, max_new_tokens,
                                     seed, priority, resume,
                                     self._extra(epoch, rid, tenant,
                                                 extra, slo)))
            _, out, _ = self._read_frame()
        return np.array(out)

    def prefill_ship(self, prompt, *, seed: int = 0, priority: int = 0,
                     ship_to: str, kv_ship: str, epoch=None, rid=None,
                     tenant=None):
        """The router's disagg prefill leg (docs/serving.md
        "Disaggregated tiers"): submit the prompt with
        ``max_new_tokens=1`` and ``ship_to``/``kv_ship`` wire params —
        the frontend prefills, parks the finished KV, ships it to
        ``ship_to`` under key ``kv_ship``, and replies with the first
        token plus a ship report.  Returns ``(tokens, info)`` where
        ``info`` is the report dict (``{"shipped": bool, ...}``; a
        failed ship is a DOWNGRADE — the tokens are still valid, the
        decode side just re-prefills)."""
        with self._lock:
            self._check_usable()
            self._send(_submit_frame(
                OP_SUBMIT, prompt, 1, seed, priority, None,
                self._extra(epoch, rid, tenant,
                            {"ship_to": str(ship_to),
                             "kv_ship": str(kv_ship)})))
            rname, out, _ = self._read_frame()
        info = (json.loads(rname)
                if rname.startswith("{") else {"shipped": False,
                                               "error": "no ship report"})
        return np.array(out), info

    def _note_failover(self, e: BaseException,
                       deadline: float) -> BaseException:
        """One failover-loop step: propagate non-retryable typed
        refusals, enforce the deadline, otherwise rotate to the next
        router with a short pause (a standby needs its takeover window
        before it can serve).  Returns the error for chaining."""
        if isinstance(e, ServeReplyError) and not e.retryable:
            raise e
        with self._lock:
            self._rotate_locked()
        if time.monotonic() + 0.05 > deadline:
            raise ServeConnectionError(
                f"no serve endpoint of {self._addrs} could complete "
                f"the request within timeout={self.timeout}s "
                f"(last: {type(e).__name__}: {e})") from e
        time.sleep(0.05)
        return e

    def stream(self, prompt, max_new_tokens: int, *, seed: int = 0,
               priority: int = 0, resume=None, epoch=None, rid=None,
               tenant=None, slo=None, extra=None):
        """Token iterator over the OP_STREAM wire op: yields each token
        as its frame arrives (``resume`` = already-emitted tokens for a
        failover re-dispatch — only NEW tokens are streamed back).  A
        frontend death mid-stream raises :class:`ServeConnectionError`
        within ``timeout``; a replica-side typed error raises
        :class:`ServeReplyError` carrying the error name.  Abandoning
        the iterator mid-stream POISONS the client (the server keeps
        sending the orphaned stream's frames, so request/reply pairing
        is lost) — later calls raise ``ServeConnectionError`` instead
        of silently reading wrong replies (a multi-router client
        reconnects instead).

        With several router addresses the stream is failover-wrapped:
        a dead router (or a standby's typed refusal) re-issues the
        request to the next address with ``resume=`` the prefix already
        received — the consumer sees ONE uninterrupted token-identical
        sequence."""
        if len(self._addrs) == 1:
            return self._stream_once(prompt, max_new_tokens, seed=seed,
                                     priority=priority, resume=resume,
                                     epoch=epoch, rid=rid,
                                     tenant=tenant, slo=slo,
                                     extra=extra)
        return self._stream_failover(prompt, max_new_tokens, seed=seed,
                                     priority=priority, resume=resume,
                                     epoch=epoch, rid=rid,
                                     tenant=tenant, slo=slo,
                                     extra=extra)

    def _stream_failover(self, prompt, max_new_tokens: int, *, seed,
                         priority, resume, epoch, rid, tenant,
                         slo=None, extra=None):
        emitted: List[int] = ([int(t) for t in resume]
                              if resume is not None else [])
        deadline = time.monotonic() + self.timeout
        while True:
            try:
                for tok in self._stream_once(
                        prompt, max_new_tokens, seed=seed,
                        priority=priority, resume=emitted or None,
                        epoch=epoch, rid=rid, tenant=tenant,
                        slo=slo, extra=extra):
                    emitted.append(int(tok))
                    # the failover budget is timeout WITHOUT PROGRESS:
                    # a healthy stream longer than self.timeout must
                    # not exhaust its own HA protection, so every
                    # token re-arms the deadline
                    deadline = time.monotonic() + self.timeout
                    yield int(tok)
                return
            except (ServeConnectionError, ServeReplyError) as e:
                if len(emitted) >= max_new_tokens:
                    # the endpoint died between the final token and the
                    # terminal frame: the stream is already fully
                    # delivered (the router tier's argument, one tier
                    # higher)
                    return
                self._note_failover(e, deadline)

    def _stream_once(self, prompt, max_new_tokens: int, *, seed,
                     priority, resume, epoch, rid, tenant, slo=None,
                     extra=None):
        with self._lock:
            self._check_usable()
            in_flight = False
            # the poison write happens INSIDE the locked region: a
            # concurrent caller blocked on the lock must observe it the
            # moment it gets in, never a window where the abandoning
            # thread has released the lock but not yet set the flag
            try:
                self._send(_submit_frame(OP_STREAM, prompt,
                                         max_new_tokens, seed,
                                         priority, resume,
                                         self._extra(epoch, rid,
                                                     tenant, extra,
                                                     slo)))
                in_flight = True
                while True:
                    try:
                        rname, out, _ = self._read_frame()
                    except RuntimeError:
                        # a typed status=1 frame TERMINATED the stream
                        # server-side: the connection stays in sync
                        in_flight = False
                        raise
                    if rname == "t":
                        yield int(out[0])
                    else:  # "end" — sequence already yielded piecewise
                        in_flight = False
                        return
            finally:
                if in_flight:
                    self._poisoned = True

    def cancel(self, rid: str, epoch=None) -> bool:
        """Wire-level cancel (OP_CANCEL) of the in-flight request
        submitted with ``rid=`` — sent on a FRESH short-lived
        connection, because the streaming connection is busy relaying
        the very stream being cancelled.  Through a router the cancel
        propagates router -> replica, so the replica's slot and paged
        KV blocks are reclaimed same-tick.  Returns whether a live
        request was found (False usually means it already finished, or
        the cancel was tombstoned ahead of a racing submit).

        Failover-aware like every other op, deadline-bounded by
        ``timeout``: with several router addresses every sweep tries
        them ALL — one router's False is not authoritative (a
        restarted or partitioned stale active answers False for a rid
        the true active is still serving), and a sweep that only met
        dead routers / standby refusals sleeps and retries so a cancel
        issued inside the takeover window still lands once the standby
        promotes (the tombstone it leaves then kills the request's own
        failover re-submit).  Returns True the moment any router
        cancels; False when every router answered without one; raises
        ``ServeConnectionError`` when none ever answered within the
        deadline.  Non-retryable typed errors propagate immediately."""
        params = {"rid": str(rid)}
        if epoch is not None:
            params["epoch"] = int(epoch)
        # snapshot WITHOUT the client lock: an in-flight stream() holds
        # it for the stream's whole lifetime, and this cancel must not
        # wait behind the very stream it is cancelling (_addrs is
        # immutable after construction; _cur is a plain int read)
        cur = self._cur
        addrs = [self._addrs[(cur + j) % len(self._addrs)]
                 for j in range(len(self._addrs))]
        if len(addrs) == 1:
            return self._cancel_once(addrs[0], params)
        deadline = time.monotonic() + self.timeout
        while True:
            answered = False
            errs = []
            for a in addrs:
                try:
                    if self._cancel_once(a, params):
                        return True
                    answered = True
                except ServeConnectionError as e:
                    errs.append(str(e))
                except ServeReplyError as e:
                    if not e.retryable:
                        raise
                    errs.append(f"{a}: {e.name}")
            if answered:
                # an active-claiming router answered and none held the
                # rid: authoritative — every other address was already
                # swept this round, so retrying buys nothing
                return False
            if time.monotonic() + 0.05 > deadline:
                raise ServeConnectionError(
                    f"no serve endpoint of {addrs} accepted cancel"
                    f"({rid!r}) within timeout={self.timeout}s: "
                    f"{'; '.join(errs)}")
            time.sleep(0.05)

    def _cancel_once(self, addr: str, params: dict) -> bool:
        return _wire_cancel(addr, params, self.timeout,
                            self._transport_pref)

    def journal(self, entries: list) -> dict:
        """Router-HA journal push (OP_JOURNAL; routers only).  Returns
        the receiver's ack — ``{"epoch": N}`` — which is how a deposed
        active router discovers a standby took over."""
        _, _, payload = self._rpc(OP_JOURNAL, json.dumps(entries))
        return json.loads(payload.decode()) if payload else {}

    def stats(self) -> dict:
        _, _, payload = self._rpc(OP_STATS)
        return json.loads(payload.decode())

    def ping(self) -> bool:
        try:
            self._rpc(OP_PING)
            return True
        except (OSError, RuntimeError):
            return False

    def close(self) -> None:
        if self._sock is None:
            return
        try:
            self._sock.close()
        except OSError:
            pass


# ------------------------------------------------------------ launcher role


def _model_from_env(cfg_str: str):
    """Build a (model, variables) pair from ``BYTEPS_SERVE_MODEL``: a
    comma-separated ``k=v`` list over TransformerConfig's integer axes
    (vocab_size, num_layers, num_heads, d_model, d_ff, max_seq_len) —
    random-initialized weights unless ``BYTEPS_SERVE_CHECKPOINT`` points
    at a checkpoint produced by ``training.checkpoint``.  A serving
    process with random weights is still the real engine — that is what
    the smoke tooling runs against."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import Transformer, TransformerConfig

    kw = {}
    if cfg_str:
        for pair in cfg_str.split(","):
            k, _, v = pair.partition("=")
            kw[k.strip()] = int(v)
    kw.setdefault("vocab_size", 256)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("d_model", 128)
    kw.setdefault("d_ff", 256)
    kw.setdefault("max_seq_len", 512)
    cfg = TransformerConfig(dtype=jnp.float32, **kw)
    model = Transformer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    return model, variables


def build_engine_from_env(env=None) -> ServingEngine:
    """Build the serving engine from ``BYTEPS_SERVE_*`` — the first half
    of the launcher's ``serve`` role, split out so an in-process caller
    (``chip_smoke.py``, tests) can put the SAME engine behind
    ``serve(..., in_thread=True)``.  An explicit ``env`` mapping
    overrides the process environment for the ``BYTEPS_*``/``DMLC_*``
    keys it carries; either way the cached process config is reset
    first, so knobs set after an earlier ``get_config()`` call are
    honored."""
    import os

    from ..common.compile_cache import configure_compile_cache
    from ..common.config import get_config, reset_config

    if env is not None:
        os.environ.update({k: str(v) for k, v in env.items()
                           if k.startswith(("BYTEPS_", "DMLC_"))})
    reset_config()
    cfg = get_config()
    configure_compile_cache()
    model, variables = _model_from_env(cfg.serve_model)
    if cfg.serve_checkpoint:
        from ..training.checkpoint import restore_checkpoint

        variables = {"params": restore_checkpoint(
            cfg.serve_checkpoint, variables["params"], broadcast=False)}
    return ServingEngine(
        model, variables,
        n_slots=cfg.serve_slots,
        max_seq=(cfg.serve_max_seq or model.cfg.max_seq_len),
        temperature=cfg.serve_temperature,
        top_k=cfg.serve_top_k, top_p=cfg.serve_top_p,
        eos_id=cfg.serve_eos_id,
        max_queue=cfg.serve_max_queue,
        prefill_credits=cfg.serve_prefill_credits,
        chunk=cfg.serve_chunk,
        prefix_cache=cfg.serve_prefix_cache,
        prefix_block=cfg.serve_prefix_block,
        prefix_bytes=cfg.serve_prefix_mb << 20,
        paged=cfg.serve_paged,
        block=cfg.serve_block,
        kv_mb=cfg.serve_kv_mb,
        kv_dtype=cfg.serve_kv_dtype,
        paged_kernel=cfg.serve_paged_kernel,
        spec_k=(cfg.serve_spec_k if cfg.serve_spec else 0),
        spec_ngram=cfg.serve_spec_ngram)


def serve_from_env(env=None) -> int:
    """Entry point for the launcher's ``serve`` role: build the engine
    from the environment (:func:`build_engine_from_env`) and block on
    the TCP frontend."""
    from ..common.config import get_config

    engine = build_engine_from_env(env)
    serve(engine, get_config().serve_port)
    return 0
