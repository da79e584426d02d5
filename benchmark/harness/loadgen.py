#!/usr/bin/env python3
"""The load generator: one process, one thread, no JAX.

Started by the serve runner as ``python loadgen.py <spec.json>`` so
that it never shares an interpreter lock with the engine's tick thread.
It reads a traffic mix, builds the run's requests from the seed
(``traffic.requests``), drives them at the serve frontend over TCP —
one STREAM connection per request, all sockets multiplexed by one
``selectors`` loop — and stamps every token frame with the host clock
as it is read.  Open loop: a request goes out when it is due, whether
or not earlier ones have finished, and its latency counts from the due
instant.  Closed loop: ``clients`` callers each send their next request
when the last one ended.

Timeline (offsets from this process's start of load): a ramp of
``ramp_s`` seconds that is set-up, the measured window of ``seconds``,
then at most ``drain_s`` for open-loop requests due inside the window
to finish.  It prints ``window_start`` / ``window_end`` events as JSON
lines on stdout when they happen, writes every request's record to the
spec's ``out`` file, and prints ``done``.  Reduction to metrics is the
runner's (``runners/serve.py``), not this file's.
"""

from __future__ import annotations

import json
import os
import selectors
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import manifest, traffic, wire  # noqa: E402


class Stream:
    """One request in flight on its own connection."""

    __slots__ = ("req", "sock", "buf", "due", "sent", "token_times",
                 "done", "error", "client")

    def __init__(self, req, due, client):
        self.req = req
        self.due = due
        self.client = client
        self.sock = None
        self.buf = bytearray()
        self.sent = None
        self.token_times = []
        self.done = None
        self.error = None

    def record(self, to_wall):
        w = to_wall
        return {"index": self.req.index,
                "prompt_len": int(self.req.prompt.shape[0]),
                "max_new_tokens": self.req.max_new_tokens,
                "due": w(self.due), "sent": w(self.sent),
                "done": w(self.done), "error": self.error,
                "token_times": [w(t) for t in self.token_times]}


emit = manifest.note


def run(spec: dict) -> dict:
    mix = spec["mix"]
    seconds = float(spec["seconds"])
    ramp = float(mix.get("ramp_s", 5.0))
    drain = float(mix.get("drain_s", 10.0)) if mix["kind"] == "open_loop" \
        else 0.0
    closed = mix["kind"] == "closed_loop"
    reqs = traffic.requests(
        mix, spec["seed"], spec["vocab"], spec["max_seq"],
        horizon_s=ramp + seconds, count=mix.get("replay_count"))
    addr = tuple(spec["addr"])
    sel = selectors.DefaultSelector()
    streams, finished = {}, []

    def start(req, due, client):
        s = Stream(req, due, client)
        try:
            s.sock = socket.create_connection(addr, timeout=10.0)
            s.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.sock.sendall(wire.encode_stream_request(
                req.prompt, req.max_new_tokens))
            s.sent = time.perf_counter()
            s.sock.setblocking(False)
            sel.register(s.sock, selectors.EVENT_READ, s)
            streams[s.sock.fileno()] = s
        except OSError as e:
            s.sent = time.perf_counter()
            finish(s, f"{type(e).__name__}: {e}")

    def finish(s, error=None):
        s.error = error
        s.done = time.perf_counter()
        if s.sock is not None:
            streams.pop(s.sock.fileno(), None)
            try:
                sel.unregister(s.sock)
            except (KeyError, ValueError):
                pass
            s.sock.close()
        finished.append(s)

    def read(s):
        try:
            data = s.sock.recv(1 << 16)
        except BlockingIOError:
            return
        except OSError as e:
            finish(s, f"{type(e).__name__}: {e}")
            return
        now = time.perf_counter()
        if not data:
            finish(s, "connection closed before the end frame")
            return
        s.buf += data
        off = 0
        while True:
            fr = wire.parse_frame(s.buf, off)
            if fr is None:
                break
            status, name, items, payload, off = fr
            if status != 0:
                finish(s, payload.decode(errors="replace")[:300])
                return
            if name == "t":
                s.token_times.append(now)
            elif name == "end":
                ok = (items == s.req.max_new_tokens
                      and len(s.token_times) == items)
                finish(s, None if ok else
                       f"{len(s.token_times)} token frames, end frame "
                       f"of {items}, wanted {s.req.max_new_tokens}")
                return
        del s.buf[:off]

    wall0 = time.time() - time.perf_counter()
    t0 = time.perf_counter()
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    nxt = 0
    started = ended = False
    if closed:
        for c in range(int(mix["clients"])):
            if nxt < len(reqs):
                start(reqs[nxt], time.perf_counter(), c)
                nxt += 1
    while True:
        now = time.perf_counter()
        if not started and now >= w0:
            started = True
            emit(event="window_start", wall=wall0 + w0)
        if not ended and now >= w1:
            ended = True
            emit(event="window_end", wall=wall0 + w1)
        if closed:
            if now >= w1:
                break
            wake = w1 if started else w0
        else:
            while nxt < len(reqs) and t0 + reqs[nxt].due_s <= now:
                start(reqs[nxt], t0 + reqs[nxt].due_s, None)
                nxt += 1
            if now >= w1 and (not streams or now >= w1 + drain):
                break
            wake = (w1 + drain if now >= w1 else w1 if started else w0)
            if nxt < len(reqs):
                wake = min(wake, t0 + reqs[nxt].due_s)
        n_done = len(finished)
        for key, _ in sel.select(max(0.0, min(wake - time.perf_counter(),
                                              0.25))):
            read(key.data)
        if closed:
            for s in finished[n_done:]:
                if nxt < len(reqs):
                    start(reqs[nxt], time.perf_counter(), s.client)
                    nxt += 1
    unfinished = list(streams.values())
    for s in unfinished:
        finish(s, "unfinished when the run ended")
    to_wall = lambda t: None if t is None else wall0 + t  # noqa: E731
    return {"kind": mix["kind"], "window": [wall0 + w0, wall0 + w1],
            "requests_generated": len(reqs), "requests_started": nxt,
            "requests": [s.record(to_wall) for s in finished]}


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    result = run(spec)
    with open(spec["out"], "w") as f:
        json.dump(result, f)
    emit(event="done", path=spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
