"""The benchmark's client speaks the program's wire, and the load
generator's timeline holds against a scripted frontend (no JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json
import socket
import socketserver
import threading
import time

import numpy as np
import pytest

from benchmark.harness import latency, loadgen, wire


def test_frames_are_the_programs_frames():
    from byteps_tpu.engine.wire import _decode_frame, _encode

    a, b = socket.socketpair()
    try:
        prompt = np.arange(5, 12, dtype=np.int32)
        a.sendall(wire.encode_stream_request(prompt, 9, seed=4))
        op, name, arr, _, _ = _decode_frame(b)
        assert op == wire.OP_STREAM == 3
        assert json.loads(name) == {"max_new_tokens": 9, "seed": 4,
                                    "priority": 0, "resume": 0}
        assert np.array_equal(arr, prompt) and arr.dtype == np.int32
        a.sendall(wire.encode_stats_request())
        assert _decode_frame(b)[:3] == (wire.OP_STATS, "", None)
    finally:
        a.close()
        b.close()
    # replies as the frontend encodes them, split at an awkward place
    reply = (_encode(0, "t", np.asarray([17], np.int32))
             + _encode(0, "end", np.asarray([17, 3], np.int32))
             + _encode(1, "", None, b"QueueFullError: full"))
    buf = bytearray(reply[:20])
    assert wire.parse_frame(buf) is None
    buf += reply[20:]
    s1, n1, k1, _, off = wire.parse_frame(buf)
    s2, n2, k2, _, off = wire.parse_frame(buf, off)
    s3, _, k3, payload, off = wire.parse_frame(buf, off)
    assert (s1, n1, k1, s2, n2, k2) == (0, "t", 1, 0, "end", 2)
    assert (s3, k3, payload) == (1, 0, b"QueueFullError: full")
    assert off == len(buf)


class ScriptedFrontend(socketserver.ThreadingTCPServer):
    """Answers a STREAM request with one token every ``gap`` seconds
    after ``first`` seconds, as the serve frontend frames them."""

    allow_reuse_address = True
    daemon_threads = True
    first, gap = 0.05, 0.01

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            from byteps_tpu.engine.wire import _decode_frame, _encode

            _, name, arr, _, _ = _decode_frame(self.request)
            n = json.loads(name)["max_new_tokens"]
            if len(arr) == 13:                 # the scripted refusal
                self.request.sendall(_encode(1, "", None, b"Refused: 13"))
                return
            time.sleep(self.server.first)
            for i in range(n):
                self.request.sendall(_encode(
                    0, "t", np.asarray([i], np.int32)))
                time.sleep(self.server.gap)
            self.request.sendall(_encode(
                0, "end", np.arange(n, dtype=np.int32)))


@pytest.fixture()
def frontend():
    import byteps_tpu.engine.wire  # noqa: F401  (not inside a timed reply)

    srv = ScriptedFrontend(("127.0.0.1", 0), ScriptedFrontend.Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5.0)
    assert not t.is_alive()


def test_open_loop_sends_on_schedule_and_times_from_the_due_instant(
        frontend):
    mix = {"kind": "open_loop", "ramp_s": 0.2, "drain_s": 2.0,
           "arrivals": {"process": "poisson", "rate_rps": 40.0},
           "prompt_len": {"dist": "uniform", "min": 12, "max": 14},
           "output_len": {"dist": "uniform", "min": 5, "max": 5}}
    out = loadgen.run({"addr": frontend.server_address, "mix": mix,
                       "seed": 11, "seconds": 1.0, "vocab": 100,
                       "max_seq": 64})
    w0, w1 = out["window"]
    assert w1 - w0 == pytest.approx(1.0)
    assert out["requests_started"] == out["requests_generated"]
    s = latency.open_loop_samples(out["requests"], (w0, w1))
    refused = [r for r in out["requests"] if r["prompt_len"] == 13
               and w0 <= r["due"] < w1]
    assert 20 < s["attempted"] < 65 and s["failed"] == len(refused) > 0
    assert all("Refused" in r["error"] for r in refused)
    # first token 50 ms after the request, tokens 10 ms apart
    assert 45 < min(s["ttft_ms"]) and np.median(s["ttft_ms"]) < 80
    assert 9 < np.median(s["itl_ms"]) < 15
    # sent when due: the median, because under six test workers one
    # send in forty can wait 50 ms for a core
    assert np.median(s["late_ms"]) < 20 and max(s["late_ms"]) < 500
    assert len(s["itl_ms"]) == 4 * (s["attempted"] - s["failed"])


def test_closed_loop_keeps_exactly_its_clients_in_flight(frontend):
    mix = {"kind": "closed_loop", "clients": 3, "replay_count": 400,
           "ramp_s": 0.2,
           "prompt_len": {"dist": "uniform", "min": 20, "max": 20},
           "output_len": {"dist": "uniform", "min": 4, "max": 4}}
    out = loadgen.run({"addr": frontend.server_address, "mix": mix,
                       "seed": 2, "seconds": 1.0, "vocab": 100,
                       "max_seq": 64})
    s = latency.closed_loop_samples(out["requests"], tuple(out["window"]))
    # a request takes 50 + 4 x 10 ms: ~11 a second per client
    assert 20 < s["completed"] < 40 and s["failed"] == 0
    assert s["tokens_per_s"] == pytest.approx(3 * 24 / 0.095, rel=0.25)
    cut = [r for r in out["requests"] if r["error"]]
    assert len(cut) <= 3 and all("unfinished" in r["error"] for r in cut)
    # never more than `clients` requests overlap
    events = sorted([(r["sent"], 1) for r in out["requests"]]
                    + [(r["done"], -1) for r in out["requests"]])
    depth = peak = 0
    for _, d in events:
        depth += d
        peak = max(peak, depth)
    assert peak == 3
