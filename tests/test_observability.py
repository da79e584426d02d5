"""PR 6 observability layer (docs/observability.md): metrics registry,
live scrape surfaces (HTTP + OP_STATS), bounded tracer, wire-frame
trace ids, clock-offset estimation, and the merge/report tooling —
plus the env-knob documentation lint."""

import json
import os
import re
import struct
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from byteps_tpu.common.config import reset_config
from byteps_tpu.common.tracing import Tracer, get_tracer, reset_tracer
from byteps_tpu.engine import ps_server
from byteps_tpu.engine.wire import (_decode_frame, _encode_buffers,
                                    _recv_exact)
from byteps_tpu.observability import trace as obs_trace
from byteps_tpu.observability.export import (clock_offsets_from_events,
                                             load_trace_events,
                                             merge_traces, span_durations)
from byteps_tpu.observability.metrics import (Counter, Gauge, Histogram,
                                              MetricsRegistry, get_registry,
                                              reset_registry)
from byteps_tpu.observability.scrape import (start_metrics_server,
                                             stop_metrics_server)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_state():
    reset_config()
    reset_tracer()
    yield
    for k in ("BYTEPS_TRACE_PATH", "BYTEPS_TRACE_RPC",
              "BYTEPS_TRACE_BUFFER", "BYTEPS_METRICS_PORT",
              "BYTEPS_SERVER_ENABLE_PROFILE",
              "BYTEPS_SERVER_PROFILE_OUTPUT_PATH",
              "BYTEPS_PARTITION_BYTES"):
        os.environ.pop(k, None)
    stop_metrics_server()
    reset_config()
    reset_tracer()


# ------------------------------------------------------------------ registry


class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        c = reg.counter("a.count")
        assert c.inc() == 1 and c.inc(5) == 6
        g = reg.gauge("a.gauge")
        g.set(2.5)
        assert g.value == 2.5
        g.dec(0.5)
        assert g.value == 2.0
        h = reg.histogram("a.hist")
        for v in (0.002, 0.02, 0.2):
            h.observe(v)
        assert h.count == 3 and abs(h.sum - 0.222) < 1e-9

    def test_get_or_create_identity_and_type_guard(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        assert reg.counter("x") is reg.counter("x")
        # same name, different labels = different metric
        assert reg.counter("x", shard=0) is not reg.counter("x", shard=1)
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_concurrent_increments_exact(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        c = reg.counter("hot")
        n_threads, per = 8, 2000

        def worker():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * per

    def test_histogram_percentiles(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        h = reg.histogram("lat")
        for i in range(1, 101):
            h.observe(i / 1000.0)  # 1..100 ms
        assert abs(h.percentile(50) - 0.050) <= 0.002
        assert abs(h.percentile(99) - 0.099) <= 0.002
        st = h.state()
        assert st["count"] == 100
        # cumulative buckets: everything <= 0.1 bucket
        assert st["buckets"]["0.1"] == 100

    def test_histogram_reservoir_bounded(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        h = reg.histogram("ring", max_samples=64)
        for i in range(10_000):
            h.observe(float(i))
        assert h.count == 10_000
        assert len(h._samples) == 64
        # reservoir holds the most recent samples -> p50 near the tail
        assert h.percentile(50) > 9_900

    def test_snapshot_isolation(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        c = reg.counter("c")
        c.inc(3)
        snap = reg.snapshot()
        c.inc(10)
        reg.gauge("late").set(1.0)
        assert snap["counters"]["c"] == 3
        assert "late" not in snap["gauges"]

    def test_prometheus_exposition(self):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        reg.counter("wire.bytes_sent", shard=1).inc(42)
        reg.gauge("wire.inflight").set(3)
        reg.histogram("ps.handle_s").observe(0.004)
        text = reg.to_prometheus()
        assert '# TYPE byteps_wire_bytes_sent_total counter' in text
        assert 'byteps_wire_bytes_sent_total{shard="1"} 42' in text
        assert "byteps_wire_inflight 3" in text
        assert 'byteps_ps_handle_s_bucket{le="+Inf"} 1' in text
        assert "byteps_ps_handle_s_count 1" in text

    def test_subsystem_resets_clear_global_registry(self):
        """reset_* must clear the registry-backed counts, not just the
        singleton: the global registry outlives it, so a rebuilt
        accessor would otherwise report pre-reset totals."""
        reset_registry()
        from byteps_tpu.compression.stats import (get_compression_stats,
                                                  reset_compression_stats)
        from byteps_tpu.resilience import counters as rc
        from byteps_tpu.serving import metrics as sm

        rc.get_counters().bump(rc.DEDUP)
        m = sm.get_serve_metrics()
        m.bump(sm.COMPLETED)
        m.observe_request(0.1, 0.2, 0.01, 4)
        get_compression_stats().observe("w", 100, 10)

        rc.reset_counters()
        sm.reset_serve_metrics()
        reset_compression_stats()

        assert rc.get_counters().get(rc.DEDUP) == 0
        assert sm.get_serve_metrics().get(sm.COMPLETED) == 0
        assert sm.get_serve_metrics().summary().get("ttft_n", 0) == 0
        assert get_registry().get("compression.wire_bytes_sent") is None
        # and a fresh bump counts from zero, not pre-reset totals
        assert rc.get_counters().bump(rc.DEDUP) == 1

    def test_counter_mirrors_tracer_series(self, tmp_path):
        t = Tracer(path=str(tmp_path / "t.json"))
        reg = MetricsRegistry(tracer=t)
        reg.counter("resilience.retry", track="resilience").inc(shard=2)
        evs = t.events()
        kinds = {e["ph"] for e in evs}
        assert kinds == {"i", "C"}  # instant + counter track, as before
        inst = [e for e in evs if e["ph"] == "i"][0]
        assert inst["tid"] == "resilience" and inst["args"]["shard"] == 2


# ----------------------------------------------------------------- tracer


class TestBoundedTracer:
    def test_rollover_incremental_flush_valid_json(self, tmp_path):
        path = str(tmp_path / "t.json")
        t = Tracer(path=path, max_events=10)
        for i in range(25):
            t.instant(f"e{i}", "s")
        # two rollovers happened; buffer holds the remainder
        assert len(t.events()) == 5
        # batches land via the background writer: poll for the mid-run
        # file (valid JSON BETWEEN flushes is the crash-safety contract)
        deadline = time.monotonic() + 10.0
        mid = {"traceEvents": []}
        while time.monotonic() < deadline:
            try:
                mid = json.load(open(path))
            except (OSError, ValueError):
                pass
            if len(mid["traceEvents"]) == 20:
                break
            time.sleep(0.01)
        assert len(mid["traceEvents"]) == 20
        t.flush()  # drains the writer first, then appends the tail
        evs = json.load(open(path))["traceEvents"]
        assert [e["name"] for e in evs] == [f"e{i}" for i in range(25)]
        assert t.dropped == 0

    def test_failed_write_drops_loudly(self, tmp_path):
        reset_registry()
        path = str(tmp_path / "missing_dir" / "t.json")
        t = Tracer(path=path, max_events=4)
        for i in range(9):
            t.instant(f"e{i}", "s")
        t._drain_writer()  # drops happen on the background writer
        assert t.dropped == 8  # two failed 4-event batches
        dropped = get_registry().get("trace.events_dropped")
        assert dropped is not None and dropped.value == 8

    def test_flush_empty_enabled_tracer_writes_valid_file(self, tmp_path):
        path = str(tmp_path / "empty.json")
        t = Tracer(path=path)
        assert t.flush() == path
        assert json.load(open(path)) == {"traceEvents": []}

    def test_complete_spans_use_wall_anchor(self, tmp_path):
        import time

        t = Tracer(path=str(tmp_path / "t.json"))
        t0 = time.perf_counter()
        t.complete("after_the_fact", "wire", t0, 0.001, trace_id="ab")
        ev = t.events()[0]
        # wall-anchored: microseconds since epoch, i.e. ~now * 1e6
        assert abs(ev["ts"] / 1e6 - time.time()) < 5.0
        assert ev["dur"] == pytest.approx(1000.0)
        assert ev["args"]["trace_id"] == "ab"


# ------------------------------------------------------------ wire trace ids


class _Pipe:
    """Minimal socket stand-in feeding _decode_frame from bytes."""

    def __init__(self, data: bytes):
        self._data = memoryview(bytearray(data))
        self._pos = 0

    def recv_into(self, buf, n):
        n = min(n, len(self._data) - self._pos)
        buf[:n] = self._data[self._pos:self._pos + n]
        self._pos += n
        return n


class TestWireExtension:
    def _roundtrip(self, bufs):
        import socket as s

        a, b = s.socketpair()
        try:
            a.sendall(b"".join(bytes(x) for x in bufs))
            return _decode_frame(b)
        finally:
            a.close()
            b.close()

    def test_trace_id_roundtrip(self):
        tid = bytes(range(8))
        arr = np.arange(6, dtype=np.float32)
        bufs = _encode_buffers(2, "grad/w", arr, trace_id=tid)
        op, name, out, _, got = self._roundtrip(bufs)
        assert (op, name, got) == (2, "grad/w", tid)
        np.testing.assert_array_equal(np.asarray(out).reshape(-1), arr)

    def test_unextended_frame_is_bit_identical_to_seed(self):
        arr = np.ones(3, np.float32)
        plain = b"".join(bytes(b) for b in _encode_buffers(1, "x", arr))
        # no extension flag byte anywhere in the head
        assert plain[0] == 1
        op, name, out, _, tid = self._roundtrip(_encode_buffers(1, "x", arr))
        assert tid == b"" and op == 1

    def test_bad_trace_id_length_raises(self):
        with pytest.raises(ValueError, match="8 bytes"):
            _encode_buffers(1, "x", None, trace_id=b"short")

    def test_unknown_extension_version_raises(self):
        import socket as s

        tid = b"\x01" * 8
        bufs = _encode_buffers(1, "x", None, trace_id=tid)
        head = bytearray(bytes(bufs[0]))
        head[5] = 99  # extension version byte
        a, b = s.socketpair()
        try:
            a.sendall(bytes(head) + b"".join(bytes(x) for x in bufs[1:]))
            with pytest.raises(ValueError, match="extension version 99"):
                _decode_frame(b)
        finally:
            a.close()
            b.close()


# ------------------------------------------------------- scrape round trips


def _spawn_server():
    srv, _ = ps_server.serve(0, host="127.0.0.1", use_native=False,
                             in_thread=True)
    return srv, f"127.0.0.1:{srv.server_address[1]}"


class TestScrape:
    def test_http_endpoint_roundtrip(self):
        reset_registry()
        get_registry().counter("test.scraped").inc(7)
        srv = start_metrics_server(0, host="127.0.0.1", role="tester",
                                   health_fn=lambda: {"detail": 1})
        try:
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "byteps_test_scraped_total 7" in text
            snap = json.loads(
                urllib.request.urlopen(base + "/metrics.json").read())
            assert snap["counters"]["test.scraped"] == 7
            health = json.loads(
                urllib.request.urlopen(base + "/healthz").read())
            assert health["status"] == "ok"
            assert health["role"] == "tester" and health["detail"] == 1
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope")
        finally:
            srv.shutdown()
            srv.server_close()

    def test_health_fn_error_does_not_500(self):
        def broken():
            raise RuntimeError("probe died")

        srv = start_metrics_server(0, host="127.0.0.1", health_fn=broken)
        try:
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz").read())
            assert health["status"] == "ok"
            assert "probe died" in health["health_fn_error"]
        finally:
            srv.shutdown()
            srv.server_close()

    def test_op_stats_roundtrip(self):
        srv, addr = _spawn_server()
        store = ps_server.RemoteStore([addr])
        try:
            store.init_tensor("w", np.ones(8, np.float32))
            st = store.shard_stats(0)
            assert st["role"] == "ps_server" and st["tensors"] == 1
            assert st["uptime_s"] >= 0
            # the snapshot is built before the STATS request's own
            # increment, so only the preceding INIT is visible
            assert st["metrics"]["counters"]["ps.requests"] >= 1
        finally:
            store.close()
            srv.shutdown()

    def test_ping_reply_carries_server_clock(self):
        import socket as s
        import time

        srv, addr = _spawn_server()
        try:
            host, port = addr.rsplit(":", 1)
            with s.create_connection((host, int(port)), timeout=5) as sock:
                sock.sendall(ps_server._encode(ps_server.OP_PING, "", None))
                status, _, _, payload = ps_server._decode(sock)
            assert status == 0
            (t_server,) = struct.unpack_from("<d", payload)
            assert abs(t_server - time.time()) < 60
        finally:
            srv.shutdown()

    def test_clock_offset_estimation(self):
        srv, addr = _spawn_server()
        try:
            off = obs_trace.estimate_clock_offset(addr, n=3)
            # same host, same clock: the offset is bounded by the RTT
            assert abs(off.offset_s) < max(off.rtt_s, 0.5)
            assert off.samples == 3
        finally:
            srv.shutdown()


# -------------------------------------------- end-to-end trace correlation


class TestTraceCorrelation:
    def _run_traced_op(self, tmp_path, n_shards=2):
        trace_path = str(tmp_path / "client.json")
        prof_path = str(tmp_path / "server.json")
        os.environ["BYTEPS_TRACE_PATH"] = trace_path
        os.environ["BYTEPS_SERVER_ENABLE_PROFILE"] = "1"
        os.environ["BYTEPS_SERVER_PROFILE_OUTPUT_PATH"] = prof_path
        # 2 parts across shards: every frame must carry the op's ONE id
        os.environ["BYTEPS_PARTITION_BYTES"] = "8192"
        reset_config()
        reset_tracer()
        servers = [_spawn_server() for _ in range(n_shards)]
        addrs = [a for _, a in servers]
        store = ps_server.RemoteStore(addrs)
        x = np.ones(4096, np.float32)
        store.init_tensor("w", x)
        store.push_pull("w", x)
        store.record_clock_offsets(samples=2)
        store.close()
        for srv, _ in servers:
            if srv.profiler is not None:
                srv.profiler.close()
            srv.shutdown()
        get_tracer().flush()
        return trace_path, prof_path, addrs

    def test_trace_id_propagates_client_to_server(self, tmp_path):
        trace_path, prof_path, addrs = self._run_traced_op(tmp_path)
        client_evs = load_trace_events(trace_path)
        ops = {e["args"]["trace_id"]: e["name"] for e in client_evs
               if e.get("ph") == "X" and e.get("tid") == "client"
               and e.get("args", {}).get("trace_id")}
        pp_ids = [tid for tid, name in ops.items()
                  if name.startswith("push_pull")]
        assert len(pp_ids) == 1
        server_evs = load_trace_events(prof_path)
        server_ids = {e["args"]["trace_id"] for e in server_evs
                      if e.get("args", {}).get("trace_id")}
        assert pp_ids[0] in server_ids
        # client-queue and wire sub-spans carry the same id
        stages = {e["tid"] for e in client_evs
                  if e.get("args", {}).get("trace_id") == pp_ids[0]}
        assert {"client", "client-queue", "wire"} <= stages
        # clock offsets were recorded in-band for the merge tool
        offs = clock_offsets_from_events(client_evs)
        assert set(offs) == set(addrs)

    def test_trace_merge_cli(self, tmp_path):
        trace_path, prof_path, addrs = self._run_traced_op(tmp_path)
        out = str(tmp_path / "merged.json")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/trace_merge.py"),
             "--client", trace_path,
             "--server", f"{addrs[0]}={prof_path}",
             "-o", out, "--by-trace"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        doc = json.load(open(out))
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs}
        assert len(pids) >= 3  # client + server + by-trace-id groups
        by_trace = [e for e in evs if e.get("ph") != "M"
                    and isinstance(e.get("tid"), str)
                    and re.fullmatch(r"[0-9a-f]{16}", str(e["tid"]))]
        assert by_trace, "no per-trace-id rows in --by-trace output"
        # every by-trace span is COMPLETE ('X'): raw B events would
        # render as unterminated did-not-finish spans in Perfetto
        # (server E events carry no trace_id to pair them)
        assert all(e["ph"] in ("X", "i") for e in by_trace)
        # client and server spans meet under at least one shared id:
        # server-derived spans carry the profiler's args.tensor, client
        # spans don't
        rows = {}
        for e in by_trace:
            if e["ph"] != "X":
                continue
            origin = "server" if "tensor" in e.get("args", {}) else "client"
            rows.setdefault(e["tid"], set()).add(origin)
        assert any({"client", "server"} <= o for o in rows.values())

    def test_trace_report_cli(self, tmp_path):
        trace_path, _, _ = self._run_traced_op(tmp_path)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/trace_report.py"),
             trace_path],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "slowest keys" in proc.stdout
        assert "per-stage time breakdown" in proc.stdout
        assert "client-queue" in proc.stdout

    def test_trace_report_metrics_dump(self, tmp_path):
        reg = MetricsRegistry(tracer=Tracer(path=""))
        reg.counter("c").inc(4)
        reg.histogram("h").observe(0.01)
        p = tmp_path / "metrics.json"
        p.write_text(json.dumps(reg.snapshot()))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts/trace_report.py"),
             str(p)],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        assert "counters" in proc.stdout and "p99" in proc.stdout

    def test_span_durations_matches_be_pairs(self):
        evs = [{"ph": "B", "pid": 1, "tid": 1, "name": "op", "ts": 10.0},
               {"ph": "E", "pid": 1, "tid": 1, "name": "op", "ts": 35.0},
               {"ph": "X", "pid": 1, "tid": "wire", "name": "w",
                "ts": 0.0, "dur": 7.0}]
        rows = span_durations(evs)
        assert ("op", "1", 25.0) in rows and ("w", "wire", 7.0) in rows

    def test_merge_shifts_by_offset(self):
        client = [{"ph": "X", "name": "a", "ts": 100.0, "dur": 1.0,
                   "tid": "t", "args": {}}]
        server = [{"ph": "X", "name": "b", "ts": 1100.0, "dur": 1.0,
                   "tid": "t", "args": {}}]
        doc = merge_traces([("client", client, 0.0),
                            ("server", server, 1000.0)])
        by_name = {e["name"]: e for e in doc["traceEvents"]
                   if e.get("ph") == "X"}
        assert by_name["a"]["ts"] == 100.0
        assert by_name["b"]["ts"] == 100.0  # aligned onto client axis


# ----------------------------------------------------------- serving hooks


class TestServingObservability:
    def test_submit_mints_trace_id_and_finish_span(self, tmp_path):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        from byteps_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        from byteps_tpu.serving import ServeMetrics, ServingEngine
        from byteps_tpu.serving import metrics as sm

        os.environ["BYTEPS_TRACE_PATH"] = str(tmp_path / "serve.json")
        reset_config()
        reset_tracer()
        cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=64,
                                dtype=jnp.float32)
        model = Transformer(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))
        metrics = ServeMetrics()
        engine = ServingEngine(model, variables, n_slots=2, max_seq=64,
                               temperature=0.0, metrics=metrics)
        req = engine.submit(np.arange(4, dtype=np.int32), 3)
        assert re.fullmatch(r"[0-9a-f]{16}", req.trace_id)
        while req.state.value in ("queued", "prefilling", "active"):
            engine.step()
        assert req.state.value == "done"
        spans = [e for e in get_tracer().events()
                 if e.get("args", {}).get("trace_id") == req.trace_id]
        assert any(e["name"] == f"serve:req{req.id}" for e in spans)
        # credit-level gauge is live in the engine's registry
        credits = metrics.registry.get(sm.PREFILL_CREDITS)
        assert credits is not None and credits.value > 0

    def test_serve_metrics_histograms_back_summary(self):
        from byteps_tpu.serving.metrics import ServeMetrics

        m = ServeMetrics(tracer=Tracer(path=""))
        for i in range(10):
            m.observe_request(queue_wait_s=0.001 * i, ttft_s=0.01 * (i + 1),
                              tpot_s=0.002, tokens=4)
        s = m.summary()
        assert s["ttft_n"] == 10
        assert 0.04 <= s["ttft_p50_s"] <= 0.07
        # registry histograms are scrape-visible
        snap = m.registry.snapshot()
        assert snap["histograms"]["serve.ttft_s"]["count"] == 10


# The env.md knob lint that lived here (PR 6's
# test_every_config_knob_is_documented_in_env_md) moved into the
# analysis subsystem: byteps_tpu/analysis/envknobs.py, exercised by
# tests/test_analysis.py::test_every_config_knob_documented and
# scripts/lint.py — AST-accurate, and extended to flag raw BYTEPS_*
# environ reads anywhere in the package.
