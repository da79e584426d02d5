"""Runner for ``open_loop`` and ``closed_loop`` mixes against the paged
serving engine.

Three processes, so that nothing the benchmark adds shares an
interpreter lock with the engine's tick thread: this one stays off JAX
and only coordinates; ``serve_child.py`` holds the chip (engine behind
the program's TCP frontend, profiler when traced); ``loadgen.py`` sends
the traffic and stamps the replies.  End-to-end latency is taken at the
client, from the instant a request was due — the engine's own
``ttft``/``tpot`` histograms start at admission and are not used.

Counters are read from the normal surface: one ``OP_STATS`` request
over TCP when the window opens and one when it closes.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import types

from benchmark.harness import latency, manifest, stats, wire

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fetch_stats(addr) -> dict:
    with socket.create_connection(addr, timeout=30.0) as s:
        s.sendall(wire.encode_stats_request())
        buf = bytearray()
        while True:
            fr = wire.parse_frame(buf)
            if fr is not None:
                return json.loads(fr[3].decode())
            data = s.recv(1 << 16)
            if not data:
                raise ConnectionError("frontend closed during STATS")
            buf += data


def read_event(proc, want: str, note) -> dict:
    """Next JSON line of ``proc`` whose ``event`` is ``want``; other
    lines are passed on as notes.  A process that ends first is an
    error."""
    while True:
        raw = proc.stdout.readline()
        if not raw:
            raise RuntimeError(
                f"{proc.args[1]} ended (rc={proc.wait()}) before {want!r}")
        try:
            msg = json.loads(raw)
        except ValueError:
            continue
        if isinstance(msg, dict) and msg.get("event") == want:
            return msg
        note(source=os.path.basename(proc.args[1]), line=msg)


def stop(proc) -> None:
    if proc is None:
        return
    if proc.poll() is None:
        try:
            if proc.stdin:
                proc.stdin.close()
            proc.wait(timeout=60.0)
        except (subprocess.TimeoutExpired, OSError):
            proc.kill()
    proc.wait()


def percentile_over_attempted(samples, failed: int, q: float):
    """The ``q``-th percentile where every failed request counts as
    worse than any sample; ``(value, reachable)`` — unreachable when
    the rank lands among the failures."""
    padded = sorted(samples) + [float("inf")] * failed
    v = stats.pctl(padded, q)
    if v is None or v == float("inf"):
        return (max(samples) if samples else None), False
    return v, True


def run(job) -> dict:
    cfg, mix = job.config, job.mix
    builder = manifest.load_module("builders", cfg["builder"],
                                   job.bench_dir)
    env = dict(os.environ, BYTEPS_TRANSPORT="tcp")
    child = loadgen = None
    try:
        child = subprocess.Popen(
            [sys.executable, os.path.join(HARNESS, "serve_child.py"),
             json.dumps({"config": cfg, "mix": mix, "seed": job.seed,
                         "chips": job.chips, "rehearse": job.rehearse,
                         "out_dir": job.out_dir,
                         "bench_dir": job.bench_dir})],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=job.root)
        try:
            ready = read_event(child, "ready", job.note)
        except RuntimeError:
            if child.wait() == 4:
                from benchmark.harness.device import NoChipError

                raise NoChipError("the serve child found no chip")
            raise
        job.note(event="ready", **{k: ready[k] for k in ready
                                   if k != "event"})
        addr = ("127.0.0.1", ready["port"])
        # the first STATS reply fingerprints the weights with a few
        # eager programs per leaf: pay that here, not in the window
        fetch_stats(addr)
        spec_path = os.path.join(job.out_dir, "loadgen_spec.json")
        with open(spec_path, "w") as f:
            json.dump({"addr": addr, "mix": mix, "seed": job.seed,
                       "seconds": job.seconds, "vocab": cfg["vocab_size"],
                       "max_seq": cfg["engine"]["max_seq"],
                       "out": os.path.join(job.out_dir, "loadgen.json")},
                      f)
        loadgen = subprocess.Popen(
            [sys.executable, os.path.join(HARNESS, "loadgen.py"),
             spec_path], stdout=subprocess.PIPE, text=True, cwd=job.root)
        w0 = read_event(loadgen, "window_start", job.note)["wall"]
        before = fetch_stats(addr)
        traced = None
        if job.trace:
            # the profiled interval sits in the middle of the window
            t_trace = min(mix["trace_seconds"], job.seconds / 2.0)
            time.sleep(max(0.0, w0 + (job.seconds - t_trace) / 2.0
                           - time.time()))
            child.stdin.write(json.dumps(
                {"cmd": "trace", "seconds": t_trace}) + "\n")
            child.stdin.flush()
            traced = read_event(child, "traced", job.note)
        read_event(loadgen, "window_end", job.note)
        after = fetch_stats(addr)
        done = read_event(loadgen, "done", job.note)
        stop(loadgen)
        child.stdin.write(json.dumps({"cmd": "finish", "since": w0}) + "\n")
        child.stdin.flush()
        fin = read_event(child, "finished", job.note)
    finally:
        stop(loadgen)
        stop(child)

    with open(done["path"]) as f:
        lg = json.load(f)
    window = tuple(lg["window"])
    values, checks = {}, {}
    if lg["kind"] == "open_loop":
        s = latency.open_loop_samples(lg["requests"], window)
        checks["tail_reachable"] = True
        for q in mix["percentiles"]["ttft"]:
            v, ok = percentile_over_attempted(s["ttft_ms"], s["failed"], q)
            values[f"serve_ttft_p{q:g}_ms"] = v
            checks["tail_reachable"] &= ok
        for q in mix["percentiles"]["itl"]:
            values[f"serve_itl_p{q:g}_ms"] = stats.pctl(s["itl_ms"], q)
        job.note(event="samples", **stats.describe(
            "serve_ttft_ms", s["ttft_ms"], 90, "ms"))
        job.note(event="samples", **stats.describe(
            "serve_itl_ms", s["itl_ms"], 99, "ms"))
    else:
        s = latency.closed_loop_samples(lg["requests"], window)
        values["serve_tokens_per_s"] = s["tokens_per_s"]
        checks["two_prompts_finished"] = s["tokens_per_s"] is not None
        job.note(event="samples", completed=s["completed"],
                 prompts_finished=s["prompts_finished"],
                 ttft_median_ms=stats.median(s["ttft_ms"]),
                 itl_median_ms=stats.median(s["itl_ms"]),
                 requests_generated=lg["requests_generated"],
                 requests_started=lg["requests_started"])
    errors = sorted({r["error"] for r in lg["requests"]
                     if r["error"] and "unfinished" not in r["error"]})
    if errors:
        job.note(event="request_errors", errors=errors[:5])

    want_path = "paged_fused"
    checks["probe_matches_reference"] = bool(ready["probe"]["ok"])
    checks["attention_path"] = after["attention_path"] == want_path
    checks["platform"] = (after["device"]["platform"]
                          == ready["device"]["platform"]
                          == ("cpu" if job.rehearse else "tpu"))
    checks["compile_counts_unchanged"] = (
        before["compile_counts"] == after["compile_counts"]
        == fin["compile_counts"])
    checks["no_compile_in_window"] = fin["compiles_since"] == 0
    job.note(event="checks", checks=checks, queue_depth_before=before[
        "queue_depth"], queue_depth_after=after["queue_depth"],
        occupancy_after=after["occupancy"], kv_blocks=after["kv_blocks"],
        preemptions=after.get("serve.preemptions", 0),
        compile_counts=fin["compile_counts"],
        compiles=fin["compiles"], compile_s=fin["compile_s"],
        persistent_cache_hits=fin["persistent_cache_hits"],
        persistent_cache_misses=fin["persistent_cache_misses"])

    trace = None
    if traced and traced.get("xplane"):
        from benchmark.harness import xplane   # the child is gone: JAX
        trace = xplane.load(traced["xplane"])  # is free to import here
    return {
        "correct": all(checks.values()), "attempted": s["attempted"],
        "failed": s["failed"], "window_start_wall": w0, "values": values,
        "device": fin["device"],
        "ctx": types.SimpleNamespace(
            trace=trace, dims=builder.dims(cfg), train=None,
            serve={"samples": s, "records": lg["requests"],
                   "window": window, "stats_before": before,
                   "stats_after": after,
                   "trace_interval": tuple(traced["interval"])
                   if traced else None}),
    }
