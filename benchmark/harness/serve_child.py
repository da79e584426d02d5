#!/usr/bin/env python3
"""The process that holds the chip in a serve cell.

Started by ``runners/serve.py`` as ``python serve_child.py <json>``.
It builds the configuration's model with weights from the seed, puts
``ServingEngine(model, variables, **engine)`` behind the program's TCP
frontend (``serve(engine, 0, in_thread=True)``), warms every program
the cell's traffic will use, checks the mix's probe requests (one of a
single chunk, one of several) against the plain reference, and then
says ``ready`` with its port.  Requests come
over TCP from the load generator's process; this one only answers
commands on stdin (one JSON object per line, one JSON reply per line on
stdout):

    {"cmd": "trace", "seconds": s}   profile s seconds, reply with the
                                     wall-clock interval and the file
    {"cmd": "finish", "since": wall} reply with memory, compile counts
                                     and compiles since ``wall``; exit

The correctness probes compare logits, not tokens: the reference runs
the full forward on prompt + emitted tokens, and every emitted token's
reference logit must lie within ``logit_tolerance`` of the reference's
maximum at its position.  A rounding flip of the argmax passes; a wrong
block, position or head is several logit standard deviations away.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import device as dev  # noqa: E402
from benchmark.harness import compare, manifest, xplane  # noqa: E402


reply = manifest.note


def chunk_buckets(engine_cfg: dict) -> list:
    """The chunk-prefill programs a request can reach: power-of-two
    multiples of ``min_prefill_bucket`` up to ``chunk``."""
    out, b = [], engine_cfg["min_prefill_bucket"]
    while b <= engine_cfg["chunk"]:
        out.append(b)
        b *= 2
    return out


def warm(engine, cfg: dict, rng) -> dict:
    """One short request per chunk bucket, together: compiles the chunk
    programs and the decode program, nothing else."""
    vocab = cfg["vocab_size"]
    reqs = [engine.submit(rng.integers(0, vocab, n).astype("int32"), 3)
            for n in chunk_buckets(cfg["engine"])]
    for r in reqs:
        r.result(timeout=1100.0)
    return engine.compile_counts()


def probe(engine, reference, variables, cfg: dict, spec: dict, rng) -> dict:
    import jax.numpy as jnp
    import numpy as np

    new = spec["new_tokens"]
    width = max(spec["prompt_len"]) + new
    by_len, flips = {}, 0
    for plen in spec["prompt_len"]:
        prompt = rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
        toks = np.asarray(engine.submit(prompt, new).result(timeout=300.0))
        if toks.shape[0] != new:
            return {"ok": False, "why": f"{toks.shape[0]} tokens, "
                                        f"wanted {new}"}
        seq, rows = compare.teacher_forced(prompt, toks, width)
        ref = reference.logits(variables["params"], jnp.asarray(seq), cfg)
        gaps = compare.logit_gaps(np.asarray(ref[rows]), toks)
        by_len[plen] = float(gaps.max())
        flips += int((gaps > 0).sum())
    worst = max(by_len.values())
    return {"ok": worst <= spec["logit_tolerance"], "max_logit_gap": worst,
            "max_logit_gap_by_prompt_len": by_len,
            "argmax_flips": flips, "tolerance": spec["logit_tolerance"]}


def main() -> int:
    args = json.loads(sys.argv[1])
    rehearse = args["rehearse"]
    t0 = time.time()
    try:
        devices = dev.claim_devices(args["chips"], rehearse)
    except dev.NoChipError as e:
        print(f"no chip, no result: {e}", file=sys.stderr)
        return 4
    import jax
    import numpy as np

    t_devices = time.time()
    clog = dev.CompileLog()
    # the program's own rule for the persistent compilation cache:
    # JAX_COMPILATION_CACHE_DIR if set, else the fixed
    # <checkout>/.jax_cache — inside the checkout, never a temporary name
    from byteps_tpu.common.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    cfg, mix = args["config"], args["mix"]
    builder = manifest.load_module("builders", cfg["builder"],
                                   args["bench_dir"])
    reference = manifest.load_module("reference", cfg["reference"],
                                     args["bench_dir"])
    from byteps_tpu.serving.engine import ServingEngine
    from byteps_tpu.serving.frontend import serve

    model, variables = builder.build_serving(cfg, args["seed"])
    jax.block_until_ready(variables)
    t_weights = time.time()
    engine = ServingEngine(model, variables, **cfg["engine"])
    srv, thread = serve(engine, 0, host="127.0.0.1", in_thread=True)
    rng = np.random.default_rng([args["seed"], 0x9E0BE])
    try:
        counts = warm(engine, cfg, rng)
        t_warm = time.time()
        verdict = probe(engine, reference, variables, cfg, mix["probes"],
                        rng)
        reply(event="ready", port=srv.server_address[1],
              device=dev.device_report(devices), probe=verdict,
              compile_counts=counts, compile_cache_dir=cache_dir,
              setup={"claim_devices_s": t_devices - t0,
                     "weights_s": t_weights - t_devices,
                     "engine_and_warm_s": t_warm - t_weights,
                     "probe_s": time.time() - t_warm},
              **clog.summary())
        for raw in sys.stdin:
            cmd = json.loads(raw)
            if cmd["cmd"] == "trace":
                trace_dir = os.path.join(args["out_dir"], "trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                w0 = time.time()
                with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                    time.sleep(cmd["seconds"])
                w1 = time.time()
                jax.profiler.stop_trace()
                reply(event="traced", interval=[w0, w1],
                      xplane=xplane.find_xplane(trace_dir))
            elif cmd["cmd"] == "finish":
                reply(event="finished",
                      device=dev.device_report(devices),
                      compile_counts=engine.compile_counts(),
                      compiles_since=clog.count_since(cmd["since"]),
                      **clog.summary())
                break
    finally:
        srv.shutdown()
        srv.server_close()      # stops the engine's tick thread too
        thread.join(timeout=30.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
