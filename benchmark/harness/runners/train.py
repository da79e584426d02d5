"""Runner for ``kind: "train_job"`` mixes: one process owns the cell's
chips, builds the configuration's data-parallel step, checks it against
the plain reference, warms it, then counts the tokens it trains in the
window.

Timeline: set-up (weights from the seed, reference loss, one AOT
compile of the step, ``warmup_steps`` steps) — then the window.  The
host keeps exactly one step queued behind the running one (dispatch
step *i*, then wait for step *i - 1*), so the device is never starved
and the run never has more than one step to drain at the end; the
window ends in ``block_until_ready``.  With ``--trace 1`` the untraced
part is shortened and ``trace_steps`` further steps run under the
profiler with the loop's own spans (``bench.dispatch``, ``bench.wait``)
on the same clock, so idle gaps can be laid at the host's door.
"""

from __future__ import annotations

import math
import os
import shutil
import time
import types

from benchmark.harness import device as dev
from benchmark.harness import manifest, stats, xplane

UNTRACED_SHARE = 0.8   # of --seconds, when a traced part follows


def run(job) -> dict:
    marks = [("start", time.time())]
    mark = lambda name: marks.append((name, time.time()))  # noqa: E731
    devices = dev.claim_devices(job.chips, job.rehearse)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import byteps_tpu as bps

    clog = dev.CompileLog()
    bps.init(devices=devices)     # places the compile cache too
    mesh = bps.mesh()
    world = bps.size()
    if world != job.chips:
        raise RuntimeError(f"mesh world {world} != cell chips {job.chips}")
    cfg, mix = job.config, job.mix
    builder = manifest.load_module("builders", cfg["builder"])
    reference = manifest.load_module("reference", cfg["reference"])
    step, state, batches, meta = builder.build_training(
        cfg, mix, mesh, job.seed)
    ring = len(batches)
    checks = {}
    jax.block_until_ready(state)
    mark("devices_and_state")

    # the reference's loss on the first batch and the initial weights,
    # taken before the step donates them
    tokens0 = jax.device_put(batches[0]["tokens"],
                             NamedSharding(mesh, P()))
    ref_loss = reference.loss(state.params, tokens0, cfg)
    mark("reference")

    lowered = step.lower(state, batches[0])
    mark("trace_and_lower")
    compiled = lowered.compile()
    mark("compile_or_cache_load")
    names = dev.mosaic_calls(compiled.as_text())
    missing = dev.missing_kernels(names, mix["kernels"])
    # a rehearsal interprets its kernels: no Mosaic call may appear
    checks["kernels"] = (not names) if job.rehearse else (not missing)
    job.note(event="compiled", mosaic_calls=sorted(
        {n.split(".")[0] for n in names}), missing=missing,
        memory=str(compiled.memory_analysis()))

    if world > 1:
        shard_dev = {s.device for s in
                     batches[0]["tokens"].addressable_shards}
        replicated = all(
            leaf.sharding.is_fully_replicated
            and len(leaf.sharding.device_set) == world
            for leaf in jax.tree_util.tree_leaves(state.params))
        checks["batch_on_every_chip"] = len(shard_dev) == world
        checks["params_replicated"] = bool(replicated)

    state, m = compiled(state, batches[0])
    loss0 = float(m["loss"])
    gap = abs(loss0 - ref_loss)
    checks["loss_matches_reference"] = gap <= mix["loss_tolerance"]
    job.note(event="reference", system_loss=loss0, reference_loss=ref_loss,
             abs_gap=gap, tolerance=mix["loss_tolerance"])
    for i in range(1, mix["warmup_steps"]):
        state, m = compiled(state, batches[i % ring])
    jax.block_until_ready((state, m))
    mark("warm_steps")
    job.note(event="warm", setup_s={
        b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])},
        memory_stats=devices[0].memory_stats(), **clog.summary())

    # ------------------------------------------------------------ window
    untraced = job.seconds * (UNTRACED_SHARE if job.trace else 1.0)
    losses, done_at = [], []
    n, prev = 0, None
    wall_start = time.time()
    t_start = time.perf_counter()
    while True:
        state, m = compiled(state, batches[n % ring])
        n += 1
        losses.append(m["loss"])
        if prev is not None:
            prev.block_until_ready()
            done_at.append(time.perf_counter())
        prev = m["loss"]
        if time.perf_counter() - t_start >= untraced:
            break
    jax.block_until_ready((state, m))
    t_end = time.perf_counter()
    tokens_per_s = n * meta["tokens_per_step"] / (t_end - t_start)
    step_s = [b - a for a, b in zip(done_at, done_at[1:])]

    trace, traced_steps = None, 0
    if job.trace:
        trace_dir = os.path.join(job.out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        traced_steps = mix["trace_steps"]
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            for k in range(traced_steps):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, m = compiled(state, batches[(n + k) % ring])
                losses.append(m["loss"])
                with jax.profiler.TraceAnnotation("bench.wait"):
                    prev.block_until_ready()
                prev = m["loss"]
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready((state, m))
        traced_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        path = xplane.find_xplane(trace_dir)
        trace = xplane.load(path) if path else None
        job.note(event="traced", steps=traced_steps,
                 tokens_per_s_while_traced=traced_steps
                 * meta["tokens_per_step"] / traced_s,
                 tokens_per_s_untraced=tokens_per_s, xplane=path)

    vals = [float(x) for x in losses]
    finite = [math.isfinite(v) for v in vals]
    checks["losses_finite"] = all(finite)
    checks["loss_fell"] = (sum(vals[-10:]) / len(vals[-10:])) < loss0
    checks["no_compile_in_window"] = clog.count_since(wall_start) == 0
    job.note(event="steps", **stats.describe(
        "step_s", step_s, 90, "s"), steps=n, first_loss=loss0,
        last_losses=vals[-3:], tokens_per_s_per_chip=tokens_per_s / world,
        checks=checks)
    report = dev.device_report(devices)
    bps.shutdown()
    return {
        "correct": all(checks.values()), "attempted": len(vals),
        "failed": finite.count(False), "window_start_wall": wall_start,
        "values": {"train_tokens_per_s": tokens_per_s},
        "device": report,
        "ctx": types.SimpleNamespace(
            trace=trace, dims=builder.dims(cfg),
            train={"tokens_per_s": tokens_per_s,
                   "traced_steps": traced_steps,
                   "per_chip_batch": mix["per_chip_batch"],
                   "seq_len": mix["seq_len"],
                   "table_rows": builder.vocab_rows(cfg)},
            serve=None),
    }
