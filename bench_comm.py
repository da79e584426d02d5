"""Comm-visible benchmark matrix (VERDICT r2 #4): every point runs on a
virtual 8-device ``dcn×dp`` mesh (the ``BYTEPS_FORCE_DISTRIBUTED``
harness), so collectives do real work and the numbers expose what the
single-chip bench.py cannot:

  * **bucket-size sweep** — the scheduled DP train step at 1/4/16 MB
    partition_bytes, with a measured **comm fraction** per point (step
    time vs the identical local-update step with no collectives);
  * **scheduled vs unscheduled priority order** on the eager engine — the
    runtime ScheduledQueue drains gradient-sized tensors arriving in
    backward order (last layer first) either with reference priorities
    (earlier-declared = higher priority — what the next forward needs
    first) or with reversed priorities; reported as time-to-first-needed
    (layer 0) and full drain — the metric ByteScheduler optimizes
    (bytescheduler/torch/optimizer.py:180-214);
  * **jit bucket order** — the same DP step with the BucketPlan's
    schedule_order reversed, showing the traced path's order sensitivity
    (XLA owns the final schedule there; the eager path is where runtime
    order matters — this line quantifies both honestly);
  * **pipelined wire** (PR 4, docs/wire.md) — serial vs windowed
    ``RemoteStore.push_pull`` against 4 real PS shard processes with
    a >=4-partition tensor, on raw loopback AND on an emulated
    5 ms/hop wire; archived into BENCH_COMM.json (these rows stay
    pinned to TCP so the longitudinal comparison holds);
  * **endpoint transports** (docs/wire.md "Transports") — same-host
    tcp vs unix vs shm A/B on single-frame ``pull``/``push_pull``
    round trips against one real shard process (``--transports-only``
    runs just this; ``--wire-only`` runs the wire benches);
  * **hierarchical push/pull** (docs/wire.md "Hierarchical reduction")
    — on-vs-off A/B of the local-mesh reduce-scatter stage: 4 emulated
    colocated workers against real shard processes on the 5 ms wire;
    measured mutation wire bytes/step must drop by ~local_size
    (``--hierarchical`` runs just this);
  * **ZeRO-1 optimizer-state sharding** (docs/parallel.md,
    ``training/zero.py``) — replicated vs span-sharded eager PS
    optimizer loop against real shard processes: per-rank mutation
    wire bytes AND client optimizer-state bytes must drop by ~world,
    final params bit-equal (``--zero`` runs just this).

Prints ONE JSON line per point.  Runs anywhere (CPU virtual mesh by
construction):  python bench_comm.py [--layers 8 --dim 1024]
"""

from __future__ import annotations

import argparse
import json
import os
import time

from bench_util import archive_rows, emit_row

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # for the shard processes

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
_PLATFORM = "cpu"  # pinned above; stamped on every row this script prints

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402


from byteps_tpu.engine.transport import free_port as _free_port  # noqa: E402


def _wait_port(p):
    import socket as _socket

    for _ in range(150):
        try:
            _socket.create_connection(("127.0.0.1", p), timeout=0.2).close()
            return
        except OSError:
            time.sleep(0.2)
    raise RuntimeError(f"PS shard on :{p} never came up")


def _time(fn, state, batch, iters, warmup=2):
    for _ in range(warmup):
        state, m = fn(state, batch)
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = fn(state, batch)
    jax.block_until_ready((m, state))
    return (time.perf_counter() - t0) / iters, state


def bucket_sweep(mesh, layers, dim, iters):
    from byteps_tpu.parallel.collectives import shard_map
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    def loss_fn(params, mstate, batch):
        h = batch["x"]
        for i in range(layers):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h[:, 0] - batch["y"]) ** 2), mstate

    params = {f"w{i}": jnp.full((dim, dim), 0.01, jnp.float32)
              for i in range(layers)}
    tx = optax.sgd(0.01)
    batch = shard_batch(
        {"x": jnp.ones((64, dim)), "y": jnp.zeros((64,))}, mesh,
        axes=("dcn", "dp"))

    # local-update analog: same mesh, same per-device compute, NO
    # collectives — the denominator of the comm fraction
    def local_step(state, b):
        p, o = state

        def lf(pp):
            return loss_fn(pp, {}, b)[0]

        loss, g = jax.value_and_grad(lf)(p)
        upd, o = tx.update(g, o, p)
        return (optax.apply_updates(p, upd), o), {"loss": loss}

    local_jit = jax.jit(shard_map(
        local_step, mesh, in_specs=((P(), P()), P(("dcn", "dp"))),
        out_specs=((P(), P()), P())), donate_argnums=(0,))
    # own copy: local_jit donates its state, and params seeds the bucketed
    # steps below too
    t_local, _ = _time(
        local_jit,
        (jax.tree_util.tree_map(jnp.copy, params), tx.init(params)),
        batch, iters)

    out = []
    for mb in (1, 4, 16):
        step = make_data_parallel_step(
            loss_fn, tx, mesh, axes=("dcn", "dp"),
            partition_bytes=mb * 1024 * 1024)
        state = step.init_state(jax.tree_util.tree_map(jnp.copy, params))
        t, _ = _time(step, state, batch, iters)
        out.append({
            "metric": f"dp_step_bucket_{mb}mb_ms",
            "value": round(t * 1e3, 2),
            "unit": "ms/step",
            "comm_fraction": round(max(0.0, 1 - t_local / t), 4),
            "ms_per_step_local_only": round(t_local * 1e3, 2),
            "mesh": "dcn2_dp4" if "dcn" in mesh.axis_names else "dp8",
        })
        emit_row(out[-1], _PLATFORM)
    return out


def eager_priority_order(mesh, n_tensors, mbytes, iters):
    """Drain gradient-sized tensors arriving in backward order through the
    real engine, with reference priorities vs reversed priorities."""
    import byteps_tpu as bps
    from byteps_tpu.engine import dispatcher as _dispatcher

    bps.init(mesh=mesh)
    engine = _dispatcher.get_engine()
    world = engine.world
    elems = mbytes * 1024 * 1024 // 4
    x = jnp.ones((world, elems), jnp.float32)
    jax.block_until_ready(x)

    def drain(prio_sign, tag, rep):
        handles = {}
        t0 = time.perf_counter()
        # backward produces the LAST layer's gradient first
        for i in reversed(range(n_tensors)):
            handles[i] = engine.push_pull_async(
                x, f"CommBench{tag}{rep}.layer{i}", average=True,
                priority=prio_sign * (n_tensors - i))
        engine.synchronize(handles[0])      # layer 0: needed first by the
        t_first = time.perf_counter() - t0  # next forward
        for i in range(1, n_tensors):
            engine.synchronize(handles[i])
        return t_first, time.perf_counter() - t0

    # warmup (compiles the stacked reduce)
    drain(+1, "warm", 0)
    sched_first = unsched_first = float("inf")
    sched_all = unsched_all = float("inf")
    for r in range(iters):
        tf, ta = drain(+1, "sched", r)      # reference: layer 0 highest
        sched_first, sched_all = min(sched_first, tf), min(sched_all, ta)
        tf, ta = drain(-1, "rev", r)        # reversed: arrival order wins
        unsched_first, unsched_all = (min(unsched_first, tf),
                                      min(unsched_all, ta))
    res = {
        "metric": "eager_first_needed_gradient_ms",
        "value": round(sched_first * 1e3, 2),
        "unit": "ms",
        "unscheduled_ms": round(unsched_first * 1e3, 2),
        "vs_unscheduled": round(unsched_first / sched_first, 3),
        "drain_all_ms": round(sched_all * 1e3, 2),
        "drain_all_unscheduled_ms": round(unsched_all * 1e3, 2),
        "tensors": n_tensors,
        "mbytes_each": mbytes,
    }
    emit_row(res, _PLATFORM)
    return res


def delayed_vs_sync(mesh, layers, dim, iters):
    """Delayed-grad overlap step (training/overlap.py — the ByteScheduler
    analog, 1-step-stale updates) vs the synchronous bucketed step on the
    same model/mesh: the throughput the staleness buys (VERDICT r3
    missing #2).  Both steps run identical compute and identical
    collective volume; the delayed step's collectives have no data
    dependency on the current batch, so the scheduler may overlap them
    with forward+backward."""
    from byteps_tpu.training import make_data_parallel_step, shard_batch
    from byteps_tpu.training.overlap import make_delayed_grad_step

    def loss_fn(params, mstate, batch):
        h = batch["x"]
        for i in range(layers):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h[:, 0] - batch["y"]) ** 2), mstate

    params = {f"w{i}": jnp.full((dim, dim), 0.01, jnp.float32)
              for i in range(layers)}
    tx = optax.sgd(0.01)
    batch = shard_batch(
        {"x": jnp.ones((64, dim)), "y": jnp.zeros((64,))}, mesh,
        axes=("dcn", "dp"))

    sync = make_data_parallel_step(
        loss_fn, tx, mesh, axes=("dcn", "dp"),
        partition_bytes=4 * 1024 * 1024)
    s_state = sync.init_state(jax.tree_util.tree_map(jnp.copy, params))
    t_sync, _ = _time(sync, s_state, batch, iters)

    delayed = make_delayed_grad_step(
        loss_fn, tx, mesh, axes=("dcn", "dp"),
        partition_bytes=4 * 1024 * 1024)
    d_state = delayed.init_state(jax.tree_util.tree_map(jnp.copy, params))
    t_del, _ = _time(delayed, d_state, batch, iters)

    res = {
        "metric": "delayed_grad_vs_sync_ms",
        "value": round(t_del * 1e3, 2),
        "unit": "ms/step",
        "sync_bucketed_ms": round(t_sync * 1e3, 2),
        "overlap_speedup": round(t_sync / t_del, 3),
        "staleness": "updates lag their gradients by exactly 1 step",
    }
    emit_row(res, _PLATFORM)
    return res


def jit_bucket_order(mesh, layers, dim, iters):
    """Reversed BucketPlan.schedule_order inside the traced step: XLA owns
    the final schedule, so ~1.0 is the expected (and honest) result."""
    from byteps_tpu.common import partition as partition_mod
    from byteps_tpu.training import make_data_parallel_step, shard_batch

    def loss_fn(params, mstate, batch):
        h = batch["x"]
        for i in range(layers):
            h = jnp.tanh(h @ params[f"w{i}"])
        return jnp.mean((h[:, 0] - batch["y"]) ** 2), mstate

    params = {f"w{i}": jnp.full((dim, dim), 0.01, jnp.float32)
              for i in range(layers)}
    tx = optax.sgd(0.01)
    batch = shard_batch(
        {"x": jnp.ones((64, dim)), "y": jnp.zeros((64,))}, mesh,
        axes=("dcn", "dp"))

    def build(reverse):
        orig = partition_mod.BucketPlan.schedule_order
        if reverse:
            partition_mod.BucketPlan.schedule_order = \
                lambda self: list(reversed(orig(self)))
        try:
            step = make_data_parallel_step(
                loss_fn, tx, mesh, axes=("dcn", "dp"),
                partition_bytes=4 * 1024 * 1024, donate=False)
            state = step.init_state(
                jax.tree_util.tree_map(jnp.copy, params))
            # schedule_order is consulted at TRACE time (push_pull_tree
            # runs under jit on the first call) — trace while the patch
            # is live or the reversed variant silently uses the original
            jax.block_until_ready(step(state, batch))
            return step, state
        finally:
            partition_mod.BucketPlan.schedule_order = orig

    step_s, st_s = build(False)
    t_sched, _ = _time(step_s, st_s, batch, iters)
    step_r, st_r = build(True)
    t_rev, _ = _time(step_r, st_r, batch, iters)
    res = {
        "metric": "jit_bucket_order_scheduled_ms",
        "value": round(t_sched * 1e3, 2),
        "unit": "ms/step",
        "reversed_ms": round(t_rev * 1e3, 2),
        "vs_reversed": round(t_rev / t_sched, 3),
    }
    emit_row(res, _PLATFORM)
    return res


def pipelined_wire(mb=8, part_kb=1024, shards=4, delay_ms=5.0, reps=8,
                   archive=True):
    """Serial vs pipelined ``RemoteStore.push_pull`` (PR 4, docs/wire.md):
    4 real PS shard *processes*, one tensor split into >=4 partitions,
    measured interleaved (serial/pipelined alternating, min + median) so
    ambient load cancels.  Two rows:

      * raw loopback — honest but CPU-bound on small hosts: client and
        servers share the cores, so the overlap the window buys is
        whatever idle the serial path actually had;
      * emulated 5 ms/hop wire (protocol-aware FaultInjectingProxy
        ``delay`` on every request) — the latency-dominated regime the
        architecture targets.  The proxy serializes its delays per
        connection, which UNDERSTATES pipelining vs a real link (real
        in-flight frames overlap their latencies), so the measured
        speedup is a lower bound.
    """
    import dataclasses
    import statistics
    import subprocess
    import sys as _sys

    from byteps_tpu.common.config import get_config, set_config
    from byteps_tpu.engine import ps_server
    from byteps_tpu.resilience import FaultInjectingProxy

    ports = [_free_port() for _ in range(shards)]
    procs = []
    rows = []
    saved_cfg = get_config()
    try:
        for p in ports:  # spawn INSIDE the try: a failed spawn must not
            procs.append(subprocess.Popen(  # leak earlier shards
                [_sys.executable, "-c",
                 f"from byteps_tpu.engine import ps_server; "
                 f"ps_server.serve({p}, host='127.0.0.1', "
                 f"use_native=False)"],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}))
        for p in ports:
            _wait_port(p)
        # replace(), not a fresh Config: env-derived knobs (e.g.
        # BYTEPS_WIRE_WINDOW under test) must keep applying
        set_config(dataclasses.replace(saved_cfg,
                                       partition_bytes=part_kb * 1024))
        x = np.ones(mb * 1024 * 1024 // 4, np.float32)
        nparts = max(1, mb * 1024 // part_kb)

        def measure(addrs, tag):
            # pinned to TCP: these are the longitudinal serial-vs-window
            # A/B rows — letting BYTEPS_TRANSPORT=auto flip them onto
            # the UDS fast path would silently change what they measure
            # (transport_ab() below owns the per-transport comparison)
            stores = {
                "serial": ps_server.RemoteStore(addrs, wire_window=0,
                                                transport="tcp"),
                "pipelined": ps_server.RemoteStore(addrs,
                                                   transport="tcp"),
            }
            for mode, st in stores.items():
                st.init_tensor(f"{tag}_{mode}", np.zeros_like(x))
                st.push_pull(f"{tag}_{mode}", x)  # warm the path
            t = {m: [] for m in stores}
            for _ in range(reps):  # interleaved: load hits both alike
                for mode, st in stores.items():
                    t0 = time.perf_counter()
                    st.push_pull(f"{tag}_{mode}", x)
                    t[mode].append(time.perf_counter() - t0)
            for st in stores.values():
                st.close()
            return t

        direct = measure([f"127.0.0.1:{p}" for p in ports], "raw")
        proxies = [FaultInjectingProxy(f"127.0.0.1:{p}", seed=i)
                   for i, p in enumerate(ports)]
        for px in proxies:
            px.set_rates(delay=delay_ms / 1e3)
        try:
            lat = measure([px.addr for px in proxies], "lat")
        finally:
            for px in proxies:
                px.close()

        for metric, t, wire in (
                ("pipelined_wire_push_pull_ms", direct, "raw loopback"),
                (f"pipelined_wire_{delay_ms:g}ms_hop_ms", lat,
                 f"emulated {delay_ms:g}ms/hop (proxy; conservative)")):
            row = {
                "metric": metric,
                "value": round(min(t["pipelined"]) * 1e3, 2),
                "unit": "ms/push_pull",
                "serial_ms": round(min(t["serial"]) * 1e3, 2),
                "speedup_min": round(min(t["serial"])
                                     / min(t["pipelined"]), 3),
                "speedup_median": round(
                    statistics.median(t["serial"])
                    / statistics.median(t["pipelined"]), 3),
                "shards": shards,
                "parts": nparts,
                "tensor_mb": mb,
                "wire": wire,
                "window": get_config().wire_window,
                "tool": "bench_comm.py",
            }
            rows.append(row)
            emit_row(row, _PLATFORM)
    finally:
        set_config(saved_cfg)
        for pr in procs:
            pr.terminate()
        for pr in procs:  # reap, don't zombie through the rest of main()
            try:
                pr.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pr.kill()
                pr.wait(timeout=5)
    if archive and rows:
        _archive_rows(rows)
    return rows


def transport_ab(mb=1, reps=24, archive=True):
    """Same-host transport A/B (docs/wire.md "Transports"): one real PS
    shard process advertising all three endpoints, one client per
    transport, measuring ``pull`` (one-way bulk — the wire-throughput
    number the acceptance bar reads) and ``push_pull`` (round trip
    incl. the server's dense add) of an ``mb``-MiB tensor as a SINGLE
    frame.  The default 1 MiB frame is the partition-sized regime the
    colocated client actually puts on the wire, where per-frame
    transport cost (syscalls, TCP stack traversal, wakeup latency)
    dominates over memcpy — exactly what a local transport exists to
    remove.  Reps are interleaved across transports so this bursty
    2-vCPU host's throttling hits all of them alike, and the archived
    value is min-of-reps over a deliberately long rep count (24): the
    host throttles in multi-second windows, so short runs can land
    entirely inside one; ~10 reps was measurably not enough for the
    ratio to converge."""
    import dataclasses
    import subprocess
    import sys as _sys

    from byteps_tpu.common.config import get_config, set_config
    from byteps_tpu.engine import ps_server

    port = _free_port()
    saved_cfg = get_config()
    rows = []
    proc = None
    transports = ("tcp", "unix", "shm")
    try:
        proc = subprocess.Popen(
            [_sys.executable, "-c",
             f"from byteps_tpu.engine import ps_server; "
             f"ps_server.serve({port}, host='127.0.0.1', "
             f"use_native=False)"],
            # the shard must advertise its local endpoints even when
            # the operator pinned BYTEPS_TRANSPORT=tcp for the client
            # side — the unix/shm legs connect to them explicitly
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "BYTEPS_TRANSPORT": "auto"})
        _wait_port(port)
        addr = f"127.0.0.1:{port}"
        # one frame per op: wire cost, not partition pipelining
        set_config(dataclasses.replace(saved_cfg,
                                       partition_bytes=mb * 1024 * 1024))
        import numpy as _np

        x = _np.ones(mb * 1024 * 1024 // 4, _np.float32)
        # serial stores (window=0): the caller thread drives the wire
        # directly, so the A/B measures transport cost, not the
        # pipelined client's thread-handoff jitter (2 vCPUs)
        stores = {t: ps_server.RemoteStore([addr], transport=t,
                                           wire_window=0)
                  for t in transports}
        for t, st in stores.items():
            st.init_tensor(f"ab_{t}", x)
            st.pull(f"ab_{t}")           # warm the path (connect etc.)
            st.push_pull(f"ab_{t}", x)
        times = {("pull", t): [] for t in transports}
        times.update({("push_pull", t): [] for t in transports})
        for _ in range(reps):
            for t, st in stores.items():
                t0 = time.perf_counter()
                st.pull(f"ab_{t}")
                times[("pull", t)].append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                st.push_pull(f"ab_{t}", x)
                times[("push_pull", t)].append(time.perf_counter() - t0)
        for st in stores.values():
            st.close()
        for op in ("pull", "push_pull"):
            tcp_min = min(times[(op, "tcp")])
            for t in transports:
                best = min(times[(op, t)])
                moved = mb * (2 if op == "push_pull" else 1)
                row = {
                    "metric": f"wire_transport_{op}_{t}_{mb}mb_ms",
                    "value": round(best * 1e3, 2),
                    "unit": f"ms/{op}",
                    "transport": t,
                    "tensor_mb": mb,
                    "mb_per_s": round(moved / best, 1),
                    "vs_tcp_min": round(tcp_min / best, 3),
                    "wire": "same-host, single frame",
                    "tool": "bench_comm.py",
                }
                rows.append(row)
                emit_row(row, _PLATFORM)
    finally:
        set_config(saved_cfg)
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5)
    if archive and rows:
        _archive_rows(rows)
    return rows


def hierarchical_ab(workers=4, mb=2, delay_ms=5.0, steps=3, shards=2,
                    reps=3, archive=True):
    """Hierarchical on-vs-off A/B on the emulated local mesh
    (docs/wire.md "Hierarchical reduction"): ``workers`` colocated
    workers — a ``dp`` submesh over the virtual CPU devices — exchange
    an ``mb``-MiB gradient with real PS shard processes behind an
    emulated ``delay_ms``/hop wire.

      * OFF: every worker push_pulls its full dense gradient (the
        pre-hierarchical eager PS path) — mutation wire bytes/step =
        ``workers x tensor``;
      * ON: a jitted ``psum_scatter`` reduces the workers' gradients
        on-mesh first and only per-rank ``name@s{r}`` slices ride the
        wire — ``1 x tensor``/step.

    Wire bytes come from the ``compression.wire_bytes_sent`` counters
    (client-side mutation payload accounting — transport-independent);
    wall time is min-of-reps over interleaved legs.  Acceptance
    (ISSUE 8): byte reduction >= 0.9 x ``workers``."""
    import dataclasses
    import subprocess
    import sys as _sys

    from byteps_tpu.common.config import get_config, set_config
    from byteps_tpu.compression import (get_compression_stats,
                                        reset_compression_stats)
    from byteps_tpu.engine import hierarchical as hier
    from byteps_tpu.engine import ps_server
    from byteps_tpu.resilience import FaultInjectingProxy

    mesh = Mesh(np.array(jax.devices()[:workers]), axis_names=("dp",))
    ports = [_free_port() for _ in range(shards)]
    procs, proxies, rows = [], [], []
    saved_cfg = get_config()
    try:
        for p in ports:
            procs.append(subprocess.Popen(
                [_sys.executable, "-c",
                 f"from byteps_tpu.engine import ps_server; "
                 f"ps_server.serve({p}, host='127.0.0.1', "
                 f"use_native=False)"],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}))
        for p in ports:
            _wait_port(p)
        set_config(dataclasses.replace(saved_cfg, hierarchical=False))
        proxies = [FaultInjectingProxy(f"127.0.0.1:{p}", seed=i)
                   for i, p in enumerate(ports)]
        for px in proxies:
            px.set_rates(delay=delay_ms / 1e3)
        addrs = [px.addr for px in proxies]
        elems = mb * 1024 * 1024 // 4
        grads = np.stack([np.full(elems, 0.01 * (w + 1), np.float32)
                          for w in range(workers)])
        # NB: the legs close over ``stats``, bound below after
        # reset_compression_stats()

        def leg_off(store, rep):
            name = f"hier_off_{rep}"
            store.init_tensor(name, np.zeros(elems, np.float32))
            b0 = stats.summary()["wire_bytes_sent"]
            t0 = time.perf_counter()
            for _ in range(steps):
                for w in range(workers):  # every worker: full tensor
                    store.push_pull(name, grads[w])
            dt = (time.perf_counter() - t0) / steps
            return stats.summary()["wire_bytes_sent"] - b0, dt

        def leg_on(store, rep):
            name = f"hier_on_{rep}"
            # warm the scatter/gather traces before the timed window
            hier.hierarchical_push_pull(store, name, grads, mesh,
                                        min_bytes=1)
            b0 = stats.summary()["wire_bytes_sent"]
            t0 = time.perf_counter()
            for _ in range(steps):
                hier.hierarchical_push_pull(store, name, grads, mesh,
                                            min_bytes=1)
            dt = (time.perf_counter() - t0) / steps
            return stats.summary()["wire_bytes_sent"] - b0, dt

        reset_compression_stats()
        stats = get_compression_stats()
        store = ps_server.RemoteStore(addrs, transport="tcp")
        off_b = on_b = 0
        off_t, on_t = [], []
        for rep in range(reps):  # interleaved: ambient load hits both
            b, t = leg_off(store, rep)
            off_b = b  # bytes are deterministic per leg; keep the last
            off_t.append(t)
            b, t = leg_on(store, rep)
            on_b = b
            on_t.append(t)
        store.close()

        per_step_off = off_b / steps
        per_step_on = on_b / steps
        row = {
            "metric": "hierarchical_wire_bytes_per_step",
            "value": round(per_step_on / 1e6, 3),
            "unit": "MB/step (mutation payloads, hierarchical on)",
            "off_mb_per_step": round(per_step_off / 1e6, 3),
            "byte_reduction_x": round(per_step_off / per_step_on, 3),
            "local_size": workers,
            "ms_per_step_on": round(min(on_t) * 1e3, 2),
            "ms_per_step_off": round(min(off_t) * 1e3, 2),
            "speedup_min": round(min(off_t) / min(on_t), 3),
            "tensor_mb": mb,
            "shards": shards,
            "wire": f"emulated {delay_ms:g}ms/hop (proxy)",
            "window": get_config().wire_window,
            "tool": "bench_comm.py",
        }
        rows.append(row)
        emit_row(row, _PLATFORM)
    finally:
        set_config(saved_cfg)
        for px in proxies:
            px.close()
        for pr in procs:
            pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pr.kill()
                pr.wait(timeout=5)
    if archive and rows:
        _archive_rows(rows)
    return rows


def zero_ab(world=2, mb=2, delay_ms=2.0, steps=5, shards=2, reps=3,
            archive=True):
    """ZeRO-1 optimizer-state sharding A/B over the PS tier
    (docs/parallel.md, training/zero.py): ``world`` workers against
    real PS shard processes behind an emulated ``delay_ms``/hop wire.

      * REPLICATED: the pre-ZeRO eager loop — full client momentum,
        one full parameter-delta mutation per worker per step;
      * SHARDED: each worker keeps momentum for its owned spans only
        and pushes just its ``name@z{r}`` span delta, then pulls the
        peers' spans (pulls are reads — they never count as mutation
        bytes, matching the hierarchical accounting above).

    Both legs run the same ``sgd_momentum_update`` on the same
    gradients, so the final parameters must match bitwise (reported as
    ``bit_equal`` — a False here is a correctness bug, not noise).
    Acceptance (ISSUE 20): per-rank mutation-byte AND client
    optimizer-state reductions >= 0.9 x ``world`` (>= 1.8x at
    world=2)."""
    import dataclasses
    import subprocess
    import sys as _sys

    from byteps_tpu.common.config import get_config, set_config
    from byteps_tpu.compression import (get_compression_stats,
                                        reset_compression_stats)
    from byteps_tpu.engine import ps_server
    from byteps_tpu.resilience import FaultInjectingProxy
    from byteps_tpu.training.zero import (ReplicatedOptimizerState,
                                          ShardedOptimizerState)

    elems = mb * 1024 * 1024 // 4
    rng = np.random.RandomState(0)
    params0 = {"w": rng.randn(elems).astype(np.float32),
               "b": rng.randn(257).astype(np.float32)}
    grads = [{n: rng.randn(v.size).astype(np.float32)
              for n, v in params0.items()} for _ in range(steps)]

    ports = [_free_port() for _ in range(shards)]
    procs, proxies, rows = [], [], []
    saved_cfg = get_config()
    try:
        for p in ports:
            procs.append(subprocess.Popen(
                [_sys.executable, "-c",
                 f"from byteps_tpu.engine import ps_server; "
                 f"ps_server.serve({p}, host='127.0.0.1', "
                 f"use_native=False)"],
                env={**os.environ, "JAX_PLATFORMS": "cpu"}))
        for p in ports:
            _wait_port(p)
        set_config(dataclasses.replace(saved_cfg, hierarchical=False))
        proxies = [FaultInjectingProxy(f"127.0.0.1:{p}", seed=i)
                   for i, p in enumerate(ports)]
        for px in proxies:
            px.set_rates(delay=delay_ms / 1e3)
        addrs = [px.addr for px in proxies]

        def leg_replicated(store, rep):
            base = ReplicatedOptimizerState(
                store, {f"r{rep}_{n}": v.copy()
                        for n, v in params0.items()},
                lr=0.05, momentum=0.9)
            b0 = stats.summary()["wire_bytes_sent"]
            t0 = time.perf_counter()
            for g in grads:
                base.step({f"r{rep}_{n}": v for n, v in g.items()})
            dt = (time.perf_counter() - t0) / steps
            bytes_rank = stats.summary()["wire_bytes_sent"] - b0
            return bytes_rank, dt, base.state_bytes(), base

        def leg_sharded(store, rep):
            zs = [ShardedOptimizerState(
                store, {f"z{rep}_{n}": v.copy()
                        for n, v in params0.items()},
                world=world, rank=r, lr=0.05, momentum=0.9)
                for r in range(world)]
            b0 = stats.summary()["wire_bytes_sent"]
            t0 = time.perf_counter()
            for g in grads:
                gr = {f"z{rep}_{n}": v for n, v in g.items()}
                for z in zs:   # split-phase: all pushes land first,
                    z.push_updates(gr)
                for z in zs:   # then every rank pulls peers' spans
                    z.pull_params()
            dt = (time.perf_counter() - t0) / steps
            bytes_rank = (stats.summary()["wire_bytes_sent"] - b0) / world
            return bytes_rank, dt, zs[0].state_bytes(), zs

        reset_compression_stats()
        stats = get_compression_stats()
        store = ps_server.RemoteStore(addrs, transport="tcp")
        rep_b = shd_b = rep_state = shd_state = 0
        rep_t, shd_t, bit_equal = [], [], True
        for rep in range(reps):  # interleaved: ambient load hits both
            rep_b, t, rep_state, base = leg_replicated(store, rep)
            rep_t.append(t)
            shd_b, t, shd_state, zs = leg_sharded(store, rep)
            shd_t.append(t)
            bit_equal = bit_equal and all(
                base.params[f"r{rep}_{n}"].tobytes()
                == z.params[f"z{rep}_{n}"].tobytes()
                for n in params0 for z in zs)
        store.close()

        row = {
            "metric": "zero_mutation_bytes_per_rank_step",
            "value": round(shd_b / steps / 1e6, 3),
            "unit": "MB/rank/step (mutation payloads, ZeRO on)",
            "replicated_mb_per_step": round(rep_b / steps / 1e6, 3),
            "byte_reduction_x": round(rep_b / shd_b, 3),
            "state_bytes_reduction_x": round(rep_state / shd_state, 3),
            "bit_equal": bool(bit_equal),
            "world": world,
            "ms_per_step_sharded": round(min(shd_t) * 1e3, 2),
            "ms_per_step_replicated": round(min(rep_t) * 1e3, 2),
            "tensor_mb": mb,
            "shards": shards,
            "wire": f"emulated {delay_ms:g}ms/hop (proxy)",
            "window": get_config().wire_window,
            "tool": "bench_comm.py",
        }
        rows.append(row)
        emit_row(row, _PLATFORM)
    finally:
        set_config(saved_cfg)
        for px in proxies:
            px.close()
        for pr in procs:
            pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pr.kill()
                pr.wait(timeout=5)
    if archive and rows:
        _archive_rows(rows)
    return rows


def registered_recv_ab(kb=64, reps=2000, archive=True):
    """Registered-buffer receive A/B (the carried-over ps-lite-van
    gap): ps-lite's RDMA van registers each receive buffer once and
    reuses it for every message, while our ``_recv_exact`` allocates a
    fresh ``bytearray`` per frame.  The hardware half (verbs
    registration, NIC DMA) is gated on ``rdma_available()`` — absent
    here — so this measures the hardware-independent half on a UNIX
    socketpair: per-frame allocation vs a recycled
    :class:`~byteps_tpu.engine.transport.RegisteredBufferPool` buffer,
    at the disagg KV-ship frame size (one paged block, tens of KB —
    where the allocator, not the copy, is the marginal cost).  Rows
    archive into BENCH_COMM.json under ``wire_registered_recv_*``."""
    import socket as _socket

    from byteps_tpu.engine.transport import (RegisteredBufferPool,
                                             rdma_available)
    from byteps_tpu.engine.wire import _recv_exact

    n = kb * 1024
    payload = b"\xab" * n
    a, b = _socket.socketpair()
    pool = RegisteredBufferPool()
    rows = []
    try:
        a.setblocking(True)
        b.setblocking(True)

        def _run(recv_one):
            # warm
            for _ in range(8):
                a.sendall(payload)
                recv_one()
            t0 = time.perf_counter()
            for _ in range(reps):
                a.sendall(payload)
                recv_one()
            return (time.perf_counter() - t0) / reps

        plain = _run(lambda: _recv_exact(b, n))

        def _pooled():
            view = pool.recv_exact(b, n)
            pool.recycle(view)

        pooled = _run(_pooled)
        st = pool.stats()
        for tag, dt in (("plain", plain), ("pooled", pooled)):
            row = {
                "metric": f"wire_registered_recv_{tag}_{kb}kb_us",
                "value": round(dt * 1e6, 2),
                "unit": "us/frame",
                "frame_kb": kb,
                "mb_per_s": round(n / dt / 1e6, 1),
                "vs_plain": round(plain / dt, 3),
                "rdma_available": rdma_available(),
                "pool_hit_rate": (round(st["hits"] /
                                        max(1, st["hits"] + st["misses"]),
                                        3) if tag == "pooled" else None),
                "wire": "socketpair, single frame",
                "tool": "bench_comm.py",
            }
            rows.append(row)
            emit_row(row, _PLATFORM)
    finally:
        a.close()
        b.close()
    if archive and rows:
        _archive_rows(rows)
    return rows


def _archive_rows(rows, path="BENCH_COMM.json"):
    """Merge rows into BENCH_COMM.json by metric name (acceptance
    artifact: the pipelined-wire numbers live next to the PR-4-era
    comm matrix)."""
    archive_rows(rows, path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--eager-tensors", type=int, default=12)
    ap.add_argument("--eager-mbytes", type=int, default=8)
    ap.add_argument("--eager-iters", type=int, default=3)
    ap.add_argument("--wire-mb", type=int, default=8)
    ap.add_argument("--wire-part-kb", type=int, default=1024)
    ap.add_argument("--wire-delay-ms", type=float, default=5.0)
    ap.add_argument("--wire-reps", type=int, default=8)
    ap.add_argument("--wire-only", action="store_true",
                    help="run only the pipelined-wire A/B + the "
                         "per-transport A/B + the hierarchical A/B")
    ap.add_argument("--transports-only", action="store_true",
                    help="run only the per-transport same-host A/B")
    ap.add_argument("--hierarchical", action="store_true",
                    help="run only the hierarchical on-vs-off A/B "
                         "(docs/wire.md 'Hierarchical reduction')")
    ap.add_argument("--hier-workers", type=int, default=4,
                    help="emulated colocated worker count (= local_size)")
    ap.add_argument("--hier-mb", type=int, default=2)
    ap.add_argument("--zero", action="store_true",
                    help="run only the ZeRO-1 optimizer-state sharding "
                         "A/B (docs/parallel.md, training/zero.py)")
    ap.add_argument("--zero-world", type=int, default=2,
                    help="ownership-group size for the --zero leg")
    ap.add_argument("--zero-mb", type=int, default=2)
    # 1 MiB frames: the partition-sized regime the colocated client
    # actually sends, where per-frame transport cost dominates; 24
    # interleaved reps so min-of-reps escapes this host's throttle
    # windows (see transport_ab docstring)
    ap.add_argument("--transport-mb", type=int, default=1)
    ap.add_argument("--transport-reps", type=int, default=24)
    ap.add_argument("--no-archive", action="store_true",
                    help="do not update BENCH_COMM.json")
    args = ap.parse_args()

    if args.transports_only:
        transport_ab(mb=args.transport_mb, reps=args.transport_reps,
                     archive=not args.no_archive)
        registered_recv_ab(archive=not args.no_archive)
        return
    if args.hierarchical:
        hierarchical_ab(workers=args.hier_workers, mb=args.hier_mb,
                        delay_ms=args.wire_delay_ms,
                        archive=not args.no_archive)
        return
    if args.zero:
        zero_ab(world=args.zero_world, mb=args.zero_mb,
                archive=not args.no_archive)
        return
    pipelined_wire(mb=args.wire_mb, part_kb=args.wire_part_kb,
                   delay_ms=args.wire_delay_ms, reps=args.wire_reps,
                   archive=not args.no_archive)
    transport_ab(mb=args.transport_mb, reps=args.transport_reps,
                 archive=not args.no_archive)
    hierarchical_ab(workers=args.hier_workers, mb=args.hier_mb,
                    delay_ms=args.wire_delay_ms,
                    archive=not args.no_archive)
    zero_ab(world=args.zero_world, mb=args.zero_mb,
            archive=not args.no_archive)
    if args.wire_only:
        return

    from byteps_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(force_distributed=True)   # dcn(2) x dp(4)
    bucket_sweep(mesh, args.layers, args.dim, args.iters)
    jit_bucket_order(mesh, args.layers, args.dim, args.iters)
    delayed_vs_sync(mesh, args.layers, args.dim, args.iters)
    eager_priority_order(mesh, args.eager_tensors, args.eager_mbytes,
                         args.eager_iters)


if __name__ == "__main__":
    main()
