#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that byteps_tpu starts on the chip.

One process owns every visible chip for all four legs (a chip belongs to
one process at a time; the serve frontend runs in a thread and the
clients talk TCP to it):

  1. device — pin the platform to ``tpu`` in code BEFORE any backend touch
     (a missing chip raises instead of quietly becoming ``CpuDevice``),
     print what JAX reports.
  2. train  — ``bps.init()`` + the exact builder ``examples/
     benchmark_byteps.py --model transformer --attn flash --fused-head
     --bf16`` uses, ten steps on one repeated batch over ALL visible
     chips, Mosaic custom calls asserted in the compiled step, eager
     ``push_pull``/``broadcast`` against numpy.
  3. serve  — the launcher's ``serve`` role engine
     (``build_engine_from_env``) behind the TCP frontend, eight concurrent
     ``RemoteServeClient`` requests, twice; token-identical reruns, one
     decode compile, the fused paged kernel asserted from ``OP_STATS`` and
     from the decode program's compiled text.
  4. kernel — every public Pallas entry point compiled by Mosaic
     (``interpret=False``, explicitly) at this model's shapes and compared
     with a plain float32 ``jax.numpy`` reference under
     ``default_matmul_precision("highest")``.

Any failed leg exits non-zero after printing which leg and why.  Without
a chip the script exits non-zero in seconds and runs nothing on the CPU.
``--rehearse`` (2 layers, d_model 32, interpret-mode kernels, the CPU
pinned in code) exists only so the command can be debugged without chip
time; it prints ``"platform": "cpu"`` on every line and can never print
the pass verdict.

The compile seconds and step times printed here are information for the
next PR, labelled with the device — not metrics.

Last stdout line on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import importlib.util
import json
import math
import os
import re
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    """A leg's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Size:
    """Every shape the legs use.  ``FULL`` is the 12-layer d768 model both
    hot paths support at full width; ``TINY`` is the rehearsal."""

    vocab: int
    layers: int
    heads: int
    d_model: int
    train_T: int
    train_batch: int        # per chip
    eq_batch: int           # per chip, dp-vs-one-device equality check
    serve_max_seq: int
    prompt_lo: int
    prompt_hi: int
    new_tokens: int
    kern_T: int             # flash sequence length
    kern_N: int             # fused-CE rows
    kern_S: int             # decode / paged cache length
    blocks: tuple           # paged block sizes
    window: int

    @property
    def d_head(self) -> int:
        return self.d_model // self.heads


FULL = Size(vocab=32000, layers=12, heads=12, d_model=768, train_T=1024,
            train_batch=8, eq_batch=2, serve_max_seq=2048, prompt_lo=128,
            prompt_hi=512, new_tokens=64, kern_T=1024, kern_N=8192,
            kern_S=2048, blocks=(16, 128), window=256)
TINY = Size(vocab=256, layers=2, heads=2, d_model=32, train_T=128,
            train_batch=2, eq_batch=1, serve_max_seq=128, prompt_lo=8,
            prompt_hi=32, new_tokens=8, kern_T=128, kern_N=256,
            kern_S=128, blocks=(16, 32), window=24)


class Reporter:
    """One JSON object per stdout line; every line repeats the platform
    and device_kind so no number can be read apart from its device."""

    def __init__(self):
        self.platform = "uninitialized"
        self.device_kind = "uninitialized"
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def __call__(self, leg: str, **fields) -> None:
        print(json.dumps({"leg": leg, "platform": self.platform,
                          "device_kind": self.device_kind, **fields}),
              flush=True)

    def listen(self) -> None:
        """Sum the seconds JAX spends obtaining executables (backend
        compile, or persistent-cache retrieval on a hit)."""
        from jax import monitoring

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def mosaic_calls(compiled_text: str) -> list:
    """Names of the Mosaic (``tpu_custom_call``) instructions in a
    compiled program's HLO text.  A Pallas kernel's ``name=`` becomes the
    instruction name, so this reads what was compiled, not what a
    selection function says it chose."""
    return re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text)


def has_kernel(names: list, kernel: str) -> bool:
    # differentiation wraps the name (jvp_<name>_, transpose_jvp_<name>__)
    return any(kernel in n for n in names)


# --------------------------------------------------------------- device leg


def device_leg(rep: Reporter, rehearse: bool) -> dict:
    import jax

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 2)
    else:
        jax.config.update("jax_platforms", "tpu")
    want = "cpu" if rehearse else "tpu"
    try:
        devices = jax.devices()
    except RuntimeError as e:  # the pinned platform is absent
        rep("device", ok=False,
            error=f"no {want.upper()} for this process: {e}")
        raise SystemExit(1)
    d0 = devices[0]
    check(d0.platform == want,
          f"pinned platform not honoured: jax reports {d0.platform!r}")
    rep.platform, rep.device_kind = d0.platform, d0.device_kind
    rep.listen()

    from byteps_tpu.common.compile_cache import configure_compile_cache
    from byteps_tpu.native import reducer

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    rep("device", count=len(devices),
        python=sys.version.split()[0],
        versions={p: version(p) for p in
                  ("jax", "jaxlib", "libtpu", "flax", "optax",
                   "orbax-checkpoint")},
        compile_cache_dir=configure_compile_cache(),
        compile_cache_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        host_reducer="native" if reducer.available() else "numpy",
        rehearsal=rehearse)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------- train leg


def _load_example_builder():
    """``examples/benchmark_byteps.py`` as a module: the train leg calls
    the example's own ``build_transformer``, it does not re-implement
    it."""
    path = os.path.join(ROOT, "examples", "benchmark_byteps.py")
    spec = importlib.util.spec_from_file_location("benchmark_byteps", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train_args(size: Size, batch: int) -> argparse.Namespace:
    # examples/benchmark_byteps.py --model transformer --attn flash
    # --fused-head --bf16 (its other arguments at their defaults)
    return argparse.Namespace(
        model="transformer", batch_size=batch, seq_len=size.train_T,
        vocab_size=size.vocab, num_layers=size.layers,
        num_heads=size.heads, d_model=size.d_model, bf16=True,
        attn="flash", fused_head=True, partition_bytes=4_096_000)


TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq_flash_bwd_dkv",
                 "fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw")


def train_leg(rep: Reporter, size: Size, rehearse: bool) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import byteps_tpu as bps
    from byteps_tpu.common import partition
    from byteps_tpu.common.timing import readback_barrier

    example = _load_example_builder()
    bps.init()
    mesh = bps.mesh()
    world = bps.size()
    check(world == len(jax.devices()),
          f"mesh world {world} != visible devices {len(jax.devices())}")
    step, state, batch, global_batch = example.build_transformer(
        _train_args(size, size.train_batch), mesh)

    c0 = rep.compile_s
    t0 = time.perf_counter()
    lowered = step.lower(state, batch)
    compiled_text = lowered.compile().as_text()
    aot_s = time.perf_counter() - t0
    names = mosaic_calls(compiled_text)
    if rehearse:
        check(not names, f"rehearsal compiled Mosaic calls: {names}")
    else:
        missing = [k for k in TRAIN_KERNELS if not has_kernel(names, k)]
        check(not missing,
              f"compiled train step lacks Mosaic custom calls for "
              f"{missing}; found {sorted(set(names))}")

    if world > 1:
        # the batch spans every chip, the parameters sit on all of them
        bdev = batch["tokens"].sharding.device_set
        check(len(bdev) == world,
              f"batch spans {len(bdev)} devices, mesh has {world}")
        shard_dev = {s.device for s in
                     batch["tokens"].addressable_shards}
        check(len(shard_dev) == world,
              f"batch shards sit on {len(shard_dev)} distinct devices")
        # (a leaf whose dim 0 divides lies on its dim-0 shares between
        # steps, any other replicated)
        on_shares = NamedSharding(mesh, P(mesh.axis_names[-1]))
        for leaf in jax.tree_util.tree_leaves(state.params):
            check(len(leaf.sharding.device_set) == world
                  and (leaf.sharding.is_fully_replicated
                       or leaf.sharding.is_equivalent_to(on_shares,
                                                         leaf.ndim)),
                  f"a parameter is neither replicated on all {world} chips "
                  f"nor on their dim-0 shares: {leaf.sharding}")
        # one cross-replica reduction per bucket leaves the tracer; what
        # XLA's combiner passes make of them is reported, not asserted
        n_buckets = partition.plan_buckets(
            state.params, 4_096_000).num_buckets
        lowered_rs = lowered.as_text().count("reduce_scatter")
        check(lowered_rs >= n_buckets,
              f"{n_buckets} buckets but only {lowered_rs} reduce_scatter "
              f"ops were traced")
        compiled_coll = len(re.findall(
            r"= [^\n]*\b(?:all-reduce|reduce-scatter)(?:-start)?\(",
            compiled_text))
        check(compiled_coll >= 1,
              "compiled step contains no cross-replica reduction")
        rep("train", event="collectives", world=world,
            buckets=n_buckets, traced_reduce_scatter=lowered_rs,
            compiled_cross_replica_reductions=compiled_coll)

    losses = []
    t0 = time.perf_counter()
    state, metrics = step(state, batch)
    losses.append(float(metrics["loss"]))
    first_step_s = time.perf_counter() - t0
    for _ in range(9):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    # A random-init head over RMS-normed hidden states emits logits of
    # about unit variance, so the expected first loss is ln(V) + 1/2,
    # not ln(V) (measured 10.88 on the v5e against 10.37 + 0.5).
    want0 = math.log(size.vocab) + 0.5
    check(abs(losses[0] - want0) < 0.25,
          f"first loss {losses[0]:.4f} not within 0.25 of "
          f"ln({size.vocab}) + 0.5 = {want0:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")

    # Do the two barriers agree on this runtime?  Ten steps timed each
    # way (information for common/timing.py, not a metric).
    def timed(barrier):
        nonlocal state, metrics
        barrier()  # untimed: compiles the readback's own tiny programs
        t0 = time.perf_counter()
        for _ in range(10):
            state, metrics = step(state, batch)
        barrier()
        return (time.perf_counter() - t0) / 10

    t_block = timed(lambda: jax.block_until_ready((state, metrics)))
    t_read = timed(lambda: readback_barrier(metrics, state))
    rep("train", event="steps", world=world, global_batch=global_batch,
        seq_len=size.train_T, losses=[round(x, 4) for x in losses],
        mosaic_calls=sorted({n.split(".")[0] for n in names}),
        aot_lower_compile_s=round(aot_s, 2),
        first_step_s=round(first_step_s, 2),
        compile_s=round(rep.compile_s - c0, 2),
        step_s_block_until_ready=round(t_block, 5),
        step_s_value_readback=round(t_read, 5),
        barriers_agree=bool(abs(t_block - t_read)
                            <= 0.1 * max(t_block, t_read)))

    # eager push_pull / broadcast over the same mesh, against numpy
    # (contributions stacked on a leading worker axis; with one worker
    # the API takes that worker's contribution itself)
    x = (np.arange(world * 4096, dtype=np.float32)
         .reshape(world, 4096) % 251) - 125.0
    arg = x if world > 1 else x[0]
    got = np.asarray(bps.push_pull(arg, average=False))
    check(got.shape == (4096,) and np.array_equal(got, x.sum(0)),
          "eager push_pull sum disagrees with numpy")
    got = np.asarray(bps.broadcast(arg, root_rank=world - 1))
    check(got.shape == (4096,) and np.array_equal(got, x[world - 1]),
          "eager broadcast disagrees with numpy")
    rep("train", event="eager_collectives", world=world, ok=True)
    del state, batch, step

    if world > 1:
        # dp=world vs the SAME global batch on a one-device mesh, two
        # steps.  Tolerance 5e-3 absolute on a loss near ln(vocab):
        # bf16 activations (eps 7.8e-3) averaged over thousands of
        # tokens, and the gradient mean is reduced in another order
        # (per-replica mean then cross-replica mean vs one global mean),
        # which the second step's loss sees through AdamW.
        def two_steps(m):
            st, s, b, _ = example.build_transformer(
                _train_args(size, size.eq_batch), m)
            out = []
            for _ in range(2):
                s, met = st(s, b)
                out.append(float(met["loss"]))
            return out

        one = Mesh(np.array(jax.devices()[:1]), ("dp",))
        l_dp, l_one = two_steps(mesh), two_steps(one)
        diffs = [abs(a - b) for a, b in zip(l_dp, l_one)]
        check(max(diffs) <= 5e-3,
              f"dp={world} losses {l_dp} vs one-device {l_one}: "
              f"|diff| {diffs} exceeds 5e-3")
        rep("train", event="dp_vs_one_device", world=world,
            global_batch=size.eq_batch * world, loss_dp=l_dp,
            loss_one_device=l_one, abs_diff=diffs, tolerance=5e-3)
    bps.shutdown()
    rep("train", ok=True)


# ---------------------------------------------------------------- serve leg


def serve_leg(rep: Reporter, size: Size, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.serving.frontend import (RemoteServeClient,
                                             build_engine_from_env, serve)

    env = {
        "BYTEPS_SERVE_MODEL": (
            f"vocab_size={size.vocab},num_layers={size.layers},"
            f"num_heads={size.heads},d_model={size.d_model},"
            f"d_ff={4 * size.d_model},max_seq_len={size.serve_max_seq}"),
        "BYTEPS_SERVE_PAGED": "1",
        "BYTEPS_SERVE_SLOTS": "8",
    }
    if rehearse:
        # off the chip `auto` resolves to the gather; forcing the kernel
        # (interpret mode) rehearses the path the chip run takes
        env["BYTEPS_SERVE_PAGED_KERNEL"] = "on"
    c0 = rep.compile_s
    engine = build_engine_from_env(env)
    srv, thread = serve(engine, 0, host="127.0.0.1", in_thread=True)
    try:
        addr = f"127.0.0.1:{srv.server_address[1]}"
        rng = np.random.default_rng(21)
        prompts = [rng.integers(1, size.vocab,
                                int(rng.integers(size.prompt_lo,
                                                 size.prompt_hi + 1)))
                   .astype(np.int32) for _ in range(8)]

        def one_pass():
            outs, errs = [None] * 8, []

            def run(i):
                client = RemoteServeClient(addr, timeout=900.0,
                                           transport="tcp")
                try:
                    outs[i] = np.asarray(client.generate(
                        prompts[i], size.new_tokens)).tolist()
                except Exception as e:  # surfaced below, typed
                    errs.append(f"request {i}: {type(e).__name__}: {e}")
                finally:
                    client.close()

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(8)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=1000.0)
            check(not any(t.is_alive() for t in threads),
                  "a serve request hung past 1000 s")
            check(not errs, "; ".join(errs))
            return outs, time.perf_counter() - t0

        first, t_first = one_pass()
        compile_s = rep.compile_s - c0
        second, t_second = one_pass()
        for i, (a, b) in enumerate(zip(first, second)):
            check(len(a) == size.new_tokens and len(b) == size.new_tokens,
                  f"request {i}: {len(a)}/{len(b)} tokens, wanted "
                  f"{size.new_tokens}")
            check(a == b, f"request {i}: second pass differs from first")

        client = RemoteServeClient(addr, timeout=60.0, transport="tcp")
        try:
            stats = client.stats()
        finally:
            client.close()
        check(stats["device"]["platform"] == rep.platform,
              f"OP_STATS device {stats['device']} != {rep.platform}")
        check(stats["attention_path"] == "paged_fused",
              f"engine resolved to {stats['attention_path']!r}, not the "
              f"fused paged kernel")
        check("gathered_blocks" not in json.dumps(stats),
              "serve.gathered_blocks counter present: a tick took the "
              "gather")
        cc = stats["compile_counts"]
        check(cc["decode"] == 1,
              f"compile_counts.decode == {cc['decode']} after 16 requests")
        check(stats.get("serve.requests_completed") == 16,
              f"completed {stats.get('serve.requests_completed')} of 16")

        # the decode program's compiled text (lowered with the shapes
        # _decode_tick feeds it; AFTER the compile-count check, because
        # lowering re-traces)
        used = set()
        for leaf in jax.tree_util.tree_leaves(engine.pool.caches):
            used |= set(leaf.devices())
        n = engine.pool.n_slots
        with engine._lock:
            text = engine._paged_decode_fn(None).lower(
                engine.variables, engine.pool.caches, engine._tok,
                jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool),
                engine._keys, engine.pool.tables_device(),
                jnp.zeros((n, 1), jnp.int32),
                jnp.zeros((n, 1), jnp.int32)).compile().as_text()
        names = mosaic_calls(text)
        if rehearse:
            check(not names, f"rehearsal compiled Mosaic calls: {names}")
        else:
            check(has_kernel(names, "paged_decode_attention"),
                  f"decode program lacks the paged kernel's "
                  f"tpu_custom_call; found {sorted(set(names))}")
        rep("serve", event="requests", requests=16,
            tokens_each=size.new_tokens,
            prompt_lens=[int(p.shape[0]) for p in prompts],
            attention_path=stats["attention_path"],
            stats_device=stats["device"], devices_used=len(used),
            compile_counts=cc, compile_s=round(compile_s, 2),
            first_pass_wall_s=round(t_first, 2),
            second_pass_wall_s=round(t_second, 2),
            mosaic_calls=sorted({n.split(".")[0] for n in names}))
    finally:
        srv.shutdown()
        srv.server_close()  # stops the engine's tick thread too
        thread.join(timeout=30.0)
    check(not thread.is_alive(), "serve frontend thread did not stop")
    rep("serve", ok=True)


# --------------------------------------------------------------- kernel leg
#
# Tolerances are on the normalized max error  max|got - ref| / max|ref|
# per output, with the reference in float32 at "highest" precision.
#
#   bf16 kernels, 2e-2: operands carry 8 mantissa bits (eps 3.9e-3) and
#     the probabilities / dlogits are rounded to bf16 again before the
#     second dot; the error of a sum of ~1e3 such terms stays within a
#     few eps of the largest output.
#   f32 kernels, 5e-3: Mosaic, like XLA at DEFAULT precision, feeds f32
#     operands to the MXU as one bf16 pass with f32 accumulation, so an
#     f32 pool buys storage precision, not dot precision (measured
#     2.0e-3 .. 2.8e-3 on the v5e, the bf16 kernels' own range).
#   int8 pools: the reference dequantizes the SAME s8 values, so the
#     quantization error is not in the comparison; tolerance as for the
#     compute dtype.

TOL = {"bfloat16": 2e-2, "float32": 5e-3}


def _np_to(x, dtype):
    """Host array -> device array of ``dtype``, cast on the host (an
    eager device-side cast would compile one tiny program per shape)."""
    import jax.numpy as jnp
    import numpy as np

    return jnp.asarray(np.asarray(x).astype(dtype))


def _ref_attention(q, k, v, seg):
    """Plain causal softmax attention, float32, ``[B, T, H, D]``."""
    import jax
    import jax.numpy as jnp

    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    T, D = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
    if seg is not None:
        mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _flash_case(size, D, with_seg, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.ops.flash_attention import flash_attention

    B, T = 2, size.kern_T
    H = max(1, size.d_model // D)
    rng = np.random.default_rng(D + with_seg)
    q, k, v, do = (_np_to(rng.standard_normal((B, T, H, D)) * 0.5,
                          jnp.bfloat16) for _ in range(4))
    args = [q, k, v, do]
    if with_seg:
        # three packed documents whose borders miss every block border
        cuts = np.array([T // 3 + 5, 2 * T // 3 - 7])
        seg = np.searchsorted(cuts, np.arange(T), side="right")
        args.append(jnp.asarray(np.tile(seg, (B, 1)), jnp.int32))

    def run(attend):
        def fn(q, k, v, do, seg=None):
            o, vjp = jax.vjp(lambda a, b, c: attend(a, b, c, seg), q, k, v)
            return (o,) + vjp(do.astype(o.dtype))
        return fn

    kern = run(lambda a, b, c, seg: flash_attention(
        a, b, c, True, None, None, None, interpret, seg))
    return kern, run(_ref_attention), args, TOL["bfloat16"]


def _fused_ce_case(size, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.ops.fused_cross_entropy import (
        fused_linear_cross_entropy)

    N, H, V = size.kern_N, size.d_model, size.vocab
    rng = np.random.default_rng(3)
    x = _np_to(rng.standard_normal((N, H)), jnp.bfloat16)
    w = _np_to(rng.standard_normal((H, V)) * H ** -0.5, jnp.bfloat16)
    t = rng.integers(0, V, N)
    t[::97] = -100  # the ignore-index rows ride the same kernel
    dl = _np_to(rng.uniform(0.5, 1.5, N), jnp.float32)
    args = [x, w, jnp.asarray(t, jnp.int32), dl]

    def kern(x, w, t, dl):
        loss, vjp = jax.vjp(
            lambda a, b: fused_linear_cross_entropy(
                a, b, t, None, None, interpret), x, w)
        return (loss,) + vjp(dl)

    def ref(x, w, t, dl):
        def f(a, b):
            logits = a.astype(jnp.float32) @ b.astype(jnp.float32)
            valid = (t >= 0) & (t < V)
            tl = jnp.take_along_axis(
                logits, jnp.where(valid, t, 0)[:, None], axis=1)[:, 0]
            return jnp.where(
                valid, jax.nn.logsumexp(logits, axis=-1) - tl, 0.0)
        loss, vjp = jax.vjp(f, x, w)
        return (loss,) + vjp(dl)

    return kern, ref, args, TOL["bfloat16"]


def _quantized(x, kv):
    """``[..., S, KV*D]`` float -> (s8 values, f32 scales ``[..., S,
    KV]``) with the model's own per-(position, head) quantizer."""
    import jax

    from byteps_tpu.models.transformer import _quantize_kv

    def quantize(x):
        lead = x.shape[:-1]
        q8, scale = _quantize_kv(
            x.reshape(lead + (kv, x.shape[-1] // kv)))
        return q8.reshape(x.shape), scale

    return jax.jit(quantize)(x)


def _decode_case(size, quant, interpret):
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models.transformer import _cached_attention
    from byteps_tpu.ops.decode_attention import decode_attention

    B, S, H, D = 8, size.kern_S, size.heads, size.d_head
    rng = np.random.default_rng(5 + quant)
    q = _np_to(rng.standard_normal((B, 1, H, D)), jnp.bfloat16)
    ck, cv = (_np_to(rng.standard_normal((B, S, H * D)), jnp.bfloat16)
              for _ in range(2))
    pos = jnp.int32(3 * S // 4 + 3)
    if quant:
        (ck, ks), (cv, vs) = _quantized(ck, H), _quantized(cv, H)
        args = [q, ck, cv, pos, ks, vs]
    else:
        args = [q, ck, cv, pos]

    def kern(q, ck, cv, pos, ks=None, vs=None):
        return (decode_attention(q, ck, cv, pos, k_scale=ks, v_scale=vs,
                                 interpret=interpret),)

    def ref(q, ck, cv, pos, ks=None, vs=None):
        def dense(c, s):
            c = c.astype(jnp.float32).reshape(B, S, H, D)
            return c if s is None else c * s[..., None]
        return (_cached_attention(q.astype(jnp.float32), dense(ck, ks),
                                  dense(cv, vs), pos),)

    return kern, ref, args, TOL["bfloat16"]


def _paged_case(size, tq, block, pool, window, interpret, tp=1):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.models.transformer import _cached_attention
    from byteps_tpu.ops.paged_attention import (
        paged_decode_attention, paged_decode_attention_sharded)

    B, S, H, D = 8, size.kern_S, size.heads, size.d_head
    nb = S // block
    n_phys = B * nb + 1  # block 0 is the null block
    cdt = jnp.bfloat16 if pool == "bfloat16" else jnp.float32
    rng = np.random.default_rng(7 * tq + block)
    q = _np_to(rng.standard_normal((B, tq, H, D)), cdt)
    ck, cv = (_np_to(rng.standard_normal((n_phys, block, H * D)), cdt)
              for _ in range(2))
    # cursors at the edges that matter: 0, either side of a block
    # border, mid-cache, and the last position the row can hold
    pos = np.array([0, block - 1, block, block + 1, S // 4 + 3,
                    S // 2 - 1, S - block - tq, S - tq], np.int32)
    # physical blocks scattered over the pool, distinct across slots;
    # entries past a slot's cursor point at the null block
    perm = rng.permutation(np.arange(1, n_phys)).reshape(B, nb)
    need = -(-(pos + tq) // block)
    table = np.where(np.arange(nb)[None, :] < need[:, None], perm, 0)
    args = [q, ck, cv, jnp.asarray(table, jnp.int32), jnp.asarray(pos)]
    if pool == "int8":
        (ck, ks), (cv, vs) = _quantized(ck, H), _quantized(cv, H)
        args = [q, ck, cv, args[3], args[4], ks, vs]

    def shard(x):  # [n, bs, KV*X] -> [tp, n, bs, (KV/tp)*X]
        return x.reshape(x.shape[:2] + (tp, -1)).transpose(2, 0, 1, 3)

    def kern(q, ck, cv, table, pos, ks=None, vs=None):
        if tp == 1:
            return (paged_decode_attention(
                q, ck, cv, table, pos, k_scale=ks, v_scale=vs,
                window=window, interpret=interpret),)
        return (paged_decode_attention_sharded(
            q, shard(ck), shard(cv), table, pos,
            k_scale=None if ks is None else shard(ks),
            v_scale=None if vs is None else shard(vs),
            window=window, interpret=interpret),)

    def ref(q, ck, cv, table, pos, ks=None, vs=None):
        def rows(c, s):  # gather each slot's dense row through its table
            c = c.astype(jnp.float32).reshape(n_phys, block, H, D)
            if s is not None:
                c = c * s[..., None]
            return c[table].reshape(B, S, H, D)
        one = jax.vmap(lambda qq, kk, vv, pp: _cached_attention(
            qq[None], kk[None], vv[None], pp, window=window)[0])
        return (one(q.astype(jnp.float32), rows(ck, ks), rows(cv, vs),
                    pos),)

    return kern, ref, args, TOL["float32" if pool == "float32"
                                else "bfloat16"]


def kernel_variants(size: Size, interpret: bool):
    """``(name, must, kernel_name, build)`` for every public Pallas entry
    point.  ``must`` marks what the train and serve legs select by
    default: those have to compile and match; the rest have to be
    attempted, and may only fail as a typed ``KernelRefusedError``."""
    out = []
    for D in (size.d_head, 2 * size.d_head):
        for seg in (False, True):
            out.append((f"flash_fwd_bwd[d_head={D},segment_ids={seg}]",
                        D == size.d_head and not seg, "flash_fwd",
                        lambda D=D, seg=seg: _flash_case(
                            size, D, seg, interpret)))
    out.append(("fused_linear_cross_entropy_fwd_bwd", True,
                "fused_ce_fwd", lambda: _fused_ce_case(size, interpret)))
    for quant in (False, True):
        out.append((f"decode_attention[{'int8' if quant else 'bf16'},"
                    f"flat]", False, "flat_decode_attention",
                    lambda quant=quant: _decode_case(
                        size, quant, interpret)))
    for pool in ("float32", "bfloat16", "int8"):
        for block in size.blocks:
            for tq in (1, 5):
                for window in (None, size.window):
                    must = (pool == "float32" and block == 16
                            and tq == 1 and window is None)
                    out.append((
                        f"paged_decode_attention[pool={pool},block="
                        f"{block},tq={tq},window={window}]", must,
                        "paged_decode_attention",
                        lambda a=(tq, block, pool, window): _paged_case(
                            size, *a, interpret)))
    out.append(("paged_decode_attention_sharded[tp=2,pool=float32,"
                "block=16,tq=1]", False, "paged_decode_attention",
                lambda: _paged_case(size, 1, 16, "float32", None,
                                    interpret, tp=2)))
    return out


def kernel_leg(rep: Reporter, size: Size, rehearse: bool) -> None:
    import jax
    import numpy as np

    from byteps_tpu.ops._pallas_utils import KernelRefusedError

    failed, refused = [], []
    for name, must, kname, build in kernel_variants(size, rehearse):
        c0, t0 = rep.compile_s, time.perf_counter()
        try:
            kern, ref, args, tol = build()
            compiled = jax.jit(kern).lower(*args).compile()
            names = mosaic_calls(compiled.as_text())
            if rehearse:
                check(not names, f"rehearsal compiled Mosaic: {names}")
            else:
                check(has_kernel(names, kname),
                      f"no {kname} tpu_custom_call in the compiled "
                      f"program; found {sorted(set(names))}")
            got = compiled(*args)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(ref)(*args)
            errs = []
            for g, w in zip(got, want):
                g, w = (np.asarray(a, np.float32) for a in (g, w))
                check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
                check(np.isfinite(g).all(), "non-finite kernel output")
                errs.append(float(np.max(np.abs(g - w))
                                  / max(np.max(np.abs(w)), 1e-6)))
            check(max(errs) <= tol,
                  f"normalized max error {max(errs):.3e} > {tol:.0e}")
        except KernelRefusedError as e:
            refused.append(name)
            rep("kernel", variant=name, must=must, outcome="refused",
                kernel=e.kernel, reason=e.reason[:600])
            if must:
                failed.append(name)
            continue
        except Exception as e:
            failed.append(name)
            rep("kernel", variant=name, must=must, outcome="FAILED",
                error=f"{type(e).__name__}: {str(e)[:1500]}")
            continue
        rep("kernel", variant=name, must=must, outcome="compiled_ok",
            mosaic=not rehearse, norm_max_err=[float(f"{e:.3g}")
                                               for e in errs],
            tolerance=tol, compile_s=round(rep.compile_s - c0, 2),
            wall_s=round(time.perf_counter() - t0, 2))
    check(not failed,
          f"{len(failed)} kernel variant(s) failed: {failed}")
    rep("kernel", ok=True, refused=refused)


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, interpret-mode kernels, CPU pinned "
                         "in code; debugging only, never the pass verdict")
    args = ap.parse_args()
    size = TINY if args.rehearse else FULL
    rep = Reporter()
    t_start = time.perf_counter()

    # The device leg fails the whole run on its own: with no chip,
    # nothing below may execute on whatever backend is left.
    device = device_leg(rep, args.rehearse)

    failed = []
    for name, leg in (("train", train_leg), ("serve", serve_leg),
                      ("kernel", kernel_leg)):
        try:
            leg(rep, size, args.rehearse)
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            rep(name, ok=False,
                error=f"{type(e).__name__}: {str(e)[:2000]}")
    rep("summary", legs_failed=failed,
        compile_s_total=round(rep.compile_s, 2),
        persistent_cache_hits=rep.cache_hits,
        persistent_cache_misses=rep.cache_misses,
        wall_s=round(time.perf_counter() - t_start, 1))
    if failed:
        return 1
    if args.rehearse:
        print(json.dumps({"rehearsed": True, "device": device}),
              flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
