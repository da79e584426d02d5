#!/usr/bin/env python3
"""Compile a cell's programs for the v5e WITHOUT a chip.

    python3 benchmark/aot_check.py --workload <cell>

libtpu's compile-only topology (``v5e:2x2``) compiles for a chip that
is described, not attached: what Mosaic or the HBM allocator would
refuse on the chip is refused here, at no chip time.  For a train cell
this lowers the configuration's data-parallel step at the real sizes
on 1 or 4 described devices; for a serve cell the engine's fused decode
program and every chunk-prefill bucket.  It prints each program's
``memory_analysis()``, its Mosaic calls and (several chips) its
collectives.  Nothing runs: no result, no time, no metric comes from
here.  Run it with ``JAX_PLATFORMS=cpu`` (the sandbox's setting).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", TPU_SKIP_MDS_QUERY="1")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import device as dev  # noqa: E402
from benchmark.harness import manifest  # noqa: E402


def compile_kernels_for_the_chip() -> None:
    """The default backend here is still the CPU, where the program's
    kernels would choose interpret mode; the compile is for the chip,
    so they must lower through Mosaic."""
    import importlib

    # (``ops/__init__`` shadows some submodules with functions of the
    # same name, so the modules are reached through ``sys.modules``)
    for mod in ("flash_attention", "fused_cross_entropy",
                "paged_attention", "decode_attention"):
        importlib.import_module(f"byteps_tpu.ops.{mod}")
        sys.modules[f"byteps_tpu.ops.{mod}"].resolve_interpret = (
            lambda interpret, name=None: False)


def report(name: str, compiled, t0: float) -> None:
    text = compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({
        "program": name, "compile_s": round(time.time() - t0, 1),
        "argument_gb": m.argument_size_in_bytes / 1e9,
        "output_gb": m.output_size_in_bytes / 1e9,
        "alias_gb": m.alias_size_in_bytes / 1e9,
        "temp_gb": m.temp_size_in_bytes / 1e9,
        "live_gb_args_out_temp_minus_alias": total / 1e9,
        "mosaic_calls": sorted({n.split(".")[0]
                                for n in dev.mosaic_calls(text)}),
        "collectives": {k: len(re.findall(rf"= [^\n]*\b{k}(?:-start)?\(",
                                          text))
                        for k in ("all-reduce", "reduce-scatter",
                                  "all-gather")}}), flush=True)


def abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def check_train(cfg, mix, topo, chips):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.harness import weights
    from byteps_tpu.training.step import create_train_state

    builder = manifest.load_module("builders", cfg["builder"])
    mesh = Mesh(np.array(topo.devices[:chips]), ("dp",))
    step, shapes = builder.build_step(cfg, mix, mesh)
    state = jax.eval_shape(lambda: create_train_state(
        weights.make_tree(shapes, jax.random.PRNGKey(0), jnp.float32),
        step.tx))
    state = abstract(state, NamedSharding(mesh, P()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (mix["per_chip_batch"] * chips, mix["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P("dp")))}
    t0 = time.time()
    report(f"train_step[chips={chips}]",
           step.lower(state, batch).compile(), t0)


def check_serve(cfg, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import weights
    from benchmark.harness.serve_child import chunk_buckets
    from byteps_tpu.serving.engine import ServingEngine

    builder = manifest.load_module("builders", cfg["builder"])
    one = SingleDeviceSharding(topo.devices[0])
    model = builder.build_model(cfg)
    variables = {"params": abstract(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        weights.param_shapes(model)), one)}
    # "auto" would resolve to the gather on this (CPU) backend
    eng = ServingEngine(model, variables,
                        **dict(cfg["engine"], paged_kernel="on"))
    n, mb = eng.pool.n_slots, eng.pool.max_blocks
    print(json.dumps({"pool": eng.pool.block_stats(),
                      "attention_path": eng.attention_path}), flush=True)
    caches = abstract(eng.pool.caches, one)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    t0 = time.time()
    report("decode_fn", eng._paged_decode_fn(None).lower(
        variables, caches, sds((n,), jnp.int32), sds((n,), jnp.int32),
        sds((n,), bool), sds((n, 2), jnp.uint32), sds((n, mb), jnp.int32),
        sds((n, 1), jnp.int32), sds((n, 1), jnp.int32)).compile(), t0)
    for b in chunk_buckets(cfg["engine"]):
        t0 = time.time()
        report(f"chunk_fn[{b}]", eng._paged_chunk_fn(b).lower(
            variables, caches, sds((1, b), jnp.int32),
            sds((mb,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
            sds((2,), jnp.uint32)).compile(), t0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    man = manifest.load_manifest()
    cell = manifest.find_cell(man, args.workload)
    cfg = manifest.load_config(man, cell)
    mix = manifest.load_traffic(cell)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    compile_kernels_for_the_chip()
    if mix["runner"] == "train":
        check_train(cfg, mix, topo, int(cell["chips"]))
    else:
        check_serve(cfg, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
