#!/usr/bin/env python3
"""``aot_check.py`` for a cell whose step holds the grouped-product
kernel (``byteps_tpu/ops/grouped_matmul.py``).

    python3 benchmark/aot_check_grouped.py --workload <cell>

``aot_check.py`` makes the kernels of four modules it lists lower
through Mosaic although the default backend here is the CPU; this
wrapper adds the fifth module and runs the same check.  It goes when
``aot_check.py``'s list gains the module (a ``benchmark`` PR's edit).
After the step it lowers the builder's comparisons with the reference
(``builders/joyai_flash.py:gap_programs``) at the real sizes too: they
run on the chip in every run's set-up.
"""

from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import aot_check  # noqa: E402

_listed = aot_check.compile_kernels_for_the_chip


def compile_kernels_for_the_chip() -> None:
    _listed()
    mod = importlib.import_module("byteps_tpu.ops.grouped_matmul")
    mod.resolve_interpret = lambda interpret, name=None: False


_train = aot_check.check_train


def check_train(cfg, mix, topo, chips) -> None:
    _train(cfg, mix, topo, chips)
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import manifest, weights
    from byteps_tpu.models import Transformer

    builder = manifest.load_module("builders", cfg["builder"])
    one = SingleDeviceSharding(topo.devices[0])
    shapes = aot_check.abstract(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        weights.param_shapes(Transformer(builder.transformer_config(
            cfg, mix)), seq_len=256)), one)
    state = jax.ShapeDtypeStruct(
        (mix["seq_len"], cfg["hidden_size"]), jnp.float32, sharding=one)
    block_gap, layer_gaps = builder.gap_programs(cfg, mix)
    first = cfg["first_k_dense_replace"]
    for name, lowered in (
            ("block_gap[dense]", block_gap.lower(
                shapes["block_0"], state, state, False)),
            ("block_gap[experts]", block_gap.lower(
                shapes[f"block_{first}"], state, state, True)),
            ("layer_gaps", layer_gaps.lower(
                shapes[f"block_{first}"]["moe"], state))):
        t0 = time.time()
        aot_check.report(name, lowered.compile(), t0)


aot_check.compile_kernels_for_the_chip = compile_kernels_for_the_chip
aot_check.check_train = check_train

if __name__ == "__main__":
    sys.exit(aot_check.main())
