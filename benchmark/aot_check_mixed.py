#!/usr/bin/env python3
"""``aot_check_grouped.py`` for the cell whose layers mix attention
kinds (``builders/smallthinker.py``).

    python3 benchmark/aot_check_mixed.py --workload <cell>

The step is lowered as ``aot_check_grouped.py`` lowers it (the grouped
product's kernel through Mosaic); the builder's comparisons with the
reference have other names and arguments here — a block and an
attention sublayer once a KIND of layer, the expert layer on its two
inputs — so they are lowered from this file.  It goes when
``aot_check.py`` asks the builder for its comparisons (a ``benchmark``
PR's edit).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import aot_check, aot_check_grouped  # noqa: E402,F401

_train = aot_check_grouped._train      # the step alone


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
    block_gap, attn_gaps, layer_gaps = builder.gap_programs(cfg, mix)
    programs = []
    windows = builder.window_layout(cfg)
    for layer in sorted(set(builder.kind_layers(cfg))):
        p = shapes[f"block_{layer}"]
        short = None if windows[layer] is None else state
        programs += [
            (f"block_gap[layer {layer}]",
             block_gap.lower(p, state, state, layer)),
            (f"attn_gaps[layer {layer}]",
             attn_gaps.lower(p["attn"], state, state, short, layer))]
    programs.append(("layer_gaps", layer_gaps.lower(
        shapes["block_0"]["moe"], state, state)))
    for name, lowered in programs:
        t0 = time.time()
        aot_check.report(name, lowered.compile(), t0)


aot_check.check_train = check_train

if __name__ == "__main__":
    sys.exit(aot_check.main())
