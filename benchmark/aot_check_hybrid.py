#!/usr/bin/env python3
"""``aot_check_grouped.py`` for the cell whose blocks are one sublayer
each, some of them Mamba-2 mixers (``builders/nemotron_h.py``).

    python3 benchmark/aot_check_hybrid.py --workload <cell>

The step is lowered as ``aot_check_grouped.py`` lowers it, with the
scan's kernels (``byteps_tpu/ops/ssd_scan.py``) made to lower through
Mosaic too; the builder's comparisons with the reference have other
names and arguments here — a block once a KIND, the mixer, the scan's
carried state, the expert layer — so they are lowered from this file.
It goes when ``aot_check.py`` asks the builder for its comparisons and
its kernel modules (a ``benchmark`` PR's edit).
"""

from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import aot_check, aot_check_grouped  # noqa: E402,F401

_train = aot_check_grouped._train      # the step alone
_listed = aot_check.compile_kernels_for_the_chip


def compile_kernels_for_the_chip() -> None:
    _listed()
    mod = importlib.import_module("byteps_tpu.ops.ssd_scan")
    mod.resolve_interpret = lambda interpret, name=None: False


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
    T = mix["seq_len"]
    f32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one)
    state = f32(T, cfg["hidden_size"])
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    block_gap, mixer_gaps, state_gap, layer_gaps = builder.gap_programs(
        cfg, mix)
    kinds = builder.kinds(cfg)
    programs = [
        (f"block_gap[{kinds[layer]}]", block_gap.lower(
            shapes[f"block_{layer}"], state, state, layer))
        for layer in sorted(set(builder.kind_layers(cfg)))]
    programs += [
        ("mixer_gaps", mixer_gaps.lower(
            shapes[f"block_{kinds.index('mamba')}"]["mamba"], state, state)),
        ("state_gap", state_gap.lower(
            f32(T, H, P), f32(T, H), f32(H), f32(T, G, N), f32(T, G, N),
            f32(H), f32(H, builder.STATE_CHANNELS, N))),
        ("layer_gaps", layer_gaps.lower(
            shapes[f"block_{kinds.index('moe')}"]["moe"], state))]
    for name, lowered in programs:
        t0 = time.time()
        aot_check.report(name, lowered.compile(), t0)


aot_check.compile_kernels_for_the_chip = compile_kernels_for_the_chip
aot_check.check_train = check_train

if __name__ == "__main__":
    sys.exit(aot_check.main())
