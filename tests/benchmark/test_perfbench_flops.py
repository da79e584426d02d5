"""FLOPs, bytes and peaks against hand-worked values for both
configurations."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json

import pytest

from benchmark.harness import flops, manifest, peaks


def dims(config_name):
    # by file, not through BENCHMARK.json: a configuration whose cells
    # are only planned (planned_cells.json) is held to the same numbers
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           config_name + ".json")) as f:
        cfg = json.load(f)
    return manifest.load_module("builders", cfg["builder"]).dims(cfg), cfg


def test_gpt2_medium_trains_at_2_27_gflop_per_token():
    d, _ = dims("gpt2-medium")
    # per block: qkv + o = 4 x 1024^2, mlp = 2 x 1024 x 4096
    assert flops.linear_params_per_layer(d) == 4 * 1024**2 + 8 * 1024**2
    assert flops.head_params(d) == 1024 * 50257      # published vocabulary
    linear = 24 * 12 * 1024**2 + 1024 * 50257        # 353.5 M
    attn = 3 * 2 * 1024 * 1024 * 24                  # 6 d T per layer
    assert flops.train_flops_per_token(d, 1024) == 6 * linear + attn
    assert flops.train_flops_per_token(d, 1024) == pytest.approx(
        2.27e9, rel=2e-3)
    assert flops.param_count(d) == linear            # tied: counted once


def test_mistral_l16_decode_tick_streams_7_25_gb_of_weights():
    d, cfg = dims("mistral-7b-v0.3-l16")
    per_layer = (4096 * 128 * (2 * 32 + 2 * 8)) + 3 * 4096 * 14336
    assert flops.linear_params_per_layer(d) == per_layer == 218_103_808
    assert flops.param_count(d) == 16 * per_layer + 2 * 4096 * 32768
    assert flops.param_count(d) * 2 == pytest.approx(7.52e9, rel=2e-3)
    assert flops.decode_weight_bytes(d) == 2 * (16 * per_layer
                                                + 4096 * 32768)
    assert flops.decode_weight_bytes(d) == pytest.approx(7.25e9, rel=2e-3)
    # K and V of one position over 16 layers: 64 KiB
    assert flops.kv_bytes_per_token(d) == 2 * 16 * 8 * 128 * 2 == 65536
    # 6 GiB pool -> 98304 tokens, as the configuration says
    assert (cfg["engine"]["kv_mb"] << 20) // 65536 == 98304
    # whole blocks are read: a 1-token and a 128-token context cost the
    # same at block 128, a 129-token one costs two blocks
    assert (flops.paged_decode_read_bytes(d, [1, 128, 129], 128)
            == (128 + 128 + 256) * 65536)
    assert (flops.decode_tick_bytes(d, [300] * 32, 128)
            == flops.decode_weight_bytes(d) + 32 * 384 * 65536)


def test_kernel_costs_from_shapes():
    d, _ = dims("gpt2-medium")
    f, b = flops.flash_attention_cost(d, 8, 1024)
    per_matmul = 2 * 8 * 16 * 1024 * 1024 * 64
    assert f == 7 * per_matmul / 2                   # 2 fwd + 5 bwd, causal
    assert b == 12 * (8 * 1024 * 1024) * 2           # q,k,v,o + 8 more
    f, b = flops.fused_ce_cost(d, 8192)
    assert f == 6 * 8192 * 1024 * 50257
    assert b == 3 * 8192 * 1024 * 2 + 3 * 1024 * 50257 * 2


def test_peaks_are_keyed_by_exact_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and p["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in p["source"]
    for unknown in ("TPU v5", "cpu", "TPU v5 lite "):
        with pytest.raises(KeyError):
            peaks.peaks_for(unknown)
    assert peaks.roofline_seconds(197e12, 1.0, p) == (1.0, "compute")
    assert peaks.roofline_seconds(1.0, 819e9, p) == (1.0, "memory")
