"""``harness/flops_mixed.py`` against counts worked out by hand from the
published sizes of ``configs/smallthinker-21b-l4-ep4.json``, the two
readers this configuration brought (``swa_flash_roofline``,
``train_step.mfu_mixed``) and the three it joined on a hand-made trace,
the manifest's entries, and the builder's comparison of the program's
blocks with the reference's, sound and with each fault of
``benchmark/controls_mixed.py`` planted."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import importlib.util
import types

import pytest

from benchmark.harness import (flops, flops_mixed, flops_sparse, manifest,
                               peaks, scopes, xplane)

CELL = "smallthinker_train_ep4share"
CFG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", "smallthinker-21b-l4-ep4.json"))
BUILDER = manifest.load_module("builders", CFG["builder"])
MIX = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "traffic", "lm_b1_t16384_remat.json"))
T, W = 16384, 4096
M = 1e6


def scopes_tests():
    """``plane`` of the scopes tests: a text-proto plane whose events
    carry their ``op_name`` where libtpu keeps it."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_perfbench_scopes.py")
    spec = importlib.util.spec_from_file_location("_scopes_tests", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------- counts, by hand


def test_dims_carry_the_published_widths_the_layout_and_the_share():
    d = BUILDER.dims(CFG)
    assert (d["d_model"], d["heads"], d["kv_heads"], d["d_head"]) == (
        2560, 28, 4, 128)
    assert d["window_layout"] == [None, W, W, W]
    assert (d["d_expert"], d["top_k"], d["experts"], d["experts_held"]) == (
        768, 6, 64, 16)
    assert (d["layers"], d["expert_layers"], d["vocab"]) == (4, 4, 18992)
    assert d["held_assignments_per_token_layer"] == 1.5   # 6 * 16 / 64
    assert d["held_assignments_per_step"] is None or (
        d["held_assignments_per_step"] > 0)
    assert BUILDER.vocab_rows(CFG) == 19456
    assert BUILDER.kind_layers(CFG) == [0, 1, 1, 1]
    # the file keeps both layouts as published; the first four are built
    assert len(CFG["rope_layout"]) == len(CFG["sliding_window_layout"]) == 52
    assert CFG["rope_layout"] == CFG["sliding_window_layout"] == (
        [0, 1, 1, 1] * 13)


def dims(per_token_layer=1.5):
    return dict(BUILDER.dims(CFG), held_assignments_per_step=None,
                held_assignments_per_token_layer=per_token_layer)


@pytest.mark.parametrize("what,got,want", [
    ("pairs in the causal triangle",
     flops_mixed.band_pairs(T, None), 134_217_728),
    ("pairs in a window-4096 band: W*W/2 + (T - W) * W",
     flops_mixed.band_pairs(T, W), 8_388_608 + 12_288 * 4096),
    ("a window as long as the sequence is the triangle",
     flops_mixed.band_pairs(4096, W), 4096 * 4096 / 2),
    ("attention matrices: q, o at 28 heads, k, v at 4",
     flops_mixed.attn_params(dims()), 2560 * 128 * 2 * 32),
    ("one expert: gate, up, down",
     flops_mixed.expert_params(dims()), 3 * 2560 * 768),
    ("scores a token, full layer: 2 products * 2 * 28 * 128 * T/2",
     flops_mixed.scores_flops_per_token(dims(), T, None), 117.44 * M),
    ("scores a token, window layer: the band keeps 43.75 %",
     flops_mixed.scores_flops_per_token(dims(), T, W), 51.38 * M),
    ("forward a token: scores 271.58 + projections 167.77 + router 1.31 "
     "+ held experts 70.78 + head 97.24",
     flops_mixed.forward_flops_per_token(dims(), T), 608.68 * M),
    ("train a token", flops_mixed.train_flops_per_token(dims(), T),
     3 * 608.68 * M),
    ("parameters held: 4 x 115.5 M + 2 x 19456 x 2560",
     flops_mixed.param_count(dims(), 19456), 561_643_520),
])
def test_counts_from_the_published_sizes(what, got, want):
    assert got == pytest.approx(want, rel=2e-4), what


def test_the_band_keeps_44_percent_of_a_full_layer():
    share = flops_mixed.band_pairs(T, W) / flops_mixed.band_pairs(T, None)
    assert share == pytest.approx(0.4375)
    # at half the context the band would keep 75 %: the reason for 16 384
    assert flops_mixed.band_pairs(8192, W) / flops_mixed.band_pairs(
        8192, None) == pytest.approx(0.75)


@pytest.mark.parametrize("window", [None, W])
def test_flash_cost_counts_seven_products_inside_the_band(window):
    d = dims()
    f, b = flops_mixed.gqa_flash_cost(d, 1, T, window)
    pairs = flops_mixed.band_pairs(T, window)
    assert f == pytest.approx(7 * 2 * 28 * 128 * pairs)
    # q, o, do, dq at 28 heads; k, v, dk, dv at 4: forward reads q k v and
    # writes o, backward reads q k v o do and writes dq dk dv
    assert b == (6 * 28 + 6 * 4) * 128 * T * 2
    # the same convention as the latent-attention count at equal widths
    same = {"heads": 28, "d_nope": 128, "d_rope": 0, "d_v": 128}
    if window is None:
        assert f == pytest.approx(
            flops_sparse.mla_flash_cost(same, 1, T)[0])
    # compute-bound by far at 16 384 positions
    assert f / 197e12 > 10 * b / 819e9


def test_a_step_at_the_chips_peak():
    """The needed work of one step, were the chip at its peak: the floor
    under every measured step time."""
    per_step = flops_mixed.train_flops_per_token(dims(), T) * T
    assert per_step / 197e12 == pytest.approx(0.1519, rel=1e-3)


def test_the_joined_readers_counts_fit_this_configuration():
    """``fused_ce_roofline`` counts one head pass over the slice;
    ``moe_experts_roofline`` three products a held expert (gate, up,
    down — SiLU or ReLU alike) on the held assignments."""
    d = dims()
    f, b = flops.fused_ce_cost(d, T)
    assert f == pytest.approx(3 * 2 * T * 2560 * 18992)
    assert b == 2 * (3 * T * 2560 + 3 * 2560 * 18992)
    rows = 1.5 * T * 4
    assert flops_sparse.held_assignments_per_step(d, T) == rows
    f, b = flops_sparse.held_experts_cost(d, T)
    assert f == pytest.approx(6 * 3 * 2560 * 768 * rows)
    assert b == pytest.approx(
        2 * (3 * 3 * 2560 * 768 * 16 * 4 + 5 * rows * 2560))
    counted = flops_sparse.counted(dict(
        d, held_assignments_per_step=1.4 * T * 4), T)
    assert counted["held_assignments_per_token_layer"] == pytest.approx(1.4)


# ----------------------------------------- the readers, a hand-made trace

J = "jit(local_step)/"
FWD = J + "jvp(bps.model)/Transformer.hidden/"
BWD = (J + "transpose(jvp(bps.model))/Transformer.hidden/"
       "jvp(bps.model)/Transformer.hidden/checkpoint/")
REMAT = BWD + "rematted_computation/"
KERNEL = "%{0} = bf16[8] custom-call(bf16[8] %p)"
FUSION = "%{0} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"
FUSED_BWD = "flash_bwd_dq_flash_bwd_dkv"

# (instruction, op_name, microseconds) of one step, laid end to end: the
# full layer (block_0) and ONE of the window layers
STEP = [
    (FUSION.format("fusion.1"), FWD + "block_0/moe/router/dot_general", 10),
    (KERNEL.format("flash_fwd.2"),
     FWD + "block_0/attn/flash_fwd/pallas_call", 100),
    (FUSION.format("fusion.3"), FWD + "block_0/moe/dispatch/gather", 20),
    (KERNEL.format("grouped_matmul.4"),
     FWD + "block_0/moe/experts/grouped_matmul/pallas_call", 40),
    (FUSION.format("fusion.5"), FWD + "block_0/moe/experts/mul", 5),
    (FUSION.format("fusion.6"), FWD + "block_0/moe/combine/gather", 30),
    (KERNEL.format("flash_fwd_w4096.7"),
     FWD + "block_1/attn/flash_fwd_w4096/pallas_call", 50),
    # the backward pass: the block again (its flash output was kept),
    # then its gradients
    (FUSION.format("fusion.8"), REMAT + "block_1/moe/router/dot_general",
     10),
    (KERNEL.format("grouped_matmul.9"),
     REMAT + "block_1/moe/experts/grouped_matmul/pallas_call", 40),
    (KERNEL.format("grouped_matmul_dw.10"),
     BWD + "block_1/moe/experts/grouped_matmul_dw/pallas_call", 80),
    (FUSION.format("fusion.11"), BWD + "block_1/moe/combine/mul", 25),
    (FUSION.format("fusion.12"), BWD + "block_1/moe/dispatch/gather", 15),
    (KERNEL.format(FUSED_BWD + "_w4096.13"),
     BWD + f"block_1/attn/{FUSED_BWD}_w4096/pallas_call", 120),
    (FUSION.format("fusion.14"), BWD + "block_1/attn/k/repeat", 7),
    (KERNEL.format(FUSED_BWD + ".15"),
     BWD + f"block_0/attn/{FUSED_BWD}/pallas_call", 230),
    (FUSION.format("fusion.16"), J + "bps.optimizer/add", 15),
    (KERNEL.format("fused_ce_fwd.17"),
     J + "jvp(bps.model)/bps.head/fused_ce_fwd/pallas_call", 70),
    (KERNEL.format("fused_ce_bwd_dx.18"),
     J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dx/pallas_call",
     90),
    (KERNEL.format("fused_ce_bwd_dw.19"),
     J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dw/pallas_call",
     90),
]
STEP_US = sum(us for _, _, us in STEP)


def hand_made_trace(steps=2):
    h = scopes_tests()
    ops, modules, t = [], [], 0
    for _ in range(steps):
        modules.append(("jit_local_step(1)", t, t + STEP_US))
        for name, _, us in STEP:
            ops.append((name, t, t + us))
            t += us
        t += 100
    text = h.plane(1, "/device:TPU:0", [("XLA Ops", ops),
                                        ("XLA Modules", modules)],
                   {name: op_name for name, op_name, _ in STEP})
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(text)
    return (xplane.from_profile_data(
        ProfileData.from_serialized_xspace(data)),
        scopes.from_serialized(data))


def context(per_token_layer=1.5):
    trace, scoped = hand_made_trace()
    notes = []
    return types.SimpleNamespace(
        trace=trace, scoped_trace=scoped, dims=dims(per_token_layer),
        peaks=peaks.peaks_for("TPU v5 lite"), chips=1, rehearse=False,
        cell={"name": CELL},
        train={"tokens_per_s": 30000.0, "traced_steps": 2,
               "per_chip_batch": 1, "seq_len": T, "table_rows": 19456},
        note=lambda **kw: notes.append(kw), notes=notes)


def reader(name):
    return manifest.reader_for(manifest.layer_readers(), name)


def test_swa_flash_reader_counts_each_layers_band_and_splits_the_time():
    ctx = context()
    full, _ = flops_mixed.gqa_flash_cost(ctx.dims, 1, T, None)
    band, _ = flops_mixed.gqa_flash_cost(ctx.dims, 1, T, W)
    least = (full + 3 * band) / 197e12
    got = reader("swa_flash_roofline").read(ctx)
    assert got == pytest.approx(100 * least / 500e-6)
    note = next(n for n in ctx.notes if n.get("kernel") == "swa_flash")
    assert note["bound"] == ["compute"]
    assert note["ms_per_step_by_kind"] == pytest.approx({
        "full.fwd": 0.100, "full.bwd": 0.230,
        "window.fwd": 0.050, "window.bwd": 0.120})
    assert note["calls_per_step"] == {"full.fwd": 1, "full.bwd": 1,
                                      "window.fwd": 1, "window.bwd": 1}
    assert note["least_ms_per_step_by_kind"] == pytest.approx({
        "full": 1e3 * full / 197e12, "window": 3e3 * band / 197e12})
    assert note["share_by_kind"]["full"] == pytest.approx(
        100 * (full / 197e12) / 330e-6)
    assert note["device_ms_per_step"] == pytest.approx(0.5)


def test_mfu_mixed_reader_is_needed_flops_times_rate_over_peak():
    ctx = context()
    got = reader("train_step.mfu_mixed").read(ctx)
    assert got == pytest.approx(100 * 3 * 608.68e6 * 30000 / 197e12,
                                rel=1e-3)
    assert ctx.notes[-1]["counted"] is False
    # with the steps' own count the routed term follows it
    ctx.dims["held_assignments_per_step"] = 1.6 * T * 4
    more = reader("train_step.mfu_mixed").read(ctx)
    assert more - got == pytest.approx(
        100 * 3 * 4 * 0.1 * 2 * 3 * 2560 * 768 * 30000 / 197e12, rel=1e-6)
    assert ctx.notes[-1]["counted"] is True
    # the dotted name finds its own reader, not ``train_step.mfu``'s
    assert reader("train_step.mfu_mixed").SPEC["name"] == (
        "train_step.mfu_mixed")


def test_the_joined_readers_read_this_cells_trace():
    ctx = context()
    assert reader("moe.route_dispatch_ms_per_step").read(
        ctx) == pytest.approx((10 + 20 + 30 + 10 + 25 + 15) / 1e3)
    f, b = flops_sparse.held_experts_cost(ctx.dims, T)
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 165e-6)
    f, _ = flops.fused_ce_cost(ctx.dims, T)
    assert reader("fused_ce_roofline").read(ctx) == pytest.approx(
        100 * (f / 197e12) / 250e-6)


@pytest.mark.parametrize("name", ["swa_flash_roofline",
                                  "train_step.mfu_mixed"])
@pytest.mark.parametrize("builder,config", [
    ("gpt2", "gpt2-medium.json"),
    ("joyai_flash", "joyai-llm-flash-l5-ep16.json")])
def test_the_new_readers_find_nothing_in_another_program(name, builder,
                                                         config):
    """With another configuration's dims (or on the parent's program):
    no value and no error."""
    cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                          config))
    ctx = context()
    ctx.dims = manifest.load_module("builders", builder).dims(cfg)
    assert reader(name).read(ctx) is None
    ctx = context()
    ctx.trace = None
    if name == "swa_flash_roofline":
        assert reader(name).read(ctx) is None


# ------------------------------------------------------------ the manifest


def test_the_cell_and_its_metrics_are_in_the_manifest():
    man = manifest.load_manifest()
    cell = manifest.find_cell(man, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "smallthinker-21b-l4-ep4", "lm_b1_t16384_remat")
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert CFG["reduced_from"] == {"num_hidden_layers": 52,
                                   "moe_num_primary_experts": 64,
                                   "vocab_size": 151936}
    e2e, layer = manifest.cell_metrics(man, CELL)
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in layer} == {
        "train_prog.step_device_ms", "device.idle_share.train",
        "device.peak_hbm_gb.train", "train_step.post_backward_ms",
        "optimizer.update_ms_per_step", "model.blocks_xla_ms_per_step",
        "train_step.mfu_mixed", "swa_flash_roofline",
        "moe_experts_roofline", "moe.route_dispatch_ms_per_step",
        "fused_ce_roofline"}
    # nothing another cell reported was taken from it
    joyai = {m["name"] for m in manifest.cell_metrics(
        man, "joyai_flash_train_ep16share")[1]}
    assert {"train_step.mfu_sparse", "mla_flash_roofline",
            "fused_ce_roofline_mtp"} <= joyai
    assert not {"swa_flash_roofline", "train_step.mfu_mixed"} & joyai


def test_the_mix_asks_for_the_model_s_context_and_both_backward_names():
    assert (MIX["per_chip_batch"], MIX["seq_len"], MIX["remat"]) == (
        1, 16384, True)
    assert MIX["seq_len"] == CFG["max_position_embeddings"]
    # substrings: ``flash_bwd`` holds whichever backward the tree builds,
    # ``flash_fwd`` both the full and the windowed forward
    assert MIX["kernels"] == ["flash_fwd", "flash_bwd", "fused_ce_fwd",
                              "fused_ce_bwd_dx", "fused_ce_bwd_dw",
                              "grouped_matmul"]
    for limits in (MIX["reference_limits"],
                   MIX["rehearsal"]["reference_limits"]):
        assert set(limits) == {"block_p90", "attn_worst_token",
                               "window_edge_gap", "router_flip_share",
                               "router_weight_gap", "expert_worst_token"}
    # every other field equals the 8k mix's: the agreed fallback's terms
    t8k = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", "lm_b1_t8192_remat.json"))
    same = ("runner", "kind", "per_chip_batch", "batch_ring", "remat",
            "attn_impl", "fused_head", "optimizer", "learning_rate",
            "partition_bytes", "warmup_steps", "trace_steps")
    assert {k: MIX[k] for k in same} == {k: t8k[k] for k in same}


# --------------------------------------- held to the reference, and faults


@pytest.fixture(scope="module")
def built():
    """``build_training`` at the rehearsal's tiny size on one CPU device
    (it holds the blocks to the reference before it returns), and the
    same matrices ten times larger: at the rehearsal's widths N(0, 0.02)
    leaves every score near zero, so attention is a plain mean and no
    fault of the mask or the rotation can show; at ten times the seed's
    the scores are of order one, as they are at the published widths."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cfg = manifest.effective(CFG, True)
    mix = manifest.effective(MIX, True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step, state, batches, meta = BUILDER.build_training(cfg, mix, mesh, 7)
    large = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("scale", "embedding")
        else 10.0 * a, state.params)
    return cfg, mix, step, state, batches, meta, large


def test_the_builder_builds_at_the_rehearsals_size(built):
    import numpy as np

    cfg, mix, step, state, batches, meta, _ = built
    assert cfg == manifest.effective(CFG, True)
    assert meta["tokens_per_step"] == 256 and len(batches) == 3
    assert int(max(b["tokens"].max() for b in batches)) < cfg["vocab_size"]
    block = state.params["block_1"]
    assert block["moe"]["experts"]["gate"].shape == (4, 64, 32)  # 4 of 16
    assert block["moe"]["router"]["kernel"].shape == (64, 16)
    assert set(block["moe"]["router"]) == {"kernel"}             # no bias
    assert block["attn"]["q"]["kernel"].shape == (64, 14, 16)
    assert block["attn"]["k"]["kernel"].shape == (64, 2, 16)
    table = np.asarray(state.params["embed"]["embedding"])
    assert table.shape == (512, 64)
    # the table at unit variance, the matrices at 0.02 (``assumed``)
    assert table.std() == pytest.approx(1.0, rel=0.02)
    assert np.asarray(block["attn"]["q"]["kernel"]).std() == pytest.approx(
        0.02, rel=0.05)
    assert len([k for k in state.params if k.startswith("block_")]) == 4
    tc = BUILDER.transformer_config(cfg, mix)
    assert tc.attn_window_layout == (None, 64, 64, 64) and tc.remat
    assert not np.any(np.isnan(np.asarray(
        state.params["ln_f"]["scale"])))


# which limit each planted fault has to trip at the rehearsal's size and
# ten times the seed's weights (limits of this test: sound readings are
# 3-10 times below them, each fault's 3 times or more above)
LIMITS = {"block_p90": 0.1, "attn_worst_token": 0.1,
          "window_edge_gap": 0.2, "router_flip_share": 0.001,
          "router_weight_gap": 0.0005, "expert_worst_token": 0.04}
FAULTS = {
    None: set(),
    # (a window of 64 is short enough for one key to move a token too)
    "window_short": {"window_edge_gap", "attn_worst_token", "block_p90"},
    "rope_on_nope": {"attn_worst_token", "block_p90"},
    # (window layers without their rotation weigh the edge key anew)
    "no_rope_window": {"attn_worst_token", "block_p90", "window_edge_gap"},
    "silu_gate": {"expert_worst_token"},
    "router_ffn_in": {"block_p90"},
    "bf16_router": {"router_flip_share", "router_weight_gap"},
    "drop_one": {"expert_worst_token"},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_blocks_are_held_to_the_reference_and_a_fault_is_told(
        built, fault, monkeypatch):
    """``hold_to_reference`` passes the sound program and ends a run
    whose program has one of ``benchmark/controls_mixed.py``'s faults, by
    the limits that fault is for and by no other."""
    from benchmark import controls_mixed
    from byteps_tpu.integrations import smallthinker
    from byteps_tpu.parallel import moe

    cfg, mix, _, _, batches, _, large = built
    for mod, name in ((moe, "route"), (moe, "plan"),
                      (smallthinker, "smallthinker_config")):
        monkeypatch.setattr(mod, name, getattr(mod, name))  # restored after
    if fault:
        controls_mixed.CONTROLS[fault]()
    tokens = batches[0]["tokens"][0]
    job = dict(mix, reference_limits=LIMITS)
    worst = BUILDER.reference_gaps(cfg, job, large, tokens)
    over = {n for n in LIMITS if not worst[n] <= LIMITS[n]}
    assert over == FAULTS[fault], worst
    if fault:
        with pytest.raises(BUILDER.ReferenceMismatch, match=min(
                FAULTS[fault])):
            BUILDER.hold_to_reference(cfg, job, large, tokens)
    else:
        BUILDER.hold_to_reference(cfg, job, large, tokens)
        assert worst["router_flip_share"] == 0.0
        assert worst["router_weight_gap"] < 1e-6


def test_the_controls_file_names_every_fault_and_ends_in_run_main():
    from benchmark import controls, controls_mixed

    assert set(controls_mixed.CONTROLS) == set(FAULTS) - {None}
    assert controls_mixed.CONTROLS["drop_one"] is controls.drop_one
    assert controls_mixed.main(["no_such_fault"]) == 2


def test_the_new_cell_rehearses_on_the_cpu():
    """The whole command at the rehearsal's tiny size: the runner's
    comparison with the reference, the step's checks, no device metric
    (25 s here: the step and the reference are small)."""
    import json
    import subprocess

    r = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147484001", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    assert lines[-1]["correct"] and lines[-1]["device"]["platform"] == "cpu"
    assert set(lines[-1]["metrics"]) == {"train_tokens_per_s", "setup_s"}
    held = next(x for x in lines if x.get("event") == "reference_limits")
    assert held["over"] == [] and held["router_flip_share"]["value"] == 0
    ref = next(x for x in lines if x.get("event") == "reference")
    assert ref["abs_gap"] < 1e-3
    built = next(x for x in lines if x.get("event") == "built")
    assert built["bytes_master_grad_moments"] == 16 * built["parameters"]
