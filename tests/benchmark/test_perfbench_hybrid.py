"""``harness/flops_hybrid.py`` against counts worked out by hand from the
published sizes of ``configs/nemotron3-nano-30b-l9-ep16.json``, the four
readers this configuration brought (``ssd_scan_roofline``,
``mamba.mixer_xla_ms_per_step``, ``moe_experts_roofline_2p``,
``train_step.mfu_hybrid``) and the three it joined on a hand-made trace,
the manifest's entries, and the builder's comparison of the program's
blocks with the reference's, sound and with each fault of
``benchmark/controls_hybrid.py`` planted."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import importlib.util
import types

import pytest

from benchmark.harness import (flops, flops_hybrid, flops_mixed,
                               flops_sparse, manifest, peaks, scopes,
                               xplane)

CELL = "nemotron3_nano_train_ep16share"
CFG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", "nemotron3-nano-30b-l9-ep16.json"))
BUILDER = manifest.load_module("builders", CFG["builder"])
MIX = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "traffic", "lm_b1_t8192_remat_ssd.json"))
T = 8192
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


def test_dims_carry_the_published_widths_the_pattern_and_the_share():
    d = BUILDER.dims(CFG)
    assert (d["d_model"], d["heads"], d["kv_heads"], d["d_head"]) == (
        2688, 32, 2, 128)
    assert (d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"],
            d["ssm_state"], d["ssm_conv"], d["ssm_chunk"]) == (
        64, 64, 8, 128, 4, 128)
    assert (d["layers"], d["mamba_layers"], d["expert_layers"],
            d["attn_layers"]) == (9, 4, 4, 1)
    assert d["window_layout"] == [None]      # one full attention layer
    assert (d["d_expert"], d["d_shared"], d["top_k"], d["experts"],
            d["experts_held"], d["vocab"]) == (1856, 3712, 6, 128, 8, 16384)
    assert d["held_assignments_per_token_layer"] == 0.375   # 6 * 8 / 128
    assert d["held_assignments_per_step"] is None or (
        d["held_assignments_per_step"] > 0)
    assert BUILDER.vocab_rows(CFG) == 16384
    assert BUILDER.pattern(CFG) == "MEMEM*EME"
    assert BUILDER.kind_layers(CFG) == [0, 1, 0, 1, 0, 5, 1, 0, 1]
    # the file keeps the pattern as published; its first nine are built
    assert len(CFG["hybrid_override_pattern"]) == 52
    assert {c: CFG["hybrid_override_pattern"].count(c) for c in "ME*"} == {
        "M": 23, "E": 23, "*": 6}


def dims(per_token_layer=0.375):
    return dict(BUILDER.dims(CFG), held_assignments_per_step=None,
                held_assignments_per_token_layer=per_token_layer)


@pytest.mark.parametrize("what,got,want", [
    ("a mixer's matrices: 2688 x (4096 + 6144 + 64) + 4096 x 2688",
     flops_hybrid.mixer_params(dims()), 2688 * 10304 + 4096 * 2688),
    ("the scan a token and layer: Q N G + Q P H + 4 N P H",
     flops_hybrid.scan_flops_per_token(dims()),
     131_072 + 524_288 + 2_097_152),
    ("one non-gated expert: up, down",
     flops_hybrid.expert_params(2688, 1856), 2 * 2688 * 1856),
    ("attention matrices: q, o at 32 heads, k, v at 2",
     flops_mixed.attn_params(dims()), 23.40 * M),
    ("scores a token: 2 products * 2 * 32 * 128 * T/2",
     flops_mixed.scores_flops_per_token(dims(), T, None), 67.11 * M),
    ("forward a token: mixers 4 x 80.17 + attention 46.80 + 67.11 + "
     "expert blocks 4 x (0.69 + 39.91 + 0.375 x 19.96) + head 88.08",
     flops_hybrid.forward_flops_per_token(dims(), T), 715.0 * M),
    ("train a token", flops_hybrid.train_flops_per_token(dims(), T),
     3 * 715.0 * M),
    ("parameters held without norms, convolutions and per-head leaves",
     flops_hybrid.param_count(dims(), 16384),
     4 * 38_707_200 + 4 * 100_122_624 + 23_396_352 + 88_080_384),
])
def test_counts_from_the_published_sizes(what, got, want):
    assert got == pytest.approx(want, rel=3e-4), what


def test_the_blocks_no_cell_has_run_are_most_of_the_needed_work():
    """ISSUE 33's shares at T 8192: the mixers 45 %, attention 16 %, the
    expert blocks 27 %, the head 12 %."""
    d = dims()
    total = flops_hybrid.forward_flops_per_token(d, T)
    mamba = 4 * (2 * flops_hybrid.mixer_params(d)
                 + flops_hybrid.scan_flops_per_token(d))
    assert mamba / total == pytest.approx(0.449, abs=2e-3)
    assert 2 * 2688 * 16384 / total == pytest.approx(0.123, abs=2e-3)
    # a step at the chip's peak: the floor under every measured step
    assert 3 * total * T / 197e12 == pytest.approx(0.0892, rel=2e-3)


def test_the_scan_is_bound_by_memory_not_by_the_mxu():
    f, b = flops_hybrid.ssd_scan_cost(dims(), T)
    assert f == pytest.approx(3 * 2_752_512 * T * 4)
    # a token and layer: X, B, C, dt read and Y written forward (20 736
    # bytes); those and dY read (20 736), dX, dB, dC, d-dt written (12 544)
    assert b == (20_736 + 20_736 + 12_544) * T * 4
    least, bound = peaks.roofline_seconds(f, b, peaks.peaks_for(
        "TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(2.16e-3, rel=5e-3)


def test_a_held_expert_counts_two_products():
    d = dims()
    rows = 0.375 * T * 4
    assert flops_hybrid.held_assignments_per_step(d, T) == rows
    f, b = flops_hybrid.held_experts_cost(d, T)
    assert f == pytest.approx(6 * 2 * 2688 * 1856 * rows)
    assert b == pytest.approx(
        2 * (3 * 2 * 2688 * 1856 * 8 * 4 + 5 * rows * 2688))
    # the accepted three-product count would read 1.5 x too high here
    f3, _ = flops_sparse.held_experts_cost(d, T)
    assert f3 == pytest.approx(1.5 * f)
    # the joined readers' counts fit: one head pass over the slice, one
    # full attention layer at 32 / 2 heads
    fc, _ = flops.fused_ce_cost(d, T)
    assert fc == pytest.approx(3 * 2 * T * 2688 * 16384)
    fa, ba = flops_mixed.gqa_flash_cost(d, 1, T, None)
    assert fa == pytest.approx(7 * 2 * 32 * 128 * T * T / 2)
    assert ba == (6 * 32 + 6 * 2) * 128 * T * 2


# ----------------------------------------- the readers, a hand-made trace

J = "jit(local_step)/"
FWD = J + "jvp(bps.model)/Transformer.hidden/"
BWD = (J + "transpose(jvp(bps.model))/Transformer.hidden/"
       "jvp(bps.model)/Transformer.hidden/checkpoint/")
REMAT = BWD + "rematted_computation/"
KERNEL = "%{0} = bf16[8] custom-call(bf16[8] %p)"
FUSION = "%{0} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"
FUSED_BWD = "flash_bwd_dq_flash_bwd_dkv"

# (instruction, op_name, microseconds) of one step, laid end to end: one
# mixer (block_0), one expert block (block_1), the attention block
STEP = [
    (FUSION.format("fusion.1"), FWD + "block_0/mamba/in_proj/dot_general",
     60),
    (FUSION.format("fusion.2"), FWD + "block_0/mamba/conv/mul", 12),
    (FUSION.format("fusion.3"), FWD + "block_0/mamba/ssd/cumsum", 3),
    (KERNEL.format("ssd_fwd.4"),
     FWD + "block_0/mamba/ssd/ssd_fwd/pallas_call", 22),
    (FUSION.format("fusion.5"), FWD + "block_0/mamba/norm/mul", 8),
    (FUSION.format("fusion.6"), FWD + "block_0/mamba/out_proj/dot_general",
     25),
    (FUSION.format("fusion.7"), FWD + "block_1/moe/router/dot_general", 10),
    (FUSION.format("fusion.8"), FWD + "block_1/moe/dispatch/gather", 20),
    (KERNEL.format("grouped_matmul.9"),
     FWD + "block_1/moe/experts/grouped_matmul/pallas_call", 30),
    (FUSION.format("fusion.10"), FWD + "block_1/moe/experts/square", 6),
    (FUSION.format("fusion.11"), FWD + "block_1/moe/combine/gather", 25),
    (FUSION.format("fusion.12"), FWD + "block_1/moe/shared/up/dot_general",
     40),
    (KERNEL.format("flash_fwd.13"),
     FWD + "block_5/attn/flash_fwd/pallas_call", 40),
    # the backward pass: each block again, then its gradients
    (KERNEL.format("ssd_fwd.14"),
     REMAT + "block_0/mamba/ssd/ssd_fwd/pallas_call", 24),
    (FUSION.format("fusion.15"),
     REMAT + "block_0/mamba/in_proj/dot_general", 60),
    (KERNEL.format("ssd_bwd.16"),
     BWD + "block_0/mamba/ssd/ssd_bwd/pallas_call", 61),
    (FUSION.format("fusion.17"), BWD + "block_0/mamba/ssd/transpose", 5),
    (FUSION.format("fusion.18"), BWD + "block_0/mamba/conv/mul", 20),
    (FUSION.format("fusion.19"), BWD + "block_0/mamba/in_proj/dot_general",
     120),
    (KERNEL.format("grouped_matmul_dw.20"),
     BWD + "block_1/moe/experts/grouped_matmul_dw/pallas_call", 50),
    (FUSION.format("fusion.21"), BWD + "block_1/moe/dispatch/gather", 15),
    (KERNEL.format(FUSED_BWD + ".22"),
     BWD + f"block_5/attn/{FUSED_BWD}/pallas_call", 90),
    (FUSION.format("fusion.23"), J + "bps.optimizer/add", 15),
    (KERNEL.format("fused_ce_fwd.24"),
     J + "jvp(bps.model)/bps.head/fused_ce_fwd/pallas_call", 50),
    (KERNEL.format("fused_ce_bwd_dx.25"),
     J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dx/pallas_call",
     80),
    (KERNEL.format("fused_ce_bwd_dw.26"),
     J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dw/pallas_call",
     80),
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


def context(per_token_layer=0.375):
    trace, scoped = hand_made_trace()
    notes = []
    return types.SimpleNamespace(
        trace=trace, scoped_trace=scoped, dims=dims(per_token_layer),
        peaks=peaks.peaks_for("TPU v5 lite"), chips=1, rehearse=False,
        cell={"name": CELL},
        train={"tokens_per_s": 24000.0, "traced_steps": 2,
               "per_chip_batch": 1, "seq_len": T, "table_rows": 16384},
        note=lambda **kw: notes.append(kw), notes=notes)


def reader(name):
    return manifest.reader_for(manifest.layer_readers(), name)


def test_ssd_scan_reader_takes_every_op_under_the_scope_by_pass():
    ctx = context()
    _, b = flops_hybrid.ssd_scan_cost(ctx.dims, T)
    got = reader("ssd_scan_roofline").read(ctx)
    # kernel or not, forward, recomputed and backward: 3 + 22 + 24 + 61 + 5
    assert got == pytest.approx(100 * (b / 819e9) / 115e-6)
    note = next(n for n in ctx.notes if n.get("kernel") == "ssd_scan")
    assert note["bound"] == "memory"
    assert note["ms_per_step_by_pass"] == pytest.approx(
        {"fwd": 0.025, "remat": 0.024, "bwd": 0.066})
    assert note["kernels_ms_per_step"] == pytest.approx(
        {"ssd_fwd": 0.046, "ssd_bwd": 0.061})
    assert note["kernel_calls_per_step"] == {"ssd_fwd": 2, "ssd_bwd": 1}
    assert note["device_ms_per_step"] == pytest.approx(0.115)


def test_mixer_reader_is_the_mixer_outside_its_scan_by_child():
    ctx = context()
    got = reader("mamba.mixer_xla_ms_per_step").read(ctx)
    assert got == pytest.approx((60 + 12 + 8 + 25 + 60 + 20 + 120) / 1e3)
    note = next(n for n in ctx.notes if n.get("event") == "mixer_xla")
    assert note["ms_per_step_by_child"] == pytest.approx({
        "in_proj": 0.240, "conv": 0.032, "norm": 0.008, "out_proj": 0.025})


def test_experts_2p_reader_counts_two_products_on_the_counted_rows():
    ctx = context()
    f, b = flops_hybrid.held_experts_cost(ctx.dims, T)
    got = reader("moe_experts_roofline_2p").read(ctx)
    assert got == pytest.approx(100 * max(f / 197e12, b / 819e9) / 86e-6)
    # with the steps' own count the rows follow it
    ctx = context()
    ctx.dims["held_assignments_per_step"] = 0.7 * T * 4
    more = reader("moe_experts_roofline_2p").read(ctx)
    note = next(n for n in ctx.notes if n.get("kernel") == "moe_experts_2p")
    assert note["held_assignments_per_step"] == pytest.approx(0.7 * T * 4)
    assert more > got
    # two thirds of what the three-product reader would say
    three = reader("moe_experts_roofline").read(context())
    assert got < three


def test_mfu_hybrid_reader_is_needed_flops_times_rate_over_peak():
    ctx = context()
    got = reader("train_step.mfu_hybrid").read(ctx)
    assert got == pytest.approx(100 * 3 * 715.0e6 * 24000 / 197e12,
                                rel=1e-3)
    assert ctx.notes[-1]["counted"] is False
    ctx.dims["held_assignments_per_step"] = 0.475 * T * 4
    more = reader("train_step.mfu_hybrid").read(ctx)
    assert more - got == pytest.approx(
        100 * 3 * 4 * 0.1 * 2 * 2 * 2688 * 1856 * 24000 / 197e12, rel=1e-6)
    assert ctx.notes[-1]["counted"] is True
    # the dotted name finds its own reader, not ``train_step.mfu``'s
    assert reader("train_step.mfu_hybrid").SPEC["name"] == (
        "train_step.mfu_hybrid")


def test_the_joined_readers_read_this_cells_trace():
    ctx = context()
    assert reader("moe.route_dispatch_ms_per_step").read(
        ctx) == pytest.approx((10 + 20 + 25 + 15) / 1e3)
    f, _ = flops.fused_ce_cost(ctx.dims, T)
    assert reader("fused_ce_roofline").read(ctx) == pytest.approx(
        100 * (f / 197e12) / 210e-6)
    # one full layer at 32 / 2 heads, no window kernel
    f, _ = flops_mixed.gqa_flash_cost(ctx.dims, 1, T, None)
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(
        100 * (f / 197e12) / 130e-6)
    note = next(n for n in ctx.notes if n.get("kernel") == "swa_flash")
    assert set(note["ms_per_step_by_kind"]) == {"full.fwd", "full.bwd"}


NEW = ["ssd_scan_roofline", "mamba.mixer_xla_ms_per_step",
       "moe_experts_roofline_2p", "train_step.mfu_hybrid"]


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("builder,config", [
    ("gpt2", "gpt2-medium.json"),
    ("smallthinker", "smallthinker-21b-l4-ep4.json")])
def test_the_new_readers_find_nothing_in_another_program(name, builder,
                                                         config):
    """With another configuration's dims and trace (or on the parent's
    program, which has no such scope): no value and no error."""
    cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                          config))
    mixed = importlib.util.spec_from_file_location(
        "_mixed_tests", os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "test_perfbench_mixed.py"))
    other = importlib.util.module_from_spec(mixed)
    mixed.loader.exec_module(other)
    ctx = other.context()            # a trace with no mamba scope
    ctx.dims = manifest.load_module("builders", builder).dims(cfg)
    assert reader(name).read(ctx) is None
    ctx = context()
    ctx.trace = ctx.scoped_trace = None
    ctx.peaks = None
    assert reader(name).read(ctx) is None


# ------------------------------------------------------------ the manifest


def test_the_cell_and_its_metrics_are_in_the_manifest():
    man = manifest.load_manifest()
    cell = manifest.find_cell(man, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "nemotron3-nano-30b-l9-ep16", "lm_b1_t8192_remat_ssd")
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == CFG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CFG["source"]
    assert CFG["reduced_from"] == {"num_hidden_layers": 52,
                                   "n_routed_experts": 128,
                                   "vocab_size": 131072}
    e2e, layer = manifest.cell_metrics(man, CELL)
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in layer} == {
        "train_prog.step_device_ms", "device.idle_share.train",
        "device.peak_hbm_gb.train", "train_step.post_backward_ms",
        "optimizer.update_ms_per_step", "model.blocks_xla_ms_per_step",
        "moe.route_dispatch_ms_per_step", "fused_ce_roofline",
        "swa_flash_roofline", *NEW}
    # the three-product experts roofline is not this cell's
    assert "moe_experts_roofline" not in {m["name"] for m in layer}
    # nothing another cell reported was taken from it, nothing given
    for other in ("joyai_flash_train_ep16share",
                  "smallthinker_train_ep4share", "gpt2m_train_1chip"):
        names = {m["name"] for m in manifest.cell_metrics(man, other)[1]}
        assert not set(NEW) & names
    assert "moe_experts_roofline" in {m["name"] for m in manifest.
                                      cell_metrics(
                                          man, "smallthinker_train_ep4share"
                                      )[1]}
    # new entries stand at the end of their lists
    assert man["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in man["per_layer"][-4:]] == NEW
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


def test_every_published_number_of_the_catalog_row_is_in_the_file():
    """The widths as published; only the three reduced keys differ."""
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "intermediate_size": 1856, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "max_position_embeddings": 262144,
        "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5, "rope_theta": 10000,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 0.0001, "n_group": 1, "topk_group": 1,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1}
    assert {k: CFG[k] for k in published} == published
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (9, 8, 16384)
    for key in ("assumed", "deployment", "reduced_why", "rehearsal"):
        assert CFG[key]
    assert "16 chips share each layer" in CFG["deployment"]
    # the rehearsal keeps the pattern, 8 heads a group in the mixer, 16
    # query heads a key-value head, a chunk shorter than the sequence
    r = manifest.effective(CFG, True)
    assert r["hybrid_override_pattern"] == CFG["hybrid_override_pattern"]
    assert r["mamba_num_heads"] // r["n_groups"] == 8
    assert r["num_attention_heads"] // r["num_key_value_heads"] == 16
    assert r["chunk_size"] < manifest.effective(MIX, True)["seq_len"]


def test_the_mix_is_the_8k_mix_with_its_own_kernels_and_limits():
    assert (MIX["per_chip_batch"], MIX["seq_len"], MIX["remat"]) == (
        1, 8192, True)
    assert MIX["kernels"] == ["ssd_fwd", "ssd_bwd", "flash_fwd",
                              "flash_bwd", "fused_ce_fwd", "fused_ce_bwd_dx",
                              "fused_ce_bwd_dw", "grouped_matmul"]
    for limits in (MIX["reference_limits"],
                   MIX["rehearsal"]["reference_limits"]):
        assert set(limits) == {"block_p90", "mixer_worst_token",
                               "state_gap", "router_flip_share",
                               "router_weight_gap", "expert_worst_token"}
    t8k = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", "lm_b1_t8192_remat.json"))
    same = ("runner", "kind", "per_chip_batch", "seq_len", "batch_ring",
            "remat", "attn_impl", "fused_head", "optimizer",
            "learning_rate", "partition_bytes", "warmup_steps",
            "trace_steps")
    assert {k: MIX[k] for k in same} == {k: t8k[k] for k in same}


# --------------------------------------- held to the reference, and faults


@pytest.fixture(scope="module")
def built():
    """``build_training`` at the rehearsal's tiny size on one CPU device
    (it holds the blocks to the reference before it returns), and the
    same matrices ten times larger: at the rehearsal's widths N(0, 0.02)
    leaves B, C and every score near zero, so the scan adds nothing to
    its ``D X`` skip and no fault of the groups can show; at ten times
    the seed's they are of order one, as at the published widths."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cfg = manifest.effective(CFG, True)
    mix = manifest.effective(MIX, True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step, state, batches, meta = BUILDER.build_training(cfg, mix, mesh, 7)
    keep = ("scale", "embedding", "A_log", "dt_bias", "D", "bias")
    large = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in keep or (
            path[-2].key == "conv") else 10.0 * a, state.params)
    return cfg, mix, step, state, batches, meta, large


def test_the_builder_builds_at_the_rehearsals_size(built):
    import numpy as np

    cfg, mix, step, state, batches, meta, _ = built
    assert cfg == manifest.effective(CFG, True)
    assert meta["tokens_per_step"] == 256 and len(batches) == 3
    assert int(max(b["tokens"].max() for b in batches)) < cfg["vocab_size"]
    assert [k for k in state.params if k.startswith("block_")] == [
        f"block_{i}" for i in range(9)]
    mixer = state.params["block_0"]["mamba"]
    assert set(state.params["block_0"]) == {"norm", "mamba"}
    assert mixer["in_proj"]["kernel"].shape == (64, 128 + 192 + 16)
    assert mixer["conv"]["kernel"].shape == (4, 192)
    # the leaves the builder sets as the published initialiser has them
    np.testing.assert_allclose(np.exp(np.asarray(mixer["ssd"]["A_log"])),
                               np.arange(1, 17), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mixer["ssd"]["D"]), 1.0)
    dt = np.log1p(np.exp(np.asarray(mixer["ssd"]["dt_bias"])))
    assert 0.001 <= dt.min() and dt.max() <= 0.1
    w = np.asarray(mixer["conv"]["kernel"])
    assert np.abs(w).max() <= 0.5 and w.std() == pytest.approx(
        0.5 / 3 ** 0.5, rel=0.1)
    assert not np.any(np.asarray(mixer["conv"]["bias"]))
    experts = state.params["block_1"]["moe"]
    assert set(experts["experts"]) == {"up", "down"}             # no gate
    assert experts["experts"]["up"].shape == (4, 64, 32)         # 4 of 16
    assert experts["router"]["kernel"].shape == (64, 16)
    assert experts["shared"]["up"]["kernel"].shape == (64, 64)
    # the selection bias as the balancing rule would leave it: centred,
    # of the size of a score gap, another in every layer
    bias = np.asarray(experts["router"]["bias"])
    assert bias.shape == (16,) and abs(bias.mean()) < 1e-6
    assert 0 < np.abs(bias).max() < 0.5
    assert np.any(bias != np.asarray(
        state.params["block_3"]["moe"]["router"]["bias"]))
    attn = state.params["block_5"]["attn"]
    assert attn["q"]["kernel"].shape == (64, 16, 16)
    assert attn["k"]["kernel"].shape == (64, 1, 16)
    assert np.asarray(attn["q"]["kernel"]).std() == pytest.approx(
        0.02, rel=0.05)
    tc = BUILDER.transformer_config(cfg, mix)
    assert tc.layer_kinds == ("mamba", "moe", "mamba", "moe", "mamba",
                              "attn", "moe", "mamba", "moe") and tc.remat


# which limit each planted fault has to trip at the rehearsal's size and
# ten times the seed's weights (limits of this test: sound readings are
# 3 times or more below them, each fault's 3 times or more above)
LIMITS = {"block_p90": 0.1, "mixer_worst_token": 0.1, "state_gap": 3e-4,
          "router_flip_share": 0.001, "router_weight_gap": 0.0005,
          "expert_worst_token": 0.1}
FAULTS = {
    None: set(),
    "no_softplus": {"mixer_worst_token", "block_p90"},
    "conv_late": {"mixer_worst_token", "block_p90"},
    "group_mod": {"mixer_worst_token", "block_p90"},
    "norm_first": {"mixer_worst_token", "block_p90"},
    "bf16_state": {"state_gap"},
    "no_skip": {"mixer_worst_token", "block_p90"},
    "relu_act": {"expert_worst_token", "block_p90"},
    "bf16_router": {"router_flip_share", "router_weight_gap"},
    "drop_one": {"expert_worst_token"},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_blocks_are_held_to_the_reference_and_a_fault_is_told(
        built, fault, monkeypatch):
    """``hold_to_reference`` passes the sound program and ends a run
    whose program has one of ``benchmark/controls_hybrid.py``'s faults,
    by the limits that fault is for and by no other."""
    import byteps_tpu.ops.ssd_scan  # noqa: F401
    from benchmark import controls_hybrid
    from byteps_tpu.models import transformer
    from byteps_tpu.parallel import moe

    scan = sys.modules["byteps_tpu.ops.ssd_scan"]
    cfg, mix, _, _, batches, _, large = built
    for mod, name in ((moe, "route"), (moe, "plan"), (scan, "ssd_scan"),
                      (scan, "CARRY_DTYPE"),
                      (transformer, "causal_depthwise_conv"),
                      (transformer, "gated_group_norm")):
        monkeypatch.setattr(mod, name, getattr(mod, name))  # restored after
    monkeypatch.setitem(moe.UNGATED, "relu2", moe.UNGATED["relu2"])
    if fault:
        controls_hybrid.CONTROLS[fault]()
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
        assert worst["state_gap"] < 3e-5 and worst["state_worst_head"] < 1e-4


def test_the_controls_file_names_every_fault_and_ends_in_run_main():
    from benchmark import controls, controls_hybrid

    assert set(controls_hybrid.CONTROLS) == set(FAULTS) - {None}
    assert controls_hybrid.CONTROLS["drop_one"] is controls.drop_one
    assert controls_hybrid.CONTROLS["bf16_router"] is controls.bf16_router
    assert controls_hybrid.main(["no_such_fault"]) == 2


def test_the_new_cell_rehearses_on_the_cpu():
    """The whole command at the rehearsal's tiny size: the runner's
    comparison with the reference, the step's checks, no device metric
    (~45 s here: nine blocks and four jitted comparisons)."""
    import json
    import subprocess

    r = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147484001", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=manifest.ROOT)
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
    assert set(built["parameters_a_block_by_kind"]) == {"mamba", "moe",
                                                       "attn"}
