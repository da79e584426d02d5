"""``harness/flops_sparse.py`` against counts worked out by hand from the
published sizes of ``configs/joyai-llm-flash-l5-ep16.json``, the five
readers this configuration brought (``train_step.mfu_sparse``,
``mla_flash_roofline``, ``moe_experts_roofline``,
``moe.route_dispatch_ms_per_step``, ``fused_ce_roofline_mtp``) on a
hand-made trace, and the builder's comparison of the program's blocks
with the reference's, sound and with a fault planted."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import importlib.util
import types

import pytest

from benchmark.harness import (flops_sparse, manifest, module_spans, peaks,
                               scopes, xplane)

CFG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", "joyai-llm-flash-l5-ep16.json"))
BUILDER = manifest.load_module("builders", CFG["builder"])
T = 8192
M = 1e6


def hand_made_helpers():
    """``plane`` of the scopes tests: a text-proto plane whose events
    carry their ``op_name`` where libtpu keeps it."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_perfbench_scopes.py")
    spec = importlib.util.spec_from_file_location("_scopes_tests", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dims(per_token_layer=0.5):
    return dict(BUILDER.dims(CFG),
                held_assignments_per_token_layer=per_token_layer)


# ---------------------------------------------------- counts, by hand


def test_dims_carry_the_published_widths_and_the_share():
    d = BUILDER.dims(CFG)
    assert (d["d_model"], d["heads"], d["q_rank"], d["kv_rank"]) == (
        2048, 32, 1536, 512)
    assert (d["d_nope"], d["d_rope"], d["d_v"]) == (128, 64, 128)
    assert (d["d_ff"], d["d_expert"], d["top_k"]) == (7168, 768, 8)
    assert (d["experts"], d["experts_held"], d["vocab"]) == (256, 16, 16160)
    assert (d["layers"], d["dense_layers"], d["expert_layers"],
            d["mtp_layers"]) == (5, 1, 5, 1)
    # the nominal 8 * 16 / 256
    assert d["held_assignments_per_token_layer"] == 0.5
    assert BUILDER.vocab_rows(CFG) == 16384


def test_dims_take_the_held_assignments_from_the_programs_counters():
    """What the steps counted (``training/step.py:_count_step``) is laid
    over the nominal count; before any step nothing is."""
    from byteps_tpu.observability.metrics import (get_registry,
                                                  reset_registry)
    from byteps_tpu.training.step import _count_step

    reset_registry()
    d = BUILDER.dims(CFG)
    assert d["held_assignments_per_step"] is None
    assert flops_sparse.counted(d, T) is d
    for held in (19000, 21000):
        _count_step({"moe_assignments_held": held,
                     "moe_rows_computed": held})
    assert BUILDER.counted_assignments() == {
        "steps": 2, "assignments": 40000, "rows_computed": 40000,
        "held": 20000.0}
    d = flops_sparse.counted(BUILDER.dims(CFG), T)
    assert d["held_assignments_per_token_layer"] == pytest.approx(
        20000 / (T * 5))
    assert get_registry().counter("train.steps_counted").value == 2
    reset_registry()


@pytest.mark.parametrize("what,got,want", [
    # 3.15 + 9.44 + 1.18 + 4.19 + 8.39 M
    ("mla", lambda d: flops_sparse.mla_params(d),
     2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
     + 32 * 128 * 2048),
    ("dense", lambda d: flops_sparse.swiglu_params(2048, 7168), 44.04 * M),
    ("expert", lambda d: flops_sparse.swiglu_params(2048, 768), 4.7186 * M),
    # (192 + 128) wide, 32 heads, half of 8192 positions, 2 FLOPs
    ("scores", lambda d: flops_sparse.scores_flops_per_token(d, T),
     83.886 * M),
    # 6 x (52.69 + 83.89) + 88.08 + 5 x (1.05 + 9.44 + 4.72) + 2 x 66.19
    # + 16.78 M = 1.133 G forward
    ("forward", lambda d: flops_sparse.forward_flops_per_token(d, T),
     1132.7 * M),
    ("trained", lambda d: flops_sparse.train_flops_per_token(d, T),
     3398.2 * M),
    # 681 M parameters: 10.9 GB at 16 bytes each
    ("params", lambda d: flops_sparse.param_count(d, 16384), 681.3 * M),
])
def test_counts_from_the_published_sizes(what, got, want):
    assert got(dims()) == pytest.approx(want, rel=1e-3)


def test_a_step_at_the_chips_peak():
    """27.8 TFLOP a step of 8192 tokens = 141 ms at 197 TFLOP/s."""
    per_step = flops_sparse.train_flops_per_token(dims(), T) * T
    assert per_step == pytest.approx(27.84e12, rel=1e-3)
    v5e = peaks.peaks_for("TPU v5 lite")
    assert 1e3 * per_step / v5e["bf16_flops"] == pytest.approx(141.3,
                                                               rel=1e-3)


def test_fused_ce_cost_counts_both_head_passes():
    """Two passes of the 16 160-row head on 8192 positions: 2 x 3
    products of 8192 x 2048 x 16160 (``flops.py`` counts one pass)."""
    from benchmark.harness import flops

    f, b = flops_sparse.fused_ce_cost(dims(), T)
    assert f == 2 * 3 * 2 * T * 2048 * 16160
    assert b == 2 * 3 * 2 * (T * 2048 + 2048 * 16160)
    once = flops.fused_ce_cost(dims(), T)
    assert (f, b) == (2 * once[0], 2 * once[1])
    assert flops_sparse.fused_ce_cost(dict(dims(), mtp_layers=0), T) == once


def test_flash_cost_counts_two_head_widths_over_half_the_square():
    f, b = flops_sparse.mla_flash_cost(dims(), 1, T)
    # 4 products 192 wide, 3 products 128 wide, 32 heads, T^2 / 2, 2 FLOPs
    assert f == (4 * 192 + 3 * 128) * 32 * T * T
    # forward q k v o, backward q k v o do dq dk dv: 6 x 192 + 6 x 128
    assert b == (6 * 192 + 6 * 128) * 32 * T * 2
    # at equal widths the count is flops.py's 7 products
    equal = dict(dims(), d_nope=64, d_rope=0, d_v=64)
    from benchmark.harness import flops
    f1, b1 = flops_sparse.mla_flash_cost(equal, 8, 1024)
    f2, b2 = flops.flash_attention_cost(
        {"heads": 32, "kv_heads": 32, "d_head": 64}, 8, 1024)
    assert (f1, b1) == (f2, b2)


@pytest.mark.parametrize("per_token_layer", [0.5, 0.488, 8.0])
def test_held_experts_cost_follows_the_assignments(per_token_layer):
    d = dims(per_token_layer)
    rows = per_token_layer * T * 5
    assert flops_sparse.held_assignments_per_step(d, T) == rows
    f, b = flops_sparse.held_experts_cost(d, T)
    assert f == pytest.approx(6 * 3 * 2048 * 768 * rows)
    assert b == pytest.approx(
        2 * (3 * 3 * 2048 * 768 * 16 * 5 + 5 * rows * 2048))
    # the routed term of the forward count moves by the same assignments
    lo = flops_sparse.forward_flops_per_token(dims(0.0), T)
    hi = flops_sparse.forward_flops_per_token(d, T)
    assert hi - lo == pytest.approx(
        5 * per_token_layer * 2 * 3 * 2048 * 768)


# ----------------------------------------- the readers, a hand-made trace

J = "jit(local_step)/"
FWD = J + "jvp(bps.model)/Transformer.hidden_mtp/Transformer.hidden/"
BWD = (J + "transpose(jvp(bps.model))/Transformer.hidden_mtp/"
       "Transformer.hidden/jvp(bps.model)/Transformer.hidden_mtp/"
       "Transformer.hidden/checkpoint/")
REMAT = BWD + "rematted_computation/"
KERNEL = "%{0} = bf16[8] custom-call(bf16[8] %p)"
FUSION = "%{0} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"

# (instruction, op_name, microseconds) of one step, laid end to end
STEP = [
    (FUSION.format("fusion.1"), FWD + "block_1/moe/router/dot_general", 10),
    (FUSION.format("fusion.2"), FWD + "block_1/moe/dispatch/gather", 20),
    (KERNEL.format("grouped_matmul.3"),
     FWD + "block_1/moe/experts/grouped_matmul/pallas_call", 40),
    (FUSION.format("fusion.4"), FWD + "block_1/moe/experts/mul", 5),
    (FUSION.format("fusion.5"), FWD + "block_1/moe/combine/gather", 30),
    (FUSION.format("fusion.6"),
     FWD + "block_1/moe/shared/up/dot_general", 50),
    (KERNEL.format("flash_fwd.7"),
     FWD + "block_1/attn/flash_fwd/pallas_call", 100),
    (FUSION.format("fusion.8"), FWD + "block_0/mlp/up/dot_general", 60),
    # the backward pass: the block again, then its gradients
    (FUSION.format("fusion.9"), REMAT + "block_1/moe/router/dot_general",
     10),
    (KERNEL.format("grouped_matmul.10"),
     REMAT + "block_1/moe/experts/grouped_matmul/pallas_call", 40),
    (KERNEL.format("flash_fwd.11"),
     REMAT + "block_1/attn/flash_fwd/pallas_call", 100),
    (KERNEL.format("grouped_matmul_dw.12"),
     BWD + "block_1/moe/experts/grouped_matmul_dw/pallas_call", 80),
    (FUSION.format("fusion.13"), BWD + "block_1/moe/combine/mul", 25),
    (FUSION.format("fusion.14"), BWD + "block_1/moe/dispatch/gather", 15),
    (KERNEL.format("flash_bwd_dq.15"),
     BWD + "block_1/attn/flash_bwd_dq/pallas_call", 150),
    (KERNEL.format("flash_bwd_dkv.16"),
     BWD + "block_1/attn/flash_bwd_dkv/pallas_call", 150),
    (FUSION.format("fusion.17"), J + "bps.optimizer/add", 15),
    # the head, twice a step: the model's own pass and the module's
    (KERNEL.format("fused_ce_fwd.18"),
     J + "jvp(bps.model)/bps.head/fused_ce_fwd/pallas_call", 70),
    (KERNEL.format("fused_ce_fwd.19"),
     J + "jvp(bps.model)/bps.head/fused_ce_fwd/pallas_call", 70),
    (KERNEL.format("fused_ce_bwd_dx.20"),
     J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dx/pallas_call",
     90),
    (KERNEL.format("fused_ce_bwd_dw.21"),
     J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dw/pallas_call",
     90),
]
STEP_US = sum(us for _, _, us in STEP)


def hand_made_trace(steps=2):
    h = hand_made_helpers()
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


def context(per_token_layer=0.5):
    trace, scoped = hand_made_trace()
    notes = []
    return types.SimpleNamespace(
        trace=trace, scoped_trace=scoped, dims=dims(per_token_layer),
        peaks=peaks.peaks_for("TPU v5 lite"), chips=1, rehearse=False,
        cell={"name": "joyai_flash_train_ep16share"},
        train={"tokens_per_s": 16000.0, "traced_steps": 2,
               "per_chip_batch": 1, "seq_len": T, "table_rows": 16384},
        note=lambda **kw: notes.append(kw), notes=notes)


def test_module_spans_sort_the_expert_layers_time_by_child_and_pass():
    _, scoped = hand_made_trace()
    us = {k: 1e6 * v for k, v in module_spans.child_seconds(
        scoped, "moe").items()}
    assert us == pytest.approx({
        ("router", "fwd"): 10, ("router", "remat"): 10,
        ("dispatch", "fwd"): 20, ("dispatch", "bwd"): 15,
        ("experts", "fwd"): 45, ("experts", "remat"): 40,
        ("experts", "bwd"): 80,
        ("combine", "fwd"): 30, ("combine", "bwd"): 25,
        ("shared", "fwd"): 50})
    assert module_spans.child_seconds(scoped, "no_such_module") is None


def reader(name):
    return manifest.reader_for(manifest.layer_readers(), name)


def test_route_dispatch_reader_sums_router_dispatch_and_combine():
    ctx = context()
    got = reader("moe.route_dispatch_ms_per_step").read(ctx)
    assert got == pytest.approx((10 + 10 + 20 + 15 + 30 + 25) / 1e3)
    assert ctx.notes[0]["event"] == "module_spans"


def test_experts_roofline_reader_counts_the_held_assignments():
    ctx = context(0.488)
    f, b = flops_sparse.held_experts_cost(ctx.dims, T)
    least = max(f / 197e12, b / 819e9)
    got = reader("moe_experts_roofline").read(ctx)
    assert got == pytest.approx(100 * least / 165e-6)
    note = next(n for n in ctx.notes if n.get("kernel") == "moe_experts")
    assert note["held_assignments_per_step"] == pytest.approx(
        0.488 * T * 5)


def test_mla_flash_reader_takes_all_three_kernels_and_the_recomputed_one():
    ctx = context()
    f, _ = flops_sparse.mla_flash_cost(ctx.dims, 1, T)
    got = reader("mla_flash_roofline").read(ctx)
    assert got == pytest.approx(100 * (6 * f / 197e12) / 500e-6)
    note = next(n for n in ctx.notes if n.get("kernel") == "mla_flash")
    assert note["calls_per_step"] == 4 and note["bound"] == "compute"


def test_fused_ce_mtp_reader_counts_both_passes_over_all_the_calls():
    ctx = context()
    f, _ = flops_sparse.fused_ce_cost(ctx.dims, T)
    got = reader("fused_ce_roofline_mtp").read(ctx)
    assert got == pytest.approx(100 * (f / 197e12) / 320e-6)
    note = next(n for n in ctx.notes if n.get("kernel") == "fused_ce_mtp")
    assert note["calls_per_step"] == 4 and note["bound"] == "compute"


def test_mfu_sparse_reader_is_needed_flops_times_rate_over_peak():
    ctx = context()
    got = reader("train_step.mfu_sparse").read(ctx)
    assert got == pytest.approx(100 * 3398.2e6 * 16000 / 197e12, rel=1e-3)
    assert ctx.notes[-1]["counted"] is False
    # with the steps' own count the routed term follows it
    ctx.dims["held_assignments_per_step"] = 0.6 * T * 5
    more = reader("train_step.mfu_sparse").read(ctx)
    assert more - got == pytest.approx(
        100 * 3 * 5 * 0.1 * 2 * 3 * 2048 * 768 * 16000 / 197e12, rel=1e-6)
    assert ctx.notes[-1]["counted"] is True


@pytest.mark.parametrize("name", [
    "train_step.mfu_sparse", "mla_flash_roofline", "moe_experts_roofline",
    "moe.route_dispatch_ms_per_step", "fused_ce_roofline_mtp"])
def test_the_new_readers_find_nothing_in_a_dense_program(name):
    """On a program without the modules (the parent's, or the GPT-2
    cells'), with the dense builder's dims: no value and no error."""
    h = hand_made_helpers()
    gpt2 = manifest.load_module("builders", "gpt2")
    cfg = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "gpt2-medium.json"))
    ctx = context()
    ctx.dims = gpt2.dims(cfg)
    ctx.scoped_trace = h.two_chip_trace()
    ctx.trace = None if name != "train_step.mfu_sparse" else ctx.trace
    assert reader(name).read(ctx) is None


def test_the_cell_and_its_metrics_are_in_the_manifest():
    man = manifest.load_manifest()
    cell = manifest.find_cell(man, "joyai_flash_train_ep16share")
    assert (cell["chips"], cell["traffic"]) == (1, "lm_b1_t8192_remat")
    e2e, layer = manifest.cell_metrics(man, cell["name"])
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in layer} == {
        "train_prog.step_device_ms", "device.idle_share.train",
        "device.peak_hbm_gb.train", "train_step.post_backward_ms",
        "optimizer.update_ms_per_step", "model.blocks_xla_ms_per_step",
        "train_step.mfu_sparse", "mla_flash_roofline",
        "moe_experts_roofline", "moe.route_dispatch_ms_per_step",
        "fused_ce_roofline_mtp"}
    mix = manifest.load_traffic(cell)
    assert (mix["per_chip_batch"], mix["seq_len"], mix["remat"]) == (
        1, 8192, True)
    assert "grouped_matmul" in mix["kernels"]
    for limits in (mix["reference_limits"],
                   mix["rehearsal"]["reference_limits"]):
        assert set(limits) == {"block_gap", "router_flip_share",
                               "router_weight_gap", "expert_worst_token"}


@pytest.fixture(scope="module")
def built():
    """``build_training`` at the rehearsal's tiny size on one CPU
    device (it holds the blocks to the reference before it returns)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cfg = manifest.effective(CFG, True)
    mix = manifest.effective(manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", "lm_b1_t8192_remat.json")), True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    return (cfg, mix) + BUILDER.build_training(cfg, mix, mesh, 7)


def test_the_builder_builds_at_the_rehearsals_size(built):
    """State from the seed with the router's bias at zero, a ring of
    batches inside the vocabulary's slice; nothing left in ``cfg``."""
    import numpy as np

    cfg, mix, step, state, batches, meta = built
    assert cfg == manifest.effective(CFG, True)
    assert meta["tokens_per_step"] == 256 and len(batches) == 3
    assert int(max(b["tokens"].max() for b in batches)) < cfg["vocab_size"]
    moe = state.params["block_1"]["moe"]
    assert moe["experts"]["gate"].shape == (4, 64, 32)       # 4 of 16 held
    assert moe["router"]["kernel"].shape == (64, 16)
    assert not np.any(moe["router"]["bias"])
    assert state.params["embed"]["embedding"].shape == (512, 64)
    assert BUILDER.dims(cfg)["expert_layers"] == 3


# which limit each planted fault has to trip, at the rehearsal's size
FAULTS = {
    None: set(),
    "bf16_router": {"router_flip_share", "router_weight_gap"},
    "drop_one": {"expert_worst_token"},
    "no_shared": {"expert_worst_token"},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_blocks_are_held_to_the_reference_and_a_fault_is_told(
        built, fault, monkeypatch):
    """``hold_to_reference`` passes the sound program and ends a run
    whose program has one of ``benchmark/controls.py``'s faults, by the
    limit that fault is for and by no other."""
    from benchmark import controls
    from byteps_tpu.models import transformer
    from byteps_tpu.parallel import moe

    cfg, mix, _, state, batches, _ = built
    for mod, name in ((moe, "route"), (moe, "plan"), (transformer, "MLP")):
        monkeypatch.setattr(mod, name, getattr(mod, name))  # restored after
    if fault:
        controls.CONTROLS[fault]()
    tokens = batches[0]["tokens"][0]
    limits = mix["reference_limits"]
    worst = BUILDER.reference_gaps(cfg, mix, state.params, tokens)
    assert {n for n in worst if not worst[n] <= limits[n]} == FAULTS[fault]
    if fault:
        with pytest.raises(BUILDER.ReferenceMismatch, match=min(
                FAULTS[fault])):
            BUILDER.hold_to_reference(cfg, mix, state.params, tokens)
    else:
        BUILDER.hold_to_reference(cfg, mix, state.params, tokens)
        assert worst["router_flip_share"] == 0.0
        assert worst["router_weight_gap"] < 1e-6


def test_the_missing_loss_term_control_takes_the_weight_out():
    from benchmark import controls
    from byteps_tpu.integrations import deepseek_v3

    sound = deepseek_v3.deepseek_v3_config
    try:
        controls.no_mtp()
        tc = BUILDER.transformer_config(
            manifest.effective(CFG, True), {"attn_impl": "flash"})
    finally:
        deepseek_v3.deepseek_v3_config = sound
    assert tc.mtp_layers == 1 and tc.mtp_loss_weight == 0.0


@pytest.mark.slow
def test_the_new_cell_rehearses_on_the_cpu():
    """The whole command at the rehearsal's tiny size (slow, like the
    other cells' rehearsals in test_perfbench_manifest.py: it compiles a
    step and the reference): the runner's comparison with the reference,
    the step's checks, no device metric."""
    import json
    import subprocess

    r = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", "joyai_flash_train_ep16share", "--seed",
         "2147484001", "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=manifest.ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    assert lines[-1]["correct"] and lines[-1]["device"]["platform"] == "cpu"
    assert set(lines[-1]["metrics"]) == {"train_tokens_per_s", "setup_s"}
    held = next(x for x in lines if x.get("event") == "reference_limits")
    assert held["over"] == [] and held["router_flip_share"]["value"] == 0
    ref = next(x for x in lines if x.get("event") == "reference")
    assert ref["abs_gap"] < 1e-3
