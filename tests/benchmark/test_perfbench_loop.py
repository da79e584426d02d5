"""``harness/flops_loop.py`` against counts worked out by hand from the
published sizes of ``configs/ouro-2.6b-l7.json``, the two readers this
configuration brought (``train_step.mfu_loop``,
``loop.readout_ms_per_step``) and the flash reader it joined on a
hand-made trace, the manifest's entries, the configuration's file and
its deployment's arithmetic, and the builder's comparison of the
program with the reference, sound and with each fault of
``benchmark/controls_loop.py`` planted."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import importlib.util
import types

import pytest

from benchmark.harness import flops, flops_loop, manifest, peaks, scopes, \
    xplane

CELL = "ouro_2p6b_train_ut4"
CFG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", "ouro-2.6b-l7.json"))
BUILDER = manifest.load_module("builders", CFG["builder"])
MIX = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "traffic", "lm_b2_t4096_remat_loop.json"))
T, B, R, L = 4096, 2, 4, 7
M = 1e6
NEW = ["train_step.mfu_loop", "loop.readout_ms_per_step"]


def sibling(name):
    """Another test file of this directory, as a module."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------- counts, by hand


def test_dims_count_the_applications_the_layers_held_and_the_passes():
    d = BUILDER.dims(CFG)
    assert (d["layers"], d["layers_held"], d["loop_steps"]) == (28, 7, 4)
    assert (d["d_model"], d["heads"], d["kv_heads"], d["d_head"], d["d_ff"],
            d["vocab"], d["mlp"], d["tied"]) == (
        2048, 16, 16, 128, 5632, 49152, "swiglu", False)
    assert BUILDER.vocab_rows(CFG) == 49152


@pytest.mark.parametrize("what,got,want", [
    ("a layer's matrices: 4 x 2048^2 + 3 x 2048 x 5632",
     flops.linear_params_per_layer(BUILDER.dims(CFG)),
     4 * 2048 ** 2 + 3 * 2048 * 5632),
    ("a layer application a token: 2 x 51.38 M + 2 x 2 x 2048 x T/2",
     flops_loop.layer_flops_per_token(BUILDER.dims(CFG), T),
     2 * 51_380_224 + 2 * 2 * 2048 * T / 2),
    ("a readout a token: the head 2 x 2048 x 49152 and the gate 2 x 2048",
     flops_loop.readout_flops_per_token(BUILDER.dims(CFG)),
     2 * 2048 * 49152 + 2 * 2048),
    ("forward a token: 28 applications of 119.54 M + 4 readouts of "
     "201.33 M", flops_loop.forward_flops_per_token(BUILDER.dims(CFG), T),
     28 * 119.537664 * M + 4 * 201.330688 * M),
    ("train a token", flops_loop.train_flops_per_token(BUILDER.dims(CFG), T),
     12.457e9),
    ("the readouts' head with cross-entropy: 4 x 3 x 2 x rows x d x V",
     flops_loop.readouts_cost(BUILDER.dims(CFG), B * T)[0],
     4 * 3 * 2.0 * B * T * 2048 * 49152),
    ("parameters: 7 layers of 51.39 M, the table and the head, the gate "
     "and the final norm", flops_loop.param_count(BUILDER.dims(CFG)),
     561_049_601),
])
def test_counts_from_the_published_sizes(what, got, want):
    assert got == pytest.approx(want, rel=3e-4), what


def test_the_readouts_are_a_fifth_of_the_needed_work():
    """The readouts' share at 7 layers: the 4 readouts ~19 % of the needed
    FLOPs (~17 % at 8); a step needs ~102 TFLOP, 0.52 s at the chip's
    peak: the floor under every measured step."""
    d = BUILDER.dims(CFG)
    total = flops_loop.forward_flops_per_token(d, T)
    assert 4 * flops_loop.readout_flops_per_token(d) / total == (
        pytest.approx(0.194, abs=2e-3))
    assert 3 * total * T * B / 197e12 == pytest.approx(0.518, rel=2e-3)
    # flops.py counts the head once: 1/R of the readouts
    dense = flops.train_flops_per_token(d, T)
    assert flops_loop.train_flops_per_token(d, T) - dense == pytest.approx(
        3 * 3 * (2 * 2048 * 49152) + 3 * 4 * 2 * 2048, rel=1e-9)


# ----------------------------------------- the readers, a hand-made trace

J = "jit(local_step)/shard_map/"
FWD = J + "jvp(bps.model)/Transformer.loop_pass/"
BWD = J + "transpose(jvp(bps.model))/Transformer.loop_pass/"
READ_FWD = FWD + "bps.head/loop_readout/"
READ_BWD = BWD + "bps.head/loop_readout/"
HEAD_FWD = J + "jvp(bps.model)/jvp(bps.head)/loop_readout/"
HEAD_BWD = J + "transpose(jvp(bps.model))/transpose(jvp(bps.head))/" \
    "loop_readout/"
KERNEL = "%{0} = bf16[8] custom-call(bf16[8] %p)"
FUSION = "%{0} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"
FUSED_BWD = "flash_bwd_dq_flash_bwd_dkv"

# (instruction, op_name, microseconds) of one step, laid end to end
STEP = [
    (FUSION.format("fusion.1"), FWD + "block_0/attn/q/dot_general", 60),
    (KERNEL.format("flash_fwd.2"),
     FWD + "block_0/attn/flash_fwd/pallas_call", 40),
    (FUSION.format("fusion.3"), FWD + "block_0/ln1_out/mul", 5),
    (FUSION.format("fusion.4"), READ_FWD + "ln_f/mul", 6),
    (FUSION.format("fusion.5"), READ_FWD + "exit_gate/dot_general", 2),
    (KERNEL.format("fused_ce_fwd.6"),
     HEAD_FWD + "while/body/fused_ce_fwd/pallas_call", 50),
    (FUSION.format("fusion.7"), HEAD_FWD + "jit(log_sigmoid)/neg", 3),
    (FUSION.format("fusion.8"), J + "jvp(bps.model)/bps.head/convert", 9),
    (KERNEL.format("fused_ce_bwd_dx.9"),
     HEAD_BWD + "while/body/fused_ce_bwd_dx/pallas_call", 80),
    (KERNEL.format("fused_ce_bwd_dw.10"),
     HEAD_BWD + "while/body/fused_ce_bwd_dw/pallas_call", 70),
    (FUSION.format("fusion.11"), READ_BWD + "ln_f/mul", 4),
    (KERNEL.format(FUSED_BWD + ".12"),
     BWD + f"block_0/attn/{FUSED_BWD}/pallas_call", 90),
    (FUSION.format("fusion.13"), BWD + "block_0/mlp/up/dot_general", 120),
    (FUSION.format("fusion.14"), J + "bps.optimizer/add", 15),
]
STEP_US = sum(us for _, _, us in STEP)
READOUT_FWD_US = 6 + 2 + 50 + 3
READOUT_BWD_US = 80 + 70 + 4


def hand_made_trace(steps=2):
    h = sibling("test_perfbench_scopes.py")
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


def context():
    trace, scoped = hand_made_trace()
    notes = []
    return types.SimpleNamespace(
        trace=trace, scoped_trace=scoped, dims=BUILDER.dims(CFG),
        peaks=peaks.peaks_for("TPU v5 lite"), chips=1, rehearse=False,
        cell={"name": CELL},
        train={"tokens_per_s": 6000.0, "traced_steps": 2,
               "per_chip_batch": B, "seq_len": T, "table_rows": 49152},
        note=lambda **kw: notes.append(kw), notes=notes)


def reader(name):
    return manifest.reader_for(manifest.layer_readers(), name)


def test_readout_reader_takes_every_op_under_the_scope_both_ways():
    """The final norm and the gate in the scan's body, the head's kernels
    and the exit distribution in the loss, forward and backward — and
    not the head's weight cast (``bps.head``, outside the readout)."""
    ctx = context()
    got = reader("loop.readout_ms_per_step").read(ctx)
    assert got == pytest.approx((READOUT_FWD_US + READOUT_BWD_US) / 1e3)
    note = next(n for n in ctx.notes if n.get("event") == "loop_readout")
    assert note["ms_per_step_by_direction"] == pytest.approx({
        "fwd": READOUT_FWD_US / 1e3, "bwd": READOUT_BWD_US / 1e3})
    f = 4 * 3 * 2.0 * B * T * 2048 * 49152
    assert note["head_ce_least_ms_per_step"] == pytest.approx(
        1e3 * f / 197e12)
    assert note["loop_steps"] == 4
    # every op of it is the head's stage: blocks_xla leaves it out
    res = scopes.analyse(ctx.scoped_trace)
    assert res["model_blocks_xla_s"] == pytest.approx((60 + 5 + 120) / 1e6)


def test_mfu_loop_reader_is_needed_flops_times_rate_over_peak():
    ctx = context()
    got = reader("train_step.mfu_loop").read(ctx)
    per_token = flops_loop.train_flops_per_token(ctx.dims, T)
    assert got == pytest.approx(100 * per_token * 6000 / 197e12)
    note = ctx.notes[-1]
    assert (note["layer_applications"], note["loop_steps"]) == (28, 4)


def test_the_flash_reader_counts_every_application():
    """``flash_roofline`` multiplies one layer's cost by ``dims["layers"]``:
    the 28 applications, not the 7 layers held."""
    ctx = context()
    f, _ = flops.flash_attention_cost(ctx.dims, B, T)
    assert reader("flash_roofline").read(ctx) == pytest.approx(
        100 * (28 * f / 197e12) / 130e-6)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("builder,config", [
    ("gpt2", "gpt2-medium.json"),
    ("smallthinker", "smallthinker-21b-l4-ep4.json"),
    ("lfm2_moe", "lfm2-8b-a1b-l5-ep4.json"),
    ("joyai_flash", "joyai-llm-flash-l5-ep16.json")])
def test_the_new_readers_find_nothing_in_another_program(name, builder,
                                                         config):
    """With another configuration's dims and trace (or on the parent's
    program, which has no such scope): no value and no error."""
    cfg = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                          config))
    ctx = sibling("test_perfbench_conv.py").context()   # no loop_readout
    ctx.dims = manifest.load_module("builders", builder).dims(cfg)
    assert reader(name).read(ctx) is None
    ctx.dims = BUILDER.dims(CFG)           # this cell's dims, no scope
    if name == "loop.readout_ms_per_step":
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
        1, "ouro-2.6b-l7", "lm_b2_t4096_remat_loop")
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CFG["source"]
    assert CFG["reduced_from"] == {"num_hidden_layers": 48}
    e2e, layer = manifest.cell_metrics(man, CELL)
    assert {m["name"] for m in e2e} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in layer} == {
        "train_prog.step_device_ms", "device.idle_share.train",
        "device.peak_hbm_gb.train", "train_step.post_backward_ms",
        "optimizer.update_ms_per_step", "model.blocks_xla_ms_per_step",
        "flash_roofline", *NEW}
    # the fused-CE reader counts B x T rows once: 1/R of the readouts
    assert "fused_ce_roofline" not in {m["name"] for m in layer}
    for other in ("joyai_flash_train_ep16share", "gpt2m_train_1chip",
                  "smallthinker_train_ep4share",
                  "nemotron3_nano_train_ep16share",
                  "lfm2_8b_a1b_train_ep4share"):
        names = {m["name"] for m in manifest.cell_metrics(man, other)[1]}
        assert not set(NEW) & names
    # present, and after every entry that stood before this PR's
    names = [w["name"] for w in man["workloads"]]
    assert names.index(CELL) > names.index("lfm2_8b_a1b_train_ep4share")
    configs = [c["name"] for c in man["configs"]]
    assert configs.index("ouro-2.6b-l7") > configs.index(
        "lfm2-8b-a1b-l5-ep4")
    metrics = [m["name"] for m in man["per_layer"]]
    assert metrics.index(NEW[0]) + 1 == metrics.index(NEW[1])
    assert metrics.index(NEW[0]) > metrics.index(
        "shortconv.operator_ms_per_step")
    for m in man["per_layer"] + man["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


def test_every_published_number_of_the_catalog_row_is_in_the_file():
    """The widths as published; only the depth differs, and the layer
    types stay whole."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: CFG[k] for k in published} == published
    assert CFG["layer_types"] == ["full_attention"] * 48
    assert CFG["num_hidden_layers"] == L
    for key in ("assumed", "deployment", "reduced_why", "rehearsal"):
        assert CFG[key]
    assert CFG["assumed"]["entropy_weight"] == 0.1
    # the rehearsal keeps every pass and the MHA heads
    r = manifest.effective(CFG, True)
    assert r["total_ut_steps"] == 4
    assert r["num_attention_heads"] == r["num_key_value_heads"]


def test_the_deployment_s_arithmetic():
    """7 pipeline stages share the 48 layers (six of 7, one of 6); the
    stage here folds in the embedding, the final norm, the gate and the
    head: 561 049 601 parameters, 8.98 GB at 16 bytes a parameter; 8
    layers would have been 612 438 017, 9.80 GB."""
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert 6 * 7 + 6 == 48
    here = 7 * layer + 2 * 49152 * 2048 + 2049 + 2048
    assert here == 561_049_601 == flops_loop.param_count(BUILDER.dims(CFG))
    assert 16 * here / 1e9 == pytest.approx(8.98, abs=5e-3)
    eight = 8 * layer + 2 * 49152 * 2048 + 2049 + 2048
    assert eight == 612_438_017 and 16 * eight / 1e9 == pytest.approx(
        9.80, abs=5e-3)
    for number in ("561 049 601", "8.98 GB", "six of 7, one of 6",
                   "4 times a step"):
        assert number in CFG["deployment"], number
    assert "15.24 GB" in CFG["reduced_why"]


def test_the_mix_is_two_4k_sequences_with_its_own_kernels_and_limits():
    assert (MIX["per_chip_batch"], MIX["seq_len"], MIX["remat"],
            MIX["attn_impl"], MIX["fused_head"], MIX["batch_ring"],
            MIX["partition_bytes"], MIX["warmup_steps"],
            MIX["trace_steps"]) == (2, 4096, True, "flash", True, 8,
                                    4096000, 4, 4)
    assert MIX["kernels"] == ["flash_fwd", "flash_bwd", "fused_ce_fwd",
                              "fused_ce_bwd_dx", "fused_ce_bwd_dw"]
    # the rehearsal keeps the timed limits: its sound readings lie as far
    # below them
    assert set(MIX["reference_limits"]) == set(LIMITS)
    assert "reference_limits" not in MIX["rehearsal"]
    assert MIX["loss_tolerance_why"] and MIX["reference_limits_why"]


# --------------------------------------- held to the reference, and faults


@pytest.fixture(scope="module")
def built():
    """``build_training`` at the rehearsal's tiny size on one CPU device
    (it holds the program to the reference before it returns)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cfg = manifest.effective(CFG, True)
    mix = manifest.effective(MIX, True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    step, state, batches, meta = BUILDER.build_training(cfg, mix, mesh, 7)
    return cfg, mix, step, state, batches, meta


def test_the_builder_builds_at_the_rehearsals_size(built):
    cfg, mix, step, state, batches, meta = built
    assert meta["tokens_per_step"] == 512 and len(batches) == 3
    assert int(max(b["tokens"].max() for b in batches)) < cfg["vocab_size"]
    assert [k for k in state.params if k.startswith("block_")] == [
        "block_0", "block_1"]
    assert set(state.params["block_0"]) == {"ln1", "attn", "ln1_out", "ln2",
                                            "mlp", "ln2_out"}
    assert state.params["exit_gate"]["kernel"].shape == (64, 1)
    assert state.params["lm_head"]["kernel"].shape == (64, 512)
    tc = BUILDER.transformer_config(cfg, mix)
    assert (tc.loop_steps, tc.sandwich_norm, tc.remat) == (4, True, True)


# which limit each planted fault has to trip at the rehearsal's size
# (limits of this test: sound readings are 3 times or more below them,
# each fault's 3 times or more above)
LIMITS = {"block_p90": 0.035, "grad_p90": 0.05, "param_grad_gap": 0.06,
          "loop_ce_gap": 0.007, "exit_p_gap": 0.0045, "loop_loss_gap": 0.0006}
BLOCKS = {"block_p90", "grad_p90", "param_grad_gap"}
FAULTS = {
    None: set(),
    "no_post_norm": BLOCKS | {"loop_ce_gap", "exit_p_gap", "loop_loss_gap"},
    "pre_norm_carry": {"loop_ce_gap", "exit_p_gap", "loop_loss_gap"},
    "no_remaining_mass": {"exit_p_gap", "loop_loss_gap"},
    "gate_before_norm": {"exit_p_gap", "loop_loss_gap"},
    "readout_late": {"loop_ce_gap", "loop_loss_gap"},
    "entropy_sign": {"loop_loss_gap"},
    "fp8_operands": BLOCKS | {"loop_ce_gap", "exit_p_gap", "loop_loss_gap"},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_the_program_is_held_to_the_reference_and_a_fault_is_told(
        built, fault, monkeypatch):
    """``hold_to_reference`` passes the sound program and ends a run
    whose program has one of ``benchmark/controls_loop.py``'s faults, by
    the limits that fault is for and by no other."""
    from benchmark import controls_loop
    from byteps_tpu.models import transformer
    from byteps_tpu.training import step as train_step

    cfg, mix, _, state, batches, _ = built
    for mod, name in ((transformer, "QuantDense"),
                      (transformer, "loop_pass"),
                      (transformer, "exit_log_probs"),
                      (train_step, "exit_weighted_loss")):
        monkeypatch.setattr(mod, name, getattr(mod, name))  # restored after
    monkeypatch.setattr(transformer.TransformerConfig, "make_norm",
                        transformer.TransformerConfig.make_norm)
    monkeypatch.setattr(transformer.Transformer, "hidden",
                        transformer.Transformer.hidden)
    if fault:
        controls_loop.CONTROLS[fault]()
    tokens = batches[0]["tokens"]          # the step's first batch
    job = dict(mix, reference_limits=LIMITS)
    worst = BUILDER.reference_gaps(cfg, job, state.params, tokens)
    over = {n for n in LIMITS if not worst[n] <= LIMITS[n]}
    assert over == FAULTS[fault], worst
    if fault:
        with pytest.raises(BUILDER.ReferenceMismatch, match=min(
                FAULTS[fault])):
            BUILDER.hold_to_reference(cfg, job, state.params, tokens)
    else:
        BUILDER.hold_to_reference(cfg, job, state.params, tokens)


def test_the_controls_file_names_every_fault_and_ends_in_run_main():
    from benchmark import controls_loop

    assert set(controls_loop.CONTROLS) == set(FAULTS) - {None}
    assert controls_loop.main(["no_such_fault"]) == 2


def test_the_new_cell_rehearses_on_the_cpu():
    """The whole command at the rehearsal's tiny size: the set-up's
    comparison with the reference, the runner's first loss, the step's
    checks, no device metric."""
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
    assert held["over"] == []
    blocks = next(x for x in lines if x.get("event") == "reference_blocks")
    assert len(blocks["per_block"]) == 2 * 4 and len(blocks["per_pass"]) == 4
    ref = next(x for x in lines if x.get("event") == "reference")
    assert ref["abs_gap"] < 1e-3
    built = next(x for x in lines if x.get("event") == "built")
    assert built["bytes_master_grad_moments"] == 16 * built["parameters"]
    assert (built["layers_held"], built["loop_steps"]) == (2, 4)
