"""The program's scopes read back from a device trace
(``benchmark/harness/scopes.py``): the ``op_name`` parser, the metadata
tables of a hand-made text proto, ``post_backward`` on hand-made
intervals, and the four readers on a reduction of PR 25's own four-chip
trace with its numbers pinned."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import types

import pytest

from benchmark.harness import manifest, scopes, xplane

US = 1_000_000          # picoseconds per microsecond
J = "jit(local_step)/shard_map/"
HLO = "%{0} = f32[8]{{0}} {1}(f32[8]{{0}} %p)"


@pytest.mark.parametrize("op_name,want", [
    (J + "jvp(bps.model)/Transformer.hidden/block_3/mlp/up/dot_general",
     scopes.Scope("model", False, None, "Transformer.hidden/block_3/mlp/up")),
    (J + "transpose(jvp(bps.model))/Transformer.hidden/block_0/attn/q/"
     "dot_general",
     scopes.Scope("model", True, None, "Transformer.hidden/block_0/attn/q")),
    # nested: the innermost bps scope wins, the side comes from outside
    (J + "transpose(jvp(bps.model))/bps.head/fused_ce_bwd_dw/pallas_call",
     scopes.Scope("head", True)),
    (J + "jvp(bps.model)/bps.head/convert_element_type",
     scopes.Scope("head", False)),
    # a wrapper around a scope that holds slashes (the issue's example)
    ("jit(f)/jvp(bps.push_pull/pack/b003)/cos",
     scopes.Scope("pack", False, 3)),
    ("jit(f)/transpose(jvp(bps.push_pull/pack/b003))/mul",
     scopes.Scope("pack", True, 3)),
    (J + "bps.push_pull/reduce/b345/all_gather",
     scopes.Scope("reduce", False, 345)),
    (J + "bps.push_pull/unpack/dynamic_slice", scopes.Scope("unpack")),
    (J + "bps.optimizer/jit(_where)/select_n", scopes.Scope("optimizer")),
    (J + "bps.step_metrics/psum", scopes.Scope("step_metrics")),
    # a name repeated inside a cond of an interpreted kernel
    (J + "jvp(bps.model)/bps.head/fused_ce_fwd/while/body/cond/"
     + J + "jvp(bps.model)/bps.head/fused_ce_fwd/while/body/cond/"
     "branch_1_fun/mul", scopes.Scope("head", False)),
    (J + "add", scopes.UNSCOPED),
    (J + "bps.unknown_stage/add", scopes.UNSCOPED),
    (J + "bps.push_pull/elsewhere/add", scopes.UNSCOPED),
    ("", scopes.UNSCOPED), (None, scopes.UNSCOPED),
])
def test_scope_of_an_op_name(op_name, want):
    assert scopes.parse(op_name) == want


@pytest.mark.parametrize("module,part", [
    ("Transformer.hidden/block_3/attn/q", "attn_proj"),
    ("Transformer.hidden/block_3/attn/o", "attn_proj"),
    ("Transformer.hidden/block_3/attn", "attn_other"),
    ("Transformer.hidden/block_3/attn/flash_fwd", "attn_other"),
    ("Transformer.hidden/block_3/mlp/up", "mlp"),
    ("Transformer.hidden/block_3/mlp", "mlp"),
    ("Transformer.hidden/block_3/ln2", "norm"),
    ("Transformer.hidden/ln_f", "norm"),
    ("Transformer.hidden/embed/jit(_take)", "embed"),
    ("Transformer.hidden/pos", "embed"),
    ("Transformer.hidden/block_3", "other"), ("", "other"),
])
def test_block_part_of_a_flax_module_path(module, part):
    assert scopes.model_part(module) == part


def test_components_take_wrappers_off_and_keep_jit_whole():
    assert scopes.components(
        "jit(f)/transpose(jvp(bps.push_pull/pack/b003))/mul") == [
        "jit(f)", "bps.push_pull", "pack", "b003", "mul"]
    assert scopes.components("jit(local_step)/bps.optimizer/jit(_where)"
                             "/select_n")[-2:] == ["jit(_where)", "select_n"]


# ------------------------------------------------------- a hand-made trace


def plane(pid, name, lines, op_names=None):
    """A text-proto plane; ``op_names`` maps an event's name to its
    ``tf_op`` stat, kept with the event's metadata as libtpu writes it
    (XProf's form: the op_name and a closing ``:``)."""
    op_names = op_names or {}
    names = sorted({ev[0] for _, evs in lines for ev in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"',
           '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }',
           '  stat_metadata { key: 2 value { id: 2 name: "flops" } }']
    for n, i in ids.items():
        stat = ""
        if n in op_names:
            stat = (f' stats {{ metadata_id: 2 uint64_value: 7 }}'
                    f' stats {{ metadata_id: 1 str_value: '
                    f'"{op_names[n]}:" }}')
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}"{stat} }} }}')
    for k, (lname, evs) in enumerate(lines):
        out.append(f'  lines {{ id: {k + 1} name: "{lname}" '
                   f'timestamp_ns: 0')
        for n, start_us, end_us in evs:
            out.append(f'    events {{ metadata_id: {ids[n]} offset_ps: '
                       f'{start_us * US} duration_ps: '
                       f'{(end_us - start_us) * US} }}')
        out.append("  }")
    out.append("}")
    return "\n".join(out)


def step_ops(t0, flash_end=300):
    """One step on one chip, microseconds from ``t0``:

    forward matmul 0-100, flash kernel 100-200 (a custom call), head
    forward 200-250, flash backward kernel 250-``flash_end``, backward
    norm fusion to 400, pack b000 400-410, an all-reduce of bucket 0
    410-450 with its wire cast 450-455, an all-gather of bucket 1
    455-475, an optimizer fusion 475-560 that swallowed the unpack
    slices (ONE root scope), a ``while`` 560-600 under the optimizer
    whose body op 570-590 has no op_name at all, the loss psum 600-605.
    The program runs 0-610."""
    return [(n, t0 + a, t0 + b) for n, a, b in [
        (HLO.format("fusion.1", "fusion"), 0, 100),
        ("%flash_fwd.2 = bf16[8] custom-call(bf16[8] %p)", 100, 200),
        (HLO.format("fusion.3", "fusion"), 200, 250),
        ("%flash_bwd_dq.4 = bf16[8] custom-call(bf16[8] %p)", 250,
         flash_end),
        (HLO.format("fusion.5", "fusion"), flash_end, 400),
        (HLO.format("fusion.6", "fusion"), 400, 410),
        (HLO.format("all-reduce.7", "all-reduce"), 410, 450),
        (HLO.format("convert.8", "convert"), 450, 455),
        (HLO.format("all-gather.9", "all-gather"), 455, 475),
        (HLO.format("fusion.10", "fusion"), 475, 560),
        (HLO.format("while.11", "while"), 560, 600),
        (HLO.format("copy.12", "copy"), 570, 590),
        (HLO.format("all-reduce.13", "all-reduce"), 600, 605)]]


OP_NAMES = {
    HLO.format("fusion.1", "fusion"):
        J + "jvp(bps.model)/Transformer.hidden/block_0/mlp/up/dot_general",
    "%flash_fwd.2 = bf16[8] custom-call(bf16[8] %p)":
        J + "jvp(bps.model)/Transformer.hidden/block_0/attn/flash_fwd/"
        "pallas_call",
    HLO.format("fusion.3", "fusion"): J + "jvp(bps.model)/bps.head/mul",
    "%flash_bwd_dq.4 = bf16[8] custom-call(bf16[8] %p)":
        J + "transpose(jvp(bps.model))/Transformer.hidden/block_0/attn/"
        "flash_bwd_dq/pallas_call",
    HLO.format("fusion.5", "fusion"):
        J + "transpose(jvp(bps.model))/Transformer.hidden/block_0/ln1/mul",
    HLO.format("fusion.6", "fusion"):
        J + "bps.push_pull/pack/b000/concatenate",
    HLO.format("all-reduce.7", "all-reduce"):
        J + "bps.push_pull/reduce/b000/reduce_scatter",
    HLO.format("convert.8", "convert"):
        J + "bps.push_pull/reduce/b000/convert_element_type",
    HLO.format("all-gather.9", "all-gather"):
        J + "bps.push_pull/reduce/b001/all_gather",
    HLO.format("fusion.10", "fusion"): J + "bps.optimizer/add",
    HLO.format("while.11", "while"): J + "bps.optimizer/while",
    HLO.format("all-reduce.13", "all-reduce"): J + "bps.step_metrics/psum",
}


def two_chip_trace():
    """Two steps on each of two chips; chip 1's backward flash kernel
    ends 20 us later in its first step (same end of the backward pass)."""
    text = "\n".join([
        plane(1, "/device:TPU:0", [
            ("XLA Ops", step_ops(0) + step_ops(1000)),
            ("XLA Modules", [("jit_local_step(1)", 0, 610),
                             ("jit_local_step(1)", 1000, 1610),
                             ("jit_tiny(2)", 700, 710)])],
            OP_NAMES),
        plane(2, "/device:TPU:1", [
            ("XLA Ops", step_ops(0, 320) + step_ops(1000)),
            ("XLA Modules", [("jit_local_step(1)", 0, 620),
                             ("jit_local_step(1)", 1000, 1610)])],
            OP_NAMES),
        plane(3, "/host:CPU", [
            ("python3/1", [("bench.window", 0, 1700)])]),
    ])
    return scopes.from_text_proto(text)


def test_scopes_of_a_hand_made_trace():
    st = two_chip_trace()
    assert st.op_name_key == "tf_op"
    assert sorted(st.ops) == [0, 1] and len(st.ops[0]) == 26
    by_name = {ev.name: ev for ev in st.ops[0][:13]}
    assert by_name["fusion.1 f32[8]"].scope.stage == "model"
    assert by_name["flash_fwd.2 bf16[8]"].kernel
    assert by_name["all-gather.9 f32[8]"].collective
    assert by_name["all-gather.9 f32[8]"].scope.bucket == 1
    # the op without an op_name is unscoped, and is taken off its parent
    assert by_name["copy.12 f32[8]"].scope is scopes.UNSCOPED
    assert by_name["while.11 f32[8]"].self_s == pytest.approx(20e-6)
    res = scopes.analyse(st)
    assert (res["steps"], res["chips"]) == (2, 2)
    us = {k: 1e6 * v for k, v in res["per_step_s"].items()}
    # per step, averaged over 2 chips x 2 steps; chip 1's first step has
    # its backward kernel 20 us longer and its norm fusion 20 us shorter
    assert us == pytest.approx({
        "model.mlp.fwd": 100, "model.kernel.fwd": 100, "head.xla.fwd": 50,
        "model.kernel.bwd": 55, "model.norm.bwd": 95, "pack": 10,
        "reduce.collective": 60, "reduce.copies": 5,
        "optimizer": 85 + 20, "unscoped": 20, "step_metrics": 5})
    assert 1e6 * res["scoped_sum_s"] == pytest.approx(605)
    assert res["unscoped_share"] == pytest.approx(20 / 605)
    # the program: the longest module, not the tiny one in between
    assert 1e6 * res["step_device_s"] == pytest.approx(610)
    # from the last backward op (400) to the program's end: 210 us on
    # chip 0; on chip 1 the median of 220 and 210 -- the worst chip
    assert 1e6 * res["post_backward_s"] == pytest.approx(215)
    assert 1e6 * res["optimizer_s"] == pytest.approx(105)
    # pack + unpack (fused away: nothing under its name) + the cast
    assert 1e6 * res["pack_unpack_s"] == pytest.approx(15)
    assert res["has_push_pull"]
    # the model outside kernels and outside the head
    assert 1e6 * res["model_blocks_xla_s"] == pytest.approx(195)


def test_the_buckets_note_of_a_hand_made_trace():
    b = scopes.buckets(two_chip_trace())
    assert b["chip"] == 0 and b["planned_buckets"] == 2
    assert b["collective_instructions_per_step"] == 3
    assert b["instructions_and_buffers_by_kind"] == {
        "all-reduce": [2, 2], "all-gather": [1, 1]}
    rows = b["collectives"]
    assert [r[:2] for r in rows] == [["all-reduce.7 f32[8]", 0],
                                     ["all-gather.9 f32[8]", 1],
                                     ["all-reduce.13 f32[8]", None]]
    # start after the program's start, in flight, exposed (all of it:
    # nothing computes beside a synchronous collective)
    assert rows[0][2:] == pytest.approx([0.410, 0.040, 0.040, 1])
    assert rows[1][2:] == pytest.approx([0.455, 0.020, 0.020, 1])
    assert b["bwd_first_start_ms"] == pytest.approx(0.250)
    assert b["bwd_last_end_ms"] == pytest.approx(0.400)
    assert b["first_collective_start_ms"] == pytest.approx(0.410)
    assert b["collectives_started_before_bwd_end"] == 0
    assert b["step_ms"] == pytest.approx(0.610)


def test_an_asynchronous_collective_is_one_row_and_hides_behind_compute():
    ops = [(HLO.format("fusion.1", "fusion"), 0, 100),
           (HLO.format("all-reduce-start.2", "all-reduce-start"), 100, 105),
           (HLO.format("fusion.3", "fusion"), 105, 200),
           (HLO.format("all-reduce-done.2", "all-reduce-done"), 200, 230)]
    names = {ops[0][0]: J + "transpose(jvp(bps.model))/mul",
             ops[1][0]: J + "bps.push_pull/reduce/b004/psum",
             ops[2][0]: J + "transpose(jvp(bps.model))/mul",
             ops[3][0]: J + "bps.push_pull/reduce/b004/psum"}
    st = scopes.from_text_proto(plane(1, "/device:TPU:0", [
        ("XLA Ops", ops), ("XLA Modules", [("jit_step(1)", 0, 230)])],
        names))
    b = scopes.buckets(st)
    (row,) = b["collectives"]
    # from the start op's begin to the done op's end; exposed are the
    # start op itself and the wait in done
    assert row[:2] == ["all-reduce-start.2 f32[8]", 4]
    assert row[2:] == pytest.approx([0.100, 0.130, 0.035, 1])
    assert b["collectives_started_before_bwd_end"] == 1
    assert 1e6 * scopes.analyse(st)["post_backward_s"] == pytest.approx(30)


def test_the_compilers_data_movement_takes_its_neighbours_scope():
    """A prefetch into the fast memory space (start, done) for an
    optimizer fusion, a layout copy of a parameter for the head, the
    layout copy of a backward fusion's result that nothing traced reads
    (the program's output): each takes the scope it moves data for, else
    from; an ``add`` that names a traced operation outside every scope
    does not, though a scoped fusion reads it."""
    ops = [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0)", 0, 100),
        ("%copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start("
         "f32[8]{0} %fusion.1)", 100, 101),
        ("%copy.5 = f32[8,8]{0,1} copy(f32[8,8]{1,0} %state_params_w.1)",
         101, 140),
        ("%fusion.2 = bf16[8,8]{1,0} fusion(f32[8,8]{0,1} %copy.5)", 140,
         200),
        ("%copy-done.1 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, "
         "f32[8]{0}, u32[]) %copy-start.1)", 200, 230),
        ("%add.7 = s32[] add(s32[] %state_step.1, s32[] %c.1)", 230, 235),
        ("%fusion.9 = f32[8]{0} fusion(f32[8]{0:S(1)} %copy-done.1, "
         "s32[] %add.7)", 235, 300),
        ("%copy.6 = f32[8]{0} copy(f32[8]{0} %fusion.1)", 300, 320)]
    names = {ops[0][0]: J + "transpose(jvp(bps.model))/block_0/mlp/up/mul",
             ops[2][0]: "state.params['w']",
             ops[3][0]: J + "jvp(bps.model)/bps.head/convert_element_type",
             ops[5][0]: "jit(local_step)/add",
             ops[6][0]: J + "bps.optimizer/add"}
    st = scopes.from_text_proto(plane(1, "/device:TPU:0", [
        ("XLA Ops", ops), ("XLA Modules", [("jit_step(1)", 0, 320)])],
        names))
    got = {ev.name.split(" ")[0]: (ev.scope.stage, ev.scope.bwd,
                                   ev.inherited) for ev in st.ops[0]}
    assert got == {
        "fusion.1": ("model", True, False),
        "copy-start.1": ("optimizer", False, True),   # through its done
        "copy.5": ("head", False, True),
        "fusion.2": ("head", False, False),
        "copy-done.1": ("optimizer", False, True),
        "add.7": ("unscoped", False, False),
        "fusion.9": ("optimizer", False, False),
        "copy.6": ("model", True, True)}               # from its producer
    res = scopes.analyse(st)
    assert 1e6 * res["inherited_s"] == pytest.approx(1 + 39 + 30 + 20)
    assert 1e6 * res["per_step_s"]["unscoped"] == pytest.approx(5)
    # the backward pass ends with its own last op, not with a copy of it
    assert 1e6 * res["post_backward_s"] == pytest.approx(220)


def test_a_combined_all_reduce_takes_its_readers_bucket_and_counts_buffers():
    """XLA's combiner merges per-bucket reductions into one variadic
    instruction and drops its name: the row carries the bucket of the
    first op that reads it, and how many buffers went in."""
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0)", 0, 100),
           ("%all-reduce.2 = (f32[8]{0:T(8)}, f32[8]{0:T(8)}, f32[4]{0}) "
            "all-reduce(f32[8]{0} %fusion.1, f32[8]{0} %p.1, f32[4]{0} "
            "%p.2), replica_groups={{0,1}}", 100, 160),
           ("%fusion.3 = f32[2]{0} fusion((f32[8]{0:T(8)}, f32[8]{0:T(8)}, "
            "f32[4]{0}) %all-reduce.2)", 160, 170),
           ("%all-gather.4 = f32[8]{0} all-gather(f32[2]{0} %fusion.3)",
            170, 180)]
    names = {ops[0][0]: J + "transpose(jvp(bps.model))/mul",
             ops[2][0]: J + "bps.push_pull/reduce/b007/dynamic_slice",
             ops[3][0]: J + "bps.push_pull/reduce/b007/all_gather"}
    st = scopes.from_text_proto(plane(1, "/device:TPU:0", [
        ("XLA Ops", ops), ("XLA Modules", [("jit_step(1)", 0, 180)])],
        names))
    assert scopes.result_count(ops[1][0]) == 3
    assert scopes.result_count(ops[3][0]) == 1
    b = scopes.buckets(st)
    assert [r[:2] + r[5:] for r in b["collectives"]] == [
        ["all-reduce.2 f32[8]", 7, 3], ["all-gather.4 f32[8]", 7, 1]]
    assert b["instructions_and_buffers_by_kind"] == {
        "all-reduce": [1, 3], "all-gather": [1, 1]}
    us = {k: 1e6 * v for k, v in scopes.analyse(st)["per_step_s"].items()}
    assert us["reduce.collective"] == pytest.approx(70)
    assert us["reduce.copies"] == pytest.approx(10)


def test_post_backward_on_hand_made_intervals():
    bwd = scopes.Scope("model", True)
    head_bwd = scopes.Scope("head", True)
    ev = lambda s, e, sc: scopes.ScopedEvent(  # noqa: E731
        "op", s, e, e - s, sc)
    launch = xplane.Event("jit_step(1)", 10.0, 20.0)
    ops = [ev(10, 12, scopes.Scope("model")), ev(12, 15, bwd),
           ev(15, 16.5, head_bwd), ev(16.5, 19, scopes.Scope("optimizer")),
           ev(21, 23, bwd)]                 # the next launch's
    assert scopes.post_backward_s(ops, launch) == pytest.approx(3.5)
    assert scopes.post_backward_s(ops[:1] + ops[3:4], launch) is None


def test_a_trace_without_scopes_reads_as_nothing():
    """The parent's program: the same trace, no ``bps.`` in any name."""
    plain = {k: v.replace("bps.", "") for k, v in OP_NAMES.items()}
    st = scopes.from_text_proto(plane(1, "/device:TPU:0", [
        ("XLA Ops", step_ops(0)),
        ("XLA Modules", [("jit_local_step(1)", 0, 610)])], plain))
    assert st.op_name_key == "tf_op" and scopes.analyse(st) is None
    bare = scopes.from_text_proto(plane(1, "/device:TPU:0", [
        ("XLA Ops", step_ops(0)),
        ("XLA Modules", [("jit_local_step(1)", 0, 610)])]))
    assert bare.op_name_key is None and scopes.analyse(bare) is None
    notes = []
    ctx = types.SimpleNamespace(
        trace=object(), train={}, scoped_trace=st,
        note=lambda **kw: notes.append(kw))
    for name in READERS:
        assert manifest.load_module("layer_metrics", name).read(ctx) is None
    assert [n["event"] for n in notes] == ["scopes"]      # said once
    assert notes[0]["found"] is False
    no_trace = types.SimpleNamespace(trace=None, train={}, note=None)
    assert scopes.for_run(no_trace) is None


READERS = {"train_step_post_backward_ms": 0.215,
           "optimizer_update_ms_per_step": 0.105,
           "push_pull_pack_unpack_ms_per_step": 0.015,
           "model_blocks_xla_ms_per_step": 0.195}


def test_the_four_readers_share_one_analysis_and_print_two_notes():
    notes = []
    ctx = types.SimpleNamespace(
        trace=object(), train={}, scoped_trace=two_chip_trace(),
        cell={"name": "hand_made"}, note=lambda **kw: notes.append(kw))
    for name, want in READERS.items():
        reader = manifest.load_module("layer_metrics", name)
        assert reader.read(ctx) == pytest.approx(want), name
        assert reader.SPEC["unit"] == "ms"
        assert reader.SPEC["source"] == "program_span"
    assert [n["event"] for n in notes] == ["scopes", "buckets"]
    note = notes[0]
    assert note["found"] and note["op_name_key"] == "tf_op"
    assert sum(note["ms_per_step"].values()) == pytest.approx(
        note["scoped_sum_ms"])
    assert note["unscoped_share_pct"] == pytest.approx(100 * 20 / 605)
    assert note["step_device_ms"] == pytest.approx(0.610)


def test_a_one_chip_step_reports_no_push_pull_metric():
    keep = {k: v for k, v in OP_NAMES.items() if "push_pull" not in v}
    st = scopes.from_text_proto(plane(1, "/device:TPU:0", [
        ("XLA Ops", [op for op in step_ops(0)
                     if op[0] in keep or "copy.12" in op[0]]),
        ("XLA Modules", [("jit_local_step(1)", 0, 610)])], keep))
    ctx = types.SimpleNamespace(trace=object(), train={}, scoped_trace=st,
                                note=lambda **kw: None)
    read = lambda name: manifest.load_module(  # noqa: E731
        "layer_metrics", name).read(ctx)
    assert read("push_pull_pack_unpack_ms_per_step") is None
    assert read("optimizer_update_ms_per_step") == pytest.approx(0.105)
    assert read("train_step_post_backward_ms") == pytest.approx(0.210)
    assert scopes.buckets(st)["planned_buckets"] == 0


def test_metadata_tables_hold_interned_strings_and_numbers():
    text = """planes { id: 1 name: "/device:TPU:0"
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(f)/bps.optimizer/add" } }
  event_metadata { key: 1 value { id: 1 name: "%a.1 = f32[] add()"
    stats { metadata_id: 1 ref_value: 9 }
    stats { metadata_id: 2 uint64_value: 300 } } }
  event_metadata { key: 2 value { id: 2 name: "%b.2 = f32[] add()" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 5000000 } }
}"""
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(text)
    assert scopes.metadata_stats(data) == {"/device:TPU:0": {
        "%a.1 = f32[] add()": {"tf_op": "jit(f)/bps.optimizer/add",
                               "flops": 300},
        "%b.2 = f32[] add()": {}}}
    st = scopes.from_serialized(data)
    assert [ev.scope.stage for ev in st.ops[0]] == ["optimizer", "unscoped"]


# ------------------------------------------------ the traces recorded on the chip

TESTDATA = os.path.join(manifest.BENCH_DIR, "testdata")


def recorded(name):
    path = os.path.join(TESTDATA, name)
    assert os.path.getsize(path) < 1 << 20
    with open(path) as f:
        return scopes.from_text_proto(f.read())


@pytest.fixture(scope="module")
def recorded_1chip():
    """Two steps of ``gpt2m_train_1chip`` on the v5e (PR 25, the program
    with its scopes), cut by ``testdata/record_scoped.py`` to ops of
    150 us and more: names and ``tf_op`` stats are the trace's own."""
    return recorded("v5e_train_1chip_scoped.textproto")


def reader_ctx(st, notes=None):
    notes = [] if notes is None else notes
    return types.SimpleNamespace(
        trace=object(), train={}, scoped_trace=st, cell={"name": "recorded"},
        note=lambda **kw: notes.append(kw))


def test_recorded_one_chip_step_by_scope(recorded_1chip):
    st = recorded_1chip
    assert st.op_name_key == "tf_op" and len(st.ops[0]) == 466
    res = scopes.analyse(st)
    assert (res["steps"], res["chips"]) == (2, 1)
    ms = {k: 1e3 * v for k, v in res["per_step_s"].items()}
    # the three flash kernels and the three fused-CE kernels, by side
    assert ms["model.kernel.fwd"] == pytest.approx(10.483565, abs=1e-5)
    assert ms["model.kernel.bwd"] == pytest.approx(33.751991, abs=1e-5)
    assert ms["head.kernel.fwd"] == pytest.approx(11.189304, abs=1e-5)
    assert ms["head.kernel.bwd"] == pytest.approx(19.684953, abs=1e-5)
    # on one chip AdamW rides in the weight-gradient fusions of the
    # backward pass (their name is the matmul's): the MLP's backward is
    # twice its forward, and the optimizer's own ops are the embedding
    # table's update and little else
    assert ms["model.mlp.bwd"] == pytest.approx(38.200757, abs=1e-5)
    assert ms["model.mlp.fwd"] == pytest.approx(18.297779, abs=1e-5)
    assert ms["optimizer"] == pytest.approx(2.017202, abs=1e-5)
    assert 1e3 * res["step_device_s"] == pytest.approx(190.926088)
    assert not res["has_push_pull"] and scopes.buckets(st) is None
    # the cut dropped the short ops: what is left sums to less
    assert 1e3 * res["scoped_sum_s"] == pytest.approx(139.738265)
    assert 100 * res["unscoped_share"] == pytest.approx(2.237076, abs=1e-5)


@pytest.mark.parametrize("reader,want", [
    ("train_step_post_backward_ms", 5.284864),
    ("optimizer_update_ms_per_step", 2.017202),
    ("model_blocks_xla_ms_per_step", 58.421223),
    ("push_pull_pack_unpack_ms_per_step", None)])
def test_readers_on_the_recorded_one_chip_step(recorded_1chip, reader, want):
    got = manifest.load_module("layer_metrics", reader).read(
        reader_ctx(recorded_1chip))
    assert got == (want if want is None else pytest.approx(want, abs=1e-5))


@pytest.fixture(scope="module")
def recorded_dp4():
    """One step of ``gpt2m_train_dp4`` on the 2x2 v5e host (PR 25), chips
    0 and 1, cut to ops of 400 us and more plus EVERY collective op."""
    return recorded("v5e_train_dp4_scoped.textproto")


def test_recorded_four_chip_step_by_scope(recorded_dp4):
    st = recorded_dp4
    assert sorted(st.ops) == [0, 1]
    assert [len(st.ops[d]) for d in (0, 1)] == [791, 790]
    res = scopes.analyse(st)
    assert (res["steps"], res["chips"]) == (1, 2)
    ms = {k: 1e3 * v for k, v in res["per_step_s"].items()}
    # all of the collective time, the 12 nameless combined all-reduces
    # included: what collectives.exposed_ms_per_step reads from outside
    assert ms["reduce.collective"] == pytest.approx(37.587691, abs=1e-5)
    assert "step_metrics" not in ms     # the loss psum was combined in
    assert ms["head.kernel.bwd"] == pytest.approx(23.746988, abs=1e-5)
    assert ms["model.kernel.bwd"] == pytest.approx(33.915921, abs=1e-5)
    assert 1e3 * res["step_device_s"] == pytest.approx(259.562054)
    assert res["has_push_pull"]
    assert 1e3 * res["inherited_s"] == pytest.approx(24.871543, abs=1e-5)


def test_recorded_four_chip_buckets_note(recorded_dp4):
    b = scopes.buckets(recorded_dp4)
    # 346 all-gathers, one a bucket, each under its own name; the
    # reduce-scatters of all 347 buckets and the loss psum arrive as 12
    # combined all-reduces of 16 to 32 buffers
    assert b["collective_instructions_per_step"] == 358
    assert b["instructions_and_buffers_by_kind"] == {
        "all-reduce": [12, 348], "all-gather": [346, 346]}
    assert b["planned_buckets"] == 346          # ids seen in the cut
    rows = b["collectives"]
    assert rows[0][:2] == ["all-reduce.347 f32[567296]", None]
    assert rows[0][2:] == pytest.approx([138.176873, 2.208131, 2.208131, 32])
    assert [r[5] for r in rows[1:12]] == [16] + [30] * 10
    gathers = rows[12:]
    assert all(r[0].startswith("all-gather") and r[5] == 1 for r in gathers)
    assert len({r[1] for r in gathers}) == 346      # one bucket each
    assert [r[1] for r in gathers[:5]] == [227, 231, 228, 229, 230]
    # one combined reduction starts in the middle of the backward pass
    # (and blocks it: exposed = in flight), the other eleven right after
    assert b["bwd_first_start_ms"] == pytest.approx(67.094671)
    assert b["bwd_last_end_ms"] == pytest.approx(185.060894)
    assert b["collectives_started_before_bwd_end"] == 1
    assert rows[1][2] == pytest.approx(189.610877)
    assert all(r[3] == pytest.approx(r[4]) for r in rows)   # nothing hides
    assert b["step_ms"] == pytest.approx(259.558122)


@pytest.mark.parametrize("reader,want", [
    ("train_step_post_backward_ms", 74.497228),
    ("optimizer_update_ms_per_step", 4.662845),
    ("push_pull_pack_unpack_ms_per_step", 0.425787),
    ("model_blocks_xla_ms_per_step", 3.040412)])
def test_readers_on_the_recorded_four_chip_step(recorded_dp4, reader, want):
    """(The cut keeps ops of 400 us and more: the sums are the cut's,
    the elapsed time after the backward pass nearly the trace's.)"""
    notes = []
    got = manifest.load_module("layer_metrics", reader).read(
        reader_ctx(recorded_dp4, notes))
    assert got == pytest.approx(want, abs=1e-5)
    assert [n["event"] for n in notes] == ["scopes", "buckets"]
