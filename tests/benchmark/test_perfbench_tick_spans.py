"""The readers of the serving engine's host spans, phase counters and
program scopes (ISSUE 36): ``harness/host_spans.py`` and the five
``layer_metrics`` files on a hand-made trace whose every number is
worked out in the comments, ``None`` on a trace without the spans (the
parent), ``planned_cells_tick.json`` held to the manifest's contract
with ``planned_cells.json`` and it laid over ``BENCHMARK.json``, and —
slow — the chat cell's traced rehearsal reporting the two counter
metrics."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import importlib.util
import json
import shutil
import subprocess
import types

import pytest
from jax.profiler import ProfileData

from benchmark.harness import host_spans, manifest, xplane

US = 1_000_000          # picoseconds per microsecond
CHAT, DOC = "mistral7b_chat_steady", "mistral7b_longdoc_batch"
CHAT_METRICS = {"submit.lock_wait_p50_ms", "tick.host_ms_per_decode_tick.itl",
                "device.idle_attributed_share.itl",
                "serve_prog.decode_outside_model_ms",
                "stream.emit_to_wire_p50_ms"}
DOC_METRICS = {"tick.host_ms_per_decode_tick.tput",
               "device.idle_attributed_share.tput"}

# ------------------------------------------------------- the hand-made trace
#
# Device (microseconds).  Two launches of the decode program, 100-400 and
# 1000-1300, each: an MLP fusion under bps.model 150, the paged kernel
# under bps.model 80, a 10 us gap, the token pick under bps.serve/select
# 50, a nameless mask 10; one chunk launch 2000-2600.  No bench.window
# survives xplane.from_profile_data here (three host lines share the name
# python3, the last one read wins), so the window is the device ops'
# span, 100-2600, as in a serve run on the chip.  Idle inside it:
# 330-340, 400-1000, 1230-1240, 1300-2000 = 1320 us.

MODEL_OP = "jit(decode_fn)/bps.model/Transformer.decode_paged_fused/" \
           "Transformer.decode/block_0/mlp/up/dot_general:"
KERNEL_OP = "jit(decode_fn)/bps.model/Transformer.decode_paged_fused/" \
            "Transformer.decode/block_0/attn/jit(paged_decode_attention)/" \
            "paged_decode_attention/pallas_call:"
SELECT_OP = "jit(decode_fn)/vmap(bps.serve/select)/argmax:"
MASK_OP = "jit(decode_fn)/select_n:"
SCOPED = {
    "%fusion.1 = bf16[32,14336]{1,0} fusion(bf16[32,4096]{1,0} %p.1)":
        MODEL_OP,
    "%paged_decode_attention.2 = bf16[32,4096]{1,0} custom-call("
    "bf16[32,4096]{1,0} %fusion.1)": KERNEL_OP,
    "%fusion.3 = s32[32]{0} fusion(f32[32,32768]{1,0} %p.2)": SELECT_OP,
    "%fusion.4 = s32[32]{0} fusion(s32[32]{0} %fusion.3)": MASK_OP,
    "%fusion.9 = bf16[512,4096]{1,0} fusion(bf16[512,4096]{1,0} %p.3)":
        "jit(chunk_fn)/bps.model/Transformer.prefill_chunk_paged/block_0/"
        "mlp/up/dot_general:",
}
F1, KERNEL, F3, F4, F9 = SCOPED


def decode_launch(t):
    return [(F1, t, t + 150), (KERNEL, t + 150, t + 230),
            (F3, t + 240, t + 290), (F4, t + 290, t + 300)]


DEVICE_OPS = decode_launch(100) + decode_launch(1000) + [(F9, 2000, 2600)]
MODULES = [("jit_decode_fn(123)", 100, 400), ("jit_decode_fn(123)", 1000, 1300),
           ("jit_chunk_fn(9)", 2000, 2600)]

# Host.  The tick thread: tick A 50-480 (a decode pass), an idle wait
# 500-900, tick B 950-1400 (an admission with a middle chunk, then the
# decode pass), an idle wait 1500-1900, tick C 1950-2700 (a final chunk,
# no decode pass).  A connection thread submits 1380-1460.


def decode_pass(t, blocks, build, launch, readback, emit, tail):
    b0 = t
    b1 = b0 + blocks
    b2 = b1 + build
    b3 = b2 + launch
    b4 = b3 + readback
    b5 = b4 + emit
    return [("bps.tick/decode", t, b5 + tail),
            ("bps.tick/decode/blocks", b0, b1),
            ("bps.tick/decode/build", b1, b2),
            ("bps.tick/decode/launch", b2, b3),
            ("bps.tick/decode/readback", b3, b4),
            ("bps.tick/decode/emit", b4, b5)]


TICK_THREAD = (
    [("bps.tick", 50, 480)]
    + decode_pass(60, 10, 20, 20, 310, 40, 10)          # 60-470
    + [("bps.tick/account", 470, 478),
       ("bps.tick/idle_wait", 500, 900),
       ("bps.tick", 950, 1400),
       ("bps.tick/admit", 955, 960),
       ("bps.tick/prefill", 960, 990),
       ("bps.tick/prefill/build", 960, 970),
       ("bps.tick/prefill/launch", 970, 985)]
    + decode_pass(990, 5, 15, 15, 295, 50, 10)          # 990-1380
    + [("bps.tick/account", 1385, 1395),
       ("bps.tick/idle_wait", 1500, 1900),
       ("bps.tick", 1950, 2700),
       ("bps.tick/prefill", 1960, 2690),
       ("bps.tick/prefill/build", 1960, 1990),
       ("bps.tick/prefill/launch", 1990, 2010),
       ("bps.tick/prefill/readback", 2010, 2680),
       ("bps.tick/account", 2692, 2698),
       ("PjitFunction(decode_fn)", 90, 110)])           # the runtime's own
SUBMIT_THREAD = [("bps.submit", 1380, 1460),
                 ("bps.submit/lock_wait", 1382, 1402),
                 ("bps.submit/enqueue", 1402, 1455)]
MAIN_THREAD = [("bench.window", 0, 3000)]

# Device-idle microseconds by the innermost span over them:
#  330-340    decode/readback 10
#  400-1000   decode/readback 20 (to 420), decode/emit 40, decode 10 (its
#             tail), account 8, tick 2 (to 480), nobody 20, idle_wait 400,
#             nobody 50 (900-950), tick 5, admit 5, prefill/build 10,
#             prefill/launch 15, prefill 5, decode/blocks 5, decode/build 5
#  1230-1240  decode/readback 10
#  1300-2000  decode/readback 20, decode/emit 50, decode 10, tick 5,
#             account 10, tick 5 (to 1400), nobody 100 of which the
#             submit span covers 1400-1460 = 60, idle_wait 400, nobody 50
#             (1900-1950), tick 10, prefill/build 30, prefill/launch 10
IDLE_US = {"decode/readback": 60, "decode/emit": 90, "decode": 20,
           "account": 18, "tick": 27, "idle_wait": 800, "admit": 5,
           "prefill/build": 40, "prefill/launch": 25, "prefill": 5,
           "decode/blocks": 5, "decode/build": 5, "submit": 60,
           "unattributed": 160}


def text_proto(spans=True, scoped=True):
    metas = {}

    def mid(name):
        return metas.setdefault(name, len(metas) + 1)

    def events(evs):
        return "\n".join(
            f"    events {{ metadata_id: {mid(n)} offset_ps: {s * US} "
            f"duration_ps: {(e - s) * US} }}" for n, s, e in evs)

    dev_lines = (f'  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n'
                 f'{events(MODULES)} }}\n'
                 f'  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n'
                 f'{events(DEVICE_OPS)} }}')
    table = []
    for name, i in metas.items():
        op = SCOPED.get(name) if scoped else None
        if op is not None and "bps." not in op:
            op = None                     # (nothing is lost: no scope in it)
        stat = (f' stats {{ metadata_id: 1 str_value: "{op}" }}'
                if op else "")
        esc = name.replace('"', '\\"')
        table.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{esc}"{stat} }} }}')
    device = ('planes { id: 1 name: "/device:TPU:0"\n'
              '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
              + "\n".join(table) + "\n" + dev_lines + " }")
    metas = {}
    threads = [MAIN_THREAD,
               TICK_THREAD if spans else TICK_THREAD[-1:],
               SUBMIT_THREAD if spans else []]
    host_lines = "\n".join(
        f'  lines {{ id: {k + 1} name: "python3" timestamp_ns: 0\n'
        f'{events(evs)} }}' for k, evs in enumerate(threads) if evs)
    host = ('planes { id: 2 name: "/host:CPU"\n' + "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in metas.items()) + "\n" + host_lines + " }")
    return device + "\n" + host


def make_ctx(cell=CHAT, spans=True, scoped=True, serve=None):
    text = text_proto(spans, scoped)
    data = ProfileData.text_proto_to_serialized_xspace(text)
    pd = ProfileData.from_serialized_xspace(data)
    notes = []
    ctx = types.SimpleNamespace(
        trace=xplane.from_profile_data(pd), profile_data=pd, xspace=data,
        train=None, serve=serve, cell={"name": cell},
        note=lambda **kw: notes.append(kw))
    return ctx, notes


def read(name, ctx):
    return manifest.reader_for(manifest.layer_readers(), name).read(ctx)


# --------------------------------------------------------------- host spans


def test_spans_nest_by_thread_and_a_tick_is_its_span_less_its_readbacks():
    ctx, _ = make_ctx()
    # what the accepted reduction keeps of three lines named python3
    assert list(ctx.trace.host) == ["python3"]
    assert ctx.trace.window == pytest.approx((100e-6, 2600e-6))
    lines = host_spans.host_lines(ctx.profile_data)
    assert len(lines) == 2                       # main has no bps.* span
    tick_roots, submit_roots = lines
    assert [s.name for s in tick_roots] == [
        "bps.tick", "bps.tick/idle_wait", "bps.tick", "bps.tick/idle_wait",
        "bps.tick"]
    assert [c.name for c in tick_roots[2].children] == [
        "bps.tick/admit", "bps.tick/prefill", "bps.tick/decode",
        "bps.tick/account"]
    assert [s.name for s in submit_roots] == ["bps.submit"]
    assert [c.name for c in submit_roots[0].children] == [
        "bps.submit/lock_wait", "bps.submit/enqueue"]
    assert len(host_spans.ticks(lines)) == 3
    dec = host_spans.decode_ticks(lines)
    assert len(dec) == 2                         # tick C ran no decode pass
    # A: 430 - 310; B: 450 - 295
    assert [host_spans.host_seconds(t) for t in dec] == pytest.approx(
        [120e-6, 155e-6])
    # the final chunk's tick: 750 less its 670 us readback
    assert host_spans.host_seconds(tick_roots[4]) == pytest.approx(80e-6)
    ms = host_spans.phase_ms(dec)
    # self time: A's tick 430 - (410 + 8), B's 450 - (5 + 30 + 390 + 10)
    assert ms["tick"] == pytest.approx({"median": 0.0135, "mean": 0.0135})
    assert ms["decode/readback"]["mean"] == pytest.approx(0.3025)
    assert ms["decode/build"]["median"] == pytest.approx(0.0175)
    assert ms["decode"]["mean"] == pytest.approx(0.010)
    # a phase only B has counts as 0 in A
    assert ms["prefill/launch"] == pytest.approx(
        {"median": 0.0075, "mean": 0.0075})
    assert ms["admit"]["mean"] == pytest.approx(0.0025)


def test_idle_gaps_go_to_the_innermost_span_over_each_part():
    ctx, _ = make_ctx()
    lines = host_spans.host_lines(ctx.profile_data)
    idle = host_spans.idle_by_span(ctx.trace, lines)
    assert {k: round(v * 1e6, 3) for k, v in idle.items()} == IDLE_US
    assert sum(idle.values()) == pytest.approx(1320e-6)
    assert host_spans.attributed_share(idle) == pytest.approx(
        100.0 * (1 - 160 / 1320))


def test_idle_before_the_first_and_after_the_last_recorded_span_is_not_counted():
    """A span in progress when the profiler starts is not in the trace:
    without tick A and the first idle wait the tick thread's spans begin
    at 950, and the 600 us idle before it (330-340, 400-950) belong to no
    span's account — neither attributed nor unattributed."""
    ctx, _ = make_ctx()
    tick_roots, submit_roots = host_spans.host_lines(ctx.profile_data)
    idle = host_spans.idle_by_span(ctx.trace, [tick_roots[2:], submit_roots])
    # of the second gap only 950-1000 is left: tick 5, admit 5,
    # prefill/build 10, prefill/launch 15, prefill 5, decode/blocks 5,
    # decode/build 5; the third and fourth gaps as before
    assert sum(idle.values()) == pytest.approx((50 + 10 + 700) * 1e-6)
    assert idle["idle_wait"] == pytest.approx(400e-6)
    assert idle["unattributed"] == pytest.approx(90e-6)
    assert "decode/emit" in idle and idle["decode/emit"] == pytest.approx(
        50e-6)
    # no span on the tick thread at all: nothing to attribute to
    assert host_spans.idle_by_span(ctx.trace, [submit_roots]) is None


def test_the_decode_program_by_scope():
    ctx, _ = make_ctx()
    res = host_spans.decode_scopes(ctx.xspace)
    assert res["launches"] == 2
    assert {k: round(v * 1e6, 3) for k, v in res["per_launch_s"].items()} == {
        "model.kernel": 80, "model.mlp": 150, "select": 50, "unscoped": 10}
    assert res["outside_model_s"] == pytest.approx(60e-6)
    # the instances of an instruction summed under one name, largest first
    assert {k: round(v * 1e6, 3) for k, v in res["largest_ops_s"].items()
            } == {"fusion bf16[32,14336] [model.mlp]": 150,
                  "paged_decode_attention bf16[32,4096] [model.kernel]": 80,
                  "fusion s32[32] [select]": 50,
                  "fusion s32[32] [unscoped]": 10}
    # the chunk program is another program
    assert host_spans.decode_scopes(ctx.xspace, "chunk_fn")[
        "per_launch_s"] == pytest.approx({"model.mlp": 600e-6})
    assert host_spans.decode_scopes(ctx.xspace, "verify_fn") is None


def test_an_instruction_name_two_programs_share_is_told_apart_by_its_text():
    """``%fusion.1`` of the decode program (under the model) and a
    ``%fusion.1`` of another program (under no scope) are two
    instructions: the scope is looked up by the whole HLO text."""
    other = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.9)"
    text = text_proto().replace(
        '  lines { id: 2 name: "XLA Ops"',
        '  event_metadata { key: 99 value { id: 99 name: "' + other
        + '" } }\n  lines { id: 2 name: "XLA Ops"')
    data = ProfileData.text_proto_to_serialized_xspace(text)
    res = host_spans.decode_scopes(data)
    assert res["per_launch_s"]["model.mlp"] == pytest.approx(150e-6)


# ------------------------------------------------------------- the readers

STATS_BEFORE = {
    "serve.decode_ticks": 100, "serve.ticks_worked": 110,
    "serve.tick_seconds": {"build": 0.10, "launch": 0.20, "account": 0.011}}
STATS_AFTER = {
    "serve.decode_ticks": 300, "serve.ticks_worked": 330,
    "serve.tick_seconds": {"build": 0.30, "launch": 0.70, "account": 0.055,
                           "admit": 0.022},
    "submit_lock_wait_p50_s": 0.25, "submit_lock_wait_p99_s": 4.0,
    "submit_lock_wait_n": 24,
    "emit_to_wire_p50_s": 0.0004, "emit_to_wire_p99_s": 0.003,
    "emit_to_wire_n": 4096}


def test_the_five_readers_on_the_hand_made_trace():
    serve = {"stats_before": STATS_BEFORE, "stats_after": STATS_AFTER}
    ctx, notes = make_ctx(serve=serve)
    assert read("tick.host_ms_per_decode_tick.itl", ctx) == pytest.approx(
        0.1375)                                  # median of 0.120, 0.155
    assert read("device.idle_attributed_share.itl", ctx) == pytest.approx(
        100.0 * (1 - 160 / 1320))
    assert read("serve_prog.decode_outside_model_ms", ctx) == pytest.approx(
        0.060)
    assert read("submit.lock_wait_p50_ms", ctx) == pytest.approx(250.0)
    assert read("stream.emit_to_wire_p50_ms", ctx) == pytest.approx(0.4)
    # the longdoc cell reads the same two trace metrics under its names
    assert read("tick.host_ms_per_decode_tick.tput", ctx) == pytest.approx(
        0.1375)
    by_event = {}
    for n in notes:
        by_event.setdefault(n["event"], []).append(n)
    # each note once, whichever reader asked first
    assert {k: len(v) for k, v in by_event.items()} == {
        "tick_phases": 1, "tick_counters": 2, "decode_scopes": 1,
        "submit_lock_wait": 1, "emit_to_wire": 1}
    tp = by_event["tick_phases"][0]
    assert (tp["threads_with_spans"], tp["ticks"], tp["decode_ticks"]) == (
        2, 3, 2)
    assert tp["idle_s_by_span"]["idle_wait"] == pytest.approx(800e-6)
    assert tp["idle_s_by_span"]["unattributed"] == pytest.approx(160e-6)
    assert tp["decode_tick_ms_by_phase"]["decode/launch"][
        "median"] == pytest.approx(0.0175)
    # the counters over the whole window: a pass's phases a decode tick
    # (200 of them), the others a worked tick (220)
    tc = by_event["tick_counters"][0][
        "ms_per_tick_by_phase_over_the_window"]
    assert tc == pytest.approx({"build": 1.0, "launch": 2.5, "account": 0.2,
                                "admit": 0.1})
    ds = by_event["decode_scopes"][0]
    assert ds["ms_per_launch"]["select"] == pytest.approx(0.050)
    assert ds["largest_ops_ms"]["fusion s32[32] [select]"] == pytest.approx(
        0.050)
    assert ds["outside_model_ms"] == pytest.approx(0.060)
    assert by_event["submit_lock_wait"][0] == {
        "event": "submit_lock_wait", "n": 24, "p99_ms": 4000.0}


def test_the_parent_reports_none_of_them():
    """No spans, no scopes, no counters: every reader returns ``None``
    and prints nothing, so the line leaves the metric out."""
    serve = {"stats_before": {"serve.decode_ticks": 1},
             "stats_after": {"serve.decode_ticks": 9, "queue_wait_n": 3,
                             "queue_wait_p50_s": 0.1}}
    ctx, notes = make_ctx(spans=False, scoped=False, serve=serve)
    assert host_spans.host_lines(ctx.profile_data) == []
    for name in sorted(CHAT_METRICS | DOC_METRICS):
        assert read(name, ctx) is None, name
    assert notes == []
    # a rehearsal has no trace at all, and a train cell no serve side
    ctx.trace, ctx.serve = None, None
    del ctx.host_spans, ctx.decode_scopes
    ctx.xspace = None
    for name in sorted(CHAT_METRICS | DOC_METRICS):
        assert read(name, ctx) is None, name
    # the accepted readers still read the old trace
    ctx, _ = make_ctx(spans=False, scoped=False, serve=serve)
    assert read("serve_prog.decode_device_ms", ctx) == pytest.approx(0.3)
    assert read("sched.queue_wait_p50_ms", ctx) == pytest.approx(100.0)


def test_spans_without_counters_and_counters_without_spans():
    ctx, notes = make_ctx(serve={"stats_before": {}, "stats_after": {}})
    assert read("tick.host_ms_per_decode_tick.itl", ctx) == pytest.approx(
        0.1375)
    assert [n for n in notes if n["event"] == "tick_counters"][0][
        "ms_per_tick_by_phase_over_the_window"] is None
    assert read("submit.lock_wait_p50_ms", ctx) is None
    ctx, _ = make_ctx(spans=False, scoped=True, serve={
        "stats_before": STATS_BEFORE, "stats_after": STATS_AFTER})
    assert read("tick.host_ms_per_decode_tick.itl", ctx) is None
    assert read("serve_prog.decode_outside_model_ms", ctx) == pytest.approx(
        0.060)
    assert read("stream.emit_to_wire_p50_ms", ctx) == pytest.approx(0.4)


# ------------------------------------------------------------- the manifest


def manifest_tests():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "test_perfbench_manifest.py")
    spec = importlib.util.spec_from_file_location("_manifest_tests", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def with_both_planned():
    """``BENCHMARK.json`` as the PR that opens the serve cells will leave
    it: the entries of ``planned_cells.json`` and, after them, those of
    ``planned_cells_tick.json``."""
    man = manifest_tests().with_planned(manifest.load_manifest())
    tick = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "planned_cells_tick.json"))
    assert set(tick) == {"what", "per_layer"}
    man["per_layer"] = man["per_layer"] + [dict(e) for e in
                                           tick["per_layer"]]
    return man


@pytest.mark.parametrize("check", [
    "test_top_level_keys_and_limits",
    "test_configs_are_used_sourced_and_cut_only_in_depth",
    "test_cells_pair_once_and_one_in_four_may_take_four_chips",
    "test_metrics_are_bounded_sourced_and_every_cell_reports"])
def test_both_planned_files_laid_over_hold_to_the_contract(check):
    getattr(manifest_tests(), check)(with_both_planned())


def test_each_planned_cell_lists_exactly_its_new_metrics():
    man = with_both_planned()
    tick = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "planned_cells_tick.json"))
    new = {m["name"] for m in tick["per_layer"]}
    assert new == CHAT_METRICS | DOC_METRICS
    for cell, want in ((CHAT, CHAT_METRICS), (DOC, DOC_METRICS)):
        _, layer = manifest.cell_metrics(man, cell)
        assert {m["name"] for m in layer} & new == want
    # no open cell reports a serve metric: BENCHMARK.json alone lists none
    accepted = manifest.load_manifest()
    for c in accepted["workloads"]:
        _, layer = manifest.cell_metrics(accepted, c["name"])
        assert not {m["name"] for m in layer} & new
    for m in tick["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in {x["name"] for x in accepted["per_layer"]}


@pytest.mark.slow
def test_the_chat_cell_rehearsed_traced_reports_the_two_counter_metrics(
        tmp_path):
    """Three processes at the rehearsal's tiny size: the engine's own
    histograms reach the result line over the STATS reply; a rehearsal
    has no trace, so the three trace metrics are left out."""
    tmp = str(tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(with_both_planned(), f)
    os.symlink(os.path.join(manifest.ROOT, "byteps_tpu"),
               os.path.join(tmp, "byteps_tpu"))
    r = subprocess.run(
        [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
         "--workload", CHAT, "--seed", "3", "--seconds", "3", "--trace",
         "1", "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=tmp)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    got = set(line["metrics"])
    assert got & CHAT_METRICS == {"submit.lock_wait_p50_ms",
                                  "stream.emit_to_wire_p50_ms"}
    assert line["metrics"]["stream.emit_to_wire_p50_ms"]["value"] > 0
