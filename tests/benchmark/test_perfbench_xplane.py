"""The reduction from a profiler trace to numbers: interval arithmetic
on hand-made intervals, the whole reduction on a hand-made trace whose
every number is worked out in the comments, and on the small trace
recorded on the v5e that is checked in under ``benchmark/testdata``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


import pytest

from benchmark.harness import manifest, xplane

US = 1_000_000          # picoseconds per microsecond


def test_union_subtract_clip_on_hand_made_intervals():
    u = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert xplane.total(u) == 6
    assert xplane.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert xplane.subtract(u, [(0, 10)]) == []
    assert xplane.subtract([(0, 4), (6, 9)], [(1, 2), (3, 7), (8, 20)]) == [
        (0, 1), (2, 3), (7, 8)]
    assert xplane.overlap((0, 5), (3, 9)) == 2 and xplane.overlap(
        (0, 1), (2, 3)) == 0


def plane(pid, name, lines):
    names = sorted({ev[0] for _, evs in lines for ev in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for n, i in ids.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
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


def device_ops(done_end):
    """One step on one chip (microseconds):
    compute 0-100, all-reduce-start 100-110, flash 110-210 (hides the
    collective), all-reduce-done 210-done_end (exposed), idle until 300,
    fused CE 300-400, a ``while`` 400-500 whose body runs 420-460."""
    return [("fusion.1", 0, 100), ("all-reduce-start.1", 100, 110),
            ("flash_fwd.2", 110, 210), ("all-reduce-done.1", 210, done_end),
            ("jvp_fused_ce_fwd_.3", 300, 400), ("while.1", 400, 500),
            ("fusion.2", 420, 460)]


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    text = "\n".join([
        plane(1, "/device:TPU:0", [
            ("XLA Ops", device_ops(260)),
            ("XLA Modules", [("jit_step(1)", 0, 500)])]),
        plane(2, "/device:TPU:1", [
            ("XLA Ops", device_ops(230)),
            ("XLA Modules", [("jit_step(1)", 0, 500)])]),
        plane(3, "/host:CPU", [
            ("python3/1", [("bench.window", 0, 600),
                           ("bench.dispatch", 0, 50),
                           ("bench.wait", 50, 590)]),
            ("worker/2", [("ExecuteOnDevice", 255, 305)])]),
        plane(4, "Task Environment", []),
    ])
    return xplane.from_profile_data(ProfileData.from_text_proto(text))


def test_window_busy_and_idle_share(trace):
    assert trace.n_devices == 2
    assert trace.window == pytest.approx((0.0, 600e-6))
    # chip 0: [0, 260] + [300, 500] = 460 us; chip 1: 230 + 200 = 430 us
    assert xplane.busy_s(trace) == pytest.approx(445e-6)
    idle = manifest.load_module("layer_metrics", "device_idle_share")
    import types
    ctx = types.SimpleNamespace(trace=trace)
    assert idle.read(ctx) == pytest.approx(100 * (1 - 445 / 600))


def test_self_time_kernel_sums_and_programs(trace):
    totals = xplane.op_totals(trace)
    assert totals["while.1"] == (1, pytest.approx(60e-6))   # minus body
    assert totals["fusion.2"] == (1, pytest.approx(40e-6))
    assert xplane.kernel_time(trace, ("flash_fwd", "fused_ce_fwd")) == (
        2, pytest.approx(200e-6))
    assert xplane.kernel_time(trace, ("paged_decode",)) == (0, 0.0)
    assert xplane.module_durations(trace, "jit_step") == [
        pytest.approx(500e-6)]
    assert xplane.module_durations(trace, "decode_fn") == []


def test_exposed_and_in_flight_collective_time(trace):
    c = xplane.collective_seconds(trace)
    # exposed: the start op (10 us) and the wait in done; in flight:
    # from the start op's begin to the done op's end
    assert c[0]["exposed"] == pytest.approx(60e-6)
    assert c[1]["exposed"] == pytest.approx(30e-6)
    assert c[0]["total"] == pytest.approx(160e-6)
    assert c[0]["events"] == 2
    reader = manifest.load_module("layer_metrics",
                                  "collectives_exposed_ms_per_step")
    import types
    ctx = types.SimpleNamespace(trace=trace, train={"traced_steps": 1},
                                note=lambda **kw: None)
    assert reader.read(ctx) == pytest.approx(0.060)         # worst chip
    ctx.train["traced_steps"] = 0
    assert reader.read(ctx) is None


def test_idle_gaps_are_laid_at_the_hosts_door(trace):
    gaps = xplane.idle_gaps(trace)
    assert [(round(s * 1e6), round(e * 1e6)) for (s, e), _ in gaps] == [
        (260, 300), (500, 600)]
    # the benchmark's own span wins over the runtime's equal overlap
    assert [label for _, label in gaps] == ["bench.wait", "bench.wait"]
    b = xplane.breakdown(trace)
    # fusion.1 (100 us) and fusion.2 (40 us) are one instruction's
    # instances: summed under one name
    assert b["device_ops"][0] == ["fusion (x2)", pytest.approx(140e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"] == [["bench.wait (x2)", pytest.approx(140e-6)]]


def test_a_trace_without_device_planes_reads_as_nothing():
    from jax.profiler import ProfileData

    t = xplane.from_profile_data(ProfileData.from_text_proto(plane(
        1, "/host:CPU", [("python3/1", [("bench.window", 0, 10)])])))
    assert t.n_devices == 0 and xplane.busy_s(t) == 0.0
    assert xplane.idle_gaps(t) == [] and xplane.breakdown(t) == {
        "device_ops": [], "idle_gaps": []}
    assert xplane.collective_seconds(t) == {}


def test_chunk_programs_are_told_apart_by_their_fingerprint():
    """Three launches of a small chunk bucket and two of the full one:
    the reader gives the slowest program's median, where a median
    pooled over the five would give the small bucket's (24 us)."""
    import types

    from jax.profiler import ProfileData

    t = xplane.from_profile_data(ProfileData.from_text_proto(plane(
        1, "/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 0, 2000)]),
            ("XLA Modules", [("jit_chunk_fn(11)", 0, 20),
                             ("jit_chunk_fn(22)", 30, 830),
                             ("jit_decode_fn(5)", 830, 846),
                             ("jit_chunk_fn(11)", 850, 874),
                             ("jit_chunk_fn(22)", 900, 1698),
                             ("jit_chunk_fn(11)", 1700, 1722)])])))
    launches = xplane.module_launches(t, "chunk_fn")
    assert {k: len(v) for k, v in launches.items()} == {
        "jit_chunk_fn(11)": 3, "jit_chunk_fn(22)": 2}
    assert sorted(xplane.module_durations(t, "chunk_fn")) == pytest.approx(
        [20e-6, 22e-6, 24e-6, 798e-6, 800e-6])
    notes = []
    ctx = types.SimpleNamespace(trace=t, note=lambda **kw: notes.append(kw))
    chunk = manifest.load_module("layer_metrics",
                                 "serve_prog_prefill_chunk_device_ms")
    assert chunk.read(ctx) == pytest.approx(0.799)
    assert notes[0]["median_ms_by_bucket"] == pytest.approx(
        {"jit_chunk_fn(11)": 0.022, "jit_chunk_fn(22)": 0.799})
    decode = manifest.load_module("layer_metrics",
                                  "serve_prog_decode_device_ms")
    assert decode.read(ctx) == pytest.approx(0.016)
    ctx.trace = None
    assert chunk.read(ctx) is None and decode.read(ctx) is None


# ------------------------------------------------ the trace recorded on the chip

TESTDATA = os.path.join(manifest.BENCH_DIR, "testdata")


@pytest.fixture(scope="module")
def recorded_train():
    """Two steps of ``gpt2m_train_1chip`` on the v5e (PR 22), cut by
    ``testdata/record.py`` to ops of 150 us and more: names are the
    trace's own HLO text, times are the chip's."""
    from jax.profiler import ProfileData

    path = os.path.join(TESTDATA, "v5e_train_1chip.textproto")
    assert os.path.getsize(path) < 1 << 20
    with open(path) as f:
        return xplane.from_profile_data(ProfileData.from_text_proto(
            f.read()))


def test_recorded_trace_names_programs_and_kernels(recorded_train):
    t = recorded_train
    assert t.n_devices == 1 and len(t.ops[0]) == 466
    # the long HLO text is cut to the instruction's name and result type
    assert all(len(ev.name) < 80 and "=" not in ev.name and
               not ev.name.startswith("%") for ev in t.ops[0])
    steps = xplane.module_durations(t, "jit_local_step")
    assert steps[:2] == pytest.approx([0.190934515, 0.190930247])
    # 24 layers x 3 flash kernels x 2 steps; the fused CE's three
    n, secs = xplane.kernel_time(
        t, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    assert (n, secs) == (144, pytest.approx(0.088472999))
    n, secs = xplane.kernel_time(
        t, ("fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw"))
    assert (n, secs) == (6, pytest.approx(0.061748173))
    top = xplane.breakdown(t)["device_ops"]
    assert top[0][0] == "flash_bwd_dkv bf16[128,1024,64] (x48)"
    assert [name for name, _ in top].count(
        "jvp_fused_ce_fwd_ f32[8192,1] (x2)") == 1


def test_recorded_trace_busy_time_agrees_with_a_brute_force_count(
        recorded_train):
    t = recorded_train
    lo, hi = t.window
    # brute force on a 1 us grid: a cell is busy if any op covers it
    n = int(round((hi - lo) * 1e6))
    grid = bytearray(n)
    for ev in t.ops[0]:
        a = max(0, int(round((ev.start - lo) * 1e6)))
        b = min(n, int(round((ev.end - lo) * 1e6)))
        grid[a:b] = b"\x01" * (b - a)
    assert xplane.busy_s(t) == pytest.approx(sum(grid) * 1e-6, rel=2e-3)
    gaps = xplane.idle_gaps(t)
    assert sum(e - s for (s, e), _ in gaps) == pytest.approx(
        t.window_s - xplane.busy_s(t))
    # the cut keeps the host's own spans: the longest gaps are the
    # benchmark loop waiting (here: for the ops the cut dropped)
    assert {"bench.wait", "bench.dispatch"} >= {
        label for _, label in sorted(gaps, key=lambda g: g[0][0] - g[0][1])
        [:20]}


def test_recorded_four_chip_step_exposes_all_of_its_collective_time():
    """One step of ``gpt2m_train_dp4`` on the 2x2 v5e host (PR 22), cut
    to ops of 400 us and more plus EVERY collective op: XLA ran the 12
    combined all-reduces and 346 all-gathers of a step as synchronous
    ops, so all of their time is exposed — none is hidden."""
    from jax.profiler import ProfileData

    path = os.path.join(TESTDATA, "v5e_train_dp4.textproto")
    assert os.path.getsize(path) < 1 << 20
    with open(path) as f:
        t = xplane.from_profile_data(ProfileData.from_text_proto(f.read()))
    assert t.n_devices == 4
    assert xplane.module_durations(t, "jit_local_step", device=3)[0] == (
        pytest.approx(0.259489652))
    c = xplane.collective_seconds(t)
    assert [c[d]["events"] for d in range(4)] == [358] * 4
    assert [c[d]["exposed"] for d in range(4)] == pytest.approx(
        [0.037569, 0.037577, 0.037599, 0.037589], abs=1e-6)
    # no asynchronous collective span and no start/done pair: in flight
    # and exposed are the same time
    assert all(c[d]["total"] == pytest.approx(c[d]["exposed"])
               for d in range(4))
    names = {ev.name.split(".")[0] for ev in t.ops[0]
             if xplane.COLLECTIVE.search(ev.name)}
    assert names == {"all-gather", "all-reduce"}
    assert xplane.breakdown(t)["device_ops"][0][0] == (
        "all-reduce f32[1024000] (x11)")
