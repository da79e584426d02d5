"""BENCHMARK.json against the contract, and the requirement that the
harness is driven by data: a new configuration, mix, cell, runner and
per-layer metric run after ADDING files and entries, with no edit to a
file that is there."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json
import re
import shutil
import subprocess

import pytest

from benchmark.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEYS = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*)_size|_dim$|_rank$|n_embd"
    r"|n_inner|d_model|d_ff|head_size|expansion|experts_per_tok", re.I)


def with_planned(man):
    """BENCHMARK.json with the entries of ``planned_cells.json`` added,
    as the PR that opens those cells will add them.  A planned metric
    has no bound until it is measured: here it gets the widest the
    contract allows."""
    planned = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "planned_cells.json"))
    out = dict(man)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        out[key] = man[key] + [dict(e) for e in planned[key]]
    for m in out["end_to_end"]:
        if m["bound"] is None:
            m["bound"] = 0.1
    return out


@pytest.fixture(scope="module", params=["accepted", "with_planned"])
def man(request):
    accepted = manifest.load_manifest()
    return accepted if request.param == "accepted" else with_planned(
        accepted)


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(
        manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(man["run_seconds"], int)
    assert 1 <= man["run_seconds"] <= 51
    assert 1 <= len(man["paths"]) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p
        for p in man["paths"])
    assert len(man["command"]) <= 32
    # the command names no file of the repo outside `paths`
    assert any(man["command"][1].startswith(p + "/") for p in man["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


def test_configs_are_used_sourced_and_cut_only_in_depth(man):
    used = {c["config"] for c in man["workloads"]}
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for c in man["configs"]:
        assert c["name"] in used and len(c["why"]) <= 200
        assert c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        body = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
        for key in ("assumed", "deployment", "builder", "reference"):
            assert key in body, (c["name"], key)
        assert not [k for k in c["reduced"] if WIDTH_KEYS.search(k)]
        for k in c["reduced"]:               # what it was, and why
            assert k in body["reduced_from"] and body["reduced_why"]
        manifest.load_module("builders", body["builder"])
        manifest.load_module("reference", body["reference"])


def test_cells_pair_once_and_one_in_four_may_take_four_chips(man):
    cells = man["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    four = [c for c in cells if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in cells)
    assert len(four) == 1 <= max(1, len(cells) // 4)
    for c in cells:
        assert len(c["why"]) <= 200
        mix = manifest.load_traffic(c)
        manifest.load_module("harness/runners", mix["runner"])


def test_metrics_are_bounded_sourced_and_every_cell_reports(man):
    e2e_names = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e_names and 1 <= len(man["end_to_end"]) <= 16
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    setup = next(m for m in man["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and "workloads" not in setup
    readers = manifest.layer_readers()
    cells = {c["name"] for c in man["workloads"]}
    assert 1 <= len(man["per_layer"]) <= 128
    for m in man["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e_names
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        reader = manifest.reader_for(readers, m["name"])
        assert reader is not None, m["name"]
        assert reader.SPEC["unit"] == m["unit"]
        assert reader.SPEC["source"] == m["source"]
        assert reader.SPEC["layer"] == m["layer"]
        assert LAYER.match(m["layer"]), m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in cells:
        e2e, layer = manifest.cell_metrics(man, c)
        assert len(e2e) >= 2 and len(layer) >= 1, c
    # a longer dotted name falls back to its reader; nothing else does
    assert manifest.reader_for(readers, "device.idle_share.x.y") is (
        readers["device.idle_share"])
    assert manifest.reader_for(readers, "device") is None


# --------------------------------------------------- add files, edit nothing

TRACE = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 4000000 } } }
"""

RUNNER = '''
import types
from jax.profiler import ProfileData
from benchmark.harness import manifest, xplane

def run(job):
    builder = manifest.load_module("builders", job.config["builder"],
                                   job.bench_dir)
    trace = xplane.from_profile_data(ProfileData.from_text_proto(
        job.mix["canned_trace"])) if job.trace else None
    return {"correct": True, "attempted": job.mix["work"], "failed": 0,
            "window_start_wall": __import__("time").time(),
            "values": {"throwaway_rate": float(job.seed + job.seconds)},
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": job.chips, "memory_peak_bytes": 1},
            "ctx": types.SimpleNamespace(trace=trace, train=None,
                                         serve=None,
                                         dims=builder.dims(job.config))}
'''


def add_throwaway(tmp):
    """A configuration, a mix with a runner of its own, a cell and a
    per-layer metric — all NEW files, plus entries in BENCHMARK.json."""
    bench = os.path.join(tmp, "benchmark")

    def write(rel, text):
        with open(os.path.join(bench, rel), "x") as f:   # "x": must be new
            f.write(text)

    write("configs/throwaway.json", json.dumps(
        {"source": "https://example.org/throwaway", "layers": 3,
         "reduced": [], "assumed": {}, "deployment": "none",
         "builder": "throwaway", "reference": "throwaway"}))
    write("builders/throwaway.py",
          "def dims(cfg):\n    return {'layers': cfg['layers']}\n")
    write("reference/throwaway.py", "")
    write("traffic/throwaway_mix.json", json.dumps(
        {"runner": "throwaway_runner", "kind": "canned", "work": 17,
         "canned_trace": TRACE}))
    write("harness/runners/throwaway_runner.py", RUNNER)
    write("layer_metrics/throwaway_layers.py",
          "SPEC = {'name': 'throwaway.layers', 'unit': 'layers',\n"
          "        'layer': 'model', 'source': 'program_counter'}\n\n"
          "def read(ctx):\n    return ctx.dims['layers']\n")
    path = os.path.join(tmp, "BENCHMARK.json")
    man = manifest.load_json(path)
    man["configs"].append({"name": "throwaway", "reduced": [], "why": "t",
                           "source": "https://example.org/throwaway",
                           "file": "benchmark/configs/throwaway.json"})
    man["workloads"].append({"name": "throwaway_cell", "chips": 1,
                             "config": "throwaway", "why": "t",
                             "traffic": "throwaway_mix"})
    man["end_to_end"].append({"name": "throwaway_rate", "unit": "1/s",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock",
                              "workloads": ["throwaway_cell"]})
    for name in ("throwaway.layers", "device.idle_share.throwaway"):
        man["per_layer"].append(
            {"name": name, "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "device",
             "moves": "throwaway_rate", "workloads": ["throwaway_cell"]})
    with open(path, "w") as f:
        json.dump(man, f)


@pytest.fixture()
def copy(tmp_path):
    tmp = str(tmp_path)
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(with_planned(manifest.load_manifest()), f)
    os.symlink(os.path.join(manifest.ROOT, "byteps_tpu"),
               os.path.join(tmp, "byteps_tpu"))
    before = {}
    for d, _, files in os.walk(tmp):
        for fn in files:
            p = os.path.join(d, fn)
            if fn != "BENCHMARK.json" and os.path.isfile(p):
                with open(p, "rb") as f:
                    before[p] = f.read()
    return tmp, before


def last_line(text):
    return json.loads(text.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_added_files_run_and_the_last_line_has_exactly_its_keys(
        copy, capsys, trace):
    from benchmark import run as bench_run

    tmp, before = copy
    add_throwaway(tmp)
    rc = bench_run.main(["--workload", "throwaway_cell", "--seed", "5",
                         "--seconds", "2", "--trace", str(trace)],
                        root=tmp)
    line = last_line(capsys.readouterr().out)
    assert rc == 0
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (want | {"breakdown"} if trace else want)
    assert (line["correct"], line["attempted"], line["failed"]) == (
        True, 17, 0)
    if trace:
        # the new reader, and an old reader under a new dotted name
        assert line["metrics"] == {
            "throwaway.layers": {"value": 3.0, "unit": "%"},
            "device.idle_share.throwaway": {
                "value": pytest.approx(12.5), "unit": "%"}}
        assert line["device"]["busy_s"] == pytest.approx(7e-6)
        assert line["device"]["window_s"] == pytest.approx(8e-6)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["breakdown"]["device_ops"] == [
            ["fusion (x2)", pytest.approx(7e-6)]]
    else:
        assert set(line["metrics"]) == {"throwaway_rate", "setup_s"}
        assert line["metrics"]["throwaway_rate"] == {"value": 7.0,
                                                     "unit": "1/s"}
        assert line["metrics"]["setup_s"]["value"] > 0
    # no file that was there was edited
    for p, content in before.items():
        with open(p, "rb") as f:
            assert f.read() == content, p


def test_no_result_without_the_program_or_without_a_chip(copy):
    """In a directory that holds only BENCHMARK.json and `paths`, and on
    a machine without the accelerator, the command prints no result and
    exits non-zero — nothing falls back to the CPU, in the train
    runner's process or in the serve runner's child."""
    tmp, _ = copy
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}

    def run(root, cell):
        return subprocess.run(
            [sys.executable, os.path.join(root, "benchmark", "run.py"),
             "--workload", cell, "--seed", "0", "--seconds", "1",
             "--trace", "0"], capture_output=True, text=True, env=env,
            timeout=120, cwd=root)

    for cell in ("gpt2m_train_1chip", "mistral7b_chat_steady"):
        r = run(tmp, cell)
        assert r.returncode != 0, r.stdout
        assert '"correct"' not in r.stdout
        assert "no chip, no result" in r.stderr
    os.unlink(os.path.join(tmp, "byteps_tpu"))
    r = run(tmp, "gpt2m_train_1chip")
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no program to measure" in r.stderr


@pytest.mark.slow
def test_a_real_runner_serves_an_added_configuration_and_mix(copy):
    """The same, through the real train runner at a tiny size (slow:
    it compiles a model; the stubbed twin above runs in tier-1)."""
    tmp, _ = copy
    bench = os.path.join(tmp, "benchmark")
    real = manifest.load_json(os.path.join(
        bench, "configs", "gpt2-medium.json"))
    tiny = {**real, **real["rehearsal"], "n_layer": 1}
    with open(os.path.join(bench, "configs", "gpt2-tiny.json"), "x") as f:
        json.dump(tiny, f)
    mix = manifest.load_json(os.path.join(
        bench, "traffic", "lm_b8_t1024.json"))
    with open(os.path.join(bench, "traffic", "lm_tiny.json"), "x") as f:
        json.dump({**mix, **mix["rehearsal"]}, f)
    path = os.path.join(tmp, "BENCHMARK.json")
    man = manifest.load_json(path)
    man["configs"].append({"name": "gpt2-tiny", "reduced": [], "why": "t",
                           "source": real["source"],
                           "file": "benchmark/configs/gpt2-tiny.json"})
    man["workloads"].append({"name": "tiny_cell", "chips": 1, "why": "t",
                             "config": "gpt2-tiny", "traffic": "lm_tiny"})
    man["end_to_end"][0]["workloads"].append("tiny_cell")
    with open(path, "w") as f:
        json.dump(man, f)
    r = subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), "--workload",
         "tiny_cell", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=tmp)
    assert r.returncode == 0, r.stderr[-2000:]
    line = last_line(r.stdout)
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.slow
@pytest.mark.parametrize("cell,trace,metrics", [
    ("mistral7b_chat_steady", 0,
     {"serve_ttft_p50_ms", "serve_ttft_p90_ms", "serve_itl_p50_ms",
      "serve_itl_p99_ms", "setup_s"}),
    ("mistral7b_longdoc_batch", 1,
     {"sched.tokens_per_decode_tick.tput", "kv_pool.live_share.tput"})])
def test_the_planned_serve_cells_run_once_their_entries_are_added(
        copy, cell, trace, metrics):
    """The serve runner, child and load generator at the rehearsal's
    tiny size (slow: three processes and an engine's compiles).  The
    probes cross chunk and block boundaries; a traced rehearsal reports
    the counters and no device metric."""
    tmp, _ = copy
    r = subprocess.run(
        [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "3", "--trace",
         str(trace), "--rehearse"], capture_output=True, text=True,
        timeout=600, cwd=tmp)
    assert r.returncode == 0, r.stderr[-2000:]
    line = last_line(r.stdout)
    assert line["correct"] and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == metrics
    ready = next(json.loads(x) for x in r.stdout.splitlines()
                 if '"event": "ready"' in x)
    assert set(ready["probe"]["max_logit_gap_by_prompt_len"]) == {
        "20", "75"}
