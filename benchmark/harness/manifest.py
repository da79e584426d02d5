"""BENCHMARK.json and the files it names.

The harness is driven by data: a cell names a configuration and a
traffic mix; the configuration's file names its builder and reference;
the mix's file names its runner; per-layer metrics are found by listing
``layer_metrics/``.  Adding a cell, a configuration, a mix or a metric
is adding files and entries — no file that is there needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def note(**fields) -> None:
    """One JSON object on a stdout line of its own: how every process
    of the benchmark says what it saw (the result is the LAST line)."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json (known: "
        f"{[c['name'] for c in manifest['workloads']]})")


def load_config(manifest: dict, cell: dict, root: str = ROOT) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == cell["config"]:
            return load_json(os.path.join(root, entry["file"]))
    raise ManifestError(f"cell {cell['name']!r} names configuration "
                        f"{cell['config']!r}, which BENCHMARK.json lacks")


def load_traffic(cell: dict, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "traffic",
                                  cell["traffic"] + ".json"))


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell_metrics(manifest: dict, cell_name: str):
    """(end_to_end, per_layer) entries this cell reports.  A per-layer
    metric is reported only where the metric it moves is."""
    e2e = [m for m in manifest["end_to_end"] if applies(m, cell_name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if applies(m, cell_name) and m["moves"] in names]
    return e2e, layer


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module — builders,
    references, runners and layer-metric readers are all found this
    way, by the name a data file gives."""
    path = os.path.join(bench_dir, *kind.split("/"), name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file {path}")
    # the path is part of the name: a test's temporary copy of the
    # benchmark must not be served this checkout's modules
    modname = f"_bench_{abs(hash(path)):x}_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def layer_readers(bench_dir: str = BENCH_DIR) -> dict:
    """``{SPEC name: module}`` for every file in ``layer_metrics/``."""
    out = {}
    d = os.path.join(bench_dir, "layer_metrics")
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".py") and not fn.startswith("_"):
            mod = load_module("layer_metrics", fn[:-3], bench_dir)
            out[mod.SPEC["name"]] = mod
    return out


def reader_for(readers: dict, metric_name: str):
    """The reader whose SPEC name is ``metric_name`` or its longest
    dotted prefix: ``device.idle_share.itl`` is read by the
    ``device.idle_share`` reader.  One quantity can so be listed once
    per end-to-end metric it moves, without a second reader."""
    name = metric_name
    while name:
        if name in readers:
            return readers[name]
        name = name.rpartition(".")[0]
    return None


def effective(config: dict, rehearse: bool) -> dict:
    """The configuration as it is run: itself, or — in a rehearsal —
    with the tiny sizes of its ``rehearsal`` entry laid over it (nested
    groups merged key by key)."""
    if not rehearse:
        return config
    out = dict(config)
    for k, v in config.get("rehearsal", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and k in out else v
    return out
