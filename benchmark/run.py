#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; the mix's file names
the runner (``harness/runners/<runner>.py``) that builds the system,
checks its outputs against the configuration's plain reference, warms
every shape, and measures for ``--seconds``.  The last line of stdout
is one JSON object — ``correct, attempted, failed, metrics, device``
(and ``breakdown`` with ``--trace 1``); the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``.  Earlier
lines are JSON notes (sample counts, medians, checks), each naming the
platform once it is known.

Without the accelerator, or with fewer chips than the cell asks for,
the command prints no result and exits non-zero; nothing ever runs on a
CPU under a device metric's name.  ``--rehearse`` is the same command
at the tiny sizes of the files' ``rehearsal`` entries on a pinned CPU,
for finding faults before chip time is spent: it says
``"platform": "cpu"`` and reports no device metric.
"""

from __future__ import annotations

import time

T0_WALL = time.time()   # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402

EXIT_NO_PROGRAM, EXIT_NO_CHIP = 3, 4


note = manifest.note


def read_layer_metrics(layer_entries, ctx, bench_dir) -> dict:
    """Each listed per-layer metric through its reader; a reader that
    finds nothing to read returns ``None`` and the metric is left out."""
    readers = manifest.layer_readers(bench_dir)
    out = {}
    for entry in layer_entries:
        reader = manifest.reader_for(readers, entry["name"])
        if reader is None:
            note(event="no_reader", metric=entry["name"])
            continue
        value = reader.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on a pinned CPU; debugging only, "
                         "reports no device metric")
    args = ap.parse_args(argv)

    bench_dir = os.path.join(root, "benchmark")
    if not os.path.isdir(os.path.join(root, "byteps_tpu")):
        print(f"no program to measure: {root}/byteps_tpu is missing",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    man = manifest.load_manifest(root)
    cell = manifest.find_cell(man, args.workload)
    config = manifest.effective(
        manifest.load_config(man, cell, root), args.rehearse)
    mix = manifest.effective(
        manifest.load_traffic(cell, bench_dir), args.rehearse)
    e2e, layer = manifest.cell_metrics(man, cell["name"])
    out_dir = os.path.join(bench_dir, "out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)

    job = types.SimpleNamespace(
        cell=cell, config=config, mix=mix, chips=int(cell["chips"]),
        seed=args.seed, trace=bool(args.trace), rehearse=args.rehearse,
        seconds=float(args.seconds if args.seconds is not None
                      else man["run_seconds"]),
        out_dir=out_dir, root=root, bench_dir=bench_dir, note=note)
    runner = manifest.load_module("harness/runners", mix["runner"],
                                  bench_dir)
    from benchmark.harness.device import NoChipError

    try:
        res = runner.run(job)
    except NoChipError as e:
        print(f"no chip, no result: {e}", file=sys.stderr)
        return EXIT_NO_CHIP

    device = res["device"]
    values = dict(res["values"])
    values["setup_s"] = res["window_start_wall"] - T0_WALL
    note(event="end_to_end", platform=device["platform"],
         values=values, attempted=res["attempted"], failed=res["failed"])
    line = {"correct": bool(res["correct"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    if args.trace:
        ctx = res["ctx"]
        ctx.cell, ctx.config, ctx.mix = cell, config, mix
        ctx.chips, ctx.device, ctx.values = job.chips, device, values
        ctx.note, ctx.rehearse = note, args.rehearse
        from benchmark.harness import peaks, xplane

        # a rehearsal has no chip, so no peaks and no device trace
        ctx.peaks = None if args.rehearse else peaks.peaks_for(
            device["kind"])
        if args.rehearse:
            ctx.trace = None
        line["metrics"] = read_layer_metrics(layer, ctx, bench_dir)
        if ctx.trace is not None:
            device = dict(device, busy_s=xplane.busy_s(ctx.trace),
                          window_s=ctx.trace.window_s)
            line["breakdown"] = xplane.breakdown(ctx.trace)
    else:
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]}
            for m in e2e if values.get(m["name"]) is not None}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0        # the verdict is the line's ``correct``


if __name__ == "__main__":
    sys.exit(main())
