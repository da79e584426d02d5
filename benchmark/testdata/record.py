#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a small text proto for the
tests (``jax.profiler.ProfileData.from_text_proto`` reads it back).

    python3 benchmark/testdata/record.py <in.xplane.pb> <out.textproto> \
        --steps 2 --min-us 150

Keeps, of the first ``--steps`` launches of the longest program: every
device op of at least ``--min-us`` microseconds, every collective op
whatever its length, the program launches themselves, the asynchronous
collective spans, and the host's ``bench.*`` spans.  Names are the
trace's own (HLO text, cut to 160 characters).  This is how
``v5e_*.textproto`` in this directory were made from PR 22's chip runs;
the numbers the tests pin were read off the reduction at that time and
checked against the full trace's.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import xplane  # noqa: E402


def esc(s: str) -> str:
    return s[:160].replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", " ")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--min-us", type=float, default=150.0)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(args.src)
    planes = {p.name: p for p in pd.planes}
    dev0 = next(p for n, p in planes.items()
                if xplane.DEVICE_PLANE.match(n))
    mods = sorted((ev for ln in dev0.lines
                   if ln.name == xplane.MODULES_LINE for ev in ln.events),
                  key=lambda ev: ev.start_ns)
    longest = max(mods, key=lambda ev: ev.duration_ns).name
    picked = [ev for ev in mods if ev.name == longest][:args.steps]
    lo = picked[0].start_ns - 20_000
    hi = picked[-1].start_ns + picked[-1].duration_ns + 20_000
    out, pid = [], 0
    for name, plane in planes.items():
        device = bool(xplane.DEVICE_PLANE.match(name))
        if not device and name != xplane.HOST_PLANE:
            continue
        lines = []
        for ln in plane.lines:
            if device and ln.name not in (
                    xplane.OPS_LINE, xplane.MODULES_LINE,
                    xplane.ASYNC_LINE):
                continue
            evs = []
            for ev in ln.events:
                if not lo <= ev.start_ns <= hi:
                    continue
                coll = xplane.COLLECTIVE.search(ev.name[:200]) is not None
                if device:
                    keep = (ln.name == xplane.MODULES_LINE or coll
                            or (ln.name == xplane.OPS_LINE and
                                ev.duration_ns >= args.min_us * 1e3))
                else:
                    keep = ev.name.startswith("bench.")
                if keep:
                    evs.append(ev)
            if evs:
                lines.append((ln.name, evs))
        if not lines:
            continue
        pid += 1
        names = {}
        for _, evs in lines:
            for ev in evs:
                names.setdefault(ev.name, len(names) + 1)
        out.append(f'planes {{ id: {pid} name: "{name}"')
        for n, i in names.items():
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{esc(n)}" }} }}')
        for k, (lname, evs) in enumerate(lines):
            out.append(f'  lines {{ id: {k + 1} name: "{lname}" '
                       f'timestamp_ns: 0')
            for ev in evs:
                out.append(
                    f'    events {{ metadata_id: {names[ev.name]} '
                    f'offset_ps: {int(round((ev.start_ns - lo) * 1e3))} '
                    f'duration_ps: {int(round(ev.duration_ns * 1e3))} }}')
            out.append("  }")
        out.append("}")
    with open(args.dst, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"{args.dst}: {os.path.getsize(args.dst)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
