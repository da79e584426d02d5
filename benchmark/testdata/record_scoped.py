#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a small text proto that KEEPS
each instruction's ``tf_op`` stat (``record.py`` drops every stat), for
the tests of ``harness/scopes.py``.

    python3 benchmark/testdata/record_scoped.py <in.xplane.pb> \
        <out.textproto> --steps 1 --min-us 400 --planes 2

Keeps, of the first ``--steps`` launches of the longest program, on every
device plane (or the first ``--planes`` of them): the launches themselves, every collective op whatever its
length, and every other device op of at least ``--min-us`` microseconds.
Names are the trace's own HLO text cut to 160 characters
(the operands that the cut drops are lost to scope inheritance; a
collective with a tuple result keeps 1000, so a combined one still
shows its buffers); the
``tf_op`` of an instruction goes to its metadata as a string, where
libtpu keeps it.  This is how ``v5e_train_dp4_scoped.textproto`` was
made from PR 25's chip run; the numbers the tests pin were read off the
reduction at that time.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import scopes, xplane  # noqa: E402

TF_OP_ID = 1
NAME_CHARS = 160       # as record.py cuts; a combined collective keeps 1000


def esc(s: str, limit: int) -> str:
    return s[:limit].replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", " ")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--min-us", type=float, default=400.0)
    ap.add_argument("--planes", type=int, default=0,
                    help="keep only the first N device planes (0 = all)")
    args = ap.parse_args()
    from jax.profiler import ProfileData

    with open(args.src, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    meta = scopes.metadata_stats(data)
    planes = {p.name: p for p in pd.planes
              if xplane.DEVICE_PLANE.match(p.name)}
    if args.planes:
        planes = dict(sorted(planes.items())[:args.planes])
    picked = {}                 # plane name -> its first launches
    for name, plane in planes.items():
        mods = sorted((ev for ln in plane.lines
                       if ln.name == xplane.MODULES_LINE
                       for ev in ln.events), key=lambda ev: ev.start_ns)
        longest = max(mods, key=lambda ev: ev.duration_ns).name
        picked[name] = [ev for ev in mods
                        if ev.name == longest][:args.steps]
    lo = min(evs[0].start_ns for evs in picked.values()) - 20_000
    out = []
    for pid, (name, plane) in enumerate(sorted(planes.items()), 1):
        first, last = picked[name][0], picked[name][-1]
        end = last.start_ns + last.duration_ns
        lines = []
        for ln in plane.lines:
            if ln.name == xplane.MODULES_LINE:
                evs = picked[name]
            elif ln.name == xplane.OPS_LINE:
                evs = [ev for ev in ln.events
                       if first.start_ns <= ev.start_ns < end and (
                           xplane.COLLECTIVE.search(ev.name[:200])
                           or ev.duration_ns >= args.min_us * 1e3)]
            else:
                continue
            if evs:
                lines.append((ln.name, evs))
        ids = {}
        for _, evs in lines:
            for ev in evs:
                ids.setdefault(ev.name, len(ids) + 1)
        out.append(f'planes {{ id: {pid} name: "{name}"')
        out.append(f'  stat_metadata {{ key: {TF_OP_ID} value {{ id: '
                   f'{TF_OP_ID} name: "{scopes.OP_NAME_KEY}" }} }}')
        table = meta.get(name, {})
        for text, i in ids.items():
            op_name = table.get(text, {}).get(scopes.OP_NAME_KEY)
            stat = ""
            if isinstance(op_name, str):
                stat = (f' stats {{ metadata_id: {TF_OP_ID} str_value: '
                        f'"{esc(op_name, 400)}" }}')
            combined = (text.partition(" = ")[2].startswith("(")
                        and xplane.COLLECTIVE.search(text[:200]))
            chars = 1000 if combined else NAME_CHARS
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{esc(text, chars)}"{stat} }} }}')
        for k, (lname, evs) in enumerate(lines):
            out.append(f'  lines {{ id: {k + 1} name: "{lname}" '
                       f'timestamp_ns: 0')
            for ev in evs:
                out.append(
                    f'    events {{ metadata_id: {ids[ev.name]} '
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
