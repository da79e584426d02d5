"""From the profiler's ``.xplane.pb`` to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX:
planes (one per device, one for the host), their lines, and events with
a start and a duration in nanoseconds.  This module turns that into the
quantities the per-layer readers ask for — device busy time and idle
gaps, time per op and per kernel, launches of a program, collective
time exposed or hidden — and into the ``breakdown`` of the result line.
The interval arithmetic is pure Python on ``(start, end)`` pairs so it
is tested on hand-made intervals; ``tests/benchmark`` also runs the
whole reduction on a small trace recorded on the v5e.

What a TPU trace looks like (jax 0.9.0 / libtpu 0.0.34, read by hand
in PR 22): planes ``/device:TPU:<n>`` with lines ``XLA Modules`` (one
event per program launch, named ``<jit name>(<fingerprint>)``),
``XLA Ops`` (one event per HLO instruction executed; the event's name
is the instruction's whole HLO text, ``%fusion.12 = f32[8,128]{...}
fusion(...)`` — a Pallas kernel appears under its ``name=``) and
``Async XLA Ops`` (one event per asynchronous op, from its ``-start``
to its ``-done``: how long a copy or a collective was in flight);
plane ``/host:CPU`` with one line per host thread, ``python3`` carrying
the ``TraceAnnotation`` spans and the others the runtime's own TraceMe
events.  Device and host events are on one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
LABEL_GAPS = 200            # idle gaps laid at the host's door, longest first
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast")


# ------------------------------------------------------ interval arithmetic


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(merged: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint (``union`` output)."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# ------------------------------------------------------------------- events

_INSTRUCTION = re.compile(r"^%?([^\s=(]+)")
_RESULT = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(text: str) -> str:
    """``fusion.12 f32[8,128]`` out of the HLO text the trace gives as
    an op's name: the instruction's name, which the per-layer readers
    match, and its (first) result type, which tells a reader of the
    breakdown what the op was."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text[:80]
    r = _RESULT.search(text)
    return m.group(1) + (" " + r.group(1) if r else "")


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds on the trace's clock
    end: float
    self_s: float = 0.0   # duration minus nested events on the same line

    @property
    def dur(self) -> float:
        return self.end - self.start


def _self_times(events: List[Event]) -> None:
    """Events of one line nest (a ``while`` spans its body's ops): an
    event's self time is its duration minus its direct children's."""
    events.sort(key=lambda ev: (ev.start, -ev.end))
    stack: List[Event] = []
    for ev in events:
        ev.self_s = ev.dur
        while stack and stack[-1].end <= ev.start:
            stack.pop()
        if stack and ev.end <= stack[-1].end:
            stack[-1].self_s -= ev.dur
        stack.append(ev)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # device ordinal -> XLA Ops
    modules: Dict[int, List[Event]]    # device ordinal -> XLA Modules
    host: Dict[str, List[Event]]       # host thread line -> events
    async_ops: Dict[int, List[Event]] = dataclasses.field(
        default_factory=dict)          # device ordinal -> Async XLA Ops
    window: Optional[Interval] = None
    totals: Optional[dict] = None      # op_totals(), computed once

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) if self.window else 0.0


def from_profile_data(pd) -> Trace:
    ops, modules, asyncs, host = {}, {}, {}, {}
    by_line = {OPS_LINE: ops, MODULES_LINE: modules, ASYNC_LINE: asyncs}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in by_line:
                    # a program's name is kept whole: its fingerprint
                    # tells one shape bucket of a function from another
                    name = (str if line.name == MODULES_LINE
                            else short_name)
                    evs = [Event(name(ev.name), ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events]
                    _self_times(evs)
                    by_line[line.name][int(m.group(1))] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [Event(ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in line.events if ev.duration_ns > 0]
                if evs:
                    host[line.name] = evs
    # a device that ran nothing in the window still has a plane; one
    # that has no op line at all is not part of the trace
    ops = {d: e for d, e in ops.items() if e}
    trace = Trace(ops=ops, modules=modules, host=host, async_ops=asyncs)
    trace.window = _window(trace)
    return trace


def _window(trace: Trace) -> Optional[Interval]:
    """The traced window: the host's ``bench.window`` span where the
    benchmark wrote one (it brackets the profiled region, idle ends
    included), else the span of the device's own events."""
    spans = [ev for evs in trace.host.values() for ev in evs
             if ev.name == WINDOW_SPAN]
    dev = [ev for evs in trace.ops.values() for ev in evs]
    if spans:
        w = (min(s.start for s in spans), max(s.end for s in spans))
        if not dev or any(overlap((ev.start, ev.end), w) for ev in dev):
            return w
    if dev:
        return (min(ev.start for ev in dev), max(ev.end for ev in dev))
    return None


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile_data(ProfileData.from_file(path))


# ---------------------------------------------------------------- reductions


def busy_intervals(trace: Trace, device: int) -> List[Interval]:
    w = trace.window
    return clip(union((ev.start, ev.end) for ev in trace.ops[device]),
                w[0], w[1])


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran on the device: the union of
    op intervals inside the window, averaged over the chips used."""
    if not trace.ops or not trace.window:
        return 0.0
    return sum(total(busy_intervals(trace, d))
               for d in trace.ops) / trace.n_devices


def op_totals(trace: Trace) -> Dict[str, Tuple[int, float]]:
    """``{op name: (events, self seconds)}`` averaged over chips."""
    if trace.totals is not None:
        return trace.totals
    acc: Dict[str, List[float]] = {}
    for evs in trace.ops.values():
        for ev in evs:
            a = acc.setdefault(ev.name, [0, 0.0])
            a[0] += 1
            a[1] += ev.self_s
    n = max(1, trace.n_devices)
    trace.totals = {k: (int(round(c / n)), s / n)
                    for k, (c, s) in acc.items()}
    return trace.totals


def kernel_time(trace: Trace, patterns: Iterable[str]
                ) -> Tuple[int, float]:
    """(events, seconds) of ops whose name contains one of ``patterns``
    — how a Pallas kernel is found by its ``name=`` after
    differentiation wrapped it (``jvp_<name>``...) — averaged over
    chips."""
    pats = tuple(patterns)
    n_ev, secs = 0, 0.0
    for name, (c, s) in op_totals(trace).items():
        if any(p in name for p in pats):
            n_ev += c
            secs += s
    return n_ev, secs


def module_launches(trace: Trace, pattern: str,
                    device: Optional[int] = None) -> Dict[str, List[float]]:
    """``{module name: device seconds of each launch}`` for the programs
    whose module name contains ``pattern`` (first device unless told
    otherwise).  The name ends in the program's fingerprint, so the
    shape buckets of one jitted function stay apart."""
    out: Dict[str, List[float]] = {}
    if trace.modules:
        d = min(trace.modules) if device is None else device
        for ev in trace.modules.get(d, ()):
            if pattern in ev.name:
                out.setdefault(ev.name, []).append(ev.dur)
    return out


def module_durations(trace: Trace, pattern: str,
                     device: Optional[int] = None) -> List[float]:
    """The same launches pooled over the programs that match."""
    return [d for durs in module_launches(trace, pattern, device).values()
            for d in durs]


def _is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name))


def collective_seconds(trace: Trace) -> Dict[int, Dict[str, float]]:
    """Per device: ``exposed`` — time inside collective ops during which
    no compute op runs on that device; ``total`` — the wall time
    collectives were in flight (the asynchronous line's spans where the
    trace has them, else a ``-start`` op paired with its ``-done``);
    ``events``."""
    out = {}
    for d, evs in trace.ops.items():
        coll = [ev for ev in evs if _is_collective(ev.name)]
        comp = union((ev.start, ev.end) for ev in evs
                     if not _is_collective(ev.name) and ev.self_s > 0
                     and ev.self_s >= 0.999 * ev.dur)
        exposed = total(subtract(
            union((ev.start, ev.end) for ev in coll), comp))
        spans = [(ev.start, ev.end)
                 for ev in trace.async_ops.get(d, ())
                 if _is_collective(ev.name)]
        open_starts = {}
        for ev in sorted(coll, key=lambda e: e.start):
            key = ev.name.split(" ")[0]
            if "-start" in key:
                open_starts[key.replace("-start", "-done")] = ev.start
            elif "-done" in key and key in open_starts:
                spans.append((open_starts.pop(key), ev.end))
            else:
                spans.append((ev.start, ev.end))
        out[d] = {"exposed": exposed, "total": total(union(spans)),
                  "events": len(coll)}
    return out


def idle_gaps(trace: Trace, device: Optional[int] = None
              ) -> List[Tuple[Interval, str]]:
    """Every gap between device ops inside the window on one device,
    the ``LABEL_GAPS`` longest labelled by what the host was doing: the
    host event that overlaps the gap most, a ``bench.*`` span (the
    benchmark's own) winning over the runtime's.  The rest — thousands
    of microsecond gaps between the ops of one program — go unlabelled
    as ``between ops``."""
    if not trace.ops or not trace.window:
        return []
    d = min(trace.ops) if device is None else device
    gaps = subtract([trace.window], busy_intervals(trace, d))
    host = sorted(((ev.start, ev.end, ev.name, line)
                   for line, evs in trace.host.items() for ev in evs
                   if ev.name != WINDOW_SPAN), key=lambda h: h[0])
    gaps.sort(key=lambda g: g[0] - g[1])
    out = [(g, "between ops") for g in gaps[LABEL_GAPS:]]
    for g in gaps[:LABEL_GAPS]:
        best, best_key = "host: unattributed", (False, 0.0)
        for s, e, name, line in host:
            if s >= g[1]:
                break
            ov = overlap((s, e), g)
            key = (name.startswith("bench."), ov)
            if ov > 0 and key > best_key:
                best, best_key = name, key
        out.append((g, best))
    return sorted(out)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time (self seconds, averaged over chips; the per-layer instances of
    one instruction — ``fusion.12``, ``fusion.13`` with one result type
    — summed under one name), and device-idle seconds inside the window
    by what the host was doing, longest first."""
    grouped: Dict[str, List[float]] = {}
    for name, (count, secs) in op_totals(trace).items():
        a = grouped.setdefault(re.sub(r"\.\d+", "", name), [0, 0.0])
        a[0] += count
        a[1] += secs
    by_label: Dict[str, List[float]] = {}
    for (s, e), label in idle_gaps(trace):
        a = by_label.setdefault(label, [0, 0.0])
        a[0] += 1
        a[1] += e - s

    def ranked(groups):
        rows = sorted(groups.items(), key=lambda kv: -kv[1][1])[:top]
        return [[f"{name} (x{int(c)})", s] for name, (c, s) in rows]

    return {"device_ops": ranked(grouped), "idle_gaps": ranked(by_label)}
