"""The serving engine's own account of a tick, read back from the trace.

Since ISSUE 36 the engine names what its tick thread and ``submit()``
are doing with host spans on the device trace's clock (the ``SPAN_*``
table of ``byteps_tpu/common/tracing.py``): ``bps.tick`` with children
``/admit``, ``/prefill`` (``/build``, ``/launch``, ``/readback``),
``/decode`` and ``/verify`` (``/blocks``, ``/build``, ``/launch``,
``/readback``, ``/emit``), ``/account``; ``bps.tick/idle_wait`` between
ticks of an idle engine; ``bps.submit`` (``/lock_wait``, ``/enqueue``)
on the submitting thread.  Its serve programs run the model under
``bps.model`` and the token pick under ``bps.serve/select``.  This
module turns both into the numbers the ``tick.*``,
``device.idle_attributed_share`` and ``serve_prog.decode_outside_model_ms``
readers report, and into two notes every traced serve run prints:

``tick_phases``   ticks seen; milliseconds a decode tick by phase (median
                  and mean); device-idle seconds of the window under each
                  span, ``idle_wait`` and ``unattributed`` apart
``decode_scopes`` milliseconds a ``jit(decode_fn)`` launch by scope: the
                  parts of the model under ``bps.model``,
                  ``bps.serve/select``, ``unscoped``

Why the host plane is read from the file again and not from
``ctx.trace.host``: ``xplane.from_profile_data`` keys a host line by its
NAME, and every Python thread's line is named after the process
(``python3``) — of the main thread (``bench.window``), the tick thread
and each connection thread only the last one read survives there.
Here a line is a line.  ``xplane.idle_gaps`` cannot serve either: it
prefers ``bench.*`` and labels by the one event that overlaps a gap
most; here a gap's seconds go to the innermost ``bps.*`` span over each
part of it.

On a program from before the spans (the parent of ISSUE 36) every
function here finds nothing and the readers return ``None``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from benchmark.harness import scopes, stats, xplane

PREFIX = "bps."
TICK = "bps.tick"
DECODE = TICK + "/decode"
READBACK = "/readback"
SELECT_SCOPE, ACCEPT_SCOPE = "bps.serve/select", "bps.serve/accept"
DECODE_PROGRAM = "decode_fn"
_INSTANCE = re.compile(r"\.\d+")


@dataclasses.dataclass
class Span(xplane.Event):
    children: List["Span"] = dataclasses.field(default_factory=list)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


# ---------------------------------------------------------------- the spans


def host_lines(pd) -> List[List[Span]]:
    """Per host thread that carries any, its ``bps.*`` spans as a forest
    (a span's children are the spans inside it on the same thread),
    roots in start order.  Self time = duration less children."""
    out = []
    for plane in pd.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [Span(ev.name, ev.start_ns * 1e-9,
                          (ev.start_ns + ev.duration_ns) * 1e-9)
                     for ev in line.events
                     if ev.name.startswith(PREFIX) and ev.duration_ns > 0]
            if spans:
                out.append(_forest(spans))
    return out


def _forest(spans: List[Span]) -> List[Span]:
    spans.sort(key=lambda s: (s.start, -s.end))
    roots, stack = [], []
    for s in spans:
        s.self_s = s.dur
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack and s.end <= stack[-1].end:
            stack[-1].children.append(s)
            stack[-1].self_s -= s.dur
        else:
            stack.clear()
            roots.append(s)
        stack.append(s)
    return roots


def ticks(lines: List[List[Span]]) -> List[Span]:
    return [s for roots in lines for s in roots if s.name == TICK]


def phase(name: str) -> str:
    """``decode/build`` out of ``bps.tick/decode/build``; ``tick`` for
    the tick's own span (its self time: what no child covers)."""
    return name[len(TICK) + 1:] or "tick"


def host_seconds(tick: Span) -> float:
    """A tick's span less its ``readback`` children: what the host
    added to the device's time (a readback is the host waiting for the
    program it launched)."""
    return tick.dur - sum(s.dur for s in tick.walk()
                          if s.name.endswith(READBACK))


def decode_ticks(lines: List[List[Span]]) -> List[Span]:
    return [t for t in ticks(lines)
            if any(c.name == DECODE for c in t.children)]


def phase_ms(tick_spans: List[Span]) -> Dict[str, Dict[str, float]]:
    """``{phase: {median, mean}}`` of self milliseconds a tick (a phase
    a tick does not have counts as 0 there)."""
    per_tick = []
    for t in tick_spans:
        acc: Dict[str, float] = {}
        for s in t.walk():
            acc[phase(s.name)] = acc.get(phase(s.name), 0.0) + s.self_s
        per_tick.append(acc)
    names = sorted({p for acc in per_tick for p in acc})
    out = {}
    for p in names:
        vals = [1e3 * acc.get(p, 0.0) for acc in per_tick]
        out[p] = {"median": stats.median(vals),
                  "mean": sum(vals) / len(vals)}
    return out


# ---------------------------------------------------- idle gaps under spans


def _leaves(roots: List[Span]) -> List[Tuple[float, float, str]]:
    """One thread's time as disjoint ``(start, end, span name)`` pieces:
    each instant under the innermost span that covers it."""
    out = []
    for root in roots:
        for s in root.walk():
            own = xplane.subtract(
                [(s.start, s.end)],
                xplane.union((c.start, c.end) for c in s.children))
            out.extend((a, b, s.name) for a, b in own)
    out.sort()
    return out


def _intersect(a, b):
    return xplane.subtract(a, xplane.subtract(a, b))


def idle_by_span(trace: xplane.Trace, lines: List[List[Span]],
                 device: Optional[int] = None) -> Optional[Dict[str, float]]:
    """Device-idle seconds by the ``bps.*`` span they lie under: first
    the tick thread's innermost span, then — for what no tick span covers
    — any ``bps.submit`` span of another thread (``submit``), else
    ``unattributed``.  Counted between the tick thread's first span's
    start and its last span's end inside the window: a span in progress
    when the profiler starts or stops is not in the trace at all, and a
    tick with a 512-token chunk lasts 0.8 s of a 4 s window.  ``None``
    without device ops, a window or a span on the tick thread."""
    tick_lines, other = [], []
    for roots in lines:
        (tick_lines if any(s.name.startswith(TICK) for s in roots)
         else other).append(roots)
    if not trace.ops or not trace.window or not tick_lines:
        return None
    d = min(trace.ops) if device is None else device
    gaps = xplane.clip(
        xplane.subtract([trace.window], xplane.busy_intervals(trace, d)),
        min(roots[0].start for roots in tick_lines),
        max(roots[-1].end for roots in tick_lines))
    out: Dict[str, float] = {}
    covered = []
    for roots in tick_lines:
        pieces = _leaves(roots)
        covered.extend((a, b) for a, b, _ in pieces)
        i = 0
        for g0, g1 in gaps:                    # both sorted and disjoint
            while i < len(pieces) and pieces[i][1] <= g0:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < g1:
                ov = xplane.overlap((g0, g1), pieces[j][:2])
                if ov > 0:
                    label = phase(pieces[j][2])
                    out[label] = out.get(label, 0.0) + ov
                j += 1
    rest = xplane.subtract(gaps, xplane.union(covered))
    submit = _intersect(rest, xplane.union(
        (s.start, s.end) for roots in other for s in roots))
    if submit:
        out["submit"] = xplane.total(submit)
    out["unattributed"] = xplane.total(rest) - xplane.total(submit)
    return out


def attributed_share(idle: Dict[str, float]) -> Optional[float]:
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * (1.0 - idle["unattributed"] / total)


# ------------------------------------------------ the decode program's scopes


@dataclasses.dataclass
class _Op(xplane.Event):
    text: str = ""                # the instruction's whole HLO text


def scope_label(op_name: str, kernel: bool) -> str:
    scope = scopes.parse(op_name)
    if scope.stage == "model":
        return "model." + ("kernel" if kernel
                           else scopes.model_part(scope.module))
    for name, label in ((SELECT_SCOPE, "select"), (ACCEPT_SCOPE, "accept")):
        if name in op_name:
            return label
    return "unscoped"


def decode_scopes(data: bytes, program: str = DECODE_PROGRAM
                  ) -> Optional[dict]:
    """Device self seconds a launch of ``jit(<program>)`` by scope, on
    the first device of a serialized trace; ``None`` without a launch or
    without a single op under one of the serve scopes (a program from
    before them).

    An op's ``op_name`` is looked up by its whole HLO text in the
    metadata table (``scopes.metadata_stats``).  ``scopes.from_serialized``
    is not asked: it resolves scopes by instruction NAME over a whole
    plane, and a serve trace holds several programs (decode, a chunk
    program a bucket) whose ``%fusion.12`` are different instructions.
    The compiler's own copies stay ``unscoped`` here."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m:
            planes[int(m.group(1))] = plane
    if not planes:
        return None
    plane = planes[min(planes)]
    table = scopes.metadata_stats(data).get(plane.name, {})
    spans, ops = [], []
    for line in plane.lines:
        if line.name == xplane.MODULES_LINE:
            spans = sorted((ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events if program in ev.name)
        elif line.name == xplane.OPS_LINE:
            for ev in line.events:
                ops.append(_Op(xplane.short_name(ev.name),
                               ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9,
                               text=ev.name))
    if not spans or not ops:
        return None
    xplane._self_times(ops)                    # sorts by start, too
    by_scope: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    i = 0
    for op in ops:
        while i < len(spans) and spans[i][1] <= op.start:
            i += 1
        if i == len(spans) or spans[i][0] > op.start or not op.self_s:
            continue
        op_name = table.get(op.text, {}).get(scopes.OP_NAME_KEY)
        label = scope_label(op_name if isinstance(op_name, str) else "",
                            scopes.KERNEL in op.text)
        by_scope[label] = by_scope.get(label, 0.0) + op.self_s
        # the instances of one instruction (a layer each) under one name
        key = f"{_INSTANCE.sub('', op.name)} [{label}]"
        by_op[key] = by_op.get(key, 0.0) + op.self_s
    if not any(k != "unscoped" for k in by_scope):
        return None
    n = len(spans)
    return {"launches": n,
            "per_launch_s": {k: v / n for k, v in sorted(by_scope.items())},
            "outside_model_s": sum(v for k, v in by_scope.items()
                                   if not k.startswith("model")) / n,
            "largest_ops_s": {k: v / n for k, v in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:8]}}


# ------------------------------------------------- the readers' entry point


def _xspace(ctx) -> Optional[bytes]:
    """The traced run's serialized trace, read once and kept on ``ctx``
    (a test sets ``ctx.xspace`` itself)."""
    if not hasattr(ctx, "xspace"):
        path = xplane.find_xplane(scopes.trace_dir(ctx.cell["name"]))
        ctx.xspace = None
        if path is not None:
            with open(path, "rb") as f:
                ctx.xspace = f.read()
    return ctx.xspace


def for_run(ctx) -> Optional[dict]:
    """The host spans of the traced run behind ``ctx``, read once and
    kept on it; prints ``tick_phases`` the first time.  ``None`` where
    there is nothing to read: no trace, or a program without spans."""
    if hasattr(ctx, "host_spans"):
        return ctx.host_spans
    ctx.host_spans = None
    if getattr(ctx, "trace", None) is None or _xspace(ctx) is None:
        return None
    from jax.profiler import ProfileData

    lines = host_lines(ProfileData.from_serialized_xspace(ctx.xspace))
    if not lines:
        return None
    dec = decode_ticks(lines)
    idle = idle_by_span(ctx.trace, lines)
    res = ctx.host_spans = {
        "ticks": len(ticks(lines)), "decode_ticks": len(dec),
        "host_ms_per_decode_tick": [1e3 * host_seconds(t) for t in dec],
        "decode_tick_phase_ms": phase_ms(dec), "idle_s": idle}
    ctx.note(event="tick_phases", threads_with_spans=len(lines),
             ticks=res["ticks"], decode_ticks=res["decode_ticks"],
             decode_tick_ms_by_phase=res["decode_tick_phase_ms"],
             host_ms_per_decode_tick_median=(
                 stats.median(res["host_ms_per_decode_tick"])
                 if dec else None),
             idle_s_by_span=idle,
             idle_attributed_share_pct=(attributed_share(idle)
                                        if idle else None))
    return res


def decode_scopes_for_run(ctx) -> Optional[dict]:
    """``decode_scopes`` of the traced run behind ``ctx``, once; prints
    the ``decode_scopes`` note."""
    if hasattr(ctx, "decode_scopes"):
        return ctx.decode_scopes
    ctx.decode_scopes = None
    if getattr(ctx, "trace", None) is None or _xspace(ctx) is None:
        return None
    res = ctx.decode_scopes = decode_scopes(ctx.xspace)
    if res is not None:
        ctx.note(event="decode_scopes", program=DECODE_PROGRAM,
                 launches=res["launches"],
                 ms_per_launch={k: 1e3 * v
                                for k, v in res["per_launch_s"].items()},
                 outside_model_ms=1e3 * res["outside_model_s"],
                 largest_ops_ms={k: 1e3 * v
                                 for k, v in res["largest_ops_s"].items()})
    return res
