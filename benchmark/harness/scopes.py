"""The program's own stage names, read back from the device trace.

The train step wraps its stages in ``jax.named_scope`` (the table in
``byteps_tpu/common/tracing.py``: ``bps.model``, ``bps.head``,
``bps.push_pull/pack/b<iii>``, ``.../reduce/b<iii>``, ``.../unpack``,
``bps.optimizer``, ``bps.step_metrics``); Flax adds a scope per module
call.  A scope is HLO metadata: it reaches the trace as the ``op_name``
of every instruction, e.g.

    jit(local_step)/shard_map/transpose(jvp(bps.model))/Transformer.hidden/block_3/mlp/up/dot_general

``harness/xplane.py`` keeps an op's instruction name and drops the rest.
This module reads the same ``.xplane.pb`` once more and gives every
``XLA Ops`` event its scope: the stage, the bucket, the Flax module path
and forward or backward (``transpose(`` marks the backward side).

Where the trace keeps ``op_name`` (libtpu 0.0.34, read by hand in PR
25): NOT among the event's own stats, which is all that
``jax.profiler.ProfileData`` hands out, but among the stats of the
event's *metadata* (one entry per HLO instruction), under ``tf_op``
(XProf's framework-op key; the value ends in ``:``).  ``metadata_stats``
therefore walks the file's protobuf wire format for the two metadata
tables of each plane and nothing else; the events still come from
``ProfileData``.

The compiler's own data movement — layout copies, ``copy-start`` /
``copy-done`` and ``slice-start`` / ``slice-done`` into the fast memory
space — names no traced operation (no ``tf_op``, or a parameter's
name).  Such an instruction takes the scope of the instruction it
moves data FOR (its consumer, through a start/done chain), else of the
one that produced its operand; the ``scopes`` note says how much time
was placed that way (``inherited``).  An instruction that does name a
traced operation outside every ``bps.*`` scope stays ``unscoped``.

A fusion has ONE ``op_name``: the one XLA gave it, its principal
instruction's (a matmul fusion is named after the matmul, whatever rides
in its epilogue — on one chip that is AdamW; elsewhere the slices of
``unpack`` ride in AdamW's elementwise fusions).  The per-scope numbers
are self seconds by that one name, and a boundary between two scopes
that fuse is blurred by it.  ``post_backward`` is by elapsed time and
is not.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark.harness import stats, xplane

OP_NAME_KEY = "tf_op"                 # the metadata stat with op_name
INHERIT_DEPTH = 3                     # copy-start -> copy-done -> user
KERNEL = " custom-call("              # a Pallas kernel in the HLO text
STAGES = ("model", "head", "pack", "reduce", "unpack", "optimizer",
          "step_metrics", "unscoped")
_TRANSFORM = re.compile(
    r"\b(?:jvp|transpose|vmap|pmap|remat|checkpoint|custom_jvp|custom_vjp)"
    r"\(")
_BUCKET = re.compile(r"^b(\d+)$")
_REFERENCE = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")


# ----------------------------------------------------------- op_name -> scope


@dataclasses.dataclass(frozen=True)
class Scope:
    stage: str = "unscoped"       # one of STAGES
    bwd: bool = False             # under ``transpose(...)``
    bucket: Optional[int] = None  # pack / reduce
    module: str = ""              # Flax path under bps.model


UNSCOPED = Scope()


def components(op_name: str) -> List[str]:
    """The ``/``-separated components of an ``op_name`` with the
    transformation wrappers taken off: ``jvp(bps.push_pull/pack/b003)``
    gives ``bps.push_pull``, ``pack``, ``b003``; ``jit(local_step)``
    stays one component."""
    text = _TRANSFORM.sub("", op_name)
    out, cur, depth = [], [], 0
    for ch in text:
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                continue          # closes a wrapper that was taken off
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        cur.append(ch)
    out.append("".join(cur))
    return out


def parse(op_name: Optional[str]) -> Scope:
    """The innermost ``bps.*`` scope an ``op_name`` lies in."""
    if not op_name or "bps." not in op_name:
        return UNSCOPED
    comps = components(op_name)
    at = [i for i, c in enumerate(comps) if c.startswith("bps.")]
    if not at:
        return UNSCOPED
    bwd = "transpose(" in op_name
    i = at[-1]
    stage = comps[i][len("bps."):]
    if stage == "push_pull":
        sub = comps[i + 1] if i + 1 < len(comps) else ""
        m = _BUCKET.match(comps[i + 2]) if i + 2 < len(comps) else None
        if sub not in ("pack", "reduce", "unpack"):
            return UNSCOPED
        return Scope(sub, bwd, int(m.group(1)) if m else None)
    if stage == "model":
        return Scope("model", bwd, module="/".join(comps[i + 1:-1]))
    if stage not in STAGES:
        return UNSCOPED
    return Scope(stage, bwd)


def model_part(module: str) -> str:
    """Which part of a transformer block a Flax module path names, as
    far as the names of ``models/transformer.py`` give it."""
    comps = module.split("/")
    for i, c in enumerate(comps):
        if c == "attn":
            nxt = comps[i + 1] if i + 1 < len(comps) else ""
            return "attn_proj" if nxt in ("q", "k", "v", "o", "qkv") else (
                "attn_other")
        if c == "mlp":
            return "mlp"
        if c.startswith(("ln", "norm")):
            return "norm"
        if c in ("embed", "pos", "lm_head"):
            return "embed"
    return "other"


# ------------------------------------------- the metadata tables of the file


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int
            ) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one protobuf message: an
    int for a varint, a ``(start, end)`` span of ``buf`` for a
    length-delimited field, the raw bytes of a fixed one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            n, pos = _varint(buf, pos)
            val = (pos, pos + n)
            pos += n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            val = buf[pos:pos + n]
            pos += n
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield num, wt, val


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span):
    """The ``value`` message's span of one map entry."""
    for num, wt, val in _fields(buf, *span):
        if num == 2 and wt == 2:
            return val
    return None


def metadata_stats(data: bytes) -> Dict[str, Dict[str, Dict[str, object]]]:
    """``{plane name: {event name: {stat name: value}}}`` from the
    ``event_metadata`` and ``stat_metadata`` tables of every plane of a
    serialized ``XSpace`` (tsl/profiler/protobuf/xplane.proto: XSpace
    planes=1; XPlane name=2 lines=3 event_metadata=4 stat_metadata=5;
    XEventMetadata name=2 stats=5; XStatMetadata id=1 name=2; XStat
    metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7).  The
    ``lines`` — all of the events — are skipped, not decoded."""
    out: Dict[str, Dict[str, Dict[str, object]]] = {}
    for num, wt, plane in _fields(data, 0, len(data)):
        if num != 1 or wt != 2:
            continue
        name, events, stat_names = "", [], {}
        for pnum, pwt, val in _fields(data, *plane):
            if pwt != 2:
                continue
            if pnum == 2:
                name = _text(data, val)
            elif pnum == 4:
                events.append(_map_value(data, val))
            elif pnum == 5:
                span = _map_value(data, val)
                sid, sname = 0, ""
                for snum, swt, sval in _fields(data, *span):
                    if snum == 1 and swt == 0:
                        sid = sval
                    elif snum == 2 and swt == 2:
                        sname = _text(data, sval)
                stat_names[sid] = sname
        table: Dict[str, Dict[str, object]] = {}
        for span in events:
            if span is None:
                continue
            ev_name, stats = "", {}
            for enum, ewt, val in _fields(data, *span):
                if enum == 2 and ewt == 2:
                    ev_name = _text(data, val)
                elif enum == 5 and ewt == 2:
                    key, value = None, None
                    for snum, swt, sval in _fields(data, *val):
                        if snum == 1:
                            key = stat_names.get(sval, str(sval))
                        elif snum in (5, 6) and swt == 2:
                            value = _text(data, sval)
                        elif snum == 7:          # an interned string
                            value = stat_names.get(sval, "")
                        elif swt == 0:
                            value = sval
                    if key is not None:
                        stats[key] = value
            table[ev_name] = stats
        out[name] = table
    return out


# --------------------------------------------------------------- the events


@dataclasses.dataclass
class ScopedEvent(xplane.Event):
    scope: Scope = UNSCOPED
    kernel: bool = False
    collective: bool = False
    inherited: bool = False       # the scope is a neighbour's
    results: int = 1              # buffers in the result (a combined
    op_name: str = ""             # collective carries several buckets)


@dataclasses.dataclass
class ScopedTrace:
    ops: Dict[int, List[ScopedEvent]]      # device ordinal -> XLA Ops
    modules: Dict[int, List[xplane.Event]]  # device ordinal -> launches
    op_name_key: Optional[str] = None      # the stat that carried op_name


def plane_scopes(table: Dict[str, Dict[str, object]],
                 first_start: Dict[str, float]
                 ) -> Dict[str, Tuple[Scope, bool, str]]:
    """``{HLO text: (scope, inherited, op_name)}`` for the instructions
    of one plane's metadata table: each one's own scope from its
    ``op_name``; for the compiler's data movement (see the module's
    text) the scope of its nearest scoped consumer — the one that runs
    first, by ``first_start`` of its text — else producer."""
    own: Dict[str, Tuple[Scope, str]] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    name_of: Dict[str, str] = {}
    for text, stats in table.items():
        if not text.startswith("%"):
            continue
        refs = _REFERENCE.findall(text)
        name, ops = refs[0], refs[1:]
        op_name = stats.get(OP_NAME_KEY)
        op_name = op_name if isinstance(op_name, str) else ""
        name_of[text] = name
        own[name] = (parse(op_name), op_name)
        operands[name] = ops
        for op in ops:
            users.setdefault(op, []).append(name)
    when = {name_of[text]: t for text, t in first_start.items()
            if text in name_of}
    for names in users.values():
        names.sort(key=lambda n: (when.get(n, float("inf")), n))

    def nearest(name: str, edges: Dict[str, List[str]]) -> Optional[Scope]:
        frontier = [name]
        for _ in range(INHERIT_DEPTH):
            frontier = [n for f in frontier for n in edges.get(f, ())
                        if n in own]
            for n in frontier:
                if own[n][0] is not UNSCOPED:
                    return own[n][0]
        return None

    bucketed = any(sc.stage in ("pack", "reduce", "unpack")
                   for sc, _ in own.values())
    out = {}
    for text, name in name_of.items():
        scope, op_name = own[name]
        inherited = False
        if scope is UNSCOPED and "jit(" not in op_name:
            found = nearest(name, users) or nearest(name, operands)
            if bucketed and xplane.COLLECTIVE.search(name):
                # XLA's combiner drops the name of the reductions it
                # merges: a nameless collective of a bucketed step is one
                bucket = found.bucket if found is not None else None
                scope, inherited = Scope("reduce", False, bucket), True
            elif found is not None:
                scope, inherited = found, True
        out[text] = (scope, inherited, op_name)
    return out


def result_count(text: str) -> int:
    """Buffers in an instruction's result type: the elements of a tuple
    (as far as the trace's cut of the text shows them), else one."""
    result = text.partition(" = ")[2]
    if not result.startswith("("):
        return 1
    depth = 0
    for i, ch in enumerate(result):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return max(1, len(_SHAPE.findall(result[:i])))
    return max(1, len(_SHAPE.findall(result)))


def from_serialized(data: bytes) -> ScopedTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(data)
    meta = metadata_stats(data)
    out = ScopedTrace(ops={}, modules={})
    for plane in pd.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        d = int(m.group(1))
        table = meta.get(plane.name, {})
        if any(OP_NAME_KEY in stats for stats in table.values()):
            out.op_name_key = OP_NAME_KEY
        first_start: Dict[str, float] = {}
        for line in plane.lines:
            if line.name == xplane.OPS_LINE:
                for ev in line.events:
                    first_start.setdefault(ev.name, ev.start_ns)
        resolved = plane_scopes(table, first_start)
        cache: Dict[str, tuple] = {}
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                out.modules[d] = [
                    xplane.Event(ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
            if line.name != xplane.OPS_LINE:
                continue
            evs = []
            for ev in line.events:
                text = ev.name
                hit = cache.get(text)
                if hit is None:
                    scope, inherited, op_name = resolved.get(
                        text, (UNSCOPED, False, ""))
                    short = xplane.short_name(text)
                    hit = cache[text] = (
                        short, scope, KERNEL in text,
                        bool(xplane.COLLECTIVE.search(short)), inherited,
                        result_count(text), op_name)
                evs.append(ScopedEvent(
                    hit[0], ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9, 0.0, *hit[1:]))
            xplane._self_times(evs)
            if evs:
                out.ops[d] = evs
    return out


def load(path: str) -> ScopedTrace:
    with open(path, "rb") as f:
        return from_serialized(f.read())


def from_text_proto(text: str) -> ScopedTrace:
    from jax.profiler import ProfileData

    return from_serialized(ProfileData.text_proto_to_serialized_xspace(text))


# ------------------------------------------------------------- the analysis


def step_launches(st: ScopedTrace) -> Dict[int, List[xplane.Event]]:
    """Per device, the launches of the step program: the module that
    takes most of the first device's time."""
    if not st.modules or not st.ops:
        return {}
    first = st.modules.get(min(st.ops), [])
    by_name: Dict[str, float] = {}
    for ev in first:
        by_name[ev.name] = by_name.get(ev.name, 0.0) + ev.dur
    if not by_name:
        return {}
    step = max(by_name, key=by_name.get)
    return {d: [ev for ev in st.modules.get(d, ()) if ev.name == step]
            for d in st.ops}


def post_backward_s(ops: List[ScopedEvent], launch: xplane.Event
                    ) -> Optional[float]:
    """From the end of the last backward op of ``bps.model`` /
    ``bps.head`` inside ``launch`` to the launch's end."""
    ends = [ev.end for ev in ops
            if ev.scope.bwd and ev.scope.stage in ("model", "head")
            and not ev.inherited      # (a copy FOR the optimizer is not)
            and launch.start <= ev.start < launch.end]
    return launch.end - max(ends) if ends else None


def analyse(st: ScopedTrace) -> Optional[dict]:
    """Everything the notes and the four readers need, in seconds per
    step; ``None`` where the trace holds no scoped op (a program from
    before the scopes) or no launch of a step."""
    launches = step_launches(st)
    n_launch = sum(len(v) for v in launches.values())
    if not n_launch or not any(
            ev.scope is not UNSCOPED for evs in st.ops.values() for ev in evs):
        return None
    acc: Dict[str, float] = {}

    def add(key: str, secs: float) -> None:
        acc[key] = acc.get(key, 0.0) + secs

    post: Dict[int, List[float]] = {}
    inherited = 0.0
    for d, evs in st.ops.items():
        for launch in launches[d]:
            pb = post_backward_s(evs, launch)
            if pb is not None:
                post.setdefault(d, []).append(pb)
        spans = [(ln.start, ln.end) for ln in launches[d]]
        for ev in evs:
            if not any(s <= ev.start < e for s, e in spans):
                continue
            sc = ev.scope
            if ev.inherited:
                inherited += ev.self_s
            side = "bwd" if sc.bwd else "fwd"
            if sc.stage == "model":
                part = "kernel" if ev.kernel else model_part(sc.module)
                add(f"model.{part}.{side}", ev.self_s)
            elif sc.stage == "head":
                add(f"head.{'kernel' if ev.kernel else 'xla'}.{side}",
                    ev.self_s)
            elif sc.stage == "reduce":
                add("reduce.collective" if ev.collective
                    else "reduce.copies", ev.self_s)
            else:
                add(sc.stage, ev.self_s)
    per_step = {k: v / n_launch for k, v in sorted(acc.items())}

    def under(prefix: str) -> float:
        return sum(v for k, v in per_step.items()
                   if k == prefix or k.startswith(prefix + "."))

    total = sum(per_step.values())
    return {
        "steps": n_launch // len(launches), "chips": len(launches),
        "op_name_key": st.op_name_key,
        "per_step_s": per_step, "scoped_sum_s": total,
        "inherited_s": inherited / n_launch,
        "unscoped_share": per_step.get("unscoped", 0.0) / total,
        "step_device_s": stats.median([ln.dur for lns in launches.values()
                                  for ln in lns]),
        # median over the steps of a chip, then the worst chip
        "post_backward_s": (max(stats.median(v) for v in post.values())
                            if post else None),
        "optimizer_s": under("optimizer"),
        "pack_unpack_s": (under("pack") + under("unpack")
                          + per_step.get("reduce.copies", 0.0)),
        "has_push_pull": any(under(k) > 0
                             for k in ("pack", "reduce", "unpack")),
        "model_blocks_xla_s": sum(
            v for k, v in per_step.items()
            if k.startswith("model.") and not k.startswith("model.kernel")),
    }


def buckets(st: ScopedTrace) -> Optional[dict]:
    """The first traced step on the first chip, one row per collective
    instruction: ``[instruction, bucket id its op_name carries (XLA's
    combiner drops the name of what it merges: then the id of the first
    op that reads the result), start ms after the program's start, ms
    in flight, ms exposed, buffers it carries]`` (an asynchronous pair
    is one row, from its ``-start`` to its ``-done``; exposed = no
    compute op running, as ``xplane.collective_seconds`` has it), with
    the span of the backward pass beside them."""
    launches = step_launches(st)
    if not launches:
        return None
    d = min(launches)
    if not launches[d]:
        return None
    launch = launches[d][0]
    evs = [ev for ev in st.ops[d] if launch.start <= ev.start < launch.end]
    coll = [ev for ev in evs if ev.collective]
    if not coll:
        return None
    compute = xplane.union(
        (ev.start, ev.end) for ev in evs
        if not ev.collective and ev.self_s > 0
        and ev.self_s >= 0.999 * ev.dur)

    def exposed(ev) -> float:
        return xplane.total(xplane.subtract([(ev.start, ev.end)], compute))

    rows, pending = [], {}
    for ev in sorted(coll, key=lambda e: e.start):
        key = ev.name.split(" ")[0]
        if "-start" in key:
            pending[key.replace("-start", "-done")] = ev
            continue
        first = pending.pop(key, ev) if "-done" in key else ev
        bucket = first.scope.bucket if first.scope.bucket is not None else (
            ev.scope.bucket)
        rows.append([first.name, bucket,
                     1e3 * (first.start - launch.start),
                     1e3 * (ev.end - first.start),
                     1e3 * (exposed(ev) + (exposed(first)
                                           if first is not ev else 0.0)),
                     first.results])
    bwd = [ev for ev in evs if ev.scope.bwd and ev.scope.stage == "model"
           and not ev.inherited]
    planned = {ev.scope.bucket for evs_d in st.ops.values() for ev in evs_d
               if ev.scope.stage in ("pack", "reduce")
               and ev.scope.bucket is not None}
    kinds: Dict[str, List[int]] = {}
    for row in rows:
        kind = kinds.setdefault(re.sub(r"[.\d]*( .*)?$", "", row[0]),
                                [0, 0])
        kind[0] += 1
        kind[1] += row[5]
    out = {"chip": d, "planned_buckets": len(planned),
           "collective_instructions_per_step": len(rows),
           "instructions_and_buffers_by_kind": kinds,
           "step_ms": 1e3 * launch.dur,
           "first_collective_start_ms": min(r[2] for r in rows),
           "collectives": rows}
    if bwd:
        last = max(ev.end for ev in bwd)
        out.update(
            bwd_first_start_ms=1e3 * (min(ev.start for ev in bwd)
                                      - launch.start),
            bwd_last_end_ms=1e3 * (last - launch.start),
            collectives_started_before_bwd_end=sum(
                1 for r in rows if r[2] < 1e3 * (last - launch.start)))
    return out


# ------------------------------------------------- the readers' entry point


def trace_dir(cell_name: str) -> str:
    """Where the train runner wrote the cell's profile."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "out", cell_name, "trace")


def for_run(ctx) -> Optional[dict]:
    """``analyse`` of the traced run behind ``ctx``, made once and kept
    on it; prints the ``scopes`` and ``buckets`` notes the first time.
    ``None`` where there is nothing to read: no trace, no train step, or
    a program without the scopes."""
    if hasattr(ctx, "scopes"):
        return ctx.scopes
    ctx.scopes = None
    if getattr(ctx, "trace", None) is None or ctx.train is None:
        return None
    t0 = time.perf_counter()
    st = getattr(ctx, "scoped_trace", None)
    if st is None:
        path = xplane.find_xplane(trace_dir(ctx.cell["name"]))
        if path is None:
            return None
        st = load(path)
    res = analyse(st)
    if res is None:
        ctx.note(event="scopes", found=False, op_name_key=st.op_name_key)
        return None
    ctx.scopes = res
    ms = {k: 1e3 * v for k, v in res["per_step_s"].items()}
    ctx.note(event="scopes", found=True, op_name_key=res["op_name_key"],
             op_name_where="metadata", steps=res["steps"],
             chips=res["chips"], ms_per_step=ms,
             scoped_sum_ms=1e3 * res["scoped_sum_s"],
             step_device_ms=1e3 * res["step_device_s"],
             unscoped_share_pct=100.0 * res["unscoped_share"],
             inherited_ms=1e3 * res["inherited_s"],
             post_backward_ms=(None if res["post_backward_s"] is None
                               else 1e3 * res["post_backward_s"]),
             blurred="a fusion counts under the one name XLA gave it",
             reader_s=time.perf_counter() - t0)
    b = buckets(st)
    if b is not None:
        ctx.note(event="buckets", **b)
    return res
