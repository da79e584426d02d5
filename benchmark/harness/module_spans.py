"""Device time by Flax module, read from the scopes ``harness/scopes.py``
already attaches to every op of the traced step.

``scopes.analyse`` sums by stage and by the parts of a dense block it
knows; this asks the same events a second question: under a module named
``owner`` (``moe``), how much self time lies in each of its children
(``router``, ``dispatch``, ``experts``, ``combine``, ``shared``), and in
which pass — ``fwd``, ``bwd`` (under ``transpose(``) or ``remat`` (the
forward a recomputed block runs again in the backward pass: its ops'
names pass through ``rematted_computation``).  Seconds per step,
averaged over chips.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from benchmark.harness import scopes, xplane

REMAT = "rematted_computation"


def child_seconds(st: scopes.ScopedTrace, owner: str
                  ) -> Optional[Dict[Tuple[str, str], float]]:
    """``{(child, pass): seconds per step}`` of the ops under ``owner``;
    ``None`` where the trace holds no launch of a step or no such op."""
    launches = scopes.step_launches(st)
    n_launch = sum(len(v) for v in launches.values())
    if not n_launch:
        return None
    out: Dict[Tuple[str, str], float] = {}
    for d, evs in st.ops.items():
        spans = [(ln.start, ln.end) for ln in launches[d]]
        for ev in evs:
            if ev.scope.stage != "model" or not ev.self_s:
                continue
            comps = ev.scope.module.split("/")
            if owner not in comps or not any(
                    s <= ev.start < e for s, e in spans):
                continue
            i = comps.index(owner)
            child = comps[i + 1] if i + 1 < len(comps) else ""
            which = ("remat" if REMAT in comps
                     else "bwd" if ev.scope.bwd else "fwd")
            out[(child, which)] = out.get((child, which), 0.0) + ev.self_s
    if not out:
        return None
    return {k: v / n_launch for k, v in out.items()}


def for_run(ctx, owner: str) -> Optional[Dict[Tuple[str, str], float]]:
    """``child_seconds`` of the traced run behind ``ctx``; the scoped
    trace is loaded once and kept on it.  ``None`` where there is nothing
    to read: no trace, no train step, or a program without the module."""
    cache = ctx.__dict__.setdefault("module_spans", {})
    if owner in cache:
        return cache[owner]
    cache[owner] = None
    if getattr(ctx, "trace", None) is None or ctx.train is None:
        return None
    st = getattr(ctx, "scoped_trace", None)
    if st is None:
        path = xplane.find_xplane(scopes.trace_dir(ctx.cell["name"]))
        if path is None:
            return None
        st = ctx.scoped_trace = scopes.load(path)
    res = cache[owner] = child_seconds(st, owner)
    if res is not None:
        ctx.note(event="module_spans", owner=owner, ms_per_step={
            f"{c}.{w}": 1e3 * s for (c, w), s in sorted(res.items())})
    return res
