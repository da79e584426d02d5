"""The expert layer's core: route, dispatch, grouped products, combine.

A fine-grained expert layer (DeepSeek-V3 report, section 2.1.2) as one
rank of an expert-parallel group runs it.  The rank is told which
contiguous slice of the experts it holds (``held = (first, count)``),
routes every token over ALL the experts, and computes the part of the
result that its own experts give:

  router    one of two scoring rules, in float32.  ``sigmoid``: ``s =
            sigmoid(x W_g)``; the top ``k`` of ``s + b`` (``b`` is the
            balancing bias: it takes part in the choice and in nothing
            else, and receives no gradient); weights are the uncorrected
            ``s`` of the chosen, normalised to sum to one.
            ``softmax_topk``: the top ``k`` of the logits ``x W_g``;
            weights are the softmax over the chosen ``k`` (equal to a
            softmax over all, renormalised over the chosen).  Either way
            the weights are multiplied by ``scale``.  The router may read
            another input than the experts do (``router_x``: a model
            that routes from the block's attention input so that experts
            can be fetched while attention runs).
  dispatch  the assignments whose expert is held, sorted by expert, laid
            out group after group in ONE static row buffer — every group
            from a row-tile boundary, so a tile belongs to one expert —
            and the tokens gathered into it.  The assignments of experts
            held elsewhere fall in a tail group that is never laid out.
  experts   grouped matrix products over the real tiles,
            ``ops/grouped_matmul.py``, two kernels forward whatever the
            form: the first projection with its activation
            (``grouped_matmul_act``: gate and up of a gated expert share
            one pass over the row tile, the gate's SiLU or ReLU times up
            on the float32 tile; a non-gated expert is the same kernel
            with one matrix, ``relu(up x)^2``: no gate leaf), then down.
            Backward the first projection is one kernel too (``dx``
            summed over gate and up in it), beside a weight gradient a
            matrix.  XLA runs nothing over the buffer between them.
  combine   each token sums its held assignments' rows by their weights.

**No capacity factor and no dropped assignment.**  The buffer holds the
worst routing — ``tokens x min(k, count)`` rows plus a tile of slack a
group — and the products' cost follows the tiles that are real.  The
dispatch and the combine are gathers in both directions (an assignment
knows its row and a row its assignment), each with the other as its
backward pass; nothing scatters.  Each of the four is ONE pass over the
rows it writes: a row that holds no assignment repeats token 0 (no
mask follows the gather: its gradient is zero, so nothing reads it),
and a token's ``k`` rows are gathered ``[k, T, d]`` — laid ``[T, k, d]``
the chip pads ``k`` to a tile's 8 or 16 sublanes, a copy of two to
three times the real bytes at ``k = 6``.

With ``axis_name`` the layer is expert parallel across that mesh axis:
a rank routes ITS OWN tokens once, sends each to the ranks that hold
one of its chosen experts (``all_to_all``; the choices and weights, a
few numbers a token, go to every rank), computes its experts' part for
the tokens it was sent, and sends the parts back to the tokens' owners,
which add them up (``all_to_all``).  The send buffers are static and
sized for the worst routing — every token to every rank — so nothing
is dropped here either; a slot whose token did not choose the rank is
zero and lays out no row.  On one rank there is no exchange and nothing
stands in for it.

**A layer made of kernels alone leaves XLA nothing to prefetch
behind.**  A ``[rows, d]`` gather is five times faster when its ``[T,
d]`` source lies in VMEM (``ops/grouped_matmul.py:_VMEM_LIMIT``), and
XLA copies a source there only while an op of its OWN runs — never
behind a Mosaic call.  So ``experts_ffn`` casts ``down`` after the first
projection (``_in_order``) and ``_combine_bwd`` gathers after the
recomputed forward: the cast is the op that hides the copy of ``dy``
between a layer's two gathers, whose sources do not fit VMEM together.
``moe.route_dispatch_ms_per_step`` rising by ~4 ms a layer is the sign
that this was lost.

Counters (``observability.metrics`` registry, beside ``flash.tiles_*``):
gauges ``moe.rows_buffer``, ``moe.experts_held``, ``moe.gathered_mb``
(label ``op`` = ``dispatch``, ``combine``, ``dispatch_bwd``,
``combine_bwd``: the bytes that gather writes a call) and
``moe.expert_calls`` (label ``pass`` = ``fwd``, ``bwd``: the
``pallas_call``s ``experts_ffn`` traces a pass) are set when a
layer is traced; counters ``moe.assignments_held`` and ``moe.rows_computed``
grow by a train step's metrics of those names, step by step
(``training/step.py``): the first is what the router chose on held
experts, the second the rows of the buffer that hold a real
assignment — equal unless something was dropped.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..observability.metrics import get_registry
from ..ops.grouped_matmul import grouped_matmul, grouped_matmul_act

ROW_TILE = 256      # rows of a tile of the buffer: one expert each
PLAN = "moe_plan"   # checkpoint name of the top-k choice and its layout


class Plan(NamedTuple):
    """Where every held assignment's row lies, both ways round."""

    dest: jax.Array        # [T, k] row of the assignment (0 if not held)
    held: jax.Array        # [T, k] bool: its expert is held here
    src: jax.Array         # [R] assignment (t * k + j) of the row
    tok: jax.Array         # [R] token t of the row (0 if not valid)
    valid: jax.Array       # [R] bool: the row is a real assignment
    tile_group: jax.Array  # [R // tile] expert (local) of each row tile
    n_active: jax.Array    # [] tiles that hold real rows
    count: jax.Array       # [count] assignments per held expert


def relu2(h):
    return jnp.square(jax.nn.relu(h))


SCORING = ("sigmoid", "softmax_topk")
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}   # act(gate x) * up x
UNGATED = {"relu2": relu2}                           # act(up x): no gate


def route(x, kernel, bias, top_k: int, scale: float,
          scoring: str = "sigmoid"):
    """``(idx [T, k] int32, weights [T, k] float32)`` for tokens
    ``x [T, d]``, the product in float32 at full precision (a bf16 pass
    flips choices).  ``sigmoid``: selection on ``sigmoid score + bias``,
    weights the uncorrected scores of the chosen over their sum.
    ``softmax_topk``: selection on the logits (``+ bias`` where there is
    one), weights the softmax over the chosen logits."""
    if scoring not in SCORING:
        raise ValueError(f"unknown scoring rule {scoring!r}: {SCORING}")
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else logits
    select = scores if bias is None else scores + lax.stop_gradient(
        bias.astype(jnp.float32))
    _, idx = lax.top_k(select, top_k)
    idx = checkpoint_name(idx, PLAN)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if scoring == "sigmoid":
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    else:
        weights = jax.nn.softmax(picked, axis=-1)
    return idx.astype(jnp.int32), weights * scale


def buffer_rows(tokens: int, top_k: int, count: int, tile: int) -> int:
    """Rows of the static buffer: every assignment a token can have on
    ``count`` experts, plus a tile of slack a group."""
    worst = tokens * min(top_k, count)
    return -(-worst // tile) * tile + count * tile


def plan(idx, first, count: int, tile: int = ROW_TILE) -> Plan:
    """Lay the assignments ``idx [T, k]`` whose expert lies in
    ``[first, first + count)`` out in the row buffer (``first`` may be
    traced: a rank's own offset)."""
    T, k = idx.shape
    A, R = T * k, buffer_rows(T, k, count, tile)
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)                  # tail: not held
    group = jnp.minimum(key, count - 1)
    onehot = (key[:, None] == jnp.arange(count)[None, :]).astype(jnp.int32)
    running = jnp.cumsum(onehot, axis=0)
    n = running[-1]                                      # [count]
    pos = jnp.take_along_axis(running, group[:, None], axis=1)[:, 0] - 1
    tiles = jnp.maximum(1, -(-n // tile))                # >= 1: see dw
    tile_end = jnp.cumsum(tiles)
    row0 = (tile_end - tiles) * tile                     # group's first row
    dest = jnp.where(held, row0[group] + pos, 0)
    tile_group = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(R // tile), side="right"), count - 1)
    # row -> assignment: the stable sort's order within a group is the
    # running count's
    order = jnp.argsort(key, stable=True)
    sorted0 = jnp.cumsum(n) - n
    g = jnp.repeat(tile_group, tile, total_repeat_length=R)
    off = jnp.arange(R) - row0[g]
    valid = off < n[g]
    src = jnp.where(valid, order[jnp.clip(sorted0[g] + off, 0, A - 1)], 0)
    # named for a caller's recomputation policy: a block under
    # ``jax.checkpoint`` that saves the plan (a few integers a row) does
    # not choose and sort again in the backward pass
    return Plan(*(checkpoint_name(a, PLAN) for a in (
        dest.reshape(T, k).astype(jnp.int32), held.reshape(T, k),
        src.astype(jnp.int32), (src // k).astype(jnp.int32), valid,
        tile_group.astype(jnp.int32),
        tile_end[-1].astype(jnp.int32), n)))


# ------------------------------------------------ dispatch and combine


def _gather(op: str, source, index):
    """``source[index]``: the one way rows move.  What it writes a call
    is the gauge ``moe.gathered_mb{op}``, set as the layer is traced."""
    out = source[index]
    get_registry().gauge("moe.gathered_mb", op=op).set(
        out.size * out.dtype.itemsize / 1e6)
    return out


def _sum_held(op: str, rows, p: Plan, weights=None):
    """``[T, d]`` float32: each token's held rows (by ``weights [T, k]``
    where given) summed in the order ``j = 0 ... k - 1``, from ONE gather
    laid ``[k, T, d]``: the fold from ``[k * T, d]`` is free and the sum
    is ``k`` multiply-adds over ``[T, d]``, each on its own slice (over
    the whole array the float32 products would be written out first)."""
    picked, held = _gather(op, rows, p.dest.T), p.held.T

    def term(j):
        t = jnp.where(held[j][:, None], picked[j], 0).astype(jnp.float32)
        return t if weights is None else t * weights[:, j][:, None]

    total = term(0)
    for j in range(1, held.shape[0]):
        total = total + term(j)
    return total


@jax.custom_vjp
def dispatch(x, p: Plan):
    """``rows [R, d]``: each real row its token (the others token 0:
    ``_combine_bwd`` sends them a zero gradient)."""
    return _gather("dispatch", x, p.tok)


def _dispatch_fwd(x, p):
    return dispatch(x, p), p


def _dispatch_bwd(p, drows):
    return _sum_held("dispatch_bwd", drows, p).astype(drows.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, p: Plan):
    """``y [T, d]``: each token's held rows summed by their weights."""
    return _sum_held("combine", rows, p, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, p):
    return combine(rows, weights, p), (rows, weights, p)


def _combine_bwd(res, dy):
    rows, weights, p = res
    # in dy's dtype until the products that read it, and exactly zero on
    # the rows that hold no assignment: whatever those rows of the
    # buffer hold meets a zero in every weight gradient
    # the gather waits for the recomputed forward (``rows`` is its last
    # kernel's result), so that XLA can bring ``dy`` into VMEM after the
    # dispatch gather's source has left it: module docstring
    tok, rows = lax.optimization_barrier((p.tok, rows))
    g = _gather("combine_bwd", dy, tok)                   # [R, d]
    w_row = jnp.where(p.valid, weights.reshape(-1)[p.src], 0.0)
    drows = (w_row[:, None] * g).astype(rows.dtype)
    dw_row = jnp.sum(jnp.where(p.valid[:, None],
                               rows.astype(jnp.float32), 0.0) * g, axis=-1)
    dweights = jnp.where(p.held, dw_row[p.dest], 0.0)
    return drows, dweights.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


# ------------------------------------------------------------ the layer


@jax.custom_vjp
def _in_order(first, then):
    """Both, ``then`` not read before ``first`` is there (forward only)."""
    return lax.optimization_barrier((first, then))


_in_order.defvjp(lambda *both: (lax.optimization_barrier(both), None),
                 lambda _, d: d)


def experts_ffn(rows, gate, up, down, p: Plan, interpret=None,
                act: str = "silu"):
    """The held experts' feed-forward over the row buffer: ``up
    [count, d, f]``, ``down [count, f, d]``.  Gated (``gate [count, d,
    f]``): ``act`` of the gate (``GATES``: ``silu`` SwiGLU, ``relu``
    ReGLU) times up, then down.  Non-gated (``gate`` None): ``act`` of
    up (``UNGATED``: ``relu2``), then down.  Either way two kernels
    forward (``grouped_matmul_act``, ``grouped_matmul``); backward one
    for the first projection, a weight gradient a matrix and down's
    ``dx``: the gauge ``moe.expert_calls``."""
    where = dict(tile_group=p.tile_group, n_active=p.n_active,
                 interpret=interpret)
    if gate is None:
        ws, fn = (up,), UNGATED[act]
    else:
        ws, fn = (gate, up), GATES[act]
    for pass_, calls in (("fwd", 2), ("bwd", 3 + len(ws))):
        get_registry().gauge("moe.expert_calls", **{"pass": pass_}).set(
            calls)
    h = grouped_matmul_act(rows, tuple(w.astype(rows.dtype) for w in ws),
                           act=fn, **where)
    # down's cast after the first projection: the one XLA op between a
    # layer's two gathers (module docstring)
    h, down = _in_order(h, down)
    return grouped_matmul(h, down.astype(rows.dtype), **where)


def served(idx, first, count: int, p: Plan):
    """``(chosen, rows)``: the assignments of ``idx [T, k]`` the router
    put on the held experts, counted from ``idx`` alone, and the rows of
    the buffer that hold a real assignment — what the dispatch gathered
    and the products computed.  Equal unless an assignment was dropped
    on the way into the buffer.  (Two reductions: following every
    assignment to its row and back cost 5 ms a step at 5 x 65 536
    assignments on the v5e, 1 % of the step.)"""
    flat = idx.reshape(-1)
    chosen = (flat >= first) & (flat < first + count)
    return (jnp.sum(chosen, dtype=jnp.int32),
            jnp.sum(p.valid, dtype=jnp.int32))


def _held_part(x, idx, weights, first, gate, up, down, tile, interpret,
               act):
    """The held experts' part for tokens ``x [T, d]`` already routed
    (``idx, weights [T, k]``), and its two counts."""
    count = up.shape[0]
    with jax.named_scope("dispatch"):
        p = plan(idx, first, count, tile)
        rows = dispatch(x, p)
    reg = get_registry()
    reg.gauge("moe.rows_buffer").set(rows.shape[0])
    reg.gauge("moe.experts_held").set(count)
    with jax.named_scope("experts"):
        out = experts_ffn(rows, gate, up, down, p, interpret, act)
    with jax.named_scope("combine"):
        y = combine(out, weights, p)
    return y, served(idx, first, count, p)


def expert_layer(x, router_kernel, router_bias, gate, up, down, *,
                 top_k: int, scale: float,
                 held: Optional[Tuple[int, int]] = None,
                 axis_name: Optional[str] = None, tile: int = ROW_TILE,
                 interpret=None, router_x=None, scoring: str = "sigmoid",
                 act: str = "silu"):
    """``(y [T, d], (chosen, served))``: the routed part of the layer
    for tokens ``x [T, d]`` — the sum over each token's chosen experts
    that THIS rank holds (``up.shape[0]`` of them, from ``held[0]``;
    all of them by default) — and the two counts of ``served``.  The
    shared expert is the caller's (every rank computes it alike: it
    counts once).  ``router_x [T, d]`` is what the router scores where
    that is not ``x`` (the experts always read ``x``); ``scoring`` is
    ``route``'s rule; ``act`` with ``gate`` is ``experts_ffn``'s form
    (``gate`` None: a non-gated expert, ``act`` of ``UNGATED``).

    With ``axis_name`` (inside ``shard_map``): ``x`` is this rank's
    tokens, the rank holds the experts from ``axis_index * count``,
    ``y`` is complete — every rank's part, summed — and the counts are
    the group's."""
    count = up.shape[0]
    first = 0 if held is None else held[0]
    if held is not None and held[1] != count:
        raise ValueError(f"held {held} names {held[1]} experts, the "
                         f"weights hold {count}")
    # the default rule goes by omission: ``benchmark/controls.py`` swaps
    # ``route`` for a stand-in of the five arguments it had
    rule = {} if scoring == "sigmoid" else {"scoring": scoring}
    with jax.named_scope("router"):
        idx, weights = route(x if router_x is None else router_x,
                             router_kernel, router_bias, top_k, scale,
                             **rule)
    if axis_name is None:
        return _held_part(x, idx, weights, first, gate, up, down, tile,
                          interpret, act)
    ranks = lax.psum(1, axis_name)
    first = lax.axis_index(axis_name) * count
    T, d = x.shape
    with jax.named_scope("exchange"):
        # tokens to the ranks that hold a chosen expert: slot r of the
        # send buffer is for rank r
        wanted = jnp.any((idx // count)[None] == jnp.arange(ranks)[
            :, None, None], axis=-1)                       # [ranks, T]
        got = lax.all_to_all(jnp.where(wanted[..., None], x[None], 0),
                             axis_name, 0, 0)              # from rank s
        idx_all = lax.all_gather(idx, axis_name)
        w_all = lax.all_gather(weights, axis_name)
    part, counts = _held_part(
        got.reshape(ranks * T, d), idx_all.reshape(ranks * T, top_k),
        w_all.reshape(ranks * T, top_k), first, gate, up, down, tile,
        interpret, act)
    with jax.named_scope("exchange"):
        back = lax.all_to_all(part.reshape(ranks, T, d), axis_name, 0, 0)
        y = jnp.sum(back.astype(jnp.float32), axis=0).astype(x.dtype)
    return y, tuple(lax.psum(c, axis_name) for c in counts)
