"""Train-step factories: the framework's hot path.

The reference's hot path is loss.backward() firing per-gradient hooks that
enqueue push_pull tasks drained by C++ threads (SURVEY.md §3.2).  The TPU
rendering is one traced SPMD program per step: ``shard_map`` over the mesh,
local backward, bucketed priority-ordered push_pull (collectives.py), optax
update — XLA's latency-hiding scheduler overlaps the collective chain with
the backward compute, which is precisely the role of the reference's
10-thread pipeline (core_loops.cc).

``make_data_parallel_step`` is the Horovod-benchmark-equivalent step used by
bench.py and the examples; model-parallel (tp/sp) steps compose GSPMD jit
with these same pieces (see models/transformer.py and __graft_entry__.py).
"""

from __future__ import annotations

import collections
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.config import get_config
from ..observability.metrics import get_registry
from ..common.tracing import (SCOPE_HEAD, SCOPE_MODEL, SCOPE_OPTIMIZER,
                              SCOPE_STEP_METRICS)
from ..ops.compression import Compression
from .optimizer import DistributedOptimizer, scoped_update
from ..parallel.collectives import shard_map


class TrainState(NamedTuple):
    """Functional train state (params + optimizer state + mutable model
    collections such as BatchNorm running stats + step counter)."""

    params: Any
    opt_state: Any
    model_state: Any
    step: jax.Array


def create_train_state(
    params, tx: optax.GradientTransformation, model_state=None
) -> TrainState:
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        model_state=model_state if model_state is not None else {},
        step=jnp.zeros((), jnp.int32),
    )


def _world1_compression_tx(compression) -> Optional[optax.GradientTransformation]:
    """The single-process rendering of a compression spec: a local optax
    transformation reproducing what the scheme does to each worker's
    contribution on a multi-worker wire, so ``world == 1`` sees the same
    gradient numerics as a multi-process run (the world==1 limit of
    "compress, reduce over one worker, decompress").

    Returns None when nothing needs doing (no/none compression) or — with
    a warning — when the spec is genuinely inapplicable: an object that
    is neither a registry scheme name nor a ``compress``/``decompress``
    Compressor, whose wire behavior we cannot reproduce locally.
    """
    from ..ops.compression import Compression as C

    if compression is None or compression is C.none or compression == "none":
        return None
    if isinstance(compression, str):
        from ..compression import (compression_roundtrip,
                                   error_feedback_compress, get_scheme)

        scheme = get_scheme(compression)  # unknown names fail like multi
        if scheme.biased:
            return error_feedback_compress(scheme)
        return compression_roundtrip(scheme)
    if hasattr(compression, "compress") and hasattr(compression,
                                                    "decompress"):
        def update_fn(updates, state, params=None):
            del params

            def one(g):
                c, ctx = compression.compress(g)
                return compression.decompress(c, ctx)

            return jax.tree_util.tree_map(one, updates), state

        return optax.GradientTransformation(
            lambda params: optax.EmptyState(), update_fn)
    from ..common.logging import get_logger

    get_logger().warning(
        "make_data_parallel_step: world size is 1 and compression=%r is "
        "neither a registry scheme name nor a Compressor — it cannot be "
        "applied locally and is dropped; multi-device meshes will reject "
        "it too", compression)
    return None


def make_data_parallel_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axes: Sequence[str] = ("dp",),
    compression: Any = Compression.none,  # Compressor class or scheme name
    partition_bytes: Optional[int] = None,
    backward_passes_per_step: int = 1,
    donate: bool = True,
    local_axis: Optional[str] = None,
):
    """Build a jitted data-parallel train step.

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` runs
    on the *local* batch shard.  The returned step function has signature
    ``step(state: TrainState, batch) -> (TrainState, metrics)`` where
    ``batch`` is a pytree whose leaves have the global batch on dim 0
    (sharded over ``axes``), and metrics = {"loss": mean loss}.  A
    ``loss_fn`` may return a third element, a dict of counts of its own
    (``lm_loss_fn``: ``moe_assignments_held``, ``moe_rows_computed``);
    they join the metrics summed over the workers, and every call of the
    step adds them to the registry's counters of their names
    (``flush_step_counts``).

    Semantics match the reference benchmark
    (example/pytorch/benchmark_byteps.py): gradients are *averaged* across
    all workers via the bucketed scheduled push_pull; BatchNorm normalizes
    per-replica (torchvision semantics) while running stats are averaged
    across replicas so the state stays replicated.

    ``local_axis`` pins which of ``axes`` hosts the local (ICI)
    reduce-scatter stage of the hierarchical reduction (docs/wire.md
    "Hierarchical reduction"); default: the innermost axis.

    .. note:: At ``world == 1`` (with ``backward_passes_per_step == 1``)
       the DistributedOptimizer wrapper is dropped — matching the
       reference's ``size()==1`` short-circuit — but any ``compression``
       passed is still honored through an equivalent local
       transformation (cast roundtrip, or error-feedback compression for
       biased registry schemes), so single- and multi-process runs see
       the same gradient numerics.  The ``opt_state`` pytree nesting
       still differs from the multi-worker chain, so **checkpoints do
       not transfer between world sizes**.
    """
    axes = tuple(axes)
    world = 1
    for ax in axes:
        world *= mesh.shape[ax]
    if world == 1 and backward_passes_per_step == 1:
        # Single-worker fast path (the reference likewise short-circuits
        # when size()==1): the push_pull wrapper is already a traced no-op
        # at world==1, but its chain nesting in opt_state costs per-call
        # dispatch on small models — drop the wrapper, keep the compression
        # numerics (a compressed multi-worker run and its single-worker
        # debug rerun must not silently diverge).
        comp_tx = _world1_compression_tx(compression)
        tx = scoped_update(optimizer)
        if comp_tx is not None:
            tx = optax.chain(comp_tx, tx)
    else:
        tx = DistributedOptimizer(
            optimizer,
            compression=compression,
            axis_name=axes,
            average=True,
            partition_bytes=partition_bytes or get_config().partition_bytes,
            backward_passes_per_step=backward_passes_per_step,
            local_axis=local_axis,
        )

    def local_step(state: TrainState, batch):
        def lf(p):
            # forward here; the backward carries the same scope inside
            # ``transpose(jvp(...))``
            with jax.named_scope(SCOPE_MODEL):
                loss, *aux = loss_fn(p, state.model_state, batch)
            return loss, aux

        (loss, (new_mstate, *counts)), grads = jax.value_and_grad(
            lf, has_aux=True)(state.params)
        # push_pull and the inner update name themselves (``tx``)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        with jax.named_scope(SCOPE_OPTIMIZER):
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(SCOPE_STEP_METRICS):
            n = jax.lax.psum(1, axes)
            loss = jax.lax.psum(loss, axes) / n
            # keep mutable model state (BN stats) replicated: average
            # across dp
            new_mstate = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, axes) / n
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                new_mstate,
            )
            metrics = {"loss": loss}
            for extra in counts:
                metrics.update({k: jax.lax.psum(v, axes)
                                for k, v in extra.items()})
        return (
            TrainState(new_params, new_opt, new_mstate, state.step + 1),
            metrics,
        )

    state_spec = P()  # params/opt state replicated across data axes
    batch_spec = P(axes)
    mapped = shard_map(
        local_step,
        mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, state_spec),
    )
    jitted = jax.jit(mapped, donate_argnums=(0,) if donate else ())
    return TrainStep(jitted, tx, mesh)


# counts of steps already dispatched whose values the device has yet to
# produce, oldest first
_PENDING: collections.deque = collections.deque()


def _count_step(counts) -> None:
    """Each count of one step grows the registry counter of its name
    (``moe_assignments_held`` -> ``moe.assignments_held``: the layer's
    prefix becomes the registry's dotted one), ``train.steps_counted``
    by one."""
    reg = get_registry()
    reg.counter("train.steps_counted", instants=False).inc()
    for name, n in counts.items():
        reg.counter(name.replace("_", ".", 1), instants=False).inc(int(n))


def flush_step_counts(wait: bool = True) -> None:
    """Add the counts of the steps dispatched so far to the registry —
    all of them (waits for the device), or with ``wait=False`` those
    whose values are there already."""
    while _PENDING and (wait or all(
            v.is_ready() for v in _PENDING[0].values())):
        _count_step(_PENDING.popleft())


def _note_counts(metrics) -> None:
    """Host side of a step whose loss function counts, after its
    dispatch: the counts wait in ``_PENDING`` until the device has them
    (no host callback in the program, no wait on the step just sent)."""
    if len(metrics) > 1:
        _PENDING.append({k: v for k, v in metrics.items() if k != "loss"})
        flush_step_counts(wait=False)


class _Counting:
    """A lowered or compiled train step whose calls feed the registry's
    counters as ``TrainStep.__call__`` does; everything else
    (``as_text``, ``memory_analysis``, ...) is the wrapped object's."""

    def __init__(self, inner):
        self._inner = inner

    def compile(self, *args, **kwargs):
        return _Counting(self._inner.compile(*args, **kwargs))

    def __call__(self, state, batch):
        out = self._inner(state, batch)
        _note_counts(out[1])
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TrainStep:
    """Callable train step bundling the jitted SPMD program with the
    *wrapped* optimizer (DistributedOptimizer chain) whose state layout the
    program expects — use ``init_state`` to build a matching TrainState."""

    def __init__(self, fn, tx: optax.GradientTransformation, mesh: Mesh):
        self._fn = fn
        self.tx = tx
        self.mesh = mesh

    def __call__(self, state, batch):
        out = self._fn(state, batch)
        _note_counts(out[1])
        return out

    def init_state(self, params, model_state=None) -> TrainState:
        state = create_train_state(params, self.tx, model_state=model_state)
        return replicate_state(state, self.mesh)

    def lower(self, state, batch):
        return _Counting(self._fn.lower(state, batch))


def make_zero_step(loss_fn, zero, model_state=None, reduce_grads=None):
    """Eager ZeRO-1 train step over the PS tier (training/zero.py).

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)``
    with ``params`` a flat ``{name: array}`` dict (the replica ``zero``
    holds); the backward pass is jitted, the optimizer/wire half runs
    on the host through ``zero.step`` (push owned span deltas, pull the
    rest — docs/parallel.md).  Returns ``step(batch) -> loss``.

    ``reduce_grads`` maps this worker's raw gradients to the
    group-reduced gradients ``zero.step`` requires (e.g. stacking over
    colocated workers through ``collectives.reduce_scatter_spans``, or
    an allreduce); None means the gradients are already reduced — the
    single-worker / pre-reduced harness case.  Mutable model state is
    not threaded (this is the eager PS path, not
    ``make_data_parallel_step``); pass BN-free losses."""
    import numpy as np

    ms = {} if model_state is None else model_state

    def lf(p, b):
        with jax.named_scope(SCOPE_MODEL):
            return loss_fn(p, ms, b)[0]

    grad_fn = jax.jit(jax.value_and_grad(lf))

    def step(batch):
        loss, grads = grad_fn(zero.params, batch)
        g = {n: np.asarray(v) for n, v in grads.items()}
        if reduce_grads is not None:
            g = reduce_grads(g)
        zero.step(g)
        return float(loss)

    return step


def shard_batch(batch, mesh: Mesh, axes: Sequence[str] = ("dp",)):
    """Place a host batch on the mesh, dim 0 sharded over ``axes``."""
    sharding = NamedSharding(mesh, P(tuple(axes)))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch
    )


def replicate_state(state, mesh: Mesh):
    sharding = NamedSharding(mesh, P())
    # Copy committed jax.Arrays before placing: device_put may alias their
    # buffers into the replicated output, and TrainState is donated into the
    # jitted step — without the copy, donation would delete the caller's
    # arrays too.  Host (numpy/scalar) leaves are always copied by
    # device_put itself, so no extra materialization for them.
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.array(x) if isinstance(x, jax.Array) else x, sharding
        ),
        state,
    )


def lm_loss_fn(model, fused_head: bool = False,
               block_n: Optional[int] = None, block_v: Optional[int] = None,
               early_exit: Optional[tuple] = None):
    """Next-token cross-entropy loss closure for a causal LM whose batch
    is ``{"tokens": [B, T]}``; fits ``make_data_parallel_step``.

    ``early_exit=(layers, weight)`` adds the LayerSkip auxiliary loss:
    ``weight * CE(first-`layers` exit)`` where the exit is the model's
    own ``ln_f`` + head applied to the truncated depth — exactly the
    truncation ``inference.truncated_draft`` builds, so a model trained
    with this term accepts its own truncated self-draft under
    speculative decoding.  Without it the early-exit readout is
    untrained and the draft is useless no matter how well the full
    model converges (measured: acceptance ~0.002 on a converged
    vanilla-trained 12L model vs 0.70-0.88 with the term — see
    bench.py's trained-speculative row).  Requires a
    ``models.transformer.Transformer`` (the truncation slices its
    ``block_i`` param subtree).

    ``fused_head=True`` routes through the Pallas fused LM-head kernel
    (ops/fused_cross_entropy.py): the model's ``hidden`` method supplies
    pre-head states and the ``lm_head`` kernel multiplies inside the
    fused op — the [B, T, vocab] logits never materialize.  The full
    B*T rows go to the kernel (keeping N block-divisible for typical
    sequence lengths); the shift-off last position rides the kernel's
    ignore-index semantics (out-of-range target → loss 0, no grad).
    Requires a model exposing ``hidden`` plus either an ``lm_head``
    Dense or tied embeddings (models/transformer.Transformer, either
    way; for tied models the head weight is the embedding transpose).
    Where the model has a multi-token-prediction module
    (``cfg.mtp_layers``) the loss gains ``cfg.mtp_loss_weight`` times the
    module's cross-entropy on the token after next, through the same
    head; where it has expert layers the closure returns a third
    element, ``{"moe_assignments_held": n, "moe_rows_computed": r}``
    (``parallel/moe.py:served``, summed over its expert layers), which
    the step reports beside ``loss``.
    ``block_n``/``block_v`` pass
    through to the kernel for vocab/batch sizes its auto-fit cannot
    divide (e.g. GPT-2's 50257).

    Padded streams: pass ``batch["labels"]`` with ``-100`` on ignored
    positions (the HF convention; ``tokens`` keep an embeddable pad id).
    The mean is over *valid* targets — ignored positions contribute
    neither loss nor denominator, in both the fused and plain branches.
    """

    def _head_weight(params, h):
        if "lm_head" in params:
            return params["lm_head"]["kernel"].astype(h.dtype)
        # tied-embedding models (tie_embeddings=True) have no
        # lm_head; the head weight is the embedding transposed.
        # tp-partitioned trees box the leaf in nn.Partitioned.
        import flax.linen as nn

        emb = params["embed"]["embedding"]
        if isinstance(emb, nn.meta.AxisMetadata):
            emb = emb.unbox()
        return emb.T.astype(h.dtype)

    def _hiddens(params, m, tokens):
        """``((h, h_mtp | None), n)``: the pre-head states — with a
        multi-token-prediction module its state too — and the expert
        layers' counts summed over the layers, ``{"moe_<count>": n}``
        (None without experts)."""
        cfg = getattr(m, "cfg", None)
        method = m.hidden_mtp if getattr(cfg, "mtp_layers", 0) else m.hidden
        if getattr(cfg, "moe_experts", 0):
            hs, stats = m.apply({"params": params}, tokens, method=method,
                                mutable=["moe_stats"])
            n = {}
            for path, leaf in jax.tree_util.tree_leaves_with_path(stats):
                name = f"moe_{path[-1].key}"
                n[name] = n.get(name, 0) + leaf
        else:
            hs, n = m.apply({"params": params}, tokens, method=method), None
        return (hs if isinstance(hs, tuple) else (hs, None)), n

    def _mtp_targets(targets):
        # position t of the module predicts token t + 2
        return jnp.roll(targets, -1, axis=1).at[:, -1].set(-100)

    def _with_mtp(head_ce, m, h, h_mtp, targets):
        loss = head_ce(h, targets)
        if h_mtp is not None:
            loss = loss + m.cfg.mtp_loss_weight * head_ce(
                h_mtp, _mtp_targets(targets))
        return loss

    def _fused_ce(params, m, tokens, targets):
        from ..ops.fused_cross_entropy import fused_linear_cross_entropy

        (h, h_mtp), n = _hiddens(params, m, tokens)

        def head_ce(h, targets):
            B, T, d = h.shape
            V = w.shape[-1]
            flat_t = targets.reshape(-1)
            per_row = fused_linear_cross_entropy(
                h.reshape(-1, d), w, flat_t, block_n, block_v,
            )
            # mean over *valid* targets only: with padded token streams
            # (HF -100 convention) a fixed B*(T-1) denominator deflates
            # the loss; the kernel already zeroes ignored rows
            valid = jnp.sum((flat_t >= 0) & (flat_t < V))
            return per_row.sum() / jnp.maximum(valid, 1).astype(
                per_row.dtype)

        with jax.named_scope(SCOPE_HEAD):
            w = _head_weight(params, h)
            return _with_mtp(head_ce, m, h, h_mtp, targets), n

    def _plain_ce(params, m, tokens, targets):
        def ce_of(logits, targets):
            with jax.named_scope(SCOPE_HEAD):
                t = targets[:, :-1]
                valid = (t >= 0) & (t < logits.shape[-1])
                # optax's integer-label CE has no ignore-index:
                # out-of-range labels produce garbage — clamp them and
                # zero their loss
                per_tok = optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], jnp.where(valid, t, 0)
                )
                per_tok = jnp.where(valid, per_tok, 0.0)
                return per_tok.sum() / jnp.maximum(valid.sum(), 1).astype(
                    per_tok.dtype)

        cfg = getattr(m, "cfg", None)
        if not (getattr(cfg, "mtp_layers", 0)
                or getattr(cfg, "moe_experts", 0)):
            # the head's matmul is inside the model's own call here (its
            # Flax scope names it); the scope covers the CE over its
            # logits
            return ce_of(m.apply({"params": params}, tokens), targets), None
        (h, h_mtp), n = _hiddens(params, m, tokens)
        return _with_mtp(
            lambda h, t: ce_of(m.apply({"params": params}, h,
                                       method=m.logits), t),
            m, h, h_mtp, targets), n

    ce = _fused_ce if fused_head else _plain_ce

    def loss_fn(params, model_state, batch):
        tokens = batch["tokens"]
        if "labels" in batch:
            # HF convention: explicit labels with -100 on padded/ignored
            # positions (tokens themselves must stay embeddable pad ids)
            targets = jnp.roll(batch["labels"], -1, axis=1)
        else:
            targets = jnp.roll(tokens, -1, axis=1)
        targets = targets.at[:, -1].set(-100)  # ignore the wrap position
        loss, held = ce(params, model, tokens, targets)
        if early_exit is not None:
            from ..inference import truncated_draft

            e_layers, e_weight = early_exit
            # truncated_draft only filters the pytree, so it traces
            # cleanly under jit/grad — and it is the SAME truncation
            # speculative_generate runs at decode time, keeping the
            # trained exit and the runtime draft in lockstep
            dmodel, dvars = truncated_draft(
                model.cfg, {"params": params}, e_layers)
            loss = loss + e_weight * ce(
                dvars["params"], dmodel, tokens, targets)[0]
        if held is not None:
            return loss, model_state, held
        return loss, model_state

    return loss_fn


def classification_loss_fn(model, train: bool = True, rngs_fn=None):
    """Standard softmax-CE loss closure for a flax vision model with
    (optional) BatchNorm state; fits ``make_data_parallel_step``."""

    def loss_fn(params, model_state, batch):
        images, labels = batch["image"], batch["label"]
        variables = {"params": params, **model_state}
        mutable = list(model_state.keys())
        kwargs = {}
        if rngs_fn is not None:
            kwargs["rngs"] = rngs_fn()
        if mutable:
            logits, new_state = model.apply(
                variables, images, train=train, mutable=mutable, **kwargs
            )
        else:
            logits = model.apply(variables, images, train=train, **kwargs)
            new_state = {}
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()
        return loss, new_state

    return loss_fn
