"""Horovod-compatible public API.

Mirrors the surface of the reference's framework plugins (SURVEY.md §2.2):
``init / shutdown / rank / size / local_rank / local_size`` (reference
operations.cc:28-91), ``push_pull(_async) / poll / synchronize / declare``
(torch/ops.py:96-218), ``broadcast_parameters /
broadcast_optimizer_state`` (torch/__init__.py:234-381) and
``DistributedOptimizer`` — re-expressed for single-controller JAX:

  * ``rank``/``size`` — in multi-process runs a "worker" is a process
    (``jax.process_index/count``); in single-process runs with a multi-device
    mesh the *devices* of the data axes are the workers, and eager
    ``push_pull`` takes contributions stacked along a leading worker axis.
  * inside a jitted/shard_mapped training step, ``push_pull`` with an
    ``axis_name`` degenerates to the bucketed collective path
    (parallel/collectives.py) — that is the hot path the reference drives
    from its C++ core loops.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .common import logging as bps_log
from .common.compile_cache import configure_compile_cache
from .common.config import get_config, reset_config
from .engine import dispatcher as _dispatcher
from .ops.compression import Compression
from .parallel import collectives as _collectives
from .parallel import mesh as _mesh_mod


def _maybe_distributed_init() -> None:
    """Multi-host bootstrap: if launched via byteps_tpu.launcher (or with the
    BYTEPS_COORDINATOR_ADDR contract set by hand), bring up JAX's distributed
    runtime — the replacement for the reference's DMLC scheduler rendezvous
    (ps::StartAsync + barrier, global.cc:197-212)."""
    import os

    if os.environ.get("BYTEPS_DISTRIBUTED_INIT", "0") != "1":
        return
    # NB: do NOT probe jax.process_count() here — it initializes the XLA
    # backend, after which jax.distributed.initialize() always raises.
    try:
        from jax._src import distributed as _jax_dist

        if getattr(_jax_dist.global_state, "client", None) is not None:
            return  # already initialized
    except Exception:
        pass
    # DMLC contract fallbacks (cfg.num_worker/worker_id mirror
    # DMLC_NUM_WORKER/DMLC_WORKER_ID — reference global.cc:105-119) let the
    # bootstrap work without the launcher's derived BYTEPS_* vars.
    cfg = get_config()
    addr = os.environ.get("BYTEPS_COORDINATOR_ADDR")
    if addr is None and cfg.enable_async:
        # async-PS workers talk to the server tier over TCP and need no
        # collective bootstrap; DMLC_PS_ROOT_URI names the *server* host
        # there, not a JAX coordinator — connecting would hang.
        return
    if addr is None and os.environ.get("DMLC_PS_ROOT_URI"):
        addr = (
            os.environ["DMLC_PS_ROOT_URI"]
            + ":" + os.environ.get("DMLC_PS_ROOT_PORT", "1234")
        )
    nproc = int(os.environ.get("BYTEPS_NUM_PROCESSES", cfg.num_worker))
    pid = int(os.environ.get("BYTEPS_PROCESS_ID", cfg.worker_id))
    if addr and nproc > 1:
        jax.distributed.initialize(
            coordinator_address=addr, num_processes=nproc, process_id=pid
        )
        bps_log.info(
            "jax.distributed initialized: process %d/%d via %s", pid, nproc, addr
        )


class _GlobalState:
    def __init__(self):
        self.initialized = False
        self.mesh = None
        self.reduce_axes: List[str] = []
        self.lock = threading.Lock()


_state = _GlobalState()


def _validate_local_contract(cfg) -> None:
    """Launcher-injected ``BYTEPS_LOCAL_RANK``/``BYTEPS_LOCAL_SIZE`` must
    match the mesh/process reality.  With hierarchical push/pull
    (docs/wire.md "Hierarchical reduction") a silently wrong local rank
    means pushing the WRONG SLICE of every gradient — corrupt global
    state, not just a mislabeled log line — so mismatches raise loudly
    at init instead of surfacing as training divergence."""
    lr, ls = cfg.local_rank, cfg.local_size
    if ls is not None and ls < 1:
        raise ValueError(f"BYTEPS_LOCAL_SIZE={ls} must be >= 1")
    nproc = jax.process_count()
    # range-check the rank only against an EXPLICIT local_size — the
    # device-count default is devices-per-process, which is the wrong
    # bound for a several-processes-per-host launcher topology
    if lr is not None and ls is not None and not 0 <= lr < ls:
        raise ValueError(
            f"BYTEPS_LOCAL_RANK={lr} is out of range for "
            f"BYTEPS_LOCAL_SIZE={ls}: under hierarchical push/pull this "
            "worker would push slice keys no group member owns (corrupt "
            "gradients). Fix the launcher's injected values.")
    if lr is not None and ls is None and nproc > 1 and lr >= nproc:
        raise ValueError(
            f"BYTEPS_LOCAL_RANK={lr} exceeds the {nproc}-process world "
            "— no host has that many colocated workers. Fix the "
            "launcher env (or set BYTEPS_LOCAL_SIZE explicitly).")
    if nproc == 1:
        if lr not in (None, 0):
            raise ValueError(
                f"BYTEPS_LOCAL_RANK={lr} but this run has a single "
                f"process: its slice-mates do not exist, so every "
                f"hierarchical push would ship only slice {lr} and drop "
                "the rest. Unset BYTEPS_LOCAL_RANK (or set it to 0).")
        if ls is not None and ls > jax.local_device_count():
            raise ValueError(
                f"BYTEPS_LOCAL_SIZE={ls} exceeds this process's "
                f"{jax.local_device_count()} devices — no mesh axis can "
                "host the local reduce-scatter. Shrink it, or launch "
                "the missing colocated workers.")
    else:
        if ls is not None and nproc % ls != 0:
            raise ValueError(
                f"BYTEPS_LOCAL_SIZE={ls} does not divide the "
                f"{nproc}-process world — hosts would disagree on the "
                "hierarchical slice layout.")
        if lr is not None and ls is not None and ls > 1 \
                and lr != jax.process_index() % ls:
            raise ValueError(
                f"BYTEPS_LOCAL_RANK={lr} contradicts process index "
                f"{jax.process_index()} under local_size {ls} (expected "
                f"{jax.process_index() % ls}): this worker would push "
                "another rank's slice. Fix the launcher env.")


def init(
    mesh: Optional[jax.sharding.Mesh] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    mesh_shape: Optional[dict] = None,
) -> None:
    """Initialize byteps_tpu (reference byteps_init, operations.cc:30-75).

    Builds (or adopts) the global device mesh and starts the eager engine.
    Safe to call more than once (idempotent, like the reference's
    ``_init_done`` latch).
    """
    with _state.lock:
        if _state.initialized:
            return
        configure_compile_cache()
        _maybe_distributed_init()
        cfg = get_config()
        _validate_local_contract(cfg)
        if mesh is None:
            shape = mesh_shape or _mesh_mod.parse_mesh_shape(cfg.mesh_shape)
            mesh = _mesh_mod.build_mesh(
                devices=devices, mesh_shape=shape or None,
                force_distributed=cfg.force_distributed,
            )
        _state.mesh = mesh
        _state.reduce_axes = _mesh_mod.reduce_axes(mesh)
        if cfg.num_worker > 1 and jax.process_count() == 1:
            bps_log.warning(
                "DMLC_NUM_WORKER=%d but only 1 process is attached — "
                "launch via byteps_tpu.launcher (or set the BYTEPS_* "
                "coordinator vars) for a multi-host run", cfg.num_worker,
            )
        _dispatcher.start_engine(mesh, _state.reduce_axes)
        # live scrape endpoint for the worker role (BYTEPS_METRICS_PORT,
        # off by default) — every role has the same /metrics + /healthz
        # surface (docs/observability.md)
        from .observability.scrape import maybe_start_metrics_server

        maybe_start_metrics_server(
            role="worker",
            health_fn=lambda: {"devices": jax.local_device_count()})
        _state.initialized = True
        bps_log.info(
            "byteps_tpu initialized: mesh %s, reduce axes %s",
            dict(mesh.shape), _state.reduce_axes,
        )


def shutdown() -> None:
    """Reference byteps_shutdown (operations.cc:77-80)."""
    with _state.lock:
        if not _state.initialized:
            return
        _dispatcher.stop_engine()
        _state.mesh = None
        _state.reduce_axes = []
        _state.initialized = False
        # release the process-default async-PS store (its wire workers
        # and heartbeat are live threads — engine/async_ps owns the
        # swap-then-close lifecycle)
        from .engine.async_ps import close_async_store

        close_async_store()
        from .common.tracing import reset_tracer
        from .observability.scrape import stop_metrics_server

        stop_metrics_server()
        reset_tracer()  # flushes the chrome trace if enabled
        reset_config()


def _require_init() -> None:
    if not _state.initialized:
        init()


def mesh() -> jax.sharding.Mesh:
    _require_init()
    return _state.mesh


def size() -> int:
    """World size = product of the mesh's data axes (the analog of
    reference byteps_size, operations.cc:84-86)."""
    _require_init()
    return _mesh_mod.world_size(_state.mesh)


def rank() -> int:
    """Worker id.  Multi-process: the process index (one worker per host,
    SPMD); single-process: 0 — per-device "ranks" only exist inside
    shard_map where ``lax.axis_index`` provides them."""
    return jax.process_index()


def local_rank() -> int:
    """Launcher-injected BYTEPS_LOCAL_RANK wins (reference
    launcher/launch.py:43-60 contract); else the process index."""
    cfg = get_config()
    return cfg.local_rank if cfg.local_rank is not None else jax.process_index()


def local_size() -> int:
    """Launcher-injected BYTEPS_LOCAL_SIZE wins; else the devices handled by
    this process (reference byteps_local_size)."""
    cfg = get_config()
    return (
        cfg.local_size if cfg.local_size is not None
        else jax.local_device_count()
    )


def declare(name: str) -> int:
    """Reference byteps_torch_declare_tensor / ops.py:185-192."""
    _require_init()
    return _dispatcher.get_engine().declare(name)


# ---------------------------------------------------------------------------
# push_pull
# ---------------------------------------------------------------------------

_name_counter = [0]


def _auto_name(prefix: str = "byteps_push_pull") -> str:
    _name_counter[0] += 1
    return f"{prefix}_{_name_counter[0]}"


_roundtrip_counter = [0]


def _maybe_roundtrip(tensor, compression, stacked: bool = False,
                     name: str = ""):
    """Apply a biased registry scheme's compress→decompress to eager
    contributions (cast schemes ride the engine's wire_dtype instead).
    ``stacked=True`` treats dim 0 as the worker axis and compresses each
    row independently — per-contribution scales, matching what each
    worker would put on a real wire.

    Seeded schemes fold (config seed, tensor name, per-process call
    counter) like the wire path's ``derive_seed``, so successive pushes
    of the same tensor move the random-k mask instead of freezing one
    coordinate subset forever.  This path is still stateless (no error
    feedback) — one-shot reductions only; training loops must use
    DistributedOptimizer, whose EF state carries the unsent mass.
    """
    scheme = getattr(compression, "scheme", None)
    if scheme is None or not scheme.biased:
        return tensor
    cfg = get_config()
    key = None
    if scheme.seeded:
        from .compression import derive_seed

        _roundtrip_counter[0] += 1
        key = jax.random.PRNGKey(derive_seed(
            cfg.compression_seed, name, _roundtrip_counter[0]))

    def one(row):
        return scheme.roundtrip(row, key=key, ratio=cfg.compression_ratio)

    return jax.vmap(one)(tensor) if stacked else one(jnp.asarray(tensor))


def push_pull(
    tensor,
    average: bool = True,
    name: Optional[str] = None,
    version: int = 0,
    priority: int = 0,
    compression: Any = Compression.none,
    axis_name: Optional[Any] = None,
    hierarchical: Optional[bool] = None,
):
    """Sum (or average) a tensor across workers.

    Reference contract (torch/ops.py:96-141, mxnet tests): result equals the
    elementwise sum over every worker's contribution, identically on all
    workers.

    Two calling modes:
      * **inside shard_map / pjit** — pass ``axis_name`` (str or tuple); the
        reduce runs as reduce-scatter + all-gather on that mesh axis.  This
        is the hot path used by DistributedOptimizer's jitted step.
      * **eager** — ``tensor`` is either one worker's contribution when
        ``size()==1``, or contributions stacked on a leading worker axis
        (shape ``[size(), ...]``).  Blocks until the result is ready.

    ``compression`` accepts a Compressor class or a registry scheme name
    (``"bf16"``, ``"onebit"``, ... — docs/compression.md).  Biased
    schemes apply statelessly here (compress→decompress on each
    contribution, no error feedback): right for one-shot reductions;
    training loops should carry EF via DistributedOptimizer instead.

    ``hierarchical`` (default: ``BYTEPS_HIERARCHICAL``) applies to the
    eager path when async-PS mode is on (``BYTEPS_ENABLE_ASYNC``): the
    contributions are reduce-scattered over the mesh's reduce axes by a
    jitted ``psum_scatter`` and only per-rank slices (``name@s{r}``)
    ride the PS wire; a jitted ``all_gather`` rebuilds the result
    on-device (docs/wire.md "Hierarchical reduction").  Note the PS
    store ACCUMULATES per name — pass a fresh (or no) name for one-shot
    reductions.  The in-graph ``axis_name`` path is already hierarchical
    by construction and ignores the flag.
    """
    compression = Compression.resolve(compression)
    if axis_name is not None:
        compressed, ctx = compression.compress(tensor)
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        out = _collectives.push_pull_shard(
            compressed.reshape(-1),
            scatter_axis=axes[-1],
            sum_axes=axes[:-1],
            average=average,
        ).reshape(tensor.shape)
        return compression.decompress(out, ctx)
    handle = push_pull_async(
        tensor, average=average, name=name, version=version,
        priority=priority, compression=compression,
        hierarchical=hierarchical,
    )
    return synchronize(handle)


def _hierarchical_ps_push_pull(stacked, name: str, average: bool) -> int:
    """The mesh-aware eager PS data path (docs/wire.md "Hierarchical
    reduction"): a jitted ``psum_scatter`` over the mesh's reduce axes
    reduces the stacked contributions so each rank holds only its
    1/local_size slice, the slices ride the async-PS wire as
    independent ``name@s{r}`` sub-tensors, and a jitted ``all_gather``
    rebuilds the pulled global state on-device.  Completes
    synchronously; the returned handle is already done."""
    from .common.types import Status
    from .engine.async_ps import get_async_store
    from .engine.hierarchical import hierarchical_push_pull

    engine = _dispatcher.get_engine()
    out = hierarchical_push_pull(
        get_async_store(), name, stacked, _state.mesh,
        axis=tuple(_state.reduce_axes), average=average)
    handle = engine.handles.allocate()
    engine.handles.mark_done(handle, Status.OK(), out)
    return handle


def push_pull_async(
    tensor,
    average: bool = True,
    name: Optional[str] = None,
    version: int = 0,
    priority: int = 0,
    compression: Any = Compression.none,
    hierarchical: Optional[bool] = None,
) -> int:
    """Async eager push_pull; returns a handle (reference torch/ops.py:144-183).

    Multi-process (multi-controller SPMD) runs: ``tensor`` is **this
    process's contribution** (every process must call with the same name, in
    the same order — the reference's declaration contract); the reduce runs
    as one jitted SPMD program over the global mesh and the handle completes
    synchronously.  Single-process runs: contributions are stacked on a
    leading worker axis and drained by the engine's scheduler threads.
    """
    _require_init()
    cfg = get_config()
    compression = Compression.resolve(compression)
    engine = _dispatcher.get_engine()
    wire = getattr(compression, "wire_dtype", None)
    if jax.process_count() > 1:
        return _multihost_push_pull(
            _maybe_roundtrip(tensor, compression, name=name or ""),
            average=average, wire=wire)
    n = size()
    tensor = jnp.asarray(tensor)
    if n == 1:
        stacked = tensor[None]
    elif tensor.shape and tensor.shape[0] == n:
        stacked = tensor
    else:
        raise ValueError(
            f"eager push_pull with size()=={n} expects contributions stacked "
            f"on a leading worker axis of length {n}; got shape {tensor.shape}. "
            "Inside a jitted step, pass axis_name= instead."
        )
    stacked = _maybe_roundtrip(stacked, compression, stacked=True,
                               name=name or "")
    hier = cfg.hierarchical if hierarchical is None else bool(hierarchical)
    if hier and cfg.enable_async and _state.reduce_axes:
        # the hierarchical eager PS path: local mesh reduce-scatter,
        # slice-keyed wire exchange, on-device all_gather rebuild.
        # Meshes without data axes keep the engine path (routing them
        # to the store would scatter over a model-parallel axis).
        # Cast compression applies per contribution (the bytes each
        # worker would put on the wire); version/priority are inert
        # here like on push_pull_async_process — the store orders by
        # first-touch name priority.
        if wire is not None:
            stacked = jnp.asarray(stacked).astype(wire).astype(
                jnp.asarray(stacked).dtype)
        return _hierarchical_ps_push_pull(stacked, name or _auto_name(),
                                          average)
    return engine.push_pull_async(
        stacked,
        name or _auto_name(),
        average=average,
        priority=priority,
        version=version,
        wire_dtype=wire,
    )


def push_pull_sparse(
    indices,
    values,
    num_rows: int,
    average: bool = False,
    axis_name: Optional[Any] = None,
):
    """Row-sparse push_pull (the reference's reserved-but-unimplemented
    ``kRowSparsePushPull``, common.h:212-216): workers contribute
    ``(indices [k], values [k, d])`` embedding-row gradients and every
    worker receives the dense ``[num_rows, d]`` sum (or mean).

    Inside shard_map pass ``axis_name`` — only the nonzero rows cross the
    wire (parallel/collectives.sparse_push_pull).  Eager mode takes
    contributions stacked on a leading worker axis (``indices [n, k]``,
    ``values [n, k, d]``) like eager push_pull, and reduces locally.
    """
    _require_init()
    if axis_name is not None:
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        return _collectives.sparse_push_pull(
            indices, values, num_rows, axes=axes, average=average
        )
    if jax.process_count() > 1:
        return _process_push_pull_sparse(indices, values, num_rows, average)
    n = size()
    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    if n == 1 and indices.ndim == 1:
        indices, values = indices[None], values[None]
    if indices.ndim != 2 or values.ndim != 3 or indices.shape[0] != n:
        raise ValueError(
            f"eager push_pull_sparse with size()=={n} expects stacked "
            f"indices [{n}, k] and values [{n}, k, d]; got "
            f"{indices.shape} / {values.shape}"
        )
    dense = jnp.zeros((num_rows, values.shape[-1]), values.dtype)
    dense = dense.at[indices.reshape(-1)].add(
        values.reshape(-1, values.shape[-1]), mode="drop")
    return dense / n if average else dense


def _process_push_pull_sparse(indices, values, num_rows: int, average: bool):
    """Cross-process eager sparse reduce, worker == process (same slot
    trick as _multihost_push_pull): the process's contribution rides in
    its first local device slot; padding slots carry ``num_rows`` indices,
    which the scatter's drop mode discards — so the mesh-wide gather+add
    equals the sum over processes."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, axes = _state.mesh, tuple(_state.reduce_axes)
    idx = np.asarray(indices)
    val = np.asarray(values)
    if idx.ndim != 1 or val.ndim != 2:
        raise ValueError(
            "multi-process eager push_pull_sparse takes this process's "
            f"contribution: indices [k], values [k, d]; got {idx.shape} / "
            f"{val.shape}")
    slots = jax.local_device_count()
    pad_idx = np.full((slots - 1,) + idx.shape, num_rows, idx.dtype)
    pad_val = np.zeros((slots - 1,) + val.shape, val.dtype)
    idx = np.concatenate([idx[None], pad_idx]) if slots > 1 else idx[None]
    val = np.concatenate([val[None], pad_val]) if slots > 1 else val[None]
    sharding = NamedSharding(mesh, P(axes))
    g_idx = jax.make_array_from_process_local_data(sharding, idx)
    g_val = jax.make_array_from_process_local_data(sharding, val)
    fn = jax.jit(_collectives.shard_map(
        lambda i, v: _collectives.sparse_push_pull(
            i[0], v[0], num_rows, axes=axes, average=False),
        mesh, in_specs=(P(axes), P(axes)), out_specs=P(),
    ))
    out = fn(g_idx, g_val)
    return out / jax.process_count() if average else out


def push_pull_async_process(
    tensor,
    average: bool = True,
    name: Optional[str] = None,
    version: int = 0,
    priority: int = 0,
    compression: Any = Compression.none,
) -> int:
    """Eager push_pull with **one worker == one process** semantics in every
    topology (the reference's Horovod contract: a training process
    contributes one tensor).  Used by the multihost path and by front-ends
    whose programs are process-replicated (e.g. ``byteps_tpu.torch``).
    With one process it is the identity; name/version/priority are accepted
    for API parity (the reduce runs synchronously as one SPMD program)."""
    del name, version, priority
    _require_init()
    compression = Compression.resolve(compression)
    wire = getattr(compression, "wire_dtype", None)
    return _multihost_push_pull(_maybe_roundtrip(tensor, compression),
                                average=average, wire=wire)


def _multihost_push_pull(tensor, average: bool, wire) -> int:
    """Cross-process eager reduce: every process contributes its local
    slots' tensors, the collective spans the whole mesh (the role of the
    reference's ps-lite ZPush/ZPull across machines, core_loops.cc:430-502).

    Runs synchronously (SPMD programs must be entered by all processes in
    the same order, so deferring to per-process scheduler threads could
    diverge); the returned handle is already complete.
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    engine = _dispatcher.get_engine()
    mesh, axes = _state.mesh, tuple(_state.reduce_axes)
    local = np.asarray(tensor)
    # One worker == one *process* here (Horovod semantics).  The mesh's
    # reduce axes span all devices; the process's single contribution goes
    # in its first local slot with zeros in the rest, so the mesh-wide sum
    # equals the sum over processes exactly — for every dtype (no division,
    # so integers stay integers) and independent of host topology.
    slots = jax.local_device_count()
    local = np.concatenate(
        [local[None], np.zeros((slots - 1,) + local.shape, local.dtype)]
    ) if slots > 1 else local[None]
    sharding = NamedSharding(mesh, P(axes))
    stacked = jax.make_array_from_process_local_data(sharding, local)
    out = _collectives.push_pull_stacked(
        stacked, mesh, axes, average=False,
        wire_dtype=np.dtype(wire).name if wire is not None else None,
    )
    if average:
        out = out / jax.process_count()
    handle = engine.handles.allocate()
    from .common.types import Status

    engine.handles.mark_done(handle, Status.OK(), out)
    return handle


def poll(handle: int) -> bool:
    """Reference torch/ops.py:185-196 (poll)."""
    _require_init()
    return _dispatcher.get_engine().poll(handle)


def synchronize(handle: int):
    """Reference torch/ops.py:204-218 (synchronize)."""
    _require_init()
    return _dispatcher.get_engine().synchronize(handle)


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------


def broadcast(
    tensor,
    root_rank: int = 0,
    name: Optional[str] = None,
    axis_name: Optional[Any] = None,
):
    """Every worker receives worker ``root_rank``'s value (reference
    broadcast contract, tests/test_mxnet.py:116-158).  Same two calling
    modes as push_pull; eager stacked input has shape ``[size(), ...]``."""
    _require_init()
    if axis_name is not None:
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        return _collectives.broadcast_shard(tensor, root_rank=root_rank, axes=axes)
    n = size()
    tensor = jnp.asarray(tensor)
    if n == 1:
        return tensor
    if not tensor.shape or tensor.shape[0] != n:
        raise ValueError(
            f"eager broadcast expects stacked shape [{n}, ...]; got {tensor.shape}"
        )
    return _collectives.broadcast_stacked(
        tensor, _state.mesh, _state.reduce_axes, root_rank=root_rank
    )


def broadcast_parameters(params, root_rank: int = 0):
    """Consistent initialization: give every worker the root's parameters
    (reference torch/__init__.py:234-262 — implemented there as
    zero-non-root + push_pull(sum)).

    Under single-controller JAX parameters are already one logical pytree;
    "broadcast" means (a) across processes in a multi-host run — done with a
    process-level broadcast from ``root_rank``'s host — and (b) placing every
    leaf on the mesh fully replicated so each device holds the same bytes.
    Returns the (possibly new) pytree — functional, no in-place mutation.
    """
    _require_init()
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        from .training.checkpoint import whole_on_every_process

        # a leaf the data-parallel step keeps on dim-0 shares spans the
        # processes: gather it whole (a collective) before the broadcast
        params = whole_on_every_process(params)
        params = multihost_utils.broadcast_one_to_all(
            params, is_source=jax.process_index() == root_rank
        )
    return jax.tree_util.tree_map(
        lambda x: _collectives.replicate(jnp.asarray(x), _state.mesh), params
    )


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Reference torch/__init__.py:265-381 — there it must tensor-ize scalar
    optimizer state to broadcast it; optax state is already a pytree of
    arrays, so the same replication path as parameters applies."""
    return broadcast_parameters(opt_state, root_rank=root_rank)


# Re-exported here so `bps.DistributedOptimizer` matches the reference name.
from .training.optimizer import DistributedOptimizer  # noqa: E402  (circular-safe)
