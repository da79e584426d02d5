"""Checkpoint / resume.

The reference delegates checkpointing to the frameworks and only supplies
the *consistency* half: ``broadcast_parameters`` / ``broadcast_optimizer_state``
so every worker resumes from the root's state (SURVEY.md §5
"Checkpoint / resume"; torch/__init__.py:234-381, keras/callbacks.py:28-31).

The TPU rebuild owns the whole story: orbax-backed save/restore of the
functional TrainState plus the same broadcast-on-resume contract —
``restore_checkpoint(..., broadcast=True)`` replicates every leaf across the
mesh exactly like the reference's zero-non-root + push_pull trick did.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..common import logging as bps_log


def _checkpointer():
    import orbax.checkpoint as ocp

    if jax.process_count() == 1:
        return ocp.PyTreeCheckpointer()
    # The root writes and every process reads ON ITS OWN (below): orbax
    # must not wait at a barrier for processes that never call it.
    me = jax.process_index()
    alone = ocp.options.MultiprocessingOptions(
        primary_host=me, active_processes={me},
        barrier_sync_key_prefix=f"bps_p{me}")
    return ocp.Checkpointer(
        ocp.PyTreeCheckpointHandler(multiprocessing_options=alone),
        multiprocessing_options=alone)


def whole_on_every_process(state: Any) -> Any:
    """``state`` with every ``jax.Array`` leaf that lies sharded over a
    mesh (the data-parallel step keeps a share's moments ``P(dp)`` on
    dim 0, training/step.py) gathered to a fully replicated array of the
    same shape; every other leaf as it came.  The gather is a collective
    over the leaf's mesh: in a multi-process job EVERY process must call
    this, in the same order — a process can read a sharded leaf whole
    only after it."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    by_mesh = {}
    for k, x in enumerate(leaves):
        if (isinstance(x, jax.Array) and not x.is_fully_replicated
                and isinstance(x.sharding, NamedSharding)):
            by_mesh.setdefault(x.sharding.mesh, []).append(k)
    for mesh, ks in by_mesh.items():
        gathered = jax.jit(
            lambda xs: xs,
            out_shardings=NamedSharding(mesh, PartitionSpec()),
        )([leaves[k] for k in ks])
        for k, x in zip(ks, gathered):
            leaves[k] = x
    return treedef.unflatten(leaves)


def _to_host(x):
    if not hasattr(x, "dtype"):
        return x
    if (isinstance(x, jax.Array) and not x.is_fully_replicated
            and not x.is_fully_addressable):
        raise ValueError(
            f"a {x.shape} leaf sharded as {x.sharding} spans processes and "
            "cannot be read whole on one; shard it over a named mesh "
            "(save_checkpoint gathers those) or gather it before saving")
    return np.asarray(x)


def save_checkpoint(path: str, state: Any, force: bool = True) -> str:
    """Save a pytree (TrainState or any params tree) to ``path``.

    Multi-host: only process 0 writes (the reference's root-centric model);
    call on every process — a leaf sharded across the mesh is gathered
    first, by all of them (``whole_on_every_process``), then non-roots
    no-op.  The checkpoint holds every leaf whole, whatever the world
    that wrote it sharded.
    """
    path = os.path.abspath(path)
    state = whole_on_every_process(state)
    if jax.process_index() != 0:
        return path
    # orbax wants fully-addressable host arrays
    host_state = jax.tree_util.tree_map(_to_host, state)
    _checkpointer().save(path, host_state, force=force)
    bps_log.info("checkpoint saved to %s", path)
    return path


def restore_checkpoint(
    path: str,
    template: Any = None,
    broadcast: bool = True,
    root_rank: int = 0,
) -> Any:
    """Restore a pytree from ``path``.

    ``template`` (same structure, for dtype/shape guidance) is optional.
    With ``broadcast=True`` the restored tree is pushed through
    ``broadcast_parameters`` so every worker/device holds the root's bytes —
    the reference's resume-consistency contract.
    """
    path = os.path.abspath(path)

    def _load():
        if template is not None:
            return _checkpointer().restore(path, item=template)
        return _checkpointer().restore(path)

    if jax.process_count() > 1:
        # save_checkpoint writes only on process 0: process 0 is therefore
        # always the loader, and the broadcast sources from it regardless
        # of root_rank (the reference's root-loads-then-broadcast pattern)
        root_rank = 0
        if jax.process_index() == root_rank:
            restored = _load()
        else:
            try:
                restored = _load()
            except Exception:
                if template is None:
                    raise FileNotFoundError(
                        f"checkpoint {path} not readable on process "
                        f"{jax.process_index()} and no template given; "
                        "multi-host restore without a shared filesystem "
                        "requires template="
                    )
                if not broadcast:
                    # without the broadcast the template (fresh init) would
                    # silently diverge from the root's restored state
                    raise RuntimeError(
                        f"checkpoint {path} not readable on process "
                        f"{jax.process_index()} and broadcast=False: "
                        "cannot fall back to the template without diverging "
                        "from the root — pass broadcast=True or make the "
                        "checkpoint readable on every host"
                    )
                restored = template
        if not broadcast:
            return restored
        import byteps_tpu as bps

        return bps.broadcast_parameters(restored, root_rank=root_rank)

    restored = _load()
    if broadcast:
        import byteps_tpu as bps

        restored = bps.broadcast_parameters(restored, root_rank=root_rank)
    return restored


class CheckpointManager:
    """Rolling checkpoint manager (keep last k, save every n steps)."""

    def __init__(self, directory: str, save_every: int = 1000, keep: int = 3):
        self.directory = os.path.abspath(directory)
        self.save_every = max(1, save_every)
        self.keep = keep
        os.makedirs(self.directory, exist_ok=True)

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def steps(self):
        out = []
        if not os.path.isdir(self.directory):
            return out
        for d in os.listdir(self.directory):
            if d.startswith("step_"):
                try:
                    out.append(int(d[len("step_"):]))
                except ValueError:
                    pass
        return sorted(out)

    def maybe_save(self, state: Any, step: int) -> Optional[str]:
        if step % self.save_every != 0:
            return None
        path = save_checkpoint(self._step_dir(step), state)
        if jax.process_index() == 0:
            for old in self.steps()[: -self.keep] if self.keep > 0 else []:
                import shutil

                shutil.rmtree(self._step_dir(old), ignore_errors=True)
        return path

    def restore_latest(self, template: Any = None, broadcast: bool = True):
        steps = self.steps()
        if not steps:
            return None, -1
        step = steps[-1]
        return (
            restore_checkpoint(self._step_dir(step), template, broadcast),
            step,
        )
