"""ZeRO-1-style optimizer-state sharding over the PS tier.

The replicated eager PS loop (scripts/chaos_smoke.py, docs/wire.md)
keeps FULL optimizer state on every worker and pushes a FULL gradient
mutation per worker per step.  This module shards both by parameter
*span*: worker ``r`` of a ``world``-sized ownership group

  * holds momentum ONLY for the spans it owns (client optimizer-state
    bytes drop ``world``-fold);
  * computes the optimizer update for those spans client-side and
    pushes just the resulting parameter *delta* as its own
    ``name@z{r}`` wire key (per-step mutation wire bytes drop
    ``world``-fold — pulls are reads, not mutations);
  * pulls the other ranks' updated ``name@z{q}`` spans (one windowed
    ``pull_many`` fan-out) to rebuild its full parameter replica.

The PS tier needs NOTHING new: ``name@z{r}`` is an ordinary wire key,
so partitioning (``#p{i}``), wire compression + error feedback (the
EF residual is keyed per wire name — ``WireCompressor.residual_bytes``
shows it sharding alongside the momentum), version-guard retry dedup,
and failover re-seeding all apply per span for free.  Better: span
ownership RESTORES the single-writer-per-key condition the version
guard needs (docs/resilience.md "Exactly-once retried mutations") even
in multi-worker runs, because exactly one rank ever mutates a given
span key.  The hierarchical layer never re-slices span keys
(``hierarchical.is_sliced_name`` knows ``@z``).

Bit-equality contract: the update rule is
:func:`~byteps_tpu.training.optimizer.sgd_momentum_update` — shared
with the replicated baseline and elementwise — so given identical
reduced gradients, the sharded group's final parameters are
bitwise-identical to a replicated single-worker loop
(tests/test_zero.py).  Gradient reduction itself is out of scope here:
feed grads already summed across data-parallel workers (on-mesh via
``collectives.reduce_scatter_spans``, whose span layout matches
:func:`zero_spans` exactly, or a plain allreduce).

Honest CPU-host caveats: this is the *eager* PS data path — host numpy
math, one wire round trip batch per phase — built to pin the
byte/state accounting (tests/test_zero.py), not to win wall-clock on a
single host.  See docs/parallel.md.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..common.config import get_config
from .optimizer import sgd_momentum_update

ZERO_SEP = "@z"


def zero_key(name: str, rank: int) -> str:
    """Wire key of ``name``'s span ``rank`` — an ordinary PS tensor."""
    return f"{name}{ZERO_SEP}{rank}"


def zero_spans(n: int, world: int) -> List[Tuple[int, int]]:
    """``[(start, stop)]`` flat spans of the ``world`` ownership chunks
    of an ``n``-element tensor: equal ``ceil(n/world)`` chunks, ragged
    (possibly empty) tail — the same layout ``lax.psum_scatter`` /
    ``collectives.reduce_scatter_spans`` yield, so an on-mesh gradient
    reduce-scatter drops each rank's summed gradient span exactly on
    its owner.  Unlike ``hierarchical.slice_spans`` empty tail spans
    are allowed: an empty span simply has no wire key (every rank
    derives the same span table, so nobody ever asks for one)."""
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    c = -(-n // world) if n else 0
    return [(min(r * c, n), min((r + 1) * c, n)) for r in range(world)]


def make_optimizer_state(store, params: Dict[str, np.ndarray], **kw):
    """Config-driven factory: ``BYTEPS_ZERO=1`` (``Config.zero``) picks
    :class:`ShardedOptimizerState`, otherwise the replicated baseline —
    so a training loop opts into ZeRO with an env knob, no code change
    (docs/parallel.md)."""
    if get_config().zero:
        return ShardedOptimizerState(store, params, **kw)
    kw.pop("world", None)
    kw.pop("rank", None)
    return ReplicatedOptimizerState(store, params, **kw)


class ShardedOptimizerState:
    """Client half of the ZeRO-1 sharding: one instance per worker.

    ``params`` is a ``{name: array}`` dict (the full replica every
    worker keeps for the forward/backward pass — ZeRO-1 shards
    optimizer state, not parameters).  ``store`` is any RemoteStore-
    shaped client (``init_tensor``/``push_delta``/``pull``, optionally
    ``pull_many``).

    Step protocol (split-phase, so a caller can overlap compute):

      1. ``push_updates(grads)`` — for every owned non-empty span:
         momentum update via the shared ``sgd_momentum_update``, push
         the parameter delta to the span's wire key, fold it into the
         local replica.
      2. ``pull_params()`` — one fan-out pull of every NON-owned span
         key, folded into the local replica; returns the params dict.

    ``step(grads)`` does both.  ``state_bytes()`` is the client
    optimizer-state footprint the tests pin (momentum only —
    the params replica is identical in both legs by design).
    """

    def __init__(self, store, params: Dict[str, np.ndarray], *,
                 world: int = 0, rank: Optional[int] = None,
                 lr: float = 0.01, momentum: float = 0.9,
                 init: bool = True):
        cfg = get_config()
        self.store = store
        # world=0 defers to the BYTEPS_ZERO_WORLD knob, then the DMLC
        # worker count — the launcher-injected group size
        self.world = (int(world) or int(getattr(cfg, "zero_world", 0))
                      or max(1, cfg.num_worker))
        self.rank = int(cfg.worker_id if rank is None else rank)
        if not 0 <= self.rank < self.world:
            raise ValueError(
                f"rank {self.rank} outside the ownership group "
                f"[0, {self.world})")
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.params: Dict[str, np.ndarray] = {}
        self._spans: Dict[str, List[Tuple[int, int]]] = {}
        self._m: Dict[str, np.ndarray] = {}  # momentum, OWNED spans only
        for name, value in params.items():
            if ZERO_SEP in name:
                raise ValueError(
                    f"parameter name {name!r} contains the reserved "
                    f"ZeRO span marker {ZERO_SEP!r}")
            arr = np.ascontiguousarray(np.asarray(value))
            self.params[name] = arr
            self._spans[name] = zero_spans(arr.size, self.world)
            a, b = self._spans[name][self.rank]
            if b > a:
                self._m[name] = np.zeros(b - a, arr.dtype)
        if init:
            self._init_store()

    def _init_store(self) -> None:
        """INIT every non-empty span key with the initial parameter
        bytes.  First-push-wins on the server, so every rank seeding
        all keys with identical values is idempotent — and each INIT
        reply primes the client's failover seed (``_last_global``), so
        a mid-run shard death can re-home any span from any worker."""
        for name, arr in self.params.items():
            flat = arr.reshape(-1)
            for r, (a, b) in enumerate(self._spans[name]):
                if b > a:
                    self.store.init_tensor(zero_key(name, r), flat[a:b])

    # ------------------------------------------------------------- step

    def push_updates(self, grads: Dict[str, np.ndarray]) -> None:
        """Phase 1: momentum-update the OWNED span of every gradient,
        push the resulting parameter delta as this rank's span key, and
        fold it into the local replica.  ``grads`` must be the
        already-reduced (summed over data-parallel workers) gradients;
        extra names raise — a silently ignored gradient would freeze
        its parameter while the loss keeps moving."""
        for name, g in grads.items():
            if name not in self.params:
                raise KeyError(f"unknown parameter {name!r}")
            a, b = self._spans[name][self.rank]
            if b <= a:
                continue  # tensor smaller than the group: no owned span
            arr = self.params[name]
            gspan = np.ascontiguousarray(
                np.asarray(g, arr.dtype).reshape(-1)[a:b])
            self._m[name], delta = sgd_momentum_update(
                self._m[name], gspan, self.lr, self.momentum)
            self.store.push_delta(zero_key(name, self.rank), delta)
            arr.reshape(-1)[a:b] += delta

    def pull_params(self) -> Dict[str, np.ndarray]:
        """Phase 2: pull every NON-owned span key (one windowed fan-out
        when the store supports ``pull_many``) and fold the owners'
        updated bytes into the local replica."""
        keys = []
        for name in self.params:
            keys.extend(
                (name, q, a, b)
                for q, (a, b) in enumerate(self._spans[name])
                if q != self.rank and b > a)
        wire = [zero_key(name, q) for name, q, _, _ in keys]
        pull_many = getattr(self.store, "pull_many", None)
        if pull_many is not None:
            pulled = pull_many(wire)
        else:  # duck-typed store: serial pulls
            pulled = {k: self.store.pull(k) for k in wire}
        for (name, q, a, b), k in zip(keys, wire):
            arr = self.params[name]
            span = np.asarray(pulled[k], arr.dtype).reshape(-1)
            if span.size != b - a:
                raise ValueError(
                    f"span {k!r} came back with {span.size} elements, "
                    f"expected {b - a} — ownership tables disagree "
                    f"across the group (mismatched world sizes?)")
            arr.reshape(-1)[a:b] = span
        return self.params

    def step(self, grads: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """``push_updates`` then ``pull_params`` — one training step.

        Bit-equality at ``world > 1`` requires every rank's
        ``push_updates`` for step N to land before any rank's
        ``pull_params`` for step N reads its spans.  In a real
        deployment the per-step gradient collective provides that
        ordering; when simulating several ranks in one process, drive
        the two phases explicitly (push all ranks, then pull all
        ranks) instead of calling ``step`` rank-by-rank."""
        self.push_updates(grads)
        return self.pull_params()

    # ------------------------------------------------------ accounting

    def state_bytes(self) -> int:
        """Client optimizer-state bytes held (momentum spans): the
        number that must drop ``~world``-fold vs a replicated client
        (ISSUE 20 acceptance: >= 1.8x at world=2)."""
        return sum(int(m.nbytes) for m in self._m.values())

    def owned_spans(self) -> Dict[str, Tuple[int, int]]:
        """``{name: (start, stop)}`` of this rank's non-empty spans."""
        out = {}
        for name, spans in self._spans.items():
            a, b = spans[self.rank]
            if b > a:
                out[name] = (a, b)
        return out


class ReplicatedOptimizerState:
    """The A/B baseline: FULL momentum client-side, FULL parameter-
    delta mutation per step, one ordinary wire key per tensor — the
    pre-ZeRO eager PS loop, behind the same split-phase API so the
    tests drive both legs with one harness.  Uses the same
    ``sgd_momentum_update`` rule, so a ``world=1`` sharded group and
    this baseline are bitwise-identical by construction."""

    def __init__(self, store, params: Dict[str, np.ndarray], *,
                 lr: float = 0.01, momentum: float = 0.9,
                 init: bool = True):
        self.store = store
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.params = {n: np.ascontiguousarray(np.asarray(v))
                       for n, v in params.items()}
        self._m = {n: np.zeros(v.size, v.dtype)
                   for n, v in self.params.items()}
        if init:
            for name, arr in self.params.items():
                store.init_tensor(name, arr.reshape(-1))

    def push_updates(self, grads: Dict[str, np.ndarray]) -> None:
        for name, g in grads.items():
            arr = self.params[name]
            gflat = np.ascontiguousarray(
                np.asarray(g, arr.dtype).reshape(-1))
            self._m[name], delta = sgd_momentum_update(
                self._m[name], gflat, self.lr, self.momentum)
            self.store.push_delta(name, delta)
            arr.reshape(-1)[:] += delta

    def pull_params(self) -> Dict[str, np.ndarray]:
        return self.params

    def step(self, grads):
        self.push_updates(grads)
        return self.pull_params()

    def state_bytes(self) -> int:
        return sum(int(m.nbytes) for m in self._m.values())
