"""Benchmark timing helpers.

JAX dispatch is asynchronous: a timed region must end with a barrier or
it measures the enqueue.  Two barriers are in use here —
``jax.block_until_ready`` and ``readback_barrier`` (a value readback
that data-depends on the computation chain, whose checksum also proves
the computation ran).  On this runtime they agree:
measured on a TPU v5e under jax 0.9.0 / libtpu 0.0.34 (``chip_smoke.py``,
PR 21), ten 12-layer d768 train steps read 62.8 ms/step ended by
``block_until_ready`` and 63.1 ms/step ended by the readback — within
0.5 %, cold and warm.  ``block_until_ready`` IS a completion barrier
here; use it, or ``readback_barrier`` where the checksum is wanted.
The readback runs one tiny program of its own: call it once before the
timed region, or its first compile lands inside (that mistake read as a
7-10 ms/step "disagreement" in this PR's first measurement).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def chained_grad_loop(loss_fn, k: int):
    """Jitted ``fn(q, k, v)`` running ``k`` iterations of
    ``value_and_grad(loss_fn)`` on-device, each feeding ``x + 1e-6*dx``
    back as the next inputs — the data dependence keeps every iteration
    live under XLA while leaving the measured program unchanged.  Pair
    two of these (different ``k``) with ``two_k_differenced_time``."""
    g = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))

    def loop(q, kk, v):
        def body(i, carry):
            qc, kc, vc = carry
            _, (dq, dk, dv) = g(qc, kc, vc)
            return (qc + 1e-6 * dq, kc + 1e-6 * dk, vc + 1e-6 * dv)

        qo, _, _ = jax.lax.fori_loop(0, k, body, (q, kk, v))
        return jnp.sum(qo.astype(jnp.float32))

    return jax.jit(loop)


def two_k_differenced_time(fn_s, fn_l, args, k_s: int, k_l: int,
                           reps: int = 4):
    """Per-iteration device time via TWO-K DIFFERENCING.

    ``fn_s``/``fn_l`` are the same jitted program iterated ``k_s`` and
    ``k_l`` times on-device (e.g. a ``lax.fori_loop`` chaining a kernel
    through its own outputs).  Every host call carries a fixed
    dispatch + readback cost that a per-call or per-chunk estimator
    folds into the kernel time; the median of (t_long - t_short) over
    adjacent call pairs cancels it exactly.

    Returns seconds/iteration, or ``None`` when the median difference
    is non-positive (host noise exceeded the signal — the caller must
    fall back AND say so).
    """
    readback_barrier(fn_s(*args), fn_l(*args))  # warm / compile
    diffs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        readback_barrier(fn_s(*args))
        ts = time.perf_counter() - t0
        t0 = time.perf_counter()
        readback_barrier(fn_l(*args))
        tl = time.perf_counter() - t0
        diffs.append(tl - ts)
    diffs.sort()
    n = len(diffs)
    med = (diffs[n // 2] if n % 2
           else 0.5 * (diffs[n // 2 - 1] + diffs[n // 2]))
    if med <= 0:
        return None
    return med / (k_l - k_s)


def readback_barrier(*trees) -> float:
    """Force true completion of everything the given pytrees depend on, by
    summing one leaf of each to host.  Returns the checksum (useful to print
    — it proves the computation really ran)."""
    total = 0.0
    for tree in trees:
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            continue
        leaf = leaves[0]
        total += float(jnp.sum(jnp.asarray(leaf).astype(jnp.float32)))
    return total
