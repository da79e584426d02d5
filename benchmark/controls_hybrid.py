#!/usr/bin/env python3
"""``controls.py`` for the cell whose blocks are one sublayer each, some
of them Mamba-2 mixers: run it with ONE fault planted in the program,
through the same ``run.py``, to show that the cell's comparison against
the plain reference tells it from a sound run.

    python3 benchmark/controls_hybrid.py <control> --workload <cell> \\
        --seed <n> --seconds <s> --trace 0        (or --rehearse)

A sound cell ends with ``correct: true``; under every control the run
must end otherwise — ``builders/nemotron_h.py:ReferenceMismatch`` in
set-up (exit 1; the numbers beside their limits are the last line on
stdout) or ``correct: false`` on the result line (exit 0).

  no_softplus   ``dt`` goes into the scan without its softplus
  conv_late     the convolution is one tap late: position t sees t + 1
  group_mod     head h reads group ``h % G`` of B and C, not ``h // (H/G)``
  norm_first    the gated norm normalises BEFORE the gate, over all
                channels as one group
  bf16_state    the scan carries its state from chunk to chunk in
                bfloat16: the precision below the one the configuration
                states for it
  no_skip       ``D`` left out: the scan's output lacks ``D X``
  relu_act      the experts (routed and shared) compute ``relu`` where
                the model's compute ``relu^2``
  bf16_router   the router's scores, choice and weights in bfloat16
                (``controls.py:bf16_router``)
  drop_one      every expert layer loses ONE held assignment in its
                combine (``controls.py:drop_one``)

The faults are patched into the imported program, never written to it.
``controls.py`` cannot take these without an edit; this file goes when
it can (a ``benchmark`` PR's fold).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.controls import bf16_router, drop_one  # noqa: E402


def _wrap_scan(change) -> None:
    """The mixer's ``ssd_scan`` becomes ``change(sound)``."""
    import importlib

    mod = importlib.import_module("byteps_tpu.ops.ssd_scan")
    mod.ssd_scan = change(mod.ssd_scan)


def no_softplus():
    import jax.numpy as jnp

    def change(sound):
        def scan(x, dt, *rest, **kw):
            # softplus undone: what the in-projection and the bias gave
            return sound(x, jnp.log(jnp.expm1(dt)), *rest, **kw)
        return scan

    _wrap_scan(change)


def conv_late():
    import jax.numpy as jnp

    from byteps_tpu.models import transformer

    sound = transformer.causal_depthwise_conv

    def late(u, w):
        ahead = jnp.concatenate([u[:, 1:], jnp.zeros_like(u[:, :1])], axis=1)
        return sound(ahead, w)

    transformer.causal_depthwise_conv = late


def group_mod():
    import jax.numpy as jnp

    def change(sound):
        def scan(x, dt, A, B, C, D, **kw):
            H, G = x.shape[2], B.shape[2]
            hb = H // G
            # the heads' grid [G, hb] transposed: head h = g hb + j lies
            # where head j G + g lay and reads THAT head's group — j =
            # h % G where hb == G, as at the cell's 8 x 8
            perm = jnp.arange(H).reshape(G, hb).T.reshape(-1)
            inv = jnp.argsort(perm)
            y = sound(x[:, :, perm], dt[:, :, perm], A[perm], B, C, D[perm],
                      **kw)
            return y[:, :, inv]
        return scan

    _wrap_scan(change)


def norm_first():
    from byteps_tpu.models import transformer

    def first(y, z, scale, groups, eps):
        import jax
        import jax.numpy as jnp

        g = y.astype(jnp.float32)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1,
                                       keepdims=True) + eps)
        return (g * scale * jax.nn.silu(z.astype(jnp.float32))).astype(
            y.dtype)

    transformer.gated_group_norm = first


def bf16_state():
    import importlib

    import jax.numpy as jnp

    importlib.import_module(
        "byteps_tpu.ops.ssd_scan").CARRY_DTYPE = jnp.bfloat16


def no_skip():
    import jax.numpy as jnp

    def change(sound):
        def scan(x, dt, A, B, C, D, **kw):
            return sound(x, dt, A, B, C, jnp.zeros_like(D), **kw)
        return scan

    _wrap_scan(change)


def relu_act():
    import jax

    from byteps_tpu.parallel import moe

    moe.UNGATED["relu2"] = jax.nn.relu


CONTROLS = {f.__name__: f for f in (
    no_softplus, conv_late, group_mod, norm_first, bf16_state, no_skip,
    relu_act, bf16_router, drop_one)}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print(__doc__, file=sys.stderr)
        return 2
    from benchmark import run

    CONTROLS[argv[0]]()
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
