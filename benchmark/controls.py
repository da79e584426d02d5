#!/usr/bin/env python3
"""Run a cell of the expert-layer configuration with ONE fault planted
in the program, through the same ``run.py``, to show that the cell's
comparison against the plain reference tells it from a sound run.

    python3 benchmark/controls.py <control> --workload <cell> \
        --seed <n> --seconds <s> --trace 0        (or --rehearse)

A sound cell ends with ``correct: true``; under every control the run
must end otherwise — ``builders/joyai_flash.py:ReferenceMismatch`` in
set-up (exit 1; the numbers beside their limits are the last line on
stdout) or ``correct: false`` on the result line (exit 0).

  bf16_router  the router's scores, choice and weights in bfloat16: the
               precision below the one the configuration states
               (bfloat16 products, float32 router)
  drop_one     every expert layer loses ONE held assignment in its
               combine (what a capacity limit does to a full expert)
  no_shared    the shared expert adds nothing
  no_mtp       the loss lacks the multi-token-prediction term

The faults are patched into the imported program, never written to it.
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def bf16_router():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from byteps_tpu.parallel import moe

    def route(x, kernel, bias, top_k, scale):
        low = jnp.bfloat16
        scores = jax.nn.sigmoid(jnp.dot(x.astype(low), kernel.astype(low)))
        _, idx = lax.top_k(scores + bias.astype(low), top_k)
        picked = jnp.take_along_axis(scores, idx, axis=-1)
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), (weights * scale).astype(jnp.float32)

    moe.route = route


def drop_one():
    import jax.numpy as jnp

    from byteps_tpu.parallel import moe

    sound = moe.plan

    def plan(idx, first, count, tile=moe.ROW_TILE):
        p = sound(idx, first, count, tile)
        held = p.held.reshape(-1)
        return p._replace(held=held.at[jnp.argmax(held)].set(
            False).reshape(p.held.shape))

    moe.plan = plan


def no_shared():
    from byteps_tpu.models import transformer

    class MLP(transformer.MLP):
        def __call__(self, x):
            y = super().__call__(x)
            return y * 0 if self.name == "shared" else y

    transformer.MLP = MLP


def no_mtp():
    from byteps_tpu.integrations import deepseek_v3

    sound = deepseek_v3.deepseek_v3_config
    deepseek_v3.deepseek_v3_config = lambda *a, **kw: dataclasses.replace(
        sound(*a, **kw), mtp_loss_weight=0.0)


CONTROLS = {f.__name__: f for f in (bf16_router, drop_one, no_shared,
                                    no_mtp)}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print(__doc__, file=sys.stderr)
        return 2
    from benchmark import run

    CONTROLS[argv[0]]()
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
