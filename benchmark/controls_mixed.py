#!/usr/bin/env python3
"""``controls.py`` for the cell whose layers mix attention kinds and
whose router reads the attention input: run it with ONE fault planted in
the program, through the same ``run.py``, to show that the cell's
comparison against the plain reference tells it from a sound run.

    python3 benchmark/controls_mixed.py <control> --workload <cell> \\
        --seed <n> --seconds <s> --trace 0        (or --rehearse)

A sound cell ends with ``correct: true``; under every control the run
must end otherwise — ``builders/smallthinker.py:ReferenceMismatch`` in
set-up (exit 1; the numbers beside their limits are the last line on
stdout) or ``correct: false`` on the result line (exit 0).

  window_short    every window layer attends one position fewer
  rope_on_nope    the layers without a position encoding rotate q and k
  no_rope_window  the first window layer rotates nothing
  silu_gate       the experts' gate is SiLU where the model's is ReLU
  router_ffn_in   the router reads the feed-forward input, norm_2(x')
  bf16_router     the router's logits, choice and weights in bfloat16:
                  the precision below the one the configuration states
  drop_one        every expert layer loses ONE held assignment in its
                  combine (``controls.py:drop_one``)

The faults are patched into the imported program, never written to it.
``controls.py`` cannot take these without an edit; this file goes when
it can (a ``benchmark`` PR's fold).
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.controls import drop_one  # noqa: E402


def _reconfigure(change) -> None:
    """Every ``TransformerConfig`` the family's mapping returns passes
    through ``change(cfg) -> dict of fields to replace``."""
    from byteps_tpu.integrations import smallthinker

    sound = smallthinker.smallthinker_config

    def faulty(*args, **kw):
        cfg = sound(*args, **kw)
        return dataclasses.replace(cfg, **change(cfg))

    smallthinker.smallthinker_config = faulty


def window_short():
    _reconfigure(lambda cfg: {"attn_window_layout": tuple(
        None if w is None else w - 1 for w in cfg.attn_window_layout)})


def rope_on_nope():
    _reconfigure(lambda cfg: {"rope_layout": (True,) * cfg.num_layers})


def no_rope_window():
    def change(cfg):
        first = cfg.rope_layout.index(True)
        return {"rope_layout": tuple(
            r and i != first for i, r in enumerate(cfg.rope_layout))}

    _reconfigure(change)


def silu_gate():
    _reconfigure(lambda cfg: {"moe_act": "silu"})


def router_ffn_in():
    _reconfigure(lambda cfg: {"moe_router_pre_attn": False})


def bf16_router():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from byteps_tpu.parallel import moe

    def route(x, kernel, bias, top_k, scale, scoring="softmax_topk"):
        low = jnp.bfloat16
        logits = jnp.dot(x.astype(low), kernel.astype(low))
        picked, idx = lax.top_k(logits, top_k)
        weights = jax.nn.softmax(picked, axis=-1)
        return idx.astype(jnp.int32), (weights * scale).astype(jnp.float32)

    moe.route = route


CONTROLS = {f.__name__: f for f in (
    window_short, rope_on_nope, no_rope_window, silu_gate, router_ffn_in,
    bf16_router, drop_one)}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print(__doc__, file=sys.stderr)
        return 2
    from benchmark import run

    CONTROLS[argv[0]]()
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
