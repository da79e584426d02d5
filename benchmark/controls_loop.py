#!/usr/bin/env python3
"""``controls.py`` for the cell of a looped model (``builders/ouro.py``):
run it with ONE fault planted in the program, through the same
``run.py``, to show that the cell's comparison against the plain
reference tells it from a sound run.

    python3 benchmark/controls_loop.py <control> --workload <cell> \\
        --seed <n> --seconds <s> --trace 0        (or --rehearse)

A sound cell ends with ``correct: true``; under every control the run
must end otherwise — ``builders/ouro.py:ReferenceMismatch`` in set-up
(exit 1; the numbers beside their limits are the last line on stdout).

  no_post_norm      the norms on the sublayers' outputs are made, not
                    applied: a pre-norm layer
  pre_norm_carry    the next pass is fed the state BEFORE the final norm
  no_remaining_mass the last pass takes lam_R times what reaches it, not
                    all of it: the distribution sums to less than one
  gate_before_norm  the exit gate reads the state before the final norm
  readout_late      pass t's readout reads pass t + 1's state (the last
                    pass its own)
  entropy_sign      the loss ADDS the entropy's weight times the entropy
  fp8_operands      every projection's operands rounded to float8 (e4m3,
                    one scale a tensor): the precision below the
                    configuration's bfloat16

The faults are patched into the imported program, never written to it.
``controls.py`` cannot take these without an edit; this file goes when
it can (a ``benchmark`` PR's fold).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def no_post_norm():
    import flax.linen as nn

    from byteps_tpu.models import transformer

    sound = transformer.TransformerConfig.make_norm

    class Unapplied(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],))
            return x

    def make_norm(self, name):
        if name in ("ln1_out", "ln2_out"):
            return Unapplied(name=name)
        return sound(self, name)

    transformer.TransformerConfig.make_norm = make_norm


def _pass_reading(carry_normed: bool, gate_normed: bool):
    """A pass that feeds the next pass, and the gate, the state before
    or after the final norm."""
    import jax

    from byteps_tpu.common.tracing import SCOPE_HEAD, SCOPE_LOOP_READOUT
    from byteps_tpu.models import transformer

    def loop_pass(model, x, _):
        for block in model.blocks:
            x = block(x)
        with jax.named_scope(SCOPE_HEAD), jax.named_scope(
                SCOPE_LOOP_READOUT):
            s = model.ln_f(x)
            gate = model.exit_gate(s if gate_normed else x)[..., 0]
        return (s if carry_normed else x), (s, gate)

    transformer.loop_pass = loop_pass


def pre_norm_carry():
    _pass_reading(carry_normed=False, gate_normed=True)


def gate_before_norm():
    _pass_reading(carry_normed=True, gate_normed=False)


def no_remaining_mass():
    import jax

    from byteps_tpu.models import transformer

    sound = transformer.exit_log_probs

    def short(exit_logits):
        logp = sound(exit_logits)
        last = jax.nn.log_sigmoid(exit_logits[-1].astype(logp.dtype))
        return logp.at[-1].add(last)

    transformer.exit_log_probs = short


def readout_late():
    import jax.numpy as jnp

    from byteps_tpu.models import transformer

    sound = transformer.Transformer.hidden

    def hidden(self, tokens):
        out = sound(self, tokens)
        if self.cfg.loop_steps == 1:
            return out
        states, gates = out
        return jnp.concatenate([states[1:], states[-1:]]), gates

    transformer.Transformer.hidden = hidden


def entropy_sign():
    from byteps_tpu.training import step

    sound = step.exit_weighted_loss

    def flipped(ce, log_p, valid, entropy_weight):
        return sound(ce, log_p, valid, -entropy_weight)

    step.exit_weighted_loss = flipped


def fp8_operands():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import transformer

    sound = transformer.QuantDense

    def fp8(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale

    class Fp8Dense(sound):
        """``QuantDense``'s float path with both operands rounded."""

        @nn.compact
        def __call__(self, x):
            feats = (self.features if isinstance(self.features, tuple)
                     else (self.features,))
            kernel = self.param("kernel", self.kernel_init,
                                tuple(x.shape[-self.in_axes:]) + feats)
            dims = ((tuple(range(x.ndim - self.in_axes, x.ndim)),
                     tuple(range(self.in_axes))), ((), ()))
            out = self.accum_dtype or self.dtype
            y = jax.lax.dot_general(
                fp8(x.astype(self.dtype)), fp8(kernel.astype(self.dtype)),
                dims, preferred_element_type=out)
            if self.use_bias:
                y = y + self.param("bias", nn.initializers.zeros,
                                   feats).astype(out)
            return y

    transformer.QuantDense = Fp8Dense


CONTROLS = {f.__name__: f for f in (
    no_post_norm, pre_norm_carry, no_remaining_mass, gate_before_norm,
    readout_late, entropy_sign, fp8_operands)}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print(__doc__, file=sys.stderr)
        return 2
    from benchmark import run

    CONTROLS[argv[0]]()
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
