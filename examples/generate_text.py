"""Train a tiny causal LM on a synthetic sequence-copy task, then sample
from it with the KV-cache generation loop (byteps_tpu/inference.py).

The task: each sequence is ``[pattern, pattern, pattern, ...]`` for a
random 4-token pattern, so a trained model asked to continue a prompt of
two pattern repeats should keep echoing the pattern — visible proof that
prefill + cached decode reproduce the model the training loop built.

Run (any backend)::

    python examples/generate_text.py --steps 300
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from byteps_tpu.inference import make_generate_fn
from byteps_tpu.models import Transformer, TransformerConfig


def make_batch(rng, batch, seq_len, vocab, period=4):
    pat = jax.random.randint(rng, (batch, period), 3, vocab)
    reps = seq_len // period + 1
    return jnp.tile(pat, (1, reps))[:, :seq_len]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--num-kv-heads", type=int, default=None,
                   help="GQA/MQA: shared K/V heads (must divide 4); "
                        "shrinks the KV cache by the group factor")
    args = p.parse_args()

    cfg = TransformerConfig(
        vocab_size=args.vocab, num_layers=2, num_heads=4,
        num_kv_heads=args.num_kv_heads, d_model=128,
        d_ff=256, max_seq_len=args.seq_len + args.max_new_tokens,
        dtype=jnp.float32)
    model = Transformer(cfg)
    rng = jax.random.PRNGKey(0)
    tokens = make_batch(rng, args.batch_size, args.seq_len, args.vocab)
    variables = model.init(jax.random.PRNGKey(1), tokens)
    params = variables["params"]
    tx = optax.adam(3e-3)
    opt_state = tx.init(params)

    # LayerSkip training mode: the auxiliary early-exit CE trains
    # ln_f+head to read the first layer's output, which is what makes
    # the 1-layer truncated self-draft below actually get accepted
    # (docs/inference.md "Free self-drafts need LayerSkip training")
    from byteps_tpu.training import lm_loss_fn

    loss_closure = lm_loss_fn(model, early_exit=(1, 0.5))

    @jax.jit
    def train_step(params, opt_state, toks):
        loss, grads = jax.value_and_grad(
            lambda p: loss_closure(p, {}, {"tokens": toks})[0])(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for step in range(args.steps):
        rng, sub = jax.random.split(rng)
        toks = make_batch(sub, args.batch_size, args.seq_len, args.vocab)
        params, opt_state, loss = train_step(params, opt_state, toks)
        if step % 50 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}", flush=True)

    # prompt = two repeats of a fresh pattern; the model should continue it
    prompt = make_batch(jax.random.PRNGKey(99), 4, 8, args.vocab)
    fn = make_generate_fn(model, args.max_new_tokens,
                          temperature=args.temperature)
    out = fn({"params": params}, prompt, jax.random.PRNGKey(7))
    gen = np.asarray(out["tokens"])
    want = np.asarray(make_batch(
        jax.random.PRNGKey(99), 4, 8 + args.max_new_tokens,
        args.vocab)[:, 8:])
    acc = float((gen == want).mean())
    for row in range(4):
        print(f"prompt {np.asarray(prompt[row]).tolist()} -> "
              f"{gen[row].tolist()}")
    print(f"pattern-continuation accuracy: {acc:.2%}")

    # speculative decoding with the trained model's own first layer as
    # draft (inference.truncated_draft): on TRAINED weights the early
    # layers carry most of the next-token signal, so acceptance is high
    # — the property a random-init model cannot show
    from byteps_tpu.inference import speculative_generate, truncated_draft

    dmodel, dvars = truncated_draft(cfg, {"params": params}, 1)
    sp = speculative_generate(model, {"params": params}, dmodel, dvars,
                              prompt, args.max_new_tokens, gamma=4)
    # speculative decoding is greedy-only: its contract is agreement
    # with the GREEDY generation, so compare against that even when the
    # demo above sampled
    if args.temperature == 0:
        greedy = gen
    else:
        g0 = make_generate_fn(model, args.max_new_tokens, temperature=0)
        greedy = np.asarray(
            g0({"params": params}, prompt, jax.random.PRNGKey(7))["tokens"])
    sp_agree = float((np.asarray(sp["tokens"]) == greedy).mean())
    print(f"speculative (1-layer self-draft): acceptance "
          f"{float(sp['acceptance']):.2%}, agreement with greedy "
          f"{sp_agree:.2%}")


if __name__ == "__main__":
    main()
