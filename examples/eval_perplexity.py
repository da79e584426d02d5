"""Perplexity evaluation with the fused LM-head kernel — the fused
cross-entropy's winning configuration (forward-only: faster than the
naive path AND never allocates the [N, vocab] logits; see
ops/fused_cross_entropy.py).  Evaluates a causal LM over a token stream::

    python examples/eval_perplexity.py --seq-len 1024 --batches 8
    python examples/eval_perplexity.py --tiny     # CPU smoke
"""

from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp

import byteps_tpu as bps
from byteps_tpu.models import Transformer, TransformerConfig
from byteps_tpu.training import lm_loss_fn


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--batches", type=int, default=8)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()

    bps.init()
    if args.tiny:
        cfg = TransformerConfig(vocab_size=256, num_layers=2, num_heads=2,
                                d_model=32, d_ff=64,
                                max_seq_len=args.seq_len)
    else:
        cfg = TransformerConfig(vocab_size=32000, num_layers=12,
                                num_heads=12, d_model=768, d_ff=3072,
                                max_seq_len=args.seq_len,
                                dtype=jnp.bfloat16)
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((args.batch_size, args.seq_len), jnp.int32))["params"]

    # the library's fused LM-head loss path (training.lm_loss_fn):
    # hidden states + lm_head kernel into the Pallas kernel, no
    # [B, T, vocab] logits buffer; mean is over B*(T-1) real targets
    loss_fn = jax.jit(
        lambda p, tokens: lm_loss_fn(model, fused_head=True)(
            p, {}, {"tokens": tokens})[0])

    def batch(i):
        # synthetic eval stream (swap for real token batches)
        return jax.random.randint(
            jax.random.PRNGKey(i),
            (args.batch_size, args.seq_len), 0, cfg.vocab_size)

    per_batch = args.batch_size * (args.seq_len - 1)
    float(loss_fn(params, batch(0)))  # warmup: compile outside the timing

    total_nll, total_tokens = 0.0, 0
    t0 = time.time()
    for i in range(args.batches):
        total_nll += float(loss_fn(params, batch(i))) * per_batch
        total_tokens += per_batch
    dt = time.time() - t0
    ppl = math.exp(total_nll / total_tokens)
    print(f"perplexity {ppl:.2f} over {total_tokens} tokens "
          f"({total_tokens / dt:.0f} tok/s)")
    bps.shutdown()


if __name__ == "__main__":
    main()
