"""The comparisons that decide ``correct`` against a plain reference
(pure numpy; the processes that hold the chip feed them)."""

from __future__ import annotations

import numpy as np


def logit_gaps(ref_rows: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """For each emitted token, how far the reference's logit for it lies
    below the reference's maximum at that position (0 where the system
    picked the reference's own argmax).  ``ref_rows [n, vocab]`` are the
    reference's logits at the positions that predicted ``tokens [n]``.

    Logits, not tokens, are compared: with random weights the largest
    logit changes on rounding, so a flip costs a small gap — while a
    wrong block, position or head picks a token the reference gives a
    typical logit, several standard deviations under its maximum."""
    ref_rows = np.asarray(ref_rows, np.float64)
    tokens = np.asarray(tokens)
    return ref_rows.max(axis=-1) - ref_rows[np.arange(len(tokens)), tokens]


def teacher_forced(prompt: np.ndarray, tokens: np.ndarray, width: int):
    """The reference's input for a probe — prompt followed by all but
    the last emitted token, right-padded to ``width`` (causal attention:
    padding cannot reach back) — and the slice of its logit rows that
    predicted the emitted tokens."""
    plen, new = len(prompt), len(tokens)
    seq = np.zeros((width,), np.int32)
    seq[:plen] = prompt
    seq[plen:plen + new - 1] = tokens[:-1]
    return seq, slice(plen - 1, plen - 1 + new)
