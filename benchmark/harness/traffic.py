"""The one traffic generator: a mix is a data file of parameters.

``requests(mix, seed, vocab, max_seq, horizon_s)`` turns a file under
``benchmark/traffic/`` into a list of requests — a pure function of its
arguments, so the same seed gives the same arrivals, lengths and
tokens, and another seed gives others.  What a mix can say:

``kind: "open_loop"``
    ``arrivals``: ``{"process": "poisson", "rate_rps": r}``.
``kind: "closed_loop"``
    ``clients``: how many callers each wait for their reply before
    sending the next request of the list.

Both take ``prompt_len`` and ``output_len`` distributions
(``lognormal``: median, sigma; ``uniform``; both clipped to
``min``/``max``).  Tokens are uniform over the vocabulary.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class TrafficRequest:
    index: int
    due_s: Optional[float]      # open loop: offset from the ramp's start
    prompt: np.ndarray          # int32 [T]
    max_new_tokens: int


def draw_len(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    kind = dist["dist"]
    if kind == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], n)
    elif kind == "uniform":
        x = rng.uniform(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def arrival_times(arrivals: dict, rng: np.random.Generator,
                  horizon_s: float) -> np.ndarray:
    """Arrival instants in ``[0, horizon_s)`` of a Poisson process."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    n = rng.poisson(float(arrivals["rate_rps"]) * horizon_s)
    return np.sort(rng.uniform(0.0, horizon_s, n))


def requests(mix: dict, seed: int, vocab: int, max_seq: int,
             horizon_s: float, count: Optional[int] = None
             ) -> List[TrafficRequest]:
    """The requests of one run.  Open loop: every arrival in
    ``[0, horizon_s)``.  Closed loop: ``count`` requests to replay in
    order (the caller asks for more than it can finish)."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])   # schedule
    tok = np.random.default_rng([int(seed), 0x70CE45])   # contents
    if mix["kind"] == "open_loop":
        due = arrival_times(mix["arrivals"], rng, horizon_s)
        n = len(due)
    elif mix["kind"] == "closed_loop":
        if count is None:
            raise ValueError("a closed-loop mix needs a request count")
        due, n = None, int(count)
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    out_len = draw_len(mix["output_len"], rng, n)
    p_len = draw_len(mix["prompt_len"], rng, n)
    # a request must fit the engine: prompt + output <= max_seq
    p_len = np.minimum(p_len, max_seq - out_len)
    if n and p_len.min() < 1:
        raise ValueError("output lengths leave no room for a prompt "
                         f"within max_seq {max_seq}")
    return [TrafficRequest(
        index=i, due_s=None if due is None else float(due[i]),
        prompt=tok.integers(0, vocab, int(p_len[i])).astype(np.int32),
        max_new_tokens=int(out_len[i])) for i in range(n)]
