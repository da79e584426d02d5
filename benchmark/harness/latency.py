"""From the load generator's request records to client-side samples.

Pure functions over the records ``loadgen.py`` writes (all times are
the host's wall clock, seconds), kept apart from the process plumbing
so the arithmetic is tested on hand-made records.
"""

from __future__ import annotations

from typing import List


def open_loop_samples(records: List[dict], window) -> dict:
    """Requests DUE inside the window are the attempted ones.  Time to
    first token counts from the due instant (a stall's wait is charged
    to the requests it delays); a request that failed, was refused or
    did not finish has no sample and counts as failed.  Gaps between
    consecutive token frames are pooled over all attempted requests.
    ``late_ms`` is how long after its due instant each request was
    actually sent — the generator's own delay."""
    w0, w1 = window
    ttft, itl, late, failed = [], [], [], 0
    attempted = [r for r in records if w0 <= r["due"] < w1]
    for r in attempted:
        late.append((r["sent"] - r["due"]) * 1e3)
        t = r["token_times"]
        if r["error"] is not None or not t:
            failed += 1
            continue
        ttft.append((t[0] - r["due"]) * 1e3)
        itl.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return {"attempted": len(attempted), "failed": failed,
            "ttft_ms": ttft, "itl_ms": itl, "late_ms": late}


def closed_loop_samples(records: List[dict], window) -> dict:
    """Requests that ENDED inside the window are the attempted ones
    (a closed loop's requests straddle both edges; what is still in
    flight at the end is cut, not failed).

    The rate is taken between first-token events: a request's first
    token frame tells the client that its whole prompt has been
    processed, and every later frame that one more token has.  Between
    the first and the last first-token event inside the window, the
    tokens whose processing the client saw finish — the prompts of the
    requests whose first token arrived after the first event and up to
    the last, plus every other token frame in that interval — over the
    time between the two events.  Both ends are events, so a document
    falling either side of the window's edge does not move the rate;
    and prompts finish prefill one after another, evenly spaced by
    their work, where whole requests finish in clumps (a rate between
    completions spread by 17-24 % over the same runs, PR 22)."""
    w0, w1 = window
    inside = [r for r in records if w0 <= r["done"] < w1]
    ok = [r for r in inside if r["error"] is None]
    firsts = sorted((r["token_times"][0], r["prompt_len"]) for r in records
                    if r["token_times"] and w0 <= r["token_times"][0] < w1)
    rate = None
    if len(firsts) >= 2:
        t0, t1 = firsts[0][0], firsts[-1][0]
        prompts = sum(p for _, p in firsts[1:])
        later = sum(1 for r in records for t in r["token_times"][1:]
                    if t0 < t <= t1)
        rate = (prompts + later) / (t1 - t0)
    ttft = [(r["token_times"][0] - r["sent"]) * 1e3 for r in ok]
    itl = [(b - a) * 1e3 for r in ok
           for a, b in zip(r["token_times"], r["token_times"][1:])]
    return {"attempted": len(inside), "failed": len(inside) - len(ok),
            "completed": len(ok), "prompts_finished": len(firsts),
            "tokens_per_s": rate, "ttft_ms": ttft, "itl_ms": itl,
            "late_ms": [(r["sent"] - r["due"]) * 1e3 for r in inside]}


def decode_contexts(records: List[dict], t0: float, t1: float) -> list:
    """Context length (prompt + tokens so far, the new one included) of
    every token frame read in ``[t0, t1)`` except each request's first
    (which a prefill chunk produced, not a decode tick): one entry per
    decode-kernel row that did useful work in that interval."""
    out = []
    for r in records:
        for k, t in enumerate(r["token_times"]):
            if k and t0 <= t < t1:
                out.append(r["prompt_len"] + k)
    return out
