"""Percentile and spread arithmetic (no JAX, no numpy needed)."""

from __future__ import annotations

from typing import Optional, Sequence


def pctl(vals: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, ``None`` on an empty sample.  The same
    rank formula as the program's own histograms
    (``observability/metrics.py:_nearest_rank``, used by
    ``bench_serve.py:_pctl``), copied so the yardstick cannot move with
    the program."""
    if not vals:
        return None
    s = sorted(vals)
    k = max(0, min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1)))))
    return s[k]


def median(vals: Sequence[float]) -> Optional[float]:
    """Plain median (mean of the two middle values on an even count)."""
    if not vals:
        return None
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile's
    rank — the guide asks for at least ten before a tail is trusted."""
    if n <= 0:
        return 0
    k = max(0, min(n - 1, int(round(q / 100.0 * (n - 1)))))
    return n - 1 - k


def spread(vals: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run spread."""
    if len(vals) < 2:
        return None
    med = median(vals)
    if not med:
        return None
    return (pctl(vals, 75) - pctl(vals, 25)) / abs(med)


def describe(name: str, vals: Sequence[float], q: float, unit: str) -> dict:
    """The earlier-line record printed beside every percentile metric:
    sample count, median, the stated percentile, samples beyond it."""
    return {"metric": name, "unit": unit, "n": len(vals),
            "median": median(vals), f"p{q:g}": pctl(vals, q),
            "beyond": samples_beyond(len(vals), q)}
