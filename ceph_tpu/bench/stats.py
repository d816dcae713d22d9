"""Measurement statistics: warmup discard, repeats, robust summaries.

A single timed loop gives a point value whose error bars are unknown —
and the run-to-run spread is part of the reading.  Every published metric therefore
carries median/IQR/min/max over N post-warmup repeats next to the point
value, in the versioned schema (schema.py).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence


def _percentile(sorted_xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile of an ascending sequence."""
    n = len(sorted_xs)
    if n == 1:
        return float(sorted_xs[0])
    pos = q * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return float(sorted_xs[lo] * (1 - frac) + sorted_xs[hi] * frac)


def summarize(samples: Sequence[float]) -> Dict[str, Any]:
    """{n, median, iqr, min, max} of the samples (no warmup handling —
    the caller discards warmup before summarizing)."""
    if not samples:
        raise ValueError("summarize() needs at least one sample")
    xs = sorted(float(x) for x in samples)
    return {
        "n": len(xs),
        "median": _percentile(xs, 0.5),
        "iqr": _percentile(xs, 0.75) - _percentile(xs, 0.25),
        "min": xs[0],
        "max": xs[-1],
    }


def repeat_measure(fn: Callable[[], float], repeats: int = 5,
                   warmup: int = 1) -> Dict[str, Any]:
    """Run ``fn`` warmup+repeats times, discard the warmup samples, and
    return ``summarize`` of the rest plus the raw samples.

    ``fn`` returns one sample (e.g. one FencedTiming's throughput).
    Warmup runs absorb compile + cache-population cost; they are timed
    but excluded from the summary and reported under "warmup_samples"
    so a pathological first run is still visible.
    """
    if repeats < 1:
        raise ValueError("repeats must be >= 1")
    warm: List[float] = [float(fn()) for _ in range(max(warmup, 0))]
    xs: List[float] = [float(fn()) for _ in range(repeats)]
    out = summarize(xs)
    out["samples"] = xs
    if warm:
        out["warmup_samples"] = warm
    return out
