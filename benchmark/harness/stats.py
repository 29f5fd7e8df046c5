"""Host-side statistics: raw-sample percentiles, spreads, and deltas of the
program's own histograms and counters over a window."""

from __future__ import annotations

import math
import statistics


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile of raw samples (0 < q <= 100): the smallest
    sample with at least q% of the samples at or below it."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def spread(values) -> float:
    """Interquartile range over the median (statistics.quantiles, n=4)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def histogram_totals(store) -> dict:
    """{full name: (count, sum)} of every histogram in a stats Store."""
    with store._reg_lock:
        hists = list(store._histograms.values())
    out = {}
    for h in hists:
        s = h.snapshot()
        out[h.name] = (int(s["count"]), float(s["sum"]))
    return out


def delta(before: dict, after: dict) -> dict:
    """Per-name (count, sum) change between two histogram_totals."""
    out = {}
    for name, (c, s) in after.items():
        c0, s0 = before.get(name, (0, 0.0))
        out[name] = (c - c0, s - s0)
    return out
