"""The arithmetic from client timestamps to end-to-end metrics."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def mean(values) -> float:
    values = list(values)
    return sum(values) / len(values)


def tokens_in_window(arrivals_by_request, t0: float, t1: float) -> int:
    """Tokens whose arrival time at the client lies in [t0, t1)."""
    return sum(1 for ts in arrivals_by_request for t in ts if t0 <= t < t1)


def slice_rates(arrivals_by_request, t0: float, t1: float, width_s: float) -> list:
    """Tokens/s in each ``width_s`` slice of the window (the last one may be
    shorter)."""
    n = max(1, math.ceil((t1 - t0) / width_s - 1e-9))
    counts = [0] * n
    for ts in arrivals_by_request:
        for t in ts:
            if t0 <= t < t1:
                counts[min(int((t - t0) / width_s), n - 1)] += 1
    return [c / min(width_s, (t1 - t0) - i * width_s) for i, c in enumerate(counts)]


def token_gaps(arrivals_by_request, t0: float, t1: float) -> list:
    """Every gap between consecutive tokens of one request, in seconds, whose
    later token arrived in [t0, t1)."""
    return [b - a for ts in arrivals_by_request for a, b in zip(ts, ts[1:]) if t0 <= b < t1]


def whole_step_rate(step_ends, step_starts, tokens_per_step: int, t0: float, t1: float):
    """Tokens/s over the whole steps that ended inside [t0, t1]: their
    tokens over the time from the start of the first of them to the end of
    the last. Never steps x tokens / window: a cut step would be lost.
    Returns (rate, n_steps)."""
    inside = [(s, e) for s, e in zip(step_starts, step_ends) if s >= t0 and e <= t1]
    if not inside:
        raise ValueError("no whole step inside the window")
    return len(inside) * tokens_per_step / (inside[-1][1] - inside[0][0]), len(inside)
