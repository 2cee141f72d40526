"""Metric arithmetic of the benchmark: percentiles and the accounting of a
serving window with ramp and drain. Pure Python, no JAX."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, as numpy's default does. An infinite value (a failed
    request) sorts last, so it raises a tail only once failures reach it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def measured(requests, window_start: float, window_end: float):
    """The requests a serving window is judged on: those DUE inside it.
    Ramp traffic (due before) and drain traffic (due after) load the
    system and are not measured."""
    return [r for r in requests if window_start <= r.due < window_end]


def ttft_ms(r) -> float:
    """Due time to first token, in ms; a request with no first token
    (failed, refused, unfinished at the cap) misses: infinity."""
    if r.failed or not r.token_times:
        return math.inf
    return (r.token_times[0] - r.due) * 1e3


TPOT_MIN_TOKENS = 64


def tpot_ms(r):
    """(last token - first token) / (tokens - 1) in ms, for a finished
    request of at least TPOT_MIN_TOKENS output tokens; None for a shorter
    answer (one to three emissions: its quotient is quantisation), inf
    for a qualifying request that failed."""
    if r.max_new_tokens < TPOT_MIN_TOKENS:
        return None
    if r.failed or len(r.token_times) < r.max_new_tokens:
        return math.inf
    return ((r.token_times[-1] - r.token_times[0])
            / (len(r.token_times) - 1) * 1e3)


def tokens_in_window(requests, window_start: float, window_end: float) -> int:
    """Output tokens that reached the client inside the window, whatever
    request they belong to (ramp requests still streaming count: the
    window is judged on all the work done in it)."""
    return sum(1 for r in requests for t in r.token_times
               if window_start <= t < window_end)


def spread(values) -> float:
    """Interquartile distance over the median, as the contract's rule for
    bounds defines it (statistics.quantiles, n=4)."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
