"""Timing arithmetic of the benchmark: percentiles, per-request time
per output token, run-to-run spread. TPOT per request and not per gap and TTFT
from the scheduled instant are ``bench.py:_open_loop_load``'s; its
ceil-based percentile over tens of samples is not (the original stays
for a later PR to delete)."""
import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], pct: float) -> float:
    """Linearly interpolated percentile (the value at rank
    ``pct / 100 x (n - 1)`` of the sorted samples, as numpy's default
    gives it): a median or a tail of a few dozen requests then moves
    smoothly when one request does."""
    if not values:
        raise ValueError('percentile of no samples')
    if not 0 <= pct <= 100:
        raise ValueError(f'pct must be in [0, 100], got {pct}')
    ordered = sorted(values)
    rank = pct / 100.0 * (len(ordered) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def tpot_ms(first_token_s: float, last_token_s: float,
            n_tokens: int) -> Optional[float]:
    """Time per output token of ONE request, in ms: (last - first) /
    (tokens - 1). Per request and not per gap: the engine hands
    tokens back several to a dispatch, so single gaps are mostly
    zeros. None for a one-token reply (no gap exists)."""
    if n_tokens < 2:
        return None
    return (last_token_s - first_token_s) / (n_tokens - 1) * 1e3


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of
    the median, quartiles as ``statistics.quantiles(n=4)`` gives
    them — the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values: Sequence[float]) -> float:
    """The spread as the driver reads it for tightness: the
    quartile spread of the set without its run farthest from the
    median, where leaving that run out narrows it."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = [v for i, v in enumerate(values) if i != far]
    return min(quartile_spread(values), quartile_spread(rest))
