"""Window arithmetic on the benchmark's own clock: rates over every token
delivered in the window, and tails over every sample in it."""
from __future__ import annotations

import math

MIN_BEYOND = 10   # a tail needs at least this many samples beyond it


class TooFewSamples(ValueError):
    pass


def tail(values, q: float, min_beyond: int = MIN_BEYOND) -> float:
    """Nearest-rank ``q`` quantile (0 < q < 1) over all ``values``. Refuses
    a tail with fewer than ``min_beyond`` samples beyond it: a 95th
    percentile of a dozen samples is a maximum."""
    xs = sorted(values)
    n = len(xs)
    rank = max(1, math.ceil(q * n))
    if n - rank < min_beyond:
        raise TooFewSamples(f"p{q * 100:g} of {n} samples leaves "
                            f"{n - rank} beyond it; need {min_beyond}")
    return xs[rank - 1]


def tokens_in_window(token_times: dict, t_open: float, t_close: float) -> int:
    """Every token delivered in [t_open, t_close), finished request or not."""
    return sum(1 for ts in token_times.values() for t in ts
               if t_open <= t < t_close)


def rate(token_times: dict, t_open: float, t_close: float) -> float:
    return tokens_in_window(token_times, t_open, t_close) / (t_close - t_open)


def inter_token_gaps(token_times: dict, t_open: float, t_close: float) -> list:
    """Gaps between consecutive tokens of one request, both delivered in
    the window."""
    gaps = []
    for ts in token_times.values():
        for a, b in zip(ts, ts[1:]):
            if t_open <= a and b < t_close:
                gaps.append(b - a)
    return gaps


def first_token_waits(token_times: dict, due: dict, t_open: float,
                      t_close: float) -> list:
    """Time from each request's due time to its first token, for requests
    due in the window. A request that never delivered a token is missing
    and is reported by the caller as failed."""
    return [token_times[rid][0] - d for rid, d in due.items()
            if t_open <= d < t_close and token_times.get(rid)]


def per_slice(token_times: dict, t_open: float, t_close: float,
              width: float) -> list:
    """Tokens delivered in each ``width``-second slice of the window."""
    n = max(1, math.ceil((t_close - t_open) / width))
    out = [0] * n
    for ts in token_times.values():
        for t in ts:
            if t_open <= t < t_close:
                out[min(n - 1, int((t - t_open) // width))] += 1
    return out


def silences(token_times: dict, t_open: float, t_close: float,
             top: int) -> list:
    """The ``top`` longest stretches of the window in which no token was
    delivered, as [start after t_open, seconds]."""
    ts = sorted(t for v in token_times.values() for t in v
                if t_open <= t < t_close)
    edges = [t_open] + ts + [t_close]
    gaps = sorted(((b - a, a - t_open) for a, b in zip(edges, edges[1:])),
                  reverse=True)[:top]
    return [[round(at, 3), round(g, 4)] for g, at in gaps]
