"""Independent naive timer quantiles: the oracle of the aggregator's
timer deployment (BASELINE config #4: p50/p95/p99 per timer id and
resolution window).

What upstream's ``Timer`` aggregation answers per (id, window)
(``src/aggregator/aggregation/timer.go`` over ``quantile/cm/stream.go``)
with the sketch's rank error taken to zero, as plain numpy: no jit, no
arenas, no window ring, no code shared with ``m3_tpu/aggregator``.  A
sample belongs to the window ``[t // r * r, + r)`` of its own time and
is emitted at the window's end.

Per group of n samples the quantile q is the sample of rank
``ceil(q * n)`` (1-based, at least 1) in ascending order of value:
nearest rank, a selection, never an interpolation.  ``quantiles`` gives
it twice:

* ``f32``  selected among ``float32(values)`` — the precision the
           system carries a timer sample at (the packed sample word);
           the system's answers equal these bit for bit;
* ``f64``  selected among the f64 values as sent — what the f32 answer
           is within 2^-24 relative of (rounding to f32 is monotone, so
           the rank-th smallest f32 image is the image of the rank-th
           smallest f64 value).

Departure from upstream, stated: ``cm.Stream`` answers within a rank
error of eps * n; this is the exact rank.  NaN samples are not handled
(callers send none).
"""

from __future__ import annotations

import numpy as np

TIMER = 2                           # metrics.types.MetricType.TIMER
P50, P95, P99 = 14, 19, 20          # metrics.aggregation.AggregationType
QUANTILE = {P50: 0.5, P95: 0.95, P99: 0.99}


def ranks(q: float, n: np.ndarray) -> np.ndarray:
    """0-based position of the q-quantile among n sorted samples."""
    return np.maximum(np.ceil(q * n).astype(np.int64), 1) - 1


def select(series: np.ndarray, win: np.ndarray, values: np.ndarray,
           qs) -> dict:
    """Groups of (series, window) -> {series, window, count, q: value of
    rank ceil(q n)}, one entry per group that holds a sample, sorted by
    (series, window).  `values` in the dtype to select in."""
    order = np.lexsort((values, win, series))
    s, w, v = series[order], win[order], values[order]
    head = np.ones(len(s), bool)
    head[1:] = (s[1:] != s[:-1]) | (w[1:] != w[:-1])
    starts = np.flatnonzero(head)
    n = np.diff(np.append(starts, len(s)))
    out = {"series": s[starts], "window": w[starts], "count": n}
    for q in qs:
        out[q] = v[starts + ranks(q, n)]
    return out


def quantiles(series, times, values, resolution: int,
              qs=(0.5, 0.95, 0.99)) -> dict:
    """-> {series, window_end, count, f32: {q: f64 array}, f64: {q:
    f64 array}} per (series, window) group, sorted by (series,
    window)."""
    series = np.asarray(series, np.int64)
    win = np.asarray(times, np.int64) // resolution
    values = np.asarray(values, np.float64)
    lo = select(series, win, values.astype(np.float32), qs)
    hi = select(series, win, values, qs)
    return {
        "series": hi["series"],
        "window_end": (hi["window"] + 1) * resolution,
        "count": hi["count"],
        "f32": {q: lo[q].astype(np.float64) for q in qs},
        "f64": {q: hi[q] for q in qs},
    }


def expected(ids, series, times, values, resolution: int,
             types=(P50, P95, P99)) -> dict:
    """{(id, window_end, aggregation type): (f32 selection, f64
    selection)} the service must emit, once each."""
    r = quantiles(series, times, values, resolution,
                  tuple(QUANTILE[t] for t in types))
    out = {}
    for t in types:
        q = QUANTILE[t]
        for i, end, a, b in zip(r["series"].tolist(),
                                r["window_end"].tolist(),
                                r["f32"][q].tolist(), r["f64"][q].tolist()):
            out[(ids[i], end, t)] = (a, b)
    return out
