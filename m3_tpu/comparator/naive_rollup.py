"""Independent naive rollup: the aggregator service's oracle.

What upstream's ``CounterElem`` / ``GaugeElem`` compute per (id,
resolution window) (``src/aggregator/aggregation/counter.go``,
``gauge.go``), as plain numpy in f64/i64: no jit, no arenas, no window
ring, no code shared with ``m3_tpu/aggregator``.  A sample belongs to
the window ``[t // r * r, + r)`` of its own time and is emitted at the
window's end.

Per group, in order of arrival:

* ``sum``   counters: exact i64, then f64; gauges: f64 adds in arrival
            order (upstream's ``a.sum += value``);
* ``min`` / ``max``  the selected sample's value, bit for bit;
* ``last``  gauges only: the value at the greatest time, the FIRST to
            arrive among equal times (upstream replaces ``last`` only
            when ``timestamp.After(a.lastAt)``);
* ``count`` samples in the group.

Departures from upstream, stated: a counter has no ``last`` (upstream's
``IsValidForCounter`` leaves ``Last`` out, and so does the engine's
mask: a deployment that lists ``LAST`` for counters gets sum/min/max); a
NaN gauge is not handled here (upstream skips it in sum and counts it;
callers of this oracle send none).
"""

from __future__ import annotations

import numpy as np

COUNTER, GAUGE = 1, 3          # metrics.types.MetricType values
LAST, MIN, MAX, SUM = 1, 2, 3, 7   # metrics.aggregation.AggregationType
VALID = {COUNTER: (MIN, MAX, SUM), GAUGE: (LAST, MIN, MAX, SUM)}


def rollup(series: np.ndarray, times: np.ndarray, values: np.ndarray,
           resolution: int, integer: bool) -> dict:
    """Groups of (series, window) over samples given in arrival order.
    -> {series, window_end, sum, min, max, last, count}, one entry per
    group that holds a sample, sorted by (series, window)."""
    series = np.asarray(series, np.int64)
    times = np.asarray(times, np.int64)
    win = times // resolution
    arrival = np.arange(len(series))
    by_arrival = np.lexsort((arrival, win, series))
    s, w = series[by_arrival], win[by_arrival]
    head = np.ones(len(s), bool)
    head[1:] = (s[1:] != s[:-1]) | (w[1:] != w[:-1])
    starts = np.flatnonzero(head)
    v = np.asarray(values)[by_arrival]
    if integer:
        v = v.astype(np.int64)
    out = {
        "series": s[starts],
        "window_end": (w[starts] + 1) * resolution,
        "sum": np.add.reduceat(v, starts).astype(np.float64),
        "min": np.minimum.reduceat(v, starts).astype(np.float64),
        "max": np.maximum.reduceat(v, starts).astype(np.float64),
        "count": np.diff(np.append(starts, len(s))),
    }
    # last: greatest time, first arrival among equal times
    by_time = np.lexsort((-arrival, times, win, series))
    ends = np.append(starts[1:], len(s)) - 1
    out["last"] = np.asarray(values, np.float64)[by_time][ends]
    return out


def expected(ids, metric_types, series, times, values, resolution: int,
             types: dict) -> dict:
    """{(id, window_end, aggregation type): value} the service must
    emit, once each.  ``metric_types[i]`` is series i's type, ``types``
    the deployment's {metric type: aggregation types asked for}: those
    not valid for the metric type are left out, as upstream does."""
    metric_types = np.asarray(metric_types)
    series = np.asarray(series)
    lanes = {LAST: "last", MIN: "min", MAX: "max", SUM: "sum"}
    out = {}
    for mt in (COUNTER, GAUGE):
        sel = np.flatnonzero(metric_types[series] == mt)
        if not len(sel):
            continue
        r = rollup(series[sel], np.asarray(times)[sel],
                   np.asarray(values)[sel], resolution, mt == COUNTER)
        for t in types.get(mt, ()):
            if t not in VALID[mt]:
                continue
            for i, end, val in zip(r["series"].tolist(),
                                   r["window_end"].tolist(),
                                   r[lanes[t]].tolist()):
                out[(ids[i], end, t)] = val
    return out
