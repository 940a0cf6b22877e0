"""The plain numpy reference of the aggregator's 1 m rollup: per
(series, window) sum, min, max, last and count of the samples the
generator sent, as upstream's ``CounterElem`` / ``GaugeElem`` compute
them.  Imports nothing of the program.

The generator's samples form a matrix: ``vals[k, i]`` is series i's
sample of interval k at time ``ts[k]``, and ``present[k, i]`` says
whether it was sent (a control leaves one frame out).  A sample belongs
to the window of its own time, ``ts // resolution``, and is emitted at
the window's end.  Per group, in order of arrival (interval order):
``sum`` by adding row after row (counters in i64, exact; gauges in the
precision ``gauge_dtype``), ``min`` / ``max`` by selection, ``last`` the
value at the greatest time (the first to arrive among equal times).
Counters have no ``last`` (upstream's IsValidForCounter).
"""

from __future__ import annotations

import numpy as np

LANES = ("sum", "min", "max", "last")
AGG_TYPE = {"last": 1, "min": 2, "max": 3, "sum": 7}   # the wire's numbers
COUNTER, GAUGE = 1, 3


def rollup(ts: np.ndarray, vals: np.ndarray, types: np.ndarray,
           resolution: int, present: np.ndarray | None = None,
           gauge_dtype=np.float64):
    """-> (window ends (Wn,), lanes {name: (Wn, N) f64}, count (Wn, N)).
    A lane of a (window, series) with no sample is NaN and its count 0;
    so is a counter's `last`."""
    wins = np.unique(ts // resolution)
    n = vals.shape[1]
    is_counter = types == COUNTER
    out = {k: np.full((len(wins), n), np.nan) for k in LANES}
    count = np.zeros((len(wins), n), np.int64)
    with np.errstate(over="ignore", invalid="ignore"):
        g = vals.astype(gauge_dtype)      # the gauges' stored precision
    for wi, w in enumerate(wins):
        rows = np.flatnonzero(ts // resolution == w)
        c_sum = np.zeros(n, np.int64)
        g_sum = np.zeros(n, gauge_dtype)
        lo = np.full(n, np.inf)
        hi = np.full(n, -np.inf)
        last = np.full(n, np.nan)
        last_t = np.full(n, np.iinfo(np.int64).min)
        for k in rows:                    # arrival order
            here = (np.ones(n, bool) if present is None else present[k])
            v = np.where(is_counter, vals[k], g[k].astype(np.float64))
            c_sum += np.where(here & is_counter, vals[k], 0).astype(np.int64)
            g_sum += np.where(here & ~is_counter, g[k], 0).astype(gauge_dtype)
            lo = np.where(here, np.minimum(lo, v), lo)
            hi = np.where(here, np.maximum(hi, v), hi)
            newer = here & (ts[k] > last_t)
            last = np.where(newer, v, last)
            last_t = np.where(newer, ts[k], last_t)
            count[wi] += here
        has = count[wi] > 0
        out["sum"][wi] = np.where(
            has, np.where(is_counter, c_sum.astype(np.float64),
                          g_sum.astype(np.float64)), np.nan)
        out["min"][wi] = np.where(has, lo, np.nan)
        out["max"][wi] = np.where(has, hi, np.nan)
        out["last"][wi] = np.where(has & ~is_counter, last, np.nan)
    return (wins + 1) * resolution, out, count


def compare(got: dict, got_n: np.ndarray, want: dict, types: np.ndarray):
    """`got`, `want`: {lane: (Wn, N)}; `got_n` (4, Wn, N): how often
    each (lane, window, series) arrived.  -> (missing or extra, selected
    lanes wrong by bits, worst relative error of the gauges' sums)."""
    is_counter = types == COUNTER
    missing_or_extra = wrong = 0
    worst = 0.0
    for li, lane in enumerate(LANES):
        expected = ~np.isnan(want[lane])
        missing_or_extra += int((got_n[li] != expected).sum())
        both = expected & (got_n[li] == 1)
        g, w = got[lane][both], want[lane][both]
        exact = np.broadcast_to(
            is_counter if lane == "sum" else np.ones(len(types), bool),
            expected.shape)[both]
        wrong += int((g.view(np.int64) != w.view(np.int64))[exact].sum())
        if lane == "sum" and (~exact).any():
            with np.errstate(invalid="ignore", over="ignore"):
                err = np.abs(g[~exact] - w[~exact]) / np.maximum(
                    np.abs(w[~exact]), 1e-300)
            err = np.where(np.isnan(err), np.inf, err)
            worst = max(worst, float(err.max()))
    return missing_or_extra, wrong, worst
