"""Plain numpy references of the PromQL the dashboard cell sends:
Prometheus' extrapolated counter rate and its bucket quantile.

Copied from ``chip_smoke.py`` (``ref_rate``, ``ref_quantile``) at commit
d4ba90b; this copy, not the original, is the yardstick from now on.
Unchanged but for `hq_by_le`, which strings them together as
``histogram_quantile(q, sum by (le) (rate(m[w])))``.
"""

from __future__ import annotations

import numpy as np


def ref_rate(ts: np.ndarray, vals: np.ndarray, steps: np.ndarray,
             window: int) -> np.ndarray:
    """Prometheus extrapolated counter rate, (S, P) -> (S, len(steps)),
    numpy over the series axis: all series share their timestamps and
    the generated counters never reset."""
    out = np.full((vals.shape[0], len(steps)), np.nan)
    for j, t in enumerate(steps.tolist()):
        idx = np.nonzero((ts > t - window) & (ts <= t))[0]
        if len(idx) < 2:
            continue
        a, b = idx[0], idx[-1]
        first, last = vals[:, a], vals[:, b]
        delta = last - first
        sampled = float(ts[b] - ts[a])
        avg = sampled / (len(idx) - 1)
        dur_start = float(ts[a] - (t - window))
        dur_end = float(t - ts[b])
        ex_start = dur_start if dur_start < avg * 1.1 else avg / 2
        ex_end = dur_end if dur_end < avg * 1.1 else avg / 2
        with np.errstate(divide="ignore", invalid="ignore"):
            zero = np.where((delta > 0) & (first >= 0),
                            sampled * (first / delta), np.inf)
        ex_s = np.minimum(ex_start, zero)
        out[:, j] = delta * (sampled + ex_s + ex_end) / sampled / (window / 1e9)
    return out


def ref_quantile(q: float, ubs: np.ndarray, counts: np.ndarray) -> float:
    """Prometheus bucketQuantile over cumulative counts (ubs ascending,
    last +Inf)."""
    total = counts[-1]
    if len(counts) < 2 or not total > 0:
        return float("nan")
    rank = q * total
    b = int(np.searchsorted(counts, rank, side="left"))
    if b == len(counts) - 1:
        return float(ubs[-2])
    if b == 0 and ubs[0] <= 0:
        return float(ubs[0])
    lo = 0.0 if b == 0 else ubs[b - 1]
    prev = 0.0 if b == 0 else counts[b - 1]
    return float(lo + (ubs[b] - lo) * ((rank - prev) / (counts[b] - prev)))


def hq_by_le(q: float, ubs: np.ndarray, ts: np.ndarray, vals: np.ndarray,
             steps: np.ndarray, window: int) -> np.ndarray:
    """histogram_quantile(q, sum by (le) (rate(m[window]))) for the
    bucket series `vals` ((H * len(ubs), P), histogram-major, le-minor):
    (len(steps),), NaN where the rate has no two points."""
    rates = ref_rate(ts, vals, steps, window)
    by_le = np.nansum(rates.reshape(-1, len(ubs), len(steps)), axis=0)
    out = np.full(len(steps), np.nan)
    for j in range(len(steps)):
        if not np.isnan(rates[0, j]):
            out[j] = ref_quantile(q, ubs, by_le[:, j])
    return out
