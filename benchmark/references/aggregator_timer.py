"""The plain numpy reference of the aggregator's timer quantiles: per
(timer id, 1 m window) the sample of rank ``ceil(q n)`` (1-based) among
the window's n acked samples of that id, for q = 0.5, 0.95, 0.99 — what
upstream's ``Timer`` answers through ``cm.Stream`` with the sketch's
rank error taken to zero.  Imports nothing of the program.

The generator's samples form a matrix: ``vals[k, j]`` is sample j of
interval k at time ``ts[k]``, of the id ``series_of(k)[j]``;
``present[k, j]`` says whether it was sent and acked (a control leaves
one frame out).  A sample belongs to the window of its own time, ``ts
// resolution``, and is emitted at the window's end.  Per window: a
lexsort by (id, value), segment starts, rank arithmetic — a selection,
never an interpolation.  ``select`` does it in the precision it is
given: the system carries a sample at f32 (its stated guarantee), so
its answers equal the selection over ``float32(values)`` bit for bit and
lie within 2^-24 relative of the selection over the f64 values
(rounding is monotone: the rank-th smallest image is the image of the
rank-th smallest value).
"""

from __future__ import annotations

import numpy as np

QUANTILES = (0.5, 0.95, 0.99)
AGG_TYPE = {0.5: 14, 0.95: 19, 0.99: 20}     # the wire's numbers


def to_float32(v: np.ndarray) -> np.ndarray:
    return v.astype(np.float32)


def to_bfloat16(v: np.ndarray) -> np.ndarray:
    """f64 -> the nearest bfloat16 (ties to even), as f64: the upper 16
    bits of the f32 image, rounded."""
    b = v.astype(np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) >> 16 << 16
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def select(series: np.ndarray, values: np.ndarray, n_series: int,
           rank_shift: int = 0):
    """One window's samples -> (count (N,), {q: (N,) f64}): per id the
    value of rank ceil(q n) in ascending order, NaN where the id has no
    sample.  `values` in the precision to select in.  `rank_shift` 1
    is the control that reads every quantile one rank high."""
    order = np.lexsort((values, series))
    s, v = series[order], values[order]
    head = np.ones(len(s), bool)
    head[1:] = s[1:] != s[:-1]
    starts = np.flatnonzero(head)
    n = np.diff(np.append(starts, len(s)))
    count = np.zeros(n_series, np.int64)
    count[s[starts]] = n
    out = {}
    for q in QUANTILES:
        rank = np.maximum(np.ceil(q * n).astype(np.int64), 1) - 1
        rank = np.minimum(rank + rank_shift, n - 1)
        lane = np.full(n_series, np.nan)
        lane[s[starts]] = v[starts + rank]
        out[q] = lane
    return count, out


def quantiles(ts: np.ndarray, vals: np.ndarray, series_of, n_series: int,
              resolution: int, present: np.ndarray | None = None,
              image=None, rank_shift: int = 0):
    """-> (window ends (Wn,), {q: (Wn, N) f64}, count (Wn, N)): the
    selection over the samples' `image` (a function of the f64 values:
    `to_float32`, `to_bfloat16`; None = the f64 values themselves).  A
    lane of a (window, id) with no sample is NaN and its count 0."""
    wins = np.unique(ts // resolution)
    out = {q: np.full((len(wins), n_series), np.nan) for q in QUANTILES}
    count = np.zeros((len(wins), n_series), np.int64)
    for wi, w in enumerate(wins):
        rows = np.flatnonzero(ts // resolution == w)
        series = np.concatenate([series_of(k) for k in rows])
        v = np.concatenate([vals[k] for k in rows])
        if present is not None:
            here = np.concatenate([present[k] for k in rows])
            series, v = series[here], v[here]
        count[wi], lanes = select(series, v if image is None else image(v),
                                  n_series, rank_shift)
        for q in QUANTILES:
            out[q][wi] = lanes[q].astype(np.float64)
    return (wins + 1) * resolution, out, count


def compare(got: dict, got_n: np.ndarray, want32: dict, want64: dict):
    """`got`, `want32`, `want64`: {q: (Wn, N)}; `got_n` (3, Wn, N): how
    often each (quantile, window, id) arrived.  -> (missing or extra,
    wrong by bits against the f32 selection, worst relative error
    against the f64 selection)."""
    missing_or_extra = wrong = 0
    worst = 0.0
    for qi, q in enumerate(QUANTILES):
        expected = ~np.isnan(want32[q])
        missing_or_extra += int((got_n[qi] != expected).sum())
        both = expected & (got_n[qi] == 1)
        g, w32, w64 = got[q][both], want32[q][both], want64[q][both]
        wrong += int((g.view(np.int64) != w32.view(np.int64)).sum())
        if len(g):
            with np.errstate(invalid="ignore", over="ignore"):
                err = np.abs(g - w64) / np.maximum(np.abs(w64), 1e-300)
            err = np.where(np.isnan(err), np.inf, err)
            worst = max(worst, float(err.max()))
    return missing_or_extra, wrong, worst
