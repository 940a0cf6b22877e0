"""Temporal (sliding-window) functions as batched stencil kernels.

Equivalent of `src/query/functions/temporal`: rate/irate/delta/idelta/
increase (`rate.go:34-49` with the extrapolated-rate math of
`standardRateFunc`), *_over_time aggregations (`aggregation.go`), and
deriv/predict_linear (`linear_regression.go`).  The reference walks each
series' datapoints per step with per-series goroutine batches
(`base.go:172-230`); here every (series, step) window is computed at once:

* window boundaries by COUNTING: lo/hi[s, t] = #{p : ts[s, p] <= edge[t]},
  a comparison of the sorted per-series timestamps against the window
  edges fused into a sum over P → (S, T) lo/hi index matrices;
* sum/count/avg/stddev + the rate family read **prefix sums** and the
  window's end samples by a select against iota(P) fused into a max
  over P — S·P·T lane operations, nothing of that size stored, and no
  binary search or per-element gather (on a TPU those are dependent
  rounds at ~10 ns an element: PERF.md section 6, PR 34);
* min/max/quantile gather a bounded (S, T, W) window tensor (W = max
  points per window, a static pad) — the stencil form.

Counter-reset correction and extrapolation follow the Prometheus
algorithm the reference implements (rate.go standardRateFunc: adjust by
cumulative resets, extrapolate to window edges capped at half the average
sample spacing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NAN = jnp.nan  # weak-typed: jnp.where keeps the value operand dtype


def _window_bounds(ts, step_times, range_nanos):
    """(S, T) lo/hi: half-open [lo, hi) indices of samples in
    (step - range, step] per series.

    A bound is a COUNT, ``#{p : ts[s, p] <= edge[t]}``: on sorted rows
    that is ``searchsorted(row, edge, side="right")`` for every row the
    callers hand over (duplicates, the i64-max padded tail, an all-pad
    row).  The (S, P, T) comparison fuses into its reduction over P, so
    nothing of that size is stored; a binary search would be log2(P)
    dependent rounds of per-element gathers, which a TPU runs at ~10 ns
    an element (PERF.md section 6, PR 34)."""
    def count_le(edges):  # (T,) -> (S, T) i32
        return jnp.sum(ts[:, :, None] <= edges[None, None, :], axis=1,
                       dtype=jnp.int32)

    return count_le(step_times - range_nanos), count_le(step_times)


def _prefix(vals):
    """Exclusive prefix sum with leading zero: (S, P+1)."""
    return jnp.concatenate(
        [jnp.zeros((vals.shape[0], 1), vals.dtype), jnp.cumsum(vals, axis=1)], axis=1
    )


def _gather_rows(a, idx):
    """a (S, P), idx (S, T) in [0, P) -> a[s, idx[s, t]].

    A select against iota(P) under a max over P, fused like the bounds
    and for the same reason (``take_along_axis`` is a per-element
    gather, two for a 64-bit operand).  A SELECTION, so the chosen
    element arrives with its bits, whatever the device's f64 and i64
    are; a masked sum would turn -0.0 into +0.0."""
    floating = jnp.issubdtype(a.dtype, jnp.floating)
    lowest = -jnp.inf if floating else jnp.iinfo(a.dtype).min
    pos = jnp.arange(a.shape[1], dtype=jnp.int32)[None, :, None]
    hit = idx[:, None, :] == pos  # (S, P, T), never stored
    return jnp.max(jnp.where(hit, a[:, :, None], lowest), axis=1)


@functools.partial(jax.jit, static_argnames=("func",))
def sum_count_family(ts, vals, step_times, range_nanos, func: str):
    """sum/count/avg/stddev/stdvar_over_time via prefix sums."""
    lo, hi = _window_bounds(ts, step_times, range_nanos)
    n = (hi - lo).astype(vals.dtype)
    c1 = _prefix(vals)
    c2 = _prefix(vals * vals)
    s1 = _gather_rows(c1, hi) - _gather_rows(c1, lo)
    s2 = _gather_rows(c2, hi) - _gather_rows(c2, lo)
    empty = n == 0
    if func == "sum_over_time":
        out = s1
    elif func == "count_over_time":
        out = n
    elif func == "avg_over_time":
        out = s1 / jnp.where(empty, 1.0, n)
    else:  # stddev/stdvar: population (Prometheus semantics)
        mean = s1 / jnp.where(empty, 1.0, n)
        var = jnp.maximum(s2 / jnp.where(empty, 1.0, n) - mean * mean, 0.0)
        out = jnp.sqrt(var) if func == "stddev_over_time" else var
    return jnp.where(empty, NAN, out)


def _gather_window(vals, lo, hi, W: int):
    """(S, T, W) stencil gather of each window's samples plus the valid
    mask — the shared idiom of every W-bounded kernel."""
    S, P = vals.shape
    T = lo.shape[1]
    idx = lo[:, :, None] + jnp.arange(W, dtype=jnp.int32)[None, None, :]
    valid = idx < hi[:, :, None]
    idx = jnp.clip(idx, 0, P - 1)
    g = jnp.take_along_axis(
        vals[:, None, :], idx.reshape(S, -1)[:, None, :], axis=2
    ).reshape(S, T, W)
    return g, valid


@functools.partial(jax.jit, static_argnames=("func", "window_pad"))
def minmax_quantile_family(ts, vals, step_times, range_nanos, func: str,
                           window_pad: int, q: float = 0.0):
    """min/max/quantile_over_time via the (S, T, W) gathered stencil."""
    lo, hi = _window_bounds(ts, step_times, range_nanos)
    g, valid = _gather_window(vals, lo, hi, window_pad)
    W = window_pad
    n = (hi - lo).astype(jnp.int32)
    empty = n == 0
    if func == "min_over_time":
        out = jnp.min(jnp.where(valid, g, jnp.inf), axis=2)
    elif func == "max_over_time":
        out = jnp.max(jnp.where(valid, g, -jnp.inf), axis=2)
    else:  # quantile_over_time (Prometheus: linear interpolation)
        gs = jnp.sort(jnp.where(valid, g, jnp.inf), axis=2)
        rank = q * (n.astype(vals.dtype) - 1.0)
        lo_r = jnp.clip(
            jnp.minimum(jnp.floor(rank).astype(jnp.int32), n - 1), 0, W - 1
        )
        hi_r = jnp.clip(jnp.minimum(lo_r + 1, n - 1), 0, W - 1)
        frac = rank - lo_r.astype(vals.dtype)
        v_lo = jnp.take_along_axis(gs, lo_r[:, :, None], axis=2)[:, :, 0]
        v_hi = jnp.take_along_axis(gs, hi_r[:, :, None], axis=2)[:, :, 0]
        out = v_lo + (v_hi - v_lo) * frac
    return jnp.where(empty, NAN, out)


@functools.partial(jax.jit, static_argnames=("func", "narrow"))
def rate_family(ts, vals, step_times, range_nanos, func: str,
                narrow: bool = False):
    """rate/increase/delta with Prometheus extrapolation
    (reference rate.go:99-102 standardRateFunc); counter funcs apply
    cumulative-reset correction.

    ``narrow`` is the f32 policy's entry point (query/precision.py).
    Unlike the other stencils, rate CANNOT take f32 values: cumulative
    counters are large and window deltas small, so narrowing before the
    difference cancels catastrophically (a 1e6-count counter with a
    30-count window delta loses ~2e-3 of the delta).  Instead ``vals``
    stays f64 through the reset correction and the v_last - v_first
    difference, and only the DIFFERENCES — delta, durations — narrow
    for the extrapolation arithmetic, where error is relative to the
    delta itself (~1e-7)."""
    dt_ = jnp.float32 if narrow else vals.dtype
    lo, hi = _window_bounds(ts, step_times, range_nanos)
    n = hi - lo
    has2 = n >= 2
    P = vals.shape[1]
    last_i = jnp.clip(hi - 1, 0, P - 1)
    first_i = jnp.clip(lo, 0, P - 1)

    is_counter = func in ("rate", "increase", "irate")
    if is_counter:
        prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
        # Prometheus counter correction: on reset (v < prev) add the full
        # previous value (the counter restarted from zero).
        resets = jnp.where(vals < prev, prev, 0.0)
        resets = jnp.where(jnp.isnan(resets), 0.0, resets)
        cum_resets = jnp.cumsum(resets, axis=1)
        adj = vals + cum_resets
    else:
        adj = vals

    # All DURATION math happens in i64 nanos first and narrows only the
    # differences: sampled / dur_start / dur_end are bounded by the
    # range window, so they fit any float dtype regardless of where the
    # query sits on the epoch axis or how long its span is (epoch nanos
    # themselves fit neither f32 nor even f64 exactly).  Pad entries
    # read at an end (i64 max) wrap to garbage — every lane that can
    # read one is masked below (has2 / sampled>0 / dt>0).
    v_first = _gather_rows(adj, first_i)
    v_last = _gather_rows(adj, last_i)
    ti_first = _gather_rows(ts, first_i)  # i64 (S, T)
    ti_last = _gather_rows(ts, last_i)

    if func in ("irate", "idelta"):
        prev_i = jnp.clip(hi - 2, 0, P - 1)
        v_prev = _gather_rows(adj, prev_i)
        dv = (v_last - v_prev).astype(dt_)  # difference, then narrow
        dt = (ti_last - _gather_rows(ts, prev_i)).astype(dt_) / 1e9
        out = jnp.where(dt > 0, dv / dt if func == "irate" else dv, NAN)
        return jnp.where(has2, out, NAN)

    range_f = jnp.asarray(range_nanos, dt_)
    window_start = step_times - range_nanos  # i64 (T,)

    delta_v = (v_last - v_first).astype(dt_)  # difference, then narrow
    sampled = (ti_last - ti_first).astype(dt_)  # nanos, <= range
    avg_dur = sampled / jnp.maximum(n.astype(dt_) - 1.0, 1.0)
    dur_start = (ti_first - window_start[None, :]).astype(dt_)
    dur_end = (step_times[None, :] - ti_last).astype(dt_)

    # Prometheus extrapolation: extend to the window edge unless the gap
    # exceeds 1.1× the average sample spacing, then cap at avg/2.
    extrap_start = jnp.where(dur_start < avg_dur * 1.1, dur_start, avg_dur / 2.0)
    extrap_end = jnp.where(dur_end < avg_dur * 1.1, dur_end, avg_dur / 2.0)
    if is_counter:
        # A counter cannot extrapolate below zero: cap the start-side
        # extension at the time it would take to reach zero.  Prometheus
        # uses the RAW first sample here (pre reset-adjustment).
        v_first_raw = _gather_rows(vals, first_i)
        # Ratio of two f64 quantities (large raw value / small delta):
        # divide in f64, then narrow the bounded result.
        delta64 = v_last - v_first
        ratio = (v_first_raw
                 / jnp.where(delta64 == 0, 1.0, delta64)).astype(dt_)
        zero_dur = jnp.where(
            (delta_v > 0) & (v_first_raw.astype(dt_) >= 0),
            sampled * ratio,
            jnp.inf,
        )
        extrap_start = jnp.minimum(extrap_start, zero_dur)
    factor = (sampled + extrap_start + extrap_end) / jnp.where(sampled == 0, 1.0, sampled)
    extrapolated = delta_v * factor

    if func == "rate":
        out = extrapolated / (range_f / 1e9)
    else:  # increase, delta
        out = extrapolated
    return jnp.where(has2 & (sampled > 0), out, NAN)


@functools.partial(jax.jit, static_argnames=("func",))
def regression_family(ts, vals, step_times, range_nanos, func: str,
                      predict_offset_s: float = 0.0):
    """deriv / predict_linear: least-squares slope over each window
    (reference linear_regression.go), via prefix sums of (t, v, t·v, t²)
    with per-window re-centering at the window end for stability.

    Always f64 regardless of the precision policy: the t² prefix sums
    span ~3e9 for an hour window, past f32's 2^24 integer range."""
    vals = vals.astype(jnp.float64)
    lo, hi = _window_bounds(ts, step_times, range_nanos)
    n = (hi - lo).astype(jnp.float64)
    # Center on the first step BEFORE the prefix sums: epoch-scale t²
    # (~1e19) would otherwise swamp float64 and cancel catastrophically.
    g_ref = step_times[0]
    tsec = (ts - g_ref).astype(jnp.float64) / 1e9
    ref = ((step_times - g_ref).astype(jnp.float64) / 1e9)[None, :]  # (1, T)

    c_v = _prefix(vals)
    c_t = _prefix(tsec)
    c_tv = _prefix(tsec * vals)
    c_tt = _prefix(tsec * tsec)
    S_v = _gather_rows(c_v, hi) - _gather_rows(c_v, lo)
    S_t = _gather_rows(c_t, hi) - _gather_rows(c_t, lo)
    S_tv = _gather_rows(c_tv, hi) - _gather_rows(c_tv, lo)
    S_tt = _gather_rows(c_tt, hi) - _gather_rows(c_tt, lo)
    # Re-center times at the step time: t' = t - ref.
    S_t_c = S_t - n * ref
    S_tt_c = S_tt - 2 * ref * S_t + n * ref * ref
    S_tv_c = S_tv - ref * S_v
    denom = n * S_tt_c - S_t_c * S_t_c
    slope = jnp.where(denom != 0, (n * S_tv_c - S_t_c * S_v) / denom, NAN)
    intercept = (S_v - slope * S_t_c) / jnp.where(n == 0, 1.0, n)  # value at ref
    ok = n >= 2
    if func == "deriv":
        return jnp.where(ok, slope, NAN)
    return jnp.where(ok, intercept + slope * predict_offset_s, NAN)


@functools.partial(jax.jit, static_argnames=("func",))
def transitions_family(ts, vals, step_times, range_nanos, func: str):
    """resets / changes (reference functions.go funcResets/funcChanges):
    count the transitions between CONSECUTIVE samples inside each
    window — resets counts v[i] < v[i-1] (counter restarts), changes
    counts v[i] != v[i-1].  Prefix-summed over the adjacent-pair
    indicator, so the windowed count is two reads: pairs (i-1, i)
    with both ends inside [lo, hi) are those with i in [lo+1, hi)."""
    lo, hi = _window_bounds(ts, step_times, range_nanos)
    prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
    if func == "resets":
        ind = (vals < prev).astype(vals.dtype)
    else:  # changes
        ind = (vals != prev).astype(vals.dtype)
    c = _prefix(ind)
    P = vals.shape[1]
    count = (_gather_rows(c, hi) -
             _gather_rows(c, jnp.clip(lo + 1, 0, P)))
    n = hi - lo
    # >=1 sample emits (0 transitions for a single sample); empty -> NaN
    return jnp.where(n >= 1, jnp.maximum(count, 0.0), NAN)


@functools.partial(jax.jit, static_argnames=("window_pad",))
def holt_winters(ts, vals, step_times, range_nanos, window_pad: int,
                 sf: float, tf: float):
    """holt_winters / double_exponential_smoothing (reference
    functions/temporal + Prometheus funcHoltWinters): per window,
    level/trend smoothing over the gathered (S, T, W) stencil with a
    masked fori over W — s1 seeds from x0, trend from x1-x0, and each
    in-window sample advances (s1, b) exactly like the sequential
    reference loop."""
    lo, hi = _window_bounds(ts, step_times, range_nanos)
    W = window_pad
    g, valid = _gather_window(vals, lo, hi, W)
    g = jnp.where(valid, g, 0.0)
    n = hi - lo

    x0 = g[:, :, 0]
    x1 = g[:, :, 1] if W > 1 else x0
    s1_0 = x0
    b_0 = x1 - x0

    def body(i, carry):
        s1, b = carry
        x = jax.lax.dynamic_index_in_dim(g, i, axis=2, keepdims=False)
        active = i < n
        xs = sf * x
        y = (1.0 - sf) * (s1 + b)
        s0_new, s1_new = s1, xs + y
        b_new = tf * (s1_new - s0_new) + (1.0 - tf) * b
        return (jnp.where(active, s1_new, s1), jnp.where(active, b_new, b))

    s1, _b = jax.lax.fori_loop(1, W, body, (s1_0, b_0))
    return jnp.where(n >= 2, s1, NAN)


def last_over_time(ts, vals, step_times, range_nanos) -> np.ndarray:
    """Newest sample in (step - range, step] per (series, step): the
    instant selector and ``last_over_time``.  A SELECTION, not a
    computation, so it runs on the host in numpy: a sample that is only
    chosen must leave the engine with the bits it was stored with, and
    an accelerator's f64 is not IEEE (a TPU carries it as an f32 pair —
    ~48 mantissa bits, f32 exponent range: 1e300 comes back inf).
    ``ts`` rows are sorted with an i64-max padded tail, so no index
    ever lands on padding."""
    ts, vals = np.asarray(ts), np.asarray(vals)
    step_times = np.asarray(step_times)
    starts = step_times - range_nanos
    out = np.full((vals.shape[0], len(step_times)), np.nan)
    for s, row in enumerate(ts):
        hi = np.searchsorted(row, step_times, side="right")
        ok = hi > np.searchsorted(row, starts, side="right")
        out[s, ok] = vals[s, hi[ok] - 1]
    return out


def window_pad_for(counts: np.ndarray, ts: np.ndarray, range_nanos: int) -> int:
    """Static W bound for the stencil kernels: the exact maximum number
    of samples any range-length window can contain, computed host-side
    per series via a sliding searchsorted.  No silent cap — the (S, T, W)
    gather tensor is as wide as the densest window requires; callers
    chunk the series axis if that exceeds memory."""
    best = 1
    for s in range(len(counts)):
        n = int(counts[s])
        if n == 0:
            continue
        row = ts[s, :n]
        lo = np.searchsorted(row, row - range_nanos, side="right")
        best = max(best, int((np.arange(1, n + 1) - lo).max()))
    return best
