"""Temporal stencil kernels vs a naive per-window numpy oracle
implementing Prometheus semantics (the reference's temporal functions,
src/query/functions/temporal/{rate,aggregation,linear_regression}.go)."""

import numpy as np
import jax.numpy as jnp
import pytest

from m3_tpu.query import temporal as tp

STEP = 15 * 10**9
RANGE = 5 * 60 * 10**9
T0 = 1_700_000_000 * 10**9


def _mk_series(S=6, P=200, seed=0, counter=False, irregular=True):
    rng = np.random.default_rng(seed)
    ts = np.full((S, P), np.iinfo(np.int64).max, np.int64)
    vals = np.full((S, P), np.nan)
    counts = np.zeros(S, np.int64)
    for s in range(S):
        n = rng.integers(P // 2, P)
        gaps = rng.integers(5, 15, n) if irregular else np.full(n, 10)
        t = T0 + np.cumsum(gaps * 10**9)
        if counter:
            v = np.cumsum(rng.integers(0, 100, n)).astype(float)
            # inject counter resets
            for r in rng.integers(5, n, 2):
                v[r:] = v[r:] - v[r] + rng.integers(0, 5)
        else:
            v = rng.normal(50, 10, n)
        ts[s, :n] = t
        vals[s, :n] = v
        counts[s] = n
    steps = np.arange(T0 + RANGE, T0 + RANGE + 40 * STEP, STEP, dtype=np.int64)
    return ts, vals, counts, steps


def _window(ts_row, vals_row, count, t, rng_nanos):
    sel = (ts_row[:count] > t - rng_nanos) & (ts_row[:count] <= t)
    return ts_row[:count][sel], vals_row[:count][sel]


def _oracle_rate(ts, vals, counts, steps, rng_nanos, func):
    S = ts.shape[0]
    out = np.full((S, len(steps)), np.nan)
    for s in range(S):
        for j, t in enumerate(steps):
            wt, wv = _window(ts[s], vals[s], counts[s], t, rng_nanos)
            if len(wt) < 2:
                continue
            if func in ("rate", "increase"):
                adj = wv.copy()
                bump = 0.0
                for i in range(1, len(adj)):
                    if wv[i] < wv[i - 1]:
                        bump += wv[i - 1]
                    adj[i] = wv[i] + bump
                wv = adj
            delta = wv[-1] - wv[0]
            sampled = (wt[-1] - wt[0])
            if sampled == 0:
                continue
            avg = sampled / (len(wt) - 1)
            dstart = wt[0] - (t - rng_nanos)
            dend = t - wt[-1]
            estart = dstart if dstart < avg * 1.1 else avg / 2
            eend = dend if dend < avg * 1.1 else avg / 2
            if func in ("rate", "increase") and delta > 0:
                zdur = sampled * (wv[0] / delta)
                estart = min(estart, zdur)
            val = delta * ((sampled + estart + eend) / sampled)
            if func == "rate":
                val = val / (rng_nanos / 1e9)
            out[s, j] = val
    return out


@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_rate_family(func):
    counter = func != "delta"
    ts, vals, counts, steps = _mk_series(counter=counter)
    got = np.asarray(
        tp.rate_family(jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
                       jnp.asarray(steps), RANGE, func)
    )
    want = _oracle_rate(ts, np.nan_to_num(vals), counts, steps, RANGE, func)
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


@pytest.mark.parametrize(
    "func", ["sum_over_time", "count_over_time", "avg_over_time", "stddev_over_time"]
)
def test_sum_count_family(func):
    ts, vals, counts, steps = _mk_series()
    got = np.asarray(
        tp.sum_count_family(jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
                            jnp.asarray(steps), RANGE, func)
    )
    S = ts.shape[0]
    want = np.full_like(got, np.nan)
    for s in range(S):
        for j, t in enumerate(steps):
            _, wv = _window(ts[s], np.nan_to_num(vals[s]), counts[s], t, RANGE)
            if len(wv) == 0:
                continue
            want[s, j] = {
                "sum_over_time": wv.sum(),
                "count_over_time": float(len(wv)),
                "avg_over_time": wv.mean(),
                "stddev_over_time": wv.std(),
            }[func]
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


@pytest.mark.parametrize("func,q", [("min_over_time", 0), ("max_over_time", 0),
                                    ("quantile_over_time", 0.9)])
def test_minmax_quantile_family(func, q):
    ts, vals, counts, steps = _mk_series()
    W = tp.window_pad_for(counts, ts, RANGE)
    got = np.asarray(
        tp.minmax_quantile_family(jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
                                  jnp.asarray(steps), RANGE, func, W, q)
    )
    want = np.full_like(got, np.nan)
    for s in range(ts.shape[0]):
        for j, t in enumerate(steps):
            _, wv = _window(ts[s], np.nan_to_num(vals[s]), counts[s], t, RANGE)
            if len(wv) == 0:
                continue
            if func == "min_over_time":
                want[s, j] = wv.min()
            elif func == "max_over_time":
                want[s, j] = wv.max()
            else:
                want[s, j] = np.quantile(wv, q, method="linear")
    np.testing.assert_allclose(got, want, rtol=1e-9, equal_nan=True)


def test_irate_idelta():
    ts, vals, counts, steps = _mk_series(counter=True)
    got = np.asarray(
        tp.rate_family(jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
                       jnp.asarray(steps), RANGE, "irate")
    )
    for s in range(ts.shape[0]):
        for j, t in enumerate(steps):
            wt, wv = _window(ts[s], np.nan_to_num(vals[s]), counts[s], t, RANGE)
            if len(wt) < 2:
                assert np.isnan(got[s, j])
                continue
            dv = wv[-1] - wv[0:][-2] if False else wv[-1] - wv[-2]
            if wv[-1] < wv[-2]:  # reset between the last two samples
                dv = wv[-1]
            dt = (wt[-1] - wt[-2]) / 1e9
            np.testing.assert_allclose(got[s, j], dv / dt, rtol=1e-9)


def test_deriv_and_predict_linear():
    ts, vals, counts, steps = _mk_series()
    got_d = np.asarray(
        tp.regression_family(jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
                             jnp.asarray(steps), RANGE, "deriv")
    )
    got_p = np.asarray(
        tp.regression_family(jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
                             jnp.asarray(steps), RANGE, "predict_linear", 600.0)
    )
    for s in range(ts.shape[0]):
        for j, t in enumerate(steps):
            wt, wv = _window(ts[s], np.nan_to_num(vals[s]), counts[s], t, RANGE)
            if len(wt) < 2:
                assert np.isnan(got_d[s, j])
                continue
            x = (wt - t) / 1e9  # centered at step time, like the kernel
            slope, intercept = np.polyfit(x, wv, 1)
            np.testing.assert_allclose(got_d[s, j], slope, rtol=1e-6)
            np.testing.assert_allclose(got_p[s, j], intercept + slope * 600.0, rtol=1e-6)


def test_last_over_time():
    ts, vals, counts, steps = _mk_series()
    got = tp.last_over_time(ts, np.nan_to_num(vals), steps, RANGE)
    for s in range(ts.shape[0]):
        for j, t in enumerate(steps):
            _, wv = _window(ts[s], np.nan_to_num(vals[s]), counts[s], t, RANGE)
            if len(wv) == 0:
                assert np.isnan(got[s, j])
            else:
                assert got[s, j] == wv[-1]


def _oracle_transitions(ts, vals, counts, steps, rng_nanos, func):
    S = ts.shape[0]
    out = np.full((S, len(steps)), np.nan)
    for s in range(S):
        for j, t in enumerate(steps):
            _, wv = _window(ts[s], vals[s], counts[s], t, rng_nanos)
            if len(wv) == 0:
                continue
            if func == "resets":
                out[s, j] = float(np.sum(wv[1:] < wv[:-1]))
            else:
                out[s, j] = float(np.sum(wv[1:] != wv[:-1]))
    return out


def _oracle_holt_winters(ts, vals, counts, steps, rng_nanos, sf, tf):
    """Prometheus funcHoltWinters, verbatim sequential loop."""
    S = ts.shape[0]
    out = np.full((S, len(steps)), np.nan)
    for s in range(S):
        for j, t in enumerate(steps):
            _, wv = _window(ts[s], vals[s], counts[s], t, rng_nanos)
            if len(wv) < 2:
                continue
            s1 = wv[0]
            b = wv[1] - wv[0]
            for i in range(1, len(wv)):
                x = sf * wv[i]
                y = (1.0 - sf) * (s1 + b)
                s0, s1 = s1, x + y
                b = tf * (s1 - s0) + (1.0 - tf) * b
            out[s, j] = s1
    return out


class TestTransitionsFamily:
    @pytest.mark.parametrize("func", ["resets", "changes"])
    def test_vs_oracle(self, func):
        ts, vals, counts, steps = _mk_series(counter=True, seed=11)
        got = np.asarray(tp.transitions_family(
            jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
            jnp.asarray(steps), RANGE, func))
        want = _oracle_transitions(ts, np.nan_to_num(vals), counts, steps,
                                   RANGE, func)
        np.testing.assert_allclose(got, want, equal_nan=True)

    def test_single_sample_window_is_zero(self):
        ts = np.asarray([[T0 + 10**9]], np.int64)
        vals = np.asarray([[5.0]])
        steps = np.asarray([T0 + 2 * 10**9], np.int64)
        got = np.asarray(tp.transitions_family(
            jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(steps),
            RANGE, "resets"))
        assert got[0, 0] == 0.0


class TestHoltWinters:
    def test_vs_prometheus_loop(self):
        ts, vals, counts, steps = _mk_series(seed=5)
        W = tp.window_pad_for(counts, ts, RANGE)
        got = np.asarray(tp.holt_winters(
            jnp.asarray(ts), jnp.asarray(np.nan_to_num(vals)),
            jnp.asarray(steps), RANGE, max(W, 2), 0.3, 0.6))
        want = _oracle_holt_winters(ts, np.nan_to_num(vals), counts, steps,
                                    RANGE, 0.3, 0.6)
        np.testing.assert_allclose(got, want, rtol=1e-10, equal_nan=True)


# -- window ends by comparison (PR 34) --------------------------------------
# `_window_bounds` counts, `_gather_rows` selects: both must give what a
# binary search and a per-element gather give, to the last bit.

I64_MAX = np.iinfo(np.int64).max
SEC = 10**9


def _padded(rows, P):
    """Sorted rows of any length -> (S, P) i64 with the i64-max tail."""
    ts = np.full((len(rows), P), I64_MAX, np.int64)
    for s, row in enumerate(rows):
        ts[s, :len(row)] = row
    return ts


def _bounds_case(name):
    """(ts, steps, range_nanos) for one corner of the bounds."""
    grid = T0 + np.arange(40) * 15 * SEC
    steps = T0 + RANGE + np.arange(12, dtype=np.int64) * 15 * SEC
    rng = RANGE
    if name == "own_offsets":  # every row scraped at its own offset
        rows = [grid + off for off in (0, 1, 7 * SEC + 3, 14 * SEC + 999_999_999)]
        ts = _padded(rows, 64)
    elif name == "duplicates":  # equal timestamps, also across an edge
        ts = _padded([np.repeat(grid, 3), np.sort(np.r_[grid, steps[3], steps[3]])],
                     160)
    elif name == "step_on_sample":  # ts == step is inside the window
        ts = _padded([steps.copy(), steps[::2].copy()], 32)
    elif name == "sample_at_window_start":  # ts == step - range is outside
        ts = _padded([steps - rng, np.r_[steps[0] - rng, steps[0] - rng + 1]], 16)
    elif name == "all_pad_row":  # a series with no sample at all
        ts = _padded([[], grid, []], 48)
    elif name == "p_not_a_multiple_of_128":
        ts = _padded([T0 + np.arange(200) * 3 * SEC, T0 + np.arange(131) * 5 * SEC],
                     201)
    elif name == "one_step":  # T = 1
        ts, steps = _padded([grid, grid[:5]], 40), steps[4:5]
    elif name == "before_and_after_all":  # windows that hold nothing or all
        ts = _padded([grid], 40)
        steps = np.asarray([T0 - SEC, T0, T0 + 10**6 * SEC], np.int64)
        rng = 2 * 10**6 * SEC
    return ts, steps, rng


@pytest.mark.parametrize("case", [
    "own_offsets", "duplicates", "step_on_sample", "sample_at_window_start",
    "all_pad_row", "p_not_a_multiple_of_128", "one_step",
    "before_and_after_all"])
def test_window_bounds_equal_searchsorted(case):
    ts, steps, rng = _bounds_case(case)
    lo, hi = tp._window_bounds(jnp.asarray(ts), jnp.asarray(steps), rng)
    lo, hi = np.asarray(lo), np.asarray(hi)
    assert lo.dtype == hi.dtype == np.int32
    assert lo.shape == hi.shape == (ts.shape[0], len(steps))
    for s, row in enumerate(ts):
        np.testing.assert_array_equal(
            lo[s], np.searchsorted(row, steps - rng, side="right"))
        np.testing.assert_array_equal(
            hi[s], np.searchsorted(row, steps, side="right"))


@pytest.mark.parametrize("what", ["f64", "i64", "prefix_sums"])
def test_gather_rows_is_a_selection(what):
    """`_gather_rows` returns the chosen element with its bits: -0.0
    stays -0.0, NaN and the infinities pass, i64 extremes pass, and an
    index may be P into an (S, P + 1) prefix array."""
    rng = np.random.default_rng(3)
    S, P, T = 5, 37, 11
    if what == "f64":
        a = rng.normal(0, 1e6, (S, P))
        a[0, :] = -0.0
        a[1, 3], a[1, 4], a[1, 5] = np.nan, -np.inf, np.inf
        a[2, :] = -1e300
    elif what == "i64":
        a = rng.integers(-2**62, 2**62, (S, P), dtype=np.int64)
        a[0, :] = -I64_MAX - 1
        a[1, P // 2:] = I64_MAX
    else:
        a = np.concatenate([np.zeros((S, 1)), np.cumsum(
            rng.integers(0, 1000, (S, P - 1)).astype(float), axis=1)], axis=1)
    idx = rng.integers(0, P, (S, T)).astype(np.int32)
    idx[:, 0], idx[:, -1] = 0, P - 1
    got = np.asarray(tp._gather_rows(jnp.asarray(a), jnp.asarray(idx)))
    want = np.take_along_axis(a, idx, axis=1)
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()


def _np_rate_family(ts, vals, steps, range_nanos, func, narrow):
    """rate_family as numpy: `np.searchsorted` for the window's ends and
    fancy indexing for the reads, the arithmetic in the kernel's order
    and dtypes (IEEE on the CPU, so equal by bits).  One habit of the
    compiler is part of that order: XLA turns ``x / constant`` into
    ``x * (1 / constant)``, which rounds differently (f32(3e11) / 1e9
    is 300.0, times 1e-9 it is 299.99997)."""
    dt_ = np.float32 if narrow else np.float64
    per_1e9 = dt_(1) / dt_(1e9)
    S, P = vals.shape
    rows = np.arange(S)[:, None]
    lo = np.stack([np.searchsorted(r, steps - range_nanos, side="right")
                   for r in ts])
    hi = np.stack([np.searchsorted(r, steps, side="right") for r in ts])
    n = hi - lo
    last_i, first_i = np.clip(hi - 1, 0, P - 1), np.clip(lo, 0, P - 1)
    counter = func in ("rate", "increase", "irate")
    adj = vals
    if counter:
        prev = np.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
        adj = vals + np.cumsum(np.where(vals < prev, prev, 0.0), axis=1)
    v_first, v_last = adj[rows, first_i], adj[rows, last_i]
    t_first, t_last = ts[rows, first_i], ts[rows, last_i]
    with np.errstate(all="ignore"):
        if func in ("irate", "idelta"):
            prev_i = np.clip(hi - 2, 0, P - 1)
            dv = (v_last - adj[rows, prev_i]).astype(dt_)
            dt = (t_last - ts[rows, prev_i]).astype(dt_) * per_1e9
            out = np.where(dt > 0, dv / dt if func == "irate" else dv, np.nan)
            return np.where(n >= 2, out, np.nan).astype(dt_)
        delta_v = (v_last - v_first).astype(dt_)
        sampled = (t_last - t_first).astype(dt_)
        avg = sampled / np.maximum(n.astype(dt_) - dt_(1), dt_(1))
        d_start = (t_first - (steps - range_nanos)[None, :]).astype(dt_)
        d_end = (steps[None, :] - t_last).astype(dt_)
        e_start = np.where(d_start < avg * dt_(1.1), d_start, avg / dt_(2))
        e_end = np.where(d_end < avg * dt_(1.1), d_end, avg / dt_(2))
        if counter:
            raw = vals[rows, first_i]
            d64 = v_last - v_first
            ratio = (raw / np.where(d64 == 0, 1.0, d64)).astype(dt_)
            zero = np.where((delta_v > 0) & (raw.astype(dt_) >= 0),
                            sampled * ratio, dt_(np.inf))
            e_start = np.minimum(e_start, zero)
        out = delta_v * ((sampled + e_start + e_end)
                         / np.where(sampled == 0, dt_(1), sampled))
        if func == "rate":
            out = out / (dt_(range_nanos) * per_1e9)
        return np.where((n >= 2) & (sampled > 0), out, np.nan).astype(dt_)


def _rate_rows():
    """Rows for the bit-for-bit comparison: integer-valued counters (so
    the reset cumsum is exact in any order) with resets, a gauge that
    crosses -0.0, constant series, a row whose windows hold one sample,
    a row of one sample, an empty row; each at its own scrape offset."""
    rng = np.random.default_rng(34)
    P, n = 140, 131  # neither a multiple of 128
    base = np.arange(n) * 15 * SEC
    rows, vals = [], []
    for off in (0, 4 * SEC + 1, 14 * SEC):  # counters, two resets each
        v = np.cumsum(rng.integers(0, 2000, n)).astype(float)
        for r in sorted(rng.integers(5, n, 2)):
            v[r:] = v[r:] - v[r] + float(rng.integers(0, 5))
        rows.append(T0 + off + base), vals.append(v)
    gauge = rng.integers(-3, 4, n).astype(float)
    gauge[gauge == 0] = -0.0
    gauge[::7] = 0.0
    rows.append(T0 + 2 * SEC + base), vals.append(gauge)
    rows.append(T0 + 9 * SEC + base), vals.append(np.full(n, 1234567.0))
    rows.append(T0 + base), vals.append(np.zeros(n))
    rows.append(T0 + np.arange(6) * 6 * 60 * SEC)  # one sample a window
    vals.append(np.arange(6) * 100.0)
    rows.append(np.asarray([T0 + 5 * 60 * SEC])), vals.append(np.asarray([7.0]))
    rows.append(np.asarray([], np.int64)), vals.append(np.asarray([]))
    ts = _padded(rows, P)
    v = np.zeros((len(rows), P))
    for s, col in enumerate(vals):
        v[s, :len(col)] = col
    steps = T0 + RANGE + np.arange(50, dtype=np.int64) * 30 * SEC
    return ts, v, steps


@pytest.mark.parametrize("narrow", [False, True], ids=["f64", "narrow"])
@pytest.mark.parametrize("func", ["rate", "increase", "delta", "irate", "idelta"])
def test_rate_family_bit_for_bit(func, narrow):
    ts, vals, steps = _rate_rows()
    got = np.asarray(tp.rate_family(jnp.asarray(ts), jnp.asarray(vals),
                                    jnp.asarray(steps), RANGE, func,
                                    narrow=narrow))
    want = _np_rate_family(ts, vals, steps, RANGE, func, narrow)
    assert got.dtype == want.dtype
    assert np.isfinite(want).sum() > 200  # most windows answer
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    keep = ~np.isnan(want)  # NaN payloads are no part of the contract
    assert got[keep].tobytes() == want[keep].tobytes()
