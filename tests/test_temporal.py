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
