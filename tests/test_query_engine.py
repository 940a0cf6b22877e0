"""PromQL engine end-to-end: parse → index select → temporal kernels →
aggregation/binary — validated against hand-computed Prometheus
semantics over a seeded database."""

import numpy as np
import pytest

from m3_tpu.index.doc import Document
from m3_tpu.query.engine import Engine
from m3_tpu.query.promql import (
    Aggregation, BinaryOp, Call, NumberLiteral, VectorSelector, parse,
    parse_duration,
)
from m3_tpu.query.storage_adapter import DatabaseStorage
from m3_tpu.storage.database import Database, DatabaseOptions, NamespaceOptions

BLOCK = 2 * 3600 * 10**9
START = (1_700_000_000 * 10**9) // BLOCK * BLOCK
STEP = 15 * 10**9


class TestParser:
    def test_selector(self):
        e = parse('http_requests_total{job="api", status=~"5.."}[5m] offset 1m')
        assert isinstance(e, VectorSelector)
        assert e.name == b"http_requests_total"
        assert e.range_nanos == 5 * 60 * 10**9
        assert e.offset_nanos == 60 * 10**9
        assert e.matchers[0].name == b"job" and e.matchers[0].op == "="
        assert e.matchers[1].op == "=~"

    def test_precedence(self):
        e = parse("a + b * c")
        assert isinstance(e, BinaryOp) and e.op == "+"
        assert isinstance(e.rhs, BinaryOp) and e.rhs.op == "*"
        e2 = parse("2 ^ 3 ^ 2")  # right-assoc
        assert e2.op == "^" and isinstance(e2.rhs, BinaryOp)

    def test_aggregation_forms(self):
        e = parse('sum by (job) (rate(x[1m]))')
        assert isinstance(e, Aggregation) and e.by == (b"job",)
        e2 = parse('sum(rate(x[1m])) by (job)')
        assert e2.by == (b"job",)
        e3 = parse('topk(3, x)')
        assert isinstance(e3.param, NumberLiteral) and e3.param.value == 3

    def test_bool_and_matching(self):
        e = parse("a > bool 0")
        assert e.bool_mode
        e2 = parse("a / on (host) b")
        assert e2.on == (b"host",)

    def test_errors(self):
        with pytest.raises(ValueError):
            parse("rate(x[5m")
        with pytest.raises(ValueError):
            parse("sum(")
        with pytest.raises(ValueError):
            parse("x{a=b}")


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    root = tmp_path_factory.mktemp("qdb")
    db = Database(
        DatabaseOptions(root=str(root), commitlog_enabled=False),
        {"default": NamespaceOptions(num_shards=2, slot_capacity=1 << 10,
                                     sample_capacity=1 << 14)},
    )
    docs, all_ts, all_vals = [], [], []
    N = 120  # 30 min of 15s samples
    for host in range(4):
        for job in ("api", "db"):
            sid = f"req.{job}.h{host}".encode()
            doc = Document.from_tags(sid, {
                b"__name__": b"http_requests_total",
                b"host": f"h{host}".encode(),
                b"job": job.encode(),
            })
            t = START + np.arange(1, N + 1) * STEP
            v = np.cumsum(np.full(N, 10.0 * (host + 1)))  # counter: rate 2/3 per s * (host+1)
            docs.extend([doc] * N)
            all_ts.extend(t.tolist())
            all_vals.extend(v.tolist())
    # histogram series
    for le in ("0.1", "0.5", "1", "+Inf"):
        sid = f"lat.bucket.{le}".encode()
        doc = Document.from_tags(sid, {
            b"__name__": b"latency_bucket", b"le": le.encode(), b"job": b"api",
        })
        t = START + np.arange(1, N + 1) * STEP
        frac = {"0.1": 0.25, "0.5": 0.5, "1": 0.75, "+Inf": 1.0}[le]
        v = np.cumsum(np.full(N, 100.0)) * frac
        docs.extend([doc] * N)
        all_ts.extend(t.tolist())
        all_vals.extend(v.tolist())
    db.write_tagged_batch("default", docs, np.asarray(all_ts, np.int64),
                          np.asarray(all_vals))
    yield Engine(DatabaseStorage(db))
    db.close()


QSTART = START + 10 * 60 * 10**9
QEND = START + 28 * 60 * 10**9


_REDUCE_FUNCS = ["sum", "count", "avg", "stddev", "stdvar", "min", "max",
                 "group"]


def _reduce_case(gids, G):
    rng = np.random.default_rng(17)
    vals = np.round(rng.normal(0, 10, (len(gids), 13)), 5)
    vals[rng.random(vals.shape) < 0.15] = np.nan
    vals[0, :] = np.nan  # one fully-NaN row
    return vals, gids.astype(np.int32), G


def _balanced_case():
    rng = np.random.default_rng(18)
    S, G = 200, 23
    gids = rng.integers(0, G, S)
    gids[gids == G - 1] = 0  # leave group G-1 EMPTY
    return _reduce_case(gids, G)


def _skewed_case():
    rng = np.random.default_rng(19)
    S, G = 200, 9
    gids = np.zeros(S, np.int64)          # 150 rows in group 0
    gids[150:] = rng.integers(1, G - 1, 50)  # group G-1 EMPTY
    return _reduce_case(rng.permutation(gids), G)


_REDUCE_CASES = {"balanced": _balanced_case, "skewed": _skewed_case}


def _atol(func):
    # stddev is the root of E[x^2] - E[x]^2: a cancellation of 1e-15
    # of the squares reads 3e-8 after the root (a lone value: 0 exactly
    # in numpy's two-pass form)
    return 1e-6 if func == "stddev" else 1e-9


def _ref_reduce(vals, gids, G, func):
    """Independent numpy reference: nan-reductions per (group, step),
    NaN where the group has no present value at the step."""
    import warnings

    ref = {"sum": np.nansum, "avg": np.nanmean, "min": np.nanmin,
           "max": np.nanmax, "stddev": np.nanstd, "stdvar": np.nanvar,
           "count": lambda r, axis: (~np.isnan(r)).sum(axis=axis),
           "group": lambda r, axis: np.ones(r.shape[1])}[func]
    out = np.full((G, vals.shape[1]), np.nan)
    for g in range(G):
        rows = vals[gids == g]
        some = (~np.isnan(rows)).any(axis=0) if len(rows) else \
            np.zeros(vals.shape[1], bool)
        if some.any():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                out[g, some] = ref(rows[:, some], axis=0)
    return out


class TestEngine:
    def test_instant_selector_lookback(self, engine):
        b = engine.execute_range('http_requests_total{job="api"}', QSTART, QEND, STEP)
        assert b.num_series == 4
        assert not np.isnan(b.values).any()

    def test_rate_flat_counter(self, engine):
        b = engine.execute_range(
            'rate(http_requests_total{host="h0", job="api"}[5m])',
            QSTART, QEND, STEP,
        )
        assert b.num_series == 1
        # counter increments 10 per 15s → rate = 2/3 per second
        np.testing.assert_allclose(b.values, 10.0 / 15.0, rtol=1e-9)

    def test_sum_by_rate(self, engine):
        b = engine.execute_range(
            'sum by (job) (rate(http_requests_total[5m]))', QSTART, QEND, STEP
        )
        assert b.num_series == 2
        by_job = {m.as_dict()[b"job"]: i for i, m in enumerate(b.series)}
        want = (10 + 20 + 30 + 40) / 15.0
        np.testing.assert_allclose(b.values[by_job[b"api"]], want, rtol=1e-9)
        np.testing.assert_allclose(b.values[by_job[b"db"]], want, rtol=1e-9)

    def test_histogram_quantile(self, engine):
        b = engine.execute_range(
            'histogram_quantile(0.5, rate(latency_bucket[5m]))',
            QSTART, QEND, STEP,
        )
        assert b.num_series == 1
        # CDF: 25% ≤0.1, 50% ≤0.5 → p50 = 0.5 exactly.
        np.testing.assert_allclose(b.values, 0.5, rtol=1e-9)

    def test_binary_vector_match(self, engine):
        b = engine.execute_range(
            'rate(http_requests_total{job="api"}[5m]) '
            '/ on (host) rate(http_requests_total{job="db"}[5m])',
            QSTART, QEND, STEP,
        )
        assert b.num_series == 4
        np.testing.assert_allclose(b.values, 1.0, rtol=1e-9)

    def test_comparison_filter_and_topk(self, engine):
        b = engine.execute_range(
            'rate(http_requests_total{job="api"}[5m]) > 2', QSTART, QEND, STEP
        )
        # hosts h2 (rate 2) filtered out? rate h(i) = 10*(i+1)/15 → h2=2.0, h3≈2.67
        kept = (~np.isnan(b.values)).any(axis=1).sum()
        assert kept == 1
        t = engine.execute_range(
            'topk(2, rate(http_requests_total{job="api"}[5m]))', QSTART, QEND, STEP
        )
        kept_rows = (~np.isnan(t.values)).any(axis=1)
        assert kept_rows.sum() == 2

    def test_scalar_arith_and_unary(self, engine):
        b = engine.execute_range(
            '-rate(http_requests_total{host="h0", job="api"}[5m]) * 3',
            QSTART, QEND, STEP,
        )
        np.testing.assert_allclose(b.values, -2.0, rtol=1e-9)

    def test_increase_and_avg_over_time(self, engine):
        b = engine.execute_range(
            'increase(http_requests_total{host="h0", job="api"}[5m])',
            QSTART, QEND, STEP,
        )
        np.testing.assert_allclose(b.values, 10.0 / 15.0 * 300, rtol=1e-9)
        b2 = engine.execute_range(
            'avg_over_time(http_requests_total{host="h0", job="api"}[5m])',
            QSTART, QEND, STEP,
        )
        assert not np.isnan(b2.values).any()

    def test_absent_and_or(self, engine):
        b = engine.execute_range('absent(nonexistent_metric)', QSTART, QEND, STEP)
        np.testing.assert_allclose(b.values, 1.0)
        b2 = engine.execute_range(
            'http_requests_total{job="api"} or http_requests_total{job="db"}',
            QSTART, QEND, STEP,
        )
        assert b2.num_series == 8

    def test_group_aggregation(self, engine):
        """PromQL `group`: 1 for every group with a present value."""
        cnt = engine.execute_range('count by (job) (http_requests_total)',
                                   QSTART, QEND, STEP)
        grp = engine.execute_range('group by (job) (http_requests_total)',
                                   QSTART, QEND, STEP)
        assert [m.tags for m in grp.series] == [m.tags for m in cnt.series]
        assert grp.num_series >= 2 and (np.asarray(cnt.values) > 1).any()
        np.testing.assert_array_equal(
            np.asarray(grp.values),
            np.where(np.isnan(np.asarray(cnt.values)), np.nan, 1.0))

    @pytest.mark.parametrize("func", _REDUCE_FUNCS)
    @pytest.mark.parametrize("case", list(_REDUCE_CASES))
    def test_segment_reduce_sorted_matches_scatter(self, case, func):
        """The group reduction against numpy's nan-reductions per
        (group, step), on balanced groups and on one group of 150 of
        200 rows beside small ones and an empty one.  (The name dates
        from when a scatter form was the reference.)"""
        from m3_tpu.query import functions as fn_mod

        vals, gids, G = _REDUCE_CASES[case]()
        got = fn_mod._segment_reduce(vals, gids, G, func)
        np.testing.assert_allclose(np.asarray(got),
                                   _ref_reduce(vals, gids, G, func),
                                   atol=_atol(func), equal_nan=True)

    def test_segment_reduce_no_series(self):
        from m3_tpu.query import functions as fn_mod

        out = fn_mod._segment_reduce(np.zeros((0, 7)),
                                     np.zeros(0, np.int32), 0, "sum")
        assert out.shape == (0, 7)
        out = fn_mod._segment_reduce(np.zeros((0, 7)),
                                     np.zeros(0, np.int32), 2, "count")
        assert out.shape == (2, 7) and np.isnan(np.asarray(out)).all()

    @pytest.mark.parametrize("func", ["sum", "avg", "max"])
    def test_segment_reduce_one_global_group(self, func):
        """`sum(x)` with no `by`: G = 1, the whole block one group."""
        from m3_tpu.query import functions as fn_mod

        vals, _, _ = _balanced_case()
        gids = np.zeros(len(vals), np.int32)
        got = np.asarray(fn_mod._segment_reduce(vals, gids, 1, func))
        assert got.shape == (1, vals.shape[1])
        np.testing.assert_allclose(got, _ref_reduce(vals, gids, 1, func),
                                   atol=1e-9)

    @pytest.mark.parametrize("case", ["shared", "alone"])
    def test_segment_reduce_all_nan_row_and_group(self, case):
        """A fully-NaN row is absent from its group; a group of only
        such rows answers NaN under every func, `count` included."""
        from m3_tpu.query import functions as fn_mod

        if case == "shared":  # the NaN row beside a present one
            gids = np.asarray([0, 0, 1, 1], np.int32)
        else:  # the NaN row is all of group 0; groups 1-4 have no row
            gids = np.asarray([0] + [5] * 9, np.int32)
        S, G = len(gids), int(gids.max()) + 1
        vals = np.arange(S * 3, dtype=np.float64).reshape(S, 3) + 1.0
        vals[0] = np.nan
        for func in _REDUCE_FUNCS:
            got = np.asarray(fn_mod._segment_reduce(vals, gids, G, func))
            np.testing.assert_allclose(
                got, _ref_reduce(vals, gids, G, func), atol=_atol(func),
                equal_nan=True, err_msg=func)
        if case == "alone":
            cnt = np.asarray(fn_mod._segment_reduce(vals, gids, G, "count"))
            assert np.isnan(cnt[0]).all() and (cnt[5] == 9).all()

    @pytest.mark.parametrize("case", ["adjacent", "with_gaps"])
    def test_segment_reduce_min_max_infinities(self, case):
        """+-Inf are real samples: they win min/max against finite
        values; an answer that is the NaN-fill's own infinity (all
        present values +Inf under min, -Inf under max) reads NaN."""
        from m3_tpu.query import functions as fn_mod

        inf = np.inf
        rows = [[-inf, 1.0, inf, inf],       # group 0
                [2.0, np.nan, inf, 5.0],     # group 0
                [3.0, -inf, np.nan, -inf]]   # group 1
        if case == "adjacent":
            vals = np.asarray(rows)
            gids = np.asarray([0, 0, 1], np.int32)
        else:  # empty groups 2-4 between them and nine filler rows
            vals = np.asarray(rows + [[0.0] * 4] * 9)
            gids = np.asarray([0, 0, 1] + [5] * 9, np.int32)
        G = int(gids.max()) + 1
        mn = np.asarray(fn_mod._segment_reduce(vals, gids, G, "min"))
        mx = np.asarray(fn_mod._segment_reduce(vals, gids, G, "max"))
        np.testing.assert_array_equal(mn[0], [-inf, 1.0, np.nan, 5.0])
        np.testing.assert_array_equal(mx[0], [2.0, 1.0, inf, inf])
        np.testing.assert_array_equal(mn[1], [3.0, -inf, np.nan, -inf])
        np.testing.assert_array_equal(mx[1], [3.0, np.nan, np.nan, np.nan])

    @pytest.mark.parametrize("func", ["sum", "quantile"])
    def test_segment_reduce_stays_on_device(self, func):
        """Block contract: the (G, T) answer is a device array, f64."""
        import jax

        from m3_tpu.query import functions as fn_mod

        vals, gids, G = _balanced_case()
        out = fn_mod._segment_reduce(vals, gids, G, func, 0.5)
        assert isinstance(out, jax.Array)
        assert out.dtype == np.float64 and out.shape == (G, vals.shape[1])

    def test_segment_reduce_is_one_program(self):
        """What the span's one_program tag asserts, measured: at a shape
        nothing has compiled yet, a call compiles exactly one program,
        the kernel (an eager op beside it would compile its own), and
        the same shape again compiles nothing."""
        import jax

        from m3_tpu.query import functions as fn_mod
        from m3_tpu.x import tracewatch

        # a shape no other test compiles; f32, so the widening to f64
        # has to happen inside the program too
        vals = jax.device_put(np.ones((37, 11), np.float32))
        more = jax.device_put(np.full((37, 11), 2.0, np.float32))
        gids = (np.arange(37) % 5).astype(np.int32)
        was_installed = tracewatch.installed()
        tracewatch.install(raise_on_violation=False)
        try:
            before = dict(tracewatch.compiles())
            out = fn_mod._segment_reduce(vals, gids, 5, "sum")
            new = {k: n - before.get(k, 0)
                   for k, n in tracewatch.compiles().items()
                   if n != before.get(k, 0)}
            assert new == {"_segment_reduce_kernel": 1}
            snap = tracewatch.snapshot()
            fn_mod._segment_reduce(more, gids[::-1].copy(), 5, "sum")
            assert tracewatch.retraces_since(snap) == 0
        finally:
            if not was_installed:
                tracewatch.uninstall()
        np.testing.assert_array_equal(np.asarray(out)[:, 0],
                                      [8, 8, 7, 7, 7])

    def test_aggregation_span_says_one_program(self, engine):
        """sum by (le) (rate(x[5m])) leaves a query.eval.aggregation
        span tagged n = 1, one_program = 1, and a second call of the
        same shape compiles nothing (tracewatch: what the benchmark's
        window_compiles.* reads)."""
        from m3_tpu.instrument.tracing import Tracepoint, Tracer
        from m3_tpu.x import tracewatch

        tracer = Tracer(enabled=True)
        eng = Engine(engine.storage, tracer=tracer)
        q = 'sum by (le) (rate(latency_bucket[5m]))'
        first = eng.execute_range(q, QSTART, QEND, STEP)
        (sp,) = tracer.finished(Tracepoint.EVAL_AGGREGATION)
        assert sp.tags["op"] == "sum"
        assert sp.tags["n"] == 1 and sp.tags["one_program"] == 1
        was_installed = tracewatch.installed()
        tracewatch.install(raise_on_violation=False)
        try:
            snap = tracewatch.snapshot()
            again = eng.execute_range(q, QSTART, QEND, STEP)
            assert tracewatch.retraces_since(snap) == 0, \
                tracewatch.compiles()
        finally:
            if not was_installed:
                tracewatch.uninstall()
        np.testing.assert_array_equal(again.values, first.values)
        assert len(tracer.finished(Tracepoint.EVAL_AGGREGATION)) == 2
        # topk dispatches its mask kernel and then a select: not one
        eng.execute_range('topk(1, http_requests_total)', QSTART, QEND, STEP)
        sp = tracer.finished(Tracepoint.EVAL_AGGREGATION)[-1]
        assert sp.tags["n"] == 1 and sp.tags["one_program"] == 0

    def test_scalar_derived_parameter_collapses(self, engine):
        """scalar()-derived parameters must collapse to a float even
        when blocks are device-resident (topk's k reaches int())."""
        b = engine.execute_range(
            'topk(scalar(count(http_requests_total) > bool 0),'
            ' http_requests_total)',
            QSTART, QEND, STEP)
        assert b.num_series == 8  # k=1: all series kept, non-top masked
        top = (~np.isnan(np.asarray(b.values)[:, -1])).sum()
        assert 1 <= top <= 2  # k=1 plus the fixture's exact-tie twin
        v = engine.execute_range('vector(time())', QSTART, QEND, STEP)
        # vector(time()) keeps per-step values (Prometheus semantics)
        tv = np.asarray(v.values)[0]
        assert tv[0] != tv[-1]

    def test_bool_comparison_missing_stays_missing(self, engine):
        """`v > bool s` on a MISSING sample (NaN in the block model)
        must stay missing, not fabricate a 0.0 (Prometheus emits no
        sample where the input has none).  The rate() head drops the
        first window, so early steps are genuinely missing."""
        b = engine.execute_range(
            'rate(http_requests_total{host="h0", job="api"}[5m]) > bool 0',
            QSTART - 10 * 60 * 10**9, QEND, STEP)
        v = np.asarray(b.values)
        assert np.isnan(v[:, 0]).all()  # before data: missing, not 0.0
        assert (v[~np.isnan(v)] == 1.0).all()

    def test_label_replace(self, engine):
        b = engine.execute_range(
            'label_replace(rate(http_requests_total{job="api"}[5m]), '
            '"node", "$1", "host", "h(.*)")',
            QSTART, QEND, STEP,
        )
        assert all(b"node" in m.as_dict() for m in b.series)


class TestRound4Functions:
    def test_resets_and_changes(self, engine):
        # monotone counters: zero resets; changes > 0 where it moves
        b = engine.execute_range(
            'resets(http_requests_total{host="h0", job="api"}[5m])',
            QSTART, QEND, STEP)
        assert b.num_series == 1
        assert np.nanmax(b.values) == 0.0
        b2 = engine.execute_range(
            'changes(http_requests_total{host="h0", job="api"}[5m])',
            QSTART, QEND, STEP)
        assert np.nanmax(b2.values) > 0

    def test_holt_winters_smooths(self, engine):
        b = engine.execute_range(
            'holt_winters(http_requests_total{host="h0", job="api"}[5m], 0.3, 0.6)',
            QSTART, QEND, STEP)
        assert b.num_series == 1
        assert np.isfinite(b.values[0, -1])
        with pytest.raises(ValueError, match="smoothing"):
            engine.execute_range(
                'holt_winters(http_requests_total[5m], 1.5, 0.6)',
                QSTART, QEND, STEP)

    def test_sort_orders_series_by_final_value(self, engine):
        a = engine.execute_range('sort(http_requests_total{job="api"})',
                                 QSTART, QEND, STEP)
        d = engine.execute_range('sort_desc(http_requests_total{job="api"})',
                                 QSTART, QEND, STEP)
        assert a.num_series == d.num_series == 4
        fa = a.values[:, -1]
        fd = d.values[:, -1]
        assert np.all(np.diff(fa) >= 0)
        assert np.all(np.diff(fd) <= 0)


class TestSubqueries:
    def test_subquery_parses(self):
        from m3_tpu.query.promql import Subquery, parse

        e = parse("max_over_time(rate(x[5m])[30m:1m])")
        sq = e.args[0]
        assert isinstance(sq, Subquery)
        assert sq.range_nanos == 30 * 60 * 10**9
        assert sq.step_nanos == 60 * 10**9
        # default-step + offset forms
        e2 = parse("avg_over_time(y[1h:] offset 5m)").args[0]
        assert e2.step_nanos == 0 and e2.offset_nanos == 300 * 10**9

    def test_max_over_time_of_rate_subquery(self, engine):
        """The canonical subquery: max of a rate over a longer window
        must be >= the instantaneous rate at every step and finite for
        a steadily increasing counter."""
        inner = engine.execute_range(
            'rate(http_requests_total{host="h0", job="api"}[5m])',
            QSTART, QEND, STEP)
        outer = engine.execute_range(
            'max_over_time(rate(http_requests_total{host="h0", job="api"}[5m])[10m:1m])',
            QSTART, QEND, STEP)
        assert outer.num_series == 1
        ok = ~(np.isnan(outer.values[0]) | np.isnan(inner.values[0]))
        assert ok.any()
        assert np.all(outer.values[0][ok] >= inner.values[0][ok] - 1e-9)

    def test_avg_over_time_subquery_of_instant_vector(self, engine):
        b = engine.execute_range(
            'avg_over_time(http_requests_total{host="h0", job="api"}[10m:1m])',
            QSTART, QEND, STEP)
        assert b.num_series == 1
        assert np.isfinite(b.values[0, -1])

    def test_absent_over_time(self, engine):
        gone = engine.execute_range(
            'absent_over_time(no_such_metric[5m])', QSTART, QEND, STEP)
        assert gone.num_series == 1
        assert np.all(gone.values == 1.0)
        there = engine.execute_range(
            'absent_over_time(http_requests_total{job="api"}[5m])',
            QSTART, QEND, STEP)
        assert np.all(np.isnan(there.values))

    def test_range_function_over_absent_metric_is_empty(self, engine):
        """Every temporal family over a selector matching NO series
        must return an empty vector (Prometheus semantics), never
        error — the short-circuit sits before the jitted stencils,
        whose 0-row window gather cannot even shape itself."""
        for q in ("max_over_time(no_such_metric[5m])",
                  "rate(no_such_metric[5m])",
                  "quantile_over_time(0.9, no_such_metric[5m])",
                  "sum_over_time(no_such_metric[5m])",
                  "deriv(no_such_metric[5m])",
                  "changes(no_such_metric[5m])"):
            b = engine.execute_range(q, QSTART, QEND, STEP)
            assert b.num_series == 0, q

    def test_subquery_over_scalar_expr(self, engine):
        b = engine.execute_range('min_over_time(time()[10m:1m])',
                                 QSTART, QEND, STEP)
        assert b.num_series == 1
        # min over the trailing 10m grid of time() <= current time
        assert np.all(b.values[0] <= QEND / 1e9 + 1)
        assert np.isfinite(b.values[0, -1])


class TestAtModifier:
    def test_at_end_pins_instant_vector(self, engine):
        pinned = engine.execute_range(
            'http_requests_total{host="h0", job="api"} @ end()',
            QSTART, QEND, STEP)
        plain = engine.execute_range(
            'http_requests_total{host="h0", job="api"}',
            QSTART, QEND, STEP)
        assert pinned.num_series == 1
        # constant across steps, equal to the un-pinned final value
        assert np.all(pinned.values[0] == pinned.values[0, -1])
        assert pinned.values[0, -1] == plain.values[0, -1]

    def test_at_literal_timestamp_on_range_vector(self, engine):
        at_s = (QSTART + 6 * 60 * 10**9) / 1e9
        pinned = engine.execute_range(
            f'rate(http_requests_total{{host="h0", job="api"}}[5m] @ {at_s:.0f})',
            QSTART, QEND, STEP)
        assert np.all(pinned.values[0] == pinned.values[0, 0])
        assert np.isfinite(pinned.values[0, 0])

    def test_at_start_on_subquery(self, engine):
        b = engine.execute_range(
            'avg_over_time(http_requests_total{host="h0", job="api"}[10m:1m] @ start())',
            QSTART, QEND, STEP)
        assert np.all(b.values[0] == b.values[0, 0])

    def test_at_inside_subquery_resolves_top_level_bounds(self, engine):
        """Prometheus: start()/end() always mean the TOP-LEVEL query
        range, even inside a subquery whose inner grid is wider."""
        direct = engine.execute_range(
            'http_requests_total{host="h0", job="api"} @ start()',
            QSTART, QEND, STEP)
        sub = engine.execute_range(
            'last_over_time((http_requests_total{host="h0", job="api"}'
            ' @ start())[10m:1m])',
            QSTART, QEND, STEP)
        assert sub.values[0, -1] == direct.values[0, 0]


class TestDateAndTrigFunctions:
    def test_date_parts_of_time(self, engine):
        import datetime as _dt

        b = engine.execute_range("day_of_week()", QSTART, QEND, STEP)
        want = _dt.datetime.fromtimestamp(
            QSTART / 1e9, _dt.timezone.utc)
        # python: Monday=0..Sunday=6; Prometheus: Sunday=0..Saturday=6
        assert b.values[0, 0] == (want.weekday() + 1) % 7
        h = engine.execute_range("hour()", QSTART, QEND, STEP)
        assert h.values[0, 0] == want.hour
        m = engine.execute_range("month()", QSTART, QEND, STEP)
        assert m.values[0, 0] == want.month
        y = engine.execute_range("year()", QSTART, QEND, STEP)
        assert y.values[0, 0] == want.year
        dim = engine.execute_range("days_in_month()", QSTART, QEND, STEP)
        nxt = (want.replace(day=28) + _dt.timedelta(days=4)).replace(day=1)
        assert dim.values[0, 0] == (nxt - _dt.timedelta(days=1)).day

    def test_trig_and_pi(self, engine):
        b = engine.execute_range("sin(vector(0))", QSTART, QEND, STEP)
        assert b.values[0, 0] == 0.0
        p = engine.execute_range("pi()", QSTART, QEND, STEP)
        assert abs(p.values[0, 0] - np.pi) < 1e-15
        d = engine.execute_range("deg(vector(3.141592653589793))",
                                 QSTART, QEND, STEP)
        assert abs(d.values[0, 0] - 180.0) < 1e-9
