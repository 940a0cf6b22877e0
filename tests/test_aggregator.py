"""Aggregator: device arenas vs reference scalar semantics, CM stream
parity, engine windowing/flush."""

import math

import numpy as np
import pytest

from m3_tpu.aggregator.arena import CounterArena, GaugeArena, TimerArena
from m3_tpu.aggregator.engine import (
    Aggregator,
    AggregatorOptions,
    MetricList,
)
from m3_tpu.aggregator.quantile_cm import Stream
from m3_tpu.metrics.aggregation import AggregationID, AggregationType
from m3_tpu.metrics.policy import StoragePolicy
from m3_tpu.metrics.types import MetricType

import jax.numpy as jnp

R = 10 * 10**9  # 10s resolution


def _lane(arena, lanes, t: AggregationType):
    return np.asarray(lanes)[:, arena.lane_types.index(t)]


class TestCounterArena:
    def test_moments_match_reference_semantics(self):
        a = CounterArena(num_windows=2, capacity=8)
        rng = np.random.default_rng(0)
        slots = rng.integers(0, 8, 100).astype(np.int32)
        vals = rng.integers(-50, 100, 100).astype(np.int64)
        times = np.arange(100, dtype=np.int64)
        a.ingest(jnp.zeros(100, jnp.int32), jnp.asarray(slots), jnp.asarray(vals), jnp.asarray(times))
        lanes, counts = a.consume(0)
        counts = np.asarray(counts)
        for s in range(8):
            mine = vals[slots == s]
            assert counts[s] == mine.size
            assert _lane(a, lanes, AggregationType.SUM)[s] == mine.sum()
            assert _lane(a, lanes, AggregationType.MIN)[s] == mine.min()
            assert _lane(a, lanes, AggregationType.MAX)[s] == mine.max()
            np.testing.assert_allclose(
                _lane(a, lanes, AggregationType.MEAN)[s], mine.mean()
            )
            # stdev per reference common.go:29 (sample stdev from moments)
            if mine.size > 1:
                np.testing.assert_allclose(
                    _lane(a, lanes, AggregationType.STDEV)[s],
                    np.std(mine.astype(np.float64), ddof=1),
                    rtol=1e-9,
                )

    def test_window_isolation_and_reset(self):
        a = CounterArena(num_windows=2, capacity=4)
        a.ingest(
            jnp.asarray(np.array([0, 1], np.int32)),
            jnp.asarray(np.array([2, 2], np.int32)),
            jnp.asarray(np.array([5, 7], np.int64)),
            jnp.asarray(np.array([1, 2], np.int64)),
        )
        lanes0, c0 = a.consume(0)
        lanes1, c1 = a.consume(1)
        assert _lane(a, lanes0, AggregationType.SUM)[2] == 5
        assert _lane(a, lanes1, AggregationType.SUM)[2] == 7
        a.reset_window(0)
        lanes0b, c0b = a.consume(0)
        assert np.asarray(c0b)[2] == 0
        assert _lane(a, lanes1, AggregationType.SUM)[2] == 7


class TestGaugeArena:
    def test_last_max_timestamp_wins(self):
        a = GaugeArena(num_windows=1, capacity=4)
        # arrivals out of order; slot 1: t=30 value 3.0 must win
        wins = np.zeros(5, np.int32)
        slots = np.array([1, 1, 1, 2, 2], np.int32)
        vals = np.array([1.0, 3.0, 2.0, 9.0, 8.0])
        times = np.array([10, 30, 20, 5, 5], np.int64)
        a.ingest(jnp.asarray(wins), jnp.asarray(slots), jnp.asarray(vals), jnp.asarray(times))
        lanes, _ = a.consume(0)
        assert _lane(a, lanes, AggregationType.LAST)[1] == 3.0
        # equal timestamps: first arrival wins (reference gauge.go:82-91)
        assert _lane(a, lanes, AggregationType.LAST)[2] == 9.0

    def test_equal_timestamp_across_batches_keeps_first(self):
        a = GaugeArena(num_windows=1, capacity=2)
        z = jnp.zeros(1, jnp.int32)
        s = jnp.asarray(np.array([0], np.int32))
        t = jnp.asarray(np.array([100], np.int64))
        a.ingest(z, s, jnp.asarray(np.array([1.5])), t)
        a.ingest(z, s, jnp.asarray(np.array([2.5])), t)  # same ts: no update
        lanes, _ = a.consume(0)
        assert _lane(a, lanes, AggregationType.LAST)[0] == 1.5

    def test_nan_counted_but_not_summed(self):
        a = GaugeArena(num_windows=1, capacity=2)
        z = jnp.zeros(3, jnp.int32)
        s = jnp.asarray(np.array([0, 0, 0], np.int32))
        vals = jnp.asarray(np.array([1.0, np.nan, 3.0]))
        t = jnp.asarray(np.array([1, 2, 3], np.int64))
        a.ingest(z, s, vals, t)
        lanes, counts = a.consume(0)
        assert np.asarray(counts)[0] == 3  # NaN counted (gauge.go:85 count++)
        assert _lane(a, lanes, AggregationType.SUM)[0] == 4.0
        assert _lane(a, lanes, AggregationType.MIN)[0] == 1.0
        assert _lane(a, lanes, AggregationType.MAX)[0] == 3.0


class TestTimerArena:
    def test_exact_quantiles(self):
        a = TimerArena(num_windows=1, capacity=4, sample_capacity=1 << 12)
        rng = np.random.default_rng(42)
        n = 3000
        slots = rng.integers(0, 4, n).astype(np.int32)
        vals = rng.normal(100.0, 15.0, n)
        times = np.arange(n, dtype=np.int64)
        a.ingest(jnp.zeros(n, jnp.int32), jnp.asarray(slots), jnp.asarray(vals), jnp.asarray(times))
        lanes, counts = a.consume(0)
        for s in range(4):
            mine = np.sort(vals[slots == s])
            cnt = mine.size
            assert np.asarray(counts)[s] == cnt
            for q, t in ((0.5, AggregationType.P50), (0.95, AggregationType.P95), (0.99, AggregationType.P99)):
                rank = max(int(math.ceil(q * cnt)) - 1, 0)
                assert _lane(a, lanes, t)[s] == mine[rank]
            assert _lane(a, lanes, AggregationType.MIN)[s] == mine[0]
            assert _lane(a, lanes, AggregationType.MAX)[s] == mine[-1]

    def test_multi_batch_append(self):
        a = TimerArena(num_windows=2, capacity=2, sample_capacity=64)
        for batch in range(3):
            a.ingest(
                jnp.zeros(4, jnp.int32),
                jnp.asarray(np.array([0, 0, 1, 1], np.int32)),
                jnp.asarray(np.arange(4, dtype=np.float64) + 10 * batch),
                jnp.asarray(np.arange(4, dtype=np.int64)),
            )
        lanes, counts = a.consume(0)
        assert np.asarray(counts)[0] == 6
        assert _lane(a, lanes, AggregationType.MAX)[0] == 21.0
        a.reset_window(0)
        lanes, counts = a.consume(0)
        assert np.asarray(counts)[0] == 0


class TestCMStreamParity:
    """The CM stream is eps-approximate; exact sorted quantiles must fall
    within its error bound, and on small inputs it is exact."""

    def test_small_exact(self):
        s = Stream([0.5, 0.95, 0.99])
        s.add_batch([5.0, 1.0, 3.0])
        s.flush()
        assert s.quantile(0.5) == 3.0

    def test_large_within_eps(self):
        rng = np.random.default_rng(7)
        vals = rng.uniform(0, 1000, 50_000)
        s = Stream([0.5, 0.95, 0.99])
        s.add_batch(list(vals))
        s.flush()
        sv = np.sort(vals)
        n = sv.size
        for q in (0.5, 0.95, 0.99):
            got = s.quantile(q)
            # rank error bound: eps * n (cm guarantees biased-quantile eps)
            lo = sv[max(int((q - 0.01) * n), 0)]
            hi = sv[min(int((q + 0.01) * n), n - 1)]
            assert lo <= got <= hi, (q, lo, got, hi)

    def test_min_max(self):
        s = Stream([0.5])
        s.add_batch([4.0, 2.0, 9.0, 7.0])
        s.flush()
        assert s.min() == 2.0
        assert s.max() == 9.0

    def test_empty(self):
        s = Stream([0.5])
        s.flush()
        assert s.quantile(0.5) == 0.0

    def test_device_quantiles_within_cm_bound(self):
        """Device-exact and reference-algorithm quantiles agree within eps."""
        rng = np.random.default_rng(3)
        vals = rng.normal(50, 10, 20_000)
        cm = Stream([0.5, 0.95, 0.99])
        cm.add_batch(list(vals))
        cm.flush()

        a = TimerArena(num_windows=1, capacity=1, sample_capacity=1 << 15)
        n = vals.size
        a.ingest(
            jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32),
            jnp.asarray(vals),
            jnp.arange(n, dtype=jnp.int64),
        )
        lanes, _ = a.consume(0)
        sv = np.sort(vals)
        for q, t in ((0.5, AggregationType.P50), (0.95, AggregationType.P95), (0.99, AggregationType.P99)):
            exact = float(_lane(a, lanes, t)[0])
            approx = cm.quantile(q)
            lo = sv[max(int((q - 0.005) * n), 0)]
            hi = sv[min(int((q + 0.005) * n), n - 1)]
            assert lo <= exact <= hi
            assert lo <= approx <= hi


class TestEngine:
    def _opts(self):
        return AggregatorOptions(
            capacity=64,
            num_windows=2,
            timer_sample_capacity=1 << 10,
            storage_policies=(StoragePolicy.parse("10s:2d"),),
        )

    def test_counter_flush_default_sum(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        ids = [b"cpu.load", b"cpu.load", b"mem.used"]
        vals = np.array([3, 4, 10], np.int64)
        times = np.array([R + 1, R + 2, R + 3], np.int64)
        agg.add_untimed_batch(MetricType.COUNTER, ids, vals, times)
        flushed = agg.consume(2 * R + 1)
        assert len(flushed) == 1
        f = flushed[0]
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        got = {}
        for slot, t, v in zip(f.slots, f.types, f.values):
            mid = ml.maps[MetricType.COUNTER].id_of(int(slot))
            got[(mid, AggregationType(int(t)))] = v
        assert got[(b"cpu.load", AggregationType.SUM)] == 7.0
        assert got[(b"mem.used", AggregationType.SUM)] == 10.0
        assert f.timestamp_nanos == 2 * R

    def test_custom_aggregation_id(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        aid = AggregationID.compress([AggregationType.MIN, AggregationType.MAX])
        agg.add_untimed_batch(
            MetricType.GAUGE,
            [b"g", b"g"],
            np.array([2.0, 8.0]),
            np.array([R + 1, R + 2], np.int64),
            agg_id=aid,
        )
        f = agg.consume(2 * R + 1)[0]
        types = set(AggregationType(int(t)) for t in f.types)
        assert types == {AggregationType.MIN, AggregationType.MAX}

    def test_windows_drain_in_order(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        # two consecutive windows
        agg.add_untimed_batch(
            MetricType.COUNTER,
            [b"c", b"c"],
            np.array([1, 2], np.int64),
            np.array([R + 1, 2 * R + 1], np.int64),
        )
        flushed = agg.consume(3 * R + 1)
        assert len(flushed) == 2
        assert flushed[0].timestamp_nanos == 2 * R
        assert flushed[1].timestamp_nanos == 3 * R
        assert flushed[0].values[0] == 1.0
        assert flushed[1].values[0] == 2.0

    def test_late_metrics_dropped(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"c"], np.array([1], np.int64), np.array([5 * R], np.int64)
        )
        agg.consume(6 * R + 1)
        # now a metric for the already-consumed window
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"c"], np.array([9], np.int64), np.array([R], np.int64)
        )
        assert ml.drops == 1
        flushed = agg.consume(7 * R)
        assert flushed == []

    def test_packed32_consume_matches_exact(self):
        """The packed32 drain (one i64 slot<<32|orderable-f32 key) must
        reproduce the exact f64 lex-sort drain: counts and moments
        bit-equal, quantile/min/max lanes within f32 eps — including
        negative values and the -0.0/+0.0 bit-order edge."""
        a = TimerArena(num_windows=1, capacity=8, sample_capacity=1 << 12)
        p = TimerArena(num_windows=1, capacity=8, sample_capacity=1 << 12,
                       packed32=True)
        rng = np.random.default_rng(21)
        n = 4000
        slots = rng.integers(0, 8, n).astype(np.int32)
        vals = rng.normal(0.0, 50.0, n)  # both signs
        vals[:8] = [0.0, -0.0, 1e-38, -1e-38, 3e8, -3e8, 0.5, -0.5]
        times = np.arange(n, dtype=np.int64)
        for arena_ in (a, p):
            arena_.ingest(jnp.zeros(n, jnp.int32), jnp.asarray(slots),
                          jnp.asarray(vals), jnp.asarray(times))
        le, ce = a.consume(0)
        lp, cp = p.consume(0)
        assert np.array_equal(np.asarray(ce), np.asarray(cp))
        le, lp = np.asarray(le), np.asarray(lp)
        # moments lanes (mean/count/sum/sumsq/stdev) bit-equal
        assert np.array_equal(le[:, 3:8], lp[:, 3:8])
        # order-statistic lanes within f32 eps
        sel = np.abs(le[:, 1:3]) > 0
        rel = np.abs(le[:, 1:3] - lp[:, 1:3])[sel] / np.abs(le[:, 1:3][sel])
        assert rel.size == 0 or rel.max() < 2e-7
        qe, qp = le[:, 8:], lp[:, 8:]
        sel = np.abs(qe) > 0
        rel = np.abs(qe - qp)[sel] / np.abs(qe[sel])
        assert rel.max() < 2e-7

    def test_timer_sample_buffer_grows_no_drops(self):
        opts = AggregatorOptions(
            capacity=8,
            num_windows=2,
            timer_sample_capacity=8,  # force growth: 100 samples
            storage_policies=(StoragePolicy.parse("10s:2d"),),
        )
        agg = Aggregator(num_shards=1, opts=opts)
        vals = np.arange(1, 101, dtype=np.float64)
        agg.add_untimed_batch(
            MetricType.TIMER, [b"lat"] * 100, vals, np.full(100, R + 5, np.int64)
        )
        f = agg.consume(2 * R + 1)[0]
        got = {AggregationType(int(t)): v for t, v in zip(f.types, f.values)}
        assert got[AggregationType.MAX] == 100.0
        assert got[AggregationType.P50] == 50.0
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        assert ml.timers.sample_capacity >= 100

    def test_same_id_two_aggregation_keys(self):
        # Reference keys elements by (id, aggregation key): both sets emit.
        agg = Aggregator(num_shards=1, opts=self._opts())
        t = np.array([R + 1], np.int64)
        agg.add_untimed_batch(
            MetricType.GAUGE, [b"g"], np.array([5.0]), t,
            agg_id=AggregationID.compress([AggregationType.MIN]),
        )
        agg.add_untimed_batch(
            MetricType.GAUGE, [b"g"], np.array([7.0]), t,
            agg_id=AggregationID.compress([AggregationType.MAX]),
        )
        flushed = agg.consume(2 * R + 1)
        types = {AggregationType(int(t)) for f in flushed for t in f.types}
        assert types == {AggregationType.MIN, AggregationType.MAX}

    def test_invalid_types_filtered_from_mask(self):
        # LAST is invalid for counters (reference IsValidForCounter).
        agg = Aggregator(num_shards=1, opts=self._opts())
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"c"], np.array([5], np.int64),
            np.array([R + 1], np.int64),
            agg_id=AggregationID.compress([AggregationType.LAST, AggregationType.SUM]),
        )
        f = agg.consume(2 * R + 1)[0]
        types = {AggregationType(int(t)) for t in f.types}
        assert types == {AggregationType.SUM}

    def test_idle_gap_skips_empty_windows(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"c"], np.array([1], np.int64),
            np.array([R + 1], np.int64),
        )
        agg.consume(2 * R)
        # 1 hour idle: consume must not drain 360 windows
        target = 2 * R + 360 * R + 5
        assert len(ml.open_windows(target)) <= ml.opts.num_windows
        agg.consume(target)
        assert ml.consumed_until == (target // R) * R
        # fresh ingest at the new watermark still flushes
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"c"], np.array([2], np.int64),
            np.array([ml.consumed_until + 1], np.int64),
        )
        f = agg.consume(ml.consumed_until + R + 1)
        assert len(f) == 1 and f[0].values[0] == 2.0

    def test_expire_recycles_slots(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"old"], np.array([1], np.int64),
            np.array([R + 1], np.int64),
        )
        agg.consume(2 * R + 1)
        assert len(ml.maps[MetricType.COUNTER]) == 1
        released = ml.expire(now_nanos=100 * R, ttl_nanos=10 * R)
        assert released == 1
        assert len(ml.maps[MetricType.COUNTER]) == 0
        # slot is recycled for a new series
        agg.add_untimed_batch(
            MetricType.COUNTER, [b"new"], np.array([2], np.int64),
            np.array([100 * R + 1], np.int64),
        )
        assert len(ml.maps[MetricType.COUNTER]) == 1

    def test_expire_clears_undrained_window_state(self):
        # Regression: a slot freed with un-drained window stats must not
        # leak them into the next occupant of the same slot.
        agg = Aggregator(num_shards=1, opts=self._opts())
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        for mt, val in (
            (MetricType.COUNTER, np.array([100], np.int64)),
            (MetricType.GAUGE, np.array([100.0])),
            (MetricType.TIMER, np.array([100.0])),
        ):
            agg.add_untimed_batch(mt, [b"old"], val, np.array([R + 1], np.int64))
        # Never consumed: stats sit in the open window when expire runs.
        released = ml.expire(now_nanos=100 * R, ttl_nanos=10 * R)
        assert released == 3
        for mt, val in (
            (MetricType.COUNTER, np.array([7], np.int64)),
            (MetricType.GAUGE, np.array([7.0])),
            (MetricType.TIMER, np.array([7.0])),
        ):
            # Re-ingest into the *recycled* slot and the *same* ring row.
            ml.consumed_until = None
            agg.add_untimed_batch(mt, [b"new"], val, np.array([R + 1], np.int64))
        flushed = agg.consume(2 * R + 1)
        assert flushed
        expect = {
            AggregationType.SUM: 7.0,
            AggregationType.COUNT: 1.0,
            AggregationType.LAST: 7.0,
            AggregationType.MEAN: 7.0,
            AggregationType.P50: 7.0,
            AggregationType.MAX: 7.0,
        }
        for f in flushed:
            got = {AggregationType(int(t)): v for t, v in zip(f.types, f.values)}
            for t, want in expect.items():
                if t in got:
                    assert got[t] == want, (t, got)

    def test_timer_quantile_flush(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        vals = np.arange(1, 101, dtype=np.float64)
        agg.add_untimed_batch(
            MetricType.TIMER,
            [b"lat"] * 100,
            vals,
            np.full(100, R + 5, np.int64),
        )
        f = agg.consume(2 * R + 1)[0]
        got = {AggregationType(int(t)): v for t, v in zip(f.types, f.values)}
        assert got[AggregationType.P50] == 50.0
        assert got[AggregationType.P95] == 95.0
        assert got[AggregationType.P99] == 99.0
        assert got[AggregationType.MAX] == 100.0
        np.testing.assert_allclose(got[AggregationType.MEAN], vals.mean())


def _bits(x) -> int:
    return int(np.float64(x).view(np.int64))


class TestTimerMoments:
    """The packed timer drain runs its moments' segment sums only when
    some timer slot's mask asks for MEAN, SUM, SUM_SQ or STDEV (the
    union of the map's masks, read at each drain)."""

    POLICY = StoragePolicy.parse("10s:2d")
    QS = AggregationID.compress([AggregationType.P50, AggregationType.P95,
                                 AggregationType.P99])
    MOMENTS = (AggregationType.MEAN, AggregationType.SUM,
               AggregationType.SUM_SQ, AggregationType.STDEV)

    def _agg(self, capacity=64):
        return Aggregator(num_shards=1, opts=AggregatorOptions(
            capacity=capacity, num_windows=2, timer_sample_capacity=1 << 10,
            storage_policies=(self.POLICY,)))

    def _timers(self, agg, ids, vals, t, agg_id):
        agg.add_untimed_batch(MetricType.TIMER, ids, np.asarray(vals),
                              np.full(len(ids), t, np.int64), agg_id=agg_id)

    def _drain(self, agg, target):
        """consume under an installed tracer -> (rows by (id, type),
        the `moments` tag of each timer drain span)"""
        from m3_tpu.instrument import tracing
        from m3_tpu.instrument.tracing import Tracer

        tr = Tracer(enabled=True)
        tracing.install(tr)
        try:
            flushed = agg.consume(target)
        finally:
            tracing.uninstall(tr)
        ml = agg.shards[0].lists[self.POLICY]
        rows = {}
        for f in flushed:
            if f.metric_type is MetricType.TIMER:
                for s, t, v in zip(f.slots, f.types, f.values):
                    mid = ml.maps[MetricType.TIMER].id_of(int(s))
                    rows[(mid, AggregationType(int(t)))] = v
        tags = [s.tags["moments"]
                for s in tr.finished("aggregator.drain.timer")]
        return rows, tags

    def _expect(self, agg, window):
        """Every (id, type) the masks ask for, from the same window
        drained with the moments on (the program as it ran before)."""
        ml = agg.shards[0].lists[self.POLICY]
        lanes, counts = map(np.asarray, ml.timers.consume(window,
                                                          moments=True))
        m = ml.maps[MetricType.TIMER]
        out = {}
        for slot in np.nonzero(counts)[0]:
            mask = int(m.agg_mask[slot])
            for t in AggregationType:
                if t.is_valid() and mask >> int(t) & 1:
                    out[(m.id_of(int(slot)), t)] = lanes[
                        slot, ml.timers.lane_for_type(t)]
        return out

    def test_quantile_only_map_skips_the_moments(self):
        agg = self._agg()
        rng = np.random.default_rng(5)
        ids = [b"t%d" % (i % 9) for i in range(300)]
        self._timers(agg, ids, rng.gamma(2.0, 40.0, 300), R + 5, self.QS)
        want = self._expect(agg, 1)
        rows, tags = self._drain(agg, 2 * R + 1)
        assert agg.counters()["timer_moments_skipped"] == 1
        assert tags == [0]
        assert {t for _, t in rows} == {AggregationType.P50,
                                        AggregationType.P95,
                                        AggregationType.P99}
        assert rows.keys() == want.keys()
        for k, v in rows.items():
            assert _bits(v) == _bits(want[k]), k

    @pytest.mark.parametrize("asked", MOMENTS, ids=lambda t: t.name)
    def test_a_slot_that_asks_for_a_moment_runs_them(self, asked):
        agg = self._agg()
        rng = np.random.default_rng(int(asked))
        ids = [b"t%d" % (i % 9) for i in range(300)]
        self._timers(agg, ids, rng.gamma(2.0, 40.0, 300), R + 5, self.QS)
        self._timers(agg, [b"x"] * 7, rng.gamma(2.0, 40.0, 7), R + 6,
                     AggregationID.compress([asked]))
        want = self._expect(agg, 1)
        rows, tags = self._drain(agg, 2 * R + 1)
        assert agg.counters()["timer_moments_skipped"] == 0
        assert tags == [1]
        assert rows[(b"x", asked)] != 0.0
        assert rows.keys() == want.keys()
        for k, v in rows.items():
            assert _bits(v) == _bits(want[k]), k

    def test_the_union_follows_an_expired_slot_in_one_program(self):
        """The SUM slot goes idle and is released: the next drain runs
        without the moments, and both drains are one compiled program (a
        capacity no other test uses, so its one compile is counted)."""
        from m3_tpu.x import tracewatch

        agg = self._agg(capacity=37)
        ml = agg.shards[0].lists[self.POLICY]
        summed = [1.5, 2.25, 3.0, 4.5, 8.0]
        self._timers(agg, [b"sum"] * 5, summed, R + 1,
                     AggregationID.compress([AggregationType.SUM]))
        self._timers(agg, [b"q"] * 5, [5.0, 1.0, 4.0, 2.0, 3.0], R + 2,
                     self.QS)
        was_installed = tracewatch.installed()
        tracewatch.install(raise_on_violation=False)
        try:
            before = dict(tracewatch.compiles())
            first, tags1 = self._drain(agg, 2 * R + 1)
            self._timers(agg, [b"q"] * 5, [9.0, 7.0, 6.0, 8.0, 10.0],
                         2 * R + 1, self.QS)
            assert ml.expire(now_nanos=2 * R + 5, ttl_nanos=R) == 1
            second, tags2 = self._drain(agg, 3 * R + 1)
            new = tracewatch.compiles().get("timer_consume", 0) \
                - before.get("timer_consume", 0)
        finally:
            if not was_installed:
                tracewatch.uninstall()
        assert (tags1, tags2) == ([1], [0])
        assert new == 1
        assert agg.counters()["timer_moments_skipped"] == 1
        assert first[(b"sum", AggregationType.SUM)] == sum(summed)
        assert first[(b"q", AggregationType.P50)] == 3.0
        assert first[(b"q", AggregationType.P99)] == 5.0
        assert second == {(b"q", AggregationType.P50): 8.0,
                          (b"q", AggregationType.P95): 10.0,
                          (b"q", AggregationType.P99): 10.0}

    @pytest.mark.parametrize("asked", [AggregationType.P50,
                                       AggregationType.SUM],
                             ids=lambda t: t.name)
    def test_an_empty_window_skips_whatever_the_masks_say(self, asked):
        agg = self._agg()
        ml = agg.shards[0].lists[self.POLICY]
        # the drained window holds a counter; the timer slot exists, but
        # its sample lands in the next window
        agg.add_untimed_batch(MetricType.COUNTER, [b"c"],
                              np.array([1], np.int64),
                              np.array([R + 1], np.int64))
        self._timers(agg, [b"x"], [4.0], 2 * R + 1,
                     AggregationID.compress([asked]))
        lanes, counts = map(np.asarray, ml.timers.consume(1, moments=True))
        assert not counts.any() and not np.nan_to_num(lanes, nan=0.0).any()
        rows, tags = self._drain(agg, 2 * R + 1)
        assert rows == {}
        assert tags == [int(asked is AggregationType.SUM)]
        assert agg.counters()["timer_moments_skipped"] == 0


class TestNativeIdMapParity:
    """The native batch resolver (native/idmap.cc) must be
    observationally identical to the Python dict path: same find-or-
    create semantics, release/recycle, per-(id, mask) keying."""

    def _drive(self, mm):
        from m3_tpu.metrics.aggregation import AggregationID
        from m3_tpu.metrics.types import MetricType

        agg = AggregationID.DEFAULT
        ids1 = [b"m-%03d" % i for i in range(50)]
        s1 = mm.resolve(ids1, agg, MetricType.GAUGE)
        s2 = mm.resolve(ids1, agg, MetricType.GAUGE)
        assert (s1 == s2).all()          # idempotent find
        assert len(set(s1.tolist())) == 50
        assert mm.id_of(int(s1[7])) == b"m-007"
        # release + re-create recycles without aliasing live slots
        mm.release(int(s1[0]))
        s3 = mm.resolve([b"m-000", b"new-metric"], agg, MetricType.GAUGE)
        assert s3[0] not in s1[1:]       # may reuse slot 0 or allocate
        return {mm.id_of(int(s)) for s in s1[1:]} | {b"m-000", b"new-metric"}

    def test_native_matches_python(self):
        from m3_tpu.aggregator.engine import MetricMap

        py = MetricMap(1 << 10, use_native=False)
        out_py = self._drive(py)
        nat = MetricMap(1 << 10, use_native=True)
        assert nat._native is not None
        out_nat = self._drive(nat)
        assert out_py == out_nat

    def test_mask_keys_distinct_slots(self):
        from m3_tpu.aggregator.engine import MetricMap
        from m3_tpu.metrics.aggregation import AggregationID, AggregationType
        from m3_tpu.metrics.types import MetricType

        mm = MetricMap(1 << 8)
        a = AggregationID.compress([AggregationType.SUM])
        b = AggregationID.compress([AggregationType.MAX])
        sa = mm.resolve([b"same-id"], a, MetricType.GAUGE)
        sb = mm.resolve([b"same-id"], b, MetricType.GAUGE)
        assert sa[0] != sb[0]            # one elem per aggregation key
        assert mm.id_of(int(sa[0])) == b"same-id" == mm.id_of(int(sb[0]))


class TestTimedAndPassthrough:
    """Reference aggregator.go:77 AddTimed / :86 AddPassthrough — the
    two ingest classes round 3 lacked entirely."""

    def _opts(self):
        return AggregatorOptions(
            capacity=64,
            num_windows=2,
            timer_sample_capacity=1 << 10,
            storage_policies=(StoragePolicy.parse("10s:2d"),),
        )

    def test_timed_lands_by_own_timestamp(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        R = 10 * 10**9
        t0 = 1_700_000_000 * 10**9 // R * R
        # Two samples with explicit timestamps in DIFFERENT windows,
        # delivered in one batch (arrival time irrelevant).
        acc = agg.add_timed_batch(
            MetricType.COUNTER, [b"c", b"c"], np.asarray([5.0, 7.0]),
            np.asarray([t0 + 1, t0 + R + 1], np.int64))
        assert acc.all()
        out = agg.consume(t0 + 2 * R)
        sums = {fm.timestamp_nanos: fm.values for fm in out}
        assert float(sums[t0 + R][list(
            (np.asarray(out[0].types) == int(AggregationType.SUM)).nonzero()[0])[0]]) == 5.0
        assert len(sums) == 2

    def test_timed_rejects_out_of_window(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        R = 10 * 10**9
        t0 = 1_700_000_000 * 10**9 // R * R
        # Seed the window base.
        agg.add_timed_batch(MetricType.COUNTER, [b"c"], np.ones(1),
                            np.asarray([t0 + 1], np.int64))
        # Too far future (>= W windows ahead) and too early (behind the
        # consumed watermark after a consume).
        acc = agg.add_timed_batch(
            MetricType.COUNTER, [b"c"], np.ones(1),
            np.asarray([t0 + 5 * R], np.int64))
        assert not acc.any()
        out = agg.consume(t0 + R)
        acc2 = agg.add_timed_batch(
            MetricType.COUNTER, [b"c"], np.ones(1),
            np.asarray([t0 - R], np.int64))
        assert not acc2.any()
        ml = agg.shards[0].lists[StoragePolicy.parse("10s:2d")]
        assert ml.timed_rejects["too_far_future"] == 1
        assert ml.timed_rejects["too_early"] == 1
        # The rejected samples never pollute an aggregate: across every
        # drained window only the one accepted sample shows up.
        out += agg.consume(t0 + 3 * R)
        total = sum(float(v) for fm in out
                    for t, v in zip(fm.types, fm.values)
                    if int(t) == int(AggregationType.SUM))
        assert total == 1.0

    def test_passthrough_bypasses_arenas(self):
        got = []
        agg = Aggregator(num_shards=1, opts=self._opts(),
                         passthrough_handler=got.append)
        sp = StoragePolicy.parse("1m:40d")
        agg.add_passthrough_batch(
            [b"already.agg"], np.asarray([42.0]),
            np.asarray([123], np.int64), sp)
        assert len(got) == 1 and got[0].policy == sp
        assert list(got[0].ids) == [b"already.agg"]
        assert agg.passthrough_samples == 1
        # nothing entered the arenas
        assert agg.consume(10**30) == []

    def test_passthrough_without_handler_raises(self):
        agg = Aggregator(num_shards=1, opts=self._opts())
        with pytest.raises(RuntimeError, match="passthrough"):
            agg.add_passthrough_batch([b"x"], np.ones(1),
                                      np.zeros(1, np.int64),
                                      StoragePolicy.parse("1m:40d"))
