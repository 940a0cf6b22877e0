"""Packed arena (aggregator/packed.py) parity against the f64 oracle.

The acceptance contract (round 8): counter lanes and gauge
LAST/MIN/MAX/COUNT bit-exact vs the scatter arenas; gauge/timer
sum/sum_sq within 1e-6 relative (scan-order f64 adds / f32 value
precision); overflow-pool promotion boundaries preserve exactness.
STDEV is derived from the checked moments — cancellation amplifies the
sum envelope arbitrarily, so it is compared against a stdev recomputed
from the packed path's own moments instead of a fixed rtol.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from m3_tpu.aggregator import arena, packed
from m3_tpu.aggregator.engine import AggregatorOptions, MetricList
from m3_tpu.metrics.policy import StoragePolicy
from m3_tpu.metrics.types import MetricType

SEC = 10**9
T0 = 1_700_000_000 * SEC


def _batches(rng, n_batches, n, W, C, nonfinite=False):
    for _ in range(n_batches):
        windows = rng.integers(-1, W + 1, n).astype(np.int32)
        slots = rng.integers(-2, C + 3, n).astype(np.int32)
        cvals = rng.integers(-2000, 2000, n).astype(np.int64)
        gvals = np.round(rng.uniform(-50, 50, n), 3)
        if nonfinite:
            gvals[rng.integers(0, n, max(n // 50, 1))] = np.nan
            gvals[rng.integers(0, n, max(n // 100, 1))] = np.inf
            gvals[rng.integers(0, n, max(n // 100, 1))] = -np.inf
        times = T0 + rng.integers(0, SEC, n)
        yield windows, slots, cvals, gvals, times


@functools.partial(jax.jit, static_argnames=("capacity",))
def _moments_before(words, capacity):
    """A window's (sum, sum_sq) as packed.timer_consume computed them
    before it took the `moments` operand: two sorted segment sums over
    the decoded sorted words, sentinels in the spare row."""
    keys = jax.lax.sort(words)
    slot = (keys >> jnp.uint64(32)).astype(jnp.int32)
    val = packed.decode_orderable_f32(keys & jnp.uint64(0xFFFFFFFF))
    v = jnp.where(slot < capacity, val, 0.0)
    seg = jnp.minimum(slot, capacity)
    return tuple(
        jax.ops.segment_sum(x, seg, num_segments=capacity + 1,
                            indices_are_sorted=True)[:capacity]
        for x in (v, v * v))


def _assert_counter_parity(f64_arena, packed_arena, W):
    for w in range(W):
        cl, cc = map(np.asarray, f64_arena.consume(w))
        pl, pc = map(np.asarray, packed_arena.consume(w))
        np.testing.assert_array_equal(cc, pc)
        # every non-derived lane bit-exact (stdev = lane 7 recomputed
        # from identical moments is also identical, but keep the
        # contract explicit)
        assert np.all((cl[:, :7] == pl[:, :7])
                      | (np.isnan(cl[:, :7]) & np.isnan(pl[:, :7])))


def _assert_gauge_parity(f64_arena, packed_arena, W, rtol=1e-6):
    for w in range(W):
        gl, gc = map(np.asarray, f64_arena.consume(w))
        pl, pc = map(np.asarray, packed_arena.consume(w))
        np.testing.assert_array_equal(gc, pc)
        for lane in (0, 1, 2, 4):  # LAST/MIN/MAX/COUNT bit-exact
            a, b = gl[:, lane], pl[:, lane]
            assert np.all((a == b) | (np.isnan(a) & np.isnan(b))), lane
        for lane in (3, 5, 6):  # MEAN/SUM/SUM_SQ within the envelope
            a, b = gl[:, lane], pl[:, lane]
            same_class = (np.isnan(a) == np.isnan(b))
            assert same_class.all(), lane
            fin = np.isfinite(a) & np.isfinite(b)
            inf = np.isinf(a)
            assert np.array_equal(a[inf], b[inf]), lane
            np.testing.assert_allclose(b[fin], a[fin], rtol=rtol,
                                       atol=1e-30)
        # stdev consistent with the packed path's own moments
        cnt = pc.astype(np.float64)
        var_num = np.maximum(cnt * pl[:, 6] - pl[:, 5] ** 2, 0.0)
        div = np.where(cnt * (cnt - 1) <= 0, 1.0, cnt * (cnt - 1))
        want = np.where(cnt * (cnt - 1) <= 0, 0.0, np.sqrt(var_num / div))
        fin = np.isfinite(want)
        np.testing.assert_allclose(pl[:, 7][fin], want[fin], rtol=1e-9,
                                   atol=1e-12)


class TestCounterGaugeParity:
    W, C = 2, 257  # odd capacity: no accidental alignment

    def test_multi_batch_parity_with_oob_and_nonfinite(self):
        rng = np.random.default_rng(11)
        ca = arena.CounterArena(self.W, self.C)
        ga = arena.GaugeArena(self.W, self.C)
        pca = packed.PackedCounterArena(self.W, self.C)
        pga = packed.PackedGaugeArena(self.W, self.C)
        for windows, slots, cvals, gvals, times in _batches(
                rng, 5, 1500, self.W, self.C, nonfinite=True):
            args = (jnp.asarray(windows), jnp.asarray(slots))
            ca.ingest(*args, jnp.asarray(cvals), jnp.asarray(times))
            pca.ingest(*args, jnp.asarray(cvals), jnp.asarray(times))
            ga.ingest(*args, jnp.asarray(gvals), jnp.asarray(times))
            pga.ingest(*args, jnp.asarray(gvals), jnp.asarray(times))
        _assert_counter_parity(ca, pca, self.W)
        _assert_gauge_parity(ga, pga, self.W)
        # expiry column: window-dropped samples with a valid slot must
        # still bump last_at (the ghost region)
        np.testing.assert_array_equal(np.asarray(ca.state.last_at),
                                      np.asarray(pca.state.last_at))
        np.testing.assert_array_equal(np.asarray(ga.state.last_at),
                                      np.asarray(pga.state.last_at))

    def test_gauge_last_tie_first_arrival_wins(self):
        ga = arena.GaugeArena(1, 8)
        pga = packed.PackedGaugeArena(1, 8)
        w = jnp.zeros(3, jnp.int32)
        s = jnp.zeros(3, jnp.int32)
        t = jnp.asarray([T0, T0, T0 - 1], jnp.int64)  # two tied, one older
        v = jnp.asarray([1.25, 2.5, 9.0])
        ga.ingest(w, s, v, t)
        pga.ingest(w, s, v, t)
        gl = np.asarray(ga.consume(0)[0])
        pl = np.asarray(pga.consume(0)[0])
        assert gl[0, 0] == pl[0, 0] == 1.25  # first arrival of max time

    def test_reset_and_clear_parity(self):
        rng = np.random.default_rng(13)
        ca = arena.CounterArena(self.W, self.C)
        pca = packed.PackedCounterArena(self.W, self.C)
        for windows, slots, cvals, _g, times in _batches(
                rng, 3, 1000, self.W, self.C):
            args = (jnp.asarray(windows), jnp.asarray(slots))
            ca.ingest(*args, jnp.asarray(cvals), jnp.asarray(times))
            pca.ingest(*args, jnp.asarray(cvals), jnp.asarray(times))
        drop = np.asarray([3, 17, 100, 256], np.int32)
        ca.clear_slots(drop)
        pca.clear_slots(drop)
        _assert_counter_parity(ca, pca, self.W)
        cl, cc = map(np.asarray, pca.consume(0))
        assert cc[drop].sum() == 0
        ca.reset_window(0)
        pca.reset_window(0)
        _assert_counter_parity(ca, pca, self.W)
        assert np.asarray(pca.consume(0)[1]).sum() == 0

    def test_fused_rollup_matches_separate_ops(self):
        rng = np.random.default_rng(17)
        pca = packed.PackedCounterArena(self.W, self.C)
        pga = packed.PackedGaugeArena(self.W, self.C)
        cs = packed.counter_init(self.W, self.C)
        gs = packed.gauge_init(self.W, self.C)
        for windows, slots, cvals, gvals, times in _batches(
                rng, 3, 1200, self.W, self.C, nonfinite=True):
            args = (jnp.asarray(windows), jnp.asarray(slots))
            pca.ingest(*args, jnp.asarray(cvals), jnp.asarray(times))
            pga.ingest(*args, jnp.asarray(gvals), jnp.asarray(times))
            idx = packed.packed_flat_index(*args, self.W, self.C)
            cs, gs = packed.rollup_ingest(
                cs, gs, idx, jnp.asarray(cvals), jnp.asarray(gvals),
                jnp.asarray(packed.orderable_f64(gvals)),
                jnp.asarray(times), self.W, self.C)
        for w in range(self.W):
            for (a, _), (b, _b) in (
                (pca.consume(w), packed.counter_consume(
                    cs, jnp.int32(w), self.C)),
                (pga.consume(w), packed.gauge_lanes(
                    *packed.gauge_consume(gs, jnp.int32(w), self.C))),
            ):
                a, b = np.asarray(a), np.asarray(b)
                assert np.all((a == b) | (np.isnan(a) & np.isnan(b)))


class TestOverflowPool:
    """SALSA/Counter-Pools promotion boundaries with narrow widths."""

    def test_promotion_preserves_exact_stats(self):
        W, C = 1, 64
        widths = (4, 6)  # count saturates at 15, |sum| at 32
        st = packed.counter_init(W, C, pool_capacity=16, widths=widths)
        ref = arena.CounterArena(W, C)
        rng = np.random.default_rng(5)
        for _ in range(4):
            slots = rng.integers(0, 8, 50).astype(np.int32)  # hot slots
            vals = rng.integers(-5, 6, 50).astype(np.int64)
            times = np.full(50, T0, np.int64)
            win = np.zeros(50, np.int32)
            idx = packed.packed_flat_index(
                jnp.asarray(win), jnp.asarray(slots), W, C)
            st = packed.counter_ingest(
                st, idx, jnp.asarray(vals), jnp.asarray(times), W, C,
                widths=widths)
            ref.ingest(jnp.asarray(win), jnp.asarray(slots),
                       jnp.asarray(vals), jnp.asarray(times))
        assert int(st.pool_n) > 0  # promotions actually happened
        assert int(st.err) == 0
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C,
                                            widths=widths)
        want, wcnt = ref.consume(0)
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(wcnt))
        a, b = np.asarray(want), np.asarray(lanes)
        assert np.all((a[:, :7] == b[:, :7])
                      | (np.isnan(a[:, :7]) & np.isnan(b[:, :7])))

    def test_wide_value_promotes_immediately(self):
        W, C = 1, 16
        st = packed.counter_init(W, C, pool_capacity=8)
        big = np.int64(1 << 40)
        idx = packed.packed_flat_index(
            jnp.zeros(2, jnp.int32), jnp.asarray([3, 3], np.int32), W, C)
        st = packed.counter_ingest(
            st, idx, jnp.asarray([big, -big]),
            jnp.asarray([T0, T0], np.int64), W, C)
        assert int(st.pool_n) == 1 and int(st.err) == 0
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C)
        assert int(cnt[3]) == 2
        assert lanes[3, 1] == float(-big)  # MIN i64-exact in the pool
        assert lanes[3, 2] == float(big)
        assert lanes[3, 5] == 0.0  # sum

    @pytest.mark.parametrize("sign", [1, -1])
    def test_virgin_slot_all_wide_batch_no_sentinel_minmax(self, sign):
        # review-caught: a never-written slot promoting on a batch
        # entirely OUTSIDE the int16 range used to capture the neutral
        # minmax sentinel (32767 / -32768) as an observed value
        W, C = 1, 16
        st = packed.counter_init(W, C, pool_capacity=8)
        vals = np.asarray([1 << 40, (1 << 40) + 5], np.int64) * sign
        idx = packed.packed_flat_index(
            jnp.zeros(2, jnp.int32), jnp.asarray([7, 7], np.int32), W, C)
        st = packed.counter_ingest(
            st, idx, jnp.asarray(vals),
            jnp.asarray([T0, T0], np.int64), W, C)
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C)
        assert int(cnt[7]) == 2
        assert lanes[7, 1] == float(vals.min())
        assert lanes[7, 2] == float(vals.max())

    def test_promoted_slot_accumulates_across_batches(self):
        W, C = 1, 16
        widths = (4, 6)
        st = packed.counter_init(W, C, pool_capacity=8, widths=widths)
        for i in range(6):
            idx = packed.packed_flat_index(
                jnp.zeros(20, jnp.int32),
                jnp.full(20, 5, jnp.int32), W, C)
            st = packed.counter_ingest(
                st, idx, jnp.full(20, 3, jnp.int64),
                jnp.full(20, T0 + i, jnp.int64), W, C, widths=widths)
        assert int(st.err) == 0
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C,
                                            widths=widths)
        assert int(cnt[5]) == 120
        assert lanes[5, 5] == 360.0
        assert lanes[5, 6] == 1080.0

    def test_pool_exhaustion_sets_err_and_consume_raises(self):
        W, C = 1, 64
        widths = (4, 6)
        pa = packed.PackedCounterArena(W, C, pool_capacity=2,
                                       widths=widths)
        rng = np.random.default_rng(7)
        for _ in range(6):
            slots = rng.integers(0, 32, 200).astype(np.int32)
            pa.ingest(jnp.zeros(200, jnp.int32), jnp.asarray(slots),
                      jnp.asarray(rng.integers(-5, 6, 200), jnp.int64),
                      jnp.full(200, T0, jnp.int64))
        assert int(pa.state.err) != 0
        with pytest.raises(RuntimeError, match="overflow-pool"):
            pa.consume(0)
        # raise-once-then-clear: a transient burst must not wedge every
        # later flush — the next consume proceeds (the ring's
        # drain+reset washes the clipped rows out)
        assert int(pa.state.err) == 0
        pa.consume(0)

    def test_clear_slots_releases_pool_rows_for_reuse(self):
        # review fix: bump allocation leaked rows on slot churn — the
        # free-list allocator must survive promote->clear cycles far
        # beyond pool_capacity without tripping err
        W, C = 1, 32
        widths = (4, 6)
        pa = packed.PackedCounterArena(W, C, pool_capacity=4,
                                       widths=widths)
        for cycle in range(12):  # 12 promotions through a 4-row pool
            slot = cycle % 8
            pa.ingest(jnp.zeros(40, jnp.int32),
                      jnp.full(40, slot, jnp.int32),
                      jnp.ones(40, jnp.int64),
                      jnp.full(40, T0, jnp.int64))
            assert int(pa.state.err) == 0, cycle
            assert int(pa.state.pool_n) == 1
            lanes, cnt = pa.consume(0)
            assert int(cnt[slot]) == 40
            pa.clear_slots(np.asarray([slot], np.int32))
            assert int(pa.state.pool_n) == 0

    def test_pool_full_never_aliases_other_rows(self):
        # review fix: pool-exhausted candidates used to be assigned
        # pool_idx >= P and read row P-1 (another slot's stats) at
        # consume; they must stay unpromoted (clipped base + err flag)
        W, C = 1, 32
        widths = (4, 6)
        st = packed.counter_init(W, C, pool_capacity=1, widths=widths)
        # two hot slots, one pool row: the second promotion has no room
        for _ in range(2):
            idx = packed.packed_flat_index(
                jnp.zeros(40, jnp.int32),
                jnp.asarray([2] * 20 + [9] * 20, np.int32), W, C)
            st = packed.counter_ingest(
                st, idx, jnp.ones(40, jnp.int64),
                jnp.full(40, T0, jnp.int64), W, C, widths=widths)
        assert int(st.err) & 2  # pool full flagged
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C,
                                            widths=widths)
        pooled = int(st.pool_idx[2] >= 0) + int(st.pool_idx[9] >= 0)
        assert pooled == 1
        loser = 9 if int(st.pool_idx[2]) >= 0 else 2
        winner = 2 if loser == 9 else 9
        assert int(cnt[winner]) == 40  # exact in its pool row
        # the loser reports its own (clipped) base lanes, NOT the
        # winner's pool stats
        assert int(cnt[loser]) <= 15  # clipped at the 4-bit lane cap
        assert int(st.pool_idx[loser]) == -1

    def test_layout_arg_validation(self):
        for gone in ("packd", "auto", None):
            with pytest.raises(ValueError, match="unknown arena layout"):
                arena.make_arenas(1, 8, 32, (0.5,), layout=gone)
        c, _g, _t = arena.make_arenas(1, 8, 32, (0.5,), layout="f64")
        assert type(c) is arena.CounterArena

    def test_reset_window_zeroes_promoted_rows(self):
        W, C = 2, 16
        widths = (4, 6)
        st = packed.counter_init(W, C, pool_capacity=8, widths=widths)
        idx = packed.packed_flat_index(
            jnp.zeros(100, jnp.int32), jnp.full(100, 2, jnp.int32), W, C)
        st = packed.counter_ingest(
            st, idx, jnp.ones(100, jnp.int64),
            jnp.full(100, T0, jnp.int64), W, C, widths=widths)
        assert int(st.pool_n) == 1
        st = packed.counter_reset_window(st, jnp.int32(0), W, C,
                                         widths=widths)
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C,
                                            widths=widths)
        assert int(np.asarray(cnt).sum()) == 0
        # the slot stays promoted; new data accumulates in the pool row
        idx2 = packed.packed_flat_index(
            jnp.zeros(3, jnp.int32), jnp.full(3, 2, jnp.int32), W, C)
        st = packed.counter_ingest(
            st, idx2, jnp.full(3, 7, jnp.int64),
            jnp.full(3, T0, jnp.int64), W, C, widths=widths)
        lanes, cnt = packed.counter_consume(st, jnp.int32(0), C,
                                            widths=widths)
        assert int(cnt[2]) == 3 and lanes[2, 5] == 21.0


class TestPackedTimer:
    def test_timer_parity_vs_packed32_oracle(self):
        W, C = 1, 97
        rng = np.random.default_rng(23)
        ta = arena.TimerArena(W, C, 4096, packed32=True)
        pta = packed.PackedTimerArena(W, C, 4096)
        for _ in range(3):
            n = 1000
            win = np.zeros(n, np.int32)
            slots = rng.integers(-2, C + 2, n).astype(np.int32)
            vals = np.round(rng.gamma(2.0, 50.0, n), 3)
            times = T0 + rng.integers(0, SEC, n)
            for a in (ta, pta):
                a.ingest(jnp.asarray(win), jnp.asarray(slots),
                         jnp.asarray(vals), jnp.asarray(times))
        tl, tc = map(np.asarray, ta.consume(0))
        pl, pc = map(np.asarray, pta.consume(0))
        np.testing.assert_array_equal(tc, pc)
        # min/max/quantiles identical to the packed32 drain (same f32
        # words); moments within 1e-6; stdev via own-moment consistency
        for lane in (1, 2, 8, 9, 10):
            np.testing.assert_array_equal(tl[:, lane], pl[:, lane])
        for lane in (3, 4, 5, 6):
            a, b = tl[:, lane], pl[:, lane]
            fin = np.abs(a) > 0
            np.testing.assert_allclose(b[fin], a[fin], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(ta.state.last_at),
                                      np.asarray(pta.state.last_at))

    def test_timer_exact_vs_f64_quantiles_within_f32(self):
        # vs the EXACT f64 drain: quantile lanes within f32 rounding
        W, C = 1, 31
        rng = np.random.default_rng(29)
        ta = arena.TimerArena(W, C, 2048, packed32=False)
        pta = packed.PackedTimerArena(W, C, 2048)
        n = 800
        win = np.zeros(n, np.int32)
        slots = rng.integers(0, C, n).astype(np.int32)
        vals = np.round(rng.gamma(2.0, 50.0, n), 3)
        times = np.full(n, T0, np.int64)
        for a in (ta, pta):
            a.ingest(jnp.asarray(win), jnp.asarray(slots),
                     jnp.asarray(vals), jnp.asarray(times))
        tl, tc = map(np.asarray, ta.consume(0))
        pl, pc = map(np.asarray, pta.consume(0))
        np.testing.assert_array_equal(tc, pc)
        nz = np.abs(tl[:, 8:]) > 0
        rel = np.abs(tl[:, 8:] - pl[:, 8:]) / np.where(nz, np.abs(tl[:, 8:]), 1)
        assert float(rel[nz].max()) < 1e-6

    @pytest.mark.parametrize("filled", [True, False],
                             ids=["filled", "empty"])
    def test_moments_operand(self, filled):
        """moments=False zeroes MEAN, SUM, SUM_SQ and STDEV and leaves
        every other lane equal by bits; moments=True sums them as the
        program did before it had the operand; a window that buffered
        nothing skips them whatever the operand says, and only a
        non-empty drain without them counts as skipped."""
        W, C = 2, 41
        rng = np.random.default_rng(31)
        pta = packed.PackedTimerArena(W, C, 2048)
        n = 900
        slots = rng.integers(0, C, n).astype(np.int32)
        vals = rng.gamma(2.0, 50.0, n)
        pta.ingest(np.full(n, 0 if filled else 1, np.int32), slots, vals,
                   np.full(n, T0, np.int64))
        on_l, on_c = map(np.asarray, pta.consume(0, moments=True))
        assert pta.moments_skipped == 0
        off_l, off_c = map(np.asarray, pta.consume(0, moments=False))
        assert pta.moments_skipped == int(filled)
        np.testing.assert_array_equal(on_c, off_c)
        kept = [0, 1, 2, 4] + list(range(8, on_l.shape[1]))
        np.testing.assert_array_equal(on_l[:, kept].view(np.int64),
                                      off_l[:, kept].view(np.int64))
        assert not off_l[:, [3, 5, 6, 7]].any()

        s, ssq = map(np.asarray, _moments_before(
            pta.state.sample[0], capacity=C))
        cnt = on_c.astype(np.float64)
        assert (on_c > 0).any() == filled
        with np.errstate(invalid="ignore"):
            mean = np.where(cnt == 0, 0.0, s / cnt)
        # the compiler's own arithmetic (it may fuse a multiply-add)
        stdev = np.asarray(jax.jit(arena._stdev)(cnt, ssq, s))
        for lane, want in ((3, mean), (5, s), (6, ssq), (7, stdev)):
            np.testing.assert_array_equal(on_l[:, lane].view(np.int64),
                                          want.view(np.int64))

    def test_timer_grow_and_clear(self):
        pta = packed.PackedTimerArena(1, 8, 4)
        for i in range(4):
            pta.ingest(jnp.zeros(4, jnp.int32),
                       jnp.asarray([1, 1, 2, 3], np.int32),
                       jnp.asarray([1.0 + i, 2.0, 3.0, 4.0]),
                       jnp.full(4, T0, jnp.int64))
        assert pta.sample_capacity >= 8  # grew, no drops
        lanes, cnt = map(np.asarray, pta.consume(0))
        assert cnt[1] == 8 and cnt[2] == 4
        pta.clear_slots(np.asarray([1], np.int32))
        lanes, cnt = map(np.asarray, pta.consume(0))
        assert cnt[1] == 0 and cnt[2] == 4  # slot 1 retargeted


class TestPackedEngine:
    """Engine smoke on the packed layout (the default seam)."""

    def test_engine_flush_packed_vs_f64(self):
        out = {}
        for layout in ("packed", "f64"):
            opts = AggregatorOptions(
                capacity=64, num_windows=2, timer_sample_capacity=256,
                storage_policies=(StoragePolicy.parse("10s:2d"),),
                layout=layout)
            ml = MetricList(opts.storage_policies[0], opts)
            ids = [b"m%d" % (i % 7) for i in range(40)]
            vals = np.round(np.arange(40) * 0.25, 3)
            times = np.full(40, T0, np.int64)
            ml.add_batch(MetricType.GAUGE, ids, vals, times)
            ml.add_batch(MetricType.COUNTER, ids,
                         np.arange(40, dtype=np.float64), times)
            ml.add_batch(MetricType.TIMER, ids, vals + 1.0, times)
            flushed = ml.consume((T0 // (10 * SEC) + 1) * 10 * SEC)
            rows = {}
            for fm in flushed:
                for s, t, v in zip(fm.slots, fm.types, fm.values):
                    rows[(fm.metric_type, int(s), int(t))] = float(v)
            out[layout] = rows
        assert out["packed"].keys() == out["f64"].keys()
        for k, v in out["f64"].items():
            got = out["packed"][k]
            if np.isnan(v):
                assert np.isnan(got)
            else:
                np.testing.assert_allclose(got, v, rtol=1e-6, atol=1e-12)

    def test_default_layout_resolves_packed(self):
        opts = AggregatorOptions(capacity=8, num_windows=2,
                                 timer_sample_capacity=32)
        ml = MetricList(opts.storage_policies[0], opts)
        assert isinstance(ml.counters, packed.PackedCounterArena)
        assert isinstance(ml.gauges, packed.PackedGaugeArena)
        assert isinstance(ml.timers, packed.PackedTimerArena)

    def test_expire_recycles_packed_slots(self):
        opts = AggregatorOptions(capacity=16, num_windows=2,
                                 timer_sample_capacity=64, layout="packed")
        ml = MetricList(opts.storage_policies[0], opts)
        ml.add_batch(MetricType.COUNTER, [b"a", b"b"],
                     np.asarray([1.0, 2.0]),
                     np.asarray([T0, T0], np.int64))
        assert ml.expire(T0 + 3600 * SEC, ttl_nanos=60 * SEC) > 0
        assert len(ml.maps[MetricType.COUNTER]) == 0


class TestStdevClamp:
    """Satellite: catastrophic cancellation must clamp at 0, not abs()."""

    def test_large_mean_small_variance(self):
        # mean ~1e9, stdev ~1: count*sum_sq - sum^2 loses all mantissa
        # bits and can round negative; abs() fabricated a huge stdev.
        rng = np.random.default_rng(37)
        n = 1000
        vals = 1e9 + rng.normal(0.0, 1.0, n)
        count = jnp.float64(n)
        s = jnp.float64(vals.sum())
        ssq = jnp.float64((vals * vals).sum())
        out = float(arena._stdev(count, ssq, s))
        # reference semantics preserved: close to the true sample stdev
        # (loose: the moments formulation genuinely loses precision
        # here) and NEVER the abs()-fabricated garbage
        true = float(np.std(vals, ddof=1))
        assert 0.0 <= out < 100.0, out
        # the clamp engages exactly when cancellation goes negative
        neg = float(arena._stdev(jnp.float64(2.0),
                                 jnp.float64(1e18 * (1 - 1e-16)),
                                 jnp.float64(2e9 * (1 + 1e-13))))
        assert neg == 0.0

    def test_gauge_consume_stdev_no_nan_large_mean(self):
        ga = arena.GaugeArena(1, 4)
        vals = 1e9 + np.asarray([0.25, -0.25, 0.5, -0.5])
        ga.ingest(jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32),
                  jnp.asarray(vals), jnp.full(4, T0, jnp.int64))
        lanes = np.asarray(ga.consume(0)[0])
        assert np.isfinite(lanes[0, 7]) and lanes[0, 7] >= 0.0


# ---------------------------------------------------------------------------
# The batch domain: an ingest costs its batch, not the arena
# ---------------------------------------------------------------------------

_SW, _SN = 8, 4096  # W = 8, so that W*C exceeds N at C = 2^10 too


def _sub_jaxprs(params):
    for v in params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _leaf_eqns(jaxpr):
    """Every equation that computes something: the bodies of pjit /
    cond / custom calls walked, the containers themselves not counted
    (a cond hands the untouched pool_idx lane through)."""
    for eqn in jaxpr.eqns:
        subs = list(_sub_jaxprs(eqn.params))
        if subs:
            for sub in subs:
                yield from _leaf_eqns(sub)
        else:
            yield eqn


@functools.lru_cache(maxsize=None)
def _ingest_census(kind, C):
    """(leaf equation count, [(primitive, shape) of every output of
    W*C or more elements]) of one ingest's jaxpr; nothing is allocated."""
    cs = jax.eval_shape(lambda: packed.counter_init(_SW, C))
    gs = jax.eval_shape(lambda: packed.gauge_init(_SW, C))
    i64 = jax.ShapeDtypeStruct((_SN,), jnp.int64)
    f64 = jax.ShapeDtypeStruct((_SN,), jnp.float64)
    if kind == "counter":
        jp = jax.make_jaxpr(lambda s, i, v, t: packed.counter_ingest(
            s, i, v, t, _SW, C))(cs, i64, i64, i64)
    elif kind == "gauge":
        jp = jax.make_jaxpr(lambda s, i, v, k, t: packed.gauge_ingest(
            s, i, v, k, t, _SW, C))(gs, i64, f64, i64, i64)
    else:
        jp = jax.make_jaxpr(
            lambda c, g, i, cv, gv, k, t: packed.rollup_ingest(
                c, g, i, cv, gv, k, t, _SW, C))(
                    cs, gs, i64, i64, f64, i64, i64)
    eqns = list(_leaf_eqns(jp.jaxpr))
    big = [(e.primitive.name, o.aval.shape) for e in eqns
           for o in e.outvars if int(np.prod(o.aval.shape)) >= _SW * C]
    return len(eqns), big


class TestBatchDomain:
    """The merge never leaves the sorted batch: what keeps an ingest's
    cost from growing back with the arena."""

    # state lanes of W*C elements: gauge sum / sum_sq / count / min /
    # max / last_key / last_time; counter base / sq / minmax and, under
    # the pool's cond, pool_idx
    STATE_SCATTERS = {"gauge": 7, "counter": 4, "rollup": 11}

    @pytest.mark.parametrize("C", [1 << 10, 1 << 16])
    @pytest.mark.parametrize("kind", ["counter", "gauge", "rollup"])
    def test_no_arena_sized_compute(self, kind, C):
        n_eqns, big = _ingest_census(kind, C)
        # nothing of W*C or more elements is computed but the scatters
        # into the state's own lanes (last_at's (C,) max is smaller)
        assert all(p.startswith("scatter") and s == (_SW * C,)
                   for p, s in big), big
        assert len(big) == self.STATE_SCATTERS[kind], big
        other = (1 << 10) if C == (1 << 16) else (1 << 16)
        assert n_eqns == _ingest_census(kind, other)[0]


# (slots, rows) of the seeded batches: rows out of range and negative,
# more rows than slots and fewer
_SEEDED_SHAPES = {"n100_c257": (100, 257), "n3000_c5000": (3000, 5000),
                  "n1024_c1024": (1024, 1024), "n17_c10000": (17, 10_000)}


def _edge_batches(name):
    """(W, C, counter-arena kwargs, batches, expected err bits, pool
    rows) of one batch-domain edge case; a batch is (windows, slots,
    counter values, gauge values, times)."""
    rng = np.random.default_rng(len(name))
    i32 = lambda a: np.asarray(a, np.int32)

    def batch(windows, slots, cvals=None, gvals=None, t0=0):
        n = len(slots)
        if cvals is None:
            cvals = rng.integers(-2000, 2000, n)
        if gvals is None:
            gvals = np.round(rng.uniform(-50, 50, n), 3)
        return (i32(windows), i32(slots), np.asarray(cvals, np.int64),
                np.asarray(gvals, np.float64),
                T0 + t0 + rng.integers(0, SEC, n))

    narrow = dict(pool_capacity=8, widths=(4, 6))
    spread = batch(rng.integers(0, 2, 48), rng.integers(0, 16, 48))
    if name in _SEEDED_SHAPES:
        C, n = _SEEDED_SHAPES[name]
        g = np.round(rng.normal(0, 10, n), 6)
        g[::97] = np.nan  # a NaN is counted by its own slot only
        return 2, C, {}, [batch(rng.integers(-1, 3, n),
                                rng.integers(-3, C + 3, n), gvals=g)], 0, 0
    if name == "empty_batch":
        return 2, 16, {}, [spread, batch([], [], t0=5 * SEC)], 0, 0
    if name == "untouched_slots_keep_identity":
        return 2, 64, {}, [batch([0, 0, 0], [3, 3, 10], cvals=[-7, 9, 2],
                                 gvals=[-7.5, 9.25, 2.0])], 0, 0
    if name == "oob_rows_mixed":
        return 2, 300, {}, [batch(
            rng.integers(-1, 3, 4000), rng.integers(-3, 305, 4000),
            gvals=np.round(rng.normal(0, 100, 4000), 3))], 0, 0
    if name == "two_batches_equal_one":
        # eighths: a gauge's sums are exact in any order of addition
        halves = [batch(rng.integers(0, 2, 3500), rng.integers(0, 256, 3500),
                        gvals=rng.integers(-400, 400, 3500) / 8, t0=t0)
                  for t0 in (0, SEC // 2)]
        return 2, 256, {}, halves, 0, 0
    if name.startswith("one_slot_n"):
        n = int(name[len("one_slot_n"):])
        g = np.round(rng.uniform(-50, 50, n), 3)
        g[::7] = np.nan
        return 2, 16, {}, [batch(np.ones(n), np.full(n, 5), gvals=g)], 0, 0
    if name == "all_dropped":
        return 2, 16, {}, [spread, batch(
            rng.choice([-1, 2], 32), rng.choice([-1, 16, 17], 32),
            t0=5 * SEC)], 0, 0
    if name == "ghost_rows_only":
        return 2, 16, {}, [spread, batch(
            rng.choice([-1, 2], 32), rng.integers(0, 16, 32),
            t0=5 * SEC)], 0, 0
    if name == "slot_under_two_windows":
        return 2, 16, {}, [batch([0, 1, 0, 1, 1, 0], [3, 3, 3, 3, 9, 9])], \
            0, 0
    if name == "promotion_and_active_slot":
        hot = batch(np.zeros(20), np.full(20, 2), cvals=np.ones(20))
        both = batch(np.zeros(45), [2] * 20 + [9] * 20 + [4] * 5,
                     cvals=np.ones(45), t0=SEC)
        return 1, 16, narrow, [hot, both], 0, 2
    if name == "all_wide_virgin_slot":
        wide = np.asarray([1 << 40, -(1 << 41), (1 << 40) + 5, 1 << 33])
        return 1, 16, dict(pool_capacity=8), [
            batch(np.zeros(4), np.full(4, 7), cvals=wide)], 0, 1
    if name == "more_than_k_promotions":
        slots = np.repeat(np.arange(5), 20)
        return 1, 16, dict(promote_k=2, **narrow), [
            batch(np.zeros(100), slots, cvals=np.ones(100))], \
            packed._ERR_PROMOTE_K, 2
    if name == "pool_full":
        slots = np.repeat(np.arange(3), 20)
        return 1, 16, dict(pool_capacity=1, widths=(4, 6)), [
            batch(np.zeros(60), slots, cvals=np.ones(60))], \
            packed._ERR_POOL_FULL, 1
    raise KeyError(name)


class TestBatchDomainEdges:
    @pytest.mark.parametrize("name", [
        "one_slot_n1", "one_slot_n64", "all_dropped", "ghost_rows_only",
        "slot_under_two_windows", "promotion_and_active_slot",
        "all_wide_virgin_slot", "more_than_k_promotions", "pool_full",
        *_SEEDED_SHAPES, "empty_batch", "one_slot_n1024",
        "untouched_slots_keep_identity", "oob_rows_mixed",
        "two_batches_equal_one"])
    def test_edge_case_vs_f64_oracle(self, name):
        W, C, ckw, batches, err, pool_n = _edge_batches(name)
        ca, ga = arena.CounterArena(W, C), arena.GaugeArena(W, C)
        pca = packed.PackedCounterArena(W, C, **ckw)
        pga = packed.PackedGaugeArena(W, C)
        for windows, slots, cvals, gvals, times in batches:
            before = jax.tree.map(np.asarray, (pca.state, pga.state))
            for a, vals in ((ca, cvals), (pca, cvals),
                            (ga, gvals), (pga, gvals)):
                a.ingest(jnp.asarray(windows), jnp.asarray(slots),
                         vals if a is pga else jnp.asarray(vals),
                         jnp.asarray(times))
        assert int(pca.state.err) == err
        assert int(pca.state.pool_n) == pool_n
        for f64_arena, packed_arena in ((ca, pca), (ga, pga)):
            np.testing.assert_array_equal(
                np.asarray(f64_arena.state.last_at),
                np.asarray(packed_arena.state.last_at))
        _assert_gauge_parity(ga, pga, W)
        if not err:  # an err bit marks the clipped rows as unreliable
            _assert_counter_parity(ca, pca, W)
        after = jax.tree.map(np.asarray, (pca.state, pga.state))
        if name in ("all_dropped", "ghost_rows_only", "empty_batch"):
            # the last batch wrote no statistic: every state lane but
            # the expiry column keeps its bits
            for b, a in zip(before, after):
                for lane in b._fields:
                    moved = not np.array_equal(getattr(b, lane),
                                               getattr(a, lane))
                    assert moved == (lane == "last_at"
                                     and name == "ghost_rows_only"), lane
        if name == "untouched_slots_keep_identity":
            # every flat slot no row touched holds what init wrote, the
            # min / max identities included, in both layouts
            quiet = np.ones(W * C, bool)
            quiet[[3, 10]] = False
            fresh = jax.tree.map(np.asarray, (
                packed.counter_init(W, C), packed.gauge_init(W, C),
                arena.counter_init(W, C), arena.gauge_init(W, C)))
            for f, a in zip(fresh, after + jax.tree.map(
                    np.asarray, (ca.state, ga.state))):
                for lane in f._fields:
                    if getattr(f, lane).shape == (W * C,):
                        np.testing.assert_array_equal(
                            getattr(a, lane)[quiet],
                            getattr(f, lane)[quiet], err_msg=lane)
            assert (np.asarray(ca.state.min)[quiet] == arena.I64_MAX).all()
            assert (np.asarray(ga.state.max)[quiet] == -np.inf).all()
        if name == "two_batches_equal_one":
            # the two halves as one batch leave every lane the same bits
            whole = [np.concatenate(col) for col in zip(*batches)]
            wca = packed.PackedCounterArena(W, C)
            wga = packed.PackedGaugeArena(W, C)
            for a, vals in ((wca, jnp.asarray(whole[2])), (wga, whole[3])):
                a.ingest(jnp.asarray(whole[0]), jnp.asarray(whole[1]), vals,
                         jnp.asarray(whole[4]))
            for split, one in zip(after, (wca.state, wga.state)):
                for lane in split._fields:
                    np.testing.assert_array_equal(
                        getattr(split, lane), np.asarray(getattr(one, lane)),
                        err_msg=lane)
