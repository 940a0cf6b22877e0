"""Pallas segmented-ingest kernel vs the XLA scatter oracle.

Interpret mode (CPU): validates SEMANTICS — the (slot, value) binned
sum/count reduction, drop-sentinel handling, padding.  Mosaic lowering
and the scatter-vs-binned crossover need real-TPU measurement (see the
module docstring's decision record)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from m3_tpu.parallel.pallas_ingest import (  # noqa: E402
    pallas_segment_ingest, xla_segment_ingest,
)


@pytest.mark.parametrize("C,N,seed", [(100, 257, 0), (3000, 5000, 1),
                                      (1024, 1024, 2), (17, 10_000, 3)])
def test_matches_xla_scatter(C, N, seed):
    rng = np.random.default_rng(seed)
    slots = rng.integers(-3, C + 3, N).astype(np.int32)  # incl. OOR drops
    vals = np.round(rng.normal(0, 10, N), 6)
    vals[::97] = np.nan  # NaN must poison ONLY its own slot (select,
    # not multiply-by-mask — a mask*value kernel would NaN whole tiles)
    ps, pc = pallas_segment_ingest(jnp.asarray(slots), jnp.asarray(vals),
                                   C, interpret=True)
    xs, xc = xla_segment_ingest(jnp.asarray(slots), jnp.asarray(vals), C)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(xs), atol=1e-9)
    np.testing.assert_array_equal(np.asarray(pc), np.asarray(xc))


def test_oversized_batch_rejected():
    from m3_tpu.parallel.pallas_ingest import MAX_BATCH

    with pytest.raises(ValueError, match="MAX_BATCH"):
        pallas_segment_ingest(jnp.zeros(MAX_BATCH + 1, jnp.int32),
                              jnp.zeros(MAX_BATCH + 1), 64, interpret=True)


def test_high_collision_all_one_slot():
    """The shape where binned reduction beats serialized scatter."""
    N, C = 4096, 128
    slots = np.zeros(N, np.int32)
    vals = np.ones(N)
    ps, pc = pallas_segment_ingest(jnp.asarray(slots), jnp.asarray(vals),
                                   C, interpret=True)
    assert float(ps[0]) == N and float(pc[0]) == N
    assert float(ps[1:].sum()) == 0.0


def test_empty_batch():
    ps, pc = pallas_segment_ingest(jnp.zeros(0, jnp.int32),
                                   jnp.zeros(0), 64, interpret=True)
    assert float(ps.sum()) == 0.0 and float(pc.sum()) == 0.0


def test_chunked_matches_single(monkeypatch):
    """Crosses REAL chunk boundaries: MAX_BATCH is shrunk so the 7000-
    point batch spans 4 chunks (a cross-chunk accumulation bug would
    otherwise only surface on >262144-point production ingests)."""
    from m3_tpu.parallel import pallas_ingest as pi

    monkeypatch.setattr(pi, "MAX_BATCH", 2048)
    rng = np.random.default_rng(9)
    N, C = 7000, 256
    slots = rng.integers(0, C, N).astype(np.int32)
    vals = rng.normal(0, 5, N)
    cs, cc = pi.segment_ingest_chunked(jnp.asarray(slots),
                                       jnp.asarray(vals), C, interpret=True)
    xs, xc = xla_segment_ingest(jnp.asarray(slots), jnp.asarray(vals), C)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(xs), atol=1e-9)
    np.testing.assert_array_equal(np.asarray(cc), np.asarray(xc))
    ms, mc, msq = pi.segment_moments_chunked(
        jnp.asarray(slots), jnp.asarray(vals), C, interpret=True)
    xsq, _ = xla_segment_ingest(jnp.asarray(slots),
                                jnp.asarray(vals) ** 2, C)
    np.testing.assert_allclose(np.asarray(ms), np.asarray(xs), atol=1e-9)
    np.testing.assert_array_equal(np.asarray(mc), np.asarray(xc))
    np.testing.assert_allclose(np.asarray(msq), np.asarray(xsq), atol=1e-9)


class TestArenaIngestImplFlip:
    """The production hook: M3_ARENA_INGEST / arena.set_ingest_impl
    flips the arenas' sum/sum²/count lanes to the Pallas kernel;
    results must be identical to the scatter default (interpret mode
    pins semantics on CPU; the TPU bench child measures both)."""

    def _drive(self):
        from m3_tpu.aggregator import arena

        W, C, N = 2, 512, 4096
        rng = np.random.default_rng(4)
        windows = jnp.asarray(rng.integers(0, W, N).astype(np.int32))
        slots = jnp.asarray(rng.integers(0, C, N).astype(np.int32))
        idx = arena.flat_window_index(windows, slots, W, C)
        times = jnp.asarray(1_000 + np.arange(N, dtype=np.int64))

        cvals = jnp.asarray(rng.integers(-50, 1000, N, np.int64))
        cs = arena.counter_ingest(arena.counter_init(W, C), idx, slots,
                                  cvals, times)
        gvals = np.round(rng.normal(0, 10, N), 4)
        gvals[:7] = np.nan  # NaN: counted, not summed
        gs = arena.gauge_ingest(arena.gauge_init(W, C), idx, slots,
                                jnp.asarray(gvals), times)
        tvals = jnp.asarray(np.round(rng.gamma(2.0, 5.0, N), 4))
        ts = arena.timer_ingest(arena.timer_init(W, C, 1 << 13), windows,
                                slots, tvals, times, C)
        return cs, gs, ts

    def test_pallas_impl_matches_scatter(self):
        from m3_tpu.aggregator import arena

        assert arena.ingest_impl() == "scatter"
        base = self._drive()
        arena.set_ingest_impl("pallas")
        try:
            flip = self._drive()
        finally:
            arena.set_ingest_impl("scatter")
        for b, f in zip(base, flip):
            for name in b._fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(b, name)),
                    np.asarray(getattr(f, name)),
                    atol=1e-9, err_msg=f"{type(b).__name__}.{name}")

    def test_unknown_impl_rejected(self):
        from m3_tpu.aggregator import arena

        with pytest.raises(ValueError, match="unknown ingest impl"):
            arena.set_ingest_impl("magic")


class TestPallasMinMax:
    """Round-8 kernel: per-slot (min, max) with the binned grid — the
    TPU-side alternative to the packed arena's segmented min/max scan.
    Interpret mode on CPU: semantics only."""

    def _oracle(self, slots, vals, C, lo, hi):
        mn = np.full(C, hi)
        mx = np.full(C, lo)
        ok = (slots >= 0) & (slots < C)
        np.minimum.at(mn, slots[ok], vals[ok])
        np.maximum.at(mx, slots[ok], vals[ok])
        return mn, mx

    def test_f64_matches_oracle_with_oob(self):
        from m3_tpu.parallel.pallas_ingest import pallas_segment_minmax

        rng = np.random.default_rng(21)
        C, N = 300, 4000
        slots = rng.integers(-3, C + 5, N).astype(np.int32)
        vals = np.round(rng.normal(0, 100, N), 3)
        mn, mx = pallas_segment_minmax(
            jnp.asarray(slots), jnp.asarray(vals), C, interpret=True)
        wmn, wmx = self._oracle(slots, vals, C, -np.inf, np.inf)
        np.testing.assert_array_equal(np.asarray(mn), wmn)
        np.testing.assert_array_equal(np.asarray(mx), wmx)

    def test_i64_identities_for_empty_slots(self):
        from m3_tpu.parallel.pallas_ingest import pallas_segment_minmax

        C = 64
        slots = jnp.asarray([3, 3, 10], jnp.int32)
        vals = jnp.asarray([-7, 9, 2], jnp.int64)
        mn, mx = pallas_segment_minmax(slots, vals, C, interpret=True)
        info = np.iinfo(np.int64)
        assert int(mn[3]) == -7 and int(mx[3]) == 9
        assert int(mn[10]) == 2 and int(mx[10]) == 2
        assert int(mn[0]) == info.max and int(mx[0]) == info.min

    def test_chunked_matches_single_call(self):
        from m3_tpu.parallel import pallas_ingest as pi

        rng = np.random.default_rng(23)
        C, N = 128, 5000
        slots = jnp.asarray(rng.integers(0, C, N).astype(np.int32))
        vals = jnp.asarray(np.round(rng.uniform(-5, 5, N), 3))
        a = pi.pallas_segment_minmax(slots, vals, C, interpret=True)
        b = pi.segment_minmax_chunked(slots, vals, C, interpret=True)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
