"""Sharded aggregator step over the 8-device virtual CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from m3_tpu.aggregator import arena as _arena
from m3_tpu.parallel import make_mesh, sharded_init, sharded_ingest_consume
from m3_tpu.aggregator.packed import orderable_f64
from m3_tpu.parallel.sharded_agg import (
    ShardedBatch, gauge_lanes, rollup_lanes,
)


def _mk_batch(topo, W, C, N, seed=0):
    D = topo.num_shards
    rng = np.random.default_rng(seed)
    sh = lambda a, dt: jax.device_put(jnp.asarray(a, dt), topo.sharded(None))
    gvals = rng.normal(100.0, 10.0, (D, N))
    gvals[:, ::7] = 1e300   # beyond an accelerator's f64 range
    gvals[:, 3::11] = -5e-324
    return ShardedBatch(
        windows=sh(rng.integers(0, W, (D, N)), jnp.int32),
        slots=sh(rng.integers(0, C, (D, N)), jnp.int32),
        counter_values=sh(rng.integers(0, 1000, (D, N)), jnp.int64),
        gauge_values=sh(gvals, jnp.float64),
        gauge_keys=sh(orderable_f64(gvals), jnp.int64),
        timer_values=sh(np.abs(rng.normal(0.1, 0.02, (D, N))), jnp.float64),
        times=sh(np.tile(np.arange(1, N + 1), (D, 1)), jnp.int64),
    )


@pytest.mark.parametrize("shards,replicas", [(8, 1), (4, 2)])
def test_sharded_step_matches_single_device(shards, replicas):
    topo = make_mesh(num_shards=shards, num_replicas=replicas)
    W, C, N = 2, 32, 64
    state = sharded_init(topo, W, C, 4 * N)
    batch = _mk_batch(topo, W, C, N)
    new_state, lanes = sharded_ingest_consume(
        topo, state, batch, jnp.int32(0), W, C, (0.5, 0.95, 0.99)
    )

    # Oracle: run each shard through the single-device arenas of the
    # same (default) layout.
    windows = np.asarray(batch.windows)
    slots = np.asarray(batch.slots)
    cvals = np.asarray(batch.counter_values)
    times = np.asarray(batch.times)
    gvals = np.asarray(batch.gauge_values)
    c_lanes = np.asarray(lanes["counter"][0])
    gl = gauge_lanes(lanes)
    assert c_lanes.shape == gl.shape == (shards, C, 8)
    g_min = np.full(C, np.inf)
    g_max = np.full(C, -np.inf)
    for d in range(shards):
        a, g, _t = _arena.make_arenas(W, C, 4 * N, (0.5, 0.95, 0.99))
        a.ingest(
            jnp.asarray(windows[d]),
            jnp.asarray(slots[d]),
            jnp.asarray(cvals[d]),
            jnp.asarray(times[d]),
        )
        want, _ = a.consume(0)
        np.testing.assert_allclose(c_lanes[d], np.asarray(want), rtol=0, atol=0)
        # gauge LAST / MIN / MAX are selections: bit-equal to the
        # single-device arena, extreme values included
        g.ingest(windows[d], slots[d], gvals[d], times[d])
        want = np.asarray(g.consume(0)[0])
        assert (gl[d, :, :3].view(np.int64)
                == want[:, :3].view(np.int64)).all()
        np.testing.assert_allclose(gl[d, :, 3:], want[:, 3:], rtol=1e-12)
        g_min = np.fmin(g_min, want[:, 1])
        g_max = np.fmax(g_max, want[:, 2])

    # Packed degraded-state flags must be clean on a healthy run (the
    # engine path raises; the sharded path surfaces the same bits here).
    assert int(np.asarray(lanes["err"]).sum()) == 0

    # Global rollup = sum of per-shard sums for window 0.
    rollup = rollup_lanes(lanes)
    np.testing.assert_array_equal(
        rollup[:, 2], np.where(np.isinf(g_min), np.nan, g_min))
    np.testing.assert_array_equal(
        rollup[:, 3], np.where(np.isinf(g_max), np.nan, g_max))
    gsum_want = 0.0
    for d in range(shards):
        gsum_want += np.nan_to_num(gl[d, :, 5]) + c_lanes[d, :, 5]
    np.testing.assert_allclose(rollup[:, 0], gsum_want, rtol=1e-12)

    # The drained window's ring row was reset; only window-1 samples
    # remain.  Counts live in a plain column on the f64 layout and in
    # the packed base word's count lane on the packed layout.
    if "count" in new_state.counters._fields:
        remaining = np.asarray(new_state.counters.count).sum()
    else:
        from m3_tpu.aggregator import packed as _packed

        cnt, _ = _packed._unpack_base(
            jnp.asarray(np.asarray(new_state.counters.base)),
            _packed.DEFAULT_WIDTHS)
        remaining = int(np.asarray(cnt).sum())
    assert remaining == (windows == 1).sum()


def test_graft_entry_single_chip():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    (counters, gauges, timers), (c_lanes, g_lanes, t_lanes, cnt) = out
    assert np.asarray(c_lanes).shape[1] == 8


@pytest.mark.slow  # round-12 tier-1 budget: ~17s duplicate of the driver's
# separate `__graft_entry__.dryrun_multichip` run (TESTING.md tier 6);
# test_graft_entry_single_chip keeps the entry-point contract in tier-1.
def test_graft_entry_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_sharded_packed_err_surfaces_timer_overflow():
    """Review fix: a fixed-capacity sharded timer buffer that overflows
    loses MOMENTS (not just quantiles) on the packed layout — the step
    must flag it per shard instead of silently publishing wrong lanes."""
    topo = make_mesh(num_shards=1, num_replicas=1,
                     devices=jax.devices()[:1])
    W, C, N = 2, 16, 64
    state = sharded_init(topo, W, C, sample_capacity=8, layout="packed")
    batch = _mk_batch(topo, W, C, N, seed=3)
    _state, lanes = sharded_ingest_consume(
        topo, state, batch, jnp.int32(0), W, C, (0.5,), layout="packed")
    from m3_tpu.aggregator.packed import _ERR_TIMER_OVERFLOW

    err = np.asarray(lanes["err"])
    assert (err & _ERR_TIMER_OVERFLOW).any()


def test_sharded_layout_arg_validated():
    topo = make_mesh(num_shards=1, num_replicas=1,
                     devices=jax.devices()[:1])
    import pytest

    for gone in ("packd", "auto"):
        with pytest.raises(ValueError, match="unknown arena layout"):
            sharded_init(topo, 2, 8, 32, layout=gone)
