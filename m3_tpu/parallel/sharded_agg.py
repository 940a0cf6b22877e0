"""Multi-device aggregator: the full ingest→rollup step over a mesh.

This is the distribution layer the reference builds from sharded
placements + TChannel fan-out (`src/aggregator/aggregator/aggregator.go:505`
shardFor, `src/aggregator/sharding`) and multi-stage forwarded rollups
(`src/aggregator/aggregator/forwarded_writer.go`), re-designed as one SPMD
program:

* every logical shard's arenas live as a leading axis of the state arrays,
  laid out over the mesh's ``shard`` axis;
* ingest batches arrive pre-routed per shard (host shard router =
  murmur3 % num_shards, as `sharding/shardset.go:148`) and each device
  scatters only its own block — zero cross-device traffic on the hot path,
  exactly the property the reference's shard ownership gives it;
* window drain computes per-shard lanes locally, then the cross-shard
  rollup stage (the reference forwards partial aggregates between
  aggregator instances over TCP) is a single ``psum`` over the shard axis
  riding ICI.

State is replicated over the ``replica`` axis (the RF axis of an M3
placement); because the program is deterministic SPMD, replicas stay
bit-identical without the reference's leader/follower flush protocol
(`aggregator/aggregator/follower_flush_mgr.go`) — the election only picks
who *emits*.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from m3_tpu.aggregator import arena as _arena
from m3_tpu.aggregator import packed as _packed
from m3_tpu.parallel.mesh import (
    REPLICA_AXIS, SHARD_AXIS, MeshTopology, shard_map_compat,
)


_raw = _arena.raw


class ShardedAggregatorState(NamedTuple):
    # f64 layout: arena.CounterState/GaugeState/TimerState; packed
    # layout: packed.Packed*State.  All arrays carry a leading
    # (num_shards,) axis over the mesh's shard axis.
    counters: NamedTuple
    gauges: NamedTuple
    timers: NamedTuple


def sharded_init(
    topo: MeshTopology,
    num_windows: int,
    capacity: int,
    sample_capacity: int,
    layout: str = "packed",
) -> ShardedAggregatorState:
    """Per-shard arenas, placed: shard axis over the mesh's shard axis,
    replicated over the replica axis.  ``layout`` is "packed" or "f64"
    (arena.make_arenas' two); anything else raises."""
    D = topo.num_shards
    _arena.check_layout(layout)

    def rep(state):
        return jax.tree.map(
            lambda a: jax.device_put(
                jnp.broadcast_to(a[None], (D,) + a.shape), topo.sharded()
            ),
            state,
        )

    if layout == "packed":
        return ShardedAggregatorState(
            counters=rep(_packed.counter_init(num_windows, capacity)),
            gauges=rep(_packed.gauge_init(num_windows, capacity)),
            timers=rep(_packed.timer_init(num_windows, capacity,
                                          sample_capacity)),
        )
    return ShardedAggregatorState(
        counters=rep(_arena.counter_init(num_windows, capacity)),
        gauges=rep(_arena.gauge_init(num_windows, capacity)),
        timers=rep(_arena.timer_init(num_windows, capacity, sample_capacity)),
    )


class ShardedBatch(NamedTuple):
    """One pre-routed ingest batch: leading axis = logical shard."""

    windows: jnp.ndarray  # i32 (D, N) ring index per sample; OOB drops
    slots: jnp.ndarray  # i32 (D, N)
    counter_values: jnp.ndarray  # i64 (D, N)
    gauge_values: jnp.ndarray  # f64 (D, N)
    # i64 (D, N) packed.orderable_f64(gauge_values), made on the host
    # from the written bits (the packed layout selects min/max/last on
    # these; the f64 layout ignores them)
    gauge_keys: jnp.ndarray
    timer_values: jnp.ndarray  # f64 (D, N)
    times: jnp.ndarray  # i64 (D, N)


@functools.partial(
    jax.jit,
    static_argnames=("topo", "num_windows", "capacity", "quantiles",
                     "timer_packed32", "layout"),
    donate_argnums=(1,),
)
def sharded_ingest_consume(
    topo: MeshTopology,
    state: ShardedAggregatorState,
    batch: ShardedBatch,
    window: jnp.ndarray,  # i32 scalar: ring index to drain after ingest
    num_windows: int,
    capacity: int,
    quantiles: tuple = (0.5, 0.95, 0.99),
    timer_packed32: bool = False,
    layout: str = "packed",
):
    """The framework's "training step": ingest a routed batch into every
    shard's arenas, drain one window (then reset its ring row, as the
    single-device engine pairs consume with reset_window), and produce
    both the per-shard lane matrices and the cross-shard global rollup.

    Returns (new_state, lanes) where lanes is a dict:
      counter/gauge/timer -> ((D, C, L) lanes, (D, C) counts), sharded
      rollup              -> (C, 4) global [sum, count, min, max] across
                            shards (the forwarded-pipeline stage, via
                            psum / gathered min, max); min/max are NaN for slots
                            with no gauge samples on any shard
    On the packed layout the gauge entry is packed.gauge_consume's
    ((D, C, 5) computed, (D, C, 4) exact) pair and the rollup's min/max
    columns are NaN placeholders for the i64 keys in ``rollup_sel``
    (C, 2): read both through gauge_lanes() / rollup_lanes().
    """
    mesh = topo.mesh

    def local_step(state, batch, window):
        # Each device sees a (1, ...) block: its own shard.
        sq = lambda tree: jax.tree.map(lambda a: a[0], tree)
        st = ShardedAggregatorState(*map(sq, state))
        b = ShardedBatch(*(a[0] for a in batch))

        if layout == "packed":
            # One fused sort serves the counter+gauge arenas; the timer
            # appends packed words (see aggregator/packed.py).
            pidx = _packed.packed_flat_index(
                b.windows, b.slots, num_windows, capacity)
            counters, gauges = _raw(_packed.rollup_ingest)(
                st.counters, st.gauges, pidx, b.counter_values,
                b.gauge_values, b.gauge_keys, b.times, num_windows,
                capacity)
            timers = _raw(_packed.timer_ingest)(
                st.timers, b.windows, b.slots, b.timer_values, b.times,
                capacity)
            # The packed states can only degrade LOUDLY: the engine
            # path raises from the host wrapper, so the sharded step
            # must surface the same conditions — the counter overflow-
            # pool err bits, plus timer sample-buffer overflow (the
            # fixed-capacity sharded buffer silently loses MOMENTS as
            # well as quantiles past sample_capacity, unlike the f64
            # arenas whose scatter moments survive buffer overflow).
            scap = st.timers.sample.shape[1]
            shard_err = (counters.err
                         | jnp.where((timers.sample_n > scap).any(),
                                     jnp.int32(_packed._ERR_TIMER_OVERFLOW),
                                     jnp.int32(0)))
            c_lanes, c_cnt = _raw(_packed.counter_consume)(
                counters, window, capacity)
            g_lanes, g_cnt = _raw(_packed.gauge_consume)(
                gauges, window, capacity)
            # (computed, exact) — see packed.gauge_consume
            g_sum, g_n = g_lanes[:, 2], g_lanes[:, 1]
            k_min, k_max = g_cnt[:, 2], g_cnt[:, 3]
            t_lanes, t_cnt = _raw(_packed.timer_consume)(
                timers, window, True, capacity, quantiles)
            counters = _raw(_packed.counter_reset_window)(
                counters, window, num_windows, capacity)
            gauges = _raw(_packed.gauge_reset_window)(
                gauges, window, capacity)
            timers = _raw(_packed.timer_reset_window)(
                timers, window, capacity)
        else:
            idx = _arena.flat_window_index(
                b.windows, b.slots, num_windows, capacity)

            counters = _raw(_arena.counter_ingest)(
                st.counters, idx, b.slots, b.counter_values, b.times
            )
            gauges = _raw(_arena.gauge_ingest)(
                st.gauges, idx, b.slots, b.gauge_values, b.times
            )
            timers = _raw(_arena.timer_ingest)(
                st.timers, b.windows, b.slots, b.timer_values, b.times,
                capacity
            )

            c_lanes, c_cnt = _raw(_arena.counter_consume)(
                counters, window, capacity)
            g_lanes, g_cnt = _raw(_arena.gauge_consume)(
                gauges, window, capacity)
            g_sum, g_n = g_lanes[:, 5], g_lanes[:, 4]
            t_lanes, t_cnt = _raw(_arena.timer_consume)(
                timers, window, capacity, quantiles, timer_packed32
            )

            # The drained window's ring row resets for reuse (engine.py
            # consume() pairs every drain with reset_window).
            counters = _raw(_arena.counter_reset_window)(
                counters, window, capacity)
            gauges = _raw(_arena.gauge_reset_window)(
                gauges, window, capacity)
            timers = _raw(_arena.timer_reset_window)(
                timers, window, capacity)
            shard_err = jnp.int32(0)  # f64 arenas have no degraded mode

        # Cross-shard rollup stage: the multi-stage pipeline's second hop.
        # Sum/count roll up by psum; min/max over the gathered real values,
        # with the all-shards-empty NaN sentinel restored afterwards.
        g_sum = jax.lax.psum(
            jnp.nan_to_num(g_sum) + c_lanes[:, 5], SHARD_AXIS
        )
        g_count = jax.lax.psum(c_lanes[:, 4] + g_n, SHARD_AXIS)
        # (gather + local min/max, not pmin/pmax: the TPU compiler lowers
        # an f64 all-reduce only for sums)
        if layout == "packed":
            # selected, not computed: on the keys (see packed.py)
            K = _packed
            k_min = jax.lax.all_gather(
                jnp.where(k_min == K.KEY_NAN, K.KEY_PINF, k_min),
                SHARD_AXIS).min(axis=0)
            k_max = jax.lax.all_gather(
                jnp.where(k_max == K.KEY_NAN, K.KEY_NINF, k_max),
                SHARD_AXIS).max(axis=0)
            rollup_sel = jnp.stack(
                [jnp.where(k_min == K.KEY_PINF, K.KEY_NAN, k_min),
                 jnp.where(k_max == K.KEY_NINF, K.KEY_NAN, k_max)], axis=1)
            g_min = g_max = jnp.full_like(g_sum, jnp.nan)
        else:
            g_min = jax.lax.all_gather(
                jnp.where(jnp.isnan(g_lanes[:, 1]), jnp.inf, g_lanes[:, 1]),
                SHARD_AXIS).min(axis=0)
            g_max = jax.lax.all_gather(
                jnp.where(jnp.isnan(g_lanes[:, 2]), -jnp.inf, g_lanes[:, 2]),
                SHARD_AXIS).max(axis=0)
            g_min = jnp.where(jnp.isposinf(g_min), jnp.nan, g_min)
            g_max = jnp.where(jnp.isneginf(g_max), jnp.nan, g_max)
        rollup = jnp.stack([g_sum, g_count, g_min, g_max], axis=1)

        new_state = ShardedAggregatorState(counters, gauges, timers)
        ex = lambda tree: jax.tree.map(lambda a: a[None], tree)
        lanes = {
            "counter": (c_lanes[None], c_cnt[None]),
            "gauge": (g_lanes[None], g_cnt[None]),
            "timer": (t_lanes[None], t_cnt[None]),
            "rollup": rollup,
            # per-shard degraded-state flags: nonzero means the packed
            # layout's stats are unreliable (overflow-pool truncation /
            # timer sample overflow) — callers MUST check, the raise
            # that guards the engine path cannot fire inside shard_map
            "err": shard_err[None],
        }
        if layout == "packed":
            lanes["rollup_sel"] = rollup_sel
        return ShardedAggregatorState(*map(ex, new_state)), lanes

    shard_spec = jax.tree.map(lambda _: P(SHARD_AXIS), state)
    batch_spec = ShardedBatch(*(P(SHARD_AXIS) for _ in batch))
    out_lane_spec = {
        "counter": (P(SHARD_AXIS), P(SHARD_AXIS)),
        "gauge": (P(SHARD_AXIS), P(SHARD_AXIS)),
        "timer": (P(SHARD_AXIS), P(SHARD_AXIS)),
        "rollup": P(),
        "err": P(SHARD_AXIS),
    }
    if layout == "packed":
        out_lane_spec["rollup_sel"] = P()
    return shard_map_compat(
        local_step,
        mesh,
        in_specs=(shard_spec, batch_spec, P()),
        out_specs=(shard_spec, out_lane_spec),
    )(state, batch, window)


def gauge_lanes(lanes: dict):
    """Host: the step's (D, C, 8) gauge lanes in SCALAR_LANES order
    (the packed layout's exact LAST/MIN/MAX decoded from their keys)."""
    if "rollup_sel" in lanes:  # packed layout
        return _packed.gauge_lanes(*lanes["gauge"])[0]
    return np.asarray(lanes["gauge"][0])


def rollup_lanes(lanes: dict):
    """Host: the step's (C, 4) cross-shard [sum, count, min, max], with
    the packed layout's exact min/max decoded from ``rollup_sel``."""
    out = np.array(lanes["rollup"], np.float64)
    if "rollup_sel" in lanes:
        out[:, 2:] = _packed.decode_orderable_f64(
            np.asarray(lanes["rollup_sel"]))
    return out
