"""Device-side aggregation arenas: per-(window, slot) statistic tensors.

This is the TPU re-design of the reference's per-metric aggregation
objects (``src/aggregator/aggregation/counter.go:31-70``, ``gauge.go:31-99``,
``timer.go:31-100``) and the window-keyed element values
(``src/aggregator/aggregator/generic_elem.go:181-196`` AddUnion window
alignment).  Instead of one heap object per (metric, window), each metric
type owns flat statistic tensors of shape ``(W * C,)`` — a ring of W
resolution windows by C metric slots — and an ingest batch is a handful of
scatter reductions:

    sum/count/sumsq  ->  .at[idx].add
    min/max          ->  .at[idx].min / .at[idx].max
    last (by time)   ->  lexicographic sort (slot, time, -arrival) +
                         conditional scatter of per-slot winners

Timer quantiles are **exact**: samples append into a per-window device
buffer; flush lex-sorts (slot, value) pairs and reads ranks
``ceil(q*n)`` per segment — stronger than the reference's
Cormode-Muthukrishnan eps-approximate stream (quantile/cm/stream.go), and
TPU-shaped (one big radix sort instead of pointer chasing).  A
bit-faithful host CM stream lives in ``quantile_cm.py`` for parity tests.

All 22 aggregation outputs (src/metrics/aggregation/type.go:34-55) are
computed as lanes of a (C, L) matrix at window drain; the caller masks
lanes by each slot's compressed AggregationID.

The f64 arenas of this module are the reference the packed arenas
(``packed.py``, what ``make_arenas`` builds and every served path runs)
are tested against, not a setting: ``make_arenas(layout="f64")`` builds
them for the tests and for restoring a checkpoint an f64 list wrote.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from m3_tpu.metrics.aggregation import AggregationType
from m3_tpu.x import devguard, membudget

I64_MIN = np.iinfo(np.int64).min
I64_MAX = np.iinfo(np.int64).max

# Fixed output-lane order for non-quantile statistics.  Quantile lanes are
# appended after these, in the order of the arena's `quantiles` tuple.
SCALAR_LANES = (
    AggregationType.LAST,
    AggregationType.MIN,
    AggregationType.MAX,
    AggregationType.MEAN,
    AggregationType.COUNT,
    AggregationType.SUM,
    AggregationType.SUM_SQ,
    AggregationType.STDEV,
)


def raw(jitted):
    """The traceable python function behind a jitted arena op, for
    composing arena ops inside larger jit/shard_map programs."""
    return getattr(jitted, "__wrapped__", jitted)


LAYOUTS = ("packed", "f64")


def check_layout(layout: str) -> str:
    if layout not in LAYOUTS:
        raise ValueError(
            f"unknown arena layout {layout!r}: must be one of {LAYOUTS}")
    return layout


def make_arenas(num_windows: int, capacity: int, sample_capacity: int,
                quantiles: tuple, timer_packed32: bool = False,
                layout: str = "packed"):
    """(counter, gauge, timer) arenas of one layout: "packed"
    (aggregator/packed.py, what runs) or "f64" (this module's
    reference arenas) — the one place an arena's formulation is
    chosen."""
    if check_layout(layout) == "packed":
        from m3_tpu.aggregator import packed

        return (packed.PackedCounterArena(num_windows, capacity),
                packed.PackedGaugeArena(num_windows, capacity),
                packed.PackedTimerArena(num_windows, capacity,
                                        sample_capacity, quantiles))
    return (CounterArena(num_windows, capacity),
            GaugeArena(num_windows, capacity),
            TimerArena(num_windows, capacity, sample_capacity,
                       quantiles, packed32=timer_packed32))


def _seg3(sum_col, sq_col, cnt_col, idx, values):
    """The sum / sum² / count accumulation every f64 arena shares.
    ``idx`` >= len(sum_col) drops (the sentinel contract)."""
    return (sum_col.at[idx].add(values, mode="drop"),
            sq_col.at[idx].add(values * values, mode="drop"),
            cnt_col.at[idx].add(1, mode="drop"))


def pad_slots(slots: np.ndarray, capacity: int) -> np.ndarray:
    """Pad a slot array to the next power of two with the drop sentinel
    (slot == capacity scatters out of range under mode='drop'), bounding
    the number of distinct shapes the *_clear_slots jits see."""
    n = max(1, len(slots))
    padded = 1 << (n - 1).bit_length()
    out = np.full(padded, capacity, np.int32)
    out[: len(slots)] = slots
    return out


def flat_window_index(windows, slots, num_windows: int, capacity: int):
    """Flatten (window ring index, slot) to the arena's (W*C,) index;
    out-of-ring windows AND out-of-range slots map to the drop sentinel
    W*C.  Without the slot check, a valid window with slot >= C would
    compute w*C + slot inside window w+1's region — the exact aliasing
    timer_ingest was fixed for; sentineling here keeps both layouts
    at parity on ANY input (including pad_slots sentinels and
    negative slots)."""
    oob = ((windows < 0) | (windows >= num_windows)
           | (slots < 0) | (slots >= capacity))
    return jnp.where(
        oob, num_windows * capacity, windows * capacity + slots
    ).astype(jnp.int64)


def _sanitize_slots(slots, capacity: int):
    """Slots for the last_at scatter: a NEGATIVE slot would numpy-wrap
    under mode='drop' (a lowering artifact — it would bump slot C+s's
    expiry), so map it to the drop sentinel C; slots >= C already fall
    out of the (C,) column's range and drop.  Keeps the scatters on
    the package-wide contract (invalid indices DROP — also pinned by
    xla_segment_ingest)."""
    return jnp.where(slots < 0, capacity, slots)


def orderable_f32(v: jnp.ndarray) -> jnp.ndarray:
    """f64 -> u64 holding order-preserving f32 bits in the low 32
    (IEEE-754 total order as unsigned; negatives flip entirely,
    positives flip the sign bit).  One home for the packed32 bit trick
    — the timer drain here and the packed arena's sample words
    (aggregator/packed.py) must never diverge."""
    b = v.astype(jnp.float32).view(jnp.uint32).astype(jnp.uint64)
    return jnp.where(
        b >= jnp.uint64(0x80000000),
        jnp.uint64(0xFFFFFFFF) - b,
        b | jnp.uint64(0x80000000),
    )


def decode_orderable_f32(bits: jnp.ndarray) -> jnp.ndarray:
    """Inverse of orderable_f32 -> f64 (carries f32 precision)."""
    b = jnp.where(
        bits >= jnp.uint64(0x80000000),
        bits & jnp.uint64(0x7FFFFFFF),
        jnp.uint64(0xFFFFFFFF) - bits,
    )
    return b.astype(jnp.uint32).view(jnp.float32).astype(jnp.float64)


def _stdev(count, sum_sq, sum_):
    """Sample stdev from moments (reference aggregation/common.go:29-36).

    ``count*sum_sq - sum^2`` suffers catastrophic cancellation when the
    mean dwarfs the spread (mean ~1e9, stdev ~1 leaves no mantissa bits
    for the variance): the true difference can round to a small
    NEGATIVE number.  Clamp at 0 — the earlier ``abs()`` fabricated a
    spurious stdev out of the cancellation noise instead."""
    div = count * (count - 1)
    num = jnp.maximum(count * sum_sq - sum_ * sum_, 0.0)
    return jnp.where(div <= 0, 0.0, jnp.sqrt(num / jnp.where(div == 0, 1, div)))


# ---------------------------------------------------------------------------
# Counter arena (int64 values; reference aggregation/counter.go).
# ---------------------------------------------------------------------------


class CounterState(NamedTuple):
    sum: jnp.ndarray  # i64 (W*C,)
    sum_sq: jnp.ndarray  # i64
    count: jnp.ndarray  # i64
    max: jnp.ndarray  # i64, identity I64_MIN
    min: jnp.ndarray  # i64, identity I64_MAX
    last_at: jnp.ndarray  # i64 (C,) — per-slot last write time, for expiry


def counter_init(num_windows: int, capacity: int) -> CounterState:
    n = num_windows * capacity
    return CounterState(
        sum=jnp.zeros(n, jnp.int64),
        sum_sq=jnp.zeros(n, jnp.int64),
        count=jnp.zeros(n, jnp.int64),
        max=jnp.full(n, I64_MIN, jnp.int64),
        min=jnp.full(n, I64_MAX, jnp.int64),
        last_at=jnp.zeros(capacity, jnp.int64),
    )


@functools.partial(jax.jit, donate_argnums=0)
def counter_ingest(
    state: CounterState,
    idx: jnp.ndarray,  # i32 (N,) flattened window*C + slot; >= W*C to drop
    slots: jnp.ndarray,  # i32 (N,)
    values: jnp.ndarray,  # i64 (N,)
    times: jnp.ndarray,  # i64 (N,)
) -> CounterState:
    """Counter.Update for a batch (reference counter.go:53-76)."""
    s, sq, c = _seg3(state.sum, state.sum_sq, state.count, idx, values)
    slot_safe = _sanitize_slots(slots, state.last_at.shape[0])
    return CounterState(
        sum=s,
        sum_sq=sq,
        count=c,
        max=state.max.at[idx].max(values, mode="drop"),
        min=state.min.at[idx].min(values, mode="drop"),
        last_at=state.last_at.at[slot_safe].max(times, mode="drop"),
    )


@functools.partial(jax.jit, static_argnames=("capacity",))
def counter_consume(state: CounterState, window: jnp.ndarray, capacity: int):
    """Drain one window row -> (C, L) lane matrix (reference counter.go
    accessors Sum/SumSq/Count/Max/Min/Mean/Stdev; Last is invalid for
    counters and emitted as NaN)."""
    off = window * capacity
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, capacity)
    s = sl(state.sum).astype(jnp.float64)
    ssq = sl(state.sum_sq).astype(jnp.float64)
    cnt = sl(state.count)
    cntf = cnt.astype(jnp.float64)
    mean = jnp.where(cnt == 0, 0.0, s / jnp.where(cnt == 0, 1, cnt))
    lanes = jnp.stack(
        [
            jnp.full(capacity, jnp.nan, jnp.float64),  # LAST
            jnp.where(cnt == 0, 0.0, sl(state.min).astype(jnp.float64)),
            jnp.where(cnt == 0, 0.0, sl(state.max).astype(jnp.float64)),
            mean,
            cntf,
            s,
            ssq,
            _stdev(cntf, ssq, s),
        ],
        axis=1,
    )
    return lanes, cnt


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def counter_reset_window(state: CounterState, window: jnp.ndarray, capacity: int) -> CounterState:
    off = window * capacity
    upd = lambda a, v: jax.lax.dynamic_update_slice_in_dim(
        a, jnp.full(capacity, v, a.dtype), off, 0
    )
    return CounterState(
        sum=upd(state.sum, 0),
        sum_sq=upd(state.sum_sq, 0),
        count=upd(state.count, 0),
        max=upd(state.max, I64_MIN),
        min=upd(state.min, I64_MAX),
        last_at=state.last_at,
    )


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("num_windows", "capacity"))
def counter_clear_slots(
    state: CounterState, slots: jnp.ndarray, num_windows: int, capacity: int
) -> CounterState:
    """Zero a set of slots across every window ring row (slot free; the
    reference deletes the whole Entry object — map.go deleteExpired — so
    a recycled slot must not inherit un-drained window stats)."""
    idx = (
        jnp.arange(num_windows, dtype=jnp.int64)[:, None] * capacity + slots[None, :]
    ).ravel()
    # Padded sentinel slots (== capacity) must not alias slot 0 of the
    # next window row: route them to the global OOB drop index.
    idx = jnp.where(
        (slots[None, :] >= capacity).repeat(num_windows, 0).ravel(),
        num_windows * capacity,
        idx,
    )
    return CounterState(
        sum=state.sum.at[idx].set(0, mode="drop"),
        sum_sq=state.sum_sq.at[idx].set(0, mode="drop"),
        count=state.count.at[idx].set(0, mode="drop"),
        max=state.max.at[idx].set(I64_MIN, mode="drop"),
        min=state.min.at[idx].set(I64_MAX, mode="drop"),
        last_at=state.last_at.at[slots].set(0, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Gauge arena (float64 values; reference aggregation/gauge.go).
# ---------------------------------------------------------------------------


class GaugeState(NamedTuple):
    last: jnp.ndarray  # f64 (W*C,)
    last_time: jnp.ndarray  # i64 (W*C,) — timestamp backing `last`
    sum: jnp.ndarray  # f64
    sum_sq: jnp.ndarray  # f64
    count: jnp.ndarray  # i64
    max: jnp.ndarray  # f64, identity -inf (NaN surfaced when count==0)
    min: jnp.ndarray  # f64, identity +inf
    last_at: jnp.ndarray  # i64 (C,)


def gauge_init(num_windows: int, capacity: int) -> GaugeState:
    n = num_windows * capacity
    return GaugeState(
        last=jnp.zeros(n, jnp.float64),
        last_time=jnp.zeros(n, jnp.int64),
        sum=jnp.zeros(n, jnp.float64),
        sum_sq=jnp.zeros(n, jnp.float64),
        count=jnp.zeros(n, jnp.int64),
        max=jnp.full(n, -jnp.inf, jnp.float64),
        min=jnp.full(n, jnp.inf, jnp.float64),
        last_at=jnp.zeros(capacity, jnp.int64),
    )


@functools.partial(jax.jit, donate_argnums=0)
def gauge_ingest(
    state: GaugeState,
    idx: jnp.ndarray,  # i32 (N,) flattened; >= W*C to drop
    slots: jnp.ndarray,  # i32 (N,)
    values: jnp.ndarray,  # f64 (N,)
    times: jnp.ndarray,  # i64 (N,)
) -> GaugeState:
    """Gauge.Update for a batch (reference gauge.go:53-104).

    Semantics mirrored: `last` tracks the value with the greatest
    timestamp, first arrival winning ties (gauge.go:82-91 only updates
    when strictly after); count includes NaN values but sum/min/max
    ignore them (gauge.go:57-63,95-103).
    """
    n = values.shape[0]
    nan = jnp.isnan(values)
    safe = jnp.where(nan, 0.0, values)

    # Per-slot winner for `last`: sort by (idx asc, time asc, arrival
    # desc); the final element of each idx-segment is (max time, min
    # arrival).  Conditional scatter beats the stored (time, arrival)
    # only when strictly newer.
    arrival_desc = jnp.arange(n - 1, -1, -1, dtype=jnp.int32)
    s_idx, _s_time, _s_arr, s_val, s_times = jax.lax.sort(
        (idx, times, arrival_desc, values, times), num_keys=3
    )
    is_winner = jnp.concatenate([s_idx[1:] != s_idx[:-1], jnp.ones(1, bool)])
    old_time = state.last_time[jnp.clip(s_idx, 0, state.last_time.shape[0] - 1)]
    take = is_winner & (s_times > old_time)
    widx = jnp.where(take, s_idx, state.last.shape[0])  # OOB -> dropped

    g_s, g_sq, g_c = _seg3(state.sum, state.sum_sq, state.count, idx, safe)
    slot_safe = _sanitize_slots(slots, state.last_at.shape[0])
    return GaugeState(
        last=state.last.at[widx].set(s_val, mode="drop"),
        last_time=state.last_time.at[widx].set(s_times, mode="drop"),
        sum=g_s,
        sum_sq=g_sq,
        count=g_c,
        max=state.max.at[idx].max(jnp.where(nan, -jnp.inf, values), mode="drop"),
        min=state.min.at[idx].min(jnp.where(nan, jnp.inf, values), mode="drop"),
        last_at=state.last_at.at[slot_safe].max(times, mode="drop"),
    )


@functools.partial(jax.jit, static_argnames=("capacity",))
def gauge_consume(state: GaugeState, window: jnp.ndarray, capacity: int):
    off = window * capacity
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, capacity)
    s, ssq, cnt = sl(state.sum), sl(state.sum_sq), sl(state.count)
    cntf = cnt.astype(jnp.float64)
    mx, mn = sl(state.max), sl(state.min)
    mean = jnp.where(cnt == 0, 0.0, s / jnp.where(cnt == 0, 1, cnt))
    lanes = jnp.stack(
        [
            sl(state.last),
            jnp.where(jnp.isinf(mn), jnp.nan, mn),  # NaN until a value seen
            jnp.where(jnp.isinf(mx), jnp.nan, mx),
            mean,
            cntf,
            s,
            ssq,
            _stdev(cntf, ssq, s),
        ],
        axis=1,
    )
    return lanes, cnt


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def gauge_reset_window(state: GaugeState, window: jnp.ndarray, capacity: int) -> GaugeState:
    off = window * capacity
    upd = lambda a, v: jax.lax.dynamic_update_slice_in_dim(
        a, jnp.full(capacity, v, a.dtype), off, 0
    )
    return GaugeState(
        last=upd(state.last, 0.0),
        last_time=upd(state.last_time, 0),
        sum=upd(state.sum, 0.0),
        sum_sq=upd(state.sum_sq, 0.0),
        count=upd(state.count, 0),
        max=upd(state.max, -jnp.inf),
        min=upd(state.min, jnp.inf),
        last_at=state.last_at,
    )


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("num_windows", "capacity"))
def gauge_clear_slots(
    state: GaugeState, slots: jnp.ndarray, num_windows: int, capacity: int
) -> GaugeState:
    idx = (
        jnp.arange(num_windows, dtype=jnp.int64)[:, None] * capacity + slots[None, :]
    ).ravel()
    # Padded sentinel slots (== capacity) must not alias slot 0 of the
    # next window row: route them to the global OOB drop index.
    idx = jnp.where(
        (slots[None, :] >= capacity).repeat(num_windows, 0).ravel(),
        num_windows * capacity,
        idx,
    )
    return GaugeState(
        last=state.last.at[idx].set(0.0, mode="drop"),
        last_time=state.last_time.at[idx].set(0, mode="drop"),
        sum=state.sum.at[idx].set(0.0, mode="drop"),
        sum_sq=state.sum_sq.at[idx].set(0.0, mode="drop"),
        count=state.count.at[idx].set(0, mode="drop"),
        max=state.max.at[idx].set(-jnp.inf, mode="drop"),
        min=state.min.at[idx].set(jnp.inf, mode="drop"),
        last_at=state.last_at.at[slots].set(0, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Timer arena (float64 values + exact quantiles; reference
# aggregation/timer.go + quantile/cm/stream.go).
# ---------------------------------------------------------------------------


class TimerState(NamedTuple):
    sum: jnp.ndarray  # f64 (W*C,)
    sum_sq: jnp.ndarray  # f64
    count: jnp.ndarray  # i64
    sample_slot: jnp.ndarray  # i32 (W, S) — slot per buffered sample
    sample_val: jnp.ndarray  # f64 (W, S)
    sample_n: jnp.ndarray  # i64 (W,) — write offsets (may exceed S: overflow)
    last_at: jnp.ndarray  # i64 (C,)


def timer_append_plan(windows, slots, sample_n, capacity: int, scap: int):
    """Destination plan for appending a timer batch into per-window
    sample buffers: (drop mask, flat destination offsets with the drop
    sentinel num_w*scap, per-window appended counts).

    Buffer order is irrelevant (consume sorts the whole window at
    drain), so ranks come from one exclusive cumsum per window over the
    membership mask — W is small and static, and this avoids carrying
    the value column through a device sort.  ONE home for the plan: the
    f64 and packed timer ingests (aggregator/packed.py) share it, so
    overflow accounting can never diverge between the layouts."""
    num_w = sample_n.shape[0]
    oob = (windows < 0) | (windows >= num_w)
    drop = oob | (slots < 0) | (slots >= capacity)
    order_key = jnp.where(drop, num_w, windows)
    onehot = order_key[None, :] == jnp.arange(
        num_w, dtype=order_key.dtype)[:, None]
    ranks_all = jnp.cumsum(onehot.astype(jnp.int64), axis=1) - 1  # (W, N)
    w_clip = jnp.clip(order_key, 0, num_w - 1)
    rank = jnp.take_along_axis(ranks_all, w_clip[None, :], axis=0)[0]
    dst = sample_n[w_clip] + rank
    flat = jnp.where(
        ~drop & (dst < scap), w_clip.astype(jnp.int64) * scap + dst,
        num_w * scap)
    per_w_counts = onehot.sum(axis=1, dtype=sample_n.dtype)
    return drop, flat, per_w_counts


def timer_init(num_windows: int, capacity: int, sample_capacity: int) -> TimerState:
    n = num_windows * capacity
    return TimerState(
        sum=jnp.zeros(n, jnp.float64),
        sum_sq=jnp.zeros(n, jnp.float64),
        count=jnp.zeros(n, jnp.int64),
        sample_slot=jnp.full((num_windows, sample_capacity), capacity, jnp.int32),
        sample_val=jnp.zeros((num_windows, sample_capacity), jnp.float64),
        sample_n=jnp.zeros(num_windows, jnp.int64),
        last_at=jnp.zeros(capacity, jnp.int64),
    )


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def timer_ingest(
    state: TimerState,
    windows: jnp.ndarray,  # i32 (N,) window ring index per sample; >= W drops
    slots: jnp.ndarray,  # i32 (N,)
    values: jnp.ndarray,  # f64 (N,)
    times: jnp.ndarray,  # i64 (N,)
    capacity: int,
) -> TimerState:
    """Timer.AddBatch for a batch of (slot, value) samples
    (reference timer.go:55-76): moments scatter-add plus sample append.

    Samples append into each window's buffer at offsets
    ``sample_n[w] + rank-within-batch``; indices beyond S drop (the
    moment stats stay exact; quantiles degrade — counted by the caller
    via sample_n overflow).
    """
    num_w, scap = state.sample_slot.shape
    # Out-of-range SLOTS must drop too: w*C + slot with slot >= C would
    # otherwise land in window w+1's region (fuzz-caught).  The
    # combined mask also gates the sample APPEND — a dropped sample
    # must not consume quantile-buffer capacity or inflate sample_n's
    # overflow accounting (timer_append_plan owns both contracts).
    drop, flat, per_w_counts = timer_append_plan(
        windows, slots, state.sample_n, capacity, scap)
    idx = jnp.where(drop, num_w * capacity,
                    windows * capacity + slots)

    t_s, t_sq, t_c = _seg3(state.sum, state.sum_sq, state.count, idx, values)
    slot_safe = _sanitize_slots(slots, capacity)
    return TimerState(
        sum=t_s,
        sum_sq=t_sq,
        count=t_c,
        sample_slot=state.sample_slot.ravel()
        .at[flat]
        .set(slots, mode="drop")
        .reshape(num_w, scap),
        sample_val=state.sample_val.ravel()
        .at[flat]
        .set(values, mode="drop")
        .reshape(num_w, scap),
        sample_n=state.sample_n + per_w_counts,
        last_at=state.last_at.at[slot_safe].max(times, mode="drop"),
    )


@functools.partial(jax.jit,
                   static_argnames=("capacity", "quantiles", "packed32"))
def timer_consume(
    state: TimerState,
    window: jnp.ndarray,
    capacity: int,
    quantiles: tuple,
    packed32: bool = False,
):
    """Drain one timer window -> (C, L + Q) lanes.

    Exact quantiles via lex-sort of (slot, value) and per-segment rank
    reads at ``ceil(q*n)`` (the reference CM stream targets the same rank
    within eps error — quantile/cm/stream.go:239-247).

    ``packed32`` replaces the two-key (i32 slot, f64 value) lex-sort
    with ONE i64 key per sample: ``slot << 32 | orderable(f32)``
    (sign-flip trick keeps float order in unsigned bit order).
    Quantile reads decode the f32 back, so quantile/min/max lanes carry
    f32 precision (~1e-7 relative) — four orders tighter than the
    reference CM stream's default 1e-3 eps, but no longer bit-equal to
    the f64 sort.  The bound holds on f32's FINITE NORMAL range only:
    |v| above ~3.4e38 saturates to ±inf and |v| below ~1.2e-38 flushes
    toward 0 in these lanes — timer values are durations, so real
    deployments sit comfortably inside; pick the exact drain if yours
    do not.  Moments (sum/sum_sq/count/mean/stdev) are computed from
    the f64 accumulators either way and stay exact."""
    num_w, scap = state.sample_slot.shape
    off = window * capacity
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, capacity)
    s, ssq, cnt = sl(state.sum), sl(state.sum_sq), sl(state.count)
    cntf = cnt.astype(jnp.float64)
    mean = jnp.where(cnt == 0, 0.0, s / jnp.where(cnt == 0, 1, cnt))

    slots_w = jax.lax.dynamic_index_in_dim(state.sample_slot, window, keepdims=False)
    vals_w = jax.lax.dynamic_index_in_dim(state.sample_val, window, keepdims=False)
    if packed32:
        keys = jax.lax.sort(
            (slots_w.astype(jnp.uint64) << jnp.uint64(32))
            | orderable_f32(vals_w))
        s_slot = (keys >> jnp.uint64(32)).astype(jnp.int32)
        s_val = decode_orderable_f32(keys & jnp.uint64(0xFFFFFFFF))
    else:
        s_slot, s_val = jax.lax.sort((slots_w, vals_w), num_keys=2)

    seg_start = jnp.searchsorted(s_slot, jnp.arange(capacity, dtype=jnp.int32))
    seg_end = jnp.searchsorted(
        s_slot, jnp.arange(capacity, dtype=jnp.int32), side="right"
    )
    seg_n = (seg_end - seg_start).astype(jnp.float64)

    mn = s_val[jnp.clip(seg_start, 0, scap - 1)]
    mx = s_val[jnp.clip(seg_end - 1, 0, scap - 1)]
    empty = seg_n == 0
    mn = jnp.where(empty, 0.0, mn)
    mx = jnp.where(empty, 0.0, mx)

    qlanes = []
    for q in quantiles:
        ranks = jnp.ceil(q * seg_n).astype(jnp.int64) - 1
        ranks = jnp.clip(ranks, 0, jnp.maximum(seg_n.astype(jnp.int64) - 1, 0))
        qv = s_val[jnp.clip(seg_start + ranks, 0, scap - 1)]
        qlanes.append(jnp.where(empty, 0.0, qv))

    lanes = jnp.stack(
        [
            jnp.full(capacity, jnp.nan, jnp.float64),  # LAST (invalid for timers)
            mn,
            mx,
            mean,
            cntf,
            s,
            ssq,
            _stdev(cntf, ssq, s),
            *qlanes,
        ],
        axis=1,
    )
    return lanes, cnt


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def timer_reset_window(state: TimerState, window: jnp.ndarray, capacity: int) -> TimerState:
    num_w, scap = state.sample_slot.shape
    off = window * capacity
    upd = lambda a, v: jax.lax.dynamic_update_slice_in_dim(
        a, jnp.full(capacity, v, a.dtype), off, 0
    )
    return TimerState(
        sum=upd(state.sum, 0.0),
        sum_sq=upd(state.sum_sq, 0.0),
        count=upd(state.count, 0),
        sample_slot=jax.lax.dynamic_update_slice(
            state.sample_slot,
            jnp.full((1, scap), capacity, jnp.int32),
            (window.astype(jnp.int32), jnp.int32(0)),
        ),
        sample_val=state.sample_val,
        sample_n=state.sample_n.at[window].set(0),
        last_at=state.last_at,
    )


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("num_windows", "capacity"))
def timer_clear_slots(
    state: TimerState, slots: jnp.ndarray, num_windows: int, capacity: int
) -> TimerState:
    """Clear freed timer slots: zero the moment rows and retarget their
    buffered samples to the drop sentinel so a recycled slot's quantiles
    don't include the previous occupant's samples."""
    idx = (
        jnp.arange(num_windows, dtype=jnp.int64)[:, None] * capacity + slots[None, :]
    ).ravel()
    # Padded sentinel slots (== capacity) must not alias slot 0 of the
    # next window row: route them to the global OOB drop index.
    idx = jnp.where(
        (slots[None, :] >= capacity).repeat(num_windows, 0).ravel(),
        num_windows * capacity,
        idx,
    )
    sorted_slots = jnp.sort(slots.astype(jnp.int32))
    flat = state.sample_slot.ravel()
    pos = jnp.clip(
        jnp.searchsorted(sorted_slots, flat), 0, sorted_slots.shape[0] - 1
    )
    hit = sorted_slots[pos] == flat
    new_sample_slot = jnp.where(hit, jnp.int32(capacity), flat).reshape(
        state.sample_slot.shape
    )
    return TimerState(
        sum=state.sum.at[idx].set(0.0, mode="drop"),
        sum_sq=state.sum_sq.at[idx].set(0.0, mode="drop"),
        count=state.count.at[idx].set(0, mode="drop"),
        sample_slot=new_sample_slot,
        sample_val=state.sample_val,
        sample_n=state.sample_n,
        last_at=state.last_at.at[slots].set(0, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Thin stateful wrappers used by the engine.
# ---------------------------------------------------------------------------


class _ScalarLanesMixin:
    @property
    def lane_types(self):
        return SCALAR_LANES

    def lane_for_type(self, t: AggregationType) -> int | None:
        return SCALAR_LANES.index(t) if t in SCALAR_LANES else None


class _TimerLanesMixin:
    """Quantile-extended lane mapping shared by the f64 and packed
    timer arenas (requires a ``quantiles`` tuple attribute), and the
    host's view of their sample buffers."""

    def samples_buffered(self, window: int | None = None) -> int:
        """Samples in one window's buffer, or in the fullest (the host
        shadow of ``state.sample_n``: no device sync)."""
        n = self._sample_n_host
        return int(n.max() if window is None else n[window])

    @property
    def lane_types(self):
        """Primary type per lane; quantile-aliased types (e.g. MEDIAN ==
        P50) resolve through lane_for_type."""
        qtypes = []
        for q in self.quantiles:
            primary = next(
                (
                    t
                    for t in AggregationType
                    if t is not AggregationType.MEDIAN and t.quantile() == q
                ),
                AggregationType.UNKNOWN,
            )
            qtypes.append(primary)
        return SCALAR_LANES + tuple(qtypes)

    def lane_for_type(self, t: AggregationType) -> int | None:
        if t in SCALAR_LANES:
            return SCALAR_LANES.index(t)
        q = t.quantile()
        if q is not None and q in self.quantiles:
            return len(SCALAR_LANES) + self.quantiles.index(q)
        return None


def _guarded_ingest(call):
    """Run one arena ingest behind the device guard.  An ingest has one
    formulation, so the fallback is the same program with the device
    faultpoints skipped (the injected-fault contract).  A failure that
    persists through the fallback raises typed to the engine."""
    return devguard.run_guarded("arena.ingest", call, call)


def _guarded_consume(call):
    """Arena window drains re-probe/fall back like ingests; the
    fallback is the same program with the faultpoints skipped."""
    def primary():
        out = call()
        devguard.transfer_point("arena.consume")
        return out

    return devguard.run_guarded("arena.consume", primary, call)


def _guarded_state_op(call):
    """Window resets and slot clears ride the consume cycle's stage
    breaker (they follow a drain / an expiry sweep); like consume, the
    fallback is the same program with the faultpoints skipped."""
    return devguard.run_guarded("arena.consume", call, call)


class CounterArena(_ScalarLanesMixin):
    """Counter slots over a W-window ring (reference counter.go semantics)."""

    layout = "f64"

    def __init__(self, num_windows: int, capacity: int):
        self.num_windows = num_windows
        self.capacity = capacity
        self._mem = membudget.reserve(
            "aggregator.counter",
            membudget.counter_arena_bytes("f64", num_windows, capacity),
            owner=self)
        self.state = counter_init(num_windows, capacity)

    def ingest(self, windows, slots, values, times):
        idx = flat_window_index(windows, slots, self.num_windows, self.capacity)
        self.state = _guarded_ingest(lambda: counter_ingest(
            self.state, idx, slots, jnp.asarray(values).astype(jnp.int64),
            times))

    def consume(self, window: int):
        return _guarded_consume(lambda: counter_consume(
            self.state, jnp.int32(window), self.capacity))

    def reset_window(self, window: int):
        self.state = _guarded_state_op(lambda: counter_reset_window(self.state, jnp.int32(window), self.capacity))

    def clear_slots(self, slots):
        self.state = _guarded_state_op(lambda: counter_clear_slots(
            self.state,
            jnp.asarray(pad_slots(np.asarray(slots), self.capacity)),
            self.num_windows,
            self.capacity,
        ))


class GaugeArena(_ScalarLanesMixin):
    layout = "f64"

    def __init__(self, num_windows: int, capacity: int):
        self.num_windows = num_windows
        self.capacity = capacity
        self._mem = membudget.reserve(
            "aggregator.gauge",
            membudget.gauge_arena_bytes("f64", num_windows, capacity),
            owner=self)
        self.state = gauge_init(num_windows, capacity)

    def ingest(self, windows, slots, values, times):
        idx = flat_window_index(windows, slots, self.num_windows, self.capacity)
        self.state = _guarded_ingest(lambda: gauge_ingest(
            self.state, idx, slots, jnp.asarray(values).astype(jnp.float64),
            times))

    def consume(self, window: int):
        return _guarded_consume(lambda: gauge_consume(
            self.state, jnp.int32(window), self.capacity))

    def reset_window(self, window: int):
        self.state = _guarded_state_op(lambda: gauge_reset_window(self.state, jnp.int32(window), self.capacity))

    def clear_slots(self, slots):
        self.state = _guarded_state_op(lambda: gauge_clear_slots(
            self.state,
            jnp.asarray(pad_slots(np.asarray(slots), self.capacity)),
            self.num_windows,
            self.capacity,
        ))


class TimerArena(_TimerLanesMixin):
    layout = "f64"
    DEFAULT_QUANTILES = (0.5, 0.95, 0.99)

    def __init__(
        self,
        num_windows: int,
        capacity: int,
        sample_capacity: int,
        quantiles: tuple = DEFAULT_QUANTILES,
        packed32: bool = False,
    ):
        self.num_windows = num_windows
        self.capacity = capacity
        self.sample_capacity = sample_capacity
        self.quantiles = tuple(quantiles)
        self.packed32 = packed32
        self._mem = membudget.reserve(
            "aggregator.timer",
            membudget.timer_arena_bytes("f64", num_windows, capacity,
                                        sample_capacity),
            owner=self)
        self.state = timer_init(num_windows, capacity, sample_capacity)
        # Host shadow of state.sample_n: avoids a device sync per ingest
        # batch just to run the overflow check.
        self._sample_n_host = np.zeros(num_windows, np.int64)
        self.grows = 0  # times _grow padded the buffer (a new shape)

    def ingest(self, windows, slots, values, times):
        """Append a batch; grows the per-window sample buffer first if the
        batch would overflow it (the reference CM stream never drops
        samples — stream.go AddBatch — so neither do we; growth is
        geometric to amortize the re-jit)."""
        windows_np = np.asarray(windows)
        slots_np = np.asarray(slots)
        # Mirror the device-side drop mask exactly: samples dropped for
        # an out-of-range slot never reach the buffer, so they must not
        # count toward growth/overflow either.
        in_range = ((windows_np >= 0) & (windows_np < self.num_windows)
                    & (slots_np >= 0) & (slots_np < self.capacity))
        per_w = np.bincount(
            windows_np[in_range], minlength=self.num_windows
        )
        # Commit-after-success (the ShardBuffer.write pattern): a
        # _grow budget reject or device failure must leave the shadow
        # mirroring state.sample_n, or every later batch re-rejects.
        new_n = self._sample_n_host + per_w
        needed = int(new_n.max())
        if needed > self.sample_capacity:
            self._grow(needed)
        self.state = _guarded_ingest(lambda: timer_ingest(
            self.state,
            jnp.asarray(windows_np.astype(np.int32)),
            slots,
            jnp.asarray(values).astype(jnp.float64),
            times,
            self.capacity,
        ))
        self._sample_n_host = new_n

    def _grow(self, needed: int) -> None:
        new_cap = self.sample_capacity
        while new_cap < needed:
            new_cap *= 2
        # Admission before the pad allocates: an over-budget grow
        # raises typed (the reference CM stream's never-drop contract
        # yields to the budget — the caller sees the reject, the
        # existing samples stay intact).
        self._mem.resize(membudget.timer_arena_bytes(
            "f64", self.num_windows, self.capacity, new_cap))
        pad = new_cap - self.sample_capacity
        self.state = TimerState(
            sum=self.state.sum,
            sum_sq=self.state.sum_sq,
            count=self.state.count,
            sample_slot=jnp.pad(
                self.state.sample_slot,
                ((0, 0), (0, pad)),
                constant_values=self.capacity,
            ),
            sample_val=jnp.pad(self.state.sample_val, ((0, 0), (0, pad))),
            sample_n=self.state.sample_n,
            last_at=self.state.last_at,
        )
        self.sample_capacity = new_cap
        self.grows += 1

    # the moments are scatter accumulators here, read at no cost: a
    # drain never skips them, and `moments` is ignored
    moments_skipped = 0

    def consume(self, window: int, moments: bool = True):
        return _guarded_consume(lambda: timer_consume(
            self.state, jnp.int32(window), self.capacity, self.quantiles,
            self.packed32,
        ))

    def reset_window(self, window: int):
        self.state = _guarded_state_op(lambda: timer_reset_window(self.state, jnp.int32(window), self.capacity))
        self._sample_n_host[window] = 0

    def clear_slots(self, slots):
        self.state = _guarded_state_op(lambda: timer_clear_slots(
            self.state,
            jnp.asarray(pad_slots(np.asarray(slots), self.capacity)),
            self.num_windows,
            self.capacity,
        ))
