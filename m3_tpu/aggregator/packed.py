"""Packed arena: sort/segment ingest + adaptive-width counter state.

The scatter arenas (``arena.py``) pay one XLA scatter per statistic lane
of every SAMPLE — ~11 random-access passes plus a 3-key lex sort per
ingest batch (a scatter costs ~40-60ns an element on XLA-CPU and
~0.08us per 64-bit element on a TPU v5e; PERF.md, PR 28).  This module
reformulates the whole hot path around ONE u64 key sort per batch, and
after it never leaves the sorted batch's domain: an ingest costs its N
rows, whatever the arena holds (the dense merge it replaces paid
~0.3us per SLOT per call on the TPU):

    key    = flat_idx << AB | arrival          (AB = batch-size bits)
    sorted -> permutation + segment heads/tails (neighbour compares)
    count/sum/sum_sq/min/max/last = one segmented associative scan;
                        a segment's aggregates stand at its TAIL row
    state update      = gather the state at the N rows' flat slots,
                        merge, scatter the tail rows back (per lane,
                        batch-sized, into the donated state)

No lane of arena length is computed.  The scatters are batch-sized:
one per state lane at the tail rows (distinct slots; every other row
is sent to its own out-of-range index and dropped), one scatter-max
into the (C,) expiry column, the bounded-K overflow-pool promotion
below, and the timer sample append (one packed word).

Counter state adopts the SALSA / Counter Pools layout
(arXiv:2102.12531, arXiv:2502.14699): narrow base lanes packed per
(window, slot) —

    base   u64: count:CB | sum:SB (biased)   (default 16/48)
    sq     i64: sum of squares               (full width: squares grow
                with value^2 and saturate any narrow lane in minutes —
                the round-8 bench caught a 24-bit sq lane doing so)
    minmax u32: o16(min) << 16 | o16(max)    (int16-exact)

— with a shared overflow pool of full-width i64 rows.  A slot whose
count or sum lane would saturate, or that sees a value outside the
int16 min/max range, PROMOTES: its exact running stats move to a pool
row and later batches add deltas there.  Promotion and spill are
branchless bounded-K scatters (``jnp.nonzero(size=K)``) under a
``lax.cond`` that costs nothing while no slot is promoted.  Per-slot
memory is 24B (base 8 + sq 8 + minmax 4 + pool index 4) vs the f64
arena's 40B — 1.67x, plus P*48B of pool (default P = C/16); narrower
CB/SB widths trade promotion rate for memory.  Packed counter stats
are EXACT: count/sum/sum_sq accumulate in (wrapping) i64 exactly like
the scatter path, min/max are int16-exact in the base and i64-exact
once promoted.

Gauge state keeps f64 sum/sum_sq and carries min/max/last as
order-preserving i64 keys of the f64 bits (the parity contract pins
count/min/max/last bit-exact, on any backend: see the gauge section);
the packed win for gauges is the formulation: batch sums ride the segmented scan as tree-order f64 adds
— rounding stays at ~log2(N) ulps of each segment's OWN magnitude (a
cumsum-diff form was tried and rejected: its quantum scales with the
batch max, which blows the relative bound for tiny segments) and
+/-inf / NaN flow through with the scatter path's exact semantics —
replacing the 3-key lex sort + 8 scatters.

Timer state is one u64 word per buffered sample (slot<<32 |
orderable-f32(value)) — the packed32 drain representation extended to
ingest, so ingest is ONE scatter (append) and drain sorts the words
directly.  Moments are recovered at drain from the sorted buffer via
the same segmented scan (values carry f32 precision, within the
established packed32 1e-6 bound; counts are exact).

Everything here is jit-pure; ``arena.make_arenas`` builds these arenas
by default.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from m3_tpu.aggregator.arena import (
    I64_MAX,
    I64_MIN,
    SCALAR_LANES,
    _guarded_consume,
    _guarded_ingest,
    _guarded_state_op,
    _ScalarLanesMixin,
    _TimerLanesMixin,
    _sanitize_slots,
    _stdev,
    decode_orderable_f32,
    orderable_f32,
    pad_slots,
    timer_append_plan,
)
from m3_tpu.x import devguard, membudget

# Default adaptive-width lane split for the counter base word
# (count:16 | sum:24 | sq:24) and the int16 min/max word.  Tests pass
# narrower widths to exercise promotion; widths are STATIC jit args.
# (count:16 | sum:48) in one u64 word; sum_sq keeps a dedicated i64
# column — squares grow with value^2 and would saturate any packed
# lane in minutes of real traffic (the round-8 bench caught exactly
# that with a 24-bit sq lane), and a full i64 sq column keeps packed
# counter moments BIT-exact vs the scatter path (mod-2^64 wrap
# included) instead of merely within 1e-6.
DEFAULT_WIDTHS = (16, 48)
# Bounded promotion fan-out per ingest batch: more than K promotions or
# pool-active slots in one batch sets the sticky `err` lane (the host
# wrapper raises at the next consume).  K scatters are ~micro-seconds.
PROMOTE_K = 4096
# int16-exact min/max range of the base minmax word.
_MM_LO = -(1 << 15)
_MM_HI = (1 << 15) - 1

_ERR_PROMOTE_K = 1  # more than K promotions/active pool slots in a batch
_ERR_POOL_FULL = 2  # overflow pool exhausted
# timer sample-buffer overflow: OWNED here so the err-bit namespace has
# one home, but only RAISED by the sharded step's lanes["err"] (the
# host PackedTimerArena grows its buffer and cannot overflow)
_ERR_TIMER_OVERFLOW = 4


# ---------------------------------------------------------------------------
# Shared sort/segment machinery
# ---------------------------------------------------------------------------


class _Segments(NamedTuple):
    """One sorted batch view: permutation + per-row segment marks."""

    perm: jnp.ndarray   # i32 (N,) original position of sorted element
    sslot: jnp.ndarray  # i32 (N,) flat (window*C+slot) index, ascending
    head: jnp.ndarray   # bool (N,) first element of its segment
    tail: jnp.ndarray   # bool (N,) last element of its segment


def _arrival_bits(n: int) -> int:
    return max(1, (max(n - 1, 1)).bit_length())


def packed_flat_index(windows, slots, num_windows: int, capacity: int):
    """Flat index for the packed ingest ops, with a slot-only GHOST
    region: [0, W*C) carries stats; [W*C, W*C+C) holds samples whose
    slot is valid but whose window dropped — they contribute only the
    per-slot ``last_at`` expiry time, mirroring the scatter arenas
    (whose last_at scatter-max is gated on the slot alone); W*C+C is
    the full drop sentinel."""
    valid_s = (slots >= 0) & (slots < capacity)
    valid_w = (windows >= 0) & (windows < num_windows)
    wc = num_windows * capacity
    base = windows.astype(jnp.int64) * capacity + slots
    return jnp.where(
        valid_w & valid_s, base,
        jnp.where(valid_s, wc + slots.astype(jnp.int64),
                  jnp.int64(wc + capacity)))


def _segment_view(idx: jnp.ndarray, n_flat: int) -> _Segments:
    """Sort a batch of flat indices into per-slot segments.

    ``idx`` values == n_flat are the drop sentinel: they sort to the
    tail as one segment that no merge reads."""
    n = idx.shape[0]
    ab = _arrival_bits(n)
    if (n_flat + 1).bit_length() + ab > 63:
        raise ValueError(
            f"arena of {n_flat} flat slots with batches of {n} needs "
            f"{(n_flat + 1).bit_length() + ab} key bits > 63; shrink the "
            "batch or the arena")
    key = (idx.astype(jnp.uint64) << jnp.uint64(ab)) | jnp.arange(
        n, dtype=jnp.uint64)
    ks = jax.lax.sort(key)
    perm = (ks & jnp.uint64((1 << ab) - 1)).astype(jnp.int32)
    sslot = (ks >> jnp.uint64(ab)).astype(jnp.int32)
    edge = sslot[1:] != sslot[:-1]
    one = jnp.ones(1, bool)
    return _Segments(perm, sslot, jnp.concatenate([one, edge]),
                     jnp.concatenate([edge, one]))


def _seg_scan(seg: _Segments, lanes: tuple, combine) -> tuple:
    """Segmented associative scan over the sorted batch: ``combine``
    merges two within-segment prefixes; segment heads reset the carry.
    Returns the scanned lanes: a segment's reduction stands at its
    tail row (``seg.tail``), the only rows the merges write back."""
    def op(a, b):
        fa, va = a[0], a[1:]
        fb, vb = b[0], b[1:]
        merged = combine(va, vb)
        out = tuple(jnp.where(fb, nb, m) for nb, m in zip(vb, merged))
        return (fa | fb,) + out

    res = jax.lax.associative_scan(op, (seg.head,) + lanes)
    return res[1:]


def _tail_rows(seg: _Segments, wc: int):
    """(live, at, put) for a batch-domain merge into (W*C,) state
    lanes: ``live`` marks the rows that end a stats segment; ``at``
    gathers a lane at every row's flat slot (ghost and dropped rows
    read slot W*C-1 and are never written); ``put(lane, v)`` writes
    ``v`` back at the live rows.  Every other row goes to an
    out-of-range index OF ITS OWN, so the indices are unique as
    promised to XLA (not sorted: the large targets stand between
    sorted ones) and ``mode="drop"`` discards them."""
    n = seg.sslot.shape[0]
    live = seg.tail & (seg.sslot < wc)
    tgt = jnp.where(live, seg.sslot, wc + jnp.arange(n, dtype=jnp.int32))
    at = jnp.minimum(seg.sslot, wc - 1)
    return live, at, lambda lane, v: lane.at[tgt].set(
        v, mode="drop", unique_indices=True)


def _merge_last_at(last_at, idx, times, wc: int):
    """Fold the batch's times (the ghost region's window-dropped
    samples included) into the per-slot expiry column, straight from
    the unsorted batch.  One slot may stand under several windows of a
    batch: not a unique scatter."""
    capacity = last_at.shape[0]
    flat = idx.astype(jnp.int32)
    slot = jnp.where(flat < wc + capacity, flat % capacity, capacity)
    return last_at.at[slot].max(times, mode="drop")


# ---------------------------------------------------------------------------
# Packed counter arena (SALSA/Counter-Pools layout).  The orderable-f32
# word encoding is shared with the timer drain's packed32 form and lives
# in arena.py (one home; imported above).
# ---------------------------------------------------------------------------


class PackedCounterState(NamedTuple):
    base: jnp.ndarray      # u64 (W*C,) count | sum (biased) lanes
    sq: jnp.ndarray        # i64 (W*C,) sum of squares (wraps mod 2^64)
    minmax: jnp.ndarray    # u32 (W*C,) o16(min)<<16 | o16(max)
    pool_cnt: jnp.ndarray  # i64 (P,)
    pool_sum: jnp.ndarray  # i64 (P,)
    pool_sq: jnp.ndarray   # i64 (P,)
    pool_min: jnp.ndarray  # i64 (P,)
    pool_max: jnp.ndarray  # i64 (P,)
    pool_owner: jnp.ndarray  # i32 (P,) flat owner idx, -1 free
    pool_idx: jnp.ndarray  # i32 (W*C,) pool row, -1 unpromoted
    pool_n: jnp.ndarray    # i32 () live pool rows (derived from
    #                        pool_owner at every producer; carried for
    #                        cheap host observability — allocation
    #                        itself is the free-row scan in
    #                        _counter_merge, NOT a bump pointer)
    err: jnp.ndarray       # i32 () sticky error bits
    last_at: jnp.ndarray   # i64 (C,)


def _neutral_base(widths: tuple) -> int:
    cb, sb = widths
    return 1 << (sb - 1)  # cnt 0, sum at bias (python int: trace-safe)


_MM_NEUTRAL = np.uint32(0xFFFF0000)  # min lane 0xFFFF (+32767), max 0


def _unpack_base(base: jnp.ndarray, widths: tuple):
    cb, sb = widths
    cnt = (base >> jnp.uint64(sb)).astype(jnp.int64)
    s = (base & jnp.uint64((1 << sb) - 1)).astype(
        jnp.int64) - jnp.int64(1 << (sb - 1))
    return cnt, s


def _pack_base(cnt, s, widths: tuple) -> jnp.ndarray:
    cb, sb = widths
    return ((cnt.astype(jnp.uint64) << jnp.uint64(sb))
            | (s + jnp.int64(1 << (sb - 1))).astype(jnp.uint64))


def _unpack_minmax(mm: jnp.ndarray):
    mn = (mm >> jnp.uint32(16)).astype(jnp.int64) - jnp.int64(1 << 15)
    mx = (mm & jnp.uint32(0xFFFF)).astype(jnp.int64) - jnp.int64(1 << 15)
    return mn, mx


def _pack_minmax(mn, mx) -> jnp.ndarray:
    bias = jnp.int64(1 << 15)
    return (((mn + bias).astype(jnp.uint32) << jnp.uint32(16))
            | (mx + bias).astype(jnp.uint32))


def counter_init(num_windows: int, capacity: int,
                 pool_capacity: int | None = None,
                 widths: tuple = DEFAULT_WIDTHS) -> PackedCounterState:
    n = num_windows * capacity
    P = pool_capacity if pool_capacity is not None else max(64, n // 16)
    return PackedCounterState(
        base=jnp.full(n, _neutral_base(widths), jnp.uint64),
        sq=jnp.zeros(n, jnp.int64),
        minmax=jnp.full(n, _MM_NEUTRAL, jnp.uint32),
        pool_cnt=jnp.zeros(P, jnp.int64),
        pool_sum=jnp.zeros(P, jnp.int64),
        pool_sq=jnp.zeros(P, jnp.int64),
        pool_min=jnp.full(P, I64_MAX, jnp.int64),
        pool_max=jnp.full(P, I64_MIN, jnp.int64),
        pool_owner=jnp.full(P, -1, jnp.int32),
        pool_idx=jnp.full(n, -1, jnp.int32),
        pool_n=jnp.int32(0),
        err=jnp.int32(0),
        last_at=jnp.zeros(capacity, jnp.int64),
    )


def _counter_scan_lanes(v: jnp.ndarray):
    """Scan input lanes for a sorted counter value column: (count, sum,
    sum_sq, wide count, min, max).  The i64 adds wrap mod 2^64 — the
    scatter path's accumulate, in another (immaterial) order."""
    wide = (v < jnp.int64(_MM_LO)) | (v > jnp.int64(_MM_HI))
    return (jnp.ones_like(v), v, v * v, wide.astype(jnp.int64), v, v)


def _counter_scan_combine(a, b):
    return (a[0] + b[0], a[1] + b[1], a[2] + b[2], a[3] + b[3],
            jnp.minimum(a[4], b[4]), jnp.maximum(a[5], b[5]))


def _counter_merge(state: PackedCounterState, seg: _Segments, scanned,
                   last_at, wc: int, widths: tuple, promote_k: int):
    """Merge the batch's per-segment aggregates (at the tail rows) into
    the packed counter state, with bounded-K overflow-pool promotion:
    every flag, index and delta below is a lane over the N rows."""
    cb, sb = widths
    d_cnt, d_sum, d_sq, d_wide, d_min, d_max = scanned
    K = min(promote_k, wc)
    P = state.pool_cnt.shape[0]
    live, at, put = _tail_rows(seg, wc)

    b_cnt, b_sum = _unpack_base(state.base[at], widths)
    b_min, b_max = _unpack_minmax(state.minmax[at])
    # a slot with no base samples holds the int16 NEUTRAL sentinels
    # (32767/-32768) — mask them to the true identities before merging,
    # or a virgin slot promoting on an all-wide first batch would seed
    # its pool row with the sentinel as an "observed" min/max
    b_min = jnp.where(b_cnt > 0, b_min, I64_MAX)
    b_max = jnp.where(b_cnt > 0, b_max, I64_MIN)
    n_cnt = b_cnt + d_cnt
    n_sum = b_sum + d_sum
    n_sq = state.sq[at] + d_sq  # full-width column: never a promote trigger
    n_min = jnp.minimum(b_min, d_min)
    n_max = jnp.maximum(b_max, d_max)

    row_pid = state.pool_idx[at]
    promoted = row_pid >= 0
    lane_over = ((n_cnt >= jnp.int64(1 << cb))
                 | (n_sum >= jnp.int64(1 << (sb - 1)))
                 | (n_sum < jnp.int64(-(1 << (sb - 1))))
                 | (d_wide > 0))
    to_pool = live & ~promoted & lane_over
    active = live & promoted

    def with_pool(op):
        (pool_cnt, pool_sum, pool_sq, pool_min, pool_max, pool_owner,
         pool_idx, pool_n, err) = op
        # each promoting / active row's rank among its kind, in row
        # (= flat slot) order; a batch serves the first K of either
        new_rank = jnp.cumsum(to_pool.astype(jnp.int32)) - 1
        act_rank = jnp.cumsum(active.astype(jnp.int32)) - 1
        valid = to_pool & (new_rank < K)
        # Allocate from FREE rows (owner < 0): the scan over P reuses
        # rows released by clear_slots, so slot churn cannot
        # permanently exhaust the pool the way a bump pointer did.  A
        # candidate with no free row left keeps pool_idx == -1 (its
        # base lanes clip — flagged by err, but never aliased onto
        # another slot's pool row).
        # (the first K free rows by an i32 cumsum and one scatter:
        # jnp.nonzero's 64-bit bincount cost 4.2 ms at P = 65,536 on a
        # v5e against 0.3, and its cumsum over a K <= 1,024 was refused
        # by the TPU compiler inside this cond; PERF.md, PR 28)
        is_free = pool_owner < 0
        free = jnp.full(K, P, jnp.int32).at[
            jnp.where(is_free, jnp.cumsum(is_free.astype(jnp.int32)) - 1,
                      K)].set(jnp.arange(P, dtype=jnp.int32), mode="drop")
        pids = jnp.where(valid, free[jnp.clip(new_rank, 0, K - 1)],
                         jnp.int32(P))
        take = pids < P
        pool_idx = pool_idx.at[jnp.where(take, seg.sslot, wc)].set(
            pids, mode="drop")
        pool_owner = pool_owner.at[pids].set(seg.sslot, mode="drop")
        pool_cnt = pool_cnt.at[pids].set(n_cnt, mode="drop")
        pool_sum = pool_sum.at[pids].set(n_sum, mode="drop")
        pool_sq = pool_sq.at[pids].set(n_sq, mode="drop")
        pool_min = pool_min.at[pids].set(n_min, mode="drop")
        pool_max = pool_max.at[pids].set(n_max, mode="drop")
        # already-promoted slots with batch data: add deltas to rows
        pid_a = jnp.where(active & (act_rank < K), row_pid, jnp.int32(P))
        pool_cnt = pool_cnt.at[pid_a].add(d_cnt, mode="drop")
        pool_sum = pool_sum.at[pid_a].add(d_sum, mode="drop")
        pool_sq = pool_sq.at[pid_a].add(d_sq, mode="drop")
        pool_min = pool_min.at[pid_a].min(d_min, mode="drop")
        pool_max = pool_max.at[pid_a].max(d_max, mode="drop")
        over_k = (new_rank[-1] >= K) | (act_rank[-1] >= K)
        err = err | jnp.where(over_k, _ERR_PROMOTE_K, 0)
        err = err | jnp.where((valid & ~take).any(), _ERR_POOL_FULL, 0)
        pool_n = (pool_owner >= 0).sum().astype(jnp.int32)
        return (pool_cnt, pool_sum, pool_sq, pool_min, pool_max,
                pool_owner, pool_idx, pool_n, err.astype(jnp.int32))

    pool_ops = (state.pool_cnt, state.pool_sum, state.pool_sq,
                state.pool_min, state.pool_max, state.pool_owner,
                state.pool_idx, state.pool_n, state.err)
    (pool_cnt, pool_sum, pool_sq, pool_min, pool_max, pool_owner,
     pool_idx, pool_n, err) = jax.lax.cond(
        to_pool.any() | active.any(), with_pool, lambda op: op, pool_ops)

    # pooled slots keep neutral base lanes (an untouched one already
    # does); the rest of the touched slots repack
    in_pool = pool_idx[at] >= 0
    base = jnp.where(
        in_pool, jnp.uint64(_neutral_base(widths)),
        _pack_base(jnp.clip(n_cnt, 0, (1 << cb) - 1),
                   jnp.clip(n_sum, -(1 << (sb - 1)), (1 << (sb - 1)) - 1),
                   widths))
    minmax = jnp.where(
        in_pool, jnp.uint32(_MM_NEUTRAL),
        _pack_minmax(jnp.clip(n_min, _MM_LO, _MM_HI),
                     jnp.clip(n_max, _MM_LO, _MM_HI)))

    return PackedCounterState(
        base=put(state.base, base),
        sq=put(state.sq, jnp.where(in_pool, jnp.int64(0), n_sq)),
        minmax=put(state.minmax, minmax),
        pool_cnt=pool_cnt, pool_sum=pool_sum, pool_sq=pool_sq,
        pool_min=pool_min, pool_max=pool_max, pool_owner=pool_owner,
        pool_idx=pool_idx, pool_n=pool_n, err=err, last_at=last_at)


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("num_windows", "capacity", "widths", "promote_k"))
def counter_ingest(
    state: PackedCounterState,
    idx: jnp.ndarray,     # i64 (N,) flat window*C+slot; == W*C+C drops
    values: jnp.ndarray,  # i64 (N,)
    times: jnp.ndarray,   # i64 (N,)
    num_windows: int,
    capacity: int,
    widths: tuple = DEFAULT_WIDTHS,
    promote_k: int = PROMOTE_K,
) -> PackedCounterState:
    wc = num_windows * capacity
    seg = _segment_view(idx, wc + capacity)
    scanned = _seg_scan(seg, _counter_scan_lanes(values[seg.perm]),
                        _counter_scan_combine)
    last_at = _merge_last_at(state.last_at, idx, times, wc)
    return _counter_merge(state, seg, scanned, last_at, wc, widths,
                          promote_k)


def _counter_lanes(state: PackedCounterState, widths: tuple):
    """Dense (W*C,) full-precision stat lanes merging base and pool."""
    b_cnt, b_sum = _unpack_base(state.base, widths)
    b_min, b_max = _unpack_minmax(state.minmax)
    in_pool = state.pool_idx >= 0
    P = state.pool_cnt.shape[0]
    pidx = jnp.clip(state.pool_idx, 0, P - 1)
    cnt = jnp.where(in_pool, state.pool_cnt[pidx], b_cnt)
    s = jnp.where(in_pool, state.pool_sum[pidx], b_sum)
    sq = jnp.where(in_pool, state.pool_sq[pidx], state.sq)
    mn = jnp.where(in_pool, state.pool_min[pidx],
                   jnp.where(b_cnt > 0, b_min, I64_MAX))
    mx = jnp.where(in_pool, state.pool_max[pidx],
                   jnp.where(b_cnt > 0, b_max, I64_MIN))
    return cnt, s, sq, mn, mx


@functools.partial(jax.jit, static_argnames=("capacity", "widths"))
def counter_consume(state: PackedCounterState, window: jnp.ndarray,
                    capacity: int, widths: tuple = DEFAULT_WIDTHS):
    cnt_a, s_a, sq_a, mn_a, mx_a = _counter_lanes(state, widths)
    off = window * capacity
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, capacity)
    cnt = sl(cnt_a)
    s = sl(s_a).astype(jnp.float64)
    ssq = sl(sq_a).astype(jnp.float64)
    cntf = cnt.astype(jnp.float64)
    mean = jnp.where(cnt == 0, 0.0, s / jnp.where(cnt == 0, 1, cnt))
    lanes = jnp.stack(
        [
            jnp.full(capacity, jnp.nan, jnp.float64),  # LAST
            jnp.where(cnt == 0, 0.0, sl(mn_a).astype(jnp.float64)),
            jnp.where(cnt == 0, 0.0, sl(mx_a).astype(jnp.float64)),
            mean,
            cntf,
            s,
            ssq,
            _stdev(cntf, ssq, s),
        ],
        axis=1,
    )
    return lanes, cnt


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("num_windows", "capacity", "widths"))
def counter_reset_window(state: PackedCounterState, window: jnp.ndarray,
                         num_windows: int, capacity: int,
                         widths: tuple = DEFAULT_WIDTHS
                         ) -> PackedCounterState:
    off = window * capacity
    upd = lambda a, v: jax.lax.dynamic_update_slice_in_dim(
        a, jnp.full(capacity, v, a.dtype), off, 0)
    # pool rows owned by this window reset densely over P (no scatter)
    own_w = jnp.where(state.pool_owner >= 0,
                      state.pool_owner // capacity, -1)
    hit = own_w == window.astype(jnp.int32)
    return state._replace(
        base=upd(state.base, _neutral_base(widths)),
        sq=upd(state.sq, 0),
        minmax=upd(state.minmax, _MM_NEUTRAL),
        pool_cnt=jnp.where(hit, jnp.int64(0), state.pool_cnt),
        pool_sum=jnp.where(hit, jnp.int64(0), state.pool_sum),
        pool_sq=jnp.where(hit, jnp.int64(0), state.pool_sq),
        pool_min=jnp.where(hit, I64_MAX, state.pool_min),
        pool_max=jnp.where(hit, I64_MIN, state.pool_max),
    )


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("num_windows", "capacity", "widths"))
def counter_clear_slots(state: PackedCounterState, slots: jnp.ndarray,
                        num_windows: int, capacity: int,
                        widths: tuple = DEFAULT_WIDTHS
                        ) -> PackedCounterState:
    idx = (jnp.arange(num_windows, dtype=jnp.int64)[:, None] * capacity
           + slots[None, :]).ravel()
    idx = jnp.where(
        (slots[None, :] >= capacity).repeat(num_windows, 0).ravel(),
        num_windows * capacity, idx)
    # pool rows whose owner slot is cleared are RELEASED (owner -1)
    # via a sorted membership probe — the free-list allocator in
    # _counter_merge reuses them, so recycling slots can't leak the
    # pool dry (slots is small and host-sorted by pad_slots' caller;
    # sort again defensively)
    sorted_slots = jnp.sort(slots.astype(jnp.int32))
    own_slot = jnp.where(state.pool_owner >= 0,
                         state.pool_owner % capacity, -1)
    pos = jnp.clip(jnp.searchsorted(sorted_slots, own_slot), 0,
                   sorted_slots.shape[0] - 1)
    hit = (sorted_slots[pos] == own_slot) & (state.pool_owner >= 0)
    pool_owner = jnp.where(hit, jnp.int32(-1), state.pool_owner)
    return state._replace(
        base=state.base.at[idx].set(_neutral_base(widths), mode="drop"),
        sq=state.sq.at[idx].set(0, mode="drop"),
        minmax=state.minmax.at[idx].set(_MM_NEUTRAL, mode="drop"),
        pool_cnt=jnp.where(hit, jnp.int64(0), state.pool_cnt),
        pool_sum=jnp.where(hit, jnp.int64(0), state.pool_sum),
        pool_sq=jnp.where(hit, jnp.int64(0), state.pool_sq),
        pool_min=jnp.where(hit, I64_MAX, state.pool_min),
        pool_max=jnp.where(hit, I64_MIN, state.pool_max),
        pool_owner=pool_owner,
        pool_idx=state.pool_idx.at[idx].set(-1, mode="drop"),
        pool_n=(pool_owner >= 0).sum().astype(jnp.int32),
        last_at=state.last_at.at[slots].set(0, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Packed gauge arena (sort-formulation ingest).  sum/sum_sq are computed
# in the device's f64; min/max/last are SELECTED, never computed on, and
# ride as order-preserving i64 keys of the f64 bit pattern so that they
# leave the arena with the bits they came in with on any backend (an
# accelerator's f64 is not IEEE: a TPU carries it as an f32 pair — ~48
# mantissa bits, f32 exponent range — and has no f64<->i64 bitcast, so
# the keys are made and unmade on the host).
# ---------------------------------------------------------------------------

_KEY_FLIP = np.int64(0x7FFFFFFFFFFFFFFF)
KEY_PINF = 0x7FF0000000000000    # orderable_f64(+inf): min identity
KEY_NINF = -0x7FF0000000000001   # orderable_f64(-inf): max identity
KEY_NAN = 0x7FF8000000000000     # orderable_f64(nan)


def orderable_f64(values) -> np.ndarray:
    """Host: f64 -> i64 keys whose integer order is the float order
    (-0.0 < +0.0; NaNs sort outside [-inf, +inf])."""
    b = np.ascontiguousarray(values, np.float64).view(np.int64)
    return b ^ ((b >> 63) & _KEY_FLIP)


def decode_orderable_f64(keys) -> np.ndarray:
    """Host: the inverse of orderable_f64 (the map is an involution)."""
    k = np.ascontiguousarray(keys, np.int64)
    return (k ^ ((k >> 63) & _KEY_FLIP)).view(np.float64)


class PackedGaugeState(NamedTuple):
    sum: jnp.ndarray        # f64 (W*C,)
    sum_sq: jnp.ndarray     # f64
    count: jnp.ndarray      # i64
    min_key: jnp.ndarray    # i64 orderable_f64, identity KEY_PINF
    max_key: jnp.ndarray    # i64 orderable_f64, identity KEY_NINF
    last_key: jnp.ndarray   # i64 orderable_f64 of the value at last_time
    last_time: jnp.ndarray  # i64
    last_at: jnp.ndarray    # i64 (C,)


def gauge_init(num_windows: int, capacity: int) -> PackedGaugeState:
    n = num_windows * capacity
    return PackedGaugeState(
        sum=jnp.zeros(n, jnp.float64),
        sum_sq=jnp.zeros(n, jnp.float64),
        count=jnp.zeros(n, jnp.int64),
        min_key=jnp.full(n, KEY_PINF, jnp.int64),
        max_key=jnp.full(n, KEY_NINF, jnp.int64),
        last_key=jnp.zeros(n, jnp.int64),
        last_time=jnp.zeros(n, jnp.int64),
        last_at=jnp.zeros(capacity, jnp.int64),
    )


def _gauge_scan_lanes(v: jnp.ndarray, k: jnp.ndarray, t: jnp.ndarray):
    """Scan input lanes for a gauge value column and its keys: (count,
    sum, sum_sq, min, max, tmax, last).  Sum lanes exclude NaN (count
    still carries it) but pass +/-inf through — tree-order f64 addition
    reproduces the scatter path's inf/NaN semantics natively and keeps
    the within-segment rounding at ~log2(N) ulps of the segment's own
    magnitude (no cross-segment prefix cancellation)."""
    nan = (k > KEY_PINF) | (k < KEY_NINF)
    safe = jnp.where(nan, 0.0, v)
    return (jnp.ones_like(t), safe, safe * safe,
            jnp.where(nan, KEY_PINF, k), jnp.where(nan, KEY_NINF, k), t, k)


def _gauge_scan_combine(a, b):
    """(count, sum, sum_sq, min, max, tmax, last) segmented combine;
    last is the value of the strictly-greatest time (sorted ties =
    first arrival wins)."""
    return (
        a[0] + b[0],
        a[1] + b[1],
        a[2] + b[2],
        jnp.minimum(a[3], b[3]),
        jnp.maximum(a[4], b[4]),
        jnp.maximum(a[5], b[5]),
        jnp.where(b[5] > a[5], b[6], a[6]),
    )


def _gauge_merge(state: PackedGaugeState, seg: _Segments, scanned,
                 last_at, wc: int) -> PackedGaugeState:
    d_cnt, d_sum, d_sq, d_min, d_max, d_t, d_lastk = scanned
    _live, at, put = _tail_rows(seg, wc)
    last_time = state.last_time[at]
    replace = d_t > last_time
    return PackedGaugeState(
        sum=put(state.sum, state.sum[at] + d_sum),
        sum_sq=put(state.sum_sq, state.sum_sq[at] + d_sq),
        count=put(state.count, state.count[at] + d_cnt),
        min_key=put(state.min_key, jnp.minimum(state.min_key[at], d_min)),
        max_key=put(state.max_key, jnp.maximum(state.max_key[at], d_max)),
        last_key=put(state.last_key,
                     jnp.where(replace, d_lastk, state.last_key[at])),
        last_time=put(state.last_time,
                      jnp.where(replace, d_t, last_time)),
        last_at=last_at,
    )


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("num_windows", "capacity"))
def gauge_ingest(
    state: PackedGaugeState,
    idx: jnp.ndarray,     # i64 (N,) flat; == W*C+C drops
    values: jnp.ndarray,  # f64 (N,)
    keys: jnp.ndarray,    # i64 (N,) orderable_f64(values), host-made
    times: jnp.ndarray,   # i64 (N,)
    num_windows: int,
    capacity: int,
) -> PackedGaugeState:
    wc = num_windows * capacity
    seg = _segment_view(idx, wc + capacity)
    scanned = _seg_scan(
        seg, _gauge_scan_lanes(values[seg.perm], keys[seg.perm],
                               times[seg.perm]),
        _gauge_scan_combine)
    last_at = _merge_last_at(state.last_at, idx, times, wc)
    return _gauge_merge(state, seg, scanned, last_at, wc)


@functools.partial(jax.jit, static_argnames=("capacity",))
def gauge_consume(state: PackedGaugeState, window: jnp.ndarray,
                  capacity: int):
    """(computed (C, 5) f64, exact (C, 4) i64): the lanes the device
    computed — SCALAR_LANES[3:], mean / count / sum / sum_sq / stdev —
    and the ones it only counted or selected — count, then the
    orderable_f64 keys of last / min / max.  ``gauge_lanes`` joins them
    on the host into the arenas' common (lanes (C, 8), counts (C,))."""
    off = window * capacity
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, capacity)
    s, ssq, cnt = sl(state.sum), sl(state.sum_sq), sl(state.count)
    cntf = cnt.astype(jnp.float64)
    mean = jnp.where(cnt == 0, 0.0, s / jnp.where(cnt == 0, 1, cnt))
    computed = jnp.stack([mean, cntf, s, ssq, _stdev(cntf, ssq, s)], axis=1)
    # an empty slot's min/max (and, as in the scatter arenas, an
    # infinite one) reads NaN
    inf_to_nan = lambda k: jnp.where(
        (k == KEY_PINF) | (k == KEY_NINF), KEY_NAN, k)
    exact = jnp.stack([cnt, sl(state.last_key),
                       inf_to_nan(sl(state.min_key)),
                       inf_to_nan(sl(state.max_key))], axis=1)
    return computed, exact


def gauge_lanes(computed, exact):
    """Host: a gauge_consume result (leading axes allowed) as the
    arenas' common (lanes (..., C, 8) f64 in SCALAR_LANES order, counts
    (..., C) i64), LAST / MIN / MAX decoded from their keys."""
    exact = np.asarray(exact)
    lanes = np.concatenate(
        [decode_orderable_f64(exact[..., 1:]), np.asarray(computed)], axis=-1)
    return lanes, exact[..., 0]


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def gauge_reset_window(state: PackedGaugeState, window: jnp.ndarray,
                       capacity: int) -> PackedGaugeState:
    off = window * capacity
    upd = lambda a, v: jax.lax.dynamic_update_slice_in_dim(
        a, jnp.full(capacity, v, a.dtype), off, 0)
    return state._replace(
        sum=upd(state.sum, 0.0),
        sum_sq=upd(state.sum_sq, 0.0),
        count=upd(state.count, 0),
        min_key=upd(state.min_key, KEY_PINF),
        max_key=upd(state.max_key, KEY_NINF),
        last_key=upd(state.last_key, 0),
        last_time=upd(state.last_time, 0),
    )


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("num_windows", "capacity"))
def gauge_clear_slots(state: PackedGaugeState, slots: jnp.ndarray,
                      num_windows: int, capacity: int) -> PackedGaugeState:
    idx = (jnp.arange(num_windows, dtype=jnp.int64)[:, None] * capacity
           + slots[None, :]).ravel()
    idx = jnp.where(
        (slots[None, :] >= capacity).repeat(num_windows, 0).ravel(),
        num_windows * capacity, idx)
    return state._replace(
        sum=state.sum.at[idx].set(0.0, mode="drop"),
        sum_sq=state.sum_sq.at[idx].set(0.0, mode="drop"),
        count=state.count.at[idx].set(0, mode="drop"),
        min_key=state.min_key.at[idx].set(KEY_PINF, mode="drop"),
        max_key=state.max_key.at[idx].set(KEY_NINF, mode="drop"),
        last_key=state.last_key.at[idx].set(0, mode="drop"),
        last_time=state.last_time.at[idx].set(0, mode="drop"),
        last_at=state.last_at.at[slots].set(0, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Fused counter+gauge rollup ingest (one sort serves both arenas — the
# sharded step / bench shape, where one routed batch feeds every type)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, donate_argnums=(0, 1),
    static_argnames=("num_windows", "capacity", "widths", "promote_k"))
def rollup_ingest(
    cstate: PackedCounterState,
    gstate: PackedGaugeState,
    idx: jnp.ndarray,      # i64 (N,) flat; == W*C+C drops
    cvalues: jnp.ndarray,  # i64 (N,)
    gvalues: jnp.ndarray,  # f64 (N,)
    gkeys: jnp.ndarray,    # i64 (N,) orderable_f64(gvalues), host-made
    times: jnp.ndarray,    # i64 (N,)
    num_windows: int,
    capacity: int,
    widths: tuple = DEFAULT_WIDTHS,
    promote_k: int = PROMOTE_K,
):
    wc = num_windows * capacity
    seg = _segment_view(idx, wc + capacity)
    c_lanes = _counter_scan_lanes(cvalues[seg.perm])
    g_lanes = _gauge_scan_lanes(gvalues[seg.perm], gkeys[seg.perm],
                                times[seg.perm])
    nc = len(c_lanes)

    # ONE scan serves both arenas: the counter lanes prepended to the
    # gauge lane set
    def combine(a, b):
        return (_counter_scan_combine(a[:nc], b[:nc])
                + _gauge_scan_combine(a[nc:], b[nc:]))

    scanned = _seg_scan(seg, c_lanes + g_lanes, combine)
    return (_counter_merge(cstate, seg, scanned[:nc],
                           _merge_last_at(cstate.last_at, idx, times, wc),
                           wc, widths, promote_k),
            _gauge_merge(gstate, seg, scanned[nc:],
                         _merge_last_at(gstate.last_at, idx, times, wc), wc))


# ---------------------------------------------------------------------------
# Packed timer arena: u64 sample words, moments recovered at drain
# ---------------------------------------------------------------------------


class PackedTimerState(NamedTuple):
    sample: jnp.ndarray    # u64 (W, S) slot<<32 | orderable_f32(value)
    sample_n: jnp.ndarray  # i64 (W,) write offsets (> S = overflow)
    last_at: jnp.ndarray   # i64 (C,)


def _timer_empty_word(capacity: int) -> int:
    """The empty-sentinel sample word: slot == capacity sorts past every
    real slot (python int: safe under the tracer)."""
    return capacity << 32


def timer_init(num_windows: int, capacity: int,
               sample_capacity: int) -> PackedTimerState:
    empty = _timer_empty_word(capacity)
    return PackedTimerState(
        sample=jnp.full((num_windows, sample_capacity), empty, jnp.uint64),
        sample_n=jnp.zeros(num_windows, jnp.int64),
        last_at=jnp.zeros(capacity, jnp.int64),
    )


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def timer_ingest(
    state: PackedTimerState,
    windows: jnp.ndarray,  # i32 (N,) ring index; OOB drops
    slots: jnp.ndarray,    # i32 (N,)
    values: jnp.ndarray,   # f64 (N,)
    times: jnp.ndarray,    # i64 (N,)
    capacity: int,
) -> PackedTimerState:
    """Append a batch as packed words — ONE scatter.  Moments are
    recovered at drain from the sorted buffer, so the only other work
    is the shared append plan (arena.timer_append_plan) and the
    last_at expiry column."""
    num_w, scap = state.sample.shape
    _drop, flat, per_w_counts = timer_append_plan(
        windows, slots, state.sample_n, capacity, scap)
    word = (slots.astype(jnp.uint64) << jnp.uint64(32)) | orderable_f32(
        values)
    slot_safe = _sanitize_slots(slots, capacity)
    return PackedTimerState(
        sample=state.sample.ravel().at[flat].set(
            word, mode="drop").reshape(num_w, scap),
        sample_n=state.sample_n + per_w_counts,
        last_at=state.last_at.at[slot_safe].max(times, mode="drop"),
    )


@functools.partial(jax.jit, static_argnames=("capacity", "quantiles"))
def timer_consume(
    state: PackedTimerState,
    window: jnp.ndarray,
    moments: jnp.ndarray,
    capacity: int,
    quantiles: tuple,
):
    """Drain one window: sort the packed words (slot asc, value asc in
    f32 order), then counts from boundaries, min/max/quantiles from rank
    positions, and sum/sum_sq from a sorted segment sum of the decoded
    values (f32 value precision — the packed32 1e-6 envelope).

    The segment sums run only where the window buffered something and
    ``moments`` (a traced bool[]: the drain's caller says whether any
    slot asks for MEAN, SUM, SUM_SQ or STDEV) is set; otherwise those
    four lanes are zeros.  Traced, not static: a map whose masks change
    between drains runs the same program."""
    num_w, scap = state.sample.shape
    words = jax.lax.dynamic_index_in_dim(state.sample, window,
                                         keepdims=False)
    keys = jax.lax.sort(words)
    s_slot = (keys >> jnp.uint64(32)).astype(jnp.int32)
    s_val = decode_orderable_f32(keys & jnp.uint64(0xFFFFFFFF))

    qs = jnp.arange(capacity, dtype=jnp.int32)
    seg_start = jnp.searchsorted(s_slot, qs)
    seg_end = jnp.searchsorted(s_slot, qs, side="right")
    seg_n = (seg_end - seg_start).astype(jnp.int64)
    empty = seg_n == 0

    # Moments by one sorted segment sum a lane over the sorted words:
    # f64 adds in ascending value order within a slot (rounding stays
    # at ~n ulps of the segment's own magnitude), real non-finite
    # samples flow through with the f64 semantics (inf sums, NaN
    # poisons).  Empty-sentinel words decode to NaN: they are zeroed
    # and land in the spare row `capacity`.  (A three-lane segmented
    # associative_scan stood here until PR 31: compiled for a v5e it
    # took the TPU compiler 11 minutes over 2^21 words and 22 s over
    # 2^17; the segment sums compile in seconds at any size.)
    #
    # Two things here are the chip's doing (v5e, PERF.md PR 31):
    # - the moments decode slot and value from the keys THEMSELVES,
    #   inside the branch, and share neither `s_slot` nor `s_val` with
    #   the searches and rank gathers: a column that is also a scatter's
    #   (or a conditional's) operand stays in HBM, and both binary
    #   searches then pay 27 ns a gather for 7.5 (32 ms a search for
    #   8.9 at 2^18 words, empty or not);
    # - a window that buffered nothing skips them: the scatters cost
    #   every word of the buffer, sentinels included (39 ms at 2^18),
    #   and the coordinator's downsampler drains an empty timer buffer
    #   in every pass.  So does a drain whose slots ask for no moment
    #   (a P50/P95/P99 deployment): ~640 of 777 ms at 2^22 words.
    def segment_moments(keys):
        slot = (keys >> jnp.uint64(32)).astype(jnp.int32)
        val = decode_orderable_f32(keys & jnp.uint64(0xFFFFFFFF))
        v = jnp.where(slot < capacity, val, 0.0)
        seg = jnp.minimum(slot, capacity)
        return tuple(
            jax.ops.segment_sum(x, seg, num_segments=capacity + 1,
                                indices_are_sorted=True)[:capacity]
            for x in (v, v * v))

    zeros = jnp.zeros(capacity, jnp.float64)
    s, ssq = jax.lax.cond(
        (jax.lax.dynamic_index_in_dim(state.sample_n, window,
                                      keepdims=False) > 0) & moments,
        segment_moments, lambda keys: (zeros, zeros), keys)
    cntf = seg_n.astype(jnp.float64)
    mean = jnp.where(empty, 0.0, s / jnp.where(empty, 1.0, cntf))

    mn = jnp.where(empty, 0.0, s_val[jnp.clip(seg_start, 0, scap - 1)])
    mx = jnp.where(empty, 0.0, s_val[jnp.clip(seg_end - 1, 0, scap - 1)])

    qlanes = []
    for q in quantiles:
        ranks = jnp.ceil(q * cntf).astype(jnp.int64) - 1
        ranks = jnp.clip(ranks, 0, jnp.maximum(seg_n - 1, 0))
        qv = s_val[jnp.clip(seg_start + ranks, 0, scap - 1)]
        qlanes.append(jnp.where(empty, 0.0, qv))

    lanes = jnp.stack(
        [
            jnp.full(capacity, jnp.nan, jnp.float64),  # LAST
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
    return lanes, seg_n


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("capacity",))
def timer_reset_window(state: PackedTimerState, window: jnp.ndarray,
                       capacity: int) -> PackedTimerState:
    num_w, scap = state.sample.shape
    empty = _timer_empty_word(capacity)
    return PackedTimerState(
        sample=jax.lax.dynamic_update_slice(
            state.sample,
            jnp.full((1, scap), empty, jnp.uint64),
            (window.astype(jnp.int32), jnp.int32(0)),
        ),
        sample_n=state.sample_n.at[window].set(0),
        last_at=state.last_at,
    )


@functools.partial(
    jax.jit, donate_argnums=0,
    static_argnames=("num_windows", "capacity"))
def timer_clear_slots(state: PackedTimerState, slots: jnp.ndarray,
                      num_windows: int, capacity: int) -> PackedTimerState:
    """Retarget cleared slots' buffered words to the empty sentinel so a
    recycled slot's quantiles can't include the previous occupant."""
    empty = jnp.uint64(_timer_empty_word(capacity))
    sorted_slots = jnp.sort(slots.astype(jnp.int32))
    flat = state.sample.ravel()
    wslot = (flat >> jnp.uint64(32)).astype(jnp.int32)
    pos = jnp.clip(jnp.searchsorted(sorted_slots, wslot), 0,
                   sorted_slots.shape[0] - 1)
    hit = sorted_slots[pos] == wslot
    return PackedTimerState(
        sample=jnp.where(hit, empty, flat).reshape(state.sample.shape),
        sample_n=state.sample_n,
        last_at=state.last_at.at[slots].set(0, mode="drop"),
    )


# ---------------------------------------------------------------------------
# Host wrappers (drop-in for arena.CounterArena / GaugeArena / TimerArena)
# ---------------------------------------------------------------------------


class PackedCounterArena(_ScalarLanesMixin):
    """Packed counter slots: adaptive-width base + overflow pool."""

    layout = "packed"

    def __init__(self, num_windows: int, capacity: int,
                 pool_capacity: int | None = None,
                 widths: tuple = DEFAULT_WIDTHS,
                 promote_k: int = PROMOTE_K):
        self.num_windows = num_windows
        self.capacity = capacity
        self.widths = tuple(widths)
        self.promote_k = promote_k
        self._mem = membudget.reserve(
            "aggregator.counter",
            membudget.counter_arena_bytes("packed", num_windows, capacity,
                                          pool_capacity),
            owner=self)
        self.state = counter_init(num_windows, capacity, pool_capacity,
                                  self.widths)

    def _check_err(self):
        err = int(self.state.err)
        if err:
            what = []
            if err & _ERR_PROMOTE_K:
                what.append(f"more than promote_k={self.promote_k} pool "
                            "promotions/updates in one batch")
            if err & _ERR_POOL_FULL:
                what.append("overflow pool exhausted")
            # Raise ONCE, then clear: the flag marks stats since the
            # last check as unreliable; the window ring's drain+reset
            # cycle washes the clipped rows out within W drains, so a
            # transient burst must not wedge every later flush forever.
            # A recurring condition re-sets the flag and raises again.
            self.state = self.state._replace(err=jnp.int32(0))
            # DeviceStateError (a RuntimeError): resident arena state
            # is unreliable — typed so the device guard's classifier
            # and the engine's degrade paths see it as the state
            # poisoning it is, not a generic crash.
            raise devguard.DeviceStateError(
                "arena.consume",
                "packed counter arena overflow-pool error: "
                + "; ".join(what)
                + " — raise the list's capacity (the pool is max(64, "
                "num_windows * capacity / 16) rows) or send smaller "
                "batches; stats since the previous "
                "consume are unreliable (flag cleared: the window ring "
                "washes the damage out over the next drains)")

    def ingest(self, windows, slots, values, times):
        if len(slots) == 0:  # the segment view needs a row
            return
        idx = packed_flat_index(jnp.asarray(windows), jnp.asarray(slots),
                                self.num_windows, self.capacity)
        self.state = _guarded_ingest(lambda: counter_ingest(
            self.state, idx, jnp.asarray(values).astype(jnp.int64),
            jnp.asarray(times), self.num_windows, self.capacity,
            self.widths, self.promote_k))

    def consume(self, window: int):
        self._check_err()
        return _guarded_consume(lambda: counter_consume(
            self.state, jnp.int32(window), self.capacity, self.widths))

    def reset_window(self, window: int):
        self.state = _guarded_state_op(lambda: counter_reset_window(
            self.state, jnp.int32(window), self.num_windows,
            self.capacity, self.widths))

    def clear_slots(self, slots):
        self.state = _guarded_state_op(lambda: counter_clear_slots(
            self.state,
            jnp.asarray(pad_slots(np.asarray(slots), self.capacity)),
            self.num_windows, self.capacity, self.widths))


class PackedGaugeArena(_ScalarLanesMixin):
    layout = "packed"

    def __init__(self, num_windows: int, capacity: int):
        self.num_windows = num_windows
        self.capacity = capacity
        self._mem = membudget.reserve(
            "aggregator.gauge",
            membudget.gauge_arena_bytes("packed", num_windows, capacity),
            owner=self)
        self.state = gauge_init(num_windows, capacity)

    def ingest(self, windows, slots, values, times):
        if len(slots) == 0:  # the segment view needs a row
            return
        idx = packed_flat_index(jnp.asarray(windows), jnp.asarray(slots),
                                self.num_windows, self.capacity)
        # host f64 in, so that the keys carry the written bits (pass
        # numpy: a value that has been on an accelerator already is its
        # device image)
        values = np.asarray(values, np.float64)
        keys = jnp.asarray(orderable_f64(values))
        self.state = _guarded_ingest(lambda: gauge_ingest(
            self.state, idx, jnp.asarray(values), keys,
            jnp.asarray(times), self.num_windows, self.capacity))

    def consume(self, window: int):
        return gauge_lanes(*_guarded_consume(lambda: gauge_consume(
            self.state, jnp.int32(window), self.capacity)))

    def reset_window(self, window: int):
        self.state = _guarded_state_op(lambda: gauge_reset_window(self.state, jnp.int32(window),
                                        self.capacity))

    def clear_slots(self, slots):
        self.state = _guarded_state_op(lambda: gauge_clear_slots(
            self.state,
            jnp.asarray(pad_slots(np.asarray(slots), self.capacity)),
            self.num_windows, self.capacity))


class PackedTimerArena(_TimerLanesMixin):
    layout = "packed"
    DEFAULT_QUANTILES = (0.5, 0.95, 0.99)

    def __init__(self, num_windows: int, capacity: int,
                 sample_capacity: int,
                 quantiles: tuple = DEFAULT_QUANTILES):
        self.num_windows = num_windows
        self.capacity = capacity
        self.sample_capacity = sample_capacity
        self.quantiles = tuple(quantiles)
        self._mem = membudget.reserve(
            "aggregator.timer",
            membudget.timer_arena_bytes("packed", num_windows, capacity,
                                        sample_capacity),
            owner=self)
        self.state = timer_init(num_windows, capacity, sample_capacity)
        self._sample_n_host = np.zeros(num_windows, np.int64)
        self.grows = 0  # times _grow padded the buffer (a new shape)
        # drains of a non-empty window that ran without the moments
        self.moments_skipped = 0

    def ingest(self, windows, slots, values, times):
        windows_np = np.asarray(windows)
        slots_np = np.asarray(slots)
        in_range = ((windows_np >= 0) & (windows_np < self.num_windows)
                    & (slots_np >= 0) & (slots_np < self.capacity))
        per_w = np.bincount(windows_np[in_range],
                            minlength=self.num_windows)
        # Commit-after-success (the ShardBuffer.write pattern): a
        # _grow budget reject or device failure must leave the shadow
        # mirroring state.sample_n, or every later batch re-rejects.
        new_n = self._sample_n_host + per_w
        needed = int(new_n.max())
        if needed > self.sample_capacity:
            self._grow(needed)
        self.state = _guarded_ingest(lambda: timer_ingest(
            self.state, jnp.asarray(windows_np.astype(np.int32)),
            jnp.asarray(slots_np.astype(np.int32)),
            jnp.asarray(values).astype(jnp.float64),
            jnp.asarray(times), self.capacity))
        self._sample_n_host = new_n

    def _grow(self, needed: int) -> None:
        new_cap = self.sample_capacity
        while new_cap < needed:
            new_cap *= 2
        self._mem.resize(membudget.timer_arena_bytes(
            "packed", self.num_windows, self.capacity, new_cap))
        pad = new_cap - self.sample_capacity
        empty = np.uint64(_timer_empty_word(self.capacity))
        self.state = PackedTimerState(
            sample=jnp.pad(self.state.sample, ((0, 0), (0, pad)),
                           constant_values=empty),
            sample_n=self.state.sample_n,
            last_at=self.state.last_at,
        )
        self.sample_capacity = new_cap
        self.grows += 1

    def consume(self, window: int, moments: bool = True):
        """``moments=False``: MEAN, SUM, SUM_SQ and STDEV come back as
        zeros (no slot asks for them), the other lanes as ever."""
        out = _guarded_consume(lambda: timer_consume(
            self.state, jnp.int32(window), np.bool_(moments),
            self.capacity, self.quantiles))
        if not moments and self._sample_n_host[window] > 0:
            self.moments_skipped += 1
        return out

    def reset_window(self, window: int):
        self.state = _guarded_state_op(lambda: timer_reset_window(self.state, jnp.int32(window),
                                        self.capacity))
        self._sample_n_host[window] = 0

    def clear_slots(self, slots):
        self.state = _guarded_state_op(lambda: timer_clear_slots(
            self.state,
            jnp.asarray(pad_slots(np.asarray(slots), self.capacity)),
            self.num_windows, self.capacity))
