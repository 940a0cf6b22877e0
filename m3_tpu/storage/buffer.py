"""Device series buffer: the in-memory mutable head of every series.

Re-design of the reference's per-series `dbBuffer`
(`src/dbnode/storage/series/buffer.go:221-247` BufferBucketVersions per
block start; `Write` classifies warm/cold vs bufferPast/bufferFuture
`buffer.go:290-413`; `WarmFlush` merges bucket streams `buffer.go:634`).
Instead of an encoder object per (series, block), the whole shard buffers
into a ring of **append logs on device** — one per open block window:

    slot (W, S) i32 | ts (W, S) i64 | val (W, S) u64 | n (W,)

``val`` holds float64 BIT PATTERNS, not f64: the ring only ever moves
values (append, sort payload, transfer), and a TPU keeps an f64 array
as an f32 pair — ~48 mantissa bits, f32 exponent range — so a sample
that crossed the device as f64 came back changed (measured on a v5e:
3292 of 4109 full-mantissa values, 1e300 -> inf).  u64 lanes are exact
everywhere; the host views them back as float64.

Ingest is a single scatter per batch (same layout as the timer sample
arenas).  Seal/flush drains a window with one lex-sort by
(slot, ts, arrival) + last-write-wins dedupe — the analogue of the
reference's bucket-merge at flush, where later writes at the same
timestamp win (buffer.go conflict resolution on merge) — and hands the
host sorted runs ready for the batched M3TSZ encoder.

Out-of-window writes (cold writes / too-late / too-future) never touch the
device: the host routes them to a per-block overflow list, flushed as a
higher fileset volume (the reference's cold flush,
`storage/coldflush.go` + `fs_merge_with_mem.go`).

Device-fault contract (round 12): the two device entry points —
``buffer_append`` on the write path, ``buffer_drain`` on the
seal/snapshot/read path — run behind the ``x.devguard`` seam.  A
classified device failure (XLA OOM, lost device, an over-budget grow
rejected by ``x.membudget``) degrades instead of dropping acked
samples: the append falls back to staging the batch on the SAME host
overflow lists the cold path uses (readable immediately via
``read_sources``, snapshot-covered, merged in by the next cold flush
AFTER the block seals), and the drain falls back to a bit-identical
numpy sort+dedupe of the transferred columns.  The stage breakers
(``storage.buffer_append`` / ``storage.buffer_drain``) trip after
consecutive failures, skip the device entirely while open, and
half-open re-probe it — visible on /metrics and /health like every
other edge.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.x import devguard, membudget


class BufferState(NamedTuple):
    slot: jnp.ndarray  # i32 (W, S); capacity = empty sentinel
    ts: jnp.ndarray  # i64 (W, S)
    val: jnp.ndarray  # u64 (W, S) float64 bit patterns
    n: jnp.ndarray  # i64 (W,)


def buffer_init(num_windows: int, sample_capacity: int, slot_capacity: int) -> BufferState:
    return BufferState(
        slot=jnp.full((num_windows, sample_capacity), slot_capacity, jnp.int32),
        ts=jnp.full((num_windows, sample_capacity), jnp.iinfo(jnp.int64).max, jnp.int64),
        val=jnp.zeros((num_windows, sample_capacity), jnp.uint64),
        n=jnp.zeros(num_windows, jnp.int64),
    )


@functools.partial(jax.jit, donate_argnums=0)
def buffer_append(
    state: BufferState,
    windows: jnp.ndarray,  # i32 (N,) ring row per sample; OOB drops
    slots: jnp.ndarray,  # i32 (N,)
    ts: jnp.ndarray,  # i64 (N,)
    vals: jnp.ndarray,  # u64 (N,) float64 bit patterns
) -> BufferState:
    num_w, scap = state.slot.shape
    n = slots.shape[0]
    oob = (windows < 0) | (windows >= num_w)
    wkey = jnp.where(oob, num_w, windows)
    # Stable sort by window keeps arrival order within each window.
    # Only (key, index) ride the sort and the columns are gathered by
    # the permutation: the TPU compiler's time for a sort grows with
    # every 32-bit lane it carries (26K rows: 77 s with the i64/u64
    # columns as operands, 22 s for key+index), and each new batch
    # size is a new compile.
    s_w, perm = jax.lax.sort(
        (wkey, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    s_slot, s_ts, s_val = slots[perm], ts[perm], vals[perm]
    pos = jnp.arange(n, dtype=jnp.int64)
    rank = pos - jnp.searchsorted(s_w, s_w, side="left")
    base = state.n[jnp.clip(s_w, 0, num_w - 1)]
    dst = base + rank
    flat = jnp.where(
        (s_w < num_w) & (dst < scap), s_w.astype(jnp.int64) * scap + dst, num_w * scap
    )
    per_w = jnp.bincount(wkey, length=num_w)

    def _scatter(ops):
        fslot, fts, fval = ops
        return (fslot.at[flat].set(s_slot, mode="drop"),
                fts.at[flat].set(s_ts, mode="drop"),
                fval.at[flat].set(s_val, mode="drop"))

    flat_slot = state.slot.ravel()
    flat_ts = state.ts.ravel()
    flat_val = state.val.ravel()
    if n > 0 and n <= scap:
        # A batch whose samples ALL target one valid window and fit
        # appends CONTIGUOUSLY at that window's write head: one
        # dynamic_update_slice (memcpy) per column instead of a scatter
        # (~1us/element on TPU — round 5, window 3).  The
        # common dbnode shape: in-order writes land in one warm window
        # of the multi-window ring, so the gate is on the BATCH, not
        # the ring size.
        row = jnp.clip(windows[0], 0, num_w - 1).astype(jnp.int64)
        same = jnp.logical_not(oob.any()) & (windows == windows[0]).all()
        fits = same & (state.n[row] + n <= scap)

        def _dus(ops):
            fslot, fts, fval = ops
            start = row * scap + state.n[row]
            return (
                jax.lax.dynamic_update_slice_in_dim(fslot, s_slot, start, 0),
                jax.lax.dynamic_update_slice_in_dim(fts, s_ts, start, 0),
                jax.lax.dynamic_update_slice_in_dim(fval, s_val, start, 0),
            )

        new_slot, new_ts, new_val = jax.lax.cond(
            fits, _dus, _scatter, (flat_slot, flat_ts, flat_val))
    else:
        new_slot, new_ts, new_val = _scatter((flat_slot, flat_ts, flat_val))
    return BufferState(
        slot=new_slot.reshape(num_w, scap),
        ts=new_ts.reshape(num_w, scap),
        val=new_val.reshape(num_w, scap),
        n=state.n + per_w,
    )


@jax.jit
def buffer_drain(state: BufferState, window: jnp.ndarray):
    """One window -> (slot, ts, val, keep) sorted by (slot, ts).

    keep masks out empty sentinel entries and duplicate (slot, ts) pairs
    — the *last arrival* wins, matching the reference's merge rule where
    a later write at the same timestamp supersedes.
    """
    slot_w = jax.lax.dynamic_index_in_dim(state.slot, window, keepdims=False)
    ts_w = jax.lax.dynamic_index_in_dim(state.ts, window, keepdims=False)
    val_w = jax.lax.dynamic_index_in_dim(state.val, window, keepdims=False)
    scap = slot_w.shape[0]
    # Order by (slot, ts, arrival DESCENDING) — the latest write first
    # within (slot, ts) — as two stable single-key passes over a
    # (key, index) pair, least significant key first, starting from
    # arrival-descending order.  One 3-key sort of the four columns is
    # the same order, but the TPU compiler takes minutes over it (1M
    # rows: 239 s, against 71 s for the two passes; the i64 comparator
    # and every carried lane multiply into each merge stage).
    idx = jnp.arange(scap - 1, -1, -1, dtype=jnp.int32)
    _, idx = jax.lax.sort((ts_w[idx], idx), num_keys=1, is_stable=True)
    s_slot, idx = jax.lax.sort((slot_w[idx], idx), num_keys=1,
                               is_stable=True)
    s_ts, s_val = ts_w[idx], val_w[idx]
    first = jnp.concatenate(
        [jnp.ones(1, bool), (s_slot[1:] != s_slot[:-1]) | (s_ts[1:] != s_ts[:-1])]
    )
    return s_slot, s_ts, s_val, first


def dedupe_last_write_wins(slots: np.ndarray, ts: np.ndarray, vals: np.ndarray):
    """Sort by (slot, ts) and keep the LAST-arriving sample per (slot, ts)
    — the one merge rule every host-side path shares (cold drain,
    snapshot merge), mirroring the device path in `buffer_drain`."""
    arrival = np.arange(len(slots))
    order = np.lexsort((-arrival, ts, slots))
    slots, ts, vals = slots[order], ts[order], vals[order]
    first = np.ones(len(slots), bool)
    first[1:] = (slots[1:] != slots[:-1]) | (ts[1:] != ts[:-1])
    return slots[first], ts[first], vals[first]


class ShardBuffer:
    """Host wrapper owning one shard's buffer ring + overflow lists."""

    def __init__(self, block_size_nanos: int, num_windows: int,
                 sample_capacity: int, slot_capacity: int,
                 snapshot_counters=None):
        self.block_size = block_size_nanos
        self.num_windows = num_windows
        self.sample_capacity = sample_capacity
        self.slot_capacity = slot_capacity
        # Admission before allocation: an over-budget ring rejects
        # typed (DeviceBudgetExceeded) here instead of OOM-ing inside
        # XLA; released automatically when this buffer is collected.
        self._mem = membudget.reserve(
            "storage.buffer",
            membudget.buffer_bytes(num_windows, sample_capacity),
            owner=self)
        self.state = buffer_init(num_windows, sample_capacity, slot_capacity)
        # Warm samples routed to the host overflow lists while the
        # device path is degraded (the buffer_append fallback); counted
        # for /metrics-style visibility through devguard's counters and
        # surfaced per-buffer for tests.
        self.degraded_staged = 0
        self._n_host = np.zeros(num_windows, np.int64)
        # block_start -> ring row for open windows
        self.open_blocks: dict[int, int] = {}
        # block_start -> [(slot, ts, val)] host overflow (cold writes)
        self.cold: dict[int, list] = {}
        # Sorted-window snapshot cache: every read of an open window
        # (single-series, batched verify, snapshot peek) needs the SAME
        # device sort+dedupe of the whole window, which is O(window) —
        # at 1M buffered samples that is ~100ms of sort + a multi-MB
        # device→host transfer PER READ.  One version counter (bumped
        # on any mutation) makes the sorted snapshot reusable: K reads
        # between two writes pay ONE drain + K binary searches.
        self._version = 0
        self._snap: dict[int, tuple] = {}  # block_start -> (version, s, t, v)
        # (hits, misses, stale) of the snapshot cache on /metrics (the
        # Database's `db` scope: buffer_snapshot_*), or None
        self._snap_counters = snapshot_counters

    def _row_for(self, block_start: int) -> int:
        return (block_start // self.block_size) % self.num_windows

    def write(self, slots: np.ndarray, ts: np.ndarray, vals: np.ndarray,
              open_starts: set[int]) -> int:
        """Append a batch.  open_starts = block starts currently accepting
        warm writes (decided by the shard: retention/bufferPast/Future).
        Returns count of samples routed to the cold path."""
        block_starts = (ts // self.block_size) * self.block_size
        warm = np.isin(block_starts, list(open_starts))
        ncold = int((~warm).sum())
        if ncold:
            for bs in np.unique(block_starts[~warm]):
                sel = (~warm) & (block_starts == bs)
                self.cold.setdefault(int(bs), []).append(
                    (slots[sel].copy(), ts[sel].copy(), vals[sel].copy())
                )
        if warm.any():
            wslots, wts, wvals = slots[warm], ts[warm], vals[warm]
            wstarts = block_starts[warm]

            def _device_append():
                self._version += 1  # sorted snapshots are now stale
                rows = ((wstarts // self.block_size)
                        % self.num_windows).astype(np.int32)
                for bs in np.unique(wstarts):
                    self.open_blocks[int(bs)] = self._row_for(int(bs))
                per_row = np.bincount(rows, minlength=self.num_windows)
                if (self._n_host + per_row).max() > self.sample_capacity:
                    self._grow(int((self._n_host + per_row).max()))
                state = buffer_append(
                    self.state,
                    jnp.asarray(rows),
                    jnp.asarray(wslots.astype(np.int32)),
                    jnp.asarray(wts.astype(np.int64)),
                    jnp.asarray(wvals.astype(np.float64).view(np.uint64)),
                )
                self._n_host += per_row
                self.state = state

            def _host_stage():
                # Degraded path: warm samples land on the SAME host
                # overflow lists the cold path owns — acked samples
                # stay readable (read_sources serves the cold lists)
                # and snapshot-covered; cold_flush merges them in only
                # AFTER the block seals (Namespace.tick passes the
                # open-window skip set), so the sealed warm volume is
                # never overwritten by an early degraded flush.
                for bs in np.unique(wstarts):
                    sel = wstarts == bs
                    self.cold.setdefault(int(bs), []).append(
                        (wslots[sel].copy(), wts[sel].copy(),
                         wvals[sel].copy()))
                self.degraded_staged += len(wslots)

            devguard.run_guarded("storage.buffer_append",
                                 _device_append, _host_stage)
        return ncold

    def _grow(self, needed: int) -> None:
        new_cap = self.sample_capacity
        while new_cap < needed:
            new_cap *= 2
        # Admit the growth BEFORE padding: an over-budget grow raises
        # typed inside the guarded append, which degrades this batch to
        # the host staging path instead of OOM-ing in XLA.
        self._mem.resize(membudget.buffer_bytes(self.num_windows, new_cap))
        pad = new_cap - self.sample_capacity
        imax = np.iinfo(np.int64).max
        self.state = BufferState(
            slot=jnp.pad(self.state.slot, ((0, 0), (0, pad)),
                         constant_values=self.slot_capacity),
            ts=jnp.pad(self.state.ts, ((0, 0), (0, pad)), constant_values=imax),
            val=jnp.pad(self.state.val, ((0, 0), (0, pad))),
            n=self.state.n,
        )
        self.sample_capacity = new_cap

    def _drain_row(self, row: int):
        """One window's (slot, ts, val, first) as host arrays, behind
        the ``storage.buffer_drain`` guard: the device sort falls back
        to a bit-identical numpy lexsort of the transferred columns
        when the device path is degraded."""

        def _device():
            s_slot, s_ts, s_val, first = buffer_drain(
                self.state, jnp.int32(row))
            devguard.transfer_point("storage.buffer_drain")
            return (np.asarray(s_slot), np.asarray(s_ts),
                    np.asarray(s_val).view(np.float64), np.asarray(first))

        return devguard.run_guarded("storage.buffer_drain", _device,
                                    lambda: self._host_drain(row))

    def _host_drain(self, row: int):
        """Numpy mirror of :func:`buffer_drain` — same (slot, ts,
        arrival-desc) order, same first mask; the degraded-mode tail."""
        slot_w = np.asarray(self.state.slot)[row]
        ts_w = np.asarray(self.state.ts)[row]
        val_w = np.asarray(self.state.val)[row].view(np.float64)
        arrival = np.arange(len(slot_w))
        order = np.lexsort((-arrival, ts_w, slot_w))
        s_slot, s_ts, s_val = slot_w[order], ts_w[order], val_w[order]
        first = np.ones(len(s_slot), bool)
        first[1:] = (s_slot[1:] != s_slot[:-1]) | (s_ts[1:] != s_ts[:-1])
        return s_slot, s_ts, s_val, first

    def drain(self, block_start: int):
        """Seal one open block: device sort+dedupe, then host-side
        ragged split.  Returns (slots, ts, vals) sorted by (slot, ts)
        with duplicates resolved last-write-wins; clears the window."""
        row = self.open_blocks.pop(block_start, None)
        if row is None:
            return (np.empty(0, np.int32), np.empty(0, np.int64), np.empty(0))
        s_slot, s_ts, s_val, first = self._drain_row(row)
        keep = first & (s_slot < self.slot_capacity)
        out = (s_slot[keep], s_ts[keep], s_val[keep])
        self._reset_row(row)
        return out

    def _reset_row(self, row: int) -> None:
        self._version += 1
        imax = np.iinfo(np.int64).max
        self.state = BufferState(
            slot=self.state.slot.at[row].set(self.slot_capacity),
            ts=self.state.ts.at[row].set(imax),
            val=self.state.val,
            n=self.state.n.at[row].set(0),
        )
        self._n_host[row] = 0

    def discard(self, block_start: int) -> None:
        """Drop one open window WITHOUT the drain sort.  The flush path
        reads via :meth:`peek`, writes the volume, and discards only
        once the write is durably on disk — an ENOSPC mid-write leaves
        the window buffered and readable for the next tick's retry."""
        row = self.open_blocks.pop(block_start, None)
        if row is not None:
            self._reset_row(row)

    def drain_cold(self, block_start: int):
        """Pull the overflow list for one block (sorted, deduped)."""
        parts = self.cold.pop(block_start, None)
        return self._merge_cold(parts)

    def peek_cold(self, block_start: int):
        """Non-destructive :meth:`drain_cold` — pair with
        :meth:`discard_cold` after the merged volume lands on disk."""
        return self._merge_cold(self.cold.get(block_start))

    def discard_cold(self, block_start: int) -> None:
        self.cold.pop(block_start, None)

    @staticmethod
    def _merge_cold(parts):
        if not parts:
            return (np.empty(0, np.int32), np.empty(0, np.int64), np.empty(0))
        slots = np.concatenate([p[0] for p in parts]).astype(np.int32)
        ts = np.concatenate([p[1] for p in parts]).astype(np.int64)
        vals = np.concatenate([p[2] for p in parts]).astype(np.float64)
        return dedupe_last_write_wins(slots, ts, vals)

    def _sorted_window(self, block_start: int):
        """(slots, ts, vals) of one open window, sorted by (slot, ts),
        deduped last-write-wins, sentinel-stripped — served from the
        version-stamped snapshot cache (invalidated by any write/drain)
        so reads between mutations share ONE device sort instead of
        paying O(window) each."""
        row = self.open_blocks.get(block_start)
        if row is None:
            return None
        hit = self._snap.get(block_start)
        counters = self._snap_counters
        if hit is not None and hit[0] == self._version:
            if counters is not None:
                counters[0].inc()
            return hit[1:]
        # a miss: a snapshot of this window at an older version means a
        # mutation since (a write to any window, a drain) made it stale
        stale = int(hit is not None)
        if counters is not None:
            counters[1].inc()
            counters[2].inc(stale)
        with tracing.span(Tracepoint.DB_BUFFER_SNAPSHOT,
                          {"points": int(self._n_host[row]), "stale": stale}):
            s_slot, s_ts, s_val, first = self._drain_row(row)
            keep = first & (s_slot < self.slot_capacity)
            out = (s_slot[keep], s_ts[keep], s_val[keep])
        # one snapshot per OPEN window (reads alternate between open
        # blocks per series — a single-entry cache would thrash back to
        # O(window) per read); closed windows' entries are pruned here
        self._snap = {
            bs: v for bs, v in self._snap.items() if bs in self.open_blocks
        }
        self._snap[block_start] = (self._version,) + out
        return out

    def peek(self, block_start: int):
        """Non-destructive drain of one open window: (slots, ts, vals)
        sorted+deduped, state untouched — the snapshot read
        (reference buffer.go:537 Snapshot streams the open buckets
        without evicting them)."""
        snap = self._sorted_window(block_start)
        if snap is None:
            return (np.empty(0, np.int32), np.empty(0, np.int64), np.empty(0))
        return snap

    def read_window(self, block_start: int, slot: int):
        """Read one series' points from an open (unsealed) block — the
        read path's buffer component (buffer.go:705 ReadEncoded).  A
        binary search over the sorted snapshot: O(log window) per call
        once the snapshot is warm."""
        snap = self._sorted_window(block_start)
        if snap is None:
            return np.empty(0, np.int64), np.empty(0)
        s_slot, s_ts, s_val = snap
        lo, hi = np.searchsorted(s_slot, [slot, slot + 1])
        return s_ts[lo:hi], s_val[lo:hi]
