"""The TSDB engine: database → namespace → shard (→ device buffers).

Structural equivalent of the reference's storage hierarchy
(`src/dbnode/storage/database.go:739 db.Write`, `namespace.go:698`,
`shard.go:867-1008 writeAndIndex`, read `shard.go:1079 ReadEncoded`,
flush orchestration `mediator.go:284 ongoingTick` + `flush.go`), with the
TPU-shaped substitutions:

* per-series encoder objects → one per-shard device append-log ring
  (`storage/buffer.py`) + batched M3TSZ encode at seal time;
* the lock-free series map + insert queue → a host `SlotAllocator`;
* warm flush → `DataFileSetWriter.write_all` of batch-encoded streams;
* cold writes → host overflow lists flushed as higher fileset volumes
  (reference `coldflush.go` + `fs/merger.go`: we merge the existing
  volume's streams with the cold points and write volume+1);
* commit log → WAL appends per ingest batch before buffering.

Reads serve from sealed filesets merged with the open in-memory window —
the same two-source merge the reference does with `series buffer streams`
+ `block retriever` (`shard.go:1079`).  A batch read (`read_columns`,
`read_batch`) decodes a flushed block's segments of all its ids in one
batched device decode per fetch (`Namespace._decode_block`) and keeps no
decoded point afterwards; it holds the engine lock only while it takes
its read plan (segments, slots, buffer snapshots) and decodes, merges
and cuts after the release.  The single-id `read` goes through the
scalar iterator and the block cache's decoded-series LRU, under the
lock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from pathlib import Path

from m3_tpu.core.hash import shard_for as hash_shard_for
from typing import Dict, Iterable, List, NamedTuple, Sequence

import numpy as np

from m3_tpu.core.slots import SlotAllocator
from m3_tpu.index.doc import Document, decode_tags, encode_tags
from m3_tpu.index.namespace_index import NamespaceIndex
from m3_tpu.index.search import Query
from m3_tpu.encoding.m3tsz import decode_series, encode_series
from m3_tpu.encoding.m3tsz_jax import (
    decode_batch_device, encode_batch, last_regfile, pack_streams,
    payload_value_bits,
)
from m3_tpu.persist.commitlog import (
    CommitLogEntry, CommitLogWriter, commitlog_seq, list_commitlogs,
    read_commitlog,
)
from m3_tpu.persist import capacity as cap
from m3_tpu.persist.corruption import CorruptionError
from m3_tpu.persist.fs import (
    DataFileSetReader, DataFileSetWriter, list_fileset_volumes, list_filesets,
    remove_fileset,
)
from m3_tpu.persist import quarantine as quar
from m3_tpu.persist import snapshot as snap
from m3_tpu.instrument import logger
from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.storage.limits import NO_LIMITS, NewSeriesLimiter, QueryLimits
from m3_tpu.storage.buffer import ShardBuffer, dedupe_last_write_wins
from m3_tpu.storage.series_merge import merge_point_sources
from m3_tpu.x import deadline as xdeadline

_LOG = logger("storage.database")


@dataclasses.dataclass(frozen=True)
class NamespaceOptions:
    """Retention/block options (reference `src/dbnode/namespace/options.go`:
    RetentionOptions blockSize/retentionPeriod/bufferPast/bufferFuture)."""

    block_size_nanos: int = 2 * 3600 * 10**9
    retention_nanos: int = 48 * 3600 * 10**9
    buffer_past_nanos: int = 10 * 60 * 10**9
    buffer_future_nanos: int = 2 * 60 * 10**9
    cold_writes_enabled: bool = True
    num_shards: int = 4
    slot_capacity: int = 1 << 17
    sample_capacity: int = 1 << 18


@dataclasses.dataclass(frozen=True)
class DatabaseOptions:
    root: str = "m3tpu_data"
    commitlog_enabled: bool = True
    # Active-segment size bound: the WAL rotates once a segment crosses
    # this many bytes, so cleanup can reclaim fully-flushed segments on
    # nodes whose snapshot cadence (the only other rotation driver) is
    # long.  0 = rotate only on snapshot (the pre-round-20 behavior).
    commitlog_rotate_bytes: int = 64 << 20
    # 0 = unlimited; live-tunable via the write_new_series_limit_per_sec
    # runtime option (reference dbnode/kvconfig/keys.go).
    write_new_series_limit_per_sec: float = 0.0


class ShardNotOwnedError(RuntimeError):
    """A write or read addressed a shard this node does not own under
    the current placement (reference dbnode's per-shard state check in
    `storage/shard.go` — writes to a shard the topology moved away are
    errors, not silent drops).  Wire-mapped by server/rpc.py so a
    remote caller gets the SAME typed error; the replicated session
    counts it as a per-replica routing miss (stale placement) and
    refreshes its topology, never as a data error."""

    def __init__(self, namespace: str | None, shard: int | None):
        super().__init__(
            f"shard {shard} not owned by this node (namespace {namespace!r})"
        )
        self.namespace = namespace
        self.shard = shard


class WriteResult(int):
    """Cold-write count (plain int for back-compat) carrying the typed
    ingest-rejection info: ``rejected`` = samples dropped because their
    series creation exceeded the new-series rate limit; ``accepted`` =
    per-input-sample bool mask (None when nothing was rejected —
    everything landed)."""

    rejected: int
    accepted = None
    # Samples dropped because their shard is not owned under the
    # current placement (mixed direct-ingest batches only — an
    # ALL-unowned batch raises ShardNotOwnedError instead, which is
    # what the per-shard session fan-out sees).
    not_owned: int

    def __new__(cls, ncold: int, rejected: int = 0, not_owned: int = 0):
        obj = super().__new__(cls, ncold)
        obj.rejected = rejected
        obj.not_owned = not_owned
        return obj


def shard_for_id(sid: bytes, num_shards: int) -> int:
    """murmur3(id) % N, bit-for-bit the reference's router
    (`sharding/shardset.go:148-163`).

    NOTE: data directories written before the crc32→murmur3 switch route
    differently and are not readable by this build (no deployed data
    exists; there is no migration path by design).
    """
    return hash_shard_for(sid, num_shards)


# A batch decode's shapes, computed from what a fetch asks for and never
# set by a user: every distinct (rows, words, points) is one compile of a
# scan as long as the block has points, so each is rounded up.  Rows to
# the TPU's lane width; words above the fetch's longest stream; points
# above the most a stream of the volume was seen to hold.  A fetch of
# more rows than _DECODE_MAX_ROWS is cut into calls of that many.
_ROW_BUCKET, _WORD_BUCKET, _POINT_BUCKET = 128, 64, 16
_DECODE_MAX_ROWS = 4096


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _decode_streams(streams: list[bytes], steps: int):
    """One guarded device decode of whole M3TSZ streams -> their
    datapoints as flat columns ``(stream, ts, value)`` sorted by
    (stream, time), the mask of streams the device flagged (``err |
    prec | ann``: their points are left out, the scalar iterator must
    read them) and the padded ``(rows, words)`` handed to the device.
    ``steps`` is the scan's length: a stream with more points is
    flagged.  Values are rebuilt from the payload's BITS on the host
    (`payload_value_bits`): the device's f64 never touches a stored
    value."""
    n = len(streams)
    width = _round_up(max(map(len, streams)) // 8 + 2, _WORD_BUCKET)
    pad = _round_up(n, _ROW_BUCKET) - n
    words, nbits = pack_streams(streams + [b""] * pad, pad_words=width)
    ts, payload, meta, err, prec, ann = decode_batch_device(
        words, nbits, max_points=steps, chains="auto", scan_major=True)
    with tracing.span(Tracepoint.DB_READ_FILESET_TO_HOST):
        flagged = (np.asarray(err) | np.asarray(prec) | np.asarray(ann))[:n]
        meta = np.asarray(meta)[:, :n]                 # scan-major (P, n)
        keep = (((meta & 16) != 0) & ~flagged).T       # (n, P)
        bits = payload_value_bits(np.asarray(payload)[:, :n], meta)
        rows = np.repeat(np.arange(n), keep.sum(axis=1))
        return (rows, np.asarray(ts)[:, :n].T[keep],
                bits.T[keep].view(np.float64), flagged, words.shape)


class SeriesColumns(NamedTuple):
    """A batch of series as the columns of a query block
    (`query/block.RawBlock`): what `Database.read_columns` answers."""

    ts: np.ndarray  # (K, P) int64, time-sorted rows; padded tail = i64 max
    values: np.ndarray  # (K, P) float64; padded tail = NaN
    counts: np.ndarray  # (K,) int64 real points per row
    index: np.ndarray  # (K,) int64: row k answers the request's id index[k]
    columnar: int  # rows whose every source was already arrays


class _BlockSegments(NamedTuple):
    """One flushed block of a batch fetch as read under the lock
    (`Namespace._block_segments`), decoded after it
    (`Namespace._decode_block`)."""

    asked: int  # ids asked of the shards that have a volume of the block
    streams: list  # M3TSZ segments, shard by shard
    home: list  # a stream's (shard, row in the shard's ids, reader)
    steps: int  # the decode scan's length


class _ReadPlan(NamedTuple):
    """What a batch fetch takes under `Database._mu` (phase 1,
    `Namespace._plan`): bytes copied out of immutable volumes and
    references to buffer arrays that are never changed in place.  The
    decode, merge and cut run on it after the release (phase 2,
    `Namespace._read_shards`), so the answer is the database as it
    stood when the lock was let go."""

    n: int  # ids asked
    start: int
    end: int
    by_shard: dict  # shard -> positions in the ids asked
    blocks: list  # [_BlockSegments] in block order
    buffered: dict  # shard -> `Shard.buffer_sources`

    @property
    def streams(self) -> int:
        return sum(len(b.streams) for b in self.blocks)


def _gather_runs(keys: np.ndarray, ts: np.ndarray, vals: np.ndarray,
                 slots: np.ndarray):
    """The runs of ``slots`` out of columns sorted by slot ``keys``, as
    flat ``(row, ts, val)``: row i is ``slots[i]``'s run, rows in the
    order asked, each run in the columns' order.  A slot < 0 (an id the
    shard never saw) has no run."""
    # in the keys' dtype: a window's slots are i32, and searchsorted
    # would otherwise cast the whole window to i64 on every call
    slots = slots.astype(keys.dtype, copy=False)
    los = np.searchsorted(keys, slots)
    lens = np.where(slots >= 0, np.searchsorted(keys, slots + 1) - los, 0)
    rows = np.repeat(np.arange(len(slots)), lens)
    # position in the columns: the run's start plus the rank in the run
    idx = np.arange(len(rows)) - np.repeat(np.cumsum(lens) - lens - los, lens)
    return rows, ts[idx], vals[idx]


class Shard:
    def __init__(self, namespace: str, shard_id: int, opts: NamespaceOptions, root: str,
                 block_cache=None, new_series_limiter=None, corruption_cb=None,
                 snapshot_counters=None):
        self.namespace = namespace
        self.shard_id = shard_id
        self.opts = opts
        self.root = root
        self.block_cache = block_cache
        # Called (namespace, shard, block_start, volume, err) after a
        # corrupt volume is quarantined — the Database's counter/log hook.
        self._corruption_cb = corruption_cb
        self.slots = SlotAllocator(opts.slot_capacity,
                                   limiter=new_series_limiter)
        self.new_series_rejected = 0
        # Series the flush encoded on the device / re-encoded with the
        # scalar codec on the host (the batch encoder's fallback mask:
        # streams over its 40 bit/point budget, >2^53 values).
        self.encoded_on_device = 0
        self.encoded_on_host = 0
        # Ring must cover (bufferPast + bufferFuture) / blockSize + 2 blocks.
        span = opts.buffer_past_nanos + opts.buffer_future_nanos
        num_windows = max(2, span // opts.block_size_nanos + 2)
        self.buffer = ShardBuffer(
            opts.block_size_nanos, int(num_windows), opts.sample_capacity,
            opts.slot_capacity, snapshot_counters=snapshot_counters,
        )
        self.flushed_blocks: set[int] = set()
        for bs, _vol in list_filesets(root, namespace, shard_id):
            self.flushed_blocks.add(bs)

    # -- write path --------------------------------------------------------

    def open_starts(self, now_nanos: int) -> set[int]:
        """Block starts accepting warm writes at `now` (reference
        buffer.go:311-398: [now-bufferPast, now+bufferFuture])."""
        bsz = self.opts.block_size_nanos
        lo = (now_nanos - self.opts.buffer_past_nanos) // bsz * bsz
        hi = (now_nanos + self.opts.buffer_future_nanos) // bsz * bsz
        return {bs for bs in range(lo, hi + bsz, bsz) if bs not in self.flushed_blocks}

    def write_batch(self, ids: Sequence[bytes], ts: np.ndarray, vals: np.ndarray,
                    now_nanos: int) -> int:
        slots = self.slots.resolve(ids)
        rejected = slots < 0
        nrej = 0
        if rejected.any():
            # New-series rate limit hit: drop ONLY the rejected
            # creations (existing series in the batch still land) and
            # count them — graceful degradation under churn, never
            # unbounded state growth (dbnode/kvconfig/keys.go
            # write-new-series limits).
            nrej = int(rejected.sum())
            self.new_series_rejected += nrej
            keep = ~rejected
            slots, ts, vals = slots[keep], ts[keep], vals[keep]
        ncold = self.buffer.write(slots, ts, vals, self.open_starts(now_nanos))
        res = WriteResult(ncold, nrej)
        res.accepted = ~rejected
        return res

    # -- flush path --------------------------------------------------------

    def _encode_runs(self, slots: np.ndarray, ts: np.ndarray, vals: np.ndarray,
                     block_start: int) -> list[tuple[bytes, bytes]]:
        """(sorted, deduped) flat runs -> [(id, m3tsz stream)] via the
        batched device encoder; fallback series use the scalar oracle."""
        if len(slots) == 0:
            return []
        uniq, starts_idx, counts = np.unique(slots, return_index=True, return_counts=True)
        S, T = len(uniq), int(counts.max())
        tmat = np.zeros((S, T), np.int64)
        vmat = np.zeros((S, T), np.float64)
        for r, (i0, c) in enumerate(zip(starts_idx, counts)):
            tmat[r, :c] = ts[i0 : i0 + c]
            vmat[r, :c] = vals[i0 : i0 + c]
            if c < T:  # pad with the last sample (ignored via counts)
                tmat[r, c:] = tmat[r, c - 1]
                vmat[r, c:] = vmat[r, c - 1]
        starts = np.full(S, block_start, np.int64)
        streams, fallback = encode_batch(
            tmat, vmat, starts, counts=counts, out_words=max(16, T * 40 // 64 + 8)
        )
        self.encoded_on_host += int(fallback.sum())
        self.encoded_on_device += S - int(fallback.sum())
        out = []
        for r, slot in enumerate(uniq):
            sid = self.slots.id_of(int(slot))
            if sid is None:
                continue
            if fallback[r]:
                pts = list(zip(tmat[r, : counts[r]].tolist(), vmat[r, : counts[r]].tolist()))
                stream = encode_series(pts, start=block_start)
            else:
                stream = streams[r]
            out.append((sid, stream))
        return out

    def warm_flush(self, block_start: int) -> int:
        """Seal + persist one block (reference buffer.go:634 WarmFlush →
        persist_manager flush).  Returns series flushed.

        The window clears only AFTER the volume is durably on disk
        (peek → write → discard): a DiskCapacityError mid-write leaves
        every sample buffered and readable, and the next tick retries
        the flush against whatever space the cleanup freed."""
        slots, ts, vals = self.buffer.peek(block_start)
        with tracing.span(Tracepoint.DB_FLUSH_ENCODE, {"n": len(slots)}):
            series = self._encode_runs(slots, ts, vals, block_start)
        with tracing.span(Tracepoint.DB_FLUSH_WRITE, {"n": len(series)}):
            DataFileSetWriter(
                self.root, self.namespace, self.shard_id, block_start,
                self.opts.block_size_nanos, volume=0,
            ).write_all(series)
        self.buffer.discard(block_start)
        self.flushed_blocks.add(block_start)
        return len(series)

    def cold_flush(self, skip_open: frozenset = frozenset()) -> int:
        """Merge cold overflow writes with the existing volume and write
        volume+1 (reference coldflush.go + fs/merger.go).

        ``skip_open`` holds block starts still inside the warm window:
        their overflow entries are DEGRADED-MODE staging from the
        guarded buffer append (warm samples host-routed while the
        device path is down), and flushing them before the block seals
        would race the later warm flush for volume numbering.  They
        stay readable from the overflow lists and are merged by the
        cold flush that follows the seal."""
        flushed = 0
        for block_start in sorted(self.buffer.cold.keys()):
            if block_start in skip_open:
                continue
            slots, ts, vals = self.buffer.peek_cold(block_start)
            if len(slots) == 0:
                self.buffer.discard_cold(block_start)
                continue
            vol = -1
            for bs, v in list_filesets(self.root, self.namespace, self.shard_id):
                if bs == block_start:
                    vol = v

            # Merge from the highest INTACT volume (corrupt ones are
            # quarantined and the next-lower tried); the rewrite still
            # lands at max_vol+1 so volume numbering stays monotonic
            # across a quarantine.
            def _decode_volume(merge_vol):
                r = DataFileSetReader(
                    self.root, self.namespace, self.shard_id,
                    block_start, merge_vol
                )
                return {
                    sid: {d.timestamp: d.value for d in decode_series(seg)}
                    for sid, seg in r.read_all()
                }

            merged: Dict[bytes, Dict[int, float]] = (
                self._fold_intact_volumes(block_start, _decode_volume) or {}
            )
            for slot, t, v in zip(slots, ts, vals):
                sid = self.slots.id_of(int(slot))
                if sid is None:
                    continue
                merged.setdefault(sid, {})[int(t)] = float(v)
            series = []
            for sid, pts in merged.items():
                items = sorted(pts.items())
                series.append((sid, encode_series(items, start=block_start)))
            DataFileSetWriter(
                self.root, self.namespace, self.shard_id, block_start,
                self.opts.block_size_nanos, volume=vol + 1,
            ).write_all(series)
            # staged overflow clears only once volume+1 is on disk —
            # same no-loss-on-ENOSPC ordering as warm_flush
            self.buffer.discard_cold(block_start)
            self.flushed_blocks.add(block_start)
            if self.block_cache is not None:
                # volume+1 supersedes the cached volume's blocks
                self.block_cache.invalidate_block(
                    self.namespace, self.shard_id, block_start
                )
            flushed += len(series)
        return flushed

    def snapshot_blocks(self, snap_root: str) -> int:
        """Persist every un-flushed block (open warm window + pending cold
        overflow) as a snapshot fileset under `snap_root` without touching
        the live buffers (reference buffer.go:537 Snapshot).  Returns
        series-blocks written."""
        written = 0
        for bs in sorted(set(self.buffer.open_blocks) | set(self.buffer.cold)):
            slots, ts, vals = self.buffer.peek(bs)
            parts = self.buffer.cold.get(bs, ())
            if len(parts):
                slots = np.concatenate([slots] + [p[0] for p in parts]).astype(np.int32)
                ts = np.concatenate([ts] + [p[1] for p in parts]).astype(np.int64)
                vals = np.concatenate([vals] + [p[2] for p in parts]).astype(np.float64)
                slots, ts, vals = dedupe_last_write_wins(slots, ts, vals)
            if len(slots) == 0:
                continue
            series = self._encode_runs(slots, ts, vals, bs)
            DataFileSetWriter(
                snap_root, self.namespace, self.shard_id, bs,
                self.opts.block_size_nanos, volume=0,
            ).write_all(series)
            written += len(series)
        return written

    # -- corruption handling ----------------------------------------------

    def quarantine_volume(self, block_start: int, volume: int, err) -> None:
        """Pull one corrupt fileset volume out of the live tree
        (persist/quarantine), drop its cached readers/blocks, and — when
        no intact volume remains for the block — un-mark it flushed so
        buffers/replay may serve it again (the corrupt volume is now
        *missing*, not half-readable)."""
        qdir = quar.quarantine_fileset(self.root, self.namespace,
                                       self.shard_id, block_start, volume, err)
        if self.block_cache is not None:
            self.block_cache.invalidate_block(
                self.namespace, self.shard_id, block_start
            )
        if not any(bs == block_start for bs, _ in list_filesets(
                self.root, self.namespace, self.shard_id)):
            self.flushed_blocks.discard(block_start)
        _LOG.warning(
            "quarantined corrupt fileset ns=%s shard=%d block=%d vol=%d: %s",
            self.namespace, self.shard_id, block_start, volume, err,
        )
        if self._corruption_cb is not None:
            self._corruption_cb(self.namespace, self.shard_id, block_start,
                                volume, err, quarantined=qdir is not None)

    def _fold_intact_volumes(self, block_start: int, consume):
        """Apply ``consume(volume)`` to the block's volumes, highest
        first, returning the first result that reads clean.  A corrupt
        volume is quarantined and the next-lower one tried; a missing
        one (raced cleanup/quarantine) is skipped.  ``consume`` must
        build any partial state fresh per call — a mid-read
        CorruptionError discards that attempt wholesale.  This is the
        ONE place the quarantine-and-fall-back contract lives (read
        path, cold-flush merge, and WAL-replay dedupe all fold through
        it)."""
        vols = sorted(
            (v for bs, v in list_fileset_volumes(
                self.root, self.namespace, self.shard_id)
             if bs == block_start),
            reverse=True,
        )
        for vol in vols:
            try:
                return consume(vol)
            except FileNotFoundError:
                continue
            except CorruptionError as e:
                self.quarantine_volume(block_start, vol, e)
                continue
        return None

    def _from_intact_volume(self, block_start: int, volume: int | None,
                            consume):
        """``consume`` on the highest INTACT volume of a block, or None.
        A corrupt volume is quarantined and the next-lower volume tried
        — corruption degrades this one source (buffers and replicas
        still answer), it never fails the read (the reference's
        checksum-verify-and-skip read path, persist/fs/read.go +
        repair.go's expected-corruption contract).

        ``volume`` is the caller's already-known latest volume: the hot
        path reads it directly (no extra directory glob); only a
        corrupt/vanished volume falls back to enumerating what remains
        on disk."""
        if volume is not None:
            try:
                return consume(volume)
            except FileNotFoundError:
                pass
            except CorruptionError as e:
                self.quarantine_volume(block_start, volume, e)
            # quarantined/vanished: whatever remains on disk, if anything
        return self._fold_intact_volumes(block_start, consume)

    def _reader(self, block_start: int, volume: int) -> DataFileSetReader:
        if self.block_cache is not None:
            return self.block_cache.reader(
                self.root, self.namespace, self.shard_id, block_start, volume)
        return DataFileSetReader(
            self.root, self.namespace, self.shard_id, block_start, volume)

    def _read_fileset_series(self, block_start: int, sid: bytes,
                             volume: int | None = None):
        """Points for ``sid`` from the highest intact volume of a block
        (`_from_intact_volume`), or None: the single-id path, through
        the scalar iterator and the block cache's decoded-series LRU."""
        def consume(vol):
            if self.block_cache is not None:
                return self.block_cache.read_series(
                    self.root, self.namespace, self.shard_id,
                    block_start, vol, sid,
                )
            seg = self._reader(block_start, vol).read(sid)
            return ([(d.timestamp, d.value) for d in decode_series(seg)]
                    if seg else None)

        return self._from_intact_volume(block_start, volume, consume)

    def read_fileset_segments(self, block_start: int, sids: Sequence[bytes],
                              volume: int | None = None):
        """``(reader, [M3TSZ segment or None per id])`` from the highest
        intact volume of a block (`_from_intact_volume`), or ``(None,
        ())``: the batch path's disk read, one open reader from the
        block cache's pool and one pass over its index for all ids.
        Nothing decoded, nothing kept."""
        def consume(vol):
            reader = self._reader(block_start, vol)
            return reader, reader.read_many(sids)

        return (self._from_intact_volume(block_start, volume, consume)
                or (None, ()))

    # -- read path ---------------------------------------------------------

    def read_sources(
        self, sid: bytes, start_nanos: int, end_nanos: int
    ) -> list[list[tuple[int, float]]]:
        """Every source holding points for this series over the range,
        ordered oldest-precedence-first for the merge seam
        (series_merge.merge_point_sources): sealed fileset volume, open
        warm buffer, pending cold overflow.  This is the seam the
        reference builds from MultiReaderIterator + buffer streams
        (`shard.go:1079` ReadEncoded gathering disk + memory streams)."""
        bsz = self.opts.block_size_nanos
        slot = self.slots.get(sid)
        lo = start_nanos // bsz * bsz
        filesets = dict(list_filesets(self.root, self.namespace, self.shard_id))
        sources: list[list[tuple[int, float]]] = []
        for bs in range(lo, end_nanos, bsz):  # a block at `end` holds no t < end
            if bs in filesets:
                pts = self._read_fileset_series(bs, sid, volume=filesets[bs])
                if pts:
                    sources.append(pts)
            if slot is not None and bs in self.buffer.open_blocks:
                ts, vals = self.buffer.read_window(bs, slot)
                sources.append(list(zip(ts.tolist(), vals.tolist())))
            if slot is not None and bs in self.buffer.cold:
                # Cold writes awaiting flush are readable immediately
                # (the reference reads cold buckets too — versioned
                # buckets in buffer.go:1016 serve un-flushed cold data).
                pts: list[tuple[int, float]] = []
                for cslots, cts, cvals in self.buffer.cold[bs]:
                    m = cslots == slot
                    pts.extend(zip(cts[m].tolist(), cvals[m].tolist()))
                sources.append(pts)
        return sources

    def read(self, sid: bytes, start_nanos: int, end_nanos: int) -> list[tuple[int, float]]:
        merged = merge_point_sources(
            self.read_sources(sid, start_nanos, end_nanos)
        )
        return [(t, v) for t, v in merged if start_nanos <= t < end_nanos]

    def buffer_sources(self, sids: Sequence[bytes], start_nanos: int,
                       end_nanos: int):
        """What :meth:`read_columns` needs of this shard's mutable
        state, taken under `Database._mu`: the slots of ``sids`` (-1 for
        an id never seen) and, per block of the range in order, the
        open window's sorted snapshot (`ShardBuffer.peek`: its arrays
        are never changed in place, a write makes the next reader build
        new ones) as ``(False, (slots, ts, vals))`` and a copy of the
        cold-overflow list (its parts are copies as they arrived) as
        ``(True, parts)``."""
        bsz = self.opts.block_size_nanos
        slots = np.asarray(
            [s if (s := self.slots.get(sid)) is not None else -1
             for sid in sids], np.int64)
        sources = []
        for bs in range(start_nanos // bsz * bsz, end_nanos, bsz):
            # a block at `end` holds no t < end
            if bs in self.buffer.open_blocks:
                sources.append((False, self.buffer.peek(bs)))
            if bs in self.buffer.cold:
                # Cold writes awaiting flush are readable immediately
                # (the reference reads cold buckets too — versioned
                # buckets in buffer.go:1016 serve un-flushed cold data).
                sources.append((True, list(self.buffer.cold[bs])))
        return slots, sources

    def read_columns(self, buffered, start_nanos: int, end_nanos: int,
                     fileset):
        """Batched :meth:`read` as flat columns ``(row, ts, vals)``
        sorted by (row, ts), row i being the i-th id asked, plus the
        mask of rows that had a source made of tuples: same sources,
        same merge and range contract as the single-id path (fileset
        volume, open warm buffer, cold overflow; a later source wins a
        timestamp; ``start <= t < end``), with every source as arrays.
        Per block this pays one sorted-window snapshot and one
        cold-overflow sort for all ids, and a series whose only sources
        are open windows never leaves numpy.  ``buffered`` is what
        :meth:`buffer_sources` took under the lock; ``fileset`` is this
        shard's part of what `Namespace._read_shards` decoded: the
        flushed blocks' ``(row, ts, vals)`` chunks, decoded with the
        other shards' in one device call, and the mask of rows the
        scalar iterator had to read.  Reads nothing of the shard: it runs
        after the lock is released."""
        slots, sources = buffered
        # (row, ts, vals) in the merge's order.  The fileset chunks of
        # every block stand before the buffers': what the merge orders
        # is equal (row, ts), and those share a block
        chunks, tupled = list(fileset[0]), fileset[1]
        windows_only = not chunks
        for cold, parts in sources:
            if not cold:
                chunks.append(_gather_runs(*parts, slots))
            else:
                cslots = np.concatenate([p[0] for p in parts]).astype(np.int64)
                # arrival-stable sort by slot: a run keeps arrival order
                # (the cold merge rule's tie-break input)
                order = np.argsort(cslots, kind="stable")
                chunks.append(_gather_runs(
                    cslots[order],
                    np.concatenate([p[1] for p in parts])[order],
                    np.concatenate([p[2] for p in parts])[order], slots))
                windows_only = False
        if not chunks:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    np.empty(0), tupled)
        rows, ts, vals = (chunks[0] if len(chunks) == 1 else
                          (np.concatenate(c) for c in zip(*chunks)))
        if len(chunks) > 1 or not windows_only:
            # one open window's runs are sorted and deduped as they
            # come; anything else goes through the one merge: a stable
            # sort by (row, ts) leaves equal timestamps in source
            # order, and the last of them wins.  Columns that already
            # rise strictly in (row, ts) (one flushed block's decode)
            # are their own merge
            rises = (rows[1:] > rows[:-1]) | (
                (rows[1:] == rows[:-1]) & (ts[1:] > ts[:-1]))
            if not rises.all():
                order = np.lexsort((ts, rows))
                rows, ts, vals = rows[order], ts[order], vals[order]
                last = np.ones(len(rows), bool)
                last[:-1] = (rows[1:] != rows[:-1]) | (ts[1:] != ts[:-1])
                rows, ts, vals = rows[last], ts[last], vals[last]
        inr = (ts >= start_nanos) & (ts < end_nanos)
        if not inr.all():
            rows, ts, vals = rows[inr], ts[inr], vals[inr]
        return rows, ts, vals, tupled


class Namespace:
    def __init__(self, name: str, opts: NamespaceOptions, root: str,
                 block_cache=None, new_series_limiter=None,
                 corruption_cb=None, scope=None):
        self.name = name
        self.opts = opts
        self.root = root
        # what the batch read's fileset part counts on /metrics (the
        # Database's `db` scope): series the device decoded, rows the
        # scalar iterator had to read, datapoints decoded
        self._fileset_counters = None if scope is None else tuple(
            scope.counter("fileset_" + c) for c in (
                "series_device_decoded", "series_scalar_decoded",
                "decode_points"))
        # the open windows' sorted-snapshot cache, every shard's buffer
        # (storage/buffer.py ShardBuffer._sorted_window): reads it
        # served, reads that re-sorted a window, and of those the ones
        # a mutation since the last sort forced
        self.snapshot_counters = None if scope is None else tuple(
            scope.counter("buffer_snapshot_" + c)
            for c in ("hits", "misses", "stale"))
        self.shards = [
            Shard(name, i, opts, root, block_cache,
                  new_series_limiter=new_series_limiter,
                  corruption_cb=corruption_cb,
                  snapshot_counters=self.snapshot_counters)
            for i in range(opts.num_shards)
        ]
        # Placement-driven ownership: None = own every shard (the
        # single-node / no-placement default, bit-compatible with the
        # pre-topology behavior); a set restricts writes AND reads to
        # exactly those shards — everything else raises the typed
        # ShardNotOwnedError (reference dbnode shard state gating).
        self.owned: frozenset | None = None
        self.index = NamespaceIndex(opts.block_size_nanos, root, name)

    def check_owned(self, shard: int) -> None:
        if self.owned is not None and shard not in self.owned:
            raise ShardNotOwnedError(self.name, shard)

    def write_tagged_batch(self, docs: Sequence[Document], ts: np.ndarray,
                           vals: np.ndarray, now_nanos: int) -> int:
        """Write + index tagged series (reference WriteTagged
        `database.go:771` → shard writeAndIndex → nsIndex.WriteBatch).
        The index only learns documents whose series were ACCEPTED —
        rate-limited churn must not grow the reverse index either (that
        is the unbounded-memory failure the limit exists to stop)."""
        with tracing.span(Tracepoint.DB_BUFFER_WRITE):
            res = self.write_batch([d.id for d in docs], ts, vals, now_nanos)
        with tracing.span(Tracepoint.DB_INDEX_WRITE):
            if res.accepted is None:
                self.index.write_batch(list(docs), ts)
            else:
                acc = res.accepted
                kept = [d for d, a in zip(docs, acc) if a]
                if kept:
                    self.index.write_batch(kept, ts[acc])
        return res

    def query_ids(self, q: Query, start: int, end: int,
                  inc_docs=None) -> list[Document]:
        """Index query → matching series documents (reference db.QueryIDs
        → nsIndex.Query `storage/index.go:1483`)."""
        return self.index.query(q, start, end, inc_docs=inc_docs)

    def write_batch(self, ids: Sequence[bytes], ts: np.ndarray, vals: np.ndarray,
                    now_nanos: int) -> int:
        by_shard = self._by_shard(ids)
        # Ownership gate BEFORE any shard buffers a sample.  An
        # ALL-unowned batch rejects atomically with the typed error —
        # the session fans single-shard sub-batches, so that maps to
        # one routing miss.  A MIXED direct-ingest batch (carbon/HTTP
        # front doors hash one flush across many shards) must NOT lose
        # its owned samples to one stray id: owned shards land, the
        # unowned remainder is dropped into the accepted mask like a
        # limiter rejection (counted as ``not_owned``; never
        # WAL-logged, never indexed).
        owned_set = self.owned
        unowned = ([] if owned_set is None
                   else sorted(sh for sh in by_shard if sh not in owned_set))
        if unowned and len(unowned) == len(by_shard):
            raise ShardNotOwnedError(self.name, unowned[0])
        ncold = nrej = ndropped = 0
        full = np.ones(len(ids), bool)
        for sh, idxs in by_shard.items():
            sel = np.asarray(idxs)
            if owned_set is not None and sh not in owned_set:
                full[sel] = False
                ndropped += len(idxs)
                continue
            res = self.shards[sh].write_batch(
                [ids[i] for i in idxs], ts[sel], vals[sel], now_nanos
            )
            ncold += int(res)
            nrej += res.rejected
            if res.accepted is not None:
                full[sel] = res.accepted
        out = WriteResult(ncold, nrej, ndropped)
        if nrej or ndropped:
            out.accepted = full
        return out

    @property
    def new_series_rejected(self) -> int:
        return sum(sh.new_series_rejected for sh in self.shards)

    def read(self, sid: bytes, start: int, end: int) -> list[tuple[int, float]]:
        shard = shard_for_id(sid, self.opts.num_shards)
        self.check_owned(shard)
        return self.shards[shard].read(sid, start, end)

    def _by_shard(self, sids: Sequence[bytes]) -> Dict[int, List[int]]:
        by_shard: Dict[int, List[int]] = {}
        for i, sid in enumerate(sids):
            by_shard.setdefault(shard_for_id(sid, self.opts.num_shards),
                                []).append(i)
        return by_shard

    def _fileset_segments(self, by_shard: Dict[int, List[int]],
                          sids: Sequence[bytes], start: int,
                          end: int) -> list:
        """The sealed part of a batch read as phase 1 takes it, under
        `Database._mu`: per flushed block the range touches, the
        segments of the asked ids from ALL the shards (`_BlockSegments`,
        decoded together by `_decode_block` after the release) — not a
        decode per shard: four shards give four row counts a selector,
        and every distinct shape is a compile of a scan as long as the
        block."""
        bsz = self.opts.block_size_nanos
        filesets = {sh: dict(list_filesets(self.root, self.name, sh))
                    for sh in by_shard}
        blocks = []
        for bs in range(start // bsz * bsz, end, bsz):
            parts = [(sh, filesets[sh][bs]) for sh in by_shard
                     if bs in filesets[sh]]
            if parts:
                xdeadline.check_current("fetch series")
                blocks.append(self._block_segments(bs, parts, by_shard, sids))
        return blocks

    def _block_segments(self, block_start: int, parts: list, by_shard: dict,
                        sids: Sequence[bytes]) -> _BlockSegments:
        """One flushed block of one fetch, read under the lock: the
        asked ids' segments from each shard's open reader (copies out of
        an immutable volume; a corrupt volume is quarantined here and
        the next lower one read) and the scan's length, from what the
        readers learned of their volumes."""
        asked = sum(len(by_shard[sh]) for sh, _ in parts)
        with tracing.span(Tracepoint.DB_READ_FILESET_SEGMENTS):
            streams, home = [], []  # home: a stream's (shard, row, reader)
            for sh, vol in parts:
                reader, segs = self.shards[sh].read_fileset_segments(
                    block_start, [sids[i] for i in by_shard[sh]], vol)
                found = [(r, seg) for r, seg in enumerate(segs) if seg]
                if not found:
                    continue
                if reader.max_points is None:
                    # the format records no count: the volume's
                    # longest stream asked for says how long a scan
                    # its decode needs (a longer one is flagged,
                    # read by the scalar iterator and raises this)
                    reader.max_points = len(decode_series(
                        max((seg for _, seg in found), key=len)))
                streams.extend(seg for _, seg in found)
                home.extend((sh, r, reader) for r, _ in found)
            steps = _round_up(max((h[2].max_points for h in home), default=0),
                              _POINT_BUCKET)
        return _BlockSegments(asked, streams, home, steps)

    def _decode_block(self, blk: _BlockSegments, out: dict) -> None:
        """One flushed block of one fetch, after the lock is released:
        its segments packed once, decoded in one guarded device call
        (`_decode_streams`; a fetch over `_DECODE_MAX_ROWS` in several),
        each shard's rows appended to ``out`` as ``(row, ts, value)``
        arrays.  A stream the device flags goes through the scalar
        iterator, is counted and marks its row tupled.  Nothing decoded
        outlives the fetch: what a node keeps between fetches is the
        encoded block (the reader's page-cache-backed mmap), as
        upstream's series cache policies do."""
        streams, home, steps = blk.streams, blk.home, blk.steps
        with tracing.span(Tracepoint.DB_READ_FILESET, {"n": blk.asked}) as sp:
            if not streams:
                return
            row_of = np.fromiter((r for _, r, _ in home), np.int64, len(home))
            n_points = n_scalar = n_rows = n_words = 0
            for a in range(0, len(streams), _DECODE_MAX_ROWS):
                b = min(a + _DECODE_MAX_ROWS, len(streams))
                rows, ts, vals, flagged, shape = _decode_streams(
                    streams[a:b], steps)
                n_rows += shape[0]
                n_words += shape[0] * shape[1]
                n_points += len(rows)
                # streams stand shard by shard: a shard's are a run
                edges = [a] + [g for g in range(a + 1, b)
                               if home[g][0] != home[g - 1][0]] + [b]
                cuts = np.searchsorted(rows, np.asarray(edges) - a)
                for g, lo, hi in zip(edges, cuts[:-1], cuts[1:]):
                    out[home[g][0]][0].append(
                        (row_of[rows[lo:hi] + a], ts[lo:hi], vals[lo:hi]))
                for g in (np.nonzero(flagged)[0] + a).tolist():
                    sh, r, reader = home[g]
                    pts = decode_series(streams[g])
                    # unlocked: two fetches may race here, and the
                    # update lost costs one more scalar decode
                    reader.max_points = max(reader.max_points, len(pts))
                    out[sh][0].append((
                        np.full(len(pts), r),
                        np.fromiter((d.timestamp for d in pts), np.int64,
                                    len(pts)),
                        np.fromiter((d.value for d in pts), np.float64,
                                    len(pts))))
                    out[sh][1][r] = True
                    n_scalar += 1
                    n_points += len(pts)
            sp.set_tag("device", len(streams) - n_scalar)
            # the boundary scan's register file as it ran (select |
            # gather, `m3tsz_jax._regfile4`)
            sp.set_tag("regfile", last_regfile())
            sp.set_tag("scalar", n_scalar)
            sp.set_tag("words", n_words)
            sp.set_tag("points", n_points)
            # the decode's padded shape: rows handed to the device (all
            # calls), the scan's length
            sp.set_tag("rows", n_rows)
            sp.set_tag("steps", steps)
        if self._fileset_counters is not None:
            for c, v in zip(self._fileset_counters,
                            (len(streams) - n_scalar, n_scalar, n_points)):
                c.inc(v)

    def _plan(self, sids: Sequence[bytes], by_shard: dict, start: int,
              end: int) -> _ReadPlan:
        """Phase 1 of the one batch read, under `Database._mu`: the
        flushed blocks' segments and each shard's slots and buffer
        snapshots — everything `_read_shards` reads of the namespace.
        A bound deadline is checked between blocks."""
        return _ReadPlan(
            len(sids), start, end, by_shard,
            self._fileset_segments(by_shard, sids, start, end),
            {sh: self.shards[sh].buffer_sources(
                [sids[i] for i in idxs], start, end)
             for sh, idxs in by_shard.items()})

    def _read_shards(self, plan: _ReadPlan):
        """Phase 2 of the one batch read, after the release: (positions
        in the ids asked, `Shard.read_columns` of them) per shard of the
        plan, the flushed blocks decoded for all of them together
        first.  A bound deadline is checked between shards, so a
        cancelled query stops."""
        fileset = {sh: ([], np.zeros(len(idxs), bool))
                   for sh, idxs in plan.by_shard.items()}
        for blk in plan.blocks:
            self._decode_block(blk, fileset)
        for sh, idxs in plan.by_shard.items():
            xdeadline.check_current("fetch series")
            yield idxs, self.shards[sh].read_columns(
                plan.buffered[sh], plan.start, plan.end, fileset[sh])

    def plan_many(self, sids: Sequence[bytes], start: int,
                  end: int) -> _ReadPlan:
        """:meth:`read_many`'s phase 1.  The ownership gate is per
        SHARD and atomic like write_batch's all-unowned case: any
        unowned shard in the batch raises typed (the session fans
        single-shard sub-batches, so this maps to one routing miss,
        never a partially-silent read)."""
        by_shard = self._by_shard(sids)
        for sh in by_shard:
            self.check_owned(sh)
        return self._plan(sids, by_shard, start, end)

    def read_many(self, plan: _ReadPlan) -> list[list[tuple[int, float]]]:
        """Batched read as one point list per requested id (the RPC /
        session / verification shape): `_read_shards`' columns cut into
        lists."""
        out: list = [None] * plan.n
        for idxs, (rows, ts, vals, _) in self._read_shards(plan):
            bounds = np.searchsorted(rows, np.arange(len(idxs) + 1)).tolist()
            ts, vals = ts.tolist(), vals.tolist()
            for i, a, b in zip(idxs, bounds[:-1], bounds[1:]):
                out[i] = list(zip(ts[a:b], vals[a:b]))
        return out

    def plan_columns(self, sids: Sequence[bytes], start: int,
                     end: int) -> _ReadPlan:
        """:meth:`read_columns`' phase 1, over the ids of OWNED shards
        ("reads answer only owned shards": the index still knows
        series whose shard the placement moved away — a local query
        answers from what this node owns; the cluster-level union comes
        from the session's replica fan-out)."""
        by_shard = self._by_shard(sids)
        if self.owned is not None:
            by_shard = {sh: idxs for sh, idxs in by_shard.items()
                        if sh in self.owned}
        return self._plan(sids, by_shard, start, end)

    def read_columns(self, plan: _ReadPlan) -> SeriesColumns:
        """Batched read as a query block's columns, one row per id of
        the plan in the order asked; ``index`` says which ids those
        are."""
        by_shard = plan.by_shard
        index = np.sort(np.fromiter(
            (i for idxs in by_shard.values() for i in idxs), np.int64))
        row_of = np.empty(plan.n, np.int64)  # request position -> row
        row_of[index] = np.arange(len(index))
        counts = np.zeros(len(index), np.int64)
        columnar = len(index)
        parts = []
        for idxs, (rows, ts, vals, tupled) in self._read_shards(plan):
            columnar -= int(tupled.sum())
            n = np.bincount(rows, minlength=len(idxs))
            out_rows = row_of[np.asarray(idxs, np.int64)]
            counts[out_rows] = n
            parts.append((out_rows, n, ts, vals))
        width = max(int(counts.max(initial=0)), 1)
        ts_out = np.full((len(index), width), np.iinfo(np.int64).max, np.int64)
        vals_out = np.full((len(index), width), np.nan)
        for out_rows, n, ts, vals in parts:
            if len(n) and (n == n[0]).all():
                # every run as long (a fleet scraped together): the
                # shard's columns are its rows as they stand
                ts_out[out_rows, :n[0]] = ts.reshape(len(n), -1)
                vals_out[out_rows, :n[0]] = vals.reshape(len(n), -1)
                continue
            # a shard's rows are runs in the order asked: the rank in
            # the run is the column
            rows = np.repeat(out_rows, n)
            col = np.arange(len(rows)) - np.repeat(np.cumsum(n) - n, n)
            ts_out[rows, col] = ts
            vals_out[rows, col] = vals
        return SeriesColumns(ts_out, vals_out, counts, index, columnar)

    def tick(self, now_nanos: int) -> dict:
        """Seal + warm-flush every open block that has left the warm
        window (mediator.go tick → flush), then cold-flush overflow."""
        stats = {"warm_flushed": 0, "cold_flushed": 0, "index_sealed": 0}
        sealed_blocks: set[int] = set()
        for shard in self.shards:
            open_now = shard.open_starts(now_nanos)
            for bs in sorted(set(shard.buffer.open_blocks) - open_now):
                stats["warm_flushed"] += shard.warm_flush(bs)
                sealed_blocks.add(bs)
            if self.opts.cold_writes_enabled:
                stats["cold_flushed"] += shard.cold_flush(
                    skip_open=frozenset(open_now))
        # Index blocks seal alongside their data blocks (reference index
        # flush rides the same mediator file-system pass, mediator.go:318).
        for bs in sorted(sealed_blocks):
            if self.index.seal_block(bs) is not None:
                stats["index_sealed"] += 1
        # Background segment compaction: bound per-block segment counts
        # under churn (reference multi_segments_builder compaction).
        stats["index_compactions"] = self.index.compact()
        return stats


class Database:
    """Top-level engine (reference storage/database.go db struct;
    `Write` :739, `ReadEncoded` via namespaces, `Bootstrap` :1199)."""

    def __init__(self, opts: DatabaseOptions | None = None,
                 namespaces: Dict[str, NamespaceOptions] | None = None,
                 instrument=None, tracer=None, limits: QueryLimits | None = None,
                 new_series_limiter: NewSeriesLimiter | None = None):
        from m3_tpu.instrument.tracing import NOOP_TRACER

        self.opts = opts or DatabaseOptions()
        self._scope = instrument.scope("db") if instrument is not None else None
        # flush/snapshot latency: windowed mergeable histograms (the
        # /health ``latency`` section), interned once — these paths run
        # per mediator tick and must not pay a registry intern each time
        self._hist_tick = (self._scope.histogram("tick_seconds")
                           if self._scope is not None else None)
        self._hist_snapshot = (self._scope.histogram("snapshot_seconds")
                               if self._scope is not None else None)
        # per-batch ingest latency at the STORAGE boundary (covers every
        # front door: rpc write fan-out, HTTP json, carbon, WAL replay
        # excluded by construction) — the fleet-mergeable lane the soak
        # harness scrapes for its per-phase ingest p50/p99
        self._hist_write = (self._scope.histogram("write_batch_seconds")
                            if self._scope is not None else None)
        self.tracer = tracer if tracer is not None else NOOP_TRACER
        self.limits = limits if limits is not None else NO_LIMITS
        # One engine-wide reentrant lock serializing state mutation:
        # ingest batches (HTTP threads), the mediator's tick/snapshot/
        # cleanup thread, bootstrap, and reads: a query's selector or an
        # RPC batch takes it once for all its series (`read_columns`,
        # `read_batch`), and only while it takes its read plan (`_fetch`);
        # `read` once for its one, through its whole read.
        # The reference uses fine-grained per-shard/series locks
        # (shard.go RLock ladders); here every operation is already a
        # whole-batch array program, so one coarse lock adds no
        # meaningful serialization beyond what the batched design has.
        # Every acquisition stands under a db.lock.wait span: with
        # several requests in flight, most of a request's time can be
        # the wait for this lock, which no span opened inside it sees.
        self._mu = tracing.SpanLock(
            threading.RLock(), Tracepoint.DB_LOCK_WAIT, self.tracer)
        Path(self.opts.root).mkdir(parents=True, exist_ok=True)
        from m3_tpu.storage.block_cache import BlockCache

        self.block_cache = BlockCache(instrument=instrument)
        # Engine-wide new-series rate limiter shared by every shard's
        # allocator (0 = unlimited; runtime-tuned through the
        # write_new_series_limit_per_sec KV option, kvconfig/keys.go).
        self.new_series_limiter = (
            new_series_limiter if new_series_limiter is not None
            else NewSeriesLimiter(self.opts.write_new_series_limit_per_sec))
        self.namespaces: Dict[str, Namespace] = {}
        for name, nopts in (namespaces or {"default": NamespaceOptions()}).items():
            self.namespaces[name] = Namespace(
                name, nopts, self.opts.root, self.block_cache,
                new_series_limiter=self.new_series_limiter,
                corruption_cb=self._note_corruption, scope=self._scope,
            )
        self.commitlog = (
            CommitLogWriter(
                self.opts.root,
                rotate_bytes=self.opts.commitlog_rotate_bytes,
                # fsync wall time on the db scope: a stalling disk is
                # SLO-visible long before it is full
                fsync_histogram=(
                    self._scope.histogram("commitlog_fsync_seconds")
                    if self._scope is not None else None),
            ) if self.opts.commitlog_enabled else None
        )
        # (num_shards, owned) the topology watcher last installed:
        # inherited by namespaces created later (see ensure_namespace).
        self._ownership_template: tuple | None = None
        self.bootstrapped = False

    def _note_corruption(self, namespace: str, shard: int, block_start: int,
                         volume: int, err, quarantined: bool = True) -> None:
        """Counter hook every shard's quarantine path reports through —
        the ``corruption_*`` series on a node's /metrics.  ``detected``
        counts every corruption event; ``quarantined`` only those where
        files were actually moved (a volume whose files vanished before
        the move detects without quarantining)."""
        if self._scope is not None:
            self._scope.counter("corruption_detected").inc()
            if quarantined:
                self._scope.counter("corruption_quarantined").inc()

    def quarantine_inventory(self) -> list:
        """Reason dicts of everything under <root>/quarantine/ (served
        in /health detail)."""
        return quar.list_quarantined(self.opts.root)

    def quarantine_fileset_volume(self, namespace: str, shard: int,
                                  block_start: int, volume: int,
                                  err=None) -> None:
        """Engine-locked quarantine of one fileset volume (the
        scrubber's entry point — flushed-block bookkeeping must not
        race ingest/tick)."""
        with self._mu:
            self.namespaces[namespace].shards[shard].quarantine_volume(
                block_start, volume, err
            )

    # ---- placement-driven shard ownership -------------------------------

    def set_ownership_template(self, num_shards: int,
                               owned: Iterable[int] | None) -> None:
        """Ownership applied to namespaces created AFTER the placement
        was observed (dynamic namespace add, downsampler
        ensure_namespace): a new namespace sharing the placement's
        shard space must start placement-scoped, not own-all — without
        this it would silently bypass the ownership invariant until the
        next placement version bump."""
        with self._mu:
            self._ownership_template = (
                int(num_shards), None if owned is None else frozenset(owned))

    def set_shard_ownership(self, namespace: str | None,
                            owned: Iterable[int] | None) -> None:
        """Install the placement-derived shard set this node serves
        (None = own everything, the no-placement default).  Applies to
        one namespace, or to every namespace when ``namespace`` is None
        (the topology watcher's shape: one placement governs the node).
        Takes effect atomically under the engine lock — a mid-batch
        ingest either wholly precedes or wholly follows the swap."""
        with self._mu:
            targets = (self.namespaces.values() if namespace is None
                       else [self.namespaces[namespace]])
            for ns in targets:
                ns.owned = None if owned is None else frozenset(owned)

    def owned_shards(self, namespace: str) -> frozenset | None:
        ns = self.namespaces[namespace]
        return ns.owned

    def drop_shard(self, namespace: str, shard_id: int) -> int:
        """Discard one shard's local state: every fileset volume on
        disk, the in-memory buffers/slots, and cached blocks — the
        post-cutover cleanup of a LEAVING shard (reference dbnode
        closes and deletes shards the topology moved away).  Returns
        the number of fileset volumes removed.  The caller (migrator)
        is responsible for grace: by the time this runs, ownership has
        already been revoked and clients re-routed."""
        with self._mu:
            ns = self.namespaces[namespace]
            sh = ns.shards[shard_id]
            removed = 0
            for bs, vol in list_fileset_volumes(self.opts.root, namespace,
                                                shard_id):
                remove_fileset(self.opts.root, namespace, shard_id, bs, vol)
                self.block_cache.invalidate_block(namespace, shard_id, bs)
                removed += 1
            # A fresh Shard starts empty (the fileset scan above left
            # nothing) — buffers, slots and flushed-block bookkeeping
            # all reset in one swap.
            ns.shards[shard_id] = Shard(
                namespace, shard_id, ns.opts, self.opts.root,
                self.block_cache,
                new_series_limiter=self.new_series_limiter,
                corruption_cb=self._note_corruption,
                snapshot_counters=ns.snapshot_counters,
            )
            _LOG.info("dropped shard ns=%s shard=%d (%d fileset volumes)",
                      namespace, shard_id, removed)
            if self._scope is not None:
                self._scope.counter("shards_dropped").inc()
            return removed

    def ensure_namespace(self, name: str,
                         opts: NamespaceOptions | None = None) -> Namespace:
        """Create-if-missing (the reference adds namespaces dynamically
        through KV-watched namespace metadata, dbnode/namespace/dynamic.go;
        the coordinator provisions aggregated namespaces per policy)."""
        with self._mu:  # racing the mediator's namespace iteration
            ns = self.namespaces.get(name)
            if ns is None:
                ns = self.namespaces[name] = Namespace(
                    name, opts or NamespaceOptions(), self.opts.root,
                    self.block_cache,
                    new_series_limiter=self.new_series_limiter,
                    corruption_cb=self._note_corruption, scope=self._scope,
                )
                tpl = self._ownership_template
                if tpl is not None and tpl[0] == ns.opts.num_shards:
                    ns.owned = tpl[1]  # placement-scoped from birth
            return ns

    def write_batch(self, namespace: str, ids: Sequence[bytes], ts, vals,
                    now_nanos: int | None = None) -> int:
        import time as _time

        ns = self.namespaces[namespace]
        ts = np.asarray(ts, np.int64)
        vals = np.asarray(vals, np.float64)
        if now_nanos is None:
            now_nanos = int(ts.max())
        t0 = _time.perf_counter()
        with self._mu, self.tracer.start_span(
            Tracepoint.DB_WRITE_BATCH, {"n": len(ids), "ns": namespace}
        ):
            if self._scope is not None:
                self._scope.counter("writes").inc(len(ids))
            try:
                res = ns.write_batch(ids, ts, vals, now_nanos)
            except ShardNotOwnedError:
                if self._scope is not None:
                    self._scope.counter("shard_not_owned").inc()
                raise
            if self._scope is not None and getattr(res, "not_owned", 0):
                self._scope.counter("shard_not_owned").inc(res.not_owned)
            if self._scope is not None and res.rejected:
                self._scope.counter("new_series_rejected").inc(res.rejected)
            # Log AFTER acceptance so the WAL never contains
            # rate-limit-rejected samples (the reference writes the
            # commitlog after the in-memory write succeeds, as an async
            # enqueue - commit_log.go:716).  Bootstrap replay then
            # re-admits exactly the accepted set, bypassing the limiter.
            if self.commitlog is not None:
                with self.tracer.start_span(Tracepoint.DB_COMMITLOG_WRITE):
                    if res.accepted is None:
                        self.commitlog.write_batch(
                            list(ids), ts, vals,
                            namespace=namespace.encode())
                    else:
                        acc = res.accepted
                        self.commitlog.write_batch(
                            [sid for sid, a in zip(ids, acc) if a],
                            ts[acc], vals[acc], namespace=namespace.encode())
            if self._hist_write is not None:
                self._hist_write.record(_time.perf_counter() - t0)
            return res

    def write_tagged_batch(self, namespace: str, docs: Sequence[Document], ts, vals,
                           now_nanos: int | None = None) -> int:
        import time as _time

        ns = self.namespaces[namespace]
        ts = np.asarray(ts, np.int64)
        vals = np.asarray(vals, np.float64)
        if now_nanos is None:
            now_nanos = int(ts.max())
        t0 = _time.perf_counter()
        with self._mu, self.tracer.start_span(
            Tracepoint.DB_WRITE_BATCH, {"n": len(docs), "ns": namespace,
                                        "tagged": True}
        ):
            if self._scope is not None:
                self._scope.counter("writes_tagged").inc(len(docs))
            try:
                res = ns.write_tagged_batch(docs, ts, vals, now_nanos)
            except ShardNotOwnedError:
                if self._scope is not None:
                    self._scope.counter("shard_not_owned").inc()
                raise
            if self._scope is not None and getattr(res, "not_owned", 0):
                self._scope.counter("shard_not_owned").inc(res.not_owned)
            if self._scope is not None and res.rejected:
                self._scope.counter("new_series_rejected").inc(res.rejected)
            if self.commitlog is not None:
                # Tags ride the annotation field so WAL replay can rebuild
                # index documents (the reference's commitlog entries carry
                # the series metadata for the same reason).  Only the
                # ACCEPTED samples are logged - see write_batch.
                with self.tracer.start_span(Tracepoint.DB_COMMITLOG_WRITE):
                    if res.accepted is None:
                        kept = list(docs)
                        kts, kvs = ts, vals
                    else:
                        kept = [d for d, a in zip(docs, res.accepted) if a]
                        kts, kvs = ts[res.accepted], vals[res.accepted]
                    if kept:
                        self.commitlog.write_batch(
                            [d.id for d in kept], kts, kvs,
                            namespace=namespace.encode(),
                            annotations=[encode_tags(d) for d in kept],
                        )
            if self._hist_write is not None:
                self._hist_write.record(_time.perf_counter() - t0)
            return res

    def query_ids(self, namespace: str, q: Query, start: int, end: int):
        with self._mu, self.tracer.start_span(
            Tracepoint.DB_QUERY_IDS, {"ns": namespace}
        ):
            # windowed per-query limit, incremented DURING matching so a
            # heavy query aborts mid-match (reference storage/limits)
            return self.namespaces[namespace].query_ids(
                q, start, end, inc_docs=self.limits.inc_docs
            )

    def read(self, namespace: str, sid: bytes, start: int, end: int):
        if self._scope is not None:
            self._scope.counter("reads").inc()
        self.limits.inc_series(1)
        # bytes pre-check: an already-exhausted window rejects the read
        # BEFORE decoding; the exact size still accounts afterwards (it
        # is unknowable until decoded).
        self.limits.inc_bytes(0)
        with self._mu, self.tracer.start_span(Tracepoint.DB_READ):
            pts = self.namespaces[namespace].read(sid, start, end)
        # 16 bytes per (ts, value) sample — the bytes-read accounting unit
        self.limits.inc_bytes(16 * len(pts))
        return pts

    def read_batch(self, namespace: str, sids: Sequence[bytes],
                   start: int, end: int) -> list[list[tuple[int, float]]]:
        """Batched :meth:`read` as point lists (one engine-lock
        acquisition, one sorted-window snapshot per open block instead
        of per id): the RPC ``read_batch`` / session ``fetch_batch``
        storage entry; an unowned shard raises for the whole batch.
        The query engine's entry is :meth:`read_columns`; both hold the
        lock only while `_fetch` plans.  Same limits accounting units
        as the single-id path."""
        if self._scope is not None:
            self._scope.counter("reads").inc(len(sids))
        self.limits.inc_series(len(sids))
        self.limits.inc_bytes(0)
        ns = self.namespaces[namespace]
        with self._fetch(len(sids),
                         lambda: ns.plan_many(sids, start, end)) as (_, plan):
            out = ns.read_many(plan)
        self.limits.inc_bytes(16 * sum(len(p) for p in out))
        return out

    def read_columns(self, namespace: str, sids: Sequence[bytes],
                     start: int, end: int) -> SeriesColumns:
        """All series of a selector in one call, as the query block's
        columns (`Namespace.read_columns`): the local query engine's
        storage entry (`query/storage_adapter.DatabaseStorage`).  One
        engine-lock acquisition and one ``db.read`` span a fetch, whose
        tags say how many series were asked (``n``) and how many were
        answered from arrays alone (``columnar``; also counted on
        /metrics as ``fetch_series`` / ``fetch_series_columnar``; the
        series merged and cut after the release, `_fetch`, as
        ``fetch_series_unlocked``).  Series of shards this node does
        not own are left out, not raised.  Same limits accounting units
        as :meth:`read`."""
        n = len(sids)
        if self._scope is not None:
            self._scope.counter("reads").inc(n)
        self.limits.inc_series(n)
        self.limits.inc_bytes(0)
        ns = self.namespaces[namespace]
        with self._fetch(n, lambda: ns.plan_columns(sids, start, end)) as (
                sp, plan):
            cols = ns.read_columns(plan)
            sp.set_tag("columnar", cols.columnar)
        if self._scope is not None:
            self._scope.counter("fetch_series").inc(n)
            self._scope.counter("fetch_series_columnar").inc(cols.columnar)
            self._scope.counter("fetch_series_unlocked").inc(n)
        self.limits.inc_bytes(16 * int(cols.counts.sum()))
        return cols

    @contextlib.contextmanager
    def _fetch(self, n: int, plan):
        """One batch fetch's ``db.read`` span, ``(span, plan())``, with
        `_mu` held only while ``plan()`` takes the fetch's read plan
        (`Namespace._plan`, under a ``db.read.locked`` span, tags ``n``
        and ``streams``: the segments read): the decode, merge and cut
        that the ``with`` body runs on the plan follow the release, so
        fetches decode side by side and a write does not wait for them.
        The acquisition's ``db.lock.wait`` stands before ``db.read``,
        not in it."""
        self._mu.acquire()
        held = True
        try:
            with self.tracer.start_span(Tracepoint.DB_READ, {"n": n}) as sp:
                with self.tracer.start_span(
                        Tracepoint.DB_READ_LOCKED, {"n": n}) as locked:
                    p = plan()
                    locked.set_tag("streams", p.streams)
                held = False
                self._mu.release()
                yield sp, p
        finally:
            if held:
                self._mu.release()

    def tick(self, now_nanos: int) -> dict:
        import time as _time

        t0 = _time.perf_counter()
        with self._mu, self.tracer.start_span(Tracepoint.DB_TICK):
            stats = {}
            for name, ns in self.namespaces.items():
                stats[name] = ns.tick(now_nanos)
        if self._hist_tick is not None:
            self._hist_tick.record(_time.perf_counter() - t0)
        return stats

    # ---- block-level replication surface -------------------------------
    # The handle interface repair and peers bootstrap run against; the
    # socket RPC (server/rpc.py) exports exactly these four methods so a
    # replica works the same whether it is this object or a remote node
    # (reference FetchBlocksMetadataRawV2 `node/service.go:1529` + the
    # peer block streaming in `client/peer.go`).

    def list_block_filesets(self, namespace: str, shard: int):
        """[(block_start, latest volume)] flushed for the shard."""
        from m3_tpu.persist.fs import list_filesets

        return sorted(list_filesets(self.opts.root, namespace, shard))

    def block_metadata(self, namespace: str, shard: int, block_start: int):
        """Per-series stream checksums for one flushed block, or None
        when no fileset exists for it.

        Served from the fileset's index entries alone (the writer stores
        adler32-of-segment per entry), never touching the data file —
        the metadata-only property of the reference's
        FetchBlocksMetadataRawV2."""
        from m3_tpu.persist.fs import DataFileSetReader, list_filesets

        filesets = dict(list_filesets(self.opts.root, namespace, shard))
        if block_start not in filesets:
            return None
        r = DataFileSetReader(
            self.opts.root, namespace, shard, block_start, filesets[block_start]
        )
        return {e.id: e.checksum for e in r.entries()}

    def read_block(self, namespace: str, shard: int, block_start: int):
        """All (series id, encoded stream) pairs of one flushed block;
        [] when the block has no fileset."""
        from m3_tpu.persist.fs import DataFileSetReader, list_filesets

        filesets = dict(list_filesets(self.opts.root, namespace, shard))
        if block_start not in filesets:
            return []
        r = DataFileSetReader(
            self.opts.root, namespace, shard, block_start, filesets[block_start]
        )
        return list(r.read_all())

    def write_block(self, namespace: str, shard: int, block_start: int,
                    series) -> None:
        """Persist a full block's series as the next fileset volume and
        mark it flushed (repair rewrite / peers-bootstrap load)."""
        from m3_tpu.persist.fs import DataFileSetWriter, list_filesets

        with self._mu:
            ns = self.namespaces[namespace]
            # A non-owner must not accept streamed blocks: repair
            # writing a merged block at a decommissioned replica would
            # resurrect data the topology moved away (callers treat
            # this like any per-replica failure and skip the replica).
            ns.check_owned(shard)
            filesets = dict(list_filesets(self.opts.root, namespace, shard))
            vol = filesets.get(block_start, -1) + 1
            DataFileSetWriter(
                self.opts.root, namespace, shard, block_start,
                ns.opts.block_size_nanos, volume=vol,
            ).write_all(sorted(series))
            ns.shards[shard].flushed_blocks.add(block_start)

    def snapshot(self) -> dict:
        """Capture every namespace's un-flushed buffers as snapshot
        filesets (reference mediator.go:318 runFileSystemProcesses →
        buffer.Snapshot; metadata commit gates visibility).  The commit
        log rotates first so the snapshot covers everything in the
        now-inactive logs — recovery then replays only seq >= the active
        log (`snapshot_metadata_write.go` commitlog-identifier role)."""
        import time as _time

        t0 = _time.perf_counter()
        with self._mu, self.tracer.start_span(Tracepoint.DB_SNAPSHOT):
            seq = snap.next_snapshot_seq(self.opts.root)
            if self.commitlog is not None:
                self.commitlog.rotate()
                cl_seq = self.commitlog.seq
            else:
                cl_seq = 0
            snap_root = str(snap.snapshot_data_root(self.opts.root, seq))
            written = 0
            index_segs = 0
            for ns in self.namespaces.values():
                for shard in ns.shards:
                    written += shard.snapshot_blocks(snap_root)
                index_segs += ns.index.snapshot_mutable(snap_root)
            snap.commit_snapshot(self.opts.root, seq, cl_seq)
        if self._hist_snapshot is not None:
            self._hist_snapshot.record(_time.perf_counter() - t0)
        return {"seq": seq, "series_blocks": written, "index_segments": index_segs}

    def cleanup(self, now_nanos: int) -> dict:
        """Expired-data cleanup (reference `storage/cleanup.go`):
        out-of-retention fileset volumes, superseded (non-max) volumes,
        all-but-latest snapshots, and commitlogs fully covered by the
        latest snapshot."""
        stats = {"filesets": 0, "snapshots": 0, "commitlogs": 0}
        with self._mu:
            return self._cleanup_locked(now_nanos, stats)

    def _cleanup_locked(self, now_nanos: int, stats: dict) -> dict:
        for ns in self.namespaces.values():
            cutoff = now_nanos - ns.opts.retention_nanos - ns.opts.block_size_nanos
            for shard in ns.shards:
                vols = list_fileset_volumes(self.opts.root, ns.name, shard.shard_id)
                max_vol = {}
                for bs, vol in vols:
                    max_vol[bs] = max(max_vol.get(bs, -1), vol)
                for bs, vol in vols:
                    if bs <= cutoff or vol < max_vol[bs]:
                        remove_fileset(self.opts.root, ns.name, shard.shard_id, bs, vol)
                        self.block_cache.invalidate_block(
                            ns.name, shard.shard_id, bs
                        )
                        stats["filesets"] += 1
                        if bs <= cutoff:
                            shard.flushed_blocks.discard(bs)
        # Quarantine entries age out WITH their data's retention: once
        # the block is out of retention everywhere, the evidence (and
        # the scrubber's repair worklist entry) has nothing left to
        # heal toward — without this the inventory and /health payload
        # grow forever.
        import shutil as _shutil

        max_keep = max(
            (ns.opts.retention_nanos + ns.opts.block_size_nanos
             for ns in self.namespaces.values()),
            default=48 * 3600 * 10**9,
        )
        for entry in quar.list_quarantined(self.opts.root):
            ns = self.namespaces.get(entry.get("namespace"))
            bs = entry.get("block_start")
            if ns is not None and isinstance(bs, int):
                expired = (bs <= now_nanos - ns.opts.retention_nanos
                           - ns.opts.block_size_nanos)
            else:
                # No retention anchor (quarantined snapshots, dropped
                # namespaces, unreadable reasons): age out on the
                # wall-clock quarantine time against the longest
                # retention any namespace keeps.
                qa = entry.get("quarantined_at")
                expired = (isinstance(qa, (int, float))
                           and qa * 1e9 <= now_nanos - max_keep)
            if expired:
                _shutil.rmtree(entry["dir"], ignore_errors=True)
                stats["quarantine_reaped"] = stats.get("quarantine_reaped", 0) + 1
        stats["snapshots"] = snap.prune_snapshots(self.opts.root, keep=1)
        latest = snap.latest_snapshot(self.opts.root)
        for log in list_commitlogs(self.opts.root):
            if self.commitlog is not None and log == self.commitlog.path:
                continue
            if latest is not None and commitlog_seq(log) < latest.commitlog_seq:
                log.unlink(missing_ok=True)
                stats["commitlogs"] += 1
            elif self._commitlog_fully_flushed(log):
                # Size-rotated segments (rotate_bytes) are not covered
                # by any snapshot, so without this check they live to
                # retention — a segment every entry of which is durable
                # in a checkpointed fileset protects nothing.
                log.unlink(missing_ok=True)
                stats["commitlogs"] += 1
        return stats

    def _commitlog_fully_flushed(self, log) -> bool:
        """True iff EVERY entry in the (inactive) segment is durable in
        a checkpointed fileset: its block is flushed and nothing for
        that block is still pending in the warm/cold buffers.  Entries
        for unknown namespaces or unflushed blocks keep the segment
        (conservative — replay may still need it)."""
        try:
            for e in read_commitlog(log):
                ns = self.namespaces.get(e.namespace.decode())
                if ns is None:
                    return False
                shard = ns.shards[
                    shard_for_id(e.series_id, ns.opts.num_shards)]
                bs = (e.timestamp // ns.opts.block_size_nanos
                      * ns.opts.block_size_nanos)
                if bs not in shard.flushed_blocks:
                    return False
                if (bs in shard.buffer.open_blocks
                        or bs in shard.buffer.cold):
                    return False
        except OSError:
            return False
        return True

    def _replay_entries(self, name: str, entries: list,
                        flushed_pts: Dict[tuple, dict] | None = None) -> int:
        """Write recovered entries into a namespace's buffers, skipping
        blocks already covered by a checkpointed fileset (the fs
        bootstrapper's unfulfilled-ranges rule).  Entries whose
        annotation carries encoded tags re-index their document too, so
        recovery rebuilds the (unsealed) reverse index.  Never re-logs."""
        ns = self.namespaces.get(name)
        if ns is None:
            return 0
        if ns.owned is not None:
            # Placement-scoped recovery: WAL/snapshot entries for shards
            # this node no longer owns are NOT re-buffered (a restarting
            # ex-donor must not resurrect handed-off shards; the new
            # owner already streamed or re-ingested them).
            entries = [
                e for e in entries
                if shard_for_id(e.series_id, ns.opts.num_shards) in ns.owned
            ]
            if not entries:
                return 0
        ts = np.asarray([e.timestamp for e in entries], np.int64)
        vals = np.asarray([e.value for e in entries], np.float64)
        ids = [e.series_id for e in entries]
        keep = np.ones(len(ts), bool)
        # Lazy cache of fileset contents for flushed blocks touched by
        # recovery: a point already in the fileset is a duplicate (drop);
        # a point absent from it is a pending cold write that crashed
        # before cold_flush — keep it, and write_batch re-routes it cold
        # because the flushed block is not in open_starts.  The caller
        # (bootstrap) shares one cache across all logs so each fileset
        # decodes once, not once per commitlog file.
        if flushed_pts is None:
            flushed_pts = {}
        for i, sid in enumerate(ids):
            shard_id = shard_for_id(sid, ns.opts.num_shards)
            sh = ns.shards[shard_id]
            bs = int(ts[i]) // ns.opts.block_size_nanos * ns.opts.block_size_nanos
            if bs not in sh.flushed_blocks:
                continue
            key = (name, shard_id, bs)
            if key not in flushed_pts:
                # Decode the highest INTACT volume; a corrupt one is
                # quarantined and a lower volume tried.  When nothing
                # intact remains the dedupe set is empty, so every WAL
                # entry for the block is KEPT and re-buffered — replay
                # re-covers exactly the data the corrupt fileset lost.
                def _decode_timestamps(vol, _bs=bs, _shard=shard_id):
                    r = DataFileSetReader(
                        self.opts.root, ns.name, _shard, _bs, vol
                    )
                    return {
                        fsid: {d.timestamp for d in decode_series(seg)}
                        for fsid, seg in r.read_all()
                    }

                flushed_pts[key] = (
                    sh._fold_intact_volumes(bs, _decode_timestamps) or {}
                )
            if int(ts[i]) in flushed_pts[key].get(sid, ()):
                keep[i] = False
        if not keep.any():
            return 0
        kept = np.nonzero(keep)[0]
        now = int(ts.max())
        tagged_idx = []
        tagged_docs = []
        for i in kept:
            ann = entries[i].annotation
            doc = decode_tags(ids[i], ann) if ann else None
            if doc is not None:
                tagged_idx.append(i)
                tagged_docs.append(doc)
        if tagged_docs:
            sel = np.asarray(tagged_idx)
            ns.write_tagged_batch(tagged_docs, ts[sel], vals[sel], now)
        tagged_set = set(tagged_idx)
        plain = [i for i in kept if i not in tagged_set]
        if plain:
            sel = np.asarray(plain)
            ns.write_batch([ids[i] for i in plain], ts[sel], vals[sel], now)
        return len(kept)

    def bootstrap(self) -> dict:
        """fs → snapshot → commitlog bootstrap chain (reference
        `storage/bootstrap/process.go` + bootstrapper/README.md: filesets
        first, then the latest snapshot, then WAL-tail replay for whatever
        isn't covered — `bootstrapper/commitlog` reads snapshots + WAL)."""
        with self._mu, self.tracer.start_span(Tracepoint.DB_BOOTSTRAP):
            # Replay re-admits previously-ACCEPTED series: the limiter
            # gates foreground churn only (the WAL never contains
            # rejected samples - see write_batch's log-after-accept).
            with self.new_series_limiter.bypass():
                return self._bootstrap_locked()

    def _bootstrap_locked(self) -> dict:
        # Torn-write sweep FIRST: a crash (or classified ENOSPC whose
        # unlink itself failed) between temp-write and rename leaves a
        # dead ``*.tmp*`` beside the real artifact — invisible to every
        # reader but holding disk the ledger would count forever.
        swept = cap.sweep_temp_files(self.opts.root)
        if swept:
            _LOG.info("bootstrap: swept %d torn temp file(s)", len(swept))
        restored = 0
        flushed_pts: Dict[tuple, dict] = {}  # shared fileset-decode cache
        latest = snap.latest_snapshot(self.opts.root)
        if latest is not None:
            snap_root = str(snap.snapshot_data_root(self.opts.root, latest.seq))
            for name, ns in self.namespaces.items():
                ns.index.restore_snapshot(snap_root)
                for shard in ns.shards:
                    entries: list[CommitLogEntry] = []
                    for bs, vol in list_filesets(snap_root, name, shard.shard_id):
                        try:
                            r = DataFileSetReader(
                                snap_root, name, shard.shard_id, bs, vol
                            )
                            for sid, seg in r.read_all():
                                entries.extend(
                                    CommitLogEntry(sid, d.timestamp, d.value,
                                                   namespace=name.encode())
                                    for d in decode_series(seg)
                                )
                        except CorruptionError as e:
                            # A rotted snapshot fileset must not abort
                            # node start: quarantine it (under the DB
                            # root) and keep whatever decoded cleanly —
                            # replicas/repair re-converge the remainder.
                            qdir = quar.quarantine_fileset(
                                snap_root, name, shard.shard_id, bs, vol, e,
                                qroot=self.opts.root,
                                label=f"snapshot-{latest.seq}",
                            )
                            _LOG.warning(
                                "quarantined corrupt snapshot fileset "
                                "seq=%d ns=%s shard=%d block=%d vol=%d: %s",
                                latest.seq, name, shard.shard_id, bs, vol, e,
                            )
                            self._note_corruption(
                                name, shard.shard_id, bs, vol, e,
                                quarantined=qdir is not None)
                    if entries:
                        restored += self._replay_entries(name, entries, flushed_pts)
        replayed = 0
        min_seq = latest.commitlog_seq if latest is not None else -1
        for log in list_commitlogs(self.opts.root):
            if self.commitlog is not None and log == self.commitlog.path:
                continue
            if commitlog_seq(log) < min_seq:
                continue  # fully covered by the snapshot
            per_ns: Dict[str, list] = {}
            for e in read_commitlog(log):
                per_ns.setdefault(e.namespace.decode(), []).append(e)
            for name, entries in per_ns.items():
                replayed += self._replay_entries(name, entries, flushed_pts)
        self.bootstrapped = True
        return {"commitlog_replayed": replayed, "snapshot_restored": restored,
                "temp_files_swept": len(swept)}

    def close(self) -> None:
        with self._mu:
            if self.commitlog is not None:
                self.commitlog.close()
