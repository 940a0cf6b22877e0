"""The `DatabaseStorage` -> `Database` seam: a selector's series cross it
once, as a batch, and arrive as the block's columns.

`Database.read_columns` must answer, bit for bit, what
`RawBlock.from_lists` builds over per-id `Database.read` (the single-id
path through `Shard.read_sources` + `merge_point_sources`, which shares
no code with the batch), for every kind of source a shard can hold.
"""

import struct

import numpy as np
import pytest

from m3_tpu.index.doc import Document
from m3_tpu.index.search import Term
from m3_tpu.instrument.tracing import Tracepoint, Tracer
from m3_tpu.query.block import RawBlock, SeriesMeta
from m3_tpu.query.storage_adapter import DatabaseStorage
from m3_tpu.storage.database import (
    Database, DatabaseOptions, NamespaceOptions, Shard, shard_for_id,
)
from m3_tpu.storage.limits import LimitsOptions, QueryLimitExceeded, QueryLimits
from m3_tpu.x import deadline as xdeadline
from m3_tpu.x.deadline import Deadline, DeadlineExceeded

SEC = 10**9
MIN = 60 * SEC
BLOCK = 2 * 3600 * SEC
T0 = (1_600_000_000 * SEC) // BLOCK * BLOCK
N = 12  # series of the selector; every write is one sample of each


def _docs(name=b"m", n=N):
    return [Document.from_tags(name + b"{i=%02d}" % i,
                               {b"__name__": name, b"i": b"%02d" % i})
            for i in range(n)]


def _db(tmp_path, num_shards, **kw):
    return Database(
        DatabaseOptions(root=str(tmp_path / "db"), commitlog_enabled=False),
        namespaces={"default": NamespaceOptions(
            num_shards=num_shards, slot_capacity=64, sample_capacity=256)},
        **kw)


def _scrape(db, docs, t, vals=None, now=None, salt=0.0):
    """One sample of every series at ``t`` (full-mantissa values)."""
    if vals is None:
        vals = np.array([(i + 1) / 7.0 + t / SEC % 1000 / 3.0 + salt
                         for i in range(len(docs))])
    db.write_tagged_batch("default", docs, np.full(len(docs), t), vals,
                          now_nanos=t if now is None else now)


def one_open_block(db):
    docs = _docs()
    for k in range(20):
        _scrape(db, docs, T0 + MIN + k * 15 * SEC)
    return T0, T0 + 3600 * SEC


def two_open_blocks(db):
    docs = _docs()
    now = T0 + BLOCK + 5 * MIN  # both blocks inside [now - 10 m, now + 2 m]
    for k in range(8):
        _scrape(db, docs, T0 + BLOCK - 4 * MIN + k * 15 * SEC, now=now)
        _scrape(db, docs, T0 + BLOCK + k * 15 * SEC, now=now)
    assert all(len(sh.buffer.open_blocks) == 2
               for sh in db.namespaces["default"].shards)
    return T0 + BLOCK - 3 * MIN, T0 + BLOCK + MIN


def fileset_and_buffer(db):
    """A flushed volume and the open buffer holding the same block (the
    state between a flush's volume landing and its window's discard),
    the buffer rewriting some of the volume's timestamps: later wins."""
    docs = _docs()
    for k in range(6):
        _scrape(db, docs, T0 + k * 15 * SEC)
    db.tick(T0 + BLOCK + 11 * MIN)
    for sh in db.namespaces["default"].shards:
        assert T0 in sh.flushed_blocks
        sh.flushed_blocks.discard(T0)  # the block takes warm writes again
    for k in (2, 4, 7):
        _scrape(db, docs, T0 + k * 15 * SEC, salt=0.5)
    # and the next block, open, in the same range
    _scrape(db, docs, T0 + BLOCK + 15 * SEC, now=T0 + BLOCK + 11 * MIN)
    return T0, T0 + BLOCK + MIN


def cold_overflow(db):
    """Cold parts over a flushed block: three arrivals at one timestamp
    (the last wins), one of them twice inside a single part."""
    docs = _docs()
    for k in range(6):
        _scrape(db, docs, T0 + k * 15 * SEC)
    now = T0 + BLOCK + 11 * MIN
    db.tick(now)
    _scrape(db, docs, T0 + BLOCK + 15 * SEC, now=now)
    _scrape(db, docs, T0 + 3 * 15 * SEC, now=now, salt=0.25)  # over the volume
    _scrape(db, docs, T0 + 9 * 15 * SEC, now=now, salt=0.5)
    _scrape(db, docs, T0 + 9 * 15 * SEC, now=now, salt=0.75)
    twice = docs + docs
    db.write_tagged_batch(
        "default", twice, np.full(2 * N, T0 + 8 * 15 * SEC),
        np.arange(2 * N) / 3.0, now_nanos=now)
    _scrape(db, docs, T0 + 7 * 15 * SEC, now=now)  # out of time order
    assert any(sh.buffer.cold for sh in db.namespaces["default"].shards)
    return T0, T0 + BLOCK + MIN


def cold_alone(db):
    """Late writes into a block that was never open nor flushed: the
    cold parts are the only source, in arrival order."""
    docs = _docs()
    now = T0 + 3 * BLOCK
    _scrape(db, docs, now)
    for k in (5, 2, 9, 2, 7):
        _scrape(db, docs, T0 + k * 15 * SEC, now=now, salt=k / 16.0)
    _scrape(db, docs, T0 + 2 * 15 * SEC, now=now, salt=0.875)  # stays
    return T0, T0 + BLOCK


def duplicate_writes(db):
    docs = _docs()
    for k in range(6):
        _scrape(db, docs, T0 + MIN + k * 15 * SEC)
    _scrape(db, docs, T0 + MIN + 2 * 15 * SEC, salt=0.5)
    _scrape(db, docs, T0 + MIN + 2 * 15 * SEC, salt=0.75)  # this one stays
    _scrape(db, docs, T0 + MIN + 15 * SEC, salt=0.25)
    return T0, T0 + 3600 * SEC


def indexed_without_slot(db):
    docs = _docs()
    for k in range(4):
        _scrape(db, docs[: N - 3], T0 + MIN + k * 15 * SEC)
    # the index knows three series no shard ever buffered
    db.namespaces["default"].index.write_batch(
        docs[N - 3:], np.full(3, T0 + MIN))
    return T0, T0 + 3600 * SEC


def range_on_samples(db):
    docs = _docs()
    for k in range(10):
        _scrape(db, docs, T0 + MIN + k * 15 * SEC)
    # start on a sample (kept), end on a sample (left out)
    return T0 + MIN + 2 * 15 * SEC, T0 + MIN + 7 * 15 * SEC


def nan_and_signed_zero(db):
    docs = _docs()
    odd = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 5e-324, -1e308,
                    np.float64(0.1) + np.float64(0.2), 1 / 3, -2 / 3, 1e300,
                    2.0 ** 53 + 2])
    quiet = np.array([0x7FF8_0000_0000_BEEF], np.uint64).view(np.float64)[0]
    for k in range(5):
        _scrape(db, docs, T0 + MIN + k * 15 * SEC, vals=np.roll(odd, k))
    _scrape(db, docs, T0 + MIN + 5 * 15 * SEC, vals=np.full(N, quiet))
    return T0, T0 + 3600 * SEC


def empty_match(db):
    _scrape(db, _docs(b"other"), T0 + MIN)
    return T0, T0 + 3600 * SEC


def some_rows_empty(db):
    """Half of the series have nothing in range: their rows are all
    padding, and the others keep their columns."""
    docs = _docs()
    for k in range(4):
        _scrape(db, docs, T0 + MIN + k * 15 * SEC)
    half = docs[: N // 2]
    for k in range(3):
        db.write_tagged_batch(
            "default", half, np.full(len(half), T0 + 30 * MIN + k * 15 * SEC),
            np.arange(len(half)) + k / 3.0, now_nanos=T0 + 30 * MIN)
    return T0 + 20 * MIN, T0 + 3600 * SEC


SCENARIOS = [one_open_block, two_open_blocks, fileset_and_buffer,
             cold_overflow, cold_alone, duplicate_writes, indexed_without_slot,
             range_on_samples, nan_and_signed_zero, empty_match,
             some_rows_empty]


@pytest.fixture(scope="module",
                params=[(s, n) for s in SCENARIOS for n in (1, 4)],
                ids=lambda p: f"{p[0].__name__}-shards{p[1]}")
def world(request, tmp_path_factory):
    scenario, num_shards = request.param
    db = _db(tmp_path_factory.mktemp("w"), num_shards)
    start, end = scenario(db)
    yield db, start, end
    db.close()


def _loop_block(db, start, end, name=b"m"):
    """The block as the per-series loop built it."""
    docs = db.query_ids("default", Term(b"__name__", name), start, end)
    docs.sort(key=lambda d: d.id)
    return RawBlock.from_lists(
        [db.read("default", d.id, start, end) for d in docs],
        [SeriesMeta(tuple(sorted(d.tags().items()))) for d in docs])


def _assert_same_block(got: RawBlock, want: RawBlock):
    assert got.series == want.series
    assert got.ts.dtype == np.int64 and got.values.dtype == np.float64
    assert got.counts.dtype == np.int64
    assert np.array_equal(got.counts, want.counts)
    assert got.ts.shape == want.ts.shape
    assert np.array_equal(got.ts, want.ts)
    # bits, not values: NaN payloads, signed zeros and the NaN padding
    assert np.array_equal(got.values.view(np.uint64),
                          want.values.view(np.uint64))


class TestColumnsEqualTheLoop:
    def test_fetch_raw_is_the_loops_block(self, world):
        db, start, end = world
        got = DatabaseStorage(db).fetch_raw(b"m", (), start, end)
        _assert_same_block(got, _loop_block(db, start, end))

    def test_read_batch_keeps_its_answers(self, world):
        db, start, end = world
        ids = [d.id for d in _docs()] + [b"never-written"]
        bits = lambda rows: [[(t, struct.pack("<d", v)) for t, v in pts]  # noqa: E731
                             for pts in rows]
        assert bits(db.read_batch("default", ids, start, end)) == bits(
            [db.read("default", sid, start, end) for sid in ids])

    def test_scenario_holds_what_it_names(self, world, request):
        """The worlds are not all one open block by accident."""
        db, start, end = world
        want = _loop_block(db, start, end)
        shards = db.namespaces["default"].shards
        name = request.node.callspec.id
        if name.startswith("empty_match"):
            assert want.ts.shape == (0, 1) and want.series == []
        else:
            assert len(want.series) == N
        if name.startswith("indexed_without_slot"):
            assert sorted(want.counts.tolist()) == [0] * 3 + [4] * (N - 3)
        if name.startswith("some_rows_empty"):
            assert sorted(want.counts.tolist()) == [0] * 6 + [3] * 6
        if name.startswith("range_on_samples"):
            assert want.ts[0, 0] == start and want.counts.tolist() == [5] * N
        if name.startswith("duplicate_writes"):
            assert want.counts.tolist() == [6] * N
        if name.startswith("cold_overflow"):
            assert want.counts.tolist() == [10] * N
            # the doubled part: each series' second copy is the one kept
            col = 7  # timestamps 0..5, 7, 8, 9
            assert want.ts[0, col] == T0 + 8 * 15 * SEC
            assert sorted(want.values[:, col].tolist()) == [
                (N + i) / 3.0 for i in range(N)]
        if name.startswith("cold_alone"):
            assert want.counts.tolist() == [4] * N
            assert not any(sh.buffer.open_blocks.get(T0) for sh in shards)
        if name.startswith("fileset_and_buffer"):
            assert want.counts.tolist() == [8] * N
            held = {shard_for_id(d.id, len(shards)) for d in _docs()}
            for sh in (shards[i] for i in held):
                assert T0 in sh.buffer.open_blocks
                assert T0 in dict(
                    db.list_block_filesets("default", sh.shard_id))


class TestSeamSemantics:
    def test_unowned_shards_are_skipped_and_rows_stay_aligned(self, tmp_path):
        db = _db(tmp_path, 4)
        docs = _docs()
        for k in range(5):
            _scrape(db, docs, T0 + MIN + k * 15 * SEC)
        by_shard = {}
        for d in docs:
            by_shard.setdefault(shard_for_id(d.id, 4), []).append(d.id)
        assert len(by_shard) >= 2
        owned = sorted(by_shard)[::2]
        db.set_shard_ownership("default", owned)
        got = DatabaseStorage(db).fetch_raw(b"m", (), T0, T0 + 3600 * SEC)
        kept = sorted(i for sh in owned for i in by_shard[sh])
        assert 0 < len(kept) < N
        assert [dict(m.tags)[b"i"] for m in got.series] == [
            sid[4:6] for sid in kept]
        want = RawBlock.from_lists(
            [db.read("default", sid, T0, T0 + 3600 * SEC) for sid in kept],
            got.series)
        _assert_same_block(got, want)
        cols = db.read_columns("default", [d.id for d in docs], T0,
                               T0 + 3600 * SEC)
        assert [docs[i].id for i in cols.index.tolist()] == kept
        assert cols.columnar == len(kept)
        db.close()

    def test_series_limit_aborts_with_its_typed_error(self, tmp_path):
        db = _db(tmp_path, 2,
                 limits=QueryLimits(LimitsOptions(max_series_read=N - 1)))
        _scrape(db, _docs(), T0 + MIN)
        with pytest.raises(QueryLimitExceeded):
            DatabaseStorage(db).fetch_raw(b"m", (), T0, T0 + 3600 * SEC)
        db.close()

    def test_bytes_limit_counts_sixteen_a_point(self, tmp_path):
        db = _db(tmp_path, 2,
                 limits=QueryLimits(LimitsOptions(max_bytes_read=16 * N * 3)))
        for k in range(3):
            _scrape(db, _docs(), T0 + MIN + k * 15 * SEC)
        st = DatabaseStorage(db)
        st.fetch_raw(b"m", (), T0, T0 + 3600 * SEC)  # exactly the limit
        with pytest.raises(QueryLimitExceeded):
            st.fetch_raw(b"m", (), T0, T0 + 3600 * SEC)
        db.close()

    def test_spent_deadline_aborts_before_the_batch(self, tmp_path,
                                                     monkeypatch):
        db = _db(tmp_path, 2)
        _scrape(db, _docs(), T0 + MIN)
        monkeypatch.setattr(
            Database, "read_columns",
            lambda *a, **k: pytest.fail("read after the deadline"))
        dl = Deadline(30.0)
        dl.cancel()
        with xdeadline.bind(dl), pytest.raises(DeadlineExceeded):
            DatabaseStorage(db).fetch_raw(b"m", (), T0, T0 + 3600 * SEC)
        db.close()

    def test_cancelled_query_stops_between_shards(self, tmp_path,
                                                  monkeypatch):
        db = _db(tmp_path, 4)
        _scrape(db, _docs(), T0 + MIN)
        dl = Deadline(30.0)
        read = []
        real = Shard.read_columns

        def cancel_after_first(self, *args):
            read.append(self.shard_id)
            dl.cancel()
            return real(self, *args)

        monkeypatch.setattr(Shard, "read_columns", cancel_after_first)
        with xdeadline.bind(dl), pytest.raises(DeadlineExceeded):
            DatabaseStorage(db).fetch_raw(b"m", (), T0, T0 + 3600 * SEC)
        assert len(read) == 1
        # and the engine lock was let go
        assert db._mu.acquire(blocking=False)
        db._mu.release()
        db.close()


def _fetched(db, how, start, end):
    """A fetch's answer as bits: the columns, or the point lists."""
    ids = [d.id for d in _docs()] + [b"never-written"]
    if how == "columns":
        c = db.read_columns("default", ids, start, end)
        return (c.ts.tolist(), c.values.view(np.uint64).tolist(),
                c.counts.tolist(), c.index.tolist(), c.columnar)
    return [[(t, struct.pack("<d", v)) for t, v in pts]
            for pts in db.read_batch("default", ids, start, end)]


def _late_write(db):
    """New points in the open block, a cold one over the flushed block."""
    now = T0 + BLOCK + 11 * MIN
    _scrape(db, _docs(), T0 + BLOCK + 30 * SEC, now=now, salt=0.125)
    _scrape(db, _docs(), T0 + 11 * 15 * SEC, now=now, salt=0.375)


def _seal_and_flush(db):
    """The open block seals and flushes; the cold parts become volume 1."""
    db.tick(T0 + 2 * BLOCK + 11 * MIN)


def _expire(db):
    """Every fileset volume out of retention, removed from disk."""
    opts = db.namespaces["default"].opts
    db.cleanup(T0 + 2 * BLOCK + opts.retention_nanos + opts.block_size_nanos)


class TestAnswerIsTheLockReleased:
    @pytest.mark.parametrize("how", ["columns", "batch"])
    @pytest.mark.parametrize("between", [_late_write, _seal_and_flush,
                                         _expire],
                             ids=["write", "tick", "cleanup"])
    def test_what_lands_after_the_release_leaves_the_answer(
            self, tmp_path, monkeypatch, between, how):
        """A write, a seal and flush, or a cleanup that runs on another
        thread between the plan and its decode, merge and cut leaves the
        answer, by bits, what it was before: the plan holds bytes and
        arrays nothing changes in place.  A later fetch sees the
        change."""
        import threading

        from m3_tpu.storage.database import Namespace

        db = _db(tmp_path, 4)
        start, end = cold_overflow(db)
        want = _fetched(db, how, start, end)
        ran = []
        real = Namespace._read_shards

        def after_release(self, plan):
            t = threading.Thread(target=lambda: ran.append(between(db)))
            t.start()
            t.join(60)
            assert not t.is_alive() and ran  # the lock was free
            yield from real(self, plan)

        monkeypatch.setattr(Namespace, "_read_shards", after_release)
        assert _fetched(db, how, start, end) == want
        monkeypatch.setattr(Namespace, "_read_shards", real)
        later = _fetched(db, how, start, end)
        assert (later == want) == (between is _seal_and_flush)
        db.close()


class TestMechanismEngages:
    def _traced(self, tmp_path, num_shards=4):
        tracer = Tracer(enabled=True)
        db = _db(tmp_path, num_shards, tracer=tracer)
        return db, tracer

    def test_one_span_one_lock_and_no_per_series_read(self, tmp_path,
                                                      monkeypatch):
        db, tracer = self._traced(tmp_path)
        start, end = one_open_block(db)
        st = DatabaseStorage(db)
        st.fetch_raw(b"m", (), start, end)  # the snapshot is warm now
        monkeypatch.setattr(
            Database, "read",
            lambda *a, **k: pytest.fail("per-series Database.read"))
        monkeypatch.setattr(
            Database, "read_batch",
            lambda *a, **k: pytest.fail("tuples batch in the query path"))
        before = len(tracer.finished())
        blk = st.fetch_raw(b"m", (), start, end)
        spans = tracer.finished()[before:]
        reads = [s for s in spans if s.name == Tracepoint.DB_READ]
        assert len(reads) == 1
        assert reads[0].tags["n"] == N and reads[0].tags["columnar"] == N
        assert len(blk.series) == N
        # query_ids takes the lock once and the read once: one handoff
        # for all series, not one a series
        waits = [s for s in spans if s.name == Tracepoint.DB_LOCK_WAIT]
        assert len(waits) == 2
        assert [s.name for s in spans if s.name.startswith("db.")
                and s.name != Tracepoint.DB_LOCK_WAIT] == [
                    Tracepoint.DB_QUERY_IDS, Tracepoint.DB_READ_LOCKED,
                    Tracepoint.DB_READ]
        # the lock is held under db.read.locked, which stands in db.read;
        # the wait for it stands before db.read, not in it
        (locked,) = [s for s in spans if s.name == Tracepoint.DB_READ_LOCKED]
        assert locked.parent_id == reads[0].span_id
        assert (locked.tags["n"], locked.tags["streams"]) == (N, 0)
        assert reads[0].span_id not in {s.parent_id for s in waits}
        assert waits[-1].end_ns <= reads[0].start_ns
        db.close()

    def test_a_fileset_source_is_columnar(self, tmp_path):
        """The one merge takes every source as arrays: a flushed block
        arrives from the batch device decode as arrays too (a series the
        device flags and the scalar iterator reads is counted out of
        ``columnar``: tests/test_flushed_read.py), and so does a cold
        part (arrays as it arrived)."""
        from m3_tpu.instrument import tracing

        db, tracer = self._traced(tmp_path)
        start, end = cold_overflow(db)
        tracing.install(tracer)  # as run_node does: the spans below db.read
        try:
            DatabaseStorage(db).fetch_raw(b"m", (), start, end)
        finally:
            tracing.uninstall(tracer)
        (span,) = tracer.finished(Tracepoint.DB_READ)
        assert span.tags["n"] == N and span.tags["columnar"] == N
        (fs,) = tracer.finished(Tracepoint.DB_READ_FILESET)
        assert fs.parent_id == span.span_id
        assert (fs.tags["n"], fs.tags["device"], fs.tags["scalar"]) == (N, N, 0)
        # the open block alone, beside cold parts: arrays only
        DatabaseStorage(db).fetch_raw(b"m", (), T0 + BLOCK, T0 + BLOCK + MIN)
        span = tracer.finished(Tracepoint.DB_READ)[-1]
        assert span.tags["n"] == N and span.tags["columnar"] == N
        db.close()

    def test_every_fetched_series_is_merged_after_the_release(self,
                                                              tmp_path):
        from m3_tpu.instrument import Registry

        reg = Registry()
        db = _db(tmp_path, 4, instrument=reg.scope("m3tpu"))
        start, end = cold_overflow(db)
        st = DatabaseStorage(db)
        st.fetch_raw(b"m", (), start, end)
        st.fetch_raw(b"m", (), T0 + BLOCK, T0 + BLOCK + MIN)
        db.read_batch("default", [d.id for d in _docs()], start, end)
        snap = reg.snapshot()
        fetched = {k.rsplit(".", 1)[-1]: v for k, v in snap.items()
                   if ".db.fetch_series" in k}
        assert fetched == {"fetch_series": 2 * N,
                           "fetch_series_columnar": 2 * N,
                           "fetch_series_unlocked": 2 * N}
        db.close()

    def test_metrics_count_asked_and_columnar(self, tmp_path):
        from m3_tpu.instrument import Registry

        reg = Registry()
        db = _db(tmp_path, 2, instrument=reg.scope("m3tpu"))
        start, end = cold_overflow(db)
        st = DatabaseStorage(db)
        st.fetch_raw(b"m", (), start, end)
        st.fetch_raw(b"m", (), T0 + BLOCK, T0 + BLOCK + MIN)
        text = reg.render_prometheus()
        assert f"m3tpu_db_fetch_series {2 * N}" in text
        assert f"m3tpu_db_fetch_series_columnar {2 * N}" in text
        assert f"m3tpu_db_fileset_series_device_decoded {N}" in text
        assert "m3tpu_db_fileset_series_scalar_decoded 0" in text
        assert f"m3tpu_db_fileset_decode_points {6 * N}" in text
        assert f"m3tpu_db_reads {2 * N}" in text
        db.close()
