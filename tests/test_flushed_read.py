"""Reads of sealed blocks: `Database.read_columns` over a flushed block
goes through ONE batched device decode a fetch (`Namespace._decode_block`)
and must answer, bit for bit, what the single-id `Database.read` (scalar
iterator) answers and what the plain reference decoder
(`benchmark/references/m3tsz_decode.py`, which imports nothing of the
program) reads from the flushed bytes."""

import numpy as np
import pytest

from benchmark.datasets.prom_histogram import EXTREMES
from benchmark.references import m3tsz_decode
from m3_tpu.index.doc import Document
from m3_tpu.instrument import Registry, tracing
from m3_tpu.instrument.tracing import Tracepoint, Tracer
from m3_tpu.persist.fs import (
    DataFileSetReader, fileset_path, list_fileset_volumes,
)
from m3_tpu.storage import database as dbmod
from m3_tpu.storage.database import (
    Database, DatabaseOptions, NamespaceOptions, shard_for_id,
)
from tests.per_layer_entries import check_workloads

SEC = 10**9
MIN = 60 * SEC
BLOCK = 2 * 3600 * SEC
T0 = (1_600_000_000 * SEC) // BLOCK * BLOCK
P = 40  # points a series in the sealed block
SHARDS = 4


def _bits(values) -> list:
    return np.asarray(values, np.float64).view(np.uint64).tolist()


def _db(root, **kw):
    return Database(
        DatabaseOptions(root=str(root), commitlog_enabled=False),
        namespaces={"default": NamespaceOptions(
            num_shards=SHARDS, slot_capacity=64, sample_capacity=64 * 128)},
        **kw)


def _series():
    """{kind: [(id, [(ts, value)])]}: what a block must hand back."""
    rng = np.random.default_rng(33)
    grid = (T0 + BLOCK - (P - np.arange(P)) * 10 * SEC).tolist()
    irregular = (T0 + np.cumsum(rng.integers(1, 90, P)) * SEC
                 + rng.integers(0, SEC, P)).tolist()
    gap = [t for k, t in enumerate(grid) if not 10 <= k < 25]
    out = {
        "counters": [(b"counter-%d" % i, list(zip(
            grid, np.cumsum(rng.integers(0, 200, P)).astype(float).tolist())))
            for i in range(9)],
        "gauges": [(b"gauge-%d" % i, list(zip(
            grid, rng.normal(100.0, 15.0, P).tolist()))) for i in range(5)],
        "extremes": [(b"extreme-%d" % i, list(zip(grid, [
            EXTREMES[(i + k // 3) % len(EXTREMES)] if k % 3 == 0
            else float(rng.normal()) for k in range(P)]))) for i in range(3)],
        "irregular": [(b"irregular-0", list(zip(
            irregular, rng.normal(0.0, 1.0, P).tolist())))],
        "gap": [(b"gap-0", list(zip(gap, np.arange(len(gap), dtype=float)
                                    .tolist())))],
        "beyond_2_53": [(b"big-0", list(zip(
            grid, [2.0**53 + 2.0 * k for k in range(P)])))],
    }
    return out


def _write(db, series, now=None):
    for sid, pts in series:
        doc = Document.from_tags(sid, {b"__name__": b"m", b"id": sid})
        for t, v in pts:
            db.write_tagged_batch("default", [doc], np.array([t]),
                                  np.array([v]), now_nanos=now or t)


@pytest.fixture(scope="module")
def sealed(tmp_path_factory):
    """One node whose block T0 holds every kind of series, flushed."""
    reg, tracer = Registry(), Tracer(enabled=True)
    db = _db(tmp_path_factory.mktemp("sealed"),
             instrument=reg.scope("m3tpu"), tracer=tracer)
    series = _series()
    tracing.install(tracer)     # as run_node does: the spans below db.*
    for rows in series.values():
        _write(db, rows)
    stats = db.tick(T0 + BLOCK + 11 * MIN)["default"]
    assert stats["warm_flushed"] == sum(len(r) for r in series.values())
    yield db, series, reg, tracer
    tracing.uninstall(tracer)
    db.close()


def _flushed_bytes(db, sid):
    shard = shard_for_id(sid, SHARDS)
    return dict(db.read_block("default", shard, T0)).get(sid, b"")


@pytest.mark.parametrize("kind", ["counters", "gauges", "extremes",
                                  "irregular", "gap", "beyond_2_53", "absent"])
def test_read_columns_equals_scalar_read_and_the_reference(sealed, kind):
    db, series, _, _ = sealed
    # the kind's series in one selector with the counters (and, for
    # `absent`, an id the block never saw)
    rows = series["counters"] + series.get(kind, [])
    ids = [sid for sid, _ in rows] + ([b"never-written"] if kind == "absent"
                                      else [])
    cols = db.read_columns("default", ids, T0, T0 + BLOCK)
    assert cols.index.tolist() == list(range(len(ids)))
    for i, sid in enumerate(ids):
        n = int(cols.counts[i])
        got = (cols.ts[i, :n].tolist(), _bits(cols.values[i, :n]))
        scalar = db.read("default", sid, T0, T0 + BLOCK)
        assert got == ([t for t, _ in scalar], _bits([v for _, v in scalar]))
        ref_ts, ref_bits = m3tsz_decode.decode(_flushed_bytes(db, sid))
        assert got == (ref_ts.tolist(), ref_bits.tolist())
    if kind == "absent":
        assert cols.counts[-1] == 0
    # what was written comes back by bits wherever the codec is lossless
    if kind not in ("extremes", "beyond_2_53"):
        for i, (sid, pts) in enumerate(rows):
            n = int(cols.counts[i])
            assert cols.ts[i, :n].tolist() == [t for t, _ in pts]
            assert _bits(cols.values[i, :n]) == _bits([v for _, v in pts])
    assert db.read_batch("default", ids, T0, T0 + BLOCK) == [
        db.read("default", sid, T0, T0 + BLOCK) for sid in ids]


def test_flagged_rows_take_the_scalar_iterator_and_are_counted(sealed):
    db, series, reg, tracer = sealed
    ids = [sid for sid, _ in series["counters"] + series["beyond_2_53"]]
    before = reg.snapshot()
    n_spans = len(tracer.finished(Tracepoint.DB_READ_FILESET))
    cols = db.read_columns("default", ids, T0, T0 + BLOCK)
    # the device flags values beyond 2^53 (`prec`): that row is read by
    # the scalar iterator, says so, and is not counted columnar
    assert cols.columnar == len(ids) - 1
    (span,) = tracer.finished(Tracepoint.DB_READ_FILESET)[n_spans:]
    assert (span.tags["n"], span.tags["device"], span.tags["scalar"]) == (
        len(ids), len(ids) - 1, 1)
    assert span.tags["points"] == P * len(ids)
    assert span.tags["rows"] == 128 and span.tags["steps"] == 48
    assert span.tags["words"] == 128 * 64
    after = reg.snapshot()
    delta = {k.rsplit(".", 1)[-1]: after[k] - before.get(k, 0)
             for k in after if "fileset_" in k}
    assert delta == {"fileset_series_device_decoded": len(ids) - 1,
                     "fileset_series_scalar_decoded": 1,
                     "fileset_decode_points": P * len(ids)}
    children = {s.name for s in tracer.finished()
                if s.parent_id == span.span_id}
    assert children == {"device.decode", "db.read.fileset.to_host"}
    # the segments were read under the lock, before the decode
    (read,) = [s for s in tracer.finished(Tracepoint.DB_READ)
               if s.span_id == span.parent_id]
    (locked,) = [s for s in tracer.finished(Tracepoint.DB_READ_LOCKED)
                 if s.parent_id == read.span_id]
    assert len([s for s in tracer.finished(
        Tracepoint.DB_READ_FILESET_SEGMENTS)
        if s.parent_id == locked.span_id]) == 1
    assert locked.tags["streams"] == len(ids)
    assert locked.end_ns <= span.start_ns


@pytest.mark.parametrize("backend,regfile", [("cpu", "gather"),
                                              ("tpu", "select")])
def test_the_fileset_span_names_the_register_file_that_ran(
        sealed, monkeypatch, backend, regfile):
    """`db.read.fileset`'s tag `regfile` is the boundary scan's
    register file as the decode ran it: `select` where the backend is a
    TPU, `gather` elsewhere; the answer is the same by bits."""
    from m3_tpu.encoding import m3tsz_jax as mj
    from m3_tpu.parallel import pallas_decode

    db, series, _, tracer = sealed
    ids = [sid for sid, _ in series["counters"] + series["gauges"]]
    want = [db.read("default", sid, T0, T0 + BLOCK) for sid in ids]
    monkeypatch.setattr(mj.jax, "default_backend", lambda: backend)
    # the Pallas extraction a "tpu" resolves runs interpreted here
    monkeypatch.setattr(pallas_decode, "auto_interpret", lambda: True)
    n_spans = len(tracer.finished(Tracepoint.DB_READ_FILESET))
    cols = db.read_columns("default", ids, T0, T0 + BLOCK)
    (span,) = tracer.finished(Tracepoint.DB_READ_FILESET)[n_spans:]
    assert span.tags["regfile"] == regfile
    assert span.tags["device"] == len(ids)
    for i, pts in enumerate(want):
        n = int(cols.counts[i])
        assert cols.ts[i, :n].tolist() == [t for t, _ in pts]
        assert _bits(cols.values[i, :n]) == _bits([v for _, v in pts])


def test_a_warm_flush_stands_under_db_tick_as_encode_and_write(sealed):
    _, series, _, tracer = sealed
    (tick,) = tracer.finished(Tracepoint.DB_TICK)
    for name in (Tracepoint.DB_FLUSH_ENCODE, Tracepoint.DB_FLUSH_WRITE):
        spans = tracer.finished(name)
        assert len(spans) == SHARDS             # one a shard that flushed
        assert {s.parent_id for s in spans} == {tick.span_id}
    assert sum(s.tags["n"] for s in tracer.finished(
        Tracepoint.DB_FLUSH_WRITE)) == sum(len(r) for r in series.values())


def test_one_decode_a_fetch_whatever_the_shards(sealed):
    from m3_tpu.x import devguard

    db, series, _, _ = sealed
    ids = [sid for sid, _ in series["counters"] + series["gauges"]]
    assert len({shard_for_id(sid, SHARDS) for sid in ids}) > 1
    calls = devguard.counters().get("device.decode.calls", 0)
    db.read_columns("default", ids, T0, T0 + BLOCK)
    assert devguard.counters()["device.decode.calls"] == calls + 1


def test_two_selectors_inside_one_bucket_compile_one_program(tmp_path):
    """Rows, words and points are rounded up on the host, so selectors
    of different sizes (and a range that touches fewer series) run the
    program the first one compiled."""
    from m3_tpu.x import tracewatch

    db = _db(tmp_path)
    grid = (T0 + BLOCK - (21 - np.arange(21)) * 10 * SEC).tolist()  # 21 points: a scan
    rows = [(b"c-%d" % i, list(zip(grid, np.arange(21.0) + i)))      # no other test has
            for i in range(12)]
    _write(db, rows)
    db.tick(T0 + BLOCK + 11 * MIN)
    ids = [sid for sid, _ in rows]
    was_installed = tracewatch.installed()
    tracewatch.install(raise_on_violation=False)
    try:
        before = dict(tracewatch.compiles())
        db.read_columns("default", ids, T0, T0 + BLOCK)
        new = {k: n - before.get(k, 0)
               for k, n in tracewatch.compiles().items()
               if n != before.get(k, 0)}
        assert new == {"_decode_batch_device": 1}
        snap = tracewatch.snapshot()
        db.read_columns("default", ids[:5], T0, T0 + BLOCK)
        db.read_columns("default", ids[3:], T0 + BLOCK - MIN, T0 + BLOCK)
        assert tracewatch.retraces_since(snap) == 0
    finally:
        if not was_installed:
            tracewatch.uninstall()
    db.close()


def test_two_flushed_blocks_and_the_open_one_merge_in_source_order(tmp_path):
    db = _db(tmp_path)
    ids = [b"s-%d" % i for i in range(6)]
    want = {}
    for b in range(3):                       # blocks T0, T0 + 2h, T0 + 4h
        for k in range(8):
            t = T0 + b * BLOCK + (k + 1) * 10 * MIN
            vals = np.array([b * 100 + k + i / 7.0 for i in range(len(ids))])
            docs = [Document.from_tags(s, {b"__name__": b"m", b"id": s})
                    for s in ids]
            db.write_tagged_batch("default", docs, np.full(len(ids), t), vals,
                                  now_nanos=t)
            for s, v in zip(ids, vals):
                want.setdefault(s, {})[t] = float(v)
        if b < 2:
            db.tick(T0 + (b + 1) * BLOCK + 11 * MIN)
    now = T0 + 2 * BLOCK + 90 * MIN
    # a cold write over a point of the first sealed block: the later
    # source wins the timestamp
    t_cold = T0 + 3 * 10 * MIN
    docs = [Document.from_tags(ids[0], {b"__name__": b"m", b"id": ids[0]})]
    db.write_tagged_batch("default", docs, np.array([t_cold]),
                          np.array([-1.5]), now_nanos=now)
    want[ids[0]][t_cold] = -1.5
    cols = db.read_columns("default", ids, T0, T0 + 3 * BLOCK)
    for i, s in enumerate(ids):
        n = int(cols.counts[i])
        assert n == 24
        assert cols.ts[i, :n].tolist() == sorted(want[s])
        assert _bits(cols.values[i, :n]) == _bits(
            [want[s][t] for t in sorted(want[s])])
        assert db.read("default", s, T0, T0 + 3 * BLOCK) == sorted(
            want[s].items())
    db.close()


def test_a_corrupt_volume_is_quarantined_and_the_lower_one_answers(tmp_path):
    db = _db(tmp_path)
    rows = _series()["counters"]
    _write(db, rows)
    db.tick(T0 + BLOCK + 11 * MIN)
    ids = [sid for sid, _ in rows]
    first = db.read_batch("default", ids, T0, T0 + BLOCK)
    # a cold flush writes volume 1 over every shard's volume 0 ...
    now = T0 + BLOCK + 30 * MIN
    late = [(sid, [(T0 + BLOCK - 5 * SEC, 7.25)]) for sid in ids]
    _write(db, late, now=now)
    db.tick(now)
    shards = sorted({shard_for_id(s, SHARDS) for s in ids})
    for sh in shards:
        assert (T0, 1) in list_fileset_volumes(db.opts.root, "default", sh)
    with_late = db.read_batch("default", ids, T0, T0 + BLOCK)
    # ... which supersedes it
    assert with_late == [pts + [(T0 + BLOCK - 5 * SEC, 7.25)] for pts in first]
    cols = db.read_columns("default", ids, T0, T0 + BLOCK)
    assert cols.counts.tolist() == [P + 1] * len(ids)
    # ... and one shard's volume 1 goes bad on disk: that shard falls
    # back to volume 0, no read fails, the others keep volume 1
    bad = shards[0]
    p = fileset_path(db.opts.root, "default", bad, T0, 1, "data")
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    p.write_bytes(bytes(raw))
    db.block_cache.clear()
    got = db.read_batch("default", ids, T0, T0 + BLOCK)
    for sid, pts, a, b in zip(ids, got, first, with_late):
        assert pts == (a if shard_for_id(sid, SHARDS) == bad else b)
    assert (T0, 1) not in list_fileset_volumes(db.opts.root, "default", bad)
    assert len(db.quarantine_inventory()) == 1
    db.close()


def test_the_scan_length_is_learned_from_the_streams(tmp_path):
    """The format records no point count: a fresh process learns a
    volume's from the longest stream asked for, and a stream with more
    points than that is flagged, read by the scalar iterator, and
    lengthens the next fetch's scan."""
    db = _db(tmp_path)
    grid = (T0 + np.arange(1, 61) * MIN).tolist()
    # 17 points of full-mantissa floats: the block's longest stream;
    # 60 points of a constant: its shortest
    wide = b"wide"
    long_ = next(s for s in (b"long-%d" % i for i in range(64))
                 if shard_for_id(s, SHARDS) == shard_for_id(wide, SHARDS))
    rows = [(wide, list(zip(grid[:17], np.random.default_rng(1)
                            .normal(0, 1, 17).tolist()))),
            (long_, [(t, 5.0) for t in grid])]
    _write(db, rows)
    db.tick(T0 + BLOCK + 11 * MIN)
    db.close()
    db = _db(tmp_path)                      # nothing remembered
    ids = [wide, long_]
    want = [db.read("default", s, T0, T0 + BLOCK) for s in ids]
    ns = db.namespaces["default"]
    first = db.read_columns("default", ids, T0, T0 + BLOCK)
    assert first.columnar == 1              # `long` outran a 32-step scan
    reader = db.block_cache.reader(ns.root, "default",
                                   shard_for_id(wide, SHARDS), T0, 0)
    assert reader.max_points == 60
    again = db.read_columns("default", ids, T0, T0 + BLOCK)
    assert again.columnar == 2
    for cols in (first, again):
        for i, pts in enumerate(want):
            n = int(cols.counts[i])
            assert list(zip(cols.ts[i, :n].tolist(),
                            cols.values[i, :n].tolist())) == pts
    db.close()


def test_reader_read_many_is_read_per_id(sealed):
    db, series, _, _ = sealed
    ids = [sid for rows in series.values() for sid, _ in rows]
    for shard in range(SHARDS):
        mine = [s for s in ids if shard_for_id(s, SHARDS) == shard]
        if not mine:
            continue
        r = DataFileSetReader(db.opts.root, "default", shard, T0, 0)
        # unsorted, an id twice, ids the volume lacks (before, between
        # and after its own)
        asked = mine[::-1] + [b"", mine[0], b"zzz", b"gauge-"] + mine[:1]
        assert r.read_many(asked) == [r.read(s) for s in asked]
        assert r.read_many([]) == []


def test_a_range_that_ends_at_a_block_start_does_not_visit_that_block(
        tmp_path, monkeypatch):
    """`start <= t < end`: the block that begins at `end` cannot hold a
    point of the range, so its buffer is not peeked (a peek re-drains
    the window after every write into it)."""
    db = _db(tmp_path)
    rows = _series()["counters"]
    _write(db, rows)
    db.tick(T0 + BLOCK + 11 * MIN)
    live = [(sid, [(T0 + BLOCK + 10 * SEC, 1.0)]) for sid, _ in rows]
    _write(db, live)
    peeked = []
    real = dbmod.ShardBuffer.peek
    monkeypatch.setattr(dbmod.ShardBuffer, "peek",
                        lambda self, bs: peeked.append(bs) or real(self, bs))
    ids = [sid for sid, _ in rows]
    cols = db.read_columns("default", ids, T0, T0 + BLOCK)
    assert cols.counts.tolist() == [P] * len(ids) and peeked == []
    assert [len(db.read("default", s, T0, T0 + BLOCK)) for s in ids] == [
        P] * len(ids)
    assert peeked == []
    # one nanosecond further and the open block is a source
    cols = db.read_columns("default", ids, T0, T0 + BLOCK + 10 * SEC + 1)
    assert cols.counts.tolist() == [P + 1] * len(ids)
    assert set(peeked) == {T0 + BLOCK}
    db.close()


# the cell's twenty-two per-layer entries
_FLUSHED = [n + ".flushed" for n in (
    "fileset_read_ms_per_query", "segments_ms_per_query",
    "decode_to_host_ms_per_query", "decode_calls_per_query",
    "decode_device_ms_per_query", "decode_roofline", "device_decode_pct",
    "query_req_p50_ms", "query_p90_ms", "query_device_ms_per_query",
    "rate_family_roofline", "eval_ms_per_query", "series_read_ms_per_query",
    "lock_wait_ms_per_query", "index_query_ms_per_query",
    "render_ms_per_query", "query_unnamed_pct", "read_columnar_pct",
    "device_idle_pct", "idle_unnamed_pct", "gc_pause_pct",
    "window_compiles")]


def test_the_cells_per_layer_entries_are_well_formed():
    """PR 33's `.flushed` entries of BENCHMARK.json: one cell, the
    end-to-end metric that cell reports, a reader that exists."""
    import importlib
    import json
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    bench = json.loads((repo / "BENCHMARK.json").read_text())
    cell = "prom.dashboard_flushed"
    # by name (the two `gil_` entries and the `read_locked_` one are held by
    # tests/test_node_spans.py); a copy of a dashboard reader or a
    # `.query` guard may stand folded into that entry
    assert len(_FLUSHED) == len(set(_FLUSHED)) == 22
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "queries_per_s"]
    assert cell in e2e["workloads"]
    for name in _FLUSHED:
        m = check_workloads(bench, name, [cell])
        assert m["moves"] == "queries_per_s"
        spec = json.loads((repo / "benchmark" / "metrics"
                           / (m["name"] + ".json")).read_text())
        importlib.import_module("benchmark.reducers." + spec["reducer"])
        if name.endswith("_roofline.flushed"):
            assert (m["unit"], m["better"]) == ("%", "higher")
    (cfg,) = [c for c in bench["configs"] if c["name"] == "m3tsz_flushed_blocks"]
    file = json.loads((repo / cfg["file"]).read_text())
    assert sorted(file["reduced"]) == sorted(cfg["reduced"])
    assert file["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert file["reduced"]["series"]["here"] == (
        file["dataset"]["histograms"] * len(file["dataset"]["le"])
        + file["dataset"]["gauges"])


def test_the_lock_is_free_while_a_fetch_decodes(tmp_path, monkeypatch):
    """A fetch holds `Database._mu` only while it reads its plan: held
    inside the decode, it lets a second thread's write complete and the
    lock be taken, and answers what it would have answered alone."""
    import threading

    db = _db(tmp_path)
    rows = _series()["counters"]
    _write(db, rows)
    db.tick(T0 + BLOCK + 11 * MIN)
    ids = [sid for sid, _ in rows]
    want = db.read_columns("default", ids, T0, T0 + BLOCK)
    inside, go = threading.Event(), threading.Event()
    real = dbmod._decode_streams

    def held(*args):
        inside.set()
        assert go.wait(60)
        return real(*args)

    monkeypatch.setattr(dbmod, "_decode_streams", held)
    got = []
    fetch = threading.Thread(target=lambda: got.append(
        db.read_columns("default", ids, T0, T0 + BLOCK)))
    fetch.start()
    try:
        assert inside.wait(60)
        write = threading.Thread(target=_write, args=(
            db, [(ids[0], [(T0 + BLOCK + 10 * SEC, 1.0)])]),
            kwargs={"now": T0 + BLOCK + 11 * MIN})
        write.start()
        write.join(30)
        assert not write.is_alive()
        assert db._mu.acquire(blocking=False)
        db._mu.release()
        assert fetch.is_alive()             # still inside the decode
    finally:
        go.set()
        fetch.join(60)
    (cols,) = got
    assert np.array_equal(cols.ts, want.ts)
    assert _bits(cols.values.ravel()) == _bits(want.values.ravel())
    assert cols.counts.tolist() == want.counts.tolist() == [P] * len(ids)
    db.close()


# -- the open window's sorted snapshot: its span and its counters ------------


def _open_db(root, tracer):
    """One shard, so that one fetch asks one buffer for one snapshot."""
    reg = Registry()
    db = Database(
        DatabaseOptions(root=str(root), commitlog_enabled=False),
        namespaces={"default": NamespaceOptions(
            num_shards=1, slot_capacity=64, sample_capacity=64 * 128)},
        instrument=reg.scope("m3tpu"), tracer=tracer)
    return db, reg


def _snapshot_counts(reg) -> dict:
    return {k.rsplit("_", 1)[-1]: v for k, v in reg.snapshot().items()
            if ".db.buffer_snapshot_" in k}


def _read_write_read(db, then_read_again=False):
    """Two fetches of the open block T0 with one write into it between
    them (and a third fetch after the second, with none) -> their
    columns."""
    rows = _series()["counters"][:3]
    _write(db, rows)
    ids = [sid for sid, _ in rows]
    end = T0 + BLOCK
    out = [db.read_columns("default", ids, T0, end)]
    # a point of the same open window, after the others of its series
    _write(db, [(ids[0], [(T0 + BLOCK - 5 * SEC, 7.0)])])
    out.append(db.read_columns("default", ids, T0, end))
    if then_read_again:
        out.append(db.read_columns("default", ids, T0, end))
    return out


def test_a_write_between_two_reads_re_sorts_the_window_under_a_span(
        tmp_path):
    tracer = Tracer(enabled=True)
    db, reg = _open_db(tmp_path, tracer)
    tracing.install(tracer)
    try:
        first, second = _read_write_read(db)
        assert _snapshot_counts(reg) == {"hits": 0, "misses": 2, "stale": 1}
        spans = tracer.finished(Tracepoint.DB_BUFFER_SNAPSHOT)
        assert [s.tags["stale"] for s in spans] == [0, 1]
        assert [s.tags["points"] for s in spans] == [3 * P, 3 * P + 1]
        locked = {s.span_id for s in tracer.finished(
            Tracepoint.DB_READ_LOCKED)}
        assert len(locked) == 2 and {s.parent_id for s in spans} == locked
        assert second.counts.tolist() == [P + 1, P, P]
        # a read with no write since: the snapshot serves it, no span
        db.read_columns("default", [b"counter-0"], T0, T0 + BLOCK)
        assert _snapshot_counts(reg) == {"hits": 1, "misses": 2, "stale": 1}
        assert len(tracer.finished(Tracepoint.DB_BUFFER_SNAPSHOT)) == 2
        text = reg.render_prometheus()
        for name, v in (("hits", 1), ("misses", 2), ("stale", 1)):
            assert f"m3tpu_db_buffer_snapshot_{name} {v}" in text
    finally:
        tracing.uninstall(tracer)
        db.close()


def test_the_snapshot_span_costs_nothing_where_nothing_records(tmp_path):
    """With recording off, no span, and every answer bit for bit what a
    recording node answers; the counters count all the same."""
    answers = {}
    for on in (True, False):
        tracer = Tracer(enabled=on)
        db, reg = _open_db(tmp_path / str(on), tracer)
        tracing.install(tracer)
        try:
            answers[on] = _read_write_read(db, then_read_again=True)
            assert len(tracer.finished(
                Tracepoint.DB_BUFFER_SNAPSHOT)) == (2 if on else 0)
            assert _snapshot_counts(reg) == {
                "hits": 1, "misses": 2, "stale": 1}
        finally:
            tracing.uninstall(tracer)
            db.close()
    for a, b in zip(answers[True], answers[False]):
        assert np.array_equal(a.ts, b.ts)
        assert _bits(a.values.ravel()) == _bits(b.values.ravel())
        assert a.counts.tolist() == b.counts.tolist()
