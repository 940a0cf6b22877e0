"""Snapshots, mediator orchestration, cleanup, instrument, config.

Models the reference's crash-recovery contract: snapshot + WAL-tail
replay restores everything (`storage/series/buffer.go:537 Snapshot`,
`persist/fs/snapshot_metadata_*.go`), cleanup removes only covered/
expired artifacts (`storage/cleanup.go`), and the mediator drives all of
it (`storage/mediator.go:284`).
"""

import time

import numpy as np
import pytest

from m3_tpu import instrument
from m3_tpu.core.config import ConfigError, load_config, parse_duration
from m3_tpu.persist import snapshot as snap
from m3_tpu.persist.commitlog import list_commitlogs
from m3_tpu.persist.fs import list_fileset_volumes
from m3_tpu.server.assembly import run_node
from m3_tpu.storage.database import Database, DatabaseOptions, NamespaceOptions
from m3_tpu.storage.mediator import Mediator

BLOCK = 2 * 3600 * 10**9
START = (1_700_000_000 * 10**9) // BLOCK * BLOCK
NS_OPTS = NamespaceOptions(num_shards=2, slot_capacity=1 << 10,
                           sample_capacity=1 << 12)


def _db(root, **kw):
    return Database(
        DatabaseOptions(root=str(root)), namespaces={"default": NS_OPTS}, **kw
    )


def _write(db, n, t0, ids=("cpu.a", "cpu.b", "mem.c")):
    ids_b = [i.encode() for i in ids for _ in range(n // len(ids))]
    ts = t0 + np.arange(len(ids_b), dtype=np.int64) * 10**9
    vals = np.arange(len(ids_b), dtype=np.float64) + 0.5
    db.write_batch("default", ids_b, ts, vals)
    return ids_b, ts, vals


class TestSnapshotRecovery:
    def test_snapshot_then_crash_restores_all_points(self, tmp_path):
        db = _db(tmp_path)
        ids, ts, vals = _write(db, 30, START)
        db.snapshot()
        # WAL tail after the snapshot
        ids2, ts2, vals2 = _write(db, 30, START + 10**12)
        db.close()  # "crash" (commitlog is fsync'd on close)

        db2 = _db(tmp_path)
        stats = db2.bootstrap()
        assert stats["snapshot_restored"] > 0
        pts = db2.read("default", b"cpu.a", START, START + BLOCK)
        want = {int(t): v for i, t, v in zip(ids, ts, vals) if i == b"cpu.a"
                for t, v in [(t, v)]}
        got = dict(pts)
        for t, v in want.items():
            assert got[t] == v
        # tail points are back too
        pts2 = db2.read("default", b"cpu.a", START + 10**12, START + 10**12 + BLOCK)
        assert len(pts2) > 0
        db2.close()

    def test_snapshot_shrinks_wal_replay(self, tmp_path):
        db = _db(tmp_path)
        _write(db, 300, START)
        db.snapshot()
        _write(db, 30, START + 10**12)
        db.close()

        db2 = _db(tmp_path)
        stats = db2.bootstrap()
        # replay covers only the tail logs (snapshot rotated first), so
        # far fewer than the 330 total samples replay from WAL
        assert stats["commitlog_replayed"] <= 30
        db2.close()

    def test_uncommitted_snapshot_invisible(self, tmp_path):
        db = _db(tmp_path)
        _write(db, 30, START)
        seq = snap.next_snapshot_seq(str(tmp_path))
        snap.snapshot_data_root(str(tmp_path), seq).mkdir(parents=True)
        # no commit_snapshot -> invisible
        assert snap.latest_snapshot(str(tmp_path)) is None
        db.close()

    def test_corrupt_snapshot_meta_skipped(self, tmp_path):
        db = _db(tmp_path)
        _write(db, 30, START)
        db.snapshot()
        m = snap.meta_path(str(tmp_path), 0)
        m.write_bytes(b"\x00" * 20)
        assert snap.latest_snapshot(str(tmp_path)) is None
        db2 = _db(tmp_path)
        stats = db2.bootstrap()  # falls back to full WAL replay
        assert stats["commitlog_replayed"] >= 30
        db2.close()
        db.close()


class TestIndexRecovery:
    def _write_tagged(self, db, n, t0):
        from m3_tpu.index.doc import Document

        docs = [
            Document.from_tags(b"reqs{host=h%d}" % (i % 3),
                               {b"__name__": b"reqs", b"host": b"h%d" % (i % 3)})
            for i in range(n)
        ]
        ts = t0 + np.arange(n, dtype=np.int64) * 10**9
        db.write_tagged_batch("default", docs, ts, np.arange(float(n)))

    def test_index_survives_snapshot_cleanup_and_two_restarts(self, tmp_path):
        """Code-review scenario: tags live only in snapshot+WAL; after
        cleanup prunes both, a second restart must still find the index
        (restore_snapshot re-persists under the main root)."""
        from m3_tpu.index.search import Term

        db = _db(tmp_path)
        self._write_tagged(db, 30, START)
        db.snapshot()
        db.close()

        db2 = _db(tmp_path)
        db2.bootstrap()
        # cleanup prunes... a *second* snapshot makes the first prunable
        # and covers the WAL; after it, tags exist nowhere but the index.
        db2.snapshot()
        db2.cleanup(START)
        db2.close()

        db3 = _db(tmp_path)
        db3.bootstrap()
        docs = db3.query_ids("default", Term(b"host", b"h0"), START, START + BLOCK)
        assert len(docs) == 1 and docs[0].id == b"reqs{host=h0}"
        db3.close()

    def test_wal_replay_rebuilds_index_without_snapshot(self, tmp_path):
        from m3_tpu.index.search import Term

        db = _db(tmp_path)
        self._write_tagged(db, 12, START)
        db.close()
        db2 = _db(tmp_path)
        db2.bootstrap()
        docs = db2.query_ids("default", Term(b"host", b"h1"), START, START + BLOCK)
        assert len(docs) == 1
        db2.close()


class TestColdWriteRecovery:
    def test_pending_cold_write_to_flushed_block_survives_crash(self, tmp_path):
        """Code-review scenario: point lands cold in an already-flushed
        block, crash before cold_flush — replay must keep it (it is NOT
        in the fileset) while still dropping true duplicates."""
        db = _db(tmp_path)
        ids, ts, vals = _write(db, 30, START)
        # seal + warm-flush the block
        db.tick(START + BLOCK + NS_OPTS.buffer_past_nanos + 10**9)
        # late cold write into the flushed block
        late_t = START + 55 * 10**9
        db.write_batch("default", [b"cpu.a"], np.asarray([late_t]),
                       np.asarray([123.5]))
        db.close()  # crash before any cold flush

        db2 = _db(tmp_path)
        db2.bootstrap()
        pts = dict(db2.read("default", b"cpu.a", START, START + BLOCK))
        assert pts[late_t] == 123.5
        # originals still exactly once
        orig = [t for i, t in zip(ids, ts) if i == b"cpu.a"]
        for t in orig:
            assert int(t) in pts
        db2.close()


class TestConcurrency:
    @pytest.mark.slow  # round-12 tier-1 budget: ~60s threaded stress
    # loop; the sample-conservation invariant it shares with the race
    # tier stays tier-1 in test_race.py::TestFlushTickVsWriters
    def test_ingest_races_mediator(self, tmp_path):
        """HTTP-thread ingest concurrent with mediator snapshot/tick must
        not drop batches or hit closed commitlog files (the engine
        lock)."""
        import threading

        db = _db(tmp_path)
        med = Mediator(db, clock=lambda: START, snapshot_every=1,
                       cleanup_every=2)
        errs = []
        N_BATCH, PER = 12, 20

        def ingest(k):
            try:
                for b in range(N_BATCH):
                    t0 = START + (k * N_BATCH + b) * PER * 10**9
                    ids = [f"w{k}.s{j}".encode() for j in range(PER)]
                    ts = t0 + np.arange(PER, dtype=np.int64) * 10**8
                    db.write_batch("default", ids, ts, np.full(PER, 1.0))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def churn():
            try:
                for _ in range(8):
                    med.run_once(START)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=ingest, args=(k,)) for k in range(3)]
        threads.append(threading.Thread(target=churn))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        # every sample must be durable: crash + bootstrap, then count
        db.close()
        db2 = _db(tmp_path)
        db2.bootstrap()
        total = 0
        for k in range(3):
            for b in range(N_BATCH):
                t0 = START + (k * N_BATCH + b) * PER * 10**9
                for j in range(PER):
                    pts = db2.read("default", f"w{k}.s{j}".encode(),
                                   t0, t0 + PER * 10**9)
                    total += len(pts)
        assert total == 3 * N_BATCH * PER
        db2.close()


class TestCleanup:
    def test_cleanup_removes_expired_and_superseded(self, tmp_path):
        db = _db(tmp_path)
        ns = db.namespaces["default"]
        old_start = START - NS_OPTS.retention_nanos - 4 * BLOCK
        # old block flushed directly
        sh = ns.shards[0]
        sh.buffer.write(
            np.zeros(4, np.int32) + sh.slots.resolve([b"x"])[0],
            old_start + np.arange(4) * 10**9, np.arange(4.0), {old_start},
        )
        sh.warm_flush(old_start)
        assert list_fileset_volumes(str(tmp_path), "default", 0)
        stats = db.cleanup(START + BLOCK)
        assert stats["filesets"] == 1
        assert list_fileset_volumes(str(tmp_path), "default", 0) == []
        db.close()

    def test_cleanup_prunes_snapshots_and_covered_commitlogs(self, tmp_path):
        db = _db(tmp_path)
        _write(db, 30, START)
        db.snapshot()
        _write(db, 30, START + 10**12)
        db.snapshot()
        n_logs = len(list_commitlogs(str(tmp_path)))
        stats = db.cleanup(START)
        assert len(snap.list_snapshots(str(tmp_path))) == 1
        assert stats["commitlogs"] > 0
        assert len(list_commitlogs(str(tmp_path))) < n_logs
        # everything still readable after cleanup + restart
        db.close()
        db2 = _db(tmp_path)
        db2.bootstrap()
        assert len(db2.read("default", b"cpu.a", START, START + BLOCK)) > 0
        db2.close()


class TestMediator:
    def test_run_once_seals_and_flushes(self, tmp_path):
        db = _db(tmp_path)
        _write(db, 30, START)
        med = Mediator(db, clock=lambda: START)
        stats = med.run_once(START + BLOCK + NS_OPTS.buffer_past_nanos + 10**9)
        assert stats["tick"]["default"]["warm_flushed"] > 0

    def test_cadence_and_instrument(self, tmp_path):
        reg = instrument.new_registry()
        db = _db(tmp_path, instrument=reg.scope("node"))
        _write(db, 30, START)
        med = Mediator(db, clock=lambda: START, snapshot_every=2,
                       cleanup_every=3, instrument=reg.scope("node"))
        s1 = med.run_once()
        assert "snapshot" not in s1 and "cleanup" not in s1
        s2 = med.run_once()
        assert "snapshot" in s2
        s3 = med.run_once()
        assert "cleanup" in s3
        snap_ = reg.snapshot()
        assert snap_["node.mediator.ticks"] == 3
        assert snap_["node.db.writes"] == 30

    def test_background_loop(self, tmp_path):
        db = _db(tmp_path)
        _write(db, 30, START)
        med = Mediator(db, clock=lambda: START + BLOCK * 2,
                       tick_interval_s=0.05)
        med.open()
        # poll, don't sleep a fixed 0.3 s: the first tick seals a block
        # and a loaded box (6 xdist workers) can take longer over it
        deadline = time.monotonic() + 20
        while med._ticks < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        med.close()
        assert med._ticks >= 2


class TestInstrument:
    def test_counters_gauges_timers(self):
        reg = instrument.new_registry()
        s = reg.scope("svc", {"env": "test"})
        s.counter("requests").inc()
        s.counter("requests").inc(4)
        s.gauge("depth").update(7.5)
        t = s.timer("latency")
        for ms in (1, 2, 3):
            t.record(ms / 1000)
        snap_ = reg.snapshot()
        assert snap_["svc.requests{env=test}"] == 5
        assert snap_["svc.depth{env=test}"] == 7.5
        assert snap_["svc.latency{env=test}"]["count"] == 3

    def test_scope_interning_shares_instruments(self):
        reg = instrument.new_registry()
        reg.scope("a").counter("c").inc()
        reg.scope("a").counter("c").inc()
        assert reg.snapshot()["a.c"] == 2

    def test_prometheus_rendering(self):
        reg = instrument.new_registry()
        reg.scope("db").counter("writes").inc(3)
        reg.scope("db", {"shard": "1"}).gauge("depth").update(2.0)
        text = reg.render_prometheus()
        assert "db_writes 3" in text
        assert 'db_depth{shard="1"} 2.0' in text

    def test_timer_reservoir_bounded(self):
        t = instrument.Timer(reservoir=16)
        for i in range(10_000):
            t.record(i / 1e6)
        s = t.summary()
        assert s["count"] == 10_000
        assert len(t._reservoir) == 16


class TestConfig:
    def test_load_and_defaults(self):
        cfg = load_config("""
db:
  root: /tmp/x
  namespaces:
    default: {retention: 24h, block_size: 2h}
    agg_1m: {retention: 120h, block_size: 12h, resolution: 1m}
coordinator: {listen_port: 0}
mediator: {tick_interval: 5s}
""")
        assert cfg.db.namespaces["agg_1m"].retention == "120h"
        assert parse_duration(cfg.mediator.tick_interval) == 5 * 10**9
        assert parse_duration(cfg.db.namespaces["agg_1m"].resolution) == 60 * 10**9

    def test_env_expansion(self, monkeypatch):
        monkeypatch.setenv("M3_ROOT", "/data/m3")
        cfg = load_config("db: {root: '${M3_ROOT}'}\n")
        assert cfg.db.root == "/data/m3"
        cfg2 = load_config("db: {root: '${M3_UNSET:/fallback}'}\n")
        assert cfg2.db.root == "/fallback"

    def test_validation_aggregates_errors(self):
        with pytest.raises(ConfigError) as ei:
            load_config("""
db:
  namespaces:
    bad: {retention: nope, num_shards: 0}
""")
        msg = str(ei.value)
        assert "retention" in msg and "num_shards" in msg

    def test_unknown_field_rejected(self):
        with pytest.raises(ConfigError, match="unknown field"):
            load_config("db: {rooot: /tmp/x}\n")

    def test_coordinator_null_disables_http(self):
        cfg = load_config("db: {root: /tmp/x}\ncoordinator: null\n")
        assert cfg.coordinator is None

    def test_downsample_requires_ruleset(self, tmp_path):
        with pytest.raises(ConfigError, match="ruleset"):
            run_node(f"""
db: {{root: {tmp_path}}}
coordinator: {{downsample: true}}
mediator: {{enabled: false}}
""")

    @pytest.mark.parametrize("key", ["arena_ingest", "arena_layout"])
    def test_arena_seam_keys_are_unknown_fields(self, key):
        """The arenas have one formulation: a node file that still
        carries a seam's key is refused by name, not ignored."""
        with pytest.raises(ConfigError, match=f"coordinator.{key}: unknown field"):
            load_config(
                f"db: {{root: /tmp/x}}\ncoordinator: {{{key}: packed}}\n")

    @staticmethod
    def _assert_packed(lists):
        from m3_tpu.aggregator import packed

        assert lists
        for ml in lists:
            assert type(ml.counters) is packed.PackedCounterArena
            assert type(ml.gauges) is packed.PackedGaugeArena
            assert type(ml.timers) is packed.PackedTimerArena

    def test_node_downsampler_holds_packed_arenas(self, tmp_path):
        from m3_tpu.index.doc import Document
        from m3_tpu.metrics.filters import TagsFilter
        from m3_tpu.metrics.policy import StoragePolicy
        from m3_tpu.metrics.rules import MappingRule, RuleSet

        asm = run_node(f"""
db: {{root: {tmp_path}}}
coordinator: {{listen_port: 0, downsample: true}}
mediator: {{enabled: false}}
""", ruleset=RuleSet(version=1, mapping_rules=[MappingRule(
            "cpu", TagsFilter.parse("__name__:cpu.*"),
            (StoragePolicy.parse("10s:2d"),))], rollup_rules=[]))
        try:
            # a list is built at its policy's first sample
            doc = Document.from_tags(b"cpu.load;h=0", {
                b"__name__": b"cpu.load", b"host": b"h0"})
            asm.downsampler.write_batch(
                [doc], np.asarray([START + 10**9], np.int64),
                np.asarray([1.5]))
            self._assert_packed(list(asm.downsampler._lists.values()))
        finally:
            asm.close()

    def test_served_aggregator_holds_packed_arenas(self, tmp_path):
        from m3_tpu.server.assembly import run_aggregator

        asm = run_aggregator(f"""
db: {{root: {tmp_path}}}
coordinator: null
aggregator: {{listen_port: 0, capacity: 64, storage_policies: ["1m:2d"]}}
""")
        try:
            self._assert_packed([
                ml for sh in asm.aggregator.aggregator.shards
                for ml in sh.lists.values()])
        finally:
            asm.close()


class TestAssembly:
    def test_run_node_end_to_end(self, tmp_path):
        import json
        import urllib.request

        asm = run_node(f"""
db:
  root: {tmp_path}
  namespaces:
    default: {{retention: 48h, block_size: 2h, num_shards: 2}}
coordinator: {{listen_port: 0}}
mediator: {{enabled: false}}
""")
        try:
            port = asm.port
            body = json.dumps([
                {"tags": {"__name__": "up", "host": "a"},
                 "timestamp": START // 10**9, "value": 1.0},
            ]).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v1/json/write", data=body,
                headers={"Content-Type": "application/json"},
            )
            assert json.load(urllib.request.urlopen(req))["written"] == 1
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics"
            ).read().decode()
            assert "m3tpu_db_writes_tagged 1" in metrics
        finally:
            asm.close()
