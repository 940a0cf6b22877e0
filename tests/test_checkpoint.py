"""Bit-exact aggregation-arena checkpoint/restore (aggregator/checkpoint.py).

The acceptance criterion, verified at the unit level: save → (process
death) → restore → consume is **sha256-identical** to uninterrupted
consume — not approximately equal, IDENTICAL, because every arena lane
(packed and f64) serializes as raw bytes and restores into the same
fixed-width tensors (the SALSA/Counter-Pools discipline PR 8 adopted is
what makes this possible).  The restore side re-runs the SAME ingest
sequence post-restore, so any divergence — a lane lost, a slot remapped,
a watermark drifted, host bookkeeping forgotten — shows up as a digest
mismatch.

Corruption follows the persist discipline: magic/schema/truncation →
FormatCorruption, digest mismatch → ChecksumMismatch, and the
AggregatorCheckpointer moves a rotten file aside and boots fresh rather
than crash-looping.  The multi-process SIGKILL path (kill a live node
mid-window, restart, resume) rides the dtest tier in test_soak.py.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from m3_tpu.aggregator import checkpoint
from m3_tpu.aggregator.engine import AggregatorOptions, MetricList, MetricMap
from m3_tpu.metrics.aggregation import AggregationID
from m3_tpu.metrics.policy import StoragePolicy
from m3_tpu.metrics.types import MetricType
from m3_tpu.persist.corruption import ChecksumMismatch, FormatCorruption

R = 10 * 10**9  # 10s resolution
SP = StoragePolicy.parse("10s:2d")


def _opts(layout: str) -> AggregatorOptions:
    return AggregatorOptions(
        capacity=64, num_windows=2, timer_sample_capacity=1 << 10,
        quantiles=(0.5, 0.99), layout=layout, storage_policies=(SP,))


def _make_list(layout: str) -> MetricList:
    return MetricList(SP, _opts(layout))


def _mixed_batch(ml: MetricList, seed: int, t0: int) -> None:
    """One deterministic counter+gauge+timer batch inside window t0."""
    rng = np.random.default_rng(seed)
    n = 40
    ids = [b"m%d" % i for i in rng.integers(0, 16, n)]
    times = (t0 + rng.integers(1, R - 1, n)).astype(np.int64)
    ml.add_batch(MetricType.COUNTER, ids,
                 rng.integers(-50, 50, n).astype(np.int64), times)
    ml.add_batch(MetricType.GAUGE, ids, rng.normal(1e6, 1e3, n), times)
    ml.add_batch(MetricType.TIMER, ids, np.abs(rng.normal(0.1, 0.05, n)),
                 times)


def _digest(flushed) -> str:
    h = hashlib.sha256()
    for f in flushed:
        h.update(str(f.policy).encode())
        h.update(np.int64(f.timestamp_nanos).tobytes())
        h.update(np.int8(int(f.metric_type)).tobytes())
        h.update(np.asarray(f.slots, np.int32).tobytes())
        h.update(np.asarray(f.types, np.int8).tobytes())
        h.update(np.asarray(f.values, np.float64).tobytes())
    return h.hexdigest()


def _restore_fresh(path) -> MetricList:
    """The restart shape: a FRESH list built from the checkpoint's own
    recorded geometry, exactly like Downsampler.restore_from."""

    def make_list(policy_str, opts):
        sp = StoragePolicy.parse(policy_str)
        return MetricList(sp, AggregatorOptions(
            capacity=opts["capacity"], num_windows=opts["num_windows"],
            timer_sample_capacity=opts["timer_sample_capacity"],
            quantiles=tuple(opts["quantiles"]),
            timer_packed32=opts["timer_packed32"], layout=opts["layout"],
            storage_policies=(sp,)))

    lists, extra = checkpoint.restore_lists(path, make_list)
    assert set(lists) == {str(SP)}
    return lists[str(SP)]


class TestBitExactParity:
    """The identical op sequence, with a save→kill→restore inserted
    mid-stream on one side: flushed outputs digest-identical."""

    @pytest.mark.parametrize("layout", ["packed", "f64"])
    def test_save_restore_consume_sha256_identical(self, layout, tmp_path):
        t0 = R

        def run(with_checkpoint: bool):
            ml = _make_list(layout)
            out = []
            _mixed_batch(ml, 1, t0)
            _mixed_batch(ml, 2, t0)
            out.extend(ml.consume(2 * R + 1))   # drains window 0
            _mixed_batch(ml, 3, 2 * R)          # window 1 OPEN mid-kill
            if with_checkpoint:
                p = tmp_path / f"{layout}.ckpt"
                checkpoint.save_lists({SP: ml}, p)
                ml = _restore_fresh(p)          # the process died here
            _mixed_batch(ml, 4, 2 * R)
            out.extend(ml.consume(4 * R + 1))   # drains window 1
            return _digest(out), ml

        d_ctl, _ = run(False)
        d_ckpt, restored = run(True)
        assert d_ctl == d_ckpt
        # watermark + reject counters rode the checkpoint too
        assert restored.consumed_until == 4 * R

    @pytest.mark.parametrize("layout", ["packed", "f64"])
    def test_every_lane_restores_bit_exact(self, layout, tmp_path):
        ml = _make_list(layout)
        _mixed_batch(ml, 7, R)
        p = tmp_path / "lanes.ckpt"
        checkpoint.save_lists({SP: ml}, p)
        ml2 = _restore_fresh(p)
        for aname in ("counters", "gauges", "timers"):
            a, b = getattr(ml, aname), getattr(ml2, aname)
            for f in a.state._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(a.state, f)),
                    np.asarray(getattr(b.state, f)),
                    err_msg=f"{aname}.{f}")
            if hasattr(a, "_sample_n_host"):
                np.testing.assert_array_equal(a._sample_n_host,
                                              b._sample_n_host)

    @pytest.mark.parametrize("layout,cls", [
        ("packed", "PackedCounterArena"), ("f64", "CounterArena")])
    def test_meta_names_the_layout_the_arenas_carry(self, layout, cls):
        """What restore rebuilds a list from: read off the arenas'
        own attribute, the same for all three of a list."""
        ml = _make_list(layout)
        meta, _arrays = checkpoint.list_state(ml)
        assert meta["opts"]["layout"] == layout
        assert meta["layout"] == cls
        assert {ml.counters.layout, ml.gauges.layout,
                ml.timers.layout} == {layout}

    def test_slot_assignment_and_free_list_survive(self, tmp_path):
        ml = _make_list("packed")
        _mixed_batch(ml, 9, R)
        # free a slot so the free list is non-trivial
        m = ml.maps[MetricType.COUNTER]
        freed = m.resolve([b"m3"], AggregationID.DEFAULT,
                          MetricType.COUNTER)[0]
        m.release(int(freed))
        p = tmp_path / "slots.ckpt"
        checkpoint.save_lists({SP: ml}, p)
        ml2 = _restore_fresh(p)
        m2 = ml2.maps[MetricType.COUNTER]
        # every surviving id occupies the SAME slot...
        for s in range(64):
            assert m.id_of(s) == m2.id_of(s), s
        # ...and the next allocation recycles the SAME freed slot on
        # both sides (allocation order is part of bit-exactness: the
        # arenas key on slot numbers)
        a = m.resolve([b"fresh"], AggregationID.DEFAULT, MetricType.COUNTER)
        b = m2.resolve([b"fresh"], AggregationID.DEFAULT, MetricType.COUNTER)
        assert int(a[0]) == int(b[0])

    def test_extra_meta_round_trips(self, tmp_path):
        ml = _make_list("f64")
        _mixed_batch(ml, 5, R)
        p = tmp_path / "extra.ckpt"
        checkpoint.save_lists({SP: ml}, p,
                              extra_meta={"series_tags": {b"a": {b"t": b"v"}}})
        header, _ = checkpoint.load_lists(p)
        assert header["extra"]["series_tags"] == {b"a": {b"t": b"v"}}


class TestCorruption:
    def _saved(self, tmp_path):
        ml = _make_list("packed")
        _mixed_batch(ml, 3, R)
        p = tmp_path / "c.ckpt"
        checkpoint.save_lists({SP: ml}, p)
        return p

    def test_bad_magic_typed(self, tmp_path):
        p = self._saved(tmp_path)
        data = bytearray(p.read_bytes())
        data[0] ^= 0xFF
        p.write_bytes(bytes(data))
        with pytest.raises(FormatCorruption):
            checkpoint.load_lists(p)

    def test_truncated_typed(self, tmp_path):
        p = self._saved(tmp_path)
        p.write_bytes(p.read_bytes()[:8])
        with pytest.raises(FormatCorruption):
            checkpoint.load_lists(p)

    def test_header_flip_typed(self, tmp_path):
        p = self._saved(tmp_path)
        data = bytearray(p.read_bytes())
        data[len(checkpoint.MAGIC) + 13 + 4] ^= 0x01  # inside the header
        p.write_bytes(bytes(data))
        with pytest.raises(ChecksumMismatch):
            checkpoint.load_lists(p)

    def test_lane_flip_typed(self, tmp_path):
        p = self._saved(tmp_path)
        data = bytearray(p.read_bytes())
        data[-3] ^= 0x40  # inside the last lane blob
        p.write_bytes(bytes(data))
        with pytest.raises(ChecksumMismatch):
            checkpoint.load_lists(p)

    def test_schema_bump_typed(self, tmp_path):
        p = self._saved(tmp_path)
        data = bytearray(p.read_bytes())
        data[len(checkpoint.MAGIC)] = checkpoint.SCHEMA + 1
        p.write_bytes(bytes(data))
        with pytest.raises(FormatCorruption):
            checkpoint.load_lists(p)

    def test_geometry_mismatch_typed(self, tmp_path):
        """A checkpoint restored into a DIFFERENT geometry is format
        corruption at the restore seam, not a crash deep in XLA."""
        p = self._saved(tmp_path)
        header, per_list = checkpoint.load_lists(p)
        wrong = MetricList(SP, _opts("packed").__class__(
            capacity=32, num_windows=2, timer_sample_capacity=1 << 10,
            quantiles=(0.5, 0.99), layout="packed",
            storage_policies=(SP,)))
        with pytest.raises(FormatCorruption):
            checkpoint.restore_list_state(wrong, header["lists"][0],
                                          per_list[0])


class TestCheckpointer:
    """The mediator/drain driver: counted saves, quarantine-aside
    restore, fresh-boot on a missing file."""

    class _FakeDownsampler:
        def __init__(self, path_ok=True):
            self.lists = {SP: _make_list("packed")}
            self.saved = 0
            self.restored = 0

        def checkpoint_to(self, path):
            self.saved += 1
            return checkpoint.save_lists(self.lists, path)

        def restore_from(self, path):
            checkpoint.load_lists(path)  # raises typed on corruption
            self.restored += 1

    def test_save_restore_counts(self, tmp_path):
        ds = self._FakeDownsampler()
        ck = checkpoint.AggregatorCheckpointer(ds, tmp_path / "a.ckpt")
        info = ck.save()
        assert info["bytes"] > 0 and ck.saves == 1
        assert ck.restore() is True
        assert ck.restores == 1 and ds.restored == 1
        st = ck.status()
        assert st["saves"] == 1 and st["restores"] == 1
        assert st["corrupt"] == 0

    def test_missing_file_boots_fresh(self, tmp_path):
        ds = self._FakeDownsampler()
        ck = checkpoint.AggregatorCheckpointer(ds, tmp_path / "none.ckpt")
        assert ck.restore() is False
        assert ck.restores == 0

    def test_corrupt_file_quarantined_aside(self, tmp_path):
        ds = self._FakeDownsampler()
        path = tmp_path / "rot.ckpt"
        ck = checkpoint.AggregatorCheckpointer(ds, path)
        ck.save()
        data = bytearray(path.read_bytes())
        data[-1] ^= 0xFF
        path.write_bytes(bytes(data))
        assert ck.restore() is False
        assert ck.corrupt == 1
        # the bytes moved aside for forensics; the node boots fresh
        assert not path.exists()
        assert (tmp_path / "rot.ckpt.corrupt").exists()


class TestMetricMapEntries:
    def test_round_trip_with_masks_and_free_list(self):
        m = MetricMap(16, use_native=False)
        s0 = m.resolve([b"a"], AggregationID.DEFAULT, MetricType.COUNTER)[0]
        m.resolve([b"b"], AggregationID.DEFAULT, MetricType.COUNTER)
        m.resolve([b"c"], AggregationID.DEFAULT, MetricType.GAUGE)
        m.release(int(s0))
        saved = m.to_entries()
        m2 = MetricMap(16, use_native=False)
        m2.load_entries(saved)
        assert [m2.id_of(s) for s in range(4)] == \
            [m.id_of(s) for s in range(4)]
        np.testing.assert_array_equal(m.agg_mask, m2.agg_mask)
        np.testing.assert_array_equal(m.tail_sig, m2.tail_sig)
        # the recycled slot matches
        a = m.resolve([b"d"], AggregationID.DEFAULT, MetricType.COUNTER)
        b = m2.resolve([b"d"], AggregationID.DEFAULT, MetricType.COUNTER)
        assert int(a[0]) == int(b[0])

    def test_native_shaped_checkpoint_restores_allocatable(self):
        """A native-idmap checkpoint reports size == capacity with an
        EMPTY free list (the native resolver keeps its own); restoring
        it on the Python path must rediscover the holes — not come up
        permanently exhausted for new series."""
        cap = 8
        saved = {"entries": [(0, b"a", 1, 0), (3, b"b", 1, 0)],
                 "free": [], "size": cap}
        m = MetricMap(cap, use_native=False)
        m.load_entries(saved)
        assert m.id_of(0) == b"a" and m.id_of(3) == b"b"
        # every hole below size is allocatable again, in slot order
        got = [int(m.resolve([b"n%d" % i], AggregationID.DEFAULT,
                             MetricType.COUNTER)[0])
               for i in range(cap - 2)]
        assert got == [1, 2, 4, 5, 6, 7]
        with pytest.raises(RuntimeError, match="capacity"):
            m.resolve([b"over"], AggregationID.DEFAULT,
                      MetricType.COUNTER)


class TestDownsamplerCheckpoint:
    def _ds(self, tmp_path):
        from m3_tpu.coordinator.downsample import (
            Downsampler, DownsamplerOptions)
        from m3_tpu.metrics.filters import TagsFilter
        from m3_tpu.metrics.rules import MappingRule, RuleSet
        from m3_tpu.storage.database import (
            Database, DatabaseOptions, NamespaceOptions)

        db = Database(
            DatabaseOptions(root=str(tmp_path / "db"),
                            commitlog_enabled=False),
            {"default": NamespaceOptions(num_shards=1,
                                         slot_capacity=1 << 10,
                                         sample_capacity=1 << 12)})
        rs = RuleSet(version=1, mapping_rules=[
            MappingRule("cpu", TagsFilter.parse("__name__:cpu.*"), (SP,)),
        ], rollup_rules=[])
        return db, Downsampler(db, rs, opts=DownsamplerOptions(
            capacity=1 << 10, timer_sample_capacity=1 << 12))

    def test_checkpoint_to_restore_from(self, tmp_path):
        from m3_tpu.index.doc import Document

        db, ds = self._ds(tmp_path)
        try:
            docs = [Document.from_tags(b"cpu.load;h=%d" % i,
                                       {b"__name__": b"cpu.load",
                                        b"host": b"h%d" % i})
                    for i in range(4)]
            t0 = np.full(4, R + 1, np.int64)
            ds.write_batch(docs, t0, np.arange(4, dtype=np.float64),
                           metric_type=MetricType.GAUGE)
            p = tmp_path / "ds.ckpt"
            nbytes = ds.checkpoint_to(p)
            assert nbytes > 0
            db2, ds2 = self._ds(tmp_path)
            try:
                ds2.restore_from(p)
                # the restored downsampler flushes the SAME aggregates
                a = ds.flush(3 * R)
                b = ds2.flush(3 * R)
                assert a == b and a > 0
            finally:
                db2.close()
        finally:
            db.close()
