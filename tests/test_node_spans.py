"""The node's one span mechanism (``m3_tpu/instrument/tracing.py``) on
the profiler's clock, and the benchmark reducers that read it.

Recording is on while ``coordinator.tracing`` is set OR a JAX profiler
session is live; a recorded span goes to the ring and, as ``m3:<name>``,
into the profiler's own trace.  Spans sit where the work happens (per
request, per batch, per device call, around every lock's acquisition),
and the reducers under ``benchmark/reducers/`` turn the ring of a traced
slice into per-layer metrics, or into nothing where they cannot.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import re
import sys
import threading
import time
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import NOOP_SPAN, Tracepoint, Tracer

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import selftest, stats  # noqa: E402
from benchmark.reducers import (  # noqa: E402
    node_span_ms, node_span_slice_pct, node_span_tag_pct,
    node_span_unnamed_pct, node_spans, trace_idle_unnamed_pct, tracefile,
)


@pytest.fixture
def session(tmp_path):
    """A live profiler session (CPU): `stop()` ends it and returns the
    .xplane.pb's host events {name: [(start_s, dur_s)]}."""
    state = {"live": False}

    def start():
        jax.profiler.start_trace(str(tmp_path))
        state["live"] = True

    def stop():
        jax.profiler.stop_trace()
        state["live"] = False
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        events: dict = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for e in line.events:
                        events.setdefault(e.name, []).append(
                            (e.start_ns / 1e9, e.duration_ns / 1e9))
        return events

    yield SimpleNamespace(start=start, stop=stop)
    if state["live"]:
        jax.profiler.stop_trace()


@pytest.fixture
def node(tmp_path):
    from m3_tpu.server.assembly import run_node

    asm = run_node(f"""
db:
  root: {tmp_path / "data"}
  namespaces:
    default: {{num_shards: 2}}
coordinator: {{listen_port: 0}}
mediator: {{enabled: false}}
""")
    yield asm
    asm.close()


NOW = int(time.time())


def _write(port: int, n: int = 6) -> None:
    body = json.dumps([
        {"tags": {"__name__": "obs", "host": f"h{i}"}, "timestamp": NOW,
         "value": float(i)} for i in range(n)]).encode()
    urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/json/write", data=body,
        method="POST")).read()


def _remote_write(port: int, n: int = 6) -> None:
    """The same series as a Prometheus remote-write request."""
    from benchmark import wire

    tags = [{b"__name__": b"obs", b"host": b"h%d" % i} for i in range(n)]
    body = wire.Template(tags, NOW * 10**9).body(
        NOW * 10**9, np.arange(n, dtype=np.float64))
    assert wire.post_write(port, body) == 204


def _query(port: int) -> None:
    urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/v1/query_range?query=sum(rate(obs[1m]))"
        f"&start={NOW - 100}&end={NOW + 10}&step=10s").read()


# -- the switch -----------------------------------------------------------


class TestRecordingSwitch:
    def test_not_recording_costs_a_noop_and_leaves_no_trace(self):
        tr = Tracer(enabled=False)
        assert not tr.recording
        with tr.start_span(Tracepoint.API_WRITE) as root:
            root.set_tag("n", 3)
            # the would-be root bound the negative decision: everything
            # under it is the one shared no-op
            assert not tracing.current().sampled
            assert tr.start_span(Tracepoint.DB_WRITE_BATCH) is NOOP_SPAN
            with tr.start_span(Tracepoint.DB_LOCK_WAIT) as child:
                assert child is NOOP_SPAN
        assert tracing.current() is None
        assert tr.finished() == [] and tr.dropped == 0

    def test_enabled_outside_a_session_records_without_annotation(self):
        tr = Tracer()
        with tr.start_span("a") as active:
            assert active._annotation is None
        assert [s.name for s in tr.finished()] == ["a"]

    def test_noop_tracer_never_records(self, session):
        session.start()
        with tracing.NOOP_TRACER.start_span("a") as sp:
            assert sp is NOOP_SPAN
        assert "m3:a" not in session.stop()
        assert tracing.NOOP_TRACER.finished() == []

    def test_profiler_session_turns_recording_on_and_off(self, session):
        tr = Tracer(enabled=False)
        with tr.start_span("before"):
            pass
        session.start()
        assert tr.recording
        with tr.start_span("outer", {"k": 1}):
            with tr.start_span("inner"):
                time.sleep(0.01)
            with tr.start_span("inner"):
                pass
        events = session.stop()
        assert not tr.recording
        with tr.start_span("after"):
            pass
        ring = tr.finished()
        assert sorted(s.name for s in ring) == ["inner", "inner", "outer"]
        # one m3:<name> event per ring span, the same length within 1 ms
        for name in ("outer", "inner"):
            ours = sorted(s.duration_ns / 1e9 for s in ring if s.name == name)
            theirs = sorted(d for _, d in events["m3:" + name])
            assert len(ours) == len(theirs)
            assert all(abs(a - b) < 1e-3 for a, b in zip(ours, theirs))
        assert "m3:before" not in events and "m3:after" not in events

    def test_request_in_flight_when_a_session_opens_leaves_no_orphans(
            self, session):
        tr = Tracer(enabled=False)
        with tr.start_span(Tracepoint.API_WRITE):
            session.start()
            with tr.start_span(Tracepoint.DB_WRITE_BATCH):
                with tr.start_span(Tracepoint.DB_INDEX_WRITE):
                    pass
            with tr.start_span(Tracepoint.API_WRITE):
                pass        # a whole request, begun inside the session
        session.stop()
        # the old request's children did not enter the ring as roots
        assert tr.finished() == []

    def test_debug_endpoint_404_until_something_records(self, node, session):
        url = f"http://127.0.0.1:{node.port}/api/v1/debug/traces"
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url)
        assert e.value.code == 404
        session.start()
        try:
            out = json.loads(urllib.request.urlopen(url).read())
        finally:
            session.stop()
        assert out["status"] == "success" and out["dropped"] == 0


# -- the ring ---------------------------------------------------------------


class TestRing:
    def test_overflow_is_counted_and_dated(self):
        tr = Tracer(max_finished=4)
        for i in range(7):
            with tr.start_span(f"s{i}"):
                pass
        held = tr.finished()
        assert [s.name for s in held] == ["s3", "s4", "s5", "s6"]
        assert tr.dropped == 3
        assert tr.oldest_start_ns == held[0].start_ns
        # whatever ended by then may be missing; what is held ended later
        assert tr.dropped_until_ns <= held[0].end_ns
        tr.clear()
        assert tr.dropped == 0 and tr.oldest_start_ns is None

    def test_default_ring_holds_a_slice(self):
        assert Tracer().max_finished == 65536

    def test_ring_accounts_for_every_span_under_threads_and_collections(self):
        """More threads than cores, a short switch interval, the
        collector's hook firing inside the ring's lock: every finished
        span is either held or counted as dropped."""
        tr = Tracer(max_finished=64)
        tracing.install(tr)
        per_thread, threads = 400, 8
        collections = []

        def count(phase, info):
            if phase == "stop" and info["generation"] >= 1:
                collections.append(1)

        gc.callbacks.append(count)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)

        def work():
            for i in range(per_thread):
                with tr.start_span("outer"):
                    with tr.start_span("inner"):
                        if i % 50 == 0:
                            gc.collect(1)

        try:
            ts = [threading.Thread(target=work) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
        finally:
            sys.setswitchinterval(old)
            gc.callbacks.remove(count)
            tracing.uninstall(tr)
        held = tr.finished()
        assert len(held) == 64 and collections
        assert len(held) + tr.dropped == (
            2 * per_thread * threads + len(collections))
        assert 0 < tr.dropped_until_ns <= max(s.end_ns for s in held)
        # no stack was left unbalanced: a new span is a root again
        with tr.start_span("after") as sp:
            assert sp.span.parent_id is None


# -- spans where the work happens ----------------------------------------------


class TestSpanSites:
    def test_lock_wait_covers_the_time_another_thread_held_the_lock(
            self, tmp_path):
        from m3_tpu.storage.database import Database, DatabaseOptions, NamespaceOptions

        tr = Tracer()
        db = Database(DatabaseOptions(root=str(tmp_path)),
                      {"default": NamespaceOptions(num_shards=1)}, tracer=tr)
        held, hold_s = threading.Event(), 0.25

        def holder():
            with db._mu:
                held.set()
                time.sleep(hold_s)

        t = threading.Thread(target=holder)
        t.start()
        held.wait()
        tr.clear()
        t0 = time.monotonic()
        db.read("default", b"nobody", 0, 10)
        waited = time.monotonic() - t0
        t.join()
        (wait,) = tr.finished(Tracepoint.DB_LOCK_WAIT)
        (read,) = tr.finished(Tracepoint.DB_READ)
        db.close()
        assert hold_s - 0.05 <= wait.duration_ns / 1e9 <= waited
        # the wait ends before the work begins: siblings, not nested
        assert wait.end_ns <= read.start_ns
        assert wait.parent_id == read.parent_id

    def test_run_guarded_opens_device_span_on_primary_and_fallback(self):
        from m3_tpu.x import devguard, fault

        tr = Tracer()
        tracing.install(tr)
        try:
            assert devguard.run_guarded("t.stage", lambda: 7, lambda: 8) == 7
            with fault.armed("device.dispatch", "error", p=1.0):
                assert devguard.run_guarded(
                    "t.stage", lambda: 7, lambda: 8) == 8
        finally:
            tracing.uninstall(tr)
            devguard.reset_stages()
            devguard.reset_counters()
        names = [s.name for s in tr.finished()]
        assert names == ["device.t.stage", "device.t.stage"]
        assert tracing.span("after uninstall") is NOOP_SPAN

    def test_collection_is_a_span_under_what_is_open(self):
        tr = Tracer()
        tracing.install(tr)
        try:
            with tr.start_span("outer") as outer:
                gc.collect(2)
            gc.collect(0)       # generation 0: not recorded
        finally:
            tracing.uninstall(tr)
        gcs = tr.finished(Tracepoint.RUNTIME_GC)
        assert gcs and all(s.tags["generation"] >= 1 for s in gcs)
        assert gcs[0].parent_id == outer.span.span_id
        assert tr.gc_hook not in gc.callbacks

    def test_write_and_query_give_the_parent_chains(self, node, session):
        _write(node.port)       # compiles, creates the series
        _query(node.port)
        session.start()
        _remote_write(node.port)
        _query(node.port)
        session.stop()
        spans = node.tracer.finished()
        by_id = {s.span_id: s for s in spans}

        def chain(name):
            """name <- parent <- ... <- root, of the first such span."""
            s = next(s for s in spans if s.name == name)
            out = [s.name]
            while s.parent_id is not None:
                s = by_id[s.parent_id]
                out.append(s.name)
            return out

        assert chain("api.write.decode") == ["api.write.decode", "api.write"]
        for part in ("api.write.decode.snappy", "api.write.decode.protobuf"):
            assert chain(part) == [part, "api.write.decode", "api.write"]
        assert chain("db.writeBatch") == ["db.writeBatch", "api.write"]
        for child in ("db.buffer.write", "db.index.write",
                      "db.commitlog.write"):
            assert chain(child) == [child, "db.writeBatch", "api.write"]
        assert chain("device.storage.buffer_append") == [
            "device.storage.buffer_append", "db.buffer.write",
            "db.writeBatch", "api.write"]
        read_path = ["query.storage.fetchCompressed", "query.eval.call",
                     "query.eval.aggregation", "query.engine.execute",
                     "api.queryRange"]
        assert chain("db.queryIDs") == ["db.queryIDs"] + read_path
        assert chain("db.read") == ["db.read"] + read_path
        assert chain("api.queryRange.render") == [
            "api.queryRange.render", "api.queryRange"]
        waits = [by_id[s.parent_id].name for s in spans
                 if s.name == "db.lock.wait"]
        assert set(waits) == {"api.write", "query.storage.fetchCompressed"}
        (root,) = [s for s in spans if s.name == "api.write"]
        assert root.tags["n"] == 6
        # the handler's root covers its children
        assert all(root.start_ns <= s.start_ns and s.end_ns <= root.end_ns
                   for s in spans if s.trace_id == root.trace_id)

    def test_mediator_pass_names_its_stages(self, tmp_path):
        from benchmark import harness
        from m3_tpu.server.assembly import run_node

        rules = {"policy": "1m:2d", "rollup": [], "mapping": [
            {"name": "g", "metric": "obs", "aggregations": ["SUM"]}]}
        asm = run_node(f"""
db:
  root: {tmp_path / "data"}
  namespaces:
    default: {{num_shards: 1}}
    "1m:2d": {{num_shards: 1, retention: 48h, block_size: 2h}}
coordinator: {{listen_port: 0, tracing: true, downsample: true}}
mediator: {{enabled: true, tick_interval: 1h}}
""", ruleset=harness.build_ruleset(rules))
        try:
            _write(asm.port)
            asm.tracer.clear()
            asm.mediator.run_once(
                now_nanos=(NOW // 60 + 2) * 60 * 10**9)
            spans = asm.tracer.finished()
        finally:
            asm.close()
        by_id = {s.span_id: s for s in spans}
        parent = {s.name: by_id[s.parent_id].name for s in spans
                  if s.parent_id in by_id}
        assert parent["db.tick"] == "mediator.runOnce"
        assert parent["downsample.flush"] == "mediator.runOnce"
        assert parent["aggregator.consume"] == "downsample.flush"
        assert parent["downsample.writeback"] == "downsample.flush"
        assert parent["downsample.lock.wait"] == "downsample.flush"
        # one drain span per arena under the consume (PR 31), the
        # guarded device call, the wait for it and the copy to the host
        # below it
        for kind in ("counter", "gauge", "timer"):
            assert parent[f"aggregator.drain.{kind}"] == "aggregator.consume"
            for leaf in ("wait", "to_host"):
                assert (parent[f"aggregator.drain.{kind}.{leaf}"]
                        == f"aggregator.drain.{kind}")
        assert parent["device.arena.consume"].startswith("aggregator.drain.")


# -- the reducers ---------------------------------------------------------------


def _tree():
    """root 0..10; a 1..4, b 3..6 (overlap 3..4), c 8..12 (outlives the
    root), a1 2..3 under a; a second root 20..21 with tag n."""
    rows = [(1, None, "api.write", 0.0, 10.0, {"n": 2000}),
            (2, 1, "db.lock.wait", 1.0, 4.0, {}),
            (3, 1, "db.writeBatch", 3.0, 6.0, {}),
            (4, 1, "device.arena.ingest", 8.0, 12.0, {}),
            (5, 2, "device.inner", 2.0, 3.0, {}),
            (6, None, "api.write", 20.0, 21.0, {"n": 500})]
    return node_spans.build(rows)


class _FakeTracer:
    def __init__(self, nodes, dropped=0, dropped_until_ns=0):
        self._nodes = nodes
        self.dropped, self.dropped_until_ns = dropped, dropped_until_ns

    def finished(self):
        ids = {id(n): i + 1 for i, n in enumerate(self._nodes)}
        return [SimpleNamespace(
            span_id=ids[id(n)],
            parent_id=ids[id(n.parent)] if n.parent is not None else None,
            name=n.name, start_ns=int(round(n.t0 * 1e9)),
            end_ns=int(round(n.t1 * 1e9)), tags=n.tags)
            for n in self._nodes]


def _cell(tracer, slice_=(0.0, 30.0), trace_events=None, rows=(), spans=None):
    return SimpleNamespace(
        asm=SimpleNamespace(tracer=tracer), trace=True, slice=slice_,
        slice_facts={"rows": list(rows)}, spans=spans or {},
        trace_events=trace_events)


class TestReducers:
    def test_self_time_on_a_hand_built_tree(self):
        nodes = {(n.name, n.t0): n for n in _tree()}
        root = nodes["api.write", 0.0]
        # children cover 1..6 (overlap counted once) and 8..10 (the part
        # of c inside the root): 10 - 5 - 2
        assert root.self_seconds == pytest.approx(3.0)
        assert nodes["db.lock.wait", 1.0].self_seconds == pytest.approx(2.0)
        assert nodes["device.arena.ingest", 8.0].self_seconds == \
            pytest.approx(4.0)

    def test_cpu_self_time_is_the_threads_cpu_less_its_childrens(self):
        tr = Tracer()
        with tr.start_span("outer"):
            t_end = time.thread_time() + 0.03
            while time.thread_time() < t_end:
                pass                      # burns CPU
            with tr.start_span("inner"):
                time.sleep(0.05)          # waits: wall, no CPU
        cell = _cell(tr, slice_=(0.0, time.monotonic() + 1))
        by = {n.name: n for n in node_spans.load(cell).under_roots()}
        assert by["inner"].seconds >= 0.05 and by["inner"].cpu < 0.02
        assert 0.03 <= by["outer"].self_cpu_seconds < by["outer"].seconds - 0.04
        assert node_span_ms.read(cell, {
            "spans": ["outer"], "per": "ksample", "clock": "cpu"}) is None
        rows = node_spans.load(cell).by_name()
        assert rows["outer"][0] == 1 and rows["outer"][3] >= 0.03

    def test_per_unit_metrics_divide_by_the_roots_own_work(self):
        cell = _cell(_FakeTracer(_tree()))
        spans = node_spans.load(cell)
        assert spans.work("ksample") == pytest.approx(2.5)
        assert spans.work("query") == 0
        read = node_span_ms.read
        assert read(cell, {"spans": ["db.lock.wait"], "per": "ksample"}) == \
            pytest.approx(2000 / 2.5)
        # whole spans; device.inner lies in no counted span, so it counts
        assert read(cell, {"spans": ["device.*"], "per": "ksample",
                           "self": False}) == pytest.approx(5000 / 2.5)
        assert read(cell, {"spans": ["db.lock.wait", "device.*"],
                           "per": "ksample", "self": False}) == \
            pytest.approx(7000 / 2.5)   # device.inner nested: once
        assert read(cell, {"spans": ["nothing"], "per": "ksample"}) is None
        assert read(cell, {"spans": ["db.lock.wait"], "per": "query"}) is None
        assert node_span_unnamed_pct.read(
            cell, {"roots": ["api.write"]}) == pytest.approx(100 * 4 / 11)
        assert node_span_slice_pct.read(
            cell, {"spans": ["db.lock.wait", "db.writeBatch"]}) == \
            pytest.approx(100 * 5 / 30)

    def test_tag_share_sums_over_the_spans_that_carry_both_tags(self):
        rows = [(1, None, "api.write", 0.0, 10.0, {"n": 2000}),
                (2, 1, "api.write.decode", 0.0, 1.0,
                 {"series": 1000, "hits": 0}),
                (3, None, "api.write", 11.0, 12.0, {"n": 2000}),
                (4, 3, "api.write.decode", 11.0, 11.5,
                 {"series": 1000, "hits": 900}),
                (5, None, "api.write", 13.0, 14.0, {"n": 1}),
                (6, 5, "api.write.decode", 13.0, 13.5, {}),    # a JSON write
                (7, None, "api.write", 31.0, 32.0, {"n": 9}),  # past the slice
                (8, 7, "api.write.decode", 31.0, 31.5,
                 {"series": 9, "hits": 9})]
        params = {"spans": ["api.write.decode"], "tag": "hits", "of": "series"}
        cell = _cell(_FakeTracer(node_spans.build(rows)))
        assert node_span_tag_pct.read(cell, params) == pytest.approx(45.0)
        # a program whose decode spans carry no such tags: nothing to read
        assert node_span_tag_pct.read(
            _cell(_FakeTracer(node_spans.build(rows[4:6]))), params) is None
        assert node_span_tag_pct.read(
            cell, {**params, "spans": ["db.lock.wait"]}) is None

    def test_only_roots_wholly_inside_the_slice_count(self):
        cell = _cell(_FakeTracer(_tree()), slice_=(0.5, 30.0))
        spans = node_spans.load(cell)
        assert [r.t0 for r in spans.roots] == [20.0]
        assert spans.work("ksample") == pytest.approx(0.5)
        assert node_span_ms.read(
            cell, {"spans": ["db.lock.wait"], "per": "ksample"}) is None

    @pytest.mark.parametrize("cell", [
        _cell(None),                                             # no tracer
        _cell(SimpleNamespace(finished=lambda: [])),             # an older one
        SimpleNamespace(asm=SimpleNamespace(tracer=_FakeTracer(_tree())),
                        trace=False, slice=None, slice_facts={}, spans={},
                        trace_events=None),                      # untraced
        _cell(_FakeTracer(_tree(), dropped=5, dropped_until_ns=10**9)),
    ], ids=["no_tracer", "tracer_without_dropped", "untraced", "overflowed"])
    def test_every_reducer_reads_nothing_where_it_cannot(self, cell):
        assert node_spans.load(cell) is None
        assert node_span_ms.read(
            cell, {"spans": ["db.lock.wait"], "per": "ksample"}) is None
        assert node_span_unnamed_pct.read(
            cell, {"roots": ["api.write"]}) is None
        assert node_span_slice_pct.read(
            cell, {"spans": ["runtime.gc"]}) is None
        assert node_span_tag_pct.read(cell, {
            "spans": ["api.write.decode"], "tag": "hits",
            "of": "series"}) is None
        assert trace_idle_unnamed_pct.read(cell, {}) is None

    def test_overflow_before_the_slice_is_still_a_whole_account(self):
        cell = _cell(_FakeTracer(_tree(), dropped=5, dropped_until_ns=10**9),
                     slice_=(15.0, 30.0))
        assert node_spans.load(cell).work("ksample") == pytest.approx(0.5)


def _recorded_trace():
    with open(REPO / "benchmark" / "data" / "sample_trace.json") as f:
        return tracefile.from_json(json.load(f)["trace"])


class TestClockAlignment:
    OFFSET = -1234.5                 # trace seconds = monotonic + OFFSET

    def _logged(self, tr, skew=()):
        """The request log a harness would hold for the recorded
        trace's bench:* events, on a monotonic clock OFFSET away."""
        rows = []
        for i, (_, start, dur) in enumerate(tr.host):
            sent = start - self.OFFSET - 20e-6 + (skew[i] if i < len(skew) else 0)
            rows.append(stats.Request("write", sent, sent + dur + 40e-6,
                                      True, 100, i))
        return rows

    def test_offset_recovered_within_a_millisecond(self):
        tr = _recorded_trace()
        rows = self._logged(tr)
        logged = [(r.sent, r.done - r.sent) for r in rows]
        off = trace_idle_unnamed_pct.clock_offset(tr.host, logged)
        assert off == pytest.approx(self.OFFSET, abs=1e-3)
        # a few pairs that disagree do not move the median
        rows = self._logged(tr, skew=(0.5, -0.7))
        logged = [(r.sent, r.done - r.sent) for r in rows]
        off = trace_idle_unnamed_pct.clock_offset(tr.host, logged)
        assert off == pytest.approx(self.OFFSET, abs=1e-3)

    def test_no_offset_where_pairs_disagree_or_are_too_few(self):
        tr = _recorded_trace()
        rng = np.random.default_rng(3)
        rows = self._logged(tr, skew=rng.uniform(-5, 5, len(tr.host)))
        logged = [(r.sent, r.done - r.sent) for r in rows]
        assert trace_idle_unnamed_pct.clock_offset(tr.host, logged) is None
        assert trace_idle_unnamed_pct.clock_offset(
            tr.host[:2], logged[:2]) is None
        assert trace_idle_unnamed_pct.clock_offset(tr.host, []) is None

    def test_idle_seconds_go_to_the_innermost_span_on_the_shifted_clock(
            self, capsys):
        tr = _recorded_trace()
        rows = self._logged(tr)
        t0 = -self.OFFSET - 1e-3     # the slice opens, then the requests
        t1 = t0 + tr.window_s
        # one request's tree covers the first 0.6 s of the slice; the
        # rest of the slice stands under no span
        ring = node_spans.build([
            (1, None, "api.write", t0, t0 + 0.6, {"n": 100}),
            (2, 1, "api.write.decode", t0, t0 + 0.25, {}),
            (3, 1, "db.writeBatch", t0 + 0.25, t0 + 0.6, {}),
            (4, 3, "db.index.write", t0 + 0.3, t0 + 0.4, {})])
        cell = _cell(_FakeTracer(ring), slice_=(t0, t1), trace_events=tr,
                     rows=rows)
        by_span = trace_idle_unnamed_pct.table(
            cell, node_spans.load(cell))
        idle = dict(tr.idle_gaps(10**6))
        assert sum(by_span.values()) == pytest.approx(sum(idle.values()))
        assert set(by_span) == {"api.write.decode", "db.writeBatch",
                                "db.index.write", "nothing_due"}
        assert "api.write" not in by_span          # roots name nothing
        assert by_span["db.index.write"] <= 0.1 + 1e-9
        pct = trace_idle_unnamed_pct.read(cell, {})
        assert pct == pytest.approx(
            100 * by_span["nothing_due"] / tr.window_s)
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["idle_by_span"].keys() == by_span.keys()
        # a request that was in flight when the slice opened
        early = stats.Request("write", t0 - 1.0, t0 + 1.0, True, 100, 99)
        cell = _cell(_FakeTracer(ring), slice_=(t0, t1), trace_events=tr,
                     rows=rows + [early])
        split = trace_idle_unnamed_pct.table(
            cell, node_spans.load(cell))
        pre = trace_idle_unnamed_pct.PRE_OPENED
        assert split[pre] > 0
        assert split[pre] + split["nothing_due"] == pytest.approx(
            by_span["nothing_due"])
        assert trace_idle_unnamed_pct.read(cell, {}) == pytest.approx(pct)

    def test_gaps_are_cut_at_span_boundaries_and_chunks_change_nothing(self):
        # one gap of 10 s, a span open for one second of it: the midpoint
        # rule alone would give the span all ten or none
        ops = {"d": [("a", 0.0, 1.0), ("b", 11.0, 1.0)]}
        tr = tracefile.Trace(ops, {}, [], 12.0)
        assert trace_idle_unnamed_pct.idle_by_name(
            tr, [("db.read", 5.5, 1.0)]) == pytest.approx(
                {"db.read": 1.0, "nothing_due": 9.0})
        assert trace_idle_unnamed_pct.idle_by_name(
            tr, [("outer", 0.5, 8.5), ("inner", 3.0, 2.0),
                 ("ends_after_the_last_op", 10.0, 50.0)]) == pytest.approx(
                {"outer": 6.0, "inner": 2.0, "ends_after_the_last_op": 1.0,
                 "nothing_due": 1.0})
        # the recorded trace: same idle seconds as idle_gaps() finds,
        # whatever the chunk
        tr = _recorded_trace()
        whole = sum(dict(tr.idle_gaps(10**6)).values())
        want = trace_idle_unnamed_pct.idle_by_name(tr, tr.host, chunk=10**6)
        assert sum(want.values()) == pytest.approx(whole)
        for chunk in (1, 7, 256):
            got = trace_idle_unnamed_pct.idle_by_name(tr, tr.host, chunk=chunk)
            assert got == pytest.approx(want)

    def test_ring_and_profile_agree_within_a_millisecond(self, session):
        """A real session: bench:* annotations held on both clocks, as
        the harness holds them, put the ring on the trace's clock."""
        tr = Tracer(enabled=False)
        logged = []
        session.start()
        for i in range(4):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench:req"):
                with tr.start_span(Tracepoint.API_WRITE):
                    time.sleep(0.005 * (i + 1))
                    with tr.start_span(Tracepoint.DB_WRITE_BATCH):
                        time.sleep(0.003)
            logged.append((t0, time.monotonic() - t0))
        events = session.stop()
        host = [("req", s, d) for s, d in events["bench:req"]]
        off = trace_idle_unnamed_pct.clock_offset(host, logged)
        assert off is not None
        for name in (Tracepoint.API_WRITE, Tracepoint.DB_WRITE_BATCH):
            ours = sorted(s.start_ns / 1e9 + off for s in tr.finished(name))
            theirs = sorted(s for s, _ in events["m3:" + name])
            assert len(ours) == len(theirs) == 4
            assert all(abs(a - b) < 1e-3 for a, b in zip(ours, theirs))


# -- names cannot drift -----------------------------------------------------------


def _registry() -> dict:
    return {k: v for k, v in vars(Tracepoint).items()
            if k.isupper() and isinstance(v, str)}


def _program_source() -> str:
    return "\n".join(p.read_text() for p in (REPO / "m3_tpu").rglob("*.py"))


class TestNames:
    def test_every_tracepoint_has_a_call_site(self):
        src = _program_source()
        missing = [k for k in _registry()
                   if not re.search(r"Tracepoint\." + k + r"\b", src)]
        assert missing == []

    def test_every_span_a_metric_reads_is_registered(self):
        names = set(_registry().values())
        seen = 0
        for path in (REPO / "benchmark" / "metrics").glob("*.json"):
            params = json.loads(path.read_text()).get("params", {})
            for pat in params.get("spans", []) + params.get("roots", []):
                seen += 1
                if pat.endswith("*"):
                    assert any(n.startswith(pat[:-1]) or pat[:-1] == n
                               or pat[:-1].startswith(n) for n in names), pat
                else:
                    # one drain span per arena, named from the metric
                    # type under the registered AGG_DRAIN (their names:
                    # test_aggregator_timer_service's drain-span test)
                    assert (pat in names or pat.startswith(
                        Tracepoint.AGG_DRAIN + ".")), (path.name, pat)
        assert seen >= 27
        # what the reducers themselves name
        for n in ("api.write", "api.queryRange", "mediator.runOnce",
                  "runtime.gc"):
            assert n in names


# -- the benchmark's own checks, with the new entries ------------------------------


@pytest.mark.parametrize("check", [
    selftest.test_files_name_things_that_exist,
    selftest.test_trace_reduction_on_recorded_sample,
    selftest.test_client_reducers_on_a_hand_made_window,
], ids=["files", "trace_reduction", "client_reducers"])
def test_benchmark_selftest_checks(check):
    """The selftest's quick checks, with the new entries and files."""
    check()


@pytest.mark.slow
def test_benchmark_selftest_passes_whole():
    """`python3 benchmark/selftest.py`: every cell through
    harness.run_cell at CPU sizes, controls and planted faults."""
    import subprocess

    p = subprocess.run(
        [sys.executable, str(REPO / "benchmark" / "selftest.py")],
        capture_output=True, text=True, timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stdout[-4000:]
    assert "FAIL" not in p.stdout


def test_new_per_layer_entries_are_well_formed():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    # PR 25's, PR 26's, PR 30's and PR 32's entries (PR 27's `.agg` entries are
    # held by tests/test_aggregator_service.py, PR 31's `.timer` entries
    # by tests/test_aggregator_timer_service.py, PR 33's `.flushed`
    # entries by tests/test_flushed_read.py)
    new = [m for m in bench["per_layer"] if m["source"] == "program_span"
           and m["name"] != "maintain_ms_per_pass"
           and not m["name"].endswith((".agg", ".timer", ".flushed"))]
    assert len(new) == 29
    layers = {m["layer"] for m in bench["per_layer"]
              if m not in new}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in new:
        hit_share = m["name"].startswith(("decode_cache_hit_pct",
                                          "read_columnar_pct",
                                          "group_one_program_pct"))
        assert m["layer"] in layers
        assert m["better"] == ("higher" if hit_share else "lower")
        (cell,) = m["workloads"]
        assert cell in e2e[m["moves"]]["workloads"]
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / (m["name"] + ".json")).read_text())
        assert spec["reducer"] in ("node_span_ms", "node_span_unnamed_pct",
                                   "node_span_slice_pct", "node_span_tag_pct",
                                   "trace_idle_unnamed_pct")
