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
from tests.per_layer_entries import check_workloads, entry  # noqa: E402
from benchmark.reducers import (  # noqa: E402
    compile_log, node_span_ms, node_span_slice_pct, node_span_tag_mean,
    node_span_tag_pct, node_span_unnamed_pct, node_spans,
    trace_idle_unnamed_pct, tracefile,
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
        # the interpreter-lock probe's roots go through the same ring
        probes, record = [], tr.record

        def counting(name, *a, **kw):
            probes.append(name)
            record(name, *a, **kw)

        tr.record = counting

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
        assert set(probes) <= {Tracepoint.RUNTIME_GIL_PROBE}
        assert len(held) + tr.dropped == (
            2 * per_thread * threads + len(collections) + len(probes))
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
        names = [s.name for s in tr.finished()
                 if s.name != Tracepoint.RUNTIME_GIL_PROBE]
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

    @pytest.mark.parametrize("rows", [2, 4, 64])
    def test_fleet_query_spans_and_one_block_a_call(self, node, session,
                                                    monkeypatch, rows):
        """query.eval.block a dispatched range call (ceil(S / R) of them)
        with .to_device under it, query.storage.metas beside db.read and
        query.eval.group_keys under the aggregation, with their tags."""
        from m3_tpu.query import engine

        monkeypatch.setattr(engine, "_RANGE_BLOCK_ROWS", rows)
        _write(node.port)
        _query(node.port)       # compiles
        session.start()
        _query(node.port)
        session.stop()
        spans = node.tracer.finished()
        by_id = {s.span_id: s for s in spans}

        def parents(name):
            return [by_id[s.parent_id].name for s in spans if s.name == name]

        blocks = [s for s in spans if s.name == "query.eval.block"]
        assert len(blocks) == -(-6 // rows)
        assert set(parents("query.eval.block")) == {"query.eval.call"}
        assert sum(b.tags["rows"] for b in blocks) == 6
        assert sum(b.tags["pad"] for b in blocks) == (
            (-6) % rows if rows < 6 else 0)
        assert {(b.tags["points"], b.tags["steps"]) for b in blocks} == {
            (1, 12)}
        moves = [s for s in spans if s.name == "query.eval.to_device"]
        assert parents("query.eval.to_device") == ["query.eval.block"] * len(
            blocks)
        # a block's (rows, 1 point) i64 timestamps and f64 values
        assert [m.tags["bytes"] for m in moves] == [16 * min(rows, 6)] * len(
            blocks)
        (metas,) = [s for s in spans if s.name == "query.storage.metas"]
        assert parents("query.storage.metas") == [
            "query.storage.fetchCompressed"]
        assert metas.tags["n"] == 6
        (keys,) = [s for s in spans if s.name == "query.eval.group_keys"]
        assert parents("query.eval.group_keys") == ["query.eval.aggregation"]
        assert (keys.tags["n"], keys.tags["groups"]) == (6, 1)
        # nothing new under db.read, and db.read after the metas
        assert not {"query.storage.metas", "query.eval.block"} & set(
            s.name for s in spans if s.parent_id is not None
            and by_id[s.parent_id].name.startswith("db.read"))

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


# -- the runtime beneath the spans: the interpreter lock, the compiler -----------


def _probe_threads() -> list:
    return [t for t in threading.enumerate() if t.name == tracing.PROBE_THREAD]


@pytest.fixture
def hooked():
    """A tracer installed as the process's, as run_node installs its."""
    tr = Tracer()
    tracing.install(tr)
    yield tr
    tracing.uninstall(tr)
    assert _probe_threads() == []


def _spin(stop):
    while not stop.is_set():
        for _ in range(1000):
            pass                          # pure Python: holds the lock


def _sort(stop):
    a = np.random.default_rng(0).random(1_000_000)
    while not stop.is_set():
        np.sort(a)                        # native: releases it


def _contended_share(tr, target, seconds=1.0) -> float:
    """`contended / n` over the probe's spans while `target` runs."""
    stop = threading.Event()
    worker = threading.Thread(target=target, args=(stop,), daemon=True)
    worker.start()
    time.sleep(0.05)
    tr.clear()
    with tr.start_span("x"):
        pass                              # a recorded span: the probe is up
    time.sleep(seconds)
    stop.set()
    worker.join(timeout=10)
    assert not worker.is_alive()
    probes = tr.finished(Tracepoint.RUNTIME_GIL_PROBE)
    assert probes
    return (sum(s.tags["contended"] for s in probes)
            / sum(s.tags["n"] for s in probes))


class TestGilProbe:
    def test_not_recording_starts_no_thread(self):
        tr = Tracer(enabled=False)
        tracing.install(tr)
        try:
            for _ in range(1000):
                with tr.start_span(Tracepoint.API_WRITE):
                    with tr.start_span(Tracepoint.DB_WRITE_BATCH):
                        pass
            assert _probe_threads() == [] and tr._probe is None
        finally:
            tracing.uninstall(tr)
        assert tr.finished() == [] and tr.gil_probes == 0

    def test_a_tracer_that_is_not_the_processes_has_no_probe(self):
        tr = Tracer()
        for _ in range(10):
            with tr.start_span("a"):
                pass
        assert _probe_threads() == [] and tr._probe is None

    # best of three: a loaded machine makes a probe late for a core, not
    # for the interpreter, and an idle moment lets a spinner's probe in
    @pytest.mark.parametrize("target, holds", [(_spin, True), (_sort, False)],
                             ids=["python_spinner", "numpy_sort"])
    def test_share_of_late_probes_is_the_share_the_lock_is_held(
            self, hooked, target, holds):
        shares = []
        for _ in range(3):
            shares.append(_contended_share(hooked, target))
            if (shares[-1] >= 0.9) if holds else (shares[-1] <= 0.2):
                break
        else:
            pytest.fail(f"contended / n read {shares}")
        assert hooked.gil_probes >= sum(
            s.tags["n"] for s in hooked.finished(Tracepoint.RUNTIME_GIL_PROBE))

    def test_probe_ends_by_itself_once_nothing_records(self, hooked):
        with hooked.start_span("a"):
            pass
        (probe,) = _probe_threads()
        assert probe.daemon
        time.sleep(0.25)
        hooked.enabled = False
        probe.join(timeout=1.0)
        assert not probe.is_alive() and hooked._probe is None
        seen = len(hooked.finished(Tracepoint.RUNTIME_GIL_PROBE))
        assert seen >= 1
        # and the next span that records starts another
        hooked.enabled = True
        with hooked.start_span("b"):
            pass
        assert len(_probe_threads()) == 1

    def test_probe_spans_are_roots_that_name_no_idle_gap_and_no_work(
            self, hooked):
        with hooked.start_span(Tracepoint.API_WRITE, {"n": 1000}):
            with hooked.start_span(Tracepoint.DB_WRITE_BATCH):
                time.sleep(0.3)
        probes = hooked.finished(Tracepoint.RUNTIME_GIL_PROBE)
        assert probes and all(s.parent_id is None for s in probes)
        assert all(s.tags.keys() == {"n", "contended", "wait_us", "max_us"}
                   and s.tags["n"] >= s.tags["contended"] for s in probes)
        assert all(90e6 <= s.duration_ns < 200e6 for s in probes)
        cell = _cell(hooked, slice_=(0.0, time.monotonic() + 1))
        spans = node_spans.load(cell)
        assert spans.work("ksample") == 1.0
        assert spans.work("query") == 0 and spans.work("pass") == 0
        assert Tracepoint.RUNTIME_GIL_PROBE in spans.by_name()
        # the table's own rule for what may name a gap of the device
        tr = tracefile.Trace({"d": [("a", 0.0, 0.01), ("b", 5.0, 0.01)]},
                             {}, [("req", 0.0, 1.0)] * 3, 5.01)
        t0 = min(n.t0 for n in spans.touching)
        cell = _cell(hooked, slice_=(t0, t0 + 5.01), trace_events=tr,
                     rows=[stats.Request("write", t0, t0 + 1.0, True, 1, i)
                           for i in range(3)])
        by_span = trace_idle_unnamed_pct.table(cell, node_spans.load(cell))
        assert set(by_span) == {Tracepoint.DB_WRITE_BATCH, "nothing_due"}


class TestCompileLog:
    def test_a_fresh_jit_is_one_row_and_a_child_of_what_waited(self, hooked):
        def fresh_program(x):
            return (x * 3.0 + 1.0).sum()

        x = jax.numpy.arange(64.0)
        before = len(hooked.compile_log())
        with hooked.start_span("outer") as outer:
            jax.jit(fresh_program)(x).block_until_ready()
        (row,) = [r for r in hooked.compile_log()[before:]
                  if "fresh_program" in r.fn]
        assert row.trace_s > 0 and row.lower_s > 0 and row.compile_s > 0
        assert row.thread == threading.current_thread().name
        assert row.seconds <= (row.end_ns - row.start_ns) / 1e9 + 1e-3
        (child,) = [s for s in hooked.finished(Tracepoint.RUNTIME_COMPILE)
                    if s.tags["fn"] == row.fn]
        assert child.parent_id == outer.span.span_id
        assert child.trace_id == outer.span.trace_id
        assert (outer.span.start_ns <= child.start_ns
                and child.end_ns <= outer.span.end_ns)
        assert child.tags["cache"] == row.cache
        assert child.tags["compile_s"] == row.compile_s
        assert hooked.compile_count >= 1
        assert hooked.compile_seconds["compile"] >= row.compile_s
        # the same call again compiles nothing
        n = len(hooked.compile_log())
        jax.jit(fresh_program)
        assert len(hooked.compile_log()) == n

    def test_outside_a_span_the_row_is_kept_and_no_span_opens(self, hooked):
        jax.jit(lambda x: x * 5.0 - 2.0)(jax.numpy.arange(9.0))
        assert hooked.compile_log()
        assert hooked.finished(Tracepoint.RUNTIME_COMPILE) == []

    def test_second_compile_of_a_program_reads_the_persistent_cache(
            self, hooked, tmp_path):
        from jax.experimental.compilation_cache import compilation_cache as cc

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
        was = {k: getattr(jax.config, k) for k in keys}
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0)
        jax.config.update(keys[2], -1)
        cc.reset_cache()
        try:
            def twice_compiled(x):
                return jax.numpy.cumsum(x * 7.0)[-1]

            x = jax.numpy.arange(33.0)
            jax.jit(twice_compiled)(x).block_until_ready()
            jax.clear_caches()
            jax.jit(twice_compiled)(x).block_until_ready()
        finally:
            for k, v in was.items():
                jax.config.update(k, v)
            cc.reset_cache()
        first, second = [r for r in hooked.compile_log()
                         if "twice_compiled" in r.fn]
        assert first.cache == "miss" and first.cache_read_s == 0
        assert second.cache == "hit" and second.cache_read_s > 0
        assert hooked.compile_cache_hits >= 1
        assert hooked.compile_cache_misses >= 1

    def test_after_uninstall_nothing_is_logged_and_no_span_opens(self):
        tr = Tracer()
        tracing.install(tr)
        tracing.uninstall(tr)
        with tr.start_span("outer"):
            jax.jit(lambda x: x / 3.0 + 11.0)(jax.numpy.arange(5.0))
        assert tr.compile_log() == [] and tr.compile_count == 0
        assert [s.name for s in tr.finished()] == ["outer"]
        assert _probe_threads() == []

    def test_the_log_keeps_the_newest_rows_and_counts_the_rest(
            self, monkeypatch):
        monkeypatch.setattr(tracing, "COMPILE_LOG_MAX", 3)
        tr = Tracer()
        for i in range(5):
            tr._on_compile_duration(
                "/jax/core/compile/jaxpr_trace_duration", 0.25, fun_name="f")
            tr._on_compile_duration(
                "/jax/core/compile/backend_compile_duration", 0.5,
                fun_name=f"jit(f{i})")
        assert [r.fn for r in tr.compile_log()] == [
            "jit(f2)", "jit(f3)", "jit(f4)"]
        assert tr.compiles_dropped == 2 and tr.compile_count == 5
        assert tr.compile_seconds["trace"] == pytest.approx(1.25)
        assert tr.compile_seconds["compile"] == pytest.approx(2.5)

    def test_an_outer_trace_takes_its_inner_traces_place(self):
        tr = Tracer()
        dur = tr._on_compile_duration
        trace = "/jax/core/compile/jaxpr_trace_duration"
        dur(trace, 5.0, fun_name="never_compiled")    # an eval_shape
        time.sleep(0.01)
        dur(trace, 0.001, fun_name="inner_a")
        dur(trace, 0.001, fun_name="inner_b")
        dur(trace, 0.004, fun_name="outer")           # began before both
        dur("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.002,
            fun_name="jit_outer")
        tr._on_compile_event("/jax/compilation_cache/cache_hits")
        dur("/jax/compilation_cache/compile_time_saved_sec", 1.5)
        dur("/jax/compilation_cache/cache_retrieval_time_sec", 0.001)
        dur("/jax/core/compile/backend_compile_duration", 0.003,
            fun_name="jit(outer)")
        dur("/jax/some/other/duration", 9.0)
        (row,) = tr.compile_log()
        assert row.fn == "jit(outer)" and row.cache == "hit"
        assert (row.trace_s, row.lower_s, row.cache_read_s, row.saved_s) == (
            0.004, 0.002, 0.001, 1.5)
        assert row.compile_s == pytest.approx(0.002)
        assert row.seconds == pytest.approx(0.009)


def test_runtime_counters_are_on_metrics(node):
    _write(node.port)
    _query(node.port)
    assert _probe_threads() == []       # tracing off: served with no probe
    jax.jit(lambda x: x * 13.0 + 0.5)(jax.numpy.arange(11.0))
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{node.port}/metrics").read().decode()
    values = {}
    for line in text.splitlines():
        if line.startswith(("m3tpu_runtime_gil_", "m3tpu_jit_compile")):
            name, value = line.rsplit(" ", 1)
            values[name] = float(value)
    assert values["m3tpu_jit_compiles_total"] >= 1
    assert values['m3tpu_jit_compile_seconds_total{phase="compile"}'] > 0
    assert {'m3tpu_jit_compile_seconds_total{phase="%s"}' % p
            for p in ("trace", "lower", "compile", "cache_read")} <= set(values)
    assert {"m3tpu_jit_compile_cache_hits_total",
            "m3tpu_jit_compile_cache_misses_total",
            "m3tpu_runtime_gil_probes_total",
            "m3tpu_runtime_gil_probes_contended_total",
            "m3tpu_runtime_gil_wait_seconds_total"} <= set(values)
    # tracing is off in this node: no probe ran
    assert values["m3tpu_runtime_gil_probes_total"] == 0


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
        assert node_span_tag_mean.read(cell, {
            "spans": ["runtime.gil.probe"], "tag": "wait_us", "of": "n",
            "scale": 0.001}) is None
        for what in ("seconds", "programs", "cache_hit_pct"):
            # none of these tracers keeps a compile log
            assert compile_log.read(cell, {"read": what}) is None

    def test_tag_mean_is_one_tags_sum_over_anothers(self):
        probe = "runtime.gil.probe"
        rows = [(1, None, probe, 0.0, 0.1,
                 {"n": 20, "contended": 1, "wait_us": 700, "max_us": 700}),
                (2, None, probe, 0.1, 0.2,
                 {"n": 6, "contended": 6, "wait_us": 90300, "max_us": 24000}),
                (3, None, "api.write", 0.0, 10.0, {"n": 2000}),
                (4, None, probe, 29.95, 30.05,     # ends past the slice
                 {"n": 9, "contended": 9, "wait_us": 9000, "max_us": 1000}),
                (5, None, probe, 0.2, 0.3, {})]    # an older probe: no tags
        cell = _cell(_FakeTracer(node_spans.build(rows)))
        params = {"spans": [probe], "tag": "wait_us", "of": "n",
                  "scale": 0.001}
        assert node_span_tag_mean.read(cell, params) == pytest.approx(
            91.0 / 26)
        assert node_span_tag_mean.read(
            cell, {**params, "scale": 1}) == pytest.approx(91000 / 26)
        assert node_span_tag_pct.read(cell, {
            "spans": [probe], "tag": "contended", "of": "n"}) == \
            pytest.approx(100 * 7 / 26)
        # the probe's roots count as nobody's work
        assert node_spans.load(cell).work("ksample") == pytest.approx(2.0)
        assert node_span_tag_mean.read(
            cell, {**params, "spans": ["api.write"]}) is None
        assert node_span_tag_mean.read(
            _cell(_FakeTracer(node_spans.build(rows[2:3]))), params) is None

    def test_compile_log_reads_the_rows_that_ended_before_the_window(self):
        row = tracing.CompileRow
        log = [row("jit(a)", "t", 1 * 10**9, 3 * 10**9, trace_s=0.5,
                   lower_s=0.25, compile_s=1.0, cache="miss"),
               row("jit(b)", "t", 3 * 10**9, 4 * 10**9, trace_s=0.125,
                   lower_s=0.125, compile_s=0.25, cache_read_s=0.5,
                   saved_s=7.0, cache="hit"),
               row("jit(c)", "u", 4 * 10**9, 5 * 10**9, compile_s=0.0625),
               row("jit(in_window)", "t", 9 * 10**9, 11 * 10**9,
                   compile_s=2.0, cache="miss")]
        tracer = SimpleNamespace(compile_log=lambda: list(log),
                                 compiles_dropped=0)
        cell = SimpleNamespace(asm=SimpleNamespace(tracer=tracer),
                               window=(10.0, 50.0))
        read = compile_log.read
        assert read(cell, {"read": "seconds"}) == pytest.approx(2.8125)
        assert read(cell, {"read": "programs"}) == 3
        assert read(cell, {"read": "cache_hit_pct"}) == pytest.approx(50.0)
        with pytest.raises(ValueError):
            read(cell, {"read": "something_else"})
        # the cache took part in none; nothing before the window; a log
        # that pushed rows out; a tracer without one
        cell.window = (4.5, 50.0)
        log[:2] = []
        assert read(cell, {"read": "programs"}) is None
        cell.window = (5.0, 50.0)
        assert read(cell, {"read": "programs"}) == 1
        assert read(cell, {"read": "cache_hit_pct"}) is None
        tracer.compiles_dropped = 1
        assert read(cell, {"read": "programs"}) is None
        cell.asm.tracer = Tracer()
        cell.window = (time.monotonic() + 1, None)
        assert read(cell, {"read": "programs"}) is None      # an empty log

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


# The program-span entries of the write, load and dashboard cells, each
# with the cells that read it (the `.agg` entries are held by
# tests/test_aggregator_service.py, the `.timer` ones by
# tests/test_aggregator_timer_service.py, the `.flushed` ones by
# tests/test_flushed_read.py, the runtime, read-locked and `.fleet` ones
# below): the fleet cell reads the dashboard cell's shape-free readers,
# and prom.mixed those of prom.remote_write
_LIVE, _FLEET, _MIXED = "prom.dashboard_live", "prom.fleet_quantile", "prom.mixed"
_WRITE = "prom.remote_write"
_SPAN_ENTRIES = {
    "decode_ms_per_ksample": [_WRITE, _MIXED],
    "decode_ms_per_ksample.load": ["tsbs.load"],
    "match_ms_per_ksample": [_WRITE, _MIXED],
    "lock_wait_ms_per_ksample": [_WRITE, _MIXED],
    "lock_wait_ms_per_ksample.load": ["tsbs.load"],
    "index_ms_per_ksample": [_WRITE, _MIXED],
    "index_ms_per_ksample.load": ["tsbs.load"],
    "commitlog_ms_per_ksample": [_WRITE, _MIXED],
    "commitlog_ms_per_ksample.load": ["tsbs.load"],
    "dispatch_ms_per_ksample": [_WRITE, _MIXED],
    "flush_writeback_ms_per_pass": [_WRITE, _MIXED],
    "write_unnamed_pct": [_WRITE, _MIXED],
    "write_unnamed_pct.load": ["tsbs.load"],
    "index_query_ms_per_query": [_LIVE, _FLEET],
    "series_read_ms_per_query": [_LIVE, _FLEET],
    "eval_ms_per_query": [_LIVE, _FLEET],
    "render_ms_per_query": [_LIVE, _FLEET],
    "lock_wait_ms_per_query": [_LIVE, _FLEET],
    "query_unnamed_pct": [_LIVE, _FLEET],
    "gc_pause_pct.write": [_WRITE, _MIXED],
    "gc_pause_pct.load": ["tsbs.load"],
    "gc_pause_pct.query": [_LIVE, _FLEET],
    "idle_unnamed_pct.write": [_WRITE, _MIXED],
    "idle_unnamed_pct.load": ["tsbs.load"],
    "idle_unnamed_pct.query": [_LIVE, _FLEET],
    "decode_cache_hit_pct": [_WRITE, _MIXED],
    "decode_cache_hit_pct.load": ["tsbs.load"],
    "read_columnar_pct": [_LIVE],
    "group_one_program_pct": [_LIVE],
}


def test_new_per_layer_entries_are_well_formed():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert len(_SPAN_ENTRIES) == 29
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in _SPAN_ENTRIES}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, cells in _SPAN_ENTRIES.items():
        m = check_workloads(bench, name, cells)
        assert m["source"] == "program_span"
        hit_share = name.startswith(("decode_cache_hit_pct",
                                     "read_columnar_pct",
                                     "group_one_program_pct"))
        assert m["layer"] in layers
        assert m["better"] == ("higher" if hit_share else "lower")
        for cell in cells:
            assert cell in e2e[m["moves"]]["workloads"], (name, cell)
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / (m["name"] + ".json")).read_text())
        assert spec["reducer"] in ("node_span_ms", "node_span_unnamed_pct",
                                   "node_span_slice_pct", "node_span_tag_pct",
                                   "trace_idle_unnamed_pct")


def _consecutive(names: list, wanted: list) -> list:
    """Positions of the `wanted` names that stand in the list, asserted
    to follow one another in that order."""
    at = [names.index(n) for n in wanted if n in names]
    assert at == list(range(at[0], at[0] + len(at))), wanted
    return at


_SUFFIXES = ("write", "load", "query", "agg", "timer", "flushed")
_GIL = [f"{kind}.{suffix}" for kind in ("gil_contended_pct", "gil_wait_ms")
        for suffix in _SUFFIXES]
_UNDER_SETUP = ["setup_compile_s", "setup_programs", "setup_cache_hit_pct"]


def test_runtime_entries_are_well_formed():
    """PR 35's fifteen: two readings of the interpreter-lock probe a
    cell, and the first three metrics under setup_s, in every cell."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len(_GIL) == 12
    gil = {}
    for name in _GIL:
        kind, suffix = name.split(".")
        m = entry(bench, name)
        gc_twin = entry(bench, "gc_pause_pct." + suffix)
        assert (m["layer"], m["moves"], m["workloads"], m["source"]) == (
            "guards", gc_twin["moves"], gc_twin["workloads"], "program_span")
        assert m["better"] == "lower"
        assert m["unit"] == {"gil_contended_pct": "%", "gil_wait_ms": "ms"}[kind]
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / (m["name"] + ".json")).read_text())
        assert spec["reducer"] == {"gil_contended_pct": "node_span_tag_pct",
                                   "gil_wait_ms": "node_span_tag_mean"}[kind]
        assert spec["params"]["spans"] == [Tracepoint.RUNTIME_GIL_PROBE]
        gil[m["name"]] = m
    # every cell in one entry of each reading
    assert sorted(c for m in gil.values() for c in m["workloads"]) == sorted(
        cells * 2)
    under_setup = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in under_setup] == _UNDER_SETUP
    assert "workloads" not in e2e["setup_s"]
    for m in under_setup:
        assert (m["layer"], m["source"], m["workloads"]) == (
            "guards", "program_counter", cells)
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / (m["name"] + ".json")).read_text())
        assert spec["reducer"] == "compile_log"
    # together, and the read-locked entries right after them
    names = [m["name"] for m in bench["per_layer"]]
    at = _consecutive(names, _GIL + _UNDER_SETUP + _READ_LOCKED)
    assert len(at) == len(gil) + 3 + sum(n in names for n in _READ_LOCKED)


_READ_LOCKED = ["read_locked_ms_per_query", "read_locked_ms_per_query.flushed"]


def test_read_locked_entries_are_well_formed():
    """PR 36's two: the time a fetch holds Database._mu, one a dashboard
    cell, read from the db.read.locked span whole, per query."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    mine = [check_workloads(bench, "read_locked_ms_per_query",
                            [_LIVE, _FLEET]),
            check_workloads(bench, "read_locked_ms_per_query.flushed",
                            ["prom.dashboard_flushed"])]
    # just before the fleet cell's three
    names = [m["name"] for m in bench["per_layer"]]
    at = _consecutive(names, _READ_LOCKED + list(_FLEET_NEW))
    assert len(at) == 3 + sum(n in names for n in _READ_LOCKED)
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
            == ("ms", "lower", "program_span", "HTTP front door + read path",
                "queries_per_s")
        assert m["workloads"][0] in e2e["queries_per_s"]["workloads"]
        spec = json.loads((REPO / "benchmark" / "metrics"
                           / (m["name"] + ".json")).read_text())
        assert (spec["reducer"], spec["params"]) == ("node_span_ms", {
            "spans": [Tracepoint.DB_READ_LOCKED], "per": "query",
            "self": False})


# the fleet cell's own metrics, each with its reader and layer
_FLEET_NEW = {
    "row_blocks_per_query.fleet": ("node_span_count", "query compute"),
    "labels_ms_per_query.fleet": ("node_span_ms",
                                  "HTTP front door + read path"),
    "rate_family_roofline.fleet": ("trace_roofline_counts", "kernels"),
}
# the dashboard cell's readers that the fleet cell reports too (none of
# them reads a panel's shape)
_FLEET_SHARED = (
    "query_req_p50_ms", "query_device_ms_per_query",
    "series_read_ms_per_query", "read_locked_ms_per_query",
    "lock_wait_ms_per_query", "index_query_ms_per_query",
    "eval_ms_per_query", "render_ms_per_query", "query_unnamed_pct",
    "device_idle_pct.query", "idle_unnamed_pct.query",
    "window_compiles.query", "gc_pause_pct.query", "gil_contended_pct.query",
    "gil_wait_ms.query")


def test_fleet_entries_are_well_formed():
    """The fleet cell's three metrics, consecutive, move queries_per_s
    in that cell alone; it is appended to the workloads of the dashboard
    cell's shape-free readers and of the set-up metrics, and not to the
    panel-shaped rate_family_roofline."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert len(_consecutive(names, list(_FLEET_NEW))) == 3
    assert len(bench["per_layer"]) <= 128
    assert "prom.fleet_quantile" in e2e["queries_per_s"]["workloads"]
    metrics = REPO / "benchmark" / "metrics"
    for name, (reducer, layer) in _FLEET_NEW.items():
        m = by_name[name]
        assert (m["moves"], m["workloads"], m["layer"]) == (
            "queries_per_s", ["prom.fleet_quantile"], layer)
        spec = json.loads((metrics / (name + ".json")).read_text())
        assert spec["reducer"] == reducer
    reading = {m["name"] for m in bench["per_layer"]
               if "prom.fleet_quantile" in m.get("workloads", ())}
    assert reading == set(_FLEET_NEW) | set(_FLEET_SHARED) | {
        "setup_compile_s", "setup_programs", "setup_cache_hit_pct"}
    for name in _FLEET_SHARED:
        check_workloads(bench, name, [_LIVE, _FLEET])
    # not the panel-shaped roofline: its bytes are calls x one panel's shape
    assert "prom.fleet_quantile" not in by_name["rate_family_roofline"][
        "workloads"]


def _block_cell(blocks, slice_=(0.0, 30.0), queries=2):
    """A traced cell whose ring holds `queries` query roots, each with
    the given query.eval.block spans' tags under its eval call."""
    spans = []
    sid = 0
    for q in range(queries):
        t = q * 10.0
        root, call = sid + 1, sid + 2
        spans.append((root, None, "api.queryRange", t + 1.0, t + 9.0, {}))
        spans.append((call, root, "query.eval.call", t + 2.0, t + 8.0, {}))
        sid += 2
        for i, tags in enumerate(blocks):
            sid += 1
            spans.append((sid, call, "query.eval.block", t + 2.0 + i * 0.1,
                          t + 2.05 + i * 0.1, dict(tags)))
    return _cell(_FakeTracer(node_spans.build(spans)), slice_=slice_)


class TestFleetReducers:
    def test_blocks_per_query(self):
        from benchmark.reducers import node_span_count

        tags = [{"rows": 64, "pad": 0, "points": 40, "steps": 23}] * 15 + [
            {"rows": 40, "pad": 24, "points": 40, "steps": 23}]
        cell = _block_cell(tags, queries=2)
        assert node_span_count.read(cell, {
            "spans": ["query.eval.block"], "per": "query"}) == 16
        # a program without the spans or tags reads nothing
        bare = _block_cell([], queries=2)
        assert node_span_count.read(bare, {
            "spans": ["query.eval.block"], "per": "query"}) is None

    def test_roofline_bytes_count_real_rows_whatever_the_blocks(self):
        """One call of 1,000 rows or 16 calls of 64 padded rows: the same
        bytes over all calls."""
        from benchmark import roofline_fleet

        one = _block_cell([{"rows": 1000, "pad": 0, "points": 40,
                            "steps": 23}], queries=1)
        many = _block_cell([{"rows": 64, "pad": 0, "points": 40,
                             "steps": 23}] * 15 + [
            {"rows": 40, "pad": 24, "points": 40, "steps": 23}], queries=1)
        want = 1000 * (16.0 * 40 + 8.0 * 23)
        assert roofline_fleet.rate_family_bytes(one, 1) == want
        assert abs(roofline_fleet.rate_family_bytes(many, 16) - want) < 1e-6
        assert roofline_fleet.rate_family_bytes(_block_cell([], queries=1),
                                                1) == 0.0
