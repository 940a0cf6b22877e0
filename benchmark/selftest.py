#!/usr/bin/env python3
"""Self-test of the benchmark, on the CPU, in a few minutes:
``python3 benchmark/selftest.py`` (or ``pytest benchmark/selftest.py``).

- the percentile and rate arithmetic on hand-made request logs, a window
  with a stall among them;
- the trace reduction on the small recorded trace kept beside this file
  (``data/sample_trace.json``, cut from a chip run);
- every ``metrics/*.json`` and ``traffic/*.json`` loads and names things
  that exist, every metric file is some entry's, and the per-layer list
  holds no copy of a reading but those it is known to hold;
- a per-unit span reader counts only below the roots that count its unit;
- the measurement path refuses to run without a TPU and prints no result;
- every cell of BENCHMARK.json reads ``correct: true`` on a sound run and
  ``correct: false`` with each control its traffic file lists
  (``--control``), and a run whose timed path is broken underneath (half
  of a batch left out behind the ack; a value altered where it is stored;
  an answer altered where it is produced) reads ``correct: false`` — all
  through the harness's own comparison, at a small size, with the look
  for a chip skipped.
"""

from __future__ import annotations

import contextlib
import copy
import importlib
import io
import json
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmark import harness, stats  # noqa: E402
from benchmark.reducers import (  # noqa: E402
    node_span_count, node_span_ms, node_spans, tracefile)


# -- arithmetic ---------------------------------------------------------------


def test_percentiles_and_rates():
    log = stats.RequestLog()
    # 20 requests of 10 ms, one of them stalled for 2 s
    for i in range(20):
        log.add("write", i * 0.1, i * 0.1 + (2.0 if i == 7 else 0.010),
                True, 100)
    rows = log.of("write")
    assert abs(stats.latency_ms(rows, 50) - 10.0) < 1e-6
    assert abs(stats.latency_ms(rows, 95) - 10.0) < 1e-6   # 19 of 20 below
    assert abs(stats.latency_ms(rows, 96) - 2000.0) < 1e-6  # the stall
    assert stats.percentile([], 50) is None
    assert stats.percentile([3, 1, 2], 100) == 3
    assert stats.percentile([3, 1, 2], 1) == 1
    # a rate is all the work over all the window, stall included
    assert stats.rate(2000, 4.0) == 500.0
    assert stats.rate(1, 0) is None
    assert abs(stats.iqr_share([10, 10.2, 9.9, 10.1, 10.0, 9.8]) - 0.025) < 0.01


def test_client_reducers_on_a_hand_made_window():
    class C:
        pass

    cell = C()
    cell.log = stats.RequestLog()
    cell.window = (100.0, 110.0)
    for i in range(10):
        cell.log.add("write", 100 + i, 100 + i + 0.5, i != 3, 2000)
    cell.counters = {"device.storage.buffer_append.calls": 72}
    cell.spans = {"maintenance_pass": [(99.0, 99.5), (101.0, 101.2),
                                       (105.0, 105.4)]}
    read = lambda name: harness.read_metric(name, cell)  # noqa: E731
    assert read("write_samples_per_s") == 9 * 2000 / 10.0   # acked only
    assert abs(read("write_ack_p95_ms") - 500.0) < 1e-6
    assert read("append_calls_per_ksample") == 72 / 18.0
    assert abs(read("maintain_ms_per_pass") - 300.0) < 1e-6   # window's two
    assert read("queries_per_s") is None                    # nothing to read
    assert read("arena_calls_per_ksample") is None
    # four viewers, one query each in flight when the window closes at
    # 110: sent inside it, so in the percentiles; done after it, so not
    # in the rate
    for v in range(4):
        cell.log.add("query", 101.0 + v, 105.0 + v, True, 0, v)
        cell.log.add("query", 105.0 + v, 110.5 + v, True, 0, v)
    assert read("queries_per_s") == 4 / 10.0
    assert abs(read("query_p90_ms") - 5500.0) < 1e-6


# -- the trace reduction --------------------------------------------------------


def test_trace_reduction_on_recorded_sample():
    with open(HERE / "data" / "sample_trace.json") as f:
        doc = json.load(f)
    tr = tracefile.from_json(doc["trace"])
    want = doc["expected"]
    assert abs(tr.busy_s - want["busy_s"]) < 1e-9
    assert 0 < tr.busy_s < tr.window_s
    secs, calls = tr.program_seconds(["buffer_append"])
    assert calls == want["buffer_append_calls"]
    assert abs(secs - want["buffer_append_s"]) < 1e-9
    assert tr.top_ops(3)[0][0] == want["top_program"]
    gaps = dict(tr.idle_gaps(10))
    assert abs(sum(gaps.values()) + tr.busy_s - want["span_s"]) < 1e-6
    # overlapping events count once
    t = tracefile.Trace({"d": [("a", 0.0, 1.0), ("b", 0.5, 1.0)]}, {}, [], 4.0)
    assert t.busy_s == 1.5
    # a gap is named by the traffic file's order where it lists an open
    # annotation, else by the shortest annotation open at its midpoint
    ops = {"d": [("a", 0.0, 1.0), ("b", 3.0, 1.0), ("c", 6.0, 1.0)]}
    host = [("long", 0.0, 7.0), ("short", 1.5, 1.0), ("other", 4.5, 1.0)]
    assert dict(tracefile.Trace(ops, {}, host, 7.0).idle_gaps(5)) == {
        "short": 2.0, "other": 2.0}
    assert dict(tracefile.Trace(ops, {}, host, 7.0, ["long"]).idle_gaps(5)) == {
        "long": 4.0}
    assert dict(tracefile.Trace(ops, {}, [], 7.0).idle_gaps(5)) == {
        "nothing_due": 4.0}


# -- files name things that exist ----------------------------------------------


def test_files_name_things_that_exist():
    with open(HERE.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        spec = harness.load_json("metrics", m["name"] + ".json")
        importlib.import_module("benchmark.reducers." + spec["reducer"])
        assert set(m.get("workloads", [])) <= cells, m["name"]
        if "moves" in m:
            assert m["moves"] in e2e, m["name"]
    named = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for p in (HERE / "metrics").glob("*.json"):
        spec = json.loads(p.read_text())
        importlib.import_module("benchmark.reducers." + spec["reducer"])
        assert p.stem in named, p.name         # no orphaned reader
    for p in (HERE / "traffic").glob("*.json"):
        spec = json.loads(p.read_text())
        importlib.import_module("benchmark.generators." + spec["generator"])
        assert spec["limits"] and spec["trace"]["seconds"] > 0
    for w in bench["workloads"]:
        _, _, cfg, _ = harness.load_cell(w["name"])
        assert (HERE / "configs" / cfg["node"]).exists()
        importlib.import_module("benchmark.datasets." + cfg["dataset"]["kind"])
    for c in bench["configs"]:
        cfg = json.loads((HERE.parent / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]), c["name"]
        assert cfg["source"] == c["source"]


# -- one reader per meaning ----------------------------------------------------

# The later entries of each group of per-layer entries that read the same
# thing (see `_copies`): they stand until the CPU tests that count entries
# by suffix (tests/test_node_spans.py, test_aggregator_service.py,
# test_aggregator_timer_service.py, test_flushed_read.py) select them by
# name; then each joins the `workloads` of its group's first entry and
# this set empties.  No entry may join it.
_NOT_YET_FOLDED = frozenset({
    "query_p90_ms.flushed", "query_req_p50_ms.flushed",
    "query_device_ms_per_query.flushed", "rate_family_roofline.flushed",
    "index_query_ms_per_query.flushed", "series_read_ms_per_query.flushed",
    "eval_ms_per_query.flushed", "render_ms_per_query.flushed",
    "lock_wait_ms_per_query.flushed", "query_unnamed_pct.flushed",
    "read_columnar_pct.flushed", "read_locked_ms_per_query.flushed",
    "frame_decode_ms_per_ksample.timer", "resolve_ms_per_ksample.timer",
    "add_ms_per_ksample.timer", "lock_wait_ms_per_ksample.timer",
    "dispatch_ms_per_ksample.timer", "flush_emit_ms_per_pass.timer",
    "arena_calls_per_ksample.timer", "frame_unnamed_pct.timer",
    "consume_ms_per_pass.timer",
    *(f"{kind}.{suffix}" for kind in (
        "device_idle_pct", "idle_unnamed_pct", "gc_pause_pct",
        "window_compiles", "gil_contended_pct", "gil_wait_ms")
      for suffix in ("flushed", "agg", "timer")),
})


def _spec(name: str) -> dict:
    return harness.load_json("metrics", name + ".json")


def _copies(per_layer: list[dict], spec_of=_spec) -> set:
    """Names of the entries that read what an earlier entry reads: the
    same reducer and params (the file without its `what`), `moves`,
    `layer`, `unit`, `better` and `source`."""
    first, later = {}, set()
    for m in per_layer:
        spec = spec_of(m["name"])
        spec.pop("what", None)
        key = json.dumps([spec] + [m[k] for k in (
            "moves", "layer", "unit", "better", "source")], sort_keys=True)
        if key in first:
            later.add(m["name"])
        first.setdefault(key, m["name"])
    return later


def test_per_layer_list_holds_no_new_copy():
    with open(HERE.parent / "BENCHMARK.json") as f:
        per_layer = json.load(f)["per_layer"]
    assert len(per_layer) <= 128
    assert _copies(per_layer) == _NOT_YET_FOLDED
    # the guard sees a copy: an entry equal to one before it, renamed
    first = per_layer[0]["name"]
    assert "x" in _copies(per_layer + [dict(per_layer[0], name="x")],
                          lambda n: _spec(first if n == "x" else n))


# -- span readers count a path's own spans ---------------------------------------


class _Ring:
    """A tracer's ring as node_spans.load reads it, from (id, parent,
    name, t0_s, t1_s, tags) rows."""

    def __init__(self, rows):
        self.dropped = self.dropped_until_ns = 0
        self._spans = [SimpleNamespace(
            span_id=i, parent_id=p, name=n, start_ns=int(t0 * 1e9),
            end_ns=int(t1 * 1e9), tags=tags) for i, p, n, t0, t1, tags in rows]

    def finished(self):
        return self._spans


def test_span_readers_count_only_under_their_units_roots():
    # one write of 2,000 samples, one query, one maintenance pass, each
    # waiting for the database lock; the decode opens under writes alone
    rows = [(1, None, "api.write", 0.0, 5.0, {"n": 2000}),
            (2, 1, "db.lock.wait", 1.0, 2.0, {}),
            (3, 1, "api.write.decode", 2.0, 2.5, {}),
            (4, None, "api.queryRange", 6.0, 10.0, {}),
            (5, 4, "db.lock.wait", 6.0, 8.0, {}),
            (6, 4, "query.eval.block", 8.0, 8.1, {}),
            (7, None, "mediator.runOnce", 11.0, 16.0, {}),
            (8, 7, "db.lock.wait", 11.0, 15.0, {}),
            (9, 7, "query.eval.block", 15.0, 15.1, {})]
    cell = SimpleNamespace(asm=SimpleNamespace(tracer=_Ring(rows)),
                           trace=True, slice=(0.0, 30.0))
    wait = {"spans": ["db.lock.wait"]}
    assert abs(node_span_ms.read(cell, {**wait, "per": "ksample"})
               - 1000.0 / 2) < 1e-6              # the write's 1 s alone
    assert abs(node_span_ms.read(cell, {**wait, "per": "query"})
               - 2000.0) < 1e-6                  # the query's 2 s alone
    assert abs(node_span_ms.read(cell, {**wait, "per": "pass"})
               - 4000.0) < 1e-6                  # the pass's 4 s alone
    assert node_span_count.read(
        cell, {"spans": ["query.eval.block"], "per": "query"}) == 1
    # a span that opens under one kind of root reads as over every root
    spans = node_spans.load(cell)
    whole = sum(n.self_seconds for n in spans.under_roots()
                if n.name == "api.write.decode")
    assert abs(node_span_ms.read(
        cell, {"spans": ["api.write.decode"], "per": "ksample"})
        - whole * 1e3 / 2) < 1e-6
    # nothing of the unit's own: nothing to read
    assert node_span_ms.read(
        cell, {"spans": ["api.write.decode"], "per": "pass"}) is None


# -- no TPU, no result ----------------------------------------------------------


def test_refuses_without_a_tpu():
    with open(HERE.parent / "BENCHMARK.json") as f:
        name = json.load(f)["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", name, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
    assert "needs" in p.stderr


# -- controls and planted faults ------------------------------------------------


def _small(name: str):
    """The cell at a size a test run can hold."""
    bench, cell, cfg, traffic = copy.deepcopy(_ORIG(name))
    d = cfg["dataset"]
    if d["kind"] == "prom_histogram":
        d["histograms"], d["gauges"] = 32, 40
    else:
        d["scale"] = 40
    if "max_samples_per_send" in traffic:
        traffic["max_samples_per_send"] //= 20
    if "history_scrapes" in traffic:
        traffic["history_scrapes"] = 40
    traffic["readback_series"] = 100
    return bench, cell, cfg, traffic


_ORIG = harness.load_cell


def drive(workload: str, seconds: float = 3.0, control: str = "",
          seed: int = 2**31 + 7, trace: int = 0) -> dict:
    """One run through harness.run_cell with the look for a chip
    skipped -> the result line."""

    class Args:
        pass

    a = Args()
    a.workload, a.seed, a.seconds, a.trace, a.control = (
        workload, seed, seconds, trace, control)
    harness.load_cell = _small
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            harness.run_cell(a, time.monotonic(),
                             {"platform": "cpu", "kind": "cpu", "count": 1})
    finally:
        harness.load_cell = _ORIG
    return json.loads(out.getvalue().splitlines()[-1])


def test_every_cell_sound_run_correct_and_each_control_not():
    with open(HERE.parent / "BENCHMARK.json") as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for name in cells:
        res = drive(name, seconds=4.0)
        assert res["correct"] is True, (name, res["compared"])
        assert res["attempted"] > 0 and res["failed"] == 0
        controls = _ORIG(name)[3]["controls"]
        assert controls, name
        for control in controls:
            res = drive(name, seconds=4.0, control=control)
            assert res["correct"] is False, (name, control, res["compared"])
            over = [k for k, c in res["compared"].items()
                    if c["value"] > c["limit"]]
            assert over, (name, control)


def test_write_cell_half_a_batch_left_out():
    """The node acks a request but stores only half of its samples."""
    from m3_tpu.server import http_api

    real = http_api._Handler._ingest_tagged

    def half(self, docs, ts, vals):
        n = len(docs) // 2
        real(self, docs[:n], ts[:n], vals[:n])
        return len(docs), 0

    http_api._Handler._ingest_tagged = half
    try:
        res = drive("tsbs.load")
    finally:
        http_api._Handler._ingest_tagged = real
    assert res["correct"] is False
    assert res["compared"]["raw_wrong_or_missing"]["value"] > 0


def test_write_cell_value_altered_where_it_is_stored():
    from m3_tpu.server import http_api

    real = http_api._Handler._ingest_tagged

    def altered(self, docs, ts, vals):
        vals = list(vals)
        vals[0] = vals[0] + 1.0
        return real(self, docs, ts, vals)

    http_api._Handler._ingest_tagged = altered
    try:
        res = drive("prom.remote_write")
    finally:
        http_api._Handler._ingest_tagged = real
    assert res["correct"] is False


def test_query_cell_answer_altered_where_it_is_produced():
    """The quantile kernel's output scaled by 1 + 1e-6."""
    from m3_tpu.query import device_fns

    real = device_fns.histogram_quantile_groups

    def altered(*a, **kw):
        return real(*a, **kw) * (1.0 + 1e-6)

    device_fns.histogram_quantile_groups = altered
    try:
        res = drive("prom.dashboard_live", seconds=4.0)
    finally:
        device_fns.histogram_quantile_groups = real
    assert res["correct"] is False
    assert res["compared"]["hq_rel_err"]["value"] > 1e-7


def main() -> int:
    tests = [v for k, v in sorted(globals().items()) if k.startswith("test_")]
    failed = 0
    for t in tests:
        t0 = time.monotonic()
        try:
            t()
            print(f"ok   {t.__name__} ({time.monotonic() - t0:.1f}s)")
        except Exception:  # noqa: BLE001 — reported, counted
            import traceback

            traceback.print_exc()
            print(f"FAIL {t.__name__}")
            failed += 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
