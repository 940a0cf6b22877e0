"""The cell prom.mixed through the harness's own comparison
(`benchmark.selftest.drive`) at the selftest's small size: the fleet's
remote writes, open loop, beside the dashboard's viewers on one node."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import harness, selftest  # noqa: E402
from benchmark.generators import mixed  # noqa: E402
from benchmark.references import readback  # noqa: E402
from benchmark.reducers import node_spans  # noqa: E402

CELL = "prom.mixed"
# a scrape's period at the small size: long enough for an unloaded CPU
# to send a scrape and answer the viewers in between
PERIOD_S = 1.0


def _drive(monkeypatch, **kw):
    """-> (result line, the generator's Run, [(points, read-back)])."""
    runs, reads = [], []

    class Run(mixed.Run):
        def __init__(self, cell):
            super().__init__(cell)
            runs.append(self)

    def slowed(name):
        bench, cell, cfg, traffic = small(name)
        d = cfg["dataset"]
        n_series = d["histograms"] * len(d["le"]) + d["gauges"]
        traffic["offered_samples_per_s"] = n_series / PERIOD_S
        # a short window: the traced slice from its first second
        traffic["trace"] = dict(traffic["trace"], start_s=1, seconds=5)
        return bench, cell, cfg, traffic

    def read_raw(asm, namespace, data, idx, points):
        got = real_read(asm, namespace, data, idx, points)
        reads.append((points, idx, got))
        return got

    small, real_read = selftest._small, readback.read_raw
    monkeypatch.setattr(mixed, "Run", Run)
    monkeypatch.setattr(selftest, "_small", slowed)
    monkeypatch.setattr(readback, "read_raw", read_raw)
    res = selftest.drive(CELL, **kw)
    (run,) = runs
    return res, run, reads


def test_cell_is_in_the_benchmark_as_configured():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "prom_fleet_read_write", "mixed", 1)
    # its own configuration: prom_histogram_fleet's node and data under
    # the load of the source's whole fleet
    (entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert bench["configs"][-1] is entry
    cfg = json.loads((REPO / entry["file"]).read_text())
    fleet = json.loads((REPO / "benchmark" / "configs"
                        / "prom_histogram_fleet.json").read_text())
    assert entry["source"] == cfg["source"] != fleet["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in ("node", "namespace", "agg_namespace", "ring_points",
                "dataset", "rules", "guarantees"):
        assert cfg[key] == fleet[key], key
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("write_samples_per_s", "write_ack_p95_ms"):
        assert e2e[name]["workloads"][-1] == CELL
    # the rate of the viewers' queries spread 14.3 % over six seeds on
    # the chip, above half its bound: the cell reports the writers' two
    # metrics, and with them prom.remote_write's per-layer readers
    assert CELL not in e2e["queries_per_s"]["workloads"]
    mine = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    write = [m["name"] for m in bench["per_layer"]
             if "prom.remote_write" in m["workloads"]]
    assert mine == write and len(mine) == 24
    assert all(m["moves"] != "queries_per_s" for m in bench["per_layer"]
               if CELL in m["workloads"])
    traffic = json.loads((REPO / "benchmark" / "traffic" / "mixed.json")
                         .read_text())
    assert traffic["offered_samples_per_s"] == round(100_000 / 15) == (
        cfg["source_write_samples_per_s"])
    assert (traffic["senders"], traffic["viewers"]) == (4, 4)
    assert set(traffic["controls"]) == {"f32", "stale_read"}
    # the ring holds the history, the warm-up and every prepared scrape
    # of the largest shard (2,640 of the 10,346 series; harness.boot_node
    # sizes a shard's ring as slot_capacity x ring_points)
    ring = 4096 * cfg["ring_points"]
    scrapes = (traffic["history_scrapes"] + traffic["warmup_scrapes"]
               + traffic["max_scrapes"])
    assert scrapes * 2640 <= ring and traffic["history_scrapes"] == 240
    # at least 1.5 x a 40 s window at the offered rate
    assert traffic["max_scrapes"] >= 1.5 * 40 * 6667 / 10346


def test_both_paths_read_correct_at_the_offered_pace(monkeypatch):
    res, run, reads = _drive(monkeypatch, seconds=6.0, trace=1)
    assert res["correct"] is True, res["compared"]
    kinds = {r.kind for r in run.cell.log.rows}
    assert kinds == {"write", "query"} and res["failed"] == 0
    # open loop: each scrape of the window starts within a period of
    # its due time, and the dues are a period apart
    assert len(run.paced) >= 5
    dues = [d for d, _ in run.paced]
    assert np.allclose(np.diff(dues), PERIOD_S)
    assert all(0 <= s - d < PERIOD_S for d, s in run.paced), run.paced
    # the window's writes are in the read-back, bit for bit
    points, idx, got = reads[-1]
    assert points == run.k == run.k_window + len(run.paced)
    win = slice(run.k_window, run.k)
    assert readback.wrong_or_missing(
        got[:, win], run.data.vals[idx, win]) == 0
    # the readers of one path sum that path's own waits for the lock (the
    # queries' reader is not the cell's, but reads its run all the same)
    spans = node_spans.load(run.cell)
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert "lock_wait_ms_per_query" not in metrics
    metrics["lock_wait_ms_per_query"] = harness.read_metric(
        "lock_wait_ms_per_query", run.cell)
    own = {}
    for root, names in (("api.write", ("db.lock.wait",
                                       "downsample.lock.wait")),
                        ("api.queryRange", ("db.lock.wait",))):
        own[root] = sum(n.self_seconds for n in spans.under_roots({root})
                        if n.name in names) * 1e3
        assert own[root] > 0, root
    assert metrics["lock_wait_ms_per_ksample"] == pytest.approx(
        own["api.write"] / spans.work("ksample"))
    assert metrics["lock_wait_ms_per_query"] == pytest.approx(
        own["api.queryRange"] / spans.work("query"))
    # and not the other path's, whose waits stand in the same slice
    every = sum(n.self_seconds for n in spans.under_roots()
                if n.name == "db.lock.wait") * 1e3
    assert every > own["api.queryRange"]
    assert metrics["window_compiles.write"] == 0


@pytest.mark.parametrize("control, over, within", [
    ("f32", "hq_rel_err", "raw_wrong_or_missing"),
    ("stale_read", "raw_wrong_or_missing", "hq_rel_err"),
])
def test_each_control_reads_incorrect_by_its_own_limit(monkeypatch, control,
                                                        over, within):
    res, _, _ = _drive(monkeypatch, seconds=2.0, control=control)
    assert res["correct"] is False
    c = res["compared"]
    assert c[over]["value"] > c[over]["limit"]
    assert c[within]["value"] <= c[within]["limit"]
