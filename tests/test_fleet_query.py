"""A range query over the whole fleet: range functions evaluated in
fixed-shape row blocks (`query/engine.py` `Engine._range_rows`), and
labels that cost no Python object per fetched series per query.

The block size is patched down to 64 rows so that a node of ~1,000
bucket series over 4 shards takes 16 blocks, as a fleet of 100,000 takes
25 of 4,096.  Every family is row-wise, so a row must read the same bits
whichever way it was dispatched; the whole query through HTTP must
answer as `benchmark/references/promql.py` does; and a fleet that gains
a series without gaining a block compiles nothing.
"""

from __future__ import annotations

import json
import math
import sys
import time
import urllib.parse
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from m3_tpu.index.doc import Document
from m3_tpu.index.search import Term
from m3_tpu.instrument.tracing import Tracepoint, Tracer
from m3_tpu.query import engine as engine_mod
from m3_tpu.query.block import PaddedBlock, RawBlock
from m3_tpu.query.engine import Engine
from m3_tpu.query.storage_adapter import DatabaseStorage

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark.datasets import prom_histogram  # noqa: E402
from benchmark.references import promql  # noqa: E402

SEC = 10**9
R = 64
HIST = 40                      # scrapes of history, 15 s apart
SPEC = {"histograms": 100, "gauges": 8, "extreme_gauges": 0, "jobs": 16,
        "le": ["0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1",
               "2.5", "+Inf"],
        "scrape_interval_s": 15,
        "names": {"bucket": "smoke_http_request_duration_seconds_bucket",
                  "gauge": "smoke_temperature_celsius",
                  "extreme": "smoke_extreme_value"}}
BUCKET = SPEC["names"]["bucket"]
QUERY = f"histogram_quantile(0.5, sum by (job, le) (rate({BUCKET}[5m])))"


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """A node of 1,000 bucket series and 8 gauges over 4 shards, with
    HIST scrapes of history written as the HTTP handlers write them."""
    from m3_tpu.server.assembly import run_node

    root = tmp_path_factory.mktemp("fleet")
    start = harness.data_start(time.time_ns())
    data = prom_histogram.Dataset(SPEC, 37, start, HIST)
    asm = run_node(f"""
db:
  root: {root}
  namespaces:
    default: {{num_shards: 4, slot_capacity: 1024, sample_capacity: 65536}}
coordinator: {{listen_port: 0, tracing: true}}
mediator: {{enabled: false}}
""")
    docs = [Document.from_tags(i, t) for i, t in zip(data.ids, data.tags)]
    for k in range(HIST):
        asm.db.write_tagged_batch(
            "default", docs, np.full(data.n_series, data.ts[k], np.int64),
            data.vals[:, k])
    yield asm, data
    asm.close()


def _range(data):
    """(start, end, step) of the history's last 5 minutes and a half."""
    end = int(data.ts[HIST - 1])
    return end - 330 * SEC, end, 15 * SEC


class _Rows:
    """The node's storage with each fetch cut to its first `n` rows and
    one row emptied (no point in range), as a series that stopped
    reporting stands among those that did."""

    def __init__(self, db, n: int, empty: int):
        self._inner = DatabaseStorage(db, "default")
        self.n, self.empty = n, empty

    def fetch_raw(self, name, matchers, start_nanos, end_nanos):
        raw = self._inner.fetch_raw(name, matchers, start_nanos, end_nanos)
        ts, vals = raw.ts[:self.n].copy(), raw.values[:self.n].copy()
        counts = raw.counts[:self.n].copy()
        ts[self.empty], vals[self.empty] = np.iinfo(np.int64).max, np.nan
        counts[self.empty] = 0
        return RawBlock(ts, vals, counts, raw.series[:self.n])


@pytest.mark.parametrize("func", ["rate", "increase", "irate", "delta",
                                  "max_over_time"])
@pytest.mark.parametrize("n", [3 * R, 3 * R + 1, R - 5],
                         ids=["k_blocks", "k_blocks_plus_one", "under_one"])
def test_blocks_read_the_bits_of_one_call(fleet, monkeypatch, func, n):
    asm, data = fleet
    start, end, step = _range(data)
    storage = _Rows(asm.db, n, empty=n // 2)
    q = f"{func}({BUCKET}[5m])"
    one = Engine(storage).execute_range(q, start, end, step)
    monkeypatch.setattr(engine_mod, "_RANGE_BLOCK_ROWS", R)
    tracer = Tracer()
    blocked = Engine(storage, tracer=tracer).execute_range(q, start, end, step)
    assert blocked.series == one.series
    assert one.values.shape == (n, len(one.step_times))
    np.testing.assert_array_equal(blocked.values.view(np.uint64),
                                  one.values.view(np.uint64))
    assert np.isnan(one.values[n // 2]).all()
    assert not np.isnan(one.values).all()
    spans = tracer.finished(Tracepoint.EVAL_BLOCK)
    assert len(spans) == math.ceil(n / R)
    assert sum(s.tags["rows"] for s in spans) == n
    assert sum(s.tags["pad"] for s in spans) == ((-n) % R if n > R else 0)


def test_padded_rows_reach_no_aggregation_and_no_answer(fleet, monkeypatch):
    """sum over a padded block equals the sum over the exact rows, and a
    padded block leaves the engine with its series' rows alone."""
    asm, data = fleet
    start, end, step = _range(data)
    storage = _Rows(asm.db, 3 * R + 1, empty=7)
    ref = Engine(storage).execute_range(
        f"sum by (le) (rate({BUCKET}[5m]))", start, end, step)
    monkeypatch.setattr(engine_mod, "_RANGE_BLOCK_ROWS", R)
    eng = Engine(storage)
    got = eng.execute_range(f"sum by (le) (rate({BUCKET}[5m]))", start, end,
                            step)
    assert got.series == ref.series
    np.testing.assert_array_equal(got.values, ref.values)
    steps = np.arange(start, end + 1, step, dtype=np.int64)
    block = eng._eval(engine_mod.parse(f"rate({BUCKET}[5m])"), steps)
    assert isinstance(block, PaddedBlock)
    assert block.rows().shape[0] == 4 * R and block.num_series == 3 * R + 1
    assert block.materialized().values.shape == (3 * R + 1, len(steps))


def _http(asm, query: str, start: int, end: int, step: int) -> dict:
    url = (f"http://127.0.0.1:{asm.port}/api/v1/query_range?"
           + urllib.parse.urlencode({"query": query, "start": start / 1e9,
                                     "end": end / 1e9, "step": f"{step // SEC}s"}))
    body = json.loads(urllib.request.urlopen(url).read())
    return {tuple(sorted(s["metric"].items())):
            {int(round(t * 1e9)): float(v) for t, v in s["values"]}
            for s in body["data"]["result"]}


def test_fleet_quantile_through_http_against_the_reference(fleet, monkeypatch):
    asm, data = fleet
    monkeypatch.setattr(engine_mod, "_RANGE_BLOCK_ROWS", R)
    start, end, step = _range(data)
    steps = np.arange(start, end + 1, step, dtype=np.int64)
    got = _http(asm, QUERY, start, end, step)
    jobs = np.array([t[b"job"] for t in data.tags[:data.n_bucket]])
    assert set(got) == {(("job", "job-%d" % j),) for j in range(16)}
    worst = 0.0
    for j in range(16):
        rows = np.nonzero(jobs == b"job-%d" % j)[0]
        want = promql.hq_by_le(0.5, data.ubs, data.ts, data.vals[rows],
                               steps, 300 * SEC)
        g = got[(("job", "job-%d" % j),)]
        present = ~np.isnan(want)
        assert present.any() and set(g) == set(steps[present].tolist())
        have = np.array([g[t] for t in steps[present].tolist()])
        worst = max(worst, float(np.max(np.abs(have - want[present])
                                        / np.abs(want[present]))))
    assert worst <= 1e-9
    # the node's ring: one block span a dispatched call, with its tags
    blocks = asm.tracer.finished(Tracepoint.EVAL_BLOCK)[-math.ceil(
        data.n_bucket / R):]
    assert [b.tags["rows"] for b in blocks] == [R] * (data.n_bucket // R) + [
        data.n_bucket % R]
    assert {(b.tags["points"], b.tags["steps"]) for b in blocks} == {
        (HIST, len(steps))}


def test_a_series_more_in_the_same_blocks_compiles_nothing(fleet,
                                                           monkeypatch):
    """As test_segment_reduce_is_one_program counts: once the query has
    run at S series, S + 1 in the same number of blocks compiles no
    program at all, from the fetch to the answer."""
    from m3_tpu.x import tracewatch

    asm, data = fleet
    monkeypatch.setattr(engine_mod, "_RANGE_BLOCK_ROWS", R)
    start, end, step = _range(data)
    assert math.ceil(data.n_bucket / R) == math.ceil((data.n_bucket + 1) / R)
    was_installed = tracewatch.installed()
    tracewatch.install(raise_on_violation=False)
    try:
        before = _http(asm, QUERY, start, end, step)
        # one more instance's +Inf bucket, every scrape of the history
        tags = {b"__name__": BUCKET.encode(), b"job": b"job-3",
                b"instance": b"inst-new", b"le": b"+Inf"}
        doc = Document.from_tags(b"fleet-new-series", tags)
        for k in range(HIST):
            asm.db.write_tagged_batch("default", [doc],
                                      np.array([data.ts[k]], np.int64),
                                      np.array([float(k)]))
        snap = tracewatch.snapshot()
        after = _http(asm, QUERY, start, end, step)
        assert tracewatch.retraces_since(snap) == 0
    finally:
        if not was_installed:
            tracewatch.uninstall()
    assert set(after) == set(before)
    assert after[(("job", "job-3"),)] != before[(("job", "job-3"),)]


def test_labels_are_built_once_a_series(fleet):
    """The second fetch of a selector builds no meta: each is the object
    the first built, kept on its index document; the range function's
    name-free meta and the grouping key likewise."""
    from m3_tpu.query import functions as fn

    asm, data = fleet
    start, end, step = _range(data)
    st = DatabaseStorage(asm.db, "default")
    name = BUCKET.encode()
    a = st.fetch_raw(name, (), start, end)
    b = st.fetch_raw(name, (), start, end)
    assert len(a.series) >= data.n_bucket
    assert all(x is y for x, y in zip(a.series, b.series))
    assert all(m.drop_name() is m.drop_name() for m in a.series)
    g1, metas1 = fn.group_series([m.drop_name() for m in a.series],
                                 {b"job", b"le"}, None)
    g2, metas2 = fn.group_series([m.drop_name() for m in b.series],
                                 {b"job", b"le"}, None)
    np.testing.assert_array_equal(g1, g2)
    assert len(metas1) == 160 and all(x is y for x, y in zip(metas1, metas2))
    docs = asm.db.query_ids("default", Term(b"__name__", name), start, end)
    assert [m.tags for m in a.series] == [
        tuple(sorted(d.tags().items())) for d in sorted(docs,
                                                        key=lambda d: d.id)]


# -- the cell, through the harness's own comparison, at the selftest's size --


def _drive(monkeypatch, **kw) -> dict:
    """prom.fleet_quantile through harness.run_cell at the selftest's size
    (320 bucket series), its range calls in blocks of R rows."""
    from benchmark import selftest

    monkeypatch.setattr(engine_mod, "_RANGE_BLOCK_ROWS", R)
    # the traced slice opens 3 s into the window
    return selftest.drive("prom.fleet_quantile", seconds=6.0, **kw)


def test_cell_reads_correct_and_blocks_a_call_in_its_slice(monkeypatch):
    res = _drive(monkeypatch, trace=1)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["row_blocks_per_query.fleet"] == math.ceil(320 / R)
    assert got["window_compiles.query"] == 0
    assert got["labels_ms_per_query.fleet"] > 0
    assert "rate_family_roofline" not in got


def test_cell_reads_incorrect_when_a_block_is_lost_in_the_engine(
        monkeypatch):
    """A planted fault in the blocking itself: the last block's rates
    never reach the join (every row of it NaN, as if its call were
    dropped).  The harness's comparison has to see it."""
    real = engine_mod.Engine._range_rows

    def lossy(self, raw, vals_dtype, family):
        out = real(self, raw, vals_dtype, family)
        if out.shape[0] > R:
            out = out.at[out.shape[0] - R:].set(float("nan"))
        return out

    monkeypatch.setattr(engine_mod.Engine, "_range_rows", lossy)
    res = _drive(monkeypatch)
    assert res["correct"] is False
    assert res["compared"]["hq_rel_err"]["value"] > 1e-9 or (
        res["compared"]["answers_malformed"]["value"] > 0)


def test_fleet_columns_read_what_read_reads(fleet):
    """`Database.read_columns` cuts a shard whose runs are all as long
    (a fleet scraped together) as rows, and any other shard point by
    point: both against the single-id `Database.read`, by bits — an id
    the node never saw (no run: its shard takes the point-by-point cut)
    and a series with fewer points among them."""
    asm, data = fleet
    start, end, _ = _range(data)
    fewer = Document.from_tags(b"fleet-fewer", {
        b"__name__": BUCKET.encode(), b"job": b"job-1",
        b"instance": b"inst-fewer", b"le": b"+Inf"})
    for k in range(HIST - 5, HIST):
        asm.db.write_tagged_batch("default", [fewer],
                                  np.array([data.ts[k]], np.int64),
                                  np.array([float(k)]))
    ids = list(data.ids[:300]) + [b"never-seen", b"fleet-fewer"]
    cols = asm.db.read_columns("default", ids, start, end + 1)
    assert cols.index.tolist() == list(range(len(ids)))
    for row, sid in enumerate(ids):
        pts = asm.db.read("default", sid, start, end + 1)
        n = int(cols.counts[row])
        assert n == len(pts)
        assert cols.ts[row, :n].tolist() == [t for t, _ in pts]
        np.testing.assert_array_equal(
            cols.values[row, :n].view(np.uint64),
            np.array([v for _, v in pts], np.float64).view(np.uint64))
        assert (cols.ts[row, n:] == np.iinfo(np.int64).max).all()
        assert np.isnan(cols.values[row, n:]).all()
    assert cols.counts[-2] == 0 and cols.counts[-1] == 5
