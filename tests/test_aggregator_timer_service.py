"""The aggregator's timer deployment as a served service (BASELINE
config #4 at 1,000 timer ids): untimed timers through
client/aggregator_client.py with acks on, ids repeated inside a frame,
the flush manager ticked on the data clock, the m3msg topic consumed
and acked, every emitted P50 / P95 / P99 held to the plain reference
(m3_tpu/comparator/naive_timer.py): by bits to the selection over
float32(values), within 2^-23 of the selection over the f64 values.

Three data minutes of ten 6 s intervals; per id and window from 1 to
300 samples; the third minute holds twice the samples and overflows the
configured timer_sample_capacity, so the buffer grows once and still
answers exactly.
"""

import importlib
import json
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from m3_tpu.aggregator import packed
from m3_tpu.aggregator.quantile_cm import Stream
from m3_tpu.client.aggregator_client import AggregatorClient
from m3_tpu.cluster.placement import Instance, initial_placement
from m3_tpu.comparator import naive_timer
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.metrics.aggregation import AggregationType
from m3_tpu.msg import protocol as wire
from m3_tpu.msg.transport import RemoteBusConsumer
from m3_tpu.server.assembly import run_aggregator
from tests.per_layer_entries import check_workloads

SEC = 10**9
MINUTE = 60 * SEC
T0 = 1_700_000_000 * SEC // MINUTE * MINUTE
IDS, MINUTES, INTERVALS, SEED = 1000, 3, 10, 20261003
SAMPLE_CAPACITY = 16384
QTYPES = (naive_timer.P50, naive_timer.P95, naive_timer.P99)
REPO = Path(__file__).resolve().parent.parent

NODE_YAML = """
db:
  root: {root}
coordinator: null
aggregator:
  listen_port: 0
  num_shards: 1
  capacity: 1024
  num_windows: 2
  timer_sample_capacity: {sample_capacity}
  storage_policies: ["1m:2d"]
  default_aggregations:
    timer: [P50, P95, P99]
  instance_id: agg-0
  lease: 30s
  flush_interval: 6h
  topic: aggregated_metrics
  consumer_service: coordinator
  metrics_listen_port: 0
  tracing: true
"""


def make_minute(rng, m: int):
    """(series, interval, value) of one data minute: id i has 1 + i % 13
    samples, every hundredth id 300, all doubled in the last minute;
    each sample in an interval the seed picks, so an id repeats inside
    an interval's frame.  Values lognormal, full f64 mantissas."""
    per_id = np.where(np.arange(IDS) % 100 == 0, 300, 1 + np.arange(IDS) % 13)
    if m == MINUTES - 1:
        per_id = per_id * 2
    series = np.repeat(np.arange(IDS), per_id)
    interval = rng.integers(0, INTERVALS, len(series))
    level = 1.0 + (series % 97) * 3.7
    return series, interval, level * rng.lognormal(0.0, 0.75, len(series))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("aggtimer")
    rng = np.random.default_rng(SEED)
    asm = run_aggregator(NODE_YAML.format(
        root=root, sample_capacity=SAMPLE_CAPACITY))
    svc = asm.aggregator
    svc.handler.max_ids = 300      # several messages per drained window
    consumer = client = None
    try:
        client = AggregatorClient(
            initial_placement([Instance("agg-0", "g1")], num_shards=4, rf=1),
            lambda inst: ("127.0.0.1", svc.port))
        consumer = RemoteBusConsumer(("127.0.0.1", svc.msg_port),
                                     "coordinator", "c1")
        ids = [(b"stats.timers.req_%d+dc=d%d,host=h%05d" % (i % 7, i % 3, i))
               + b"x" * (i % 5) for i in range(IDS)]
        series, times, values, acked, roles, frames = [], [], [], 0, [], []
        for m in range(MINUTES):
            s, iv, v = make_minute(rng, m)
            for k in range(INTERVALS):
                t = T0 + m * MINUTE + k * 6 * SEC
                sel = rng.permutation(np.flatnonzero(iv == k))
                frames.append(len(sel) - len(np.unique(s[sel])))
                assert client.write_batch(
                    np.full(len(sel), naive_timer.TIMER),
                    [ids[i] for i in s[sel]], v[sel],
                    np.full(len(sel), t)) == len(sel)
                acked += client.flush()
                series.append(s[sel])
                times.append(np.full(len(sel), t))
                values.append(v[sel])
            roles.append(svc.tick(T0 + (m + 1) * MINUTE))
        series, times, values = map(np.concatenate, (series, times, values))
        want = naive_timer.expected(ids, series, times, values, MINUTE)
        got, rows = {}, 0
        deadline = time.monotonic() + 60
        while rows < len(want) and time.monotonic() < deadline:
            for mid, _shard, payload in consumer.poll(timeout_s=0.2):
                if mid not in got:
                    got[mid] = wire.decode_aggregated_batch(payload)
                    rows += len(got[mid][6])
                consumer.ack(mid)
        emitted = []
        for mt, policy, ts, mids, row_ids, row_types, vals in got.values():
            assert policy == "1m:2d" and mt == naive_timer.TIMER
            emitted.extend((mids[i], ts, t, v) for i, t, v in zip(
                row_ids.tolist(), row_types.tolist(), vals.tolist()))
        yield {"asm": asm, "svc": svc, "want": want, "emitted": emitted,
               "messages": got, "acked": acked, "roles": roles,
               "ids": ids, "series": series, "times": times,
               "values": values, "repeats_in_frames": frames}
    finally:
        for c in (consumer, client):
            if c is not None:
                c.close()
        asm.close()


def bits(x: float) -> int:
    return int(np.float64(x).view(np.int64))


class TestServedTimerQuantiles:
    def test_ids_repeat_inside_every_frame(self, served):
        assert min(served["repeats_in_frames"]) > 0

    def test_every_quantile_arrives_exactly_once(self, served):
        keys = [r[:3] for r in served["emitted"]]
        assert len(keys) == len(set(keys)), "a quantile arrived twice"
        assert set(keys) == set(served["want"])
        # every id has a sample in every minute: three types, three windows
        assert len(keys) == IDS * len(QTYPES) * MINUTES
        assert {t for _, _, t in keys} == set(QTYPES)

    def test_equal_by_bits_to_the_f32_selection(self, served):
        want = served["want"]
        for sid, ts, t, v in served["emitted"]:
            assert bits(v) == bits(want[(sid, ts, t)][0]), (sid, ts, t)

    def test_within_2_to_minus_23_of_the_f64_selection(self, served):
        want, worst = served["want"], 0.0
        for sid, ts, t, v in served["emitted"]:
            w = want[(sid, ts, t)][1]
            worst = max(worst, abs(v - w) / abs(w))
        assert 0.0 < worst <= 2.0 ** -23    # f32 rounding is real, and all

    def test_an_acked_frame_is_in_its_window(self, served):
        # every frame was acked and the leader counted every acked
        # sample; the equality with the reference is over exactly these
        n = len(served["series"])
        assert served["acked"] == n
        snap = served["asm"].registry.snapshot()
        assert snap["m3tpu.ingest_tcp.samples"] == n
        assert snap.get("m3tpu.ingest_tcp.shed_frames", 0) == 0
        assert served["svc"].aggregator.counters()["drops"] == 0
        assert served["roles"] == ["leader"] * MINUTES

    def test_the_consumer_acked_every_message(self, served):
        deadline = time.monotonic() + 10
        while served["svc"].unacked() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert served["svc"].unacked() == 0
        assert served["svc"].bus.acked == len(served["messages"])

    def test_a_window_over_the_configured_capacity_grows_and_is_exact(
            self, served):
        per_window = np.bincount((served["times"] - T0) // MINUTE)
        assert per_window[:2].max() <= SAMPLE_CAPACITY < per_window[2]
        counters = served["svc"].aggregator.counters()
        assert counters["timer_buffer_grows"] == 1
        (ml,) = served["svc"].aggregator.shards[0].lists.values()
        assert ml.timers.sample_capacity == 2 * SAMPLE_CAPACITY
        # the grown window's answers are among those held by bits above
        last = T0 + MINUTES * MINUTE
        assert sum(1 for _, ts, _, _ in served["emitted"] if ts == last) \
            == IDS * len(QTYPES)
        # drained: nothing left in either open window's buffer
        assert counters["timer_samples_buffered"] == 0

    def test_metrics_endpoint_has_the_timer_counters(self, served):
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{served['asm'].port}/metrics",
            timeout=10).read().decode()
        assert "m3tpu_aggregator_timer_buffer_grows 1" in text
        assert "m3tpu_aggregator_timer_samples_buffered 0" in text
        # P50/P95/P99 alone: every minute drained without the moments
        assert f"m3tpu_aggregator_timer_moments_skipped {MINUTES}" in text
        assert f"m3tpu_aggregator_flush_values {len(served['want'])}" in text

    def test_inside_cm_streams_eps_band(self, served):
        """Upstream answers through cm.Stream (eps 1e-3): its answer and
        the exact rank's lie within eps n ranks of each other on the
        same samples (here, for n <= 600, within one rank)."""
        in_first = served["times"] < T0 + MINUTE
        s, v = served["series"][in_first], served["values"][in_first]
        want, ids = served["want"], served["ids"]
        for i in list(range(0, IDS, 100)) + list(range(1, 40)):
            mine = np.sort(v[s == i].astype(np.float32).astype(np.float64))
            stream = Stream([0.5, 0.95, 0.99])
            stream.add_batch(mine[np.random.default_rng(i).permutation(
                len(mine))].tolist())
            stream.flush()
            for t in QTYPES:
                q = naive_timer.QUANTILE[t]
                exact = want[(ids[i], T0 + MINUTE, t)][0]
                r = int(np.searchsorted(mine, exact, side="right"))
                r_cm = int(np.searchsorted(mine, stream.quantile(q),
                                           side="right"))
                slack = int(np.ceil(stream.eps * len(mine))) + 1
                assert abs(r - r_cm) <= slack, (i, t, r, r_cm, len(mine))

    def test_drain_spans_by_arena(self, served):
        spans = served["asm"].tracer.finished()
        by_id = {s.span_id: s for s in spans}
        for kind, slots in (("counter", 0), ("gauge", 0), ("timer", IDS)):
            name = f"{Tracepoint.AGG_DRAIN}.{kind}"
            drains = [s for s in spans if s.name == name]
            assert len(drains) == MINUTES
            for d in drains:
                assert by_id[d.parent_id].name == Tracepoint.AGG_CONSUME
                assert d.tags["slots"] == slots and d.tags["bytes"] > 0
                kids = {s.name for s in spans if s.parent_id == d.span_id}
                assert kids >= {"device.arena.consume", name + ".wait",
                                name + ".to_host"}
                assert (Tracepoint.AGG_FLUSH_EMIT in kids) == (slots > 0)
        per_window = np.bincount((served["times"] - T0) // MINUTE)
        assert [s.tags["samples"] for s in spans
                if s.name == Tracepoint.AGG_DRAIN + ".timer"] \
            == per_window.tolist()


class TestArenaSentinels:
    def test_consume_with_a_buffer_64_times_the_samples(self):
        """The drain sorts the whole buffer: with 64 empty-sentinel
        words per sample the answers are still the reference's."""
        rng = np.random.default_rng(SEED)
        capacity, n = 64, 500
        arena = packed.PackedTimerArena(2, capacity, 64 * n)
        slots = rng.integers(0, 50, n)           # slots 50.. stay empty
        values = rng.lognormal(3.0, 1.0, n)
        for part in np.array_split(np.arange(n), 4):
            arena.ingest(np.ones(len(part), np.int32), slots[part],
                         values[part], np.full(len(part), T0))
        lanes, counts = map(np.asarray, arena.consume(1))
        want = naive_timer.quantiles(slots, np.full(n, T0), values, MINUTE)
        assert arena.grows == 0 and arena.sample_capacity == 64 * n
        assert np.array_equal(np.flatnonzero(counts), want["series"])
        assert np.array_equal(counts[want["series"]], want["count"])
        for t in (AggregationType.P50, AggregationType.P95,
                  AggregationType.P99):
            q = naive_timer.QUANTILE[int(t)]
            lane = lanes[want["series"], arena.lane_for_type(t)]
            assert np.array_equal(lane.view(np.int64),
                                  want["f32"][q].view(np.int64))
        # the other window holds nothing
        assert not np.asarray(arena.consume(0)[1]).any()


class TestNaiveTimer:
    def test_nearest_rank_by_hand(self):
        r = naive_timer.quantiles(
            [0] * 10 + [1] + [2] * 2, [5] * 13,
            [10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 42.5, 7, 3], 60)
        assert r["series"].tolist() == [0, 1, 2]
        assert r["count"].tolist() == [10, 1, 2]
        assert r["window_end"].tolist() == [60] * 3
        assert r["f64"][0.5].tolist() == [5, 42.5, 3]    # ranks 5, 1, 1
        assert r["f64"][0.95].tolist() == [10, 42.5, 7]  # ranks 10, 1, 2
        assert r["f64"][0.99].tolist() == [10, 42.5, 7]

    def test_f32_selection_is_the_image_of_the_f64_selection(self):
        rng = np.random.default_rng(7)
        s = rng.integers(0, 30, 4000)
        v = rng.lognormal(0, 2, 4000)
        r = naive_timer.quantiles(s, np.zeros(4000, np.int64), v, 60)
        for q in (0.5, 0.95, 0.99):
            assert np.array_equal(
                r["f32"][q], r["f64"][q].astype(np.float32).astype(np.float64))

    def test_shares_no_code_with_the_aggregator(self):
        src = (REPO / "m3_tpu" / "comparator" / "naive_timer.py").read_text()
        assert "import jax" not in src and "m3_tpu" not in "".join(
            ln for ln in src.splitlines()
            if ln.startswith(("import ", "from ")))


# the cell's twenty per-layer entries
_TIMER = [n + ".timer" for n in (
    "frame_decode_ms_per_ksample", "resolve_ms_per_ksample",
    "add_ms_per_ksample", "lock_wait_ms_per_ksample",
    "dispatch_ms_per_ksample", "flush_emit_ms_per_pass",
    "consume_ms_per_pass", "arena_calls_per_ksample", "frame_unnamed_pct",
    "device_idle_pct", "idle_unnamed_pct", "gc_pause_pct", "window_compiles",
    "timer_drain_ms_per_pass", "timer_lanes_to_host_ms_per_pass",
    "timer_ingest_device_ms_per_ksample", "timer_consume_device_ms_per_call",
    "empty_arena_consume_device_ms_per_pass", "timer_ingest_roofline",
    "timer_consume_roofline")]


class TestBenchmarkEntries:
    def test_timer_per_layer_entries_are_well_formed(self):
        """BENCHMARK.json's `.timer` entries: the cell's twenty, each
        with its reader's file, a reducer that exists, the cell in
        `workloads`, the cell's rate as what it moves."""
        sys.path.insert(0, str(REPO))
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
        (cell,) = [w for w in bench["workloads"]
                   if w["name"] == "m3agg.timer_quantile"]
        assert cell["chips"] == 1 and cell["traffic"] == "agg_timer_quantile"
        (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
        assert cfg["name"] == "m3aggregator_timer"
        # the file's own rule for a line of prose (the first hand-in
        # was refused over a `why` of 212 characters)
        for line in (cell["why"], cfg["why"], cfg["source"]):
            assert 1 <= len(line) <= 200 and line.isprintable() \
                and line.isascii()
        conf = json.loads((REPO / cfg["file"]).read_text())
        assert conf["source"] == cfg["source"]
        assert sorted(conf["reduced"]) == sorted(cfg["reduced"]) == [
            "ids", "instances", "num_shards"]
        d = conf["dataset"]
        assert d["scale"] * d["timers_per_source"] \
            == conf["reduced"]["ids"]["here"]
        assert conf["samples_per_id_per_window"] == 10
        traffic = json.loads((REPO / "benchmark" / "traffic" / (
            cell["traffic"] + ".json")).read_text())
        assert traffic["max_samples_per_send"] == 8192
        assert set(traffic["controls"]) == {
            "bf16", "lost_frame", "rank_plus_one"}
        node = (REPO / "benchmark" / "configs" / conf["node"]).read_text()
        assert "timer: [P50, P95, P99]" in node
        # by name (the two `gil_` entries are held by
        # tests/test_node_spans.py); a
        # copy of an `.agg` reader or a `.load` guard may stand folded
        # into that entry
        assert len(_TIMER) == len(set(_TIMER)) == 20
        layers = {m["layer"] for m in bench["per_layer"]
                  if not m["name"].endswith(".timer")}
        (rate,) = [m for m in bench["end_to_end"]
                   if cell["name"] in m.get("workloads", ())]
        for name in _TIMER:
            m = check_workloads(bench, name, [cell["name"]])
            assert m["layer"] in layers
            assert m["moves"] == rate["name"] == "load_samples_per_s"
            assert m["better"] == ("higher" if "roofline" in name
                                   else "lower")
            spec = json.loads((REPO / "benchmark" / "metrics"
                               / (m["name"] + ".json")).read_text())
            importlib.import_module("benchmark.reducers." + spec["reducer"])
