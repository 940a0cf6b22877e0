"""The aggregator as a served service (server/assembly.run_aggregator):
frames through client/aggregator_client.py with acks on, the flush
manager ticked on the data clock, the m3msg topic consumed and acked,
every emitted aggregate held to the plain reference
(m3_tpu/comparator/naive_rollup.py).

Reference shape: BASELINE config #3 at 2,000 series — counters and
gauges half and half, one sample per series per 10 s, policy 1m, sum /
min / max / last, three data minutes; a mirrored follower beside the
leader (reference follower_flush_mgr.go) on the same KV.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from m3_tpu.aggregator.service import AggregatorService
from m3_tpu.client.aggregator_client import AggregatorClient
from m3_tpu.cluster.placement import Instance, initial_placement
from m3_tpu.comparator import naive_rollup
from m3_tpu.core.config import ConfigError, load_config
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.metrics.types import MetricType
from m3_tpu.msg import protocol as wire
from m3_tpu.msg.transport import RemoteBusConsumer
from m3_tpu.server.assembly import run_aggregator
from tests.per_layer_entries import check_workloads

SEC = 10**9
MINUTE = 60 * SEC
T0 = 1_700_000_000 * SEC // MINUTE * MINUTE
SERIES, MINUTES, SEED = 2000, 3, 20261001
TYPES = {naive_rollup.COUNTER: (naive_rollup.SUM, naive_rollup.MIN,
                                naive_rollup.MAX, naive_rollup.LAST),
         naive_rollup.GAUGE: (naive_rollup.SUM, naive_rollup.MIN,
                              naive_rollup.MAX, naive_rollup.LAST)}

NODE_YAML = """
db:
  root: {root}
coordinator: null
aggregator:
  listen_port: 0
  num_shards: 1
  capacity: 2048
  num_windows: 2
  storage_policies: ["1m:2d"]
  default_aggregations:
    counter: [SUM, MIN, MAX, LAST]
    gauge: [SUM, MIN, MAX, LAST]
  instance_id: {instance}
  lease: 30s
  flush_interval: 6h
  topic: aggregated_metrics
  consumer_service: coordinator
  metrics_listen_port: 0
  tracing: true
"""


def make_series(rng):
    """ids in upstream's name+tag=value,... form, odd lengths; half
    counters, half gauges, interleaved by the seed."""
    ids = [(b"stats.svc%d.req+dc=d%d,host=h%05d" % (i % 7, i % 3, i))
           + b"x" * (i % 5) for i in range(SERIES)]
    types = np.where(rng.permutation(SERIES) < SERIES // 2,
                     int(MetricType.COUNTER), int(MetricType.GAUGE))
    return ids, types.astype(np.uint8)


def make_values(rng, types, k):
    """Interval k's sample of every series: gauges full-mantissa f64,
    counters integer increments, 1 % of them wide."""
    gauges = rng.standard_normal(SERIES) * 100.0
    small = rng.integers(0, 2001, SERIES)
    wide = rng.integers(1 << 20, 1 << 31, SERIES)
    counters = np.where(np.arange(SERIES) % 100 == 7, wide, small)
    return np.where(types == int(MetricType.COUNTER),
                    counters.astype(np.float64), gauges)


def poll_topic(consumer, want_rows: int, timeout_s: float = 60.0):
    """Poll and ack until `want_rows` rows arrived -> decoded messages
    by message id."""
    got, rows = {}, 0
    deadline = time.monotonic() + timeout_s
    while rows < want_rows and time.monotonic() < deadline:
        for mid, _shard, payload in consumer.poll(timeout_s=0.2):
            if mid not in got:
                got[mid] = wire.decode_aggregated_batch(payload)
                rows += len(got[mid][6])
            consumer.ack(mid)
    return got


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("agg")
    rng = np.random.default_rng(SEED)
    asm = run_aggregator(NODE_YAML.format(root=root, instance="agg-leader"))
    svc = asm.aggregator
    svc.handler.max_ids = 300      # several messages per drained window
    # a mirrored follower of the same shard set, on the leader's KV
    fcfg = load_config(NODE_YAML.format(
        root=root, instance="agg-follower")).aggregator
    follower = AggregatorService(fcfg, asm.kv)
    consumer = f_consumer = client = None
    try:
        placement = initial_placement(
            [Instance("agg-leader", "g1"), Instance("agg-follower", "g2")],
            num_shards=4, rf=2)
        ports = {"agg-leader": svc.port, "agg-follower": follower.port}
        client = AggregatorClient(
            placement, lambda inst: ("127.0.0.1", ports[inst]))
        consumer = RemoteBusConsumer(("127.0.0.1", svc.msg_port),
                                     "coordinator", "c1")
        f_consumer = RemoteBusConsumer(("127.0.0.1", follower.msg_port),
                                       "coordinator", "c1")
        ids, types = make_series(rng)
        series, times, values, acked, roles = [], [], [], 0, []
        for k in range(MINUTES * 6):
            t = T0 + k * 10 * SEC
            order = rng.permutation(SERIES)
            vals = make_values(rng, types, k)[order]
            # every sample reaches both owners of its shard
            assert client.write_batch(
                types[order], [ids[i] for i in order], vals,
                np.full(SERIES, t)) == 2 * SERIES
            acked += client.flush()
            series.append(order)
            times.append(np.full(SERIES, t))
            values.append(vals)
            if (t + 10 * SEC) % MINUTE == 0:
                roles.append((svc.tick(t + 10 * SEC),
                              follower.tick(t + 10 * SEC)))
        want = naive_rollup.expected(
            ids, types, np.concatenate(series), np.concatenate(times),
            np.concatenate(values), MINUTE, TYPES)
        messages = poll_topic(consumer, len(want))
        yield {
            "asm": asm, "svc": svc, "follower": follower, "want": want,
            "messages": messages, "acked": acked, "roles": roles,
            "types": dict(zip(ids, types.tolist())),
            "follower_polled": f_consumer.poll(timeout_s=0.3),
        }
    finally:
        for c in (consumer, f_consumer, client):
            if c is not None:
                c.close()
        follower.close()
        asm.close()


def emitted(messages) -> list:
    """(id, window_end, aggregation type, value) of every row."""
    out = []
    for _mt, policy, ts, ids, row_ids, row_types, values in messages.values():
        assert policy == "1m:2d"
        out.extend((ids[i], ts, t, v) for i, t, v in zip(
            row_ids.tolist(), row_types.tolist(), values.tolist()))
    return out


def bits(x: float) -> int:
    return int(np.float64(x).view(np.int64))


class TestServedRollup:
    def test_every_aggregate_arrives_exactly_once(self, served):
        rows = emitted(served["messages"])
        keys = [r[:3] for r in rows]
        assert len(keys) == len(set(keys)), "an aggregate arrived twice"
        assert set(keys) == set(served["want"])
        # sum/min/max for counters (LAST is not valid for a counter,
        # as upstream), sum/min/max/last for gauges, three windows
        assert len(rows) == (SERIES // 2) * (3 + 4) * MINUTES

    def test_counters_equal_by_bits(self, served):
        want, kinds = served["want"], served["types"]
        n = 0
        for sid, ts, t, v in emitted(served["messages"]):
            if kinds[sid] == int(MetricType.COUNTER):
                assert bits(v) == bits(want[(sid, ts, t)]), (sid, ts, t)
                n += 1
        assert n == (SERIES // 2) * 3 * MINUTES

    def test_gauge_min_max_last_equal_by_bits(self, served):
        want, kinds = served["want"], served["types"]
        n = 0
        for sid, ts, t, v in emitted(served["messages"]):
            if (kinds[sid] == int(MetricType.GAUGE)
                    and t != naive_rollup.SUM):
                assert bits(v) == bits(want[(sid, ts, t)]), (sid, ts, t)
                n += 1
        assert n == (SERIES // 2) * 3 * MINUTES

    def test_gauge_sums_within_1e10(self, served):
        want, kinds = served["want"], served["types"]
        worst = 0.0
        for sid, ts, t, v in emitted(served["messages"]):
            if kinds[sid] == int(MetricType.GAUGE) and t == naive_rollup.SUM:
                w = want[(sid, ts, t)]
                worst = max(worst, abs(v - w) / max(abs(w), 1e-300))
        assert worst <= 1e-10

    def test_an_acked_frame_is_in_its_window(self, served):
        # every frame was acked by both owners, and the leader counted
        # every acked sample; the equality with the reference above is
        # over exactly these samples
        assert served["acked"] == 2 * SERIES * MINUTES * 6
        snap = served["asm"].registry.snapshot()
        assert snap["m3tpu.ingest_tcp.samples"] == SERIES * MINUTES * 6
        assert snap.get("m3tpu.ingest_tcp.shed_frames", 0) == 0
        assert served["svc"].aggregator.counters()["drops"] == 0

    def test_follower_shadow_consumes_and_emits_nothing(self, served):
        assert served["roles"] == [("leader", "follower")] * MINUTES
        f, lead = served["follower"], served["svc"]
        assert f.handler.values == 0 and f.bus.published == 0
        assert served["follower_polled"] == []
        # it drained its replica to the leader's persisted flush times
        end = T0 + MINUTES * MINUTE
        for svc in (f, lead):
            (ml,) = svc.aggregator.shards[0].lists.values()
            assert ml.consumed_until == end
        assert lead.handler.values == len(served["want"])

    def test_the_consumer_acked_every_message(self, served):
        deadline = time.monotonic() + 10
        while served["svc"].unacked() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert served["svc"].unacked() == 0
        assert served["svc"].bus.acked == len(served["messages"])

    def test_metrics_endpoint_has_the_counters(self, served):
        port = served["asm"].port
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "m3tpu_ingest_tcp_samples" in text
        assert f"m3tpu_aggregator_flush_values {len(served['want'])}" in text

    def test_frame_and_flush_spans(self, served):
        spans = served["asm"].tracer.finished()
        by_id = {s.span_id: s for s in spans}
        frames = [s for s in spans if s.name == Tracepoint.INGEST_FRAME]
        assert len(frames) == MINUTES * 6
        assert all(s.parent_id is None and s.tags["n"] == SERIES
                   for s in frames)
        under = {}
        for s in spans:
            p = by_id.get(s.parent_id)
            if p is not None:
                under.setdefault(p.name, set()).add(s.name)
        assert under[Tracepoint.INGEST_FRAME] >= {
            Tracepoint.INGEST_FRAME_DECODE, Tracepoint.INGEST_QUEUE_WAIT,
            Tracepoint.AGG_LOCK_WAIT, Tracepoint.AGG_RESOLVE,
            Tracepoint.AGG_ADD}
        assert "device.arena.ingest" in under[Tracepoint.AGG_ADD]
        assert under[Tracepoint.AGG_FLUSH] >= {
            Tracepoint.AGG_CONSUME, Tracepoint.AGG_FLUSH_PERSIST}
        # one drain span per arena under the consume; the device call,
        # the wait for it, the copy to the host and the emission below it
        drains = {f"{Tracepoint.AGG_DRAIN}.{kind}"
                  for kind in ("counter", "gauge", "timer")}
        assert under[Tracepoint.AGG_CONSUME] >= drains
        for d in drains:
            assert under[d] >= {"device.arena.consume", d + ".wait",
                                d + ".to_host"}
        assert Tracepoint.AGG_FLUSH_EMIT in under[
            Tracepoint.AGG_DRAIN + ".gauge"]
        # the wait in the queue begins where decode ended
        f = frames[0]
        kids = [s for s in spans if s.parent_id == f.span_id]
        assert all(f.start_ns <= s.start_ns and s.end_ns <= f.end_ns
                   for s in kids)


class TestColumnarDecode:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_equals_scalar_decode(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 700))
        ids = [bytes(rng.integers(0, 256, int(l), dtype=np.uint8))
               for l in rng.integers(0, 300, n)]   # empty and odd lengths
        batch = wire.MetricBatch(
            rng.choice([1, 2, 3], n).astype(np.uint8), ids,
            rng.standard_normal(n) * 10.0 ** rng.integers(-300, 300, n),
            rng.integers(-2**62, 2**62, n), agg_id=int(rng.integers(0, 99)))
        raw = wire.encode_metric_batch(batch)
        a, b = wire.decode_metric_batch(raw), wire.decode_metric_columns(raw)
        assert list(b.ids) == a.ids == ids
        assert [b.ids[i] for i in range(n)] == ids
        assert a.agg_id == b.agg_id
        assert np.array_equal(a.metric_types, b.metric_types)
        assert np.array_equal(a.times, b.times)
        assert np.array_equal(a.values.view(np.int64),
                              b.values.view(np.int64))
        sel = np.flatnonzero(b.metric_types == 3)
        assert list(b.ids.take(sel)) == [ids[i] for i in sel]

    def test_empty_batch_and_bad_frames(self):
        raw = wire.encode_metric_batch(wire.MetricBatch(
            np.zeros(0, np.uint8), [], np.zeros(0), np.zeros(0, np.int64)))
        assert len(wire.decode_metric_columns(raw).ids) == 0
        raw = wire.encode_metric_batch(wire.MetricBatch(
            np.asarray([1, 3], np.uint8), [b"a", b"bcd"],
            np.asarray([1.0, 2.0]), np.asarray([5, 6])))
        with pytest.raises(wire.ProtocolError, match="trailing"):
            wire.decode_metric_columns(raw + b"\x00")
        with pytest.raises(wire.ProtocolError):
            wire.decode_metric_columns(raw[:-3])

    def test_native_resolve_reads_ids_in_place(self):
        from m3_tpu.core.idbytes import PackedIds
        from m3_tpu.native.idmap import NativeIdMap

        ids = [b"a", b"", b"bcd" * 40, b"a"]
        m = NativeIdMap(8)
        s1, new1 = m.resolve(ids, 5)
        s2, new2 = m.resolve(PackedIds.from_ids(ids), 5)
        assert s1.tolist() == s2.tolist() == [0, 1, 2, 0]
        assert new1.tolist() == [0, 1, 2] and new2.tolist() == []


class TestConfigAndEntryPoint:
    def test_bad_section_names_every_field(self):
        with pytest.raises(ConfigError) as e:
            load_config("coordinator: null\naggregator:\n  capacity: 0\n"
                        "  lease: soon\n  storage_policies: [x]\n"
                        "  default_aggregations: {gauge: [NOPE]}\n")
        for what in ("capacity", "lease", "storage_policies", "NOPE"):
            assert what in str(e.value)

    def test_run_aggregator_needs_the_section(self):
        with pytest.raises(ConfigError, match="aggregator"):
            run_aggregator("coordinator: null\n")

    def test_node_main_serves_an_aggregator_from_yaml(self, tmp_path):
        cfg = tmp_path / "agg.yaml"
        cfg.write_text(NODE_YAML.format(root=tmp_path / "data",
                                        instance="agg-0"))
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(sys.path)}
        p = subprocess.Popen(
            [sys.executable, "-m", "m3_tpu.server.node_main", str(cfg)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        try:
            status = tmp_path / "data" / "node.json"
            deadline = time.monotonic() + 300
            while not status.exists() and time.monotonic() < deadline:
                assert p.poll() is None, p.stdout.read().decode()
                time.sleep(0.2)
            st = json.loads(status.read_text())
            assert st["ingest_port"] and st["msg_port"] and st["port"]
            s = wire.connect(("127.0.0.1", st["ingest_port"]))
            wire.send_frame(s, wire.INGEST_HELLO, wire.encode_ingest_hello())
            s.close()
            assert urllib.request.urlopen(
                f"http://127.0.0.1:{st['port']}/health", timeout=10
            ).status == 200
            p.send_signal(signal.SIGTERM)
            assert p.wait(timeout=300) == 0     # six loaded workers
            assert not status.exists()
        finally:
            if p.poll() is None:
                p.kill()


class TestTopicConsumer:
    def test_poll_finishes_a_delivery_that_outlasts_its_timeout(self):
        """A message larger than the socket buffers, polled with a
        timeout far shorter than it takes to arrive: the poll reads it
        whole (a drained window's message is hundreds of KB; giving up
        mid-frame lost the consumer on the chip, PR 27)."""
        import threading

        from m3_tpu.msg.bus import (
            ConsumerService, ConsumptionType, MessageBus, Topic,
        )
        from m3_tpu.msg.transport import serve_bus_background

        bus = MessageBus(Topic("t", 1, (ConsumerService(
            "coordinator", ConsumptionType.SHARED),)))
        srv = serve_bus_background(bus)
        cons = RemoteBusConsumer(("127.0.0.1", srv.port), "coordinator", "c1")
        try:
            payload = bytes(range(256)) * (64 << 10)      # 16 MiB
            gil = threading.Event()

            def hog():  # keeps the GIL busy, as the node's threads do
                while not gil.is_set():
                    sum(range(2000))

            t = threading.Thread(target=hog, daemon=True)
            t.start()
            with srv.lock:
                bus.publish(0, payload)
                bus.publish(0, b"small")
            got = []
            deadline = time.monotonic() + 60
            while len(got) < 2 and time.monotonic() < deadline:
                for mid, _shard, body in cons.poll(timeout_s=0.001):
                    got.append(body)
                    cons.ack(mid)
            gil.set()
            t.join()
            assert got == [payload, b"small"]
        finally:
            cons.close()
            srv.shutdown()
            srv.server_close()


# the cell's seventeen per-layer entries
_AGG = [n + ".agg" for n in (
    "frame_decode_ms_per_ksample", "resolve_ms_per_ksample",
    "add_ms_per_ksample", "lock_wait_ms_per_ksample",
    "flush_emit_ms_per_pass", "consume_ms_per_pass",
    "arena_calls_per_ksample", "arena_device_ms_per_ksample",
    "dispatch_ms_per_ksample", "frame_unnamed_pct", "device_idle_pct",
    "idle_unnamed_pct", "gc_pause_pct", "window_compiles",
    "counter_ingest_roofline", "gauge_ingest_roofline",
    "arena_consume_roofline")]


class TestBenchmarkEntries:
    def test_agg_per_layer_entries_are_well_formed(self):
        """BENCHMARK.json's `.agg` entries: the cell's seventeen, each
        with its reader's file, a reducer that exists, the cell's rate
        as what it moves."""
        import importlib
        from pathlib import Path

        repo = Path(__file__).resolve().parent.parent
        sys.path.insert(0, str(repo))
        bench = json.loads((repo / "BENCHMARK.json").read_text())
        (cell,) = [w for w in bench["workloads"]
                   if w["name"] == "m3agg.untimed_rollup"]
        assert cell["chips"] == 1
        (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
        conf = json.loads((repo / cfg["file"]).read_text())
        assert conf["source"] == cfg["source"]
        assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
        assert conf["dataset"]["scale"] * 16 == conf["reduced"]["series"]["here"]
        # by name (the two `gil_` entries are held by
        # tests/test_node_spans.py); a
        # copy of a `.load` guard may stand folded into that entry
        assert len(_AGG) == len(set(_AGG)) == 17
        layers = {m["layer"] for m in bench["per_layer"]}
        (rate,) = [m for m in bench["end_to_end"]
                   if cell["name"] in m.get("workloads", ())]
        for name in _AGG:
            m = check_workloads(bench, name, [cell["name"]])
            assert m["layer"] in layers
            assert m["moves"] == rate["name"] == "load_samples_per_s"
            assert m["better"] == ("higher" if "roofline" in name
                                   else "lower")
            spec = json.loads((repo / "benchmark" / "metrics"
                               / (m["name"] + ".json")).read_text())
            importlib.import_module("benchmark.reducers." + spec["reducer"])
