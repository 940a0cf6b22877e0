"""The aggregator as a served process: front door, arenas, flush
manager and m3msg output composed (``server/assembly.run_aggregator``
builds one from the ``aggregator:`` section of a node file).

Equivalent of the reference's ``src/aggregator/server`` wiring
(``cmd/services/m3aggregator/serve``): the rawtcp server feeds
``Aggregator.AddUntimed``; the flush manager, under an election lease
on the cluster KV, drains closed windows as leader (or shadow-consumes
as follower) and persists flush times after emission; the flush handler
writes to an m3msg topic whose consumers ack every message.

One lock orders ingest against flushing: the front door's sink and a
flush tick both take it, so a window is drained between frames, never
under one.
"""

from __future__ import annotations

import threading
import time

from m3_tpu.aggregator.engine import Aggregator, AggregatorOptions
from m3_tpu.aggregator.flush_mgr import FlushManager
from m3_tpu.aggregator.handler import M3MsgFlushHandler
from m3_tpu.core.config import AggregatorConfig, parse_duration
from m3_tpu.metrics.aggregation import AggregationID, AggregationType
from m3_tpu.metrics.policy import StoragePolicy
from m3_tpu.metrics.types import MetricType
from m3_tpu.msg.bus import (
    ConsumerService, ConsumptionType, MessageBus, Topic, TopicService,
)
from m3_tpu.msg.transport import serve_bus_background
from m3_tpu.server.ingest_tcp import aggregator_sink, serve_ingest_background


def aggregator_options(cfg: AggregatorConfig) -> AggregatorOptions:
    """The engine's options from the node file's section (also how
    tools build an aggregator of a deployment's shape)."""
    return AggregatorOptions(
        capacity=cfg.capacity,
        num_windows=cfg.num_windows,
        timer_sample_capacity=cfg.timer_sample_capacity,
        storage_policies=tuple(
            StoragePolicy.parse(sp) for sp in cfg.storage_policies),
        default_aggregations=tuple(
            (MetricType[mt.upper()], AggregationID.compress(
                AggregationType[n] for n in names))
            for mt, names in cfg.default_aggregations.items()),
    )


class AggregatorService:
    def __init__(self, cfg: AggregatorConfig, kv, scope=None, tracer=None,
                 clock=time.time_ns):
        self.cfg, self.kv, self.clock = cfg, kv, clock
        self.lock = threading.Lock()
        self.aggregator = Aggregator(cfg.num_shards, aggregator_options(cfg))
        # the topic lives in KV, as upstream's (msg/topic)
        topic = Topic(cfg.topic, cfg.num_shards, (ConsumerService(
            cfg.consumer_service, ConsumptionType.SHARED),))
        TopicService(kv).set(topic)
        self.bus = MessageBus(
            topic, retry_after_s=parse_duration(cfg.msg_retry_after) / 1e9)
        self.ingest = self.bus_server = self._loop = None
        self._stop = threading.Event()
        try:
            self.bus_server = serve_bus_background(
                self.bus, cfg.listen_host, cfg.msg_listen_port)
            self.handler = M3MsgFlushHandler(
                self.bus, self.bus_server.lock,
                values_counter=(scope.counter("aggregator_flush_values")
                                if scope is not None else None))
            self.flush_manager = FlushManager(
                self.aggregator, kv, cfg.instance_id,
                flush_handler=self.handler,
                lease_nanos=parse_duration(cfg.lease))
            # a restart resumes at the persisted flush times
            self.flush_manager.restore()
            self.ingest = serve_ingest_background(
                aggregator_sink(self.aggregator, self.lock, clock=clock),
                cfg.listen_host, cfg.listen_port, instrument=scope,
                aggregator=self.aggregator, tracer=tracer)
            self._loop = threading.Thread(
                target=self._flush_loop, daemon=True, name="aggregator-flush")
            self._loop.start()
        except BaseException:
            self.close()
            raise

    @property
    def port(self) -> int:
        return self.ingest.port

    @property
    def msg_port(self) -> int:
        return self.bus_server.port

    def tick(self, now_nanos: int | None = None) -> str:
        """One flush round at ``now_nanos`` (default: the service's
        clock), between frames -> the role played."""
        with self.lock:
            return self.flush_manager.tick(
                self.clock() if now_nanos is None else now_nanos)

    def unacked(self) -> int:
        with self.bus_server.lock:
            return self.bus.unacked(self.cfg.consumer_service)

    def _flush_loop(self) -> None:
        interval = parse_duration(self.cfg.flush_interval) / 1e9
        while not self._stop.wait(interval):
            self.tick()

    def close(self) -> None:
        """Front door first (no new frames; the worker acks its
        backlog), then the flush loop, the lease, the topic's server."""
        self._stop.set()
        if self.ingest is not None:
            self.ingest.shutdown()
            self.ingest.server_close()
            self.ingest = None
        if self._loop is not None:
            self._loop.join(timeout=30)
            self._loop = None
            self.flush_manager.resign()
        if self.bus_server is not None:
            self.bus_server.shutdown()
            self.bus_server.server_close()
            self.bus_server = None
