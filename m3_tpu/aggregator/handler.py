"""Flush handler: the aggregator's output over m3msg.

Equivalent of the reference's ``src/aggregator/aggregator/handler``
(the m3msg writer behind ``flushLocalFn``): every window the flush
manager's leader drains becomes messages on the aggregated-metrics
topic, which the consumer service (the coordinator's m3msg ingester
upstream) acks one by one.  A message is one chunk of a drained
``FlushedMetric``: the ids of at most ``max_ids`` series once, and one
row per (series, aggregation type) — ``msg/protocol.
encode_aggregated_batch``.  Rows are regrouped by slot, so a series'
types travel together and its id crosses the wire once per window, not
once per type.
"""

from __future__ import annotations

import time

import numpy as np

from m3_tpu.aggregator.engine import FlushedMetric, MetricList
from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.msg import protocol as wire


class M3MsgFlushHandler:
    """``FlushHandler`` publishing to ``bus`` (a ``msg.bus.MessageBus``;
    ``lock`` is the lock its server's connections take around it)."""

    def __init__(self, bus, lock, max_ids: int = 4096, values_counter=None):
        self.bus, self.lock, self.max_ids = bus, lock, max_ids
        self.values_counter = values_counter
        self.values = 0      # rows published
        self.messages = 0

    def __call__(self, ml: MetricList, fm: FlushedMetric) -> None:
        with tracing.span(Tracepoint.AGG_FLUSH_EMIT) as span:
            n_msgs = self._emit(ml, fm)
            span.set_tag("n", len(fm.values))
            span.set_tag("messages", n_msgs)

    def _emit(self, ml: MetricList, fm: FlushedMetric) -> int:
        id_of = ml.maps[fm.metric_type].id_table()
        order = np.argsort(fm.slots, kind="stable")
        slots = fm.slots[order]
        head = np.ones(len(slots), bool)
        head[1:] = slots[1:] != slots[:-1]
        firsts = np.flatnonzero(head)          # row of each series' first
        row_series = np.cumsum(head) - 1       # series number of each row
        types, values = fm.types[order], fm.values[order]
        policy, shards = str(fm.policy), self.bus.topic.num_shards
        n_msgs = 0
        for a in range(0, len(firsts), self.max_ids):
            b = min(a + self.max_ids, len(firsts))
            r0 = firsts[a]
            r1 = firsts[b] if b < len(firsts) else len(slots)
            chunk = slots[firsts[a:b]].tolist()
            ids = [id_of[s] for s in chunk]
            payload = wire.encode_aggregated_batch(
                int(fm.metric_type), policy, fm.timestamp_nanos, ids,
                row_series[r0:r1] - a, types[r0:r1], values[r0:r1])
            with self.lock:
                self.bus.publish(chunk[0] // self.max_ids % shards, payload,
                                 now_s=time.monotonic())
                self.values += int(r1 - r0)
                self.messages += 1
            n_msgs += 1
        if self.values_counter is not None:
            self.values_counter.inc(len(values))
        return n_msgs
