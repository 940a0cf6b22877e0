"""Traffic kind ``agg_tcp``: closed-loop aggregator clients over the
rawtcp front door, the leader's flushes consumed from the m3msg topic.

This generator boots its own service, not ``harness.boot_node``:
``m3_tpu.server.assembly.run_aggregator`` from the configuration's node
file (imported at the top, so a program without it fails at once), and
hands the harness ``cell.asm`` — an ``Assembly`` with ``.tracer`` and
``.close()``, which is all the harness and the reducers ask of it.

Each of `senders` connections (a coordinator's aggregator client each)
owns the series ``i % senders == s`` and sends, interval after
interval, its share in frames of at most `max_samples_per_send`
samples, one in flight, acks on (``INGEST_HELLO``).  A frame holds the
same number of counter and of gauge samples every time (fixed shapes),
interleaved in an order drawn from the seed, all at the interval's
time.  A barrier closes every interval; the data clock then advances
one interval, back to back.  At every data minute the driver thread
ticks the leader's flush manager inline on the data clock, as
``write_loop`` runs the mediator; a consumer thread polls the topic
over ``RemoteBusConsumer`` and acks every message.

Frames are patched into templates: ids and types are fixed per frame,
only the time and value fields of its records change per interval.
"""

from __future__ import annotations

import importlib
import struct
import threading
import time

import numpy as np

from m3_tpu.core.config import load_config
from m3_tpu.msg import protocol as wire
from m3_tpu.msg.transport import RemoteBusConsumer
from m3_tpu.server.assembly import run_aggregator

from benchmark import harness
from benchmark.references import aggregator_rollup as reference

MINUTE = harness.MINUTE
_TV = np.arange(16)


class Template:
    """One frame's payload with its records' (time, value) fields
    patched per interval."""

    def __init__(self, ids, types, idx: np.ndarray):
        self.idx = idx
        self.n_counters = int((types[idx] == reference.COUNTER).sum())
        parts, pos, tv = [struct.pack("<IQ", len(idx), 0)], 12, []
        for i in idx.tolist():
            sid = ids[i]
            parts.append(struct.pack("<BH", int(types[i]), len(sid)))
            parts.append(sid)
            parts.append(bytes(16))
            tv.append(pos + 3 + len(sid))
            pos += 19 + len(sid)
        self.buf = bytearray(b"".join(parts))
        self.view = np.frombuffer(self.buf, np.uint8)
        self.tv = np.asarray(tv)[:, None] + _TV

    def payload(self, t_nanos: int, vals: np.ndarray) -> bytes:
        rec = np.empty((len(vals), 2), "<i8")
        rec[:, 0] = t_nanos
        rec[:, 1] = vals.view(np.int64)
        self.view[self.tv] = rec.view(np.uint8)
        return bytes(self.buf)


class Run:
    def __init__(self, cell):
        self.cell = cell
        self.window_end = None
        self.k = 0                  # next interval to send
        self.flushed_upto = 0       # data time of the last flush tick

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        cell, tr = self.cell, self.cell.traffic
        spec = cell.cfg["dataset"]
        kind = importlib.import_module("benchmark.datasets." + spec["kind"])
        self.points = tr["warmup_intervals"] + tr["max_scrapes"]
        self.start = harness.data_start(time.time_ns())
        data = self.data = cell.data = kind.Dataset(
            spec, cell.seed, self.start, self.points)
        per_type = int((data.types == reference.COUNTER).sum())
        assert per_type * 2 == data.n_series
        harness.say("sizes", series=data.n_series,
                    points_prepared=self.points,
                    data_start_unix=self.start // harness.SEC, seed=cell.seed)
        self._boot(per_type)
        # each connection's frames: fixed series, counters and gauges in
        # equal and fixed numbers, interleaved in an order from the seed
        rng = np.random.default_rng(cell.seed + 2)
        self.frames = []
        for own in data.owners(tr["senders"]):
            c = own[data.types[own] == reference.COUNTER]
            g = own[data.types[own] == reference.GAUGE]
            n_frames = -(-len(own) // tr["max_samples_per_send"])
            chunks = []
            for cc, gg in zip(np.array_split(rng.permutation(c), n_frames),
                              np.array_split(rng.permutation(g), n_frames)):
                idx = np.concatenate([cc, gg])
                chunks.append(Template(data.ids, data.types,
                                       idx[rng.permutation(len(idx))]))
            self.frames.append(chunks)
        harness.say("frames", per_interval=sum(len(f) for f in self.frames),
                    sizes=sorted({len(t.idx) for f in self.frames for t in f}))
        cell.facts.update(samples_acked=0, counter_samples=0, gauge_samples=0,
                          intervals=0, flushes=0, arena_capacity=self.capacity)
        self.present = np.ones((self.points, data.n_series), bool)
        self.sent = []              # (interval, sender, frame) acked, in order
        self._start_consumer()
        self._start_senders()
        took = []
        for _ in range(tr["warmup_intervals"]):
            t0 = time.monotonic()
            self._interval()
            took.append(round(time.monotonic() - t0, 2))
        harness.say("warmup_intervals", host_seconds=took,
                    flush_s=[round(b - a, 2) for a, b in
                             cell.spans.get("flush_pass", [])])

    def _boot(self, per_type: int) -> None:
        """run_aggregator from the configuration's node file; only the
        data root and the arenas' capacity (which follows the sizes)
        are filled in here."""
        cell = self.cell
        node = load_config(str(harness.HERE / "configs" / cell.cfg["node"]))
        node.db.root = cell.root
        self.capacity = node.aggregator.capacity = 1 << int(
            np.ceil(np.log2(per_type)))
        t0 = time.monotonic()
        cell.asm = run_aggregator(node)
        self.svc = cell.asm.aggregator
        harness.say("boot", host_seconds=round(time.monotonic() - t0, 1),
                    entry="m3_tpu.server.assembly.run_aggregator",
                    ingest_port=self.svc.port, msg_port=self.svc.msg_port,
                    shards=node.aggregator.num_shards,
                    capacity=self.capacity,
                    num_windows=node.aggregator.num_windows)

    def _start_consumer(self) -> None:
        self.messages: dict = {}     # message id -> payload, first delivery
        self._consumer = RemoteBusConsumer(
            ("127.0.0.1", self.svc.msg_port),
            self.svc.cfg.consumer_service, "bench-consumer")
        self._consumer_stop = False

        def consume():
            while not self._consumer_stop:
                for mid, _shard, payload in self._consumer.poll(timeout_s=0.2):
                    self.messages.setdefault(mid, payload)
                    self._consumer.ack(mid)

        self._consumer_thread = threading.Thread(
            target=consume, daemon=True, name="topic-consumer")
        self._consumer_thread.start()

    def _start_senders(self) -> None:
        n = len(self.frames)
        self._go = threading.Barrier(n + 1)
        self._done = threading.Barrier(n + 1)
        self._stop = False
        self._errors: list = []
        self._threads = [threading.Thread(target=self._sender, args=(s,),
                                          daemon=True, name=f"sender-{s}")
                         for s in range(n)]
        for t in self._threads:
            t.start()

    def _sender(self, s: int) -> None:
        cell, data = self.cell, self.data
        sock = wire.connect(("127.0.0.1", self.svc.port), timeout=600.0)
        wire.send_frame(sock, wire.INGEST_HELLO, wire.encode_ingest_hello())
        while True:
            self._go.wait()
            if self._stop:
                sock.close()
                return
            k = self.k
            t_nanos = int(data.ts[k])
            try:
                for f, tpl in enumerate(self.frames[s]):
                    c0 = time.monotonic()
                    payload = tpl.payload(t_nanos, data.vals[k, tpl.idx])
                    sent = time.monotonic()
                    cell.add_busy(sent - c0)
                    with cell.annotate("frame_in_flight"):
                        wire.send_frame(sock, wire.METRIC_BATCH, payload)
                        reply = wire.recv_frame(sock)
                    ok = (reply is not None and reply[0] == wire.INGEST_ACK
                          and wire.decode_ingest_ack(reply[1]) == len(tpl.idx))
                    cell.log.add("write", sent, time.monotonic(), ok,
                                 len(tpl.idx), k)
                    if ok:
                        self._acked[s].append((k, s, f))
                    else:
                        self.present[k, tpl.idx] = False
            except Exception as e:  # noqa: BLE001 — reported by the driver
                self._errors.append(e)
            self._done.wait()

    def _interval(self) -> None:
        """One interval from every connection, then a flush tick if a
        data minute has ended."""
        cell = self.cell
        self._acked = [[] for _ in self.frames]
        self._go.wait()
        self._done.wait()
        if self._errors:
            raise self._errors[0]
        for s, acked in enumerate(self._acked):
            self.sent.extend(acked)
            n = sum(len(self.frames[s][f].idx) for _, _, f in acked)
            c = sum(self.frames[s][f].n_counters for _, _, f in acked)
            cell.facts["samples_acked"] += n
            cell.facts["counter_samples"] += c
            cell.facts["gauge_samples"] += n - c
        cell.facts["intervals"] += 1
        nxt = int(self.data.ts[self.k]) + self.data.interval
        self.k += 1
        if nxt % MINUTE == 0:
            with cell.span("flush_pass"):
                role = self.svc.tick(nxt)
            if role != "leader":
                raise RuntimeError(f"the one instance ticked as {role}")
            self.flushed_upto = nxt
            cell.facts["flushes"] += 1

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        cell = self.cell
        self.k_window = self.k
        t0 = cell.window[0]
        # the window also ends where the prepared intervals do (a size
        # far below the cell's, as selftest.py's: the rate stays all the
        # work over the window as it was, and the line below says so)
        while time.monotonic() - t0 < seconds and self.k < self.points:
            if cell.slice_wanted():
                cell.slice_open()
            elif cell.slice_full():
                cell.slice_close()
            self._interval()
        self.window_end = time.monotonic()
        self._stop = True
        self._go.wait()
        for t in self._threads:
            t.join()
        used = self.k - self.k_window
        harness.say("intervals", warmup=self.k_window, window=used,
                    prepared=cell.traffic["max_scrapes"],
                    ended_early=self.k >= self.points,
                    drains_in_window=sum(
                        1 for a, b in cell.spans.get("flush_pass", [])
                        if a >= t0 and b <= self.window_end),
                    flush_s=[round(b - a, 2) for a, b in
                             cell.spans.get("flush_pass", []) if a >= t0])

    # -- what decides `correct` --------------------------------------------

    def verify(self, control: str = "") -> dict:
        """Every (series, window, aggregation type) of every window the
        leader drained, warm-up and window, against the reference over
        the arrays the generator made from the seed: each arrives
        exactly once; min/max/last and counters' sums by bits, gauges'
        sums by relative error; every message acked.  With a control,
        the reference under that control stands in the program's place
        in the same comparison (the program's own readings go to an
        earlier line): the run then has to read correct: false."""
        cell, data, lim = self.cell, self.data, self.cell.traffic["limits"]
        deadline = time.monotonic() + 120
        while self.svc.unacked() and time.monotonic() < deadline:
            time.sleep(0.1)
        unacked = self.svc.unacked()
        self._consumer_stop = True
        self._consumer_thread.join()
        self._consumer.close()
        # the intervals whose window was drained
        k_end = int(np.searchsorted(data.ts, self.flushed_upto))
        ts, vals = data.ts[:k_end], data.vals[:k_end]
        ends, want, _ = reference.rollup(ts, vals, data.types, MINUTE,
                                         self.present[:k_end])
        got, got_n, extra = self._decode(ends)
        harness.say("topic", messages=len(self.messages),
                    rows=int(got_n.sum()) + extra, windows=len(ends),
                    published=self.svc.bus.published,
                    acked=self.svc.bus.acked, unacked=unacked)
        if control:
            m, w, e = reference.compare(got, got_n, want, data.types)
            harness.say("program", agg_missing_or_extra=m + extra,
                        agg_selected_wrong=w, agg_sum_rel_err=e)
            got, got_n, extra = self._control(control, ts, vals, k_end), \
                np.stack([~np.isnan(want[lane]) for lane in reference.LANES]
                         ).astype(np.int64), 0
        missing_or_extra, wrong, err = reference.compare(
            got, got_n, want, data.types)
        return {
            "agg_missing_or_extra": (missing_or_extra + extra,
                                     lim["agg_missing_or_extra"]),
            "agg_selected_wrong": (wrong, lim["agg_selected_wrong"]),
            "agg_sum_rel_err": (err, lim["agg_sum_rel_err"]),
            "unacked_messages": (unacked, lim["unacked_messages"]),
        }

    def _decode(self, ends: np.ndarray):
        """The consumed topic as {lane: (windows, series)} values, how
        often each (lane, window, series) arrived, and the rows that
        belong to no expected place (unknown id, window or type)."""
        data = self.data
        n, lanes = data.n_series, reference.LANES
        lane_of = np.full(256, -1)
        for li, lane in enumerate(lanes):
            lane_of[reference.AGG_TYPE[lane]] = li
        series_of = {sid: i for i, sid in enumerate(data.ids)}
        win_of = {int(e): wi for wi, e in enumerate(ends)}
        got = {lane: np.full((len(ends), n), np.nan) for lane in lanes}
        got_n = np.zeros((len(lanes), len(ends), n), np.int64)
        extra = 0
        for payload in self.messages.values():
            mt, _policy, ts, ids, row_ids, row_types, values = \
                wire.decode_aggregated_batch(payload)
            wi = win_of.get(ts)
            ser = np.fromiter((series_of.get(s, -1) for s in ids), np.int64,
                              len(ids))[row_ids]
            li = lane_of[row_types]
            ok = (ser >= 0) & (li >= 0)
            if wi is None:
                extra += len(values)
                continue
            ok &= data.types[np.maximum(ser, 0)] == mt
            extra += int((~ok).sum())
            np.add.at(got_n, (li[ok], wi, ser[ok]), 1)
            for k, lane in enumerate(lanes):
                sel = ok & (li == k)
                got[lane][wi, ser[sel]] = values[sel]
        return got, got_n, extra

    def _control(self, control: str, ts, vals, k_end: int) -> dict:
        data = self.data
        if control == "f32":
            # the gauges' lanes one precision down
            return reference.rollup(ts, vals, data.types, MINUTE,
                                    self.present[:k_end], np.float32)[1]
        if control == "lost_frame":
            # one acked frame never reached its window
            k, s, f = next(x for x in self.sent if x[0] < k_end)
            lost = self.present[:k_end].copy()
            lost[k, self.frames[s][f].idx] = False
            return reference.rollup(ts, vals, data.types, MINUTE, lost)[1]
        raise ValueError(f"unknown control {control!r}")
