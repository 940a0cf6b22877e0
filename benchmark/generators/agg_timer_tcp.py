"""Traffic kind ``agg_timer_tcp``: ``agg_tcp``'s closed-loop aggregator
clients sending untimed TIMERS, the leader's quantiles consumed from
the m3msg topic.

The service (``run_aggregator`` from the configuration's node file),
the connections, the barrier per interval, the inline flush tick at
every data minute, the topic's consumer and the window loop are
``agg_tcp.Run``'s.  What differs is the stream: an interval's samples
are one of ``dataset.patterns`` fixed lists (``datasets/agg_timer.py``:
hot ids several times, the cold ids present in that interval once),
in seeded order, cut evenly over the connections and into frames of at
most `max_samples_per_send` samples: an id may repeat inside a frame,
and every frame has the same number of samples (fixed shapes: nothing
compiles in a window).  Interval k sends pattern ``k % patterns``, so
``self.frames`` is the pattern's frames of the interval being sent.

The generator fills in the node's `capacity` (slots: the next power of
two at or above the ids) and `timer_sample_capacity` (the next power of
two at or above `samples_per_id_per_window` x ids: a deployment
provisions above its mean window, and the buffer must never grow in a
run).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from m3_tpu.core.config import load_config
from m3_tpu.msg import protocol as wire
from m3_tpu.server.assembly import run_aggregator

from benchmark import harness
from benchmark.generators import agg_tcp
from benchmark.references import aggregator_timer as reference

MINUTE = harness.MINUTE


def _pow2_at_least(n: int) -> int:
    return 1 << int(np.ceil(np.log2(n)))


class Run(agg_tcp.Run):
    @property
    def frames(self):
        """Per connection, the frames of the interval being sent."""
        return self._frames[self.k % self.data.patterns]

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        cell, tr = self.cell, self.cell.traffic
        spec = cell.cfg["dataset"]
        kind = importlib.import_module("benchmark.datasets." + spec["kind"])
        self.points = tr["warmup_intervals"] + tr["max_scrapes"]
        self.start = harness.data_start(time.time_ns())
        data = self.data = cell.data = kind.Dataset(
            spec, cell.seed, self.start, self.points)
        n = data.n_series
        harness.say("sizes", series=n, hot=len(data.hot),
                    samples_per_interval=n, points_prepared=self.points,
                    data_start_unix=self.start // harness.SEC, seed=cell.seed)
        self._boot()
        # a pattern's samples, in the dataset's seeded order, cut evenly
        # over the connections and then into equal frames
        senders = tr["senders"]
        per_conn = n // senders
        n_frames = -(-per_conn // tr["max_samples_per_send"])
        if n % senders or per_conn % n_frames:
            raise ValueError(f"{n} samples an interval do not cut into "
                             f"{senders} x {n_frames} equal frames")
        self._frames = []
        for p in range(data.patterns):
            ids = [data.ids[i] for i in data.series_at[p].tolist()]
            self._frames.append([
                [agg_tcp.Template(ids, data.types[data.series_at[p]], pos)
                 for pos in np.split(np.arange(s * per_conn,
                                               (s + 1) * per_conn), n_frames)]
                for s in range(senders)])
        harness.say("frames", per_interval=senders * n_frames,
                    sizes=[per_conn // n_frames], patterns=data.patterns)
        # (agg_tcp's _interval also keeps per-type counts: every sample
        # here is a timer's, so `samples_acked` is the one that is read)
        cell.facts.update(samples_acked=0, counter_samples=0, gauge_samples=0,
                          intervals=0, flushes=0,
                          arena_capacity=self.capacity,
                          timer_sample_capacity=self.sample_capacity)
        self.present = np.ones((self.points, n), bool)
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

    def _boot(self) -> None:
        """run_aggregator from the configuration's node file; only the
        data root and the two capacities that follow the sizes are
        filled in here."""
        cell, n = self.cell, self.data.n_series
        node = load_config(str(harness.HERE / "configs" / cell.cfg["node"]))
        node.db.root = cell.root
        self.capacity = node.aggregator.capacity = _pow2_at_least(n)
        self.sample_capacity = node.aggregator.timer_sample_capacity = \
            _pow2_at_least(cell.cfg["samples_per_id_per_window"] * n)
        t0 = time.monotonic()
        cell.asm = run_aggregator(node)
        self.svc = cell.asm.aggregator
        if "timer_buffer_grows" not in self.svc.aggregator.counters():
            # the program before PR 31: no counter to hold the cell's
            # `timer_buffer_grows` 0 to, and a timer_consume whose
            # compile for the TPU at this buffer takes over ten minutes
            raise SystemExit(
                "m3agg.timer_quantile: this program's aggregator has no "
                "counter timer_buffer_grows; the cell cannot run on it")
        harness.say("boot", host_seconds=round(time.monotonic() - t0, 1),
                    entry="m3_tpu.server.assembly.run_aggregator",
                    ingest_port=self.svc.port, msg_port=self.svc.msg_port,
                    shards=node.aggregator.num_shards,
                    capacity=self.capacity,
                    timer_sample_capacity=self.sample_capacity,
                    num_windows=node.aggregator.num_windows)

    # -- what decides `correct` --------------------------------------------

    def verify(self, control: str = "") -> dict:
        """Every (id, window, quantile) of every window the leader
        drained, warm-up and window, against the reference over the
        arrays the generator made from the seed: each arrives exactly
        once; every value equal by bits to the selection over
        float32(values) and within the limit of the selection over the
        f64 values; every message acked; the timer buffer never grew.
        With a control, the reference under that control stands in the
        program's place in the same comparison (the program's own
        readings go to an earlier line): the run then has to read
        correct: false."""
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
        args = (data.ts[:k_end], data.vals[:k_end], data.series_of,
                data.n_series, MINUTE)
        ends, want32, count = reference.quantiles(
            *args, self.present[:k_end], reference.to_float32)
        _, want64, _ = reference.quantiles(*args, self.present[:k_end])
        got, got_n, extra = self._decode(ends)
        # how often a timer buffer was padded past the configured
        # `timer_sample_capacity` (each a new shape on the ingest path)
        grows = self.svc.aggregator.counters()["timer_buffer_grows"]
        harness.say("topic", messages=len(self.messages),
                    rows=int(got_n.sum()) + extra, windows=len(ends),
                    samples_per_window=count.sum(axis=1).tolist(),
                    published=self.svc.bus.published,
                    acked=self.svc.bus.acked, unacked=unacked,
                    timer_buffer_grows=grows)
        if control:
            m, w, e = reference.compare(got, got_n, want32, want64)
            harness.say("program", agg_missing_or_extra=m + extra,
                        agg_selected_wrong=w, quantile_rel_err=e)
            got = self._control(control, args, k_end)
            got_n = np.stack([~np.isnan(got[q]) for q in reference.QUANTILES]
                             ).astype(np.int64)
            extra = 0
        missing_or_extra, wrong, err = reference.compare(
            got, got_n, want32, want64)
        return {
            "agg_missing_or_extra": (missing_or_extra + extra,
                                     lim["agg_missing_or_extra"]),
            "agg_selected_wrong": (wrong, lim["agg_selected_wrong"]),
            "quantile_rel_err": (err, lim["quantile_rel_err"]),
            "unacked_messages": (unacked, lim["unacked_messages"]),
            "timer_buffer_grows": (grows, lim["timer_buffer_grows"]),
        }

    def _decode(self, ends: np.ndarray):
        """The consumed topic as {q: (windows, ids)} values, how often
        each (q, window, id) arrived, and the rows that belong to no
        expected place (unknown id, window, metric or aggregation
        type)."""
        data, qs = self.data, reference.QUANTILES
        n = data.n_series
        lane_of = np.full(256, -1)
        for qi, q in enumerate(qs):
            lane_of[reference.AGG_TYPE[q]] = qi
        series_of = {sid: i for i, sid in enumerate(data.ids)}
        win_of = {int(e): wi for wi, e in enumerate(ends)}
        got = {q: np.full((len(ends), n), np.nan) for q in qs}
        got_n = np.zeros((len(qs), len(ends), n), np.int64)
        extra = 0
        for payload in self.messages.values():
            mt, _policy, ts, ids, row_ids, row_types, values = \
                wire.decode_aggregated_batch(payload)
            wi = win_of.get(ts)
            if wi is None or mt != data.types[0]:
                extra += len(values)
                continue
            ser = np.fromiter((series_of.get(s, -1) for s in ids), np.int64,
                              len(ids))[row_ids]
            li = lane_of[row_types]
            ok = (ser >= 0) & (li >= 0)
            extra += int((~ok).sum())
            np.add.at(got_n, (li[ok], wi, ser[ok]), 1)
            for qi, q in enumerate(qs):
                sel = ok & (li == qi)
                got[q][wi, ser[sel]] = values[sel]
        return got, got_n, extra

    def _control(self, control: str, args, k_end: int) -> dict:
        present = self.present[:k_end]
        if control == "bf16":
            # the samples carried one precision down
            return reference.quantiles(*args, present,
                                       reference.to_bfloat16)[1]
        if control == "lost_frame":
            # one acked frame never reached its window
            k, s, f = next(x for x in self.sent if x[0] < k_end)
            lost = present.copy()
            lost[k, self._frames[k % self.data.patterns][s][f].idx] = False
            return reference.quantiles(*args, lost,
                                       reference.to_float32)[1]
        if control == "rank_plus_one":
            # every quantile read one rank high
            return reference.quantiles(*args, present, reference.to_float32,
                                       rank_shift=1)[1]
        raise ValueError(f"unknown control {control!r}")
