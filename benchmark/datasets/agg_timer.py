"""Dataset kind ``agg_timer``: what services send an m3aggregator as
untimed timers — statsd/Tally request latencies under ids of upstream's
``name+tag=value,...`` form — at BASELINE config #4's ratio of ten
samples per timer id per resolution window.

``scale`` sources (a host's collector each), every source emitting
``timers_per_source`` timers; id ``i`` belongs to source ``i //
timers_per_source``.  A data interval is ``interval_s`` seconds (ten of
them to the configuration's 1 m window).  The seed picks ``hot_share``
of the ids as hot: ``hot_per_interval`` samples in every interval (a
timer that fires many times a second).  Every other id is cold: one
sample per interval, absent in the intervals where ``rank among the
cold ids % absent_every == interval % absent_every``.  With the
configuration's numbers (1/16 hot, 4 per interval, absent every 5th of
10 intervals) an interval carries exactly ``n_series`` samples, a
window ``10 x n_series``, a hot id 40 and a cold id 8 of them.

An interval's samples are a fixed list per pattern (``interval %
absent_every``), in an order drawn from the seed: ``series_at[p][j]``
is the id of sample ``j``, and ``vals[k, j]`` its value in interval
``k``.  Values are lognormal latencies in milliseconds around a level
per id, f64 with full mantissas: rounding to f32 is real (the system
carries a sample at f32, and says so), rounding to bfloat16 moves
every answer.  `--seed` decides values, levels, which ids are hot and
the order; never shapes.
"""

from __future__ import annotations

import numpy as np

SEC = 10**9
TIMER = 2                  # the wire's metric type

_NAMES = ("latency", "db_query", "cache_get", "rpc_call", "queue_wait",
          "render", "auth_check", "fetch_time", "serialize", "gc_pause",
          "lock_wait", "disk_write", "handshake", "index_get", "compress",
          "flush")


class Dataset:
    def __init__(self, spec: dict, seed: int, start: int, points: int):
        rng = np.random.default_rng(seed)
        per = spec["timers_per_source"]
        n = self.n_series = spec["scale"] * per
        self.interval = spec["interval_s"] * SEC
        self.points = points
        self.ts = start + np.arange(points, dtype=np.int64) * self.interval
        self.ids = [
            b"stats.timers.%s+dc=dc%02d,env=production,host=host-%07d,"
            b"service=svc-%03d" % (
                _NAMES[k % len(_NAMES)].encode() + b"_%d" % k,
                src % 12, src, src % 257)
            for src in range(spec["scale"]) for k in range(per)]
        self.types = np.full(n, TIMER, np.uint8)
        hot_n = int(n * spec["hot_share"])
        order = rng.permutation(n)
        self.hot, cold = np.sort(order[:hot_n]), np.sort(order[hot_n:])
        every = self.patterns = spec["absent_every"]
        if len(cold) % every or (
                hot_n * spec["hot_per_interval"]
                + len(cold) // every * (every - 1) != n):
            raise ValueError(f"{n} ids do not give {n} samples an interval")
        rank = np.arange(len(cold))
        self.series_at = []
        for p in range(every):
            idx = np.concatenate(
                [np.repeat(self.hot, spec["hot_per_interval"]),
                 cold[rank % every != p]])
            self.series_at.append(idx[rng.permutation(n)])
        # (points, samples of an interval): an interval's lie together
        level = rng.uniform(0.5, 500.0, n)
        self.vals = np.empty((points, n))
        for k in range(points):
            self.vals[k] = (level[self.series_at[k % every]]
                            * rng.lognormal(0.0, 0.75, n))

    def series_of(self, k: int) -> np.ndarray:
        """The id of each sample of interval k."""
        return self.series_at[k % self.patterns]
