"""Dataset kind ``agg_untimed``: what collectors and coordinators send
an m3aggregator as untimed metrics — statsd-style counters and gauges
under ids of upstream's ``name+tag=value,...`` form.

``scale`` sources (a host's collector each), every source emitting
``counters_per_source`` counters and ``gauges_per_source`` gauges once
per interval; series ``i`` belongs to source ``i // per_source`` and is
a counter where ``i % per_source < counters_per_source``.  Gauges are
f64 with full mantissas (a level per series times a factor in [0.5,
1.5) per interval: positive, so a sum never cancels and a lower stored
precision shows in every lane).  Counters are integer increments per
interval: 0-2,000 for most series and 2^20-2^31 for the
``wide_counter_share`` the seed picks (those leave the packed counter
arena's narrow lanes for its overflow pool).  `--seed` decides values,
levels and which counters are wide, never shapes.
"""

from __future__ import annotations

import numpy as np

SEC = 10**9
COUNTER, GAUGE = 1, 3      # the wire's metric types

_NAMES = ("requests", "errors", "bytes_in", "bytes_out", "retries",
          "timeouts", "cache_hits", "cache_misses", "queue_depth",
          "inflight", "heap_bytes", "goroutines", "open_fds", "cpu_pct",
          "lag_seconds", "temperature")


class Dataset:
    def __init__(self, spec: dict, seed: int, start: int, points: int):
        rng = np.random.default_rng(seed)
        nc, ng = spec["counters_per_source"], spec["gauges_per_source"]
        per = nc + ng
        n = self.n_series = spec["scale"] * per
        self.interval = spec["interval_s"] * SEC
        self.points = points
        self.ts = start + np.arange(points, dtype=np.int64) * self.interval
        j = np.arange(n) % per
        self.types = np.where(j < nc, COUNTER, GAUGE).astype(np.uint8)
        self.ids = [
            b"stats.%s.%s+dc=dc%02d,env=production,host=host-%07d,"
            b"service=svc-%03d" % (
                b"counts" if k < nc else b"gauges",
                _NAMES[k % len(_NAMES)].encode() + b"_%d" % k,
                src % 12, src, src % 257)
            for src in range(spec["scale"]) for k in range(per)]
        counters = np.flatnonzero(self.types == COUNTER)
        wide = rng.choice(counters, max(1, int(len(counters)
                                               * spec["wide_counter_share"])),
                          replace=False)
        is_wide = np.zeros(n, bool)
        is_wide[wide] = True
        level = rng.uniform(1.0, 1000.0, n)
        # (points, series): one interval's samples lie together
        self.vals = np.empty((points, n))
        for k in range(points):
            inc = np.where(is_wide, rng.integers(1 << 20, 1 << 31, n),
                           rng.integers(0, 2001, n)).astype(np.float64)
            self.vals[k] = np.where(self.types == COUNTER, inc,
                                    level * rng.uniform(0.5, 1.5, n))

    def owners(self, senders: int) -> list[np.ndarray]:
        """Connection s owns the series i % senders == s: with the
        per-source counts multiples of `senders`, every connection the
        same number of counters and of gauges."""
        return [np.arange(s, self.n_series, senders) for s in range(senders)]
