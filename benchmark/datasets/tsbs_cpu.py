"""Dataset kind ``tsbs_cpu``: the Time Series Benchmark Suite's
``cpu-only`` use case as Prometheus series.

One ``cpu`` reading per host per interval: every field a random walk
clamped to [0, 100] and emitted as an integer; every host a fixed set of
tags.  As series: ``cpu_<field>{tags}``, host-major, field-minor (the
order of a TSBS row).  Written from memory of tsbs
``pkg/data/usecases/common`` and ``devops/cpu.go`` (no network): the
field and tag names and the pools are listed in the configuration under
``assumed``.  `--seed` decides values and which pool entry a host gets,
never shapes.
"""

from __future__ import annotations

import numpy as np

from benchmark.wire import series_id

SEC = 10**9


class Dataset:
    def __init__(self, spec: dict, seed: int, start: int, points: int):
        rng = np.random.default_rng(seed)
        hosts, fields = spec["scale"], spec["fields"]
        self.interval = spec["log_interval_s"] * SEC
        self.points = points
        self.ts = start + np.arange(points, dtype=np.int64) * self.interval
        pools = spec["tag_pools"]
        region = rng.integers(0, len(pools["region"]), hosts)
        picks = {k: rng.integers(0, len(v), hosts) for k, v in pools.items()
                 if k != "region"}
        rack = rng.integers(0, spec["racks"], hosts)
        self.tags, owner = [], []
        for h in range(hosts):
            r = pools["region"][region[h]]
            host = {b"hostname": b"host_%d" % h, b"region": r.encode(),
                    b"datacenter": f"{r}{'abc'[h % 3]}".encode(),
                    b"rack": b"%d" % rack[h]}
            for k, idx in picks.items():
                host[k.encode()] = pools[k][idx[h]].encode()
            for f in fields:
                self.tags.append({b"__name__": b"cpu_" + f.encode(), **host})
                owner.append(h)
        self.host = np.asarray(owner)
        self.hosts = hosts
        n = hosts * len(fields)
        # clamped random walk, integer emission (tsbs ClampedRandomWalk:
        # normal steps, state clamped to [0, 100])
        vals = np.empty((n, points))
        state = rng.uniform(0, 100, n)
        for k in range(points):
            state = np.clip(state + rng.normal(0.0, 1.0, n), 0.0, 100.0)
            vals[:, k] = np.floor(state)
        self.vals = vals
        self.ids = [series_id(t) for t in self.tags]
        self.n_series = n

    def owners(self, senders: int) -> list[np.ndarray]:
        """A worker owns a contiguous block of hosts (tsbs splits its
        input by hostname hash; a fixed partition gives the same thing:
        every worker the same series every timestamp)."""
        per = -(-self.hosts // senders)
        return [np.nonzero(self.host // per == s)[0] for s in range(senders)]

    def sample(self, seed: int, n: int) -> np.ndarray:
        rng = np.random.default_rng(seed + 1)
        return np.sort(rng.choice(self.n_series, min(n, self.n_series),
                                  replace=False))
