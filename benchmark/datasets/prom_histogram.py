"""Dataset kind ``prom_histogram``: histogram bucket counters, noise
gauges and an extreme-value gauge family on a fixed scrape interval.

Copied from ``chip_smoke.py`` (class ``Workload``, constants ``LE``,
``EXTREMES``, ``N_JOBS``) at commit d4ba90b; this copy, not the
original, is the yardstick from now on.  Changes: the sizes come from
the configuration file; timestamps run FORWARD from a start (the data
clock of a cell) instead of backward from an end; ids are minted by
``wire.series_id``; the sender that owns a series is its instance modulo
the sender count.
"""

from __future__ import annotations

import numpy as np

from benchmark.wire import series_id

SEC = 10**9
EXTREMES = (1e300, -1e300, 5e-324, -5e-324, 2.2250738585072014e-308, 0.0,
            -0.0, 2.0**53 + 2, 2.0**60 + 3, 1.7976931348623157e308,
            -1.7976931348623157e308, 1e-300, 123456789.12345679,
            -9007199254740993.0)


class Dataset:
    """ts (P,) i64; vals (S, P) f64; one tag dict per series.  Series
    order: bucket counters (histogram-major, le-minor), noise gauges,
    extreme-value gauges.  `--seed` decides values only, never shapes."""

    def __init__(self, spec: dict, seed: int, start: int, points: int):
        rng = np.random.default_rng(seed)
        histograms, gauges = spec["histograms"], spec["gauges"]
        self.le = tuple(spec["le"])
        self.n_jobs = spec["jobs"]
        self.interval = spec["scrape_interval_s"] * SEC
        self.names = {k: v.encode() for k, v in spec["names"].items()}
        self.histograms, self.points = histograms, points
        self.ts = start + np.arange(points, dtype=np.int64) * self.interval
        # bucket counters: per-scrape observation counts split over the
        # buckets by a per-histogram CDF (monotone in le, +Inf = all),
        # cumulated over time on top of a random start offset
        lam = rng.uniform(5.0, 200.0, histograms)
        obs = rng.poisson(lam[:, None], (histograms, points)).astype(np.float64)
        cdf = np.sort(rng.random((histograms, len(self.le) - 1)), axis=1)
        cdf = np.concatenate([cdf, np.ones((histograms, 1))], axis=1)
        inc = np.floor(obs[:, None, :] * cdf[:, :, None])
        base = np.floor(rng.uniform(0, 1e6, histograms)[:, None, None]
                        * cdf[:, :, None])
        buckets = (base + np.cumsum(inc, axis=2)).reshape(-1, points)
        n_ext = min(spec["extreme_gauges"], gauges)
        noise = rng.normal(100.0, 15.0, (gauges - n_ext, points))
        ext = rng.normal(0.0, 1.0, (n_ext, points))
        for s in range(n_ext):
            for k in range(0, points, 3):
                ext[s, k] = EXTREMES[(s + k // 3) % len(EXTREMES)]
        self.vals = np.concatenate([buckets, noise, ext]).astype(np.float64)
        self.n_bucket = histograms * len(self.le)
        self.n_noise = gauges - n_ext
        self.n_ext = n_ext
        self.tags: list[dict] = []
        owner = []
        for h in range(histograms):
            for le in self.le:
                self.tags.append({
                    b"__name__": self.names["bucket"],
                    b"job": b"job-%d" % (h % self.n_jobs),
                    b"instance": b"inst-%05d" % h, b"le": le.encode()})
                owner.append(h)
        for g in range(self.n_noise):
            self.tags.append({
                b"__name__": self.names["gauge"],
                b"job": b"job-%d" % (g % self.n_jobs),
                b"instance": b"inst-%05d" % g})
            owner.append(g)
        for g in range(n_ext):
            self.tags.append({b"__name__": self.names["extreme"],
                              b"instance": b"inst-%05d" % g})
            owner.append(g)
        self.instance = np.asarray(owner)
        self.ids = [series_id(t) for t in self.tags]
        self.n_series = len(self.ids)
        self.ubs = np.array([float("inf") if le == "+Inf" else float(le)
                             for le in self.le])

    def owners(self, senders: int) -> list[np.ndarray]:
        """A sender scrapes whole instances: a fixed share of the series."""
        return [np.nonzero(self.instance % senders == s)[0]
                for s in range(senders)]

    def sample(self, seed: int, n: int) -> np.ndarray:
        """Series to read back: n bucket series, n // 4 noise gauges and
        every extreme-value series."""
        rng = np.random.default_rng(seed + 1)
        buckets = rng.choice(self.n_bucket, min(n, self.n_bucket),
                             replace=False)
        noise = self.n_bucket + rng.choice(
            self.n_noise, min(n // 4, self.n_noise), replace=False)
        ext = np.arange(self.n_series - self.n_ext, self.n_series)
        return np.concatenate([np.sort(buckets), np.sort(noise), ext])
