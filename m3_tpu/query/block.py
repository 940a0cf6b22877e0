"""Columnar block model: the unit of data between storage and functions.

Equivalent of `src/query/block` (`column.go`, series/step iterators in
`types.go`): a block is a (series × step) matrix of float64 samples on a
regular step grid, plus per-series metadata (tags).  Where the reference
exposes pull-based iterators consumed one step/series at a time, the TPU
form IS the matrix — every function is an array op over it, NaN marks
missing samples (Prometheus staleness semantics).

`RawBlock` carries irregular raw datapoints (padded (S, P) with counts)
for temporal functions that need the actual samples within each window
(rate & friends, *_over_time) — mirroring how the reference's temporal
nodes re-read raw series rather than pre-aligned steps
(`src/query/functions/temporal/base.go:102-230`).
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class SeriesMeta:
    """Tags for one series (reference block.SeriesMeta)."""

    tags: tuple[tuple[bytes, bytes], ...]

    @classmethod
    def from_dict(cls, d: dict[bytes, bytes]) -> "SeriesMeta":
        return cls(tuple(sorted(d.items())))

    def as_dict(self) -> dict[bytes, bytes]:
        return dict(self.tags)

    def drop(self, names: set[bytes]) -> "SeriesMeta":
        return SeriesMeta(tuple((n, v) for n, v in self.tags if n not in names))

    def keep(self, names: set[bytes]) -> "SeriesMeta":
        return SeriesMeta(tuple((n, v) for n, v in self.tags if n in names))

    def drop_name(self) -> "SeriesMeta":
        # kept on the meta: a fetched series' meta lives as long as its
        # index document (query/storage_adapter.py), and every range
        # function over it asks again
        m = self.__dict__.get("_no_name")
        if m is None:
            m = self.drop({b"__name__"})
            object.__setattr__(self, "_no_name", m)
        return m


@dataclasses.dataclass
class Block:
    """Step-aligned block: values[s, t] at step_times[t] (NaN = no sample).

    ``values`` may be a numpy array OR a device (JAX) array: the engine
    keeps blocks device-resident between pipeline stages — a
    rate→histogram_quantile chain at 100K series moves ~200MB per hop,
    which must not round-trip through the host — and materializes ONCE
    at the query boundary (`Engine._execute_range`).  Host-side
    consumers inside the engine simply use numpy ops (a device array
    converts implicitly); anything outside the engine only ever sees
    numpy."""

    step_times: np.ndarray  # (T,) int64 UnixNanos
    values: np.ndarray  # (S, T) float64 (numpy or device array)
    series: list[SeriesMeta]

    @property
    def num_series(self) -> int:
        return self.values.shape[0]

    @property
    def num_steps(self) -> int:
        return self.values.shape[1]

    def with_values(self, values, series: list[SeriesMeta] | None = None) -> "Block":
        return Block(self.step_times, values,
                     series if series is not None else self.series)

    def rows(self):
        """The array a row gather reads: ``values``, or a padded array
        whose first ``num_series`` rows they are (`PaddedBlock`)."""
        return self.values

    def materialized(self) -> "Block":
        """Force values to host float64 (the query-boundary sync)."""
        return Block(self.step_times, np.asarray(self.values, np.float64),
                     self.series)


class PaddedBlock(Block):
    """A range function's answer evaluated in fixed-shape row blocks
    (`Engine._range_rows`): ``padded`` holds whole blocks of rows, the
    first ``len(series)`` of them the series', the rest empty (NaN).
    Consumers that gather rows by index (aggregations, histogram
    quantiles) read ``padded`` through `rows`, so the fleet's exact
    series count reaches no program's shape; ``values`` is cut from it
    on first use."""

    def __init__(self, step_times: np.ndarray, padded, series: list):
        self.step_times, self.padded, self.series = step_times, padded, series
        self._values = None

    @property
    def values(self):
        if self._values is None:
            self._values = self.padded[:len(self.series)]
        return self._values

    @property
    def num_series(self) -> int:
        return len(self.series)

    @property
    def num_steps(self) -> int:
        return self.padded.shape[1]

    def rows(self):
        return self.padded

    def materialized(self) -> Block:
        return Block(self.step_times,
                     np.asarray(self.padded, np.float64)[:len(self.series)],
                     self.series)


@dataclasses.dataclass
class RawBlock:
    """Irregular raw datapoints per series, time-sorted and right-padded."""

    ts: np.ndarray  # (S, P) int64; padded tail = i64 max
    values: np.ndarray  # (S, P) float64
    counts: np.ndarray  # (S,) int64 real points per series
    series: list[SeriesMeta]

    @classmethod
    def from_lists(cls, pts: list[list[tuple[int, float]]],
                   series: list[SeriesMeta]) -> "RawBlock":
        S = len(pts)
        P = max((len(p) for p in pts), default=0)
        P = max(P, 1)
        ts = np.full((S, P), np.iinfo(np.int64).max, np.int64)
        vals = np.full((S, P), np.nan)
        counts = np.zeros(S, np.int64)
        for i, p in enumerate(pts):
            counts[i] = len(p)
            if p:
                ts[i, : len(p)] = [t for t, _ in p]
                vals[i, : len(p)] = [v for _, v in p]
        return cls(ts, vals, counts, series)
