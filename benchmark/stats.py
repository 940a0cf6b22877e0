"""The arithmetic behind every client-side number: rates over a whole
window, percentiles over every request sent in it, spreads of repeated
runs.  Pure Python on plain lists so selftest.py can check it by hand."""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass, field


@dataclass
class Request:
    kind: str               # "write" | "query" | "live_write"
    sent: float             # monotonic seconds
    done: float
    ok: bool
    units: int = 0          # samples carried (writes)
    tag: object = None      # generator's own key (scrape index, panel, ...)


@dataclass
class RequestLog:
    """Every request a window sent, in the order the clients logged it."""
    rows: list = field(default_factory=list)

    def add(self, *a, **kw) -> None:
        self.rows.append(Request(*a, **kw))    # list.append is atomic

    def of(self, kind: str) -> list:
        return [r for r in self.rows if r.kind == kind]


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile (the smallest value with at least p % of
    the sample at or below it); None on an empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(1, math.ceil(p / 100.0 * len(vals)))
    return vals[k - 1]


def latency_ms(rows, p: float) -> float | None:
    """p-th percentile of send -> completion over ALL rows given; a
    failed request keeps the time it took to fail (it is also counted
    under `failed`)."""
    v = percentile([r.done - r.sent for r in rows], p)
    return None if v is None else v * 1e3


def rate(units: float, window_s: float) -> float | None:
    """All work over all the window's time."""
    return units / window_s if window_s > 0 else None


def work(rows, per: str) -> float:
    """The acked work among `rows` in the unit a per-unit metric divides
    by: thousands of samples written, or queries answered."""
    if per == "ksample":
        return sum(r.units for r in rows if r.ok and r.kind == "write") / 1e3
    if per == "query":
        return sum(1 for r in rows if r.ok and r.kind == "query")
    raise ValueError(f"unknown unit of work {per!r}")


def iqr_share(values) -> float:
    """Spread of repeated runs as the contract defines it: distance
    between the first and third quartile (statistics.quantiles, n=4)
    over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
