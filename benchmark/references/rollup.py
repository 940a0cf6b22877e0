"""The plain numpy reference of the downsampler's lanes: per 1 m window,
min/max/last of gauges (selections: bit equality) and computed sums (per
gauge; per group over every rolled-up counter).

Copied from ``chip_smoke.py`` (``check_rollups``) at commit d4ba90b;
this copy, not the original, is the yardstick from now on.  Changes: the
rules come from the configuration file; it counts wrong selections and
returns the worst relative error of the sums instead of a verdict; the
sums can be computed in a lower precision (the control).
"""

from __future__ import annotations

import numpy as np

MINUTE = 60 * 10**9


def lowest(v: np.ndarray) -> float:        # -0.0 < +0.0, as Go's math.Min
    m = v.min()
    return -0.0 if m == 0 and np.signbit(v[v == 0]).any() else m


def highest(v: np.ndarray) -> float:       # and math.Max
    m = v.max()
    return 0.0 if m == 0 and not np.signbit(v[v == 0]).all() else m


SELECT = {"MIN": lowest, "MAX": highest, "LAST": lambda v: v[-1]}


class Lanes:
    """Reads the aggregated namespace once; compare() can then be asked
    for the program's numbers and for the control's."""

    def __init__(self, asm, cfg: dict, data, gauges: np.ndarray, points: int,
                 upto: int):
        from m3_tpu.metrics.rules import rollup_id

        rules, ns = cfg["rules"], cfg["agg_namespace"]
        ts = data.ts[:points]
        win = ts // MINUTE
        self.wins = np.unique(win[(win + 1) * MINUTE <= upto])
        self.out_ts = (self.wins + 1) * MINUTE
        self.masks = [win == w for w in self.wins]
        lo, hi = int(self.out_ts[0]), int(self.out_ts[-1]) + 1
        self.rows = []      # (kind, got or None, source values per window)
        names = np.array([t[b"__name__"] for t in data.tags])

        def fetch(sid: bytes):
            pts = asm.db.read(ns, sid, lo, hi)
            if [t for t, _ in pts] != self.out_ts.tolist():
                return None
            return np.array([v for _, v in pts])

        for r in rules["mapping"]:
            series = np.nonzero(names == r["metric"].encode())[0]
            if len(series) > 64:       # a large family: the sampled ones
                series = np.intersect1d(series, gauges)
            for i in series.tolist():
                per_win = [data.vals[i, :points][m] for m in self.masks]
                for a in r["aggregations"]:
                    self.rows.append(
                        (a, fetch(data.ids[i] + b"." + a.lower().encode()),
                         per_win))
        for r in rules["rollup"]:
            series = np.nonzero(names == r["metric"].encode())[0]
            keys = [g.encode() for g in r["group_by"]]
            groups: dict = {}
            for i in series.tolist():
                groups.setdefault(tuple(data.tags[i][k] for k in keys),
                                  []).append(i)
            for key, members in groups.items():
                rid, _ = rollup_id(r["new_name"].encode(),
                                   dict(zip(keys, key)), tuple(keys))
                block = data.vals[members, :points]
                per_win = [block[:, m].ravel() for m in self.masks]
                self.rows.append(
                    (r["aggregation"],
                     fetch(rid + b"." + r["aggregation"].lower().encode()),
                     per_win))
        self.summary = {"windows": len(self.wins), "lanes": len(self.rows),
                        "unread": sum(1 for _, g, _ in self.rows if g is None)}

    def compare(self, dtype=None):
        """(selected values wrong or missing, worst relative error of the
        computed sums).  With `dtype` the reference stands in for the
        program, computed in that precision (the control)."""
        wrong, worst = 0, 0.0
        for kind, got, per_win in self.rows:
            if kind in SELECT:
                want = np.array([SELECT[kind](v) for v in per_win])
                have = got
                if dtype is not None:
                    have = want.astype(dtype).astype(np.float64)
                if have is None:
                    wrong += len(want)
                    continue
                wrong += int((have.view(np.uint64)
                              != want.view(np.uint64)).sum())
            else:
                want = np.array([v.sum() for v in per_win])
                have = got
                if dtype is not None:
                    have = np.array([v.astype(dtype).sum(dtype=dtype)
                                     for v in per_win], np.float64)
                if have is None:
                    worst = float("inf")
                    continue
                err = np.abs(have - want) / np.maximum(np.abs(want), 1e-300)
                worst = max(worst, float(err.max()))
        return wrong, worst
