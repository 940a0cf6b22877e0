#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served node starts on the chip.

One process.  It boots a coordinator+dbnode node through
``m3_tpu.server.assembly.run_node`` IN THE PROCESS THAT HOLDS THE CHIP,
writes a seeded Prometheus-shaped workload (BASELINE.json config #5:
histogram bucket counters + gauges, 15 s scrape, 1 h = 240 points per
series, 2 h blocks) through the node's own ingest call and its HTTP
front door, ticks past the block boundary so the buffer seals, the
device encodes and a fileset is written, reads over HTTP, and checks
what came out by the repo's own means (the naive PromQL comparator, the
scalar M3TSZ oracle, a numpy rollup reference, devguard's counters,
tracewatch).

The LAST line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Everything else (sizes, resolved impls, compile counts, per-check
verdicts) is printed on earlier lines.  The script never sets
``JAX_PLATFORMS`` and has no option that lets it pass off the chip:
sizes are arguments (``--series``, ``--seed``), the platform is not.

``--chips 4`` runs ONLY the mesh programs (sharded ingest+consume and
sharded decode->rate->histogram_quantile) against their one-device
evaluation, on a 4-device mesh from ``jax.devices()``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.parse
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
NODE_CONFIG = REPO / "m3_tpu" / "server" / "smoke_node.yaml"

SEC = 10**9
SCRAPE = 15 * SEC
MINUTE = 60 * SEC
BLOCK = 2 * 3600 * SEC
POINTS = 240                       # 1 h of 15 s scrapes
LE = ("0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1", "2.5",
      "+Inf")                      # 10 buckets per histogram
N_JOBS = 16
FULL_HISTOGRAMS = 10_000           # x 10 buckets = 100,000 counter series
FULL_GAUGES = 4_096
# What one cold run fits: at 625 histograms (10,346 series) the first
# chip run took 710 s of the 1200 s limit on a v5e, 402 compiles in its
# first pass and 422 s of them inside the first 6,250-series query
# (PR 22) — the next power of two would not fit without a warm cache.
DEFAULT_HISTOGRAMS = 625
N_EXTREME = 16                     # of the gauges: the extreme-value family
MIN_SERIES = 8_192
HTTP_SCRAPES = 8                   # the last scrapes go through HTTP
BUCKET = b"smoke_http_request_duration_seconds_bucket"
GAUGE = b"smoke_temperature_celsius"
EXTREME = b"smoke_extreme_value"
ROLLUP = b"smoke_bucket_by_job_le"
AGG_NS = "1m:2d"
RTOL = 1e-10                       # engine vs f64 reference (see check_queries)
UBS = np.array([float("inf") if le == "+Inf" else float(le) for le in LE])
EXTREMES = (1e300, -1e300, 5e-324, -5e-324, 2.2250738585072014e-308, 0.0,
            -0.0, 2.0**53 + 2, 2.0**60 + 3, 1.7976931348623157e308,
            -1.7976931348623157e308, 1e-300, 123456789.12345679,
            -9007199254740993.0)


def say(tag: str, **kw) -> None:
    print(json.dumps({tag: kw}, default=str), flush=True)


# ---------------------------------------------------------------------------
# workload (made from --seed; the references regenerate nothing: they read
# these same arrays)
# ---------------------------------------------------------------------------


class Workload:
    """ts (P,) i64; vals (S, P) f64; one tag dict / Document per series.
    Series order: bucket counters (h-major, le-minor), noise gauges,
    extreme-value gauges."""

    def __init__(self, seed: int, histograms: int, gauges: int, end: int,
                 points: int = POINTS):
        from m3_tpu.index.doc import Document
        from m3_tpu.server.http_api import _Handler

        rng = np.random.default_rng(seed)
        self.histograms, self.gauges, self.points = histograms, gauges, points
        self.ts = end - (points - np.arange(points, dtype=np.int64)) * SCRAPE
        # bucket counters: per-scrape observation counts split over the
        # buckets by a per-histogram CDF (monotone in le, +Inf = all),
        # cumulated over time on top of a random start offset
        lam = rng.uniform(5.0, 200.0, histograms)
        obs = rng.poisson(lam[:, None], (histograms, points)).astype(np.float64)
        cdf = np.sort(rng.random((histograms, len(LE) - 1)), axis=1)
        cdf = np.concatenate([cdf, np.ones((histograms, 1))], axis=1)
        inc = np.floor(obs[:, None, :] * cdf[:, :, None])
        base = np.floor(rng.uniform(0, 1e6, histograms)[:, None, None]
                        * cdf[:, :, None])
        buckets = (base + np.cumsum(inc, axis=2)).reshape(-1, points)
        n_ext = min(N_EXTREME, gauges)
        noise = rng.normal(100.0, 15.0, (gauges - n_ext, points))
        ext = rng.normal(0.0, 1.0, (n_ext, points))
        for s in range(n_ext):
            for k in range(0, points, 3):
                ext[s, k] = EXTREMES[(s + k // 3) % len(EXTREMES)]
        self.vals = np.concatenate([buckets, noise, ext]).astype(np.float64)
        self.n_bucket = histograms * len(LE)
        self.n_noise = gauges - n_ext
        self.n_ext = n_ext
        self.tags: list[dict] = []
        for h in range(histograms):
            for le in LE:
                self.tags.append({
                    b"__name__": BUCKET, b"job": b"job-%d" % (h % N_JOBS),
                    b"instance": b"inst-%05d" % h, b"le": le.encode()})
        for g in range(self.n_noise):
            self.tags.append({
                b"__name__": GAUGE, b"job": b"job-%d" % (g % N_JOBS),
                b"instance": b"inst-%05d" % g})
        for g in range(n_ext):
            self.tags.append({b"__name__": EXTREME,
                              b"instance": b"inst-%05d" % g})
        # the ids the HTTP handlers would mint for these label sets
        self.ids = [_Handler._series_id(t) for t in self.tags]
        self.docs = [Document.from_tags(i, t)
                     for i, t in zip(self.ids, self.tags)]
        self.n_series = len(self.ids)

    def key(self, i: int) -> tuple:
        """Series i's label set as http_query keys its answers."""
        return tuple(sorted((k.decode(), v.decode())
                            for k, v in self.tags[i].items()))

    def sample(self, seed: int, n: int) -> np.ndarray:
        """Series indices to inspect: n bucket series (all, if there are
        fewer) — integer counters, which the device encoder must take —
        then n // 4 noise gauges and every extreme-value series, whose
        full-mantissa streams overflow the device encoder's bit budget
        and take the product's host route."""
        rng = np.random.default_rng(seed + 1)
        buckets = rng.choice(self.n_bucket, min(n, self.n_bucket),
                             replace=False)
        noise = self.n_bucket + rng.choice(
            self.n_noise, min(n // 4, self.n_noise), replace=False)
        ext = np.arange(self.n_series - self.n_ext, self.n_series)
        return np.concatenate([np.sort(buckets), np.sort(noise), ext])


def data_end(now: int) -> int:
    """End of the data hour: the latest 15 s mark <= now whose preceding
    hour lies inside ONE 2 h block (so the block's seal time is known)."""
    end = now // SCRAPE * SCRAPE
    if end - end // BLOCK * BLOCK < 3600 * SEC:
        end = end // BLOCK * BLOCK
    return end


def smoke_ruleset():
    """1 m resolution: sum/min/max/last per noise gauge and min/max/last
    per extreme-value gauge (mapping rules; a sum over +/-1e300 has no
    reference worth the name), and the bucket counters summed across
    instances by (job, le) (rollup rule) — built as
    tests/test_rules_downsample.py builds its."""
    from m3_tpu.metrics.aggregation import AggregationID, AggregationType
    from m3_tpu.metrics.filters import TagsFilter
    from m3_tpu.metrics.pipeline import AggregationOp, Pipeline, RollupOp
    from m3_tpu.metrics.policy import StoragePolicy
    from m3_tpu.metrics.rules import (
        MappingRule, RollupRule, RollupTarget, RuleSet,
    )

    sp = StoragePolicy.parse(AGG_NS)
    selections = [AggregationType.MIN, AggregationType.MAX,
                  AggregationType.LAST]
    return RuleSet(
        version=1,
        mapping_rules=[
            MappingRule(
                "gauges-1m", TagsFilter.parse("__name__:" + GAUGE.decode()),
                (sp,), aggregation_id=AggregationID.compress(
                    [AggregationType.SUM] + selections)),
            MappingRule(
                "extremes-1m",
                TagsFilter.parse("__name__:" + EXTREME.decode()),
                (sp,), aggregation_id=AggregationID.compress(selections))],
        rollup_rules=[RollupRule(
            "buckets-by-job-le",
            TagsFilter.parse("__name__:" + BUCKET.decode()),
            (RollupTarget(Pipeline((
                AggregationOp(AggregationType.SUM),
                RollupOp(ROLLUP, (b"job", b"le")))), (sp,)),))],
    )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _pow2_capacity(n: int, shards: int) -> int:
    """Per-shard slots for n series: hash imbalance headroom, rounded
    up to a power of two."""
    return 1 << int(np.ceil(np.log2(-(-n // shards) * 1.25)))


def boot_node(root: str, n_series: int, n_agg_series: int):
    """run_node from the config file kept in the repo; only the data
    root and the per-shard capacities (which follow the sizes asked
    for) are filled in here."""
    from m3_tpu.core.config import load_config
    from m3_tpu.server.assembly import run_node

    cfg = load_config(str(NODE_CONFIG))
    cfg.db.root = root
    ns = cfg.db.namespaces["default"]
    ns.slot_capacity = _pow2_capacity(n_series, ns.num_shards)
    # 240 scrapes + the repeated HTTP pass per series
    ns.sample_capacity = ns.slot_capacity * 256
    agg = cfg.db.namespaces[AGG_NS]
    agg.slot_capacity = _pow2_capacity(n_agg_series, agg.num_shards)
    # same ring shape for the aggregated namespace: one compile of the
    # drain serves both
    agg.sample_capacity = ns.sample_capacity
    asm = run_node(cfg, ruleset=smoke_ruleset())
    say("boot", entry="m3_tpu.server.assembly.run_node", port=asm.port,
        mediator=asm.mediator is not None,
        downsampler=asm.downsampler is not None,
        shards=ns.num_shards, slot_capacity=ns.slot_capacity,
        sample_capacity=ns.sample_capacity)
    return asm


class Driver:
    """Writes scrapes in time order and runs the node's maintenance
    pass (Mediator.run_once: tick + downsampler drain) at every data
    minute, as the wall-clock loop would."""

    def __init__(self, asm, wl: Workload):
        self.asm, self.wl = asm, wl
        self.drains = 0
        self.drained = 0
        self.acked = 0
        self.http_batches = 0

    def maintain(self, now: int) -> dict:
        stats = self.asm.mediator.run_once(now_nanos=now)
        if stats.get("downsample_flushed"):
            self.drains += 1
            self.drained += stats["downsample_flushed"]
        return stats

    def _after_scrape(self, k: int) -> None:
        nxt = int(self.wl.ts[k]) + SCRAPE
        if nxt % MINUTE == 0:
            self.maintain(nxt)

    def load_direct(self, k0: int, k1: int) -> None:
        """History through Database.write_tagged_batch behind the
        downsampler — the call the HTTP handlers make (_ingest_tagged)."""
        wl, asm = self.wl, self.asm
        ns = asm.config.coordinator.namespace
        for k in range(k0, k1):
            ts = np.full(wl.n_series, wl.ts[k], np.int64)
            vals = wl.vals[:, k]
            keep = asm.downsampler.write_batch(wl.docs, ts, vals)
            if not keep.all():
                raise RuntimeError("downsampler dropped raw samples")
            res = asm.db.write_tagged_batch(ns, wl.docs, ts, vals)
            if res.rejected or getattr(res, "not_owned", 0):
                raise RuntimeError(f"write not fully accepted: {res!r}")
            self.acked += wl.n_series
            self._after_scrape(k)

    def load_http(self, k0: int, k1: int, maintain: bool = True) -> None:
        """Scrapes through /api/v1/prom/remote/write, one request per
        scrape (the node compiles its append and arena programs per
        batch size, and a whole-scrape request has the history's); each
        must be acked (204) before the next is sent."""
        from m3_tpu.server.prom_remote import (
            PromTimeSeries, build_write_request,
        )

        wl = self.wl
        for k in range(k0, k1):
            t_nanos = int(wl.ts[k])
            body = build_write_request([
                PromTimeSeries(labels=wl.tags[i],
                               samples=[(t_nanos, float(wl.vals[i, k]))])
                for i in range(wl.n_series)])
            conn = http.client.HTTPConnection("127.0.0.1", self.asm.port,
                                              timeout=600)
            conn.request("POST", "/api/v1/prom/remote/write", body)
            resp = conn.getresponse()
            resp.read()
            conn.close()
            if resp.status != 204:
                raise RuntimeError(f"remote write @{k} -> {resp.status}")
            self.http_batches += 1
            self.acked += wl.n_series
            if maintain:
                self._after_scrape(k)


def http_query(port: int, query: str, start: int, end: int,
               namespace: str | None = None) -> dict:
    """GET /api/v1/query_range -> {sorted label tuple: {t_nanos: value}}."""
    params = {"query": query, "start": repr(start / 1e9),
              "end": repr(end / 1e9), "step": "15s", "timeout": "900s"}
    if namespace:
        params["namespace"] = namespace
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=1000)
    conn.request("GET", "/api/v1/query_range?" + urllib.parse.urlencode(params))
    resp = conn.getresponse()
    raw = resp.read()
    conn.close()
    if resp.status != 200:
        raise RuntimeError(f"query {query!r} -> {resp.status}: {raw[:300]!r}")
    out = {}
    for s in json.loads(raw)["data"]["result"]:
        key = tuple(sorted(s["metric"].items()))
        out[key] = {int(round(t * 1e9)): float(v) for t, v in s["values"]}
    return out


def queries(wl: Workload) -> dict:
    b = BUCKET.decode()
    return {
        "hq_all": (f"histogram_quantile(0.99, sum by (le) (rate({b}[5m])))",
                   None),
        "rate_job3": (f'sum by (le) (rate({b}{{job="job-3"}}[5m]))', None),
        "agg_rollup": ('{__name__="%s.sum",job="job-3"}' % ROLLUP.decode(),
                       AGG_NS),
        "raw_gauge": (f'{GAUGE.decode()}{{job="job-5"}}', None),
        "raw_extreme": (EXTREME.decode(), None),
    }


def run_queries(asm, wl: Workload) -> dict:
    start, end = int(wl.ts[0]), int(wl.ts[-1])
    out = {}
    for name, (q, ns) in queries(wl).items():
        t0 = time.monotonic()
        out[name] = http_query(asm.port, q, start, end, ns)
        say("query", name=name, series=len(out[name]),
            host_seconds=round(time.monotonic() - t0, 2))
    return out


# -- references -------------------------------------------------------------


def ref_rate(ts: np.ndarray, vals: np.ndarray, steps: np.ndarray,
             window: int) -> np.ndarray:
    """Prometheus extrapolated counter rate, (S, P) -> (S, len(steps)),
    numpy over the series axis: all series share their timestamps and
    the generated counters never reset."""
    out = np.full((vals.shape[0], len(steps)), np.nan)
    for j, t in enumerate(steps.tolist()):
        idx = np.nonzero((ts > t - window) & (ts <= t))[0]
        if len(idx) < 2:
            continue
        a, b = idx[0], idx[-1]
        first, last = vals[:, a], vals[:, b]
        delta = last - first
        sampled = float(ts[b] - ts[a])
        avg = sampled / (len(idx) - 1)
        dur_start = float(ts[a] - (t - window))
        dur_end = float(t - ts[b])
        ex_start = dur_start if dur_start < avg * 1.1 else avg / 2
        ex_end = dur_end if dur_end < avg * 1.1 else avg / 2
        with np.errstate(divide="ignore", invalid="ignore"):
            zero = np.where((delta > 0) & (first >= 0),
                            sampled * (first / delta), np.inf)
        ex_s = np.minimum(ex_start, zero)
        out[:, j] = delta * (sampled + ex_s + ex_end) / sampled / (window / 1e9)
    return out


def ref_quantile(q: float, ubs: np.ndarray, counts: np.ndarray) -> float:
    """Prometheus bucketQuantile over cumulative counts (ubs ascending,
    last +Inf)."""
    total = counts[-1]
    if len(counts) < 2 or not total > 0:
        return float("nan")
    rank = q * total
    b = int(np.searchsorted(counts, rank, side="left"))
    if b == len(counts) - 1:
        return float(ubs[-2])
    if b == 0 and ubs[0] <= 0:
        return float(ubs[0])
    lo = 0.0 if b == 0 else ubs[b - 1]
    prev = 0.0 if b == 0 else counts[b - 1]
    return float(lo + (ubs[b] - lo) * ((rank - prev) / (counts[b] - prev)))


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return bool((a.view(np.uint64) == b.view(np.uint64)).all())


def _selector_matches(wl: Workload, got: dict, idx, want) -> dict:
    """A raw selector's answer holds exactly series `idx`, and at every
    scrape the value that was written, bit for bit: a sample that is
    only selected never passes through device arithmetic (the engine
    selects on the host — query/temporal.last_over_time), and the HTTP
    rendering round-trips an f64."""
    exact = total = 0
    for i in idx:
        g = got.get(wl.key(i), {})
        have = np.array([g.get(t, np.nan) for t in wl.ts.tolist()])
        exact += int((have.view(np.uint64) == want(i).view(np.uint64)).sum())
        total += wl.points
    return {"samples": total, "bit_exact": exact,
            "ok": bool(len(got) == len(idx) and exact == total > 0)}


def _close(got: float, want: float) -> bool:
    if np.isnan(want):
        return np.isnan(got)
    return abs(got - want) <= RTOL * abs(want) + 1e-12


def check_queries(wl: Workload, got: dict) -> dict:
    """(a) query answers against the naive comparator and a numpy
    reference on the generated data.  The engine computes in the
    device's f64 — on a TPU an f32 pair, ~48 mantissa bits — so computed
    answers are held to RTOL (the worst error seen is printed); raw
    selectors compute nothing and are held to bit equality."""
    from m3_tpu.comparator import naive_promql as naive

    steps = wl.ts.copy()
    start, end = int(steps[0]), int(steps[-1])
    worst = 0.0
    verdict = {}

    # rate_job3: engine == naive comparator, and naive == the numpy
    # reference (which then stands in for naive at 100K series, where a
    # straight-line python evaluator would take hours)
    job3 = [i for i in range(wl.n_bucket) if wl.tags[i][b"job"] == b"job-3"]
    series = [naive.NaiveSeries(
        tuple(sorted(wl.tags[i].items())),
        tuple(zip(wl.ts.tolist(), wl.vals[i].tolist()))) for i in job3]
    q = queries(wl)["rate_job3"][0]
    want = naive.evaluate(q, series, start, end, SCRAPE)
    rates = ref_rate(wl.ts, wl.vals[job3], steps, 5 * MINUTE)
    ok = len(want) == len(got["rate_job3"]) == len(LE)
    for key, row in want.items():
        le = dict(key)[b"le"].decode()
        g = got["rate_job3"].get((("le", le),), {})
        mine = np.nansum(rates[[j for j, i in enumerate(job3)
                                if wl.tags[i][b"le"] == le.encode()]], axis=0)
        for j, t in enumerate(steps.tolist()):
            if np.isnan(row[j]):
                ok &= t not in g
                continue
            ok &= t in g and _close(g[t], row[j]) and _close(mine[j], row[j])
            if t in g and row[j]:
                worst = max(worst, abs(g[t] - row[j]) / abs(row[j]))
    verdict["rate_job3_vs_naive"] = bool(ok)

    # hq_all: the headline query over every bucket series
    rates = ref_rate(wl.ts, wl.vals[:wl.n_bucket], steps, 5 * MINUTE)
    by_le = np.nansum(rates.reshape(wl.histograms, len(LE), -1), axis=0)
    g = got["hq_all"].get((), {})
    ok = len(got["hq_all"]) == 1
    for j, t in enumerate(steps.tolist()):
        if np.isnan(rates[0, j]):
            ok &= t not in g
            continue
        w = ref_quantile(0.99, UBS, by_le[:, j])
        ok &= t in g and _close(g[t], w)
        if t in g and w:
            worst = max(worst, abs(g[t] - w) / abs(w))
    verdict["hq_all_vs_reference"] = bool(ok)

    # agg_rollup: the aggregated namespace answers over the same front
    # door; at each window end the rollup's sum for (job-3, le)
    win = wl.ts // MINUTE
    ok = len(got["agg_rollup"]) == len(LE)
    for b, le in enumerate(LE):
        rows = [i for i in job3 if wl.tags[i][b"le"] == le.encode()]
        g = got["agg_rollup"].get(
            (("__name__", ROLLUP.decode() + ".sum"), ("job", "job-3"),
             ("le", le)), {})
        for w in np.unique(win)[:-1]:
            t = int(w + 1) * MINUTE
            want_w = float(wl.vals[rows][:, win == w].sum())
            ok &= t in g and abs(g[t] - want_w) <= 1e-12 * abs(want_w)
    verdict["agg_rollup_vs_reference"] = bool(ok)

    # raw selectors: every sample of the family
    for name, lo, hi, pick in (
            ("raw_gauge", wl.n_bucket, wl.n_bucket + wl.n_noise,
             lambda t: t[b"job"] == b"job-5"),
            ("raw_extreme", wl.n_series - wl.n_ext, wl.n_series,
             lambda t: True)):
        idx = [i for i in range(lo, hi) if pick(wl.tags[i])]
        verdict[name] = _selector_matches(
            wl, got[name], idx, lambda i: wl.vals[i])
    verdict["worst_rel_err"] = worst
    return verdict


def codec_image(wl: Workload, i: int, block: int) -> np.ndarray:
    """What the scalar codec makes of series i's values.  M3TSZ is not
    lossless on every f64: like the reference encoder it takes an
    integral value beyond 2^63 through a saturating int conversion and
    a denormal within one ulp of an integer AS that integer — so a
    flushed -1e300 or 5e-324 reads back as the codec's image of it."""
    from m3_tpu.encoding import m3tsz

    stream = m3tsz.encode_series(
        list(zip(wl.ts.tolist(), wl.vals[i].tolist())), start=block)
    return np.array([d.value for d in m3tsz.decode_series(stream)])


def check_readback(asm, wl: Workload, idx: np.ndarray,
                   block: int | None = None) -> bool:
    """(a) every acked sample of the sampled series is read back: bit
    for bit from the open buffer; as the scalar codec's image of it
    (`block` given) once the block is a fileset."""
    ns = asm.config.coordinator.namespace
    pts = asm.db.read_batch(ns, [wl.ids[i] for i in idx],
                            int(wl.ts[0]), int(wl.ts[-1]) + 1)
    ok = True
    for i, p in zip(idx.tolist(), pts):
        ok &= len(p) == wl.points
        if not ok:
            break
        want = wl.vals[i] if block is None else codec_image(wl, i, block)
        t = np.fromiter((x[0] for x in p), np.int64, len(p))
        v = np.fromiter((x[1] for x in p), np.float64, len(p))
        ok &= bool((t == wl.ts).all()) and _same_bits(v, want)
    return bool(ok)


def check_filesets(asm, wl: Workload, idx: np.ndarray, block: int) -> dict:
    """(b) the flushed streams of the sampled series are byte-identical
    to the scalar oracle's.  Which of them the DEVICE encoded is not
    taken on trust: the flush counts device-encoded and host-re-encoded
    series (printed), and the sample is encoded again here through the
    same device program with the flush's own bit budget — every sampled
    bucket series must come out of the device, and byte identity is
    claimed for those.  The same streams then decode on the device (the
    tail `auto` resolves to) back to the written points."""
    from m3_tpu.encoding import m3tsz
    from m3_tpu.encoding.m3tsz_jax import decode_batch, encode_batch
    from m3_tpu.persist.fs import DataFileSetReader, list_filesets
    from m3_tpu.storage.database import shard_for_id

    ns_name = asm.config.coordinator.namespace
    ns = asm.db.namespaces[ns_name]
    readers = {}
    streams, same = [], 0
    for i in idx.tolist():
        sh = shard_for_id(wl.ids[i], ns.opts.num_shards)
        if sh not in readers:
            vols = dict(list_filesets(asm.db.opts.root, ns_name, sh))
            if block not in vols:
                raise RuntimeError(f"shard {sh}: no fileset for the block")
            readers[sh] = DataFileSetReader(asm.db.opts.root, ns_name, sh,
                                            block, vols[block])
        seg = readers[sh].read(wl.ids[i])
        want = m3tsz.encode_series(
            list(zip(wl.ts.tolist(), wl.vals[i].tolist())), start=block)
        same += seg == want
        streams.append(seg)
    # as Shard._encode_runs calls it
    dev_streams, host_route = encode_batch(
        np.tile(wl.ts, (len(idx), 1)), wl.vals[idx],
        np.full(len(idx), block, np.int64),
        out_words=max(16, wl.points * 40 // 64 + 8))
    on_device = ~host_route
    dev_same = sum(dev_streams[r] == streams[r]
                   for r in np.nonzero(on_device)[0])
    n_bucket = int((idx < wl.n_bucket).sum())
    ts, vals, counts, fallback = decode_batch(streams, max_points=wl.points + 8)
    dev_ok = 0
    for r, i in enumerate(idx.tolist()):
        if fallback[r]:
            continue
        n = int(counts[r])
        dev_ok += bool(n == wl.points and (ts[r, :n] == wl.ts).all()
                       and _same_bits(vals[r, :n], wl.vals[i]))
    flush = {"device": sum(sh.encoded_on_device for sh in ns.shards),
             "host": sum(sh.encoded_on_host for sh in ns.shards)}
    return {"series": len(idx), "byte_identical": int(same),
            "flush_encoded": flush,
            "sampled_bucket_series": n_bucket,
            "device_encoded": int(on_device.sum()),
            "device_encoded_byte_identical": int(dev_same),
            "host_encoded": int(host_route.sum()),
            "device_decoded": int(dev_ok),
            "device_decode_fallback": int(fallback.sum()),
            "ok": bool(same == len(idx)
                       and on_device[idx < wl.n_bucket].all()
                       and dev_same == int(on_device.sum()) >= n_bucket
                       and flush["device"] >= wl.n_bucket
                       and flush["device"] + flush["host"] == wl.n_series
                       and dev_ok == len(idx) - int(fallback.sum())
                       and dev_ok >= n_bucket)}


def check_rollups(asm, wl: Workload, idx: np.ndarray, upto: int) -> dict:
    """(c) rollup lanes against numpy, per 1 m window ending at or
    before `upto`: min/max/last of the sampled noise gauges and of every
    extreme-value gauge — selections, held to bit equality — and the
    computed sums (per noise gauge; per (job, le) over every bucket
    counter) to 1e-12.  Read from the open buffer, so what is compared
    is what the arenas emitted."""
    win = wl.ts // MINUTE
    wins = np.unique(win[(win + 1) * MINUTE <= upto])
    out_ts = (wins + 1) * MINUTE
    lo, hi = int(out_ts[0]), int(out_ts[-1]) + 1
    masks = [win == w for w in wins]
    worst, n_exact, n_computed, ok = 0.0, 0, 0, True
    bad: list = []

    def compare(sid: bytes, want: np.ndarray, exact: bool) -> None:
        nonlocal worst, n_exact, n_computed, ok
        pts = asm.db.read(AGG_NS, sid, lo, hi)
        got = np.array([v for _, v in pts])
        if [t for t, _ in pts] != out_ts.tolist():
            ok = False
            bad.append({"id": sid.decode(), "points": len(pts),
                        "missing": sorted(set(out_ts.tolist())
                                          - {t for t, _ in pts})[:3]})
            return
        if exact:
            n_exact += len(want)
            miss = got.view(np.uint64) != want.view(np.uint64)
        else:
            n_computed += len(want)
            err = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
            worst = max(worst, float(err.max()))
            miss = ~(err <= 1e-12)
        if miss.any():
            ok = False
            j = int(miss.argmax())
            bad.append({"id": sid.decode(), "at": int(out_ts[j]),
                        "got": float(got[j]), "want": float(want[j])})

    def lowest(v: np.ndarray) -> float:   # -0.0 < +0.0, as Go's math.Min
        m = v.min()
        return -0.0 if m == 0 and np.signbit(v[v == 0]).any() else m

    def highest(v: np.ndarray) -> float:  # and math.Max
        m = v.max()
        return 0.0 if m == 0 and not np.signbit(v[v == 0]).all() else m

    selections = {b".min": lowest, b".max": highest,
                  b".last": lambda v: v[-1]}
    gauges = [i for i in idx.tolist()
              if wl.n_bucket <= i < wl.n_bucket + wl.n_noise]
    extremes = list(range(wl.n_series - wl.n_ext, wl.n_series))
    for i in gauges + extremes:
        per_win = [wl.vals[i, m] for m in masks]
        for suffix, fn in selections.items():
            compare(wl.ids[i] + suffix,
                    np.array([fn(v) for v in per_win]), True)
        if i in gauges:
            compare(wl.ids[i] + b".sum",
                    np.array([v.sum() for v in per_win]), False)
    from m3_tpu.metrics.rules import rollup_id

    per_win = np.stack([wl.vals[:wl.n_bucket, m].sum(axis=1) for m in masks],
                       axis=1).reshape(wl.histograms, len(LE), -1)
    jobs = np.arange(wl.histograms) % N_JOBS
    for job in range(min(N_JOBS, wl.histograms)):
        for b, le in enumerate(LE):
            rid, _ = rollup_id(ROLLUP, {b"job": b"job-%d" % job,
                                        b"le": le.encode()}, (b"job", b"le"))
            compare(rid + b".sum", per_win[jobs == job, b].sum(axis=0), False)
    return {"windows": len(wins), "gauge_series": len(gauges),
            "extreme_series": len(extremes),
            "rollup_series": min(N_JOBS, wl.histograms) * len(LE),
            "selected_values_bit_exact": n_exact,
            "computed_values": n_computed, "worst_rel_err": worst,
            "bad": bad[:5], "ok": bool(ok and n_exact and n_computed)}


def check_devguard() -> dict:
    """(d) nothing stepped down: no fallback call, no classified error,
    every stage breaker closed, fallback not forced."""
    from m3_tpu.x import devguard

    cnt = devguard.counters()
    st = devguard.status()
    bad = {k: v for k, v in cnt.items()
           if v and (k.endswith(".fallback_calls") or ".errors." in k)}
    breakers = {s: d.get("breaker") for s, d in st["stages"].items()
                if "breaker" in d}
    return {"counters": cnt, "breakers": breakers,
            "forced": devguard.fallback_forced(),
            "ok": bool(not bad and not devguard.fallback_forced()
                       and all(b == "closed" for b in breakers.values()))}


def check_impls() -> dict:
    """(e) what `auto` resolved to, whether the native module built, and
    that the Pallas kernels `auto` selects here are in the compiled
    program (a Mosaic custom call), not interpreted."""
    import jax
    import jax.numpy as jnp

    from m3_tpu.encoding import m3tsz_jax as mj
    from m3_tpu.parallel import pallas_decode, pallas_encode

    chains = mj.resolved_chains()
    out = {"place": mj.resolved_place(), "chains": chains,
           "extract": mj._resolved_extract(chains),
           # what this process tree compiled from native/*.cc (a failed
           # build raises at first use; nothing is loaded as found)
           "native_built": os.environ.get("M3_NATIVE_BUILT", "")}
    ok = "libidmap.so" in out["native_built"]
    if jax.default_backend() == "tpu":
        kernels = {}
        if out["place"] == "pallas":
            interp = pallas_encode.auto_interpret()
            txt = pallas_encode._place_pallas.lower(
                jax.ShapeDtypeStruct((8, 512), jnp.uint32),
                jax.ShapeDtypeStruct((8, 512), jnp.int32),
                w32=64, interpret=interp).compile().as_text()
            kernels["place"] = {"interpret": interp,
                                "mosaic": "tpu_custom_call" in txt}
        if out["extract"] == "pallas":
            interp = pallas_decode.auto_interpret()
            txt = pallas_decode._gather3_pallas.lower(
                jax.ShapeDtypeStruct((8, 64), jnp.uint32),
                jax.ShapeDtypeStruct((8, 512), jnp.int32),
                interpret=interp).compile().as_text()
            kernels["extract"] = {"interpret": interp,
                                  "mosaic": "tpu_custom_call" in txt}
        out["kernels"] = kernels
        ok = ok and all(k["mosaic"] and not k["interpret"]
                        for k in kernels.values())
    out["ok"] = bool(ok)
    return out


def run_smoke(histograms: int, gauges: int, seed: int, root: str,
              sample: int = 1000) -> dict:
    """The whole one-chip pass; returns {check: verdict}.  Raises on any
    phase failure.  Sizes are arguments so tests rehearse it small."""
    from m3_tpu.x import tracewatch

    tracewatch.install(raise_on_violation=False)
    t_start = time.monotonic()
    end = data_end(time.time_ns())
    block = (end - 1) // BLOCK * BLOCK
    wl = Workload(seed, histograms, gauges, end)
    say("sizes", series=wl.n_series, bucket_series=wl.n_bucket,
        gauge_series=wl.n_noise, extreme_series=wl.n_ext, points=wl.points,
        samples=wl.n_series * wl.points, seed=seed,
        data_end_unix=end // SEC, block_start_unix=block // SEC)
    # aggregated series: four lanes per noise gauge, three per extreme
    # gauge, and the rollups
    asm = boot_node(root, wl.n_series,
                    4 * wl.n_noise + 3 * wl.n_ext
                    + min(N_JOBS, histograms) * len(LE))
    try:
        drv = Driver(asm, wl)
        t0 = time.monotonic()
        k_http = wl.points - HTTP_SCRAPES
        drv.load_direct(0, k_http)
        say("history", scrapes=k_http, samples=drv.acked,
            host_seconds=round(time.monotonic() - t0, 1))
        t0 = time.monotonic()
        drv.load_http(k_http, wl.points)
        drv.maintain(end)
        say("http_writes", scrapes=HTTP_SCRAPES, acked_batches=drv.http_batches,
            acked_samples=drv.acked, drained_windows=drv.drains,
            drained_values=drv.drained,
            host_seconds=round(time.monotonic() - t0, 1))
        idx = wl.sample(seed, sample)
        before = run_queries(asm, wl)
        checks = {"a_readback_buffer": check_readback(asm, wl, idx),
                  "c_rollups": check_rollups(asm, wl, idx, end)}

        # (f) the same writes and queries again: nothing may compile
        snap = tracewatch.snapshot()
        first_pass = tracewatch.compiles()
        drv.load_http(k_http, wl.points, maintain=False)
        again = run_queries(asm, wl)
        recompiled = {k: v - first_pass.get(k, 0)
                      for k, v in tracewatch.compiles().items()
                      if v != first_pass.get(k, 0)}
        checks["f_no_recompile"] = {
            "compiles_first_pass": snap,
            "compiles_second_pass": tracewatch.retraces_since(snap),
            "recompiled": recompiled,
            "answers_identical": again == before,
            "ok": tracewatch.retraces_since(snap) == 0 and again == before}

        # seal: one maintenance pass past the block's warm window
        t0 = time.monotonic()
        ns = asm.db.namespaces[asm.config.coordinator.namespace]
        seal_at = block + BLOCK + ns.opts.buffer_past_nanos + SEC
        stats = drv.maintain(seal_at)
        flushed = {n: s.get("warm_flushed", 0)
                   for n, s in stats["tick"].items()}
        say("flush", seal_at_unix=seal_at // SEC, warm_flushed=flushed,
            open_blocks_left=sum(len(sh.buffer.open_blocks)
                                 for sh in ns.shards),
            host_seconds=round(time.monotonic() - t0, 1))
        if flushed.get(ns.name, 0) != wl.n_series:
            raise RuntimeError(f"flushed {flushed} of {wl.n_series} series")
        after = run_queries(asm, wl)
        checks["a_queries"] = check_queries(wl, before)
        # the flush changes no answer; the extreme family alone reads
        # back as the codec's image of it (see codec_image)
        checks["a_queries"]["before_equals_after_flush"] = all(
            after[q] == before[q] for q in before if q != "raw_extreme")
        checks["a_queries"]["raw_extreme_after_flush_is_codec_image"] = \
            _selector_matches(
                wl, after["raw_extreme"],
                range(wl.n_series - wl.n_ext, wl.n_series),
                lambda i: codec_image(wl, i, block))
        checks["a_queries"]["ok"] = all(
            (v["ok"] if isinstance(v, dict) else v)
            for k, v in checks["a_queries"].items() if k != "worst_rel_err")
        checks["a_readback_fileset"] = check_readback(asm, wl, idx, block)
        checks["b_filesets"] = check_filesets(asm, wl, idx, block)
        checks["d_devguard"] = check_devguard()
        checks["e_impls"] = check_impls()
        checks["windows_drained"] = {"count": drv.drains,
                                     "ok": drv.drains >= 3}
        checks["http_batches"] = {"count": drv.http_batches,
                                  "ok": drv.http_batches >= 8}
    finally:
        asm.close()
        tracewatch.uninstall()
    import jax

    mem = jax.devices()[0].memory_stats() or {}
    top = sorted(tracewatch.compiles().items(), key=lambda kv: -kv[1])[:12]
    say("run", total_compiles=tracewatch.total_compiles(),
        most_compiled=dict(top),
        peak_device_bytes=mem.get("peak_bytes_in_use"),
        host_seconds=round(time.monotonic() - t_start, 1))
    for name, v in checks.items():
        say("check", name=name,
            ok=bool(v["ok"] if isinstance(v, dict) else v), detail=v)
    return checks


# ---------------------------------------------------------------------------
# --chips 4: the mesh programs against their one-device evaluation
# ---------------------------------------------------------------------------


def run_mesh(n_devices: int, series_per_shard: int, seed: int,
             points: int = POINTS) -> dict:
    """sharded_decode_rate_hq vs single_device_reference, and
    sharded_ingest_consume vs the one-device arenas, on an n-device mesh
    built from jax.devices(); every input and output must have one
    addressable shard on each of n distinct devices."""
    import jax
    import jax.numpy as jnp

    from m3_tpu import native
    from m3_tpu.aggregator import arena
    from m3_tpu.aggregator.packed import orderable_f64
    from m3_tpu.encoding.m3tsz_jax import pack_streams
    from m3_tpu.parallel import (
        make_mesh, sharded_ingest_consume, sharded_init,
    )
    from m3_tpu.parallel.sharded_agg import (
        ShardedBatch, gauge_lanes, rollup_lanes,
    )
    from m3_tpu.parallel.sharded_query import (
        sharded_decode_rate_hq, single_device_reference,
    )

    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise RuntimeError(f"{n_devices} devices asked, {len(devs)} found")
    topo = make_mesh(num_shards=n_devices, num_replicas=1, devices=devs)
    D, S, T = n_devices, series_per_shard, points
    rng = np.random.default_rng(seed)
    say("mesh", devices=[str(d) for d in devs], series_per_shard=S, points=T)

    def spread(what: str, *arrays) -> bool:
        ok = True
        for a in arrays:
            on = {sh.device for sh in a.addressable_shards}
            if len(a.addressable_shards) != D or on != set(devs):
                say("placement", array=what, shape=a.shape,
                    devices=sorted(str(d) for d in on))
                ok = False
        return ok

    def put(a, *trailing):
        return jax.device_put(jnp.asarray(a), topo.sharded(*trailing))

    checks = {}
    # -- decode -> rate -> histogram_quantile ------------------------------
    start = 1_600_000_000 * SEC // BLOCK * BLOCK
    ts = np.tile(start + np.arange(1, T + 1) * SCRAPE, (D * S, 1))
    bucket_ids = (np.arange(D * S) % len(LE)).reshape(D, S).astype(np.int32)
    lam = 20.0 * (bucket_ids.reshape(-1) + 1) / len(LE)
    vals = np.cumsum(rng.poisson(lam[:, None], (D * S, T)), axis=1).astype(
        np.float64)
    streams, fb = native.encode_batch(
        ts.astype(np.int64), vals, np.full(D * S, start, np.int64))
    if fb.any():
        raise RuntimeError("native encoder fell back on the mesh corpus")
    words_np, nbits_np = pack_streams(streams)
    words_np = words_np.reshape(D, S, -1)
    nbits_np = nbits_np.reshape(D, S)
    steps = (start + np.arange(24, T + 1, 4) * SCRAPE).astype(np.int64)
    words, nbits, bid = (put(words_np, None, None), put(nbits_np, None),
                         put(bucket_ids, None))
    t0 = time.monotonic()
    rates, hq, errs = sharded_decode_rate_hq(
        topo, words, nbits, bid, jnp.asarray(steps), jnp.asarray(UBS),
        5 * MINUTE, 0.99, T + 1, len(LE))
    jax.block_until_ready((rates, hq, errs))
    t_sharded = time.monotonic() - t0
    r_ref, hq_ref, errs_ref = single_device_reference(
        words_np, nbits_np, bucket_ids, steps, UBS, 5 * MINUTE, 0.99, T + 1,
        len(LE))
    rates_np, hq_np = np.asarray(rates), np.asarray(hq)

    def worst(got, want) -> float:
        with np.errstate(invalid="ignore", divide="ignore"):
            err = np.abs(got - want) / np.abs(want)
        return float(np.nanmax(np.where(want == 0, 0.0, err), initial=0.0))

    checks["sharded_query"] = {
        "placement": spread("query", words, nbits, bid, rates, hq, errs),
        "no_decode_errors": not np.asarray(errs).any() and not errs_ref.any(),
        "rates_equal": bool(np.allclose(rates_np, r_ref, rtol=RTOL, atol=0,
                                        equal_nan=True)),
        "hq_equal": bool(np.allclose(hq_np, hq_ref, rtol=RTOL, atol=0)
                         and np.isfinite(hq_np).all()),
        "rates_worst_rel_err": worst(rates_np, r_ref),
        "hq_worst_rel_err": worst(hq_np, hq_ref),
        "first_call_seconds": round(t_sharded, 1)}

    # -- ingest + consume --------------------------------------------------
    # one scrape per step into window k % 2 while the other window (the
    # previous scrape) drains: `points` steps, S series per shard
    W, C, Q = 2, S, (0.5, 0.95, 0.99)
    state = sharded_init(topo, W, C, 2 * S)
    ref = [arena.make_arenas(W, C, 2 * S, Q) for _ in range(D)]
    slots = np.tile(np.arange(S, dtype=np.int32), (D, 1))
    ok_c = ok_g = ok_r = ok_e = ok_p = ok_s = True
    written = None      # the previous step's gauge values: what drains now
    t0 = time.monotonic()
    for k in range(T):
        win = np.full((D, S), k % W, np.int32)
        cvals = rng.integers(0, 1000, (D, S))
        gvals = rng.normal(100.0, 10.0, (D, S))
        # every 64th series carries the extreme family: min/max/last
        # are selections and must come back with the written bits
        gvals[:, ::64] = EXTREMES[k % len(EXTREMES)]
        tvals = np.abs(rng.normal(0.1, 0.02, (D, S)))
        times = np.full((D, S), start + (k + 1) * SCRAPE, np.int64)
        batch = ShardedBatch(
            windows=put(win, None), slots=put(slots, None),
            counter_values=put(cvals.astype(np.int64), None),
            gauge_values=put(gvals, None),
            gauge_keys=put(orderable_f64(gvals), None),
            timer_values=put(tvals, None),
            times=put(times, None))
        drain = (k + 1) % W
        state, lanes = sharded_ingest_consume(
            topo, state, batch, jnp.int32(drain), W, C, Q)
        if k == 0:
            ok_p = spread("agg", *batch, lanes["counter"][0],
                          *lanes["gauge"], lanes["timer"][0],
                          *jax.tree_util.tree_leaves(state))
        c_lanes = np.asarray(lanes["counter"][0])
        g_lanes = gauge_lanes(lanes)
        ok_e &= int(np.asarray(lanes["err"]).sum()) == 0
        gsum = np.zeros(C)
        for d in range(D):
            ca, ga, ta = ref[d]
            ca.ingest(jnp.asarray(win[d]), jnp.asarray(slots[d]),
                      jnp.asarray(cvals[d].astype(np.int64)),
                      jnp.asarray(times[d]))
            ga.ingest(win[d], slots[d], gvals[d], times[d])
            c_want = np.asarray(ca.consume(drain)[0])
            g_want = np.asarray(ga.consume(drain)[0])
            ca.reset_window(drain)
            ga.reset_window(drain)
            ok_c &= bool(np.array_equal(c_lanes[d], c_want, equal_nan=True))
            ok_g &= bool(np.allclose(g_lanes[d], g_want, rtol=1e-12,
                                     equal_nan=True))
            with np.errstate(over="ignore"):  # +/-1e308 sums reach inf
                gsum += np.nan_to_num(g_lanes[d][:, 5]) + c_lanes[d][:, 5]
        rollup = rollup_lanes(lanes)
        ok_r &= bool(np.allclose(rollup[:, 0], gsum, rtol=1e-12))
        if written is not None:
            # one sample per slot drains: LAST = MIN = MAX = what was
            # written, bit for bit, and so the cross-shard min / max
            for lane in range(3):
                ok_s &= _same_bits(g_lanes[:, :, lane], written)
            ok_s &= _same_bits(rollup[:, 2], written.min(axis=0))
            ok_s &= _same_bits(rollup[:, 3], written.max(axis=0))
        written = gvals
    checks["sharded_agg"] = {
        "placement": ok_p, "counter_lanes_equal": ok_c,
        "gauge_lanes_equal": ok_g, "gauge_selections_bit_exact": ok_s,
        "cross_shard_rollup_equal": ok_r,
        "err_bits_clean": ok_e, "steps": T,
        "seconds": round(time.monotonic() - t0, 1)}
    for v in checks.values():
        v["ok"] = all(x for k, x in v.items()
                      if isinstance(x, bool))
    for name, v in checks.items():
        say("check", name=name, ok=bool(v["ok"]), detail=v)
    return checks


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--series", type=int,
                    default=DEFAULT_HISTOGRAMS * len(LE) + FULL_GAUGES,
                    help="series in all, up to 104096: gauges stay at 4096, "
                         "the 10000 histograms are cut by powers of two")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    root = None
    try:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        if device["platform"] != "tpu":
            raise RuntimeError(
                f"no accelerator: jax.devices()[0].platform is "
                f"{device['platform']!r}; this script passes only on a TPU")
        import m3_tpu  # noqa: F401 — x64 on
        from m3_tpu.x import jaxcache

        say("start", device=device, compile_cache=jaxcache.configure(),
            jax=jax.__version__)
        if args.chips == 4:
            if device["count"] != 4:
                raise RuntimeError(f"--chips 4 on {device['count']} devices")
            checks = run_mesh(4, MIN_SERIES, args.seed)
        else:
            if args.series < MIN_SERIES:
                raise RuntimeError(f"--series below {MIN_SERIES}")
            histograms = FULL_HISTOGRAMS
            while histograms * len(LE) + FULL_GAUGES > args.series:
                histograms //= 2
            if histograms != FULL_HISTOGRAMS:
                say("reduced", histograms=histograms,
                    of=FULL_HISTOGRAMS, series=histograms * len(LE)
                    + FULL_GAUGES,
                    why="the cold run is compile-bound: full size does "
                        "not fit the 1200 s limit (see DEFAULT_HISTOGRAMS)")
            root = tempfile.mkdtemp(prefix="m3_chip_smoke_")
            checks = run_smoke(histograms, FULL_GAUGES, args.seed, root)
        ok = all(bool(v["ok"] if isinstance(v, dict) else v)
                 for v in checks.values())
    except BaseException as e:  # noqa: BLE001 — reported, then exit != 0
        import traceback

        traceback.print_exc()
        say("failed", error=f"{type(e).__name__}: {e}")
        ok = False
    finally:
        if root is not None:
            shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
