"""Traffic kind ``dashboard_live``: a Grafana dashboard over the last
hour of a live node.

Set-up loads `history_scrapes` scrapes through the call the HTTP
handlers make (downsampler, then Database.write_tagged_batch) with the
node's maintenance pass at every data minute, then runs each distinct
panel once and two live scrapes.  In the window `viewers` closed-loop
clients send the per-job panels back to back over a FIXED range (the
history's hour), in an order drawn from the seed, while a live scrape of
every series arrives through remote write every `live_every_s` seconds
of wall time at the next data timestamp after the history: it lands in
the same open block, outside the queried range, and invalidates the
buffer's sorted snapshot as a live node's scrapes do.  The live scrape
is ONE request in the history's series order, so that it reuses the
programs the history compiled (every distinct batch size is a compile).

Copied from ``chip_smoke.py`` (``Driver.load_direct``, ``Driver.maintain``,
``http_query``, ``queries``) at commit d4ba90b; this copy, not the
original, is the yardstick from now on.
"""

from __future__ import annotations

import http.client
import importlib
import json
import threading
import time
import urllib.parse

import numpy as np

from benchmark import harness, wire
from benchmark.references import promql, readback

SEC, MINUTE = harness.SEC, harness.MINUTE


def get_query(port: int, url: str):
    """GET a fixed query_range URL -> (status, {label tuple: {t_nanos:
    value}} or None): the body is parsed here, inside the request's
    time (chip_smoke.http_query)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=1000)
    try:
        conn.request("GET", url)
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, None
    out = {}
    for s in json.loads(raw)["data"]["result"]:
        key = tuple(sorted(s["metric"].items()))
        out[key] = {int(round(t * 1e9)): float(v) for t, v in s["values"]}
    return 200, out


class Run:
    def __init__(self, cell):
        self.cell = cell
        self.window_end = None
        self.answers: list = []     # (panel, parsed answer) of the window

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from m3_tpu.index.doc import Document

        cell, tr = self.cell, self.cell.traffic
        spec = cell.cfg["dataset"]
        kind = importlib.import_module("benchmark.datasets." + spec["kind"])
        self.hist = tr["history_scrapes"]
        points = self.hist + tr["live_warmup_scrapes"] + tr["live_max_scrapes"]
        self.start = harness.data_start(time.time_ns())
        self.data = data = cell.data = kind.Dataset(
            spec, cell.seed, self.start, points)
        harness.say("sizes", series=data.n_series, history_scrapes=self.hist,
                    data_start_unix=self.start // SEC, seed=cell.seed)
        asm = cell.asm = harness.boot_node(cell.cfg, cell.root, data.tags)
        self.k = 0
        cell.facts.update(samples_acked=0, agg_values=0)

        # history, as the HTTP handlers' tail writes it (_ingest_tagged)
        docs = [Document.from_tags(i, t) for i, t in zip(data.ids, data.tags)]
        ns = cell.cfg["namespace"]
        t0 = time.monotonic()
        with cell.span("history_load"):
            for k in range(self.hist):
                ts = np.full(data.n_series, data.ts[k], np.int64)
                vals = data.vals[:, k]
                if asm.downsampler is not None:
                    keep = asm.downsampler.write_batch(docs, ts, vals)
                    if not keep.all():
                        raise RuntimeError("downsampler dropped raw samples")
                res = asm.db.write_tagged_batch(ns, docs, ts, vals)
                if res.rejected or getattr(res, "not_owned", 0):
                    raise RuntimeError(f"write not fully accepted: {res!r}")
                self._after_scrape(k)
        self.k = self.hist
        harness.say("history", scrapes=self.hist,
                    samples=self.hist * data.n_series,
                    host_seconds=round(time.monotonic() - t0, 1))

        # the panels: fixed URLs over the history's hour
        q_start, q_end = int(data.ts[0]), int(data.ts[self.hist - 1])
        self.steps = np.arange(q_start, q_end + 1, tr["step_s"] * SEC,
                               dtype=np.int64)
        self.urls = []
        for j in range(data.n_jobs):
            q = tr["query"].format(q=tr["quantile"], k=j,
                                   bucket=data.names["bucket"].decode())
            self.urls.append("/api/v1/query_range?" + urllib.parse.urlencode({
                "query": q, "start": repr(q_start / 1e9),
                "end": repr(q_end / 1e9), "step": f"{tr['step_s']}s",
                "timeout": "900s"}))
        cell.facts["rate_shape"] = (data.n_bucket / data.n_jobs, self.hist,
                                    len(self.steps))
        self.live = wire.Template(data.tags, self.start)
        t0 = time.monotonic()
        self._all_panels()
        for _ in range(tr["live_warmup_scrapes"]):
            self._live_scrape()
            self._all_panels()
        harness.say("panels_warm", host_seconds=round(time.monotonic() - t0, 1))

    def _after_scrape(self, k: int) -> None:
        nxt = int(self.data.ts[k]) + self.data.interval
        if nxt % MINUTE == 0:
            st = self.cell.asm.mediator.run_once(now_nanos=nxt)
            self.cell.facts["agg_values"] += st.get("downsample_flushed", 0) or 0

    def _all_panels(self) -> None:
        for url in self.urls:
            status, _ = get_query(self.cell.asm.port, url)
            if status != 200:
                raise RuntimeError(f"warm-up query -> {status}")

    def _live_scrape(self) -> None:
        """One whole scrape at the next data timestamp, one request."""
        cell, data, k = self.cell, self.data, self.k
        if k >= data.points:
            raise RuntimeError("all prepared live scrapes used")
        c0 = time.monotonic()
        body = self.live.body(int(data.ts[k]), data.vals[:, k])
        sent = time.monotonic()
        cell.add_busy(sent - c0)
        with cell.span("live_scrape"):
            status = wire.post_write(cell.asm.port, body)
        cell.log.add("live_write", sent, time.monotonic(), status == 204,
                     data.n_series, k)
        if status != 204:
            raise RuntimeError(f"live scrape @{k} -> {status}")
        cell.facts["samples_acked"] += data.n_series
        self.k += 1

    # -- the window ---------------------------------------------------------

    def _viewer(self, v: int, t_end: float) -> None:
        cell, port = self.cell, self.cell.asm.port
        rng = np.random.default_rng((cell.seed + 3, v))
        try:
            while True:
                for j in rng.permutation(len(self.urls)).tolist():
                    if time.monotonic() >= t_end:
                        return
                    sent = time.monotonic()
                    with cell.annotate("query_in_flight"):
                        status, ans = get_query(port, self.urls[j])
                    cell.log.add("query", sent, time.monotonic(),
                                 status == 200, 0, j)
                    if ans is not None:
                        self.answers.append((j, ans))
        except Exception as e:  # noqa: BLE001 — reported by the driver
            self._errors.append(e)

    def window(self, seconds: float) -> None:
        cell, tr = self.cell, self.cell.traffic
        t0 = cell.window[0]
        t_end = t0 + seconds
        self._errors: list = []
        viewers = [threading.Thread(target=self._viewer, args=(v, t_end),
                                    daemon=True, name=f"viewer-{v}")
                   for v in range(tr["viewers"])]
        for t in viewers:
            t.start()
        due = t0 + tr["live_first_s"]
        while time.monotonic() < t_end:
            if cell.slice_wanted():
                cell.slice_open()
            elif cell.slice_full():
                cell.slice_close()
            if time.monotonic() >= due:
                self._live_scrape()
                due += tr["live_every_s"]
            time.sleep(0.02)
        # the window closes on the clock; the queries in flight finish.
        # They were sent in the window, so their latency counts in the
        # tail; they finish after it, so the rate (client_rate: rows done
        # by window_end) does not count them
        self.window_end = time.monotonic()
        for t in viewers:
            t.join()
        if self._errors:
            raise self._errors[0]

    # -- what decides `correct` --------------------------------------------

    def _panel_errors(self, answers) -> tuple[int, float]:
        """(answers of the wrong shape, worst relative error of a served
        value against the numpy reference) over the given answers."""
        data, tr = self.data, self.cell.traffic
        jobs = np.array([t.get(b"job") for t in data.tags[:data.n_bucket]])
        ts, want = data.ts[:self.hist], {}
        malformed, worst = 0, 0.0
        for j, ans in answers:
            if j not in want:
                rows = np.nonzero(jobs == b"job-%d" % j)[0]
                want[j] = promql.hq_by_le(
                    tr["quantile"], data.ubs, ts, data.vals[rows, :self.hist],
                    self.steps, tr["rate_window_s"] * SEC)
            w = want[j]
            g = ans.get((), None)
            present = ~np.isnan(w)
            if len(ans) != 1 or g is None or set(g) != set(
                    self.steps[present].tolist()):
                malformed += 1
                continue
            have = np.array([g[t] for t in self.steps[present].tolist()])
            err = np.abs(have - w[present]) / np.maximum(np.abs(w[present]),
                                                         1e-300)
            worst = max(worst, float(err.max()))
        return malformed, worst

    def verify(self, control: str = "") -> dict:
        """Every answer the window returned against the numpy reference
        on the generator's arrays (index match, fetch, rate, group sum,
        quantile, HTTP body); and a sample of series read back, history
        and live scrapes alike, bit for bit.  With the control `f32` the
        program's own lower-precision path (the engine's compute width
        one step down, query/precision.py) answers every panel once more
        and those answers stand in the window's in the same comparison:
        the run then has to read correct: false."""
        cell, data, lim = self.cell, self.data, self.cell.traffic["limits"]
        answers = self.answers
        if control:
            from m3_tpu.query import precision

            malformed, worst = self._panel_errors(answers)
            harness.say("program", answers=len(answers),
                        answers_malformed=malformed, hq_rel_err=worst)
            precision.set_compute_dtype(control)
            try:
                answers = []
                for j, url in enumerate(self.urls):
                    status, ans = get_query(cell.asm.port, url)
                    if ans is not None:
                        answers.append((j, ans))
            finally:
                precision.set_compute_dtype("f64")
        malformed, worst = self._panel_errors(answers)
        if not answers:
            malformed = 1
        idx = data.sample(cell.seed, cell.traffic["readback_series"])
        got = readback.read_raw(cell.asm, cell.cfg["namespace"], data, idx,
                                self.k)
        return {
            "raw_wrong_or_missing": (
                readback.wrong_or_missing(got, data.vals[idx, :self.k]),
                lim["raw_wrong_or_missing"]),
            "answers_malformed": (malformed, lim["answers_malformed"]),
            "hq_rel_err": (worst, lim["hq_rel_err"])}
