"""Traffic kind ``dashboard_flushed``: a Grafana dashboard whose range
lies behind the open block of a live node — every panel reads one
SEALED 2 h block from its fileset.

Set-up loads `history_scrapes` scrapes, the last of them at the end of
block ``B`` (the latest 2 h block that ended at least 15 minutes before
the process started), through the call the HTTP handlers make; then ONE
maintenance pass at ``B + 2 h + buffer_past + 1 s`` seals and flushes
the block, and the run stops unless every series was flushed and the
buffer holds nothing of ``B``.  Then every panel once and two live
scrapes, each followed by every panel.  The window is
``dashboard_live``'s: `viewers` closed-loop clients send the per-job
panels back to back in an order drawn from the seed, over the FIXED
range ``[B, B + 2 h - 10 s]`` (a sealed block is decoded whole, so the
range is the block), while a live scrape of every series arrives every
`live_every_s` seconds of wall time at ``B + 2 h + k x 10 s``: the open
block, outside the queried range.

On a program whose node has no counter
``db.fileset_series_device_decoded`` (before PR 33) the run ends right
after boot, exit 1: such a program reads a flushed block through the
scalar codec, seconds a panel.
"""

from __future__ import annotations

import importlib
import time
import urllib.parse

import numpy as np

from benchmark import harness, wire
from benchmark.generators import dashboard_live
from benchmark.references import m3tsz_decode, readback

SEC, MINUTE, BLOCK = harness.SEC, harness.MINUTE, harness.BLOCK
_DEVICE, _SCALAR = ("db.fileset_series_device_decoded",
                    "db.fileset_series_scalar_decoded")


def sealed_block(now_nanos: int) -> int:
    """Start of the latest 2 h block that ended at least 15 minutes
    ago: past its buffer_past, so a live node would have flushed it."""
    return (now_nanos - 15 * MINUTE) // BLOCK * BLOCK - BLOCK


class Run(dashboard_live.Run):
    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from m3_tpu.index.doc import Document

        cell, tr = self.cell, self.cell.traffic
        spec = cell.cfg["dataset"]
        kind = importlib.import_module("benchmark.datasets." + spec["kind"])
        self.hist = tr["history_scrapes"]
        points = self.hist + tr["live_warmup_scrapes"] + tr["live_max_scrapes"]
        self.block = sealed_block(time.time_ns())
        interval = spec["scrape_interval_s"] * SEC
        # the history ends at the block's end; the live scrapes follow it
        self.start = self.block + BLOCK - self.hist * interval
        if self.start < self.block:
            raise RuntimeError("history_scrapes does not fit one block")
        self.data = data = cell.data = kind.Dataset(
            spec, cell.seed, self.start, points)
        harness.say("sizes", series=data.n_series, history_scrapes=self.hist,
                    block_start_unix=self.block // SEC, seed=cell.seed)
        asm = cell.asm = harness.boot_node(cell.cfg, cell.root, data.tags)
        if self._counter(_DEVICE) is None:
            raise SystemExit(
                "prom.dashboard_flushed: this program's node has no counter "
                f"{_DEVICE}: it reads a flushed block through the scalar "
                "codec; the cell cannot run on it")
        self.k = 0
        cell.facts.update(samples_acked=0, agg_values=0)

        # history, as the HTTP handlers' tail writes it (_ingest_tagged)
        docs = [Document.from_tags(i, t) for i, t in zip(data.ids, data.tags)]
        ns = cell.cfg["namespace"]
        t0 = time.monotonic()
        with cell.span("history_load"):
            for k in range(self.hist):
                res = asm.db.write_tagged_batch(
                    ns, docs, np.full(data.n_series, data.ts[k], np.int64),
                    data.vals[:, k])
                if res.rejected or getattr(res, "not_owned", 0):
                    raise RuntimeError(f"write not fully accepted: {res!r}")
        self.k = self.hist
        harness.say("history", scrapes=self.hist,
                    samples=self.hist * data.n_series,
                    host_seconds=round(time.monotonic() - t0, 1))

        # one maintenance pass after buffer_past: seal, encode, fileset
        t0 = time.monotonic()
        nsopts = asm.db.namespaces[ns].opts
        with cell.span("seal_and_flush"):
            st = asm.mediator.run_once(
                now_nanos=self.block + BLOCK + nsopts.buffer_past_nanos + SEC)
        flushed = st["tick"][ns]["warm_flushed"]
        shards = asm.db.namespaces[ns].shards
        harness.say("flush", host_seconds=round(time.monotonic() - t0, 1),
                    warm_flushed=flushed,
                    index_sealed=st["tick"][ns]["index_sealed"],
                    encoded_on_device=sum(s.encoded_on_device for s in shards),
                    encoded_on_host=sum(s.encoded_on_host for s in shards))
        if flushed != data.n_series or any(
                self.block in s.buffer.open_blocks for s in shards):
            raise RuntimeError(
                f"the block did not seal: warm_flushed {flushed} of "
                f"{data.n_series} series")

        # the panels: fixed URLs over the sealed block
        q_start, q_end = self.block, self.block + BLOCK - interval
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

    def _counter(self, name: str):
        """A counter of the node's registry by the end of its name."""
        for key, v in self.cell.asm.registry.snapshot().items():
            if key.endswith(name):
                return v
        return None

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        before = self._counter(_SCALAR), self._counter(_DEVICE)
        super().window(seconds)
        self.scalar_fallback = self._counter(_SCALAR) - before[0]
        self.cell.facts["decoded_series"] = self._counter(_DEVICE) - before[1]

    # -- what decides `correct` --------------------------------------------

    def verify(self, control: str = "") -> dict:
        """The window's answers against the numpy reference on the
        generator's arrays, as ``dashboard_live`` (and its control
        `f32`).  A sample of series read back through
        `Database.read_batch`, the sealed block and the live scrapes
        alike, by bits against the generator's arrays — the extreme
        family's sealed part against the reference decoder's image of
        the flushed bytes, for M3TSZ is not lossless on it.  The same
        series' flushed segments (`Database.read_block`) through
        ``references/m3tsz_decode.py`` against the generator's arrays:
        the bytes hold what was acked.  With the control `lost_tail`
        the read-back stands in the comparison without each sealed
        stream's last datapoint."""
        cell, data, lim = self.cell, self.data, self.cell.traffic["limits"]
        answers = self.answers
        if control == "f32":
            from m3_tpu.query import precision

            malformed, worst = self._panel_errors(answers)
            harness.say("program", answers=len(answers),
                        answers_malformed=malformed, hq_rel_err=worst)
            precision.set_compute_dtype(control)
            try:
                answers = []
                for j, url in enumerate(self.urls):
                    status, ans = dashboard_live.get_query(cell.asm.port, url)
                    if ans is not None:
                        answers.append((j, ans))
            finally:
                precision.set_compute_dtype("f64")
        malformed, worst = self._panel_errors(answers)
        if not answers:
            malformed = 1

        ns, hist = cell.cfg["namespace"], self.hist
        idx = data.sample(cell.seed, cell.traffic["readback_series"])
        lossless = idx < data.n_series - data.n_ext
        # what the bytes on disk hold, by the plain reference
        segments = {}
        for shard in range(len(cell.asm.db.namespaces[ns].shards)):
            segments.update(cell.asm.db.read_block(ns, shard, self.block))
        want = np.ascontiguousarray(data.vals[idx, :self.k])
        want_bits, want_ts = want.view(np.uint64), data.ts[:hist]
        stream_wrong = 0
        t0 = time.monotonic()
        for r, i in enumerate(idx.tolist()):
            ts, bits = m3tsz_decode.decode(segments.get(data.ids[i], b""))
            ok = len(ts) == hist and bool((ts == want_ts).all())
            if ok and lossless[r]:
                ok = bool((bits == want_bits[r, :hist]).all())
            elif ok:
                want_bits[r, :hist] = bits      # the codec's image
            stream_wrong += not ok
        harness.say("reference_decode", series=len(idx),
                    host_seconds=round(time.monotonic() - t0, 1))
        got = readback.read_raw(cell.asm, ns, data, idx, self.k)
        if control == "lost_tail":
            harness.say("program", raw_wrong_or_missing=
                        readback.wrong_or_missing(got, want))
            got.view(np.uint64)[:, hist - 1] = readback.MISSING
        return {
            "raw_wrong_or_missing": (readback.wrong_or_missing(got, want),
                                     lim["raw_wrong_or_missing"]),
            "stream_decode_wrong": (stream_wrong, lim["stream_decode_wrong"]),
            "scalar_fallback_series": (self.scalar_fallback,
                                       lim["scalar_fallback_series"]),
            "answers_malformed": (malformed, lim["answers_malformed"]),
            "hq_rel_err": (worst, lim["hq_rel_err"])}
