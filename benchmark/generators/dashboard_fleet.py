"""Traffic kind ``dashboard_fleet``: one query over the whole fleet —
``histogram_quantile`` over the rate of EVERY bucket series, summed by
(job, le): one answer series a job.

Set-up is ``dashboard_live``'s: `history_scrapes` scrapes through the
call the HTTP handlers make with the node's maintenance pass at every
data minute, then the query once and two live scrapes, each followed by
the query.  In the window `viewers` closed-loop clients (one) send the
query back to back over the FIXED range of the history's hour, while a
live scrape of every series arrives through remote write every
`live_every_s` seconds of wall time, in the open block outside the
queried range.

`correct`: every answer of the window has exactly one series a job with
the right label set and steps, each within `hq_rel_err` of
``references/promql.py`` ``hq_by_le`` over that job's bucket rows; a
sample of series read back bit for bit.  Controls: `f32` (the engine's
compute width one step down) and `lost_block` (the query answered by an
engine in this process over the node's storage with one row block's
series withheld from every fetch).
"""

from __future__ import annotations

import numpy as np

from benchmark import harness
from benchmark.generators import dashboard_live
from benchmark.references import promql

SEC = harness.SEC


class Run(dashboard_live.Run):
    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        # the traffic's query names no job, so dashboard_live's per-job
        # URLs are one URL, the fleet query: its warm-up runs it once,
        # then after each of two live scrapes
        super().setup()
        data, tr = self.data, self.cell.traffic
        self.urls = self.urls[:1]
        self.query = tr["query"].format(q=tr["quantile"],
                                        bucket=data.names["bucket"].decode())
        self.cell.facts.pop("rate_shape", None)
        harness.say("fleet_query", series=data.n_bucket, jobs=data.n_jobs,
                    steps=len(self.steps))

    def _all_panels(self) -> None:
        status, _ = dashboard_live.get_query(self.cell.asm.port, self.urls[0])
        if status != 200:
            raise RuntimeError(f"warm-up query -> {status}")

    # -- what decides `correct` --------------------------------------------

    def _want(self) -> dict:
        """{job label value: hq_by_le over that job's bucket rows}."""
        if getattr(self, "_wanted", None) is None:
            data, tr = self.data, self.cell.traffic
            jobs = np.array([t[b"job"] for t in data.tags[:data.n_bucket]])
            ts = data.ts[:self.hist]
            self._wanted = {}
            for j in range(data.n_jobs):
                job = b"job-%d" % j
                rows = np.nonzero(jobs == job)[0]
                self._wanted[job.decode()] = promql.hq_by_le(
                    tr["quantile"], data.ubs, ts, data.vals[rows, :self.hist],
                    self.steps, tr["rate_window_s"] * SEC)
        return self._wanted

    def _panel_errors(self, answers) -> tuple[int, float]:
        """(answers of the wrong shape, worst relative error of a served
        value against the numpy reference) over the given answers: an
        answer is one series a job, labelled {job} alone."""
        want = self._want()
        malformed, worst = 0, 0.0
        for _, ans in answers:
            if set(ans) != {(("job", job),) for job in want}:
                malformed += 1
                continue
            bad = False
            for job, w in want.items():
                g = ans[(("job", job),)]
                present = ~np.isnan(w)
                if set(g) != set(self.steps[present].tolist()):
                    bad = True
                    break
                have = np.array([g[t] for t in self.steps[present].tolist()])
                err = np.abs(have - w[present]) / np.maximum(
                    np.abs(w[present]), 1e-300)
                worst = max(worst, float(err.max()))
            malformed += bad
        return malformed, worst

    def _answer_in_process(self, storage) -> dict | None:
        """The fleet query through an engine in this process over
        `storage`, as the HTTP body's parse would give it."""
        from m3_tpu.query.engine import Engine

        data = self.data
        block = Engine(storage).execute_range(
            self.query, int(data.ts[0]), int(data.ts[self.hist - 1]),
            self.cell.traffic["step_s"] * SEC)
        out = {}
        for meta, row in zip(block.series, np.asarray(block.values)):
            key = tuple(sorted((k.decode(), v.decode()) for k, v in meta.tags))
            out[key] = {int(t): float(v) for t, v in
                        zip(block.step_times.tolist(), row.tolist())
                        if not np.isnan(v)}
        return out

    def verify(self, control: str = "") -> dict:
        """Every answer of the window against the numpy reference, and a
        sample of series read back bit for bit (dashboard_live's, with
        its control `f32`).  With `lost_block` an engine in this process
        answers the query once more over the node's storage with the
        last row block of every fetch withheld, and that answer stands
        in the window's."""
        if control != "lost_block":
            return super().verify(control)
        cell = self.cell
        malformed, worst = self._panel_errors(self.answers)
        harness.say("program", answers=len(self.answers),
                    answers_malformed=malformed, hq_rel_err=worst)
        saved = self.answers
        self.answers = [(0, self._answer_in_process(
            _WithoutLastBlock(cell.asm.db, cell.cfg["namespace"])))]
        try:
            return super().verify("")
        finally:
            self.answers = saved


def _block_rows() -> int:
    """Rows a range function's program takes in one call (4,096 on a
    program that does not say)."""
    from m3_tpu.query import engine

    return getattr(engine, "_RANGE_BLOCK_ROWS", 4096)


class _WithoutLastBlock:
    """The node's storage with the last row block of every fetch
    withheld: the series a block-at-a-time evaluation would lose if it
    dropped one call's rows."""

    def __init__(self, db, namespace: str):
        from m3_tpu.query.storage_adapter import DatabaseStorage

        self._inner = DatabaseStorage(db, namespace)

    def fetch_raw(self, name, matchers, start_nanos, end_nanos):
        from m3_tpu.query.block import RawBlock

        raw = self._inner.fetch_raw(name, matchers, start_nanos, end_nanos)
        n = len(raw.series)
        keep = (n - 1) // _block_rows() * _block_rows()
        return RawBlock(raw.ts[:keep], raw.values[:keep], raw.counts[:keep],
                        raw.series[:keep])
