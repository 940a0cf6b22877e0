"""Traffic kind ``mixed``: a node's remote-write senders and a Grafana
dashboard's viewers at once.

Set-up is ``dashboard_live``'s: the history through the call the HTTP
handlers make, maintenance at every data minute, the per-job panels
over the history's fixed range, each run once.  Then ``write_loop``'s
senders start, and `warmup_scrapes` scrapes go through them, every
panel answered after each (so a re-drain of the open window after a
write has run, and a maintenance pass falls among them).

In the window the senders send one scrape after another as
``write_loop`` does (a fixed share of the instances each, one request
in flight, a barrier closing the scrape, maintenance inline at every
data minute), but open loop: scrape k of the window is due at
``t0 + k * n_series / offered_samples_per_s`` and starts then, or at
once where the one before it ended late; none is skipped and none is
sent early to catch up.  Beside them `viewers` closed-loop clients send
the panels as ``dashboard_live``'s do.  The scrapes land after the
panels' range, in the same open block: every answer stays what the
reference computes from the history, and every scrape invalidates
every shard's sorted snapshot of that block.

The sender loop, the scrape, the history, the panels, the viewers and
both comparisons are the two generators' own methods; only the seven
lines that cut each sender's share into requests are repeated here,
because ``write_loop`` keeps them inside its set-up.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import harness, wire
from benchmark.generators import dashboard_live, write_loop

MINUTE = harness.MINUTE


class Run(dashboard_live.Run, write_loop.Run):
    def __init__(self, cell):
        write_loop.Run.__init__(self, cell)
        dashboard_live.Run.__init__(self, cell)
        self.paced: list = []       # (due, started) of each window scrape

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        cell, tr = self.cell, self.cell.traffic
        # the history and the panels; the data set holds the writers'
        # scrapes after the history, and no live scrape is sent
        cell.traffic = dict(tr, live_warmup_scrapes=0, live_max_scrapes=(
            tr["warmup_scrapes"] + tr["max_scrapes"]))
        try:
            dashboard_live.Run.setup(self)
        finally:
            cell.traffic = tr
        data = self.data
        # the history's last maintenance pass, for the rollups' check
        self.maintained_upto = (int(data.ts[self.hist - 1]) + data.interval
                                ) // MINUTE * MINUTE
        # each sender's requests, as write_loop builds them
        rng = np.random.default_rng(cell.seed + 2)
        self.requests = []
        for own in data.owners(tr["senders"]):
            own = own[rng.permutation(len(own))]
            n_req = -(-len(own) // tr["max_samples_per_send"])
            self.requests.append([
                (idx, wire.Template([data.tags[i] for i in idx], self.start))
                for idx in np.array_split(own, n_req)])
        harness.say("requests", per_scrape=sum(len(r) for r in self.requests),
                    sizes=sorted({len(i) for r in self.requests for i, _ in r}),
                    period_s=self._period())
        cell.facts.update(scrapes=0)
        self._start_senders()
        took = []
        for _ in range(tr["warmup_scrapes"]):
            t0 = time.monotonic()
            self._scrape()
            self._all_panels()
            took.append(round(time.monotonic() - t0, 2))
        harness.say("warmup_scrapes", host_seconds=took)

    def _period(self) -> float:
        """Seconds between two scrapes' due times at the offered rate."""
        return self.data.n_series / self.cell.traffic["offered_samples_per_s"]

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        cell, tr = self.cell, self.cell.traffic
        t0 = cell.window[0]
        t_end = t0 + seconds
        period = self._period()
        self.k_window = self.k
        viewers = [threading.Thread(target=self._viewer, args=(v, t_end),
                                    daemon=True, name=f"viewer-{v}")
                   for v in range(tr["viewers"])]
        for t in viewers:
            t.start()
        while True:
            now = time.monotonic()
            if now >= t_end:
                break
            if cell.slice_wanted():
                cell.slice_open()
            elif cell.slice_full():
                cell.slice_close()
            due = t0 + len(self.paced) * period
            if now < due:
                time.sleep(min(due - now, 0.01))
                continue
            self.paced.append((due, now))
            self._scrape()
        # the scrape under way when the clock ran out ends the window;
        # the queries in flight then finish after it (dashboard_live)
        self.window_end = time.monotonic()
        self._stop = True
        self._go.wait()
        for t in self._threads + viewers:
            t.join()
        if self._errors:
            raise self._errors[0]
        late = [s - d for d, s in self.paced]
        harness.say("scrapes", warmup=self.k_window - self.hist,
                    window=len(self.paced), prepared=tr["max_scrapes"],
                    period_s=period, late_max_s=max(late, default=0.0),
                    late_over_period=sum(1 for x in late if x > period))

    # -- what decides `correct` --------------------------------------------

    def verify(self, control: str = "") -> dict:
        """The panels against the numpy reference (``dashboard_live``:
        control ``f32`` answers them once more one precision down) and
        the writes (``write_loop``: a sample read back bit for bit over
        the history and every scrape sent, and the rollups of every
        maintenance pass; control ``stale_read`` leaves the newest acked
        scrape out of the read-back).  The write side's read-back is the
        one that counts."""
        panels = dashboard_live.Run.verify(
            self, control if control == "f32" else "")
        writes = write_loop.Run.verify(
            self, control if control == "stale_read" else "")
        return {**panels, **writes}
