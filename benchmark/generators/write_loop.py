"""Traffic kind ``write_loop``: closed-loop remote-write senders.

Each of `senders` threads owns a fixed share of the dataset's series and
sends, scrape after scrape, its share in requests of at most
`max_samples_per_send` samples, one in flight, all samples of a request
at the scrape's timestamp.  A barrier closes every scrape; the data
clock then advances one interval, back to back (compressed time: the
cell measures capacity).  At every data minute the driver thread runs
the node's maintenance pass inline, inside the window.

The sender loop and the maintenance rule are copied from
``chip_smoke.py`` (``Driver.load_http``, ``Driver.maintain``,
``Driver._after_scrape``) at commit d4ba90b; this copy, not the
original, is the yardstick from now on.  Changes: several senders with a
barrier, bodies patched into templates (benchmark/wire.py) instead of
encoded per scrape, every request logged.
"""

from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from benchmark import harness, wire
from benchmark.references import readback, rollup

MINUTE = harness.MINUTE


class Run:
    def __init__(self, cell):
        self.cell = cell
        self.window_end = None
        self.k = 0                  # next scrape to send
        self.maintained_upto = 0    # data time of the last maintenance pass

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        cell, tr = self.cell, self.cell.traffic
        spec = cell.cfg["dataset"]
        kind = importlib.import_module("benchmark.datasets." + spec["kind"])
        points = tr["warmup_scrapes"] + tr["max_scrapes"]
        self.start = harness.data_start(time.time_ns())
        self.data = cell.data = kind.Dataset(spec, cell.seed, self.start, points)
        data = self.data
        harness.say("sizes", series=data.n_series, points_prepared=points,
                    data_start_unix=self.start // harness.SEC, seed=cell.seed)
        cell.asm = harness.boot_node(cell.cfg, cell.root, data.tags)
        # each sender's requests: fixed series, in an order drawn from
        # the seed (values and orders, never shapes)
        rng = np.random.default_rng(cell.seed + 2)
        self.requests = []
        for own in data.owners(tr["senders"]):
            own = own[rng.permutation(len(own))]
            n_req = -(-len(own) // tr["max_samples_per_send"])
            chunks = np.array_split(own, n_req)
            self.requests.append([
                (idx, wire.Template([data.tags[i] for i in idx], self.start))
                for idx in chunks])
        harness.say("requests", per_scrape=sum(len(r) for r in self.requests),
                    sizes=sorted({len(i) for r in self.requests for i, _ in r}))
        cell.facts.update(samples_acked=0, agg_values=0, scrapes=0)
        self._start_senders()
        took = []
        for _ in range(tr["warmup_scrapes"]):
            t0 = time.monotonic()
            self._scrape()
            took.append(round(time.monotonic() - t0, 2))
        harness.say("warmup_scrapes", host_seconds=took)

    def _start_senders(self) -> None:
        n = len(self.requests)
        self._go = threading.Barrier(n + 1)
        self._done = threading.Barrier(n + 1)
        self._stop = False
        self._errors: list = []
        self._threads = [threading.Thread(target=self._sender, args=(s,),
                                          daemon=True, name=f"sender-{s}")
                         for s in range(n)]
        for t in self._threads:
            t.start()

    def _sender(self, s: int) -> None:
        cell, data, port = self.cell, self.data, self.cell.asm.port
        while True:
            self._go.wait()
            if self._stop:
                return
            k = self.k
            t_nanos = int(data.ts[k])
            try:
                for idx, tpl in self.requests[s]:
                    c0 = time.monotonic()
                    body = tpl.body(t_nanos, data.vals[idx, k])
                    sent = time.monotonic()
                    cell.add_busy(sent - c0)
                    with cell.annotate("write_request_in_flight"):
                        status = wire.post_write(port, body)
                    cell.log.add("write", sent, time.monotonic(),
                                 status == 204, len(idx), k)
                    if status == 204:
                        self._acked[s] += len(idx)
            except Exception as e:  # noqa: BLE001 — reported by the driver
                self._errors.append(e)
            self._done.wait()

    def _scrape(self) -> None:
        """One scrape from every sender, then maintenance if a data
        minute has ended (chip_smoke.Driver._after_scrape)."""
        cell = self.cell
        if self.k >= self.data.points:
            raise RuntimeError(
                f"all {self.data.points} prepared scrapes used before the "
                "window ended: this cell is due for a larger series count")
        self._acked = [0] * len(self.requests)
        self._go.wait()
        self._done.wait()
        if self._errors:
            raise self._errors[0]
        cell.facts["samples_acked"] += sum(self._acked)
        cell.facts["scrapes"] += 1
        nxt = int(self.data.ts[self.k]) + self.data.interval
        self.k += 1
        if nxt % MINUTE == 0:
            with cell.span("maintenance_pass"):
                st = cell.asm.mediator.run_once(now_nanos=nxt)
            self.maintained_upto = nxt
            cell.facts["agg_values"] += st.get("downsample_flushed", 0) or 0

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        cell = self.cell
        self.k_window = self.k
        t0 = cell.window[0]
        while time.monotonic() - t0 < seconds:
            if cell.slice_wanted():
                cell.slice_open()
            elif cell.slice_full():
                cell.slice_close()
            self._scrape()
        self.window_end = time.monotonic()
        self._stop = True
        self._go.wait()
        for t in self._threads:
            t.join()
        used = self.k - self.k_window
        harness.say("scrapes", warmup=self.k_window, window=used,
                    prepared=cell.traffic["max_scrapes"],
                    over_half=used > cell.traffic["max_scrapes"] // 2)

    # -- what decides `correct` --------------------------------------------

    def verify(self, control: str = "") -> dict:
        """Every scrape sent (warm-up and window) against the arrays the
        generator made from the seed: a sample of series read back from
        the open buffer, bit for bit, and the aggregated lanes of every
        1 m window the maintenance passes drained.  With a control, that
        control's answers stand in the program's place in the same
        comparison (and the program's own readings go to an earlier
        line): the run then has to read correct: false."""
        cell, data, lim = self.cell, self.data, self.cell.traffic["limits"]
        idx = data.sample(cell.seed, cell.traffic["readback_series"])
        got = readback.read_raw(cell.asm, cell.cfg["namespace"], data, idx,
                                self.k)
        want = data.vals[idx, :self.k]
        agg_dtype = None
        if control:
            harness.say("program", raw_wrong_or_missing=readback.wrong_or_missing(
                got, want))
            got, agg_dtype = CONTROLS[control](want)
        out = {"raw_wrong_or_missing":
               (readback.wrong_or_missing(got, want), lim["raw_wrong_or_missing"])}
        if cell.cfg.get("rules"):
            gauges = idx[(idx >= data.n_bucket)
                         & (idx < data.n_bucket + data.n_noise)]
            gauges = gauges[:cell.traffic["rollup_gauges"]]
            agg = rollup.Lanes(cell.asm, cell.cfg, data, gauges, self.k,
                               self.maintained_upto)
            with np.errstate(over="ignore"):
                wrong, err = agg.compare(agg_dtype)
            harness.say("rollups", **agg.summary)
            out["agg_selected_wrong"] = (wrong, lim["agg_selected_wrong"])
            out["agg_sum_rel_err"] = (err, lim["agg_sum_rel_err"])
        return out


# The controls: the reference put in the program's place with one stated
# guarantee broken -> (what a read-back would return, the precision the
# aggregated lanes are computed in, None = the program's own lanes).


def _f32(want: np.ndarray):
    """Stored values and computed sums one precision down."""
    with np.errstate(over="ignore"):
        return want.astype(np.float32).astype(np.float64), np.float32


def _stale_read(want: np.ndarray):
    """The newest acked scrape is not readable yet."""
    stale = want.copy()
    stale.view(np.uint64)[:, -1] = readback.MISSING
    return stale, None


CONTROLS = {"f32": _f32, "stale_read": _stale_read}
