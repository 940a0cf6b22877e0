"""From the profiler's trace to numbers: device busy time, time per
jitted program, the operations that took most time, and the idle gaps
named by what the benchmark's threads were doing.

Reads the ``.xplane.pb`` the JAX profiler wrote with nothing but JAX
(``jax.profiler.ProfileData``).  On a TPU every chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
HLO operation and whose line ``XLA Modules`` holds one event per
executed program (``jit_<name>(<fingerprint>)``); the host's threads are
lines of the plane ``/host:CPU`` and carry the benchmark's own
``bench:<what>`` annotations.  A trace can also be given as plain JSON
(selftest's recorded sample): the same three lists.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


class Trace:
    """ops / modules: {device: [(name, start_s, dur_s)]};
    host: [(name, start_s, dur_s)] of the bench:* annotations."""

    def __init__(self, ops: dict, modules: dict, host: list, window_s: float,
                 gap_order=()):
        self.ops, self.modules, self.host = ops, modules, host
        self.window_s = window_s
        self.gap_order = tuple(gap_order)
        self.devices = sorted(set(ops) | set(modules))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device, averaged
        over the devices traced."""
        if not self.devices:
            return 0.0
        return sum(_union((s, s + d) for _, s, d in self._busy_events(dev))
                   for dev in self.devices) / len(self.devices)

    def _busy_events(self, dev):
        return self.ops.get(dev) or self.modules.get(dev, [])

    def program_seconds(self, patterns) -> tuple[float, int]:
        """(device seconds, executions) of the programs whose name
        matches any pattern, summed over devices."""
        rx = [re.compile(p) for p in patterns]
        total, calls = 0.0, 0
        for evs in self.modules.values():
            for name, _, dur in evs:
                if any(r.search(name) for r in rx):
                    total += dur
                    calls += 1
        return total, calls

    def top_ops(self, n: int) -> list:
        """The programs, then operations, that took most device time."""
        acc: dict = {}
        for evs in self.modules.values():
            for name, _, dur in evs:
                key = re.sub(r"\(\d+\)$", "", name)
                acc[key] = acc.get(key, 0.0) + dur
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int) -> list:
        """Idle seconds of the first device by what the host was doing
        at each gap's midpoint: of the bench:* annotations open on any
        thread then, the first that the traffic file's `trace.gap_order`
        lists (most specific first), else the shortest one."""
        if not self.devices:
            return []
        evs = sorted((s, s + d) for _, s, d in
                     self._busy_events(self.devices[0]))
        if not evs:
            return []
        gaps, end = [], evs[0][0]
        for s, e in evs:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        acc: dict = {}
        for g0, g1 in gaps:
            mid = (g0 + g1) / 2
            open_now = {}
            for name, s, d in self.host:
                if s <= mid <= s + d:
                    open_now[name] = min(d, open_now.get(name, d))
            name = next((w for w in self.gap_order if w in open_now), None) \
                or min(open_now, key=open_now.get, default="nothing_due")
            acc[name] = acc.get(name, 0.0) + (g1 - g0)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def from_json(doc: dict) -> Trace:
    as_tuples = lambda d: {k: [tuple(e) for e in v] for k, v in d.items()}  # noqa: E731
    return Trace(as_tuples(doc["ops"]), as_tuples(doc["modules"]),
                 [tuple(e) for e in doc["host"]], doc["window_s"],
                 doc.get("gap_order", ()))


def load(trace_dir: str, window_s: float, gap_order=()) -> Trace:
    """The newest .xplane.pb under a profiler log directory."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest[plane.name] = [
                        (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        host.append((e.name[6:], e.start_ns / 1e9,
                                     e.duration_ns / 1e9))
    return Trace(ops, modules, host, window_s, gap_order)
