"""Idle seconds of the device that fall under NO span of the node, as a
share of the traced slice — and, printed before the result line as
`{"idle_by_span": {...}}`, every idle second by the innermost node span
open then: what the host was doing while the chip waited.  The line
before it, `{"spans_in_slice": ...}`, is the ring's whole account by name.

The ring's spans are on the host's monotonic clock, the device's ops on
the profiler's (seconds since the session began).  The harness holds the
`bench:*` annotations on both: in the trace (`trace_events.host`) and as
the request log's `sent`/`done` and `cell.spans`.  Each annotation is
paired with the logged interval of its length; the offset is the median
of (trace start - monotonic start), and stands only if at least three
pairs agree with it within a millisecond.  The shifted spans then take
the annotations' place in `tracefile.Trace.idle_gaps()`: each gap goes to
the shortest span open at its midpoint.  Roots are left out (a request's
root is open all through it and would name every gap); a request that
was in flight when the slice opened carries no spans, so the seconds it
alone covers are named for what they are, and counted as unnamed.
"""

from __future__ import annotations

import statistics

from benchmark.reducers import node_spans, tracefile

PRE_OPENED = "request_in_flight_at_slice_open"
UNNAMED = ("nothing_due", PRE_OPENED)
AGREE_S, MIN_PAIRS = 1e-3, 3


def clock_offset(host_events, logged) -> float | None:
    """Seconds to add to a monotonic time to get the trace's.  `logged`:
    (monotonic start, seconds) of what the annotations wrapped."""
    offsets = []
    for _, start, dur in host_events:
        best = min(logged, key=lambda iv: abs(iv[1] - dur), default=None)
        if best is not None and abs(best[1] - dur) < AGREE_S:
            offsets.append(start - best[0])
    if len(offsets) < MIN_PAIRS:
        return None
    mid = statistics.median(offsets)
    agree = [o for o in offsets if abs(o - mid) < AGREE_S]
    return statistics.median(agree) if len(agree) >= MIN_PAIRS else None


def idle_by_name(tr, host, gap_order=(), chunk: int = 256) -> dict:
    """`tracefile.Trace.idle_gaps()` of the first device with `host` in
    the annotations' place.  It names a whole gap by what is open at its
    midpoint, and a gap can be a hundred times as long as a span: so a
    busy instant of no length is put at every span's start and end,
    which cuts the gaps there and adds no busy time, and each piece is
    then named by what is open all through it.  It also looks at every
    host event for every gap, and the ring holds thousands: so the busy
    intervals are merged, cut into runs of `chunk` that share their end
    interval (no gap is lost or counted twice), and each run sees only
    the host events that touch it."""
    ops = [(s, s + d) for _, s, d in tr._busy_events(tr.devices[0])]
    first, last = min(s for s, _ in ops), max(e for _, e in ops)
    marks = [(t, t) for _, s, d in host for t in (s, s + d)
             if first < t < last]
    busy = []
    for s, e in sorted(ops + marks):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    acc: dict = {}
    for i in range(0, max(len(busy) - 1, 1), chunk):
        run = busy[i:i + chunk + 1]
        lo, hi = run[0][0], run[-1][1]
        near = [e for e in host if e[1] < hi and e[1] + e[2] > lo]
        part = tracefile.Trace({"d": [("", a, b - a) for a, b in run]}, {},
                               near, tr.window_s, gap_order)
        for name, seconds in part.idle_gaps(10**6):
            acc[name] = acc.get(name, 0.0) + seconds
    return acc


def table(cell, spans) -> dict | None:
    """{span name: idle seconds} over the slice, or None."""
    tr = cell.trace_events
    if (spans is None or tr is None or not tr.devices
            or not tr._busy_events(tr.devices[0])):
        return None
    rows = cell.slice_facts.get("rows", [])
    logged = [(r.sent, r.done - r.sent) for r in rows]
    logged += [(a, b - a) for ivs in cell.spans.values() for a, b in ivs]
    off = clock_offset(tr.host, logged)
    if off is None:
        return None
    host = [(n.name, n.t0 + off, n.seconds) for n in spans.touching
            if n.parent is not None or n.name == "runtime.gc"]
    by_span = idle_by_name(tr, host)
    pre = [(PRE_OPENED, r.sent + off, r.done - r.sent)
           for r in rows if r.sent < spans.t0]
    if pre and by_span.get("nothing_due"):
        # of the seconds under no span, those under such a request
        split = idle_by_name(
            tr, pre + [("named", s, d) for _, s, d in host],
            gap_order=("named", PRE_OPENED))
        by_span[PRE_OPENED] = split.get(PRE_OPENED, 0.0)
        by_span["nothing_due"] -= by_span[PRE_OPENED]
    return by_span


def read(cell, params):
    from benchmark import harness

    spans = node_spans.load(cell)
    by_span = table(cell, spans)
    if not by_span or not cell.trace_events.window_s:
        return None
    harness.say("spans_in_slice", columns=["count", "wall_s", "wall_self_s",
                                           "cpu_self_s"],
                ksamples=spans.work("ksample"), queries=spans.work("query"),
                passes=spans.work("pass"), spans=spans.by_name())
    harness.say("idle_by_span", **by_span)
    return 100.0 * sum(by_span.get(k, 0.0) for k in UNNAMED) \
        / cell.trace_events.window_s
