"""Share of the traced slice during which a span named in `params.spans`
was open on any thread: 100 x the union of their intervals, cut to the
slice, over the slice's length (the collector's `runtime.gc` holds the
GIL, so its share is taken from every thread of the node at once)."""

from benchmark.reducers import node_spans
from benchmark.reducers.tracefile import _union


def read(cell, params):
    spans = node_spans.load(cell)
    if spans is None:
        return None
    t0, t1 = spans.t0, spans.t1
    return 100.0 * _union(
        (max(n.t0, t0), min(n.t1, t1)) for n in spans.touching
        if node_spans.matches(n.name, params["spans"])) / (t1 - t0)
