"""What the requests spent under no child span: 100 x the self time of
the slice's roots named in `params.roots` over their duration.  A high
reading means the span table has a hole, not that the node is slow."""

from benchmark.reducers import node_spans


def read(cell, params):
    spans = node_spans.load(cell)
    if spans is None:
        return None
    roots = [r for r in spans.roots if r.name in params["roots"]]
    total = sum(r.seconds for r in roots)
    if not total:
        return None
    return 100.0 * sum(r.self_seconds for r in roots) / total
