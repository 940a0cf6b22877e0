"""Milliseconds the node spent under the spans named in `params.spans`
(self time, or the whole span with `params.self` false; a whole span
nested in another that is counted is not counted twice), over the work
the slice's own roots counted (`params.per`: thousands of samples of the
`api.write` roots, `api.queryRange` roots, or `mediator.runOnce` roots),
the spans taken only below the roots that count that work: a wait that
opens under writes, queries and passes alike reads each path's own.
`params.clock`: `wall` (the default; what a wait costs) or `cpu` (the
span's thread's CPU clock: what Python work costs, free of the wait for
the GIL that the wall time of every span of a busy node includes).
Clocks of the node (benchmark/reducers/node_spans.py)."""

from benchmark.reducers import node_spans


def read(cell, params):
    spans = node_spans.load(cell)
    if spans is None:
        return None
    per = params["per"]
    work = spans.work(per)
    found = [n for n in spans.under_roots({node_spans.ROOT_OF[per]})
             if node_spans.matches(n.name, params["spans"])]
    if not work or not found:
        return None
    patterns = params["spans"]
    cpu = params.get("clock", "wall") == "cpu"
    if params.get("self", True):
        seconds = sum(n.self_cpu_seconds if cpu else n.self_seconds
                      for n in found)
    else:
        seconds = sum(n.cpu if cpu else n.seconds
                      for n in found if not _nested(n, patterns))
    return seconds * 1e3 / work


def _nested(node, patterns) -> bool:
    p = node.parent
    while p is not None:
        if node_spans.matches(p.name, patterns):
            return True
        p = p.parent
    return False
