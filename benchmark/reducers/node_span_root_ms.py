"""``node_span_ms`` under roots of any name: milliseconds the node spent
under the spans named in `params.spans`, below the slice's roots named
`params.root`, over those roots' own count of the work — the sum of
their tag `params.work_tag` divided by `params.work_unit` (1000 samples
of the `ingest.frame` roots' tag `n`), or the number of roots where no
tag is given (`aggregator.flush` roots: per pass).  `params.self`
(default true) and `params.clock` (`wall` | `cpu`) as `node_span_ms`.
Nothing to read (None) where `node_spans.load` reads nothing, no such
root lies in the slice, or no such span lies under one."""

from benchmark.reducers import node_span_ms, node_spans


def read(cell, params):
    spans = node_spans.load(cell)
    if spans is None:
        return None
    roots = [r for r in spans.roots if r.name == params["root"]]
    tag = params.get("work_tag")
    work = (sum(r.tags.get(tag, 0) for r in roots)
            / params.get("work_unit", 1) if tag else len(roots))
    patterns = params["spans"]
    found, todo = [], [c for r in roots for c in r.children]
    while todo:
        n = todo.pop()
        todo.extend(n.children)
        if node_spans.matches(n.name, patterns):
            found.append(n)
    if not work or not found:
        return None
    cpu = params.get("clock", "wall") == "cpu"
    if params.get("self", True):
        seconds = sum(n.self_cpu_seconds if cpu else n.self_seconds
                      for n in found)
    else:
        seconds = sum(n.cpu if cpu else n.seconds for n in found
                      if not node_span_ms._nested(n, patterns))
    return seconds * 1e3 / work
