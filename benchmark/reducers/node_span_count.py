"""How many spans named in `params.spans` stand under the slice's roots
that count `params.per`'s work, over that work (as ``node_span_ms``):
the range function's dispatched row blocks a query, for one.  Nothing
to read (None) where `node_spans.load` reads nothing or no such span
exists (a program that does not open it)."""

from benchmark.reducers import node_spans


def read(cell, params):
    spans = node_spans.load(cell)
    if spans is None:
        return None
    per = params["per"]
    work = spans.work(per)
    found = sum(1 for n in spans.under_roots({node_spans.ROOT_OF[per]})
                if node_spans.matches(n.name, params["spans"]))
    if not work or not found:
        return None
    return found / work
