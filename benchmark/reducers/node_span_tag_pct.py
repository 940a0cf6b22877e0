"""One tag as a share of another, in percent, over the spans named in
`params.spans` under the slice's roots: 100 x the sum of `params.tag`
over the sum of `params.of` (the series of every `api.write.decode`
that the series cache already knew, over all it was handed).  Spans
that lack either tag are left out; nothing to read (None) where
`node_spans.load` reads nothing, where no span carries the tags (a
program that does not set them) or where `params.of` sums to 0."""

from benchmark.reducers import node_spans


def read(cell, params):
    spans = node_spans.load(cell)
    if spans is None:
        return None
    tag, of = params["tag"], params["of"]
    found = [n.tags for n in spans.under_roots()
             if node_spans.matches(n.name, params["spans"])
             and tag in n.tags and of in n.tags]
    total = sum(t[of] for t in found)
    if not total:
        return None
    return 100.0 * sum(t[tag] for t in found) / total
