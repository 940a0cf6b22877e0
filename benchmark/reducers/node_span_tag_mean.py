"""One tag's sum over another's, times `params.scale` (default 1), over
the spans named in `params.spans` under the slice's roots: the mean of
`params.tag` per unit of `params.of`.  With the interpreter-lock probe's
`runtime.gil.probe` roots, `wait_us` over `n` at scale 0.001 is the
milliseconds a probe waited for the interpreter, contended or not: over
the 5 ms switch interval it is the number of threads queued for the
interpreter (one spinning thread reads 5 ms, four read 24).  Spans that
lack either tag are left out; nothing to read (None) where
`node_spans.load` reads nothing, where no span carries the tags (a
program without the probe) or where `params.of` sums to 0.  Printed
before the result line as `{"span_tags": ...}`: the spans counted and
every numeric tag's sum and largest value over them."""

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
    from benchmark import harness

    keys = sorted({k for t in found for k, v in t.items()
                   if isinstance(v, (int, float))})
    harness.say("span_tags", spans=params["spans"], counted=len(found),
                sums={k: sum(t.get(k, 0) for t in found) for k in keys},
                largest={k: max(t.get(k, 0) for t in found) for k in keys})
    return params.get("scale", 1) * sum(t[tag] for t in found) / total
