"""Bytes a range function's calls must move over the whole fleet, for
``trace_roofline_counts`` (peaks: ``roofline.peak``).

As ``roofline.py``'s ``rate_family_bytes``: per call the (rows, points)
timestamps (i64) and values (f64) read once and the (rows, steps) rates
(f64) written once, 16 P + 8 T bytes a row, but with the shapes read
from the tags of the node's ``query.eval.block`` spans (one a dispatched
call: ``rows``, the block's REAL rows, ``points``, ``steps``) instead of
a panel's mean shape.  So the work reads the same whether one call or
several row blocks carry it: the padding rows that fill a last block
move bytes the query did not ask for and are left out.  A program
without those spans or tags gives nothing to read.
"""

from __future__ import annotations

from benchmark.reducers import node_spans


def rate_family_bytes(cell, calls: int) -> float:
    spans = node_spans.load(cell)
    if spans is None:
        return 0.0
    found = [n.tags for n in spans.touching
             if n.name == "query.eval.block" and "rows" in n.tags]
    if not found:
        return 0.0
    per_call = sum(t["rows"] * (16.0 * t["points"] + 8.0 * t["steps"])
                   for t in found) / len(found)
    return calls * per_call
