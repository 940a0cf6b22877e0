"""Bytes the batch M3TSZ decode must move, for ``trace_roofline_counts``
(peaks: ``roofline.peak``).

As ``roofline.py``: what the WORK needs for the calls seen in the traced
slice, from the shapes the program handed the device, not what
``encoding/m3tsz_jax.py`` does to get there.  The shapes are read from
the tags of the node's ``db.read.fileset`` spans (``words``, ``rows``,
``steps``: the padded word array, the rows and the scan length of a
fetch's decode), one span a fetch and block and — under the program's
4,096 rows a call, which no fetch of a cell reaches — one call a span.
A program without those spans or tags (before PR 33) gives nothing to
read.
"""

from __future__ import annotations

from benchmark.reducers import node_spans


def decode_bytes(cell, calls: int) -> float:
    """_decode_batch_device, per call: the padded (rows, words) u64
    stream array read once (8 B a word); the outputs written once,
    rows x steps x (timestamp i64 + payload u64 + meta u8 = 17 B); and,
    for the gather tail that ``chains="auto"`` resolves to on a TPU,
    the four (steps, rows) lane tables of phase 1 (ts_off i32, p1 u32,
    val_off i32, p2 u32 = 16 B) written by the scan and read once by
    phase 2.  Left out: the scan's carry (a dozen (rows,) lanes a
    step), the 2^18-entry control table, the 4-word register-file
    gather a step, phase 2's field gathers through the stream words and
    its chain scan's second pass, the flags."""
    spans = node_spans.load(cell)
    if spans is None:
        return 0.0
    found = [n.tags for n in spans.touching
             if n.name == "db.read.fileset" and "rows" in n.tags]
    if not found:
        return 0.0
    per_call = sum(8.0 * t["words"] + t["rows"] * t["steps"] * (17.0 + 32.0)
                   for t in found) / len(found)
    return calls * per_call
