"""Bytes a program's shapes must move, and the table of peaks.

Each function counts the bytes the ALGORITHM needs for the calls seen in
the traced slice: every input read once and every output written once
at the width it is stored in.  It leaves out what an implementation adds
(sort passes, gathers through permutations, temporaries, padding), so a
share of the roofline says how far the program as written is from one
pass over its data.
"""

from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in peaks.json: "
                       "add it with its source, there is no default")
    return table[device_kind]


def buffer_append_bytes(cell, calls: int) -> float:
    """storage/buffer.py buffer_append over the slice: per appended row
    the batch columns are read (window i32 + slot i32 + ts i64 + value
    bits u64 = 24 B) and the ring columns written (slot i32 + ts i64 +
    value u64 = 20 B); per call the ring's write heads are read and
    written (2 x 8 B per ring row, two rows).  Rows = raw samples acked
    in the slice + aggregated values the maintenance passes wrote in it.
    Left out: the (key, index) sort, the permutation gathers, and the
    ring itself (donated, updated in place)."""
    facts = cell.slice_facts.get("facts", {})
    rows = facts.get("samples_acked", 0) + facts.get("agg_values", 0)
    if not rows:
        return 0.0
    return 44.0 * rows + 32.0 * calls


def rate_family_bytes(cell, calls: int) -> float:
    """query/temporal.py rate_family: per call the (S, P) timestamps
    (i64) and values (f64) are read once and the (S, steps) rates (f64)
    written once; S, P and steps are the generator's mean panel shape
    (`rate_shape`).  Left out: the window-bound searches, the reset
    cumsum's second pass, the gathers at the window ends."""
    shape = cell.facts.get("rate_shape")
    if not shape:
        return 0.0
    s, p, steps = shape
    return calls * (16.0 * s * p + 8.0 * s * steps + 8.0 * steps)
