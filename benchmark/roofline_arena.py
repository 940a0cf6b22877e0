"""Bytes the packed arenas' programs must move, for ``trace_roofline_
arena`` (peaks: ``roofline.peak``).

As ``roofline.py``: each function counts what the ALGORITHM needs for
the calls seen in the traced slice — inputs read once, outputs written
once, at the width they are stored in.  For an ingest that is the
batch's columns and the state of the slots THE BATCH TOUCHES, not the
arena: that ``aggregator/packed.py`` today builds dense segment bounds
over all W x C + C flat slots and rewrites every full-state lane per
call is the finding the share should show, so the share falls as the
arena grows against the batch.  Sample counts are the generator's
(`counter_samples` / `gauge_samples` acked in the slice; every sample of
a frame is another series, so samples = slots touched).
"""

from __future__ import annotations


def _facts(cell) -> dict:
    return cell.slice_facts.get("facts", {})


def counter_ingest_bytes(cell, calls: int) -> float:
    """packed.counter_ingest: per sample the batch columns (flat slot
    i32 + time i64 + value i64 = 20 B) read; the touched slot's state
    (base u64 + sq i64 + minmax u32 + pool index i32 = 24 B) and its
    last_at (i64) read and written: 20 + 2 x 32 = 84 B.  Left out: the
    key sort and its permutation gathers, the segmented scans, the
    dense bounds over the arena, the overflow pool's rows (1 % of the
    slots), the rewrite of untouched slots."""
    return 84.0 * _facts(cell).get("counter_samples", 0)


def gauge_ingest_bytes(cell, calls: int) -> float:
    """packed.gauge_ingest: per sample flat slot i32 + time i64 + value
    f64 = 20 B read (the host-made order key is a second image of the
    value: not counted); the touched slot's seven 8 B lanes (sum,
    sum_sq, count, min/max/last keys, last_time) and its last_at read
    and written: 20 + 2 x 64 = 148 B.  Left out: as counter_ingest."""
    return 148.0 * _facts(cell).get("gauge_samples", 0)


def arena_consume_bytes(cell, calls: int) -> float:
    """packed.counter_consume + gauge_consume, `calls` of them in all,
    half each (a drain consumes both): one window's C slots read (24 B a
    counter slot; 56 B a gauge slot) and the output written (counter:
    (C, 8) f64 lanes + i64 counts = 72 B; gauge: (C, 5) f64 + (C, 4) i64
    = 72 B): C x 112 B per call on average.  Left out: the pool gather,
    the other windows' rows the program reads before it slices, the
    timer arena's consume (another program, empty here)."""
    capacity = cell.facts.get("arena_capacity", 0)
    return calls * capacity * (24 + 72 + 56 + 72) / 2.0
