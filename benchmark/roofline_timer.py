"""Bytes the packed timer arena's programs must move, for
``trace_roofline_counts`` (peaks: ``roofline.peak``).

As ``roofline.py`` and ``roofline_arena.py``: each function counts what
the WORK needs for the calls seen in the traced slice — inputs read
once, outputs written once, at the width they are stored in — not what
``aggregator/packed.py`` does to get there, so a share says how far the
program as written is from one pass over its data.
"""

from __future__ import annotations


def timer_ingest_bytes(cell, calls: int) -> float:
    """packed.timer_ingest: per sample the batch columns read (window
    i32 + slot i32 + value f64 + time i64 = 24 B), the packed sample
    word written (u64, 8 B), the id's last_at read and written (2 x
    8 B): 48 B, times the samples acked in the slice (every acked
    sample is a timer sample).  Left out: the append plan's (W, N)
    one-hot cumsum, the f64 -> orderable-f32 conversion, anything the
    scatter into the donated (W, S) buffer costs beyond its N words."""
    return 48.0 * cell.slice_facts.get("facts", {}).get("samples_acked", 0)


def timer_consume_bytes(cell, calls: int) -> float:
    """packed.timer_consume, per call: one window's S buffered words
    read (8 S; S = `timer_sample_capacity`, empty sentinels included:
    the program cannot know which are), and per slot the output written
    ((C, 11) f64 lanes + i64 counts: C x 96 B).  Left out: the sort's
    passes over the S words (log2 S of them for a merge sort), the
    moments' two f64 segment sums over S, the two binary searches of C
    queries into S, the rank gathers, the other window's words."""
    s = cell.facts.get("timer_sample_capacity", 0)
    c = cell.facts.get("arena_capacity", 0)
    return calls * (8.0 * s + c * (11 * 8 + 8))
