"""How the tests that hold BENCHMARK.json's per-layer entries find one:
by its name, whether it stands on its own or has been folded into the
first entry of its group.

A copy is an entry that reads what an earlier entry reads (the same
reader file but its `what`, `moves`, `layer`, `unit`, `better` and
`source`: ``benchmark/selftest.py`` ``_copies``).  Folding one deletes
the copy and its reader file and appends the copy's cell to the
`workloads` of its group's first entry; these helpers accept the list
before that fold and after it, entry by entry, and nothing else.
"""

from __future__ import annotations

# a copy -> the first entry of its group (benchmark/selftest.py
# `_NOT_YET_FOLDED` names the same 39)
FOLDED_INTO = {
    "device_idle_pct.agg": "device_idle_pct.load",
    "idle_unnamed_pct.agg": "idle_unnamed_pct.load",
    "gc_pause_pct.agg": "gc_pause_pct.load",
    "window_compiles.agg": "window_compiles.load",
    "frame_decode_ms_per_ksample.timer": "frame_decode_ms_per_ksample.agg",
    "resolve_ms_per_ksample.timer": "resolve_ms_per_ksample.agg",
    "add_ms_per_ksample.timer": "add_ms_per_ksample.agg",
    "lock_wait_ms_per_ksample.timer": "lock_wait_ms_per_ksample.agg",
    "dispatch_ms_per_ksample.timer": "dispatch_ms_per_ksample.agg",
    "flush_emit_ms_per_pass.timer": "flush_emit_ms_per_pass.agg",
    "consume_ms_per_pass.timer": "consume_ms_per_pass.agg",
    "arena_calls_per_ksample.timer": "arena_calls_per_ksample.agg",
    "frame_unnamed_pct.timer": "frame_unnamed_pct.agg",
    "device_idle_pct.timer": "device_idle_pct.load",
    "idle_unnamed_pct.timer": "idle_unnamed_pct.load",
    "gc_pause_pct.timer": "gc_pause_pct.load",
    "window_compiles.timer": "window_compiles.load",
    "query_req_p50_ms.flushed": "query_req_p50_ms",
    "query_p90_ms.flushed": "query_p90_ms",
    "query_device_ms_per_query.flushed": "query_device_ms_per_query",
    "rate_family_roofline.flushed": "rate_family_roofline",
    "eval_ms_per_query.flushed": "eval_ms_per_query",
    "series_read_ms_per_query.flushed": "series_read_ms_per_query",
    "lock_wait_ms_per_query.flushed": "lock_wait_ms_per_query",
    "index_query_ms_per_query.flushed": "index_query_ms_per_query",
    "render_ms_per_query.flushed": "render_ms_per_query",
    "query_unnamed_pct.flushed": "query_unnamed_pct",
    "read_columnar_pct.flushed": "read_columnar_pct",
    "device_idle_pct.flushed": "device_idle_pct.query",
    "idle_unnamed_pct.flushed": "idle_unnamed_pct.query",
    "gc_pause_pct.flushed": "gc_pause_pct.query",
    "window_compiles.flushed": "window_compiles.query",
    "gil_contended_pct.agg": "gil_contended_pct.load",
    "gil_contended_pct.timer": "gil_contended_pct.load",
    "gil_contended_pct.flushed": "gil_contended_pct.query",
    "gil_wait_ms.agg": "gil_wait_ms.load",
    "gil_wait_ms.timer": "gil_wait_ms.load",
    "gil_wait_ms.flushed": "gil_wait_ms.query",
    "read_locked_ms_per_query.flushed": "read_locked_ms_per_query",
}

# the one cell a copy is read in, by its name's suffix
_COPY_CELL = {"agg": "m3agg.untimed_rollup", "timer": "m3agg.timer_quantile",
              "flushed": "prom.dashboard_flushed"}


def copy_cell(name: str) -> str:
    return _COPY_CELL[name.rsplit(".", 1)[1]]


def entry(bench: dict, name: str) -> dict:
    """The entry through which `name` is read: the entry of that name,
    or, once the copy is folded, its group's first entry."""
    by = {m["name"]: m for m in bench["per_layer"]}
    return by[name] if name in by else by[FOLDED_INTO[name]]


def folded(bench: dict, name: str) -> bool:
    return name in FOLDED_INTO and all(
        m["name"] != name for m in bench["per_layer"])


def check_workloads(bench: dict, name: str, own: list) -> dict:
    """`name` is read in exactly the cells `own`: an entry of that name
    lists them in that order, followed by the cell of each copy folded
    into it; a folded copy's cells stand in its group's first entry.
    -> the entry that reads it."""
    m = entry(bench, name)
    if folded(bench, name):
        assert all(c in m["workloads"] for c in own), (name, m["workloads"])
        return m
    extra = sorted(copy_cell(c) for c, first in FOLDED_INTO.items()
                   if first == name and folded(bench, c))
    assert m["workloads"][:len(own)] == own, (name, m["workloads"])
    assert sorted(m["workloads"][len(own):]) == extra, (name, m["workloads"])
    return m
