"""Device time, from the trace's program line, of the jitted programs
whose names match, over the work done inside the traced slice (ms per
thousand samples acked in it, or ms per query answered in it)."""

from benchmark import stats


def read(cell, params):
    if cell.trace_events is None:
        return None
    seconds, calls = cell.trace_events.program_seconds(params["programs"])
    work = stats.work(cell.slice_facts.get("rows", []), params["per"])
    if not calls or not work:
        return None
    return seconds * 1e3 / work
