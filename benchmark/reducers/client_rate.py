"""All acked work of a kind that COMPLETED inside the window, over the
window's time: `units` is "samples" (the samples the acked requests
carried) or "requests".  A request sent in the window that finishes after
it closed counts in the percentiles, not here."""

from benchmark import stats


def read(cell, params):
    t1 = cell.window[1]
    rows = [r for r in cell.log.of(params["kind"]) if r.ok and r.done <= t1]
    if not rows:
        return None
    work = sum(r.units for r in rows) if params["units"] == "samples" else len(rows)
    return stats.rate(work, t1 - cell.window[0])
