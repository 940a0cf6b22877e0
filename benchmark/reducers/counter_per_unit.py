"""Window delta of devguard `device.<stage>.calls` counters over the
window's work: per thousand acked samples, or per answered query.  The
counters are read once the generator's threads have joined, so the work
is every acked request they logged, those that finished after the
window's close included (the rate, client_rate.py, leaves those out)."""

from benchmark import stats


def read(cell, params):
    work = stats.work(cell.log.rows, params["per"])
    if not work or not any(c in cell.counters for c in params["counters"]):
        return None
    return sum(cell.counters.get(c, 0) for c in params["counters"]) / work
