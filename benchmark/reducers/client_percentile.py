"""p-th percentile, in ms, of send -> completion over ALL requests of a
kind sent in the window (client clock)."""

from benchmark import stats


def read(cell, params):
    return stats.latency_ms(cell.log.of(params["kind"]), params["p"])
