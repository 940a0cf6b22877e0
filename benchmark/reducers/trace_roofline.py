"""A program's share of its roofline: the least time the chip could take
for the bytes its shapes must move (benchmark/roofline.py, peaks from
benchmark/peaks.json) over the device time its executions took in the
traced slice.  These programs are integer sort/gather/select work: the
bound is HBM bytes, not FLOPs.  Nothing to read -> None, never 0."""

from benchmark import roofline


def read(cell, params):
    tr = cell.trace_events
    if tr is None:
        return None
    seconds, calls = tr.program_seconds(params["programs"])
    if not calls or seconds <= 0:
        return None
    nbytes = getattr(roofline, params["bytes"])(cell, calls)
    if not nbytes:
        return None
    peak = roofline.peak(cell.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / seconds
