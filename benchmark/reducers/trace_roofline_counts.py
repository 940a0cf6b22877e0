"""``trace_roofline`` with the byte counts of the module that
``params["counts"]`` names (``benchmark/<counts>.py``, for one
``roofline_timer``): the least time the chip could take for the bytes
its programs' work must move, over the device time their executions
took in the traced slice.  Nothing to read -> None, never 0."""

import importlib

from benchmark import roofline


def read(cell, params):
    tr = cell.trace_events
    if tr is None:
        return None
    seconds, calls = tr.program_seconds(params["programs"])
    if not calls or seconds <= 0:
        return None
    counts = importlib.import_module("benchmark." + params["counts"])
    nbytes = getattr(counts, params["bytes"])(cell, calls)
    if not nbytes:
        return None
    peak = roofline.peak(cell.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (nbytes / peak) / seconds
