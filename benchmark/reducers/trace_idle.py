"""Idle share of the device over the traced slice: 1 - union of the
device-operation intervals over the slice's length, in percent."""


def read(cell, params):
    tr = cell.trace_events
    if tr is None or not tr.window_s or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
