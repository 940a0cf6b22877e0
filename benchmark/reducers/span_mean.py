"""Mean duration, in ms, of the benchmark's spans of one name that ended
inside the window (host clock around the call into the layer)."""


def read(cell, params):
    t0, t1 = cell.window
    d = [b - a for a, b in cell.spans.get(params["span"], []) if t0 <= a and b <= t1]
    return sum(d) / len(d) * 1e3 if d else None
