"""Device time, from the trace's program line, of the jitted programs
whose names match, in milliseconds per `params.per`: ``call`` (one
execution of such a program) or the name of one of the generator's
counts over the traced slice (``flushes``: per flush tick in it).
``trace_program_time`` divides by the work of the slice's requests;
this by what a drain is counted in."""


def read(cell, params):
    if cell.trace_events is None:
        return None
    seconds, calls = cell.trace_events.program_seconds(params["programs"])
    per = (calls if params["per"] == "call" else
           cell.slice_facts.get("facts", {}).get(params["per"], 0))
    if not calls or not per:
        return None
    return seconds * 1e3 / per
