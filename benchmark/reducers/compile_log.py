"""Set-up's compilation, from the compile log of the node's tracer
(`cell.asm.tracer.compile_log()`, `m3_tpu/instrument/tracing.py`: one
row per program the process compiled or read from the persistent cache,
from `jax.monitoring`'s events).  Over the rows that ENDED before the
window began, `params.read` is

- `seconds`: the seconds of all four phases (trace, lower, backend
  compile, cache read) — what of `setup_s` the compiler took;
- `programs`: the rows counted;
- `cache_hit_pct`: 100 x rows the cache answered over rows it answered
  or was written for (`hit` / (`hit` + `miss`); a row whose `cache` is
  `off` took no part).

The log begins where `run_node` / `run_aggregator` installs the tracer:
programs the process compiled before that (the data set's, where it
uses JAX; the harness's own) are in tracewatch's count on the `warm`
line and not here.  Needs no traced slice.  Nothing to read (None) on a
program whose tracer keeps no such log (the parent of the PR that added
it), where the log pushed rows out, where no row ended before the
window, or for `cache_hit_pct` where the cache took part in none.
Reading `seconds` also prints, before the result line, the free-form
`{"compile_log": ...}`: set-up's programs, seconds by phase, the
cache's verdicts and what it says it saved, the five dearest programs
by name, and the rows that ended inside the window (expected none)."""

PHASES = ("trace_s", "lower_s", "compile_s", "cache_read_s")


def _account(rows, in_window) -> dict:
    dearest = sorted(rows, key=lambda r: r.seconds, reverse=True)[:5]
    return {
        "programs": len(rows),
        "seconds": {p: sum(getattr(r, p) for r in rows) for p in PHASES},
        "cache": {v: sum(r.cache == v for r in rows)
                  for v in ("hit", "miss", "off")},
        "saved_s": sum(r.saved_s for r in rows),
        "dearest": [[r.fn, r.seconds, r.cache] for r in dearest],
        "in_window": [[r.fn, r.seconds, r.cache] for r in in_window],
    }


def read(cell, params):
    tracer = getattr(cell.asm, "tracer", None)
    log = getattr(tracer, "compile_log", None)
    if log is None or cell.window is None or tracer.compiles_dropped:
        return None
    t0, t1 = cell.window
    log = log()
    rows = [r for r in log if r.end_ns / 1e9 <= t0]
    if not rows:
        return None
    what = params["read"]
    if what == "seconds":
        from benchmark import harness

        harness.say("compile_log", **_account(rows, [
            r for r in log if t0 < r.end_ns / 1e9 <= (t1 or t0)]))
        return sum(r.seconds for r in rows)
    if what == "programs":
        return len(rows)
    if what == "cache_hit_pct":
        hits = sum(r.cache == "hit" for r in rows)
        asked = hits + sum(r.cache == "miss" for r in rows)
        return 100.0 * hits / asked if asked else None
    raise ValueError(f"compile_log: nothing to read as {what!r}")
