"""Query compute-precision policy.

Prometheus evaluates in float64 and so does this engine: a node always
runs f64.  The f32 policy is the benchmark's control (``--control f32``
of ``prom.dashboard_live`` reads ``hq_rel_err`` 6.5e-7 to 1.0e-6 against
the limit 1e-9: PERF.md section 2) and a subject of
tests/test_query_precision.py; no environment variable or node file
selects it.

The policy narrows the BULK stencil math (temporal kernels, the
histogram-quantile kernel) to f32 when selected, keeping:
- window *bounds* exact (counts of i64 comparisons, unaffected);
- times recentered at the first step before narrowing, so f32 holds
  window-relative nanos (<=hours, ~0.4ms resolution) instead of epoch
  nanos;
- regression stencils (deriv/predict_linear) in f64 always — their
  t^2 prefix sums exceed f32's 2^24 integer range;
- the f64 API surface: blocks upcast on exit, so callers never see the
  narrow dtype.

Accuracy envelope (validated by tests/test_query_precision.py and the
bench promql stage's scalar oracle): ~1e-6 relative per op; through the
rate+histogram_quantile chain the interpolation step AMPLIFIES by the
rank-to-bucket-width ratio — observed ~2e-4, bench-bounded at 5e-3.
Comparison operators are exempt (always f64): narrowing before ==/>/<
flips booleans for f64-distinct operands, which no relative envelope
covers.  Counter values above 2^24 lose integer exactness in f32 —
reset detection on such counters can misfire; deployments with
billion-count counters should stay on f64.

Selection: ``set_compute_dtype("f32"|"f64")``.  The dtype rides the
ARRAYS (engine casts at the fetch boundary; kernels follow
``vals.dtype``), so jitted kernels re-specialize per dtype
automatically — no stale-trace hazard.
"""

from __future__ import annotations

import numpy as np

_VALID = {"f32": np.float32, "f64": np.float64}
_dtype = np.float64


def set_compute_dtype(name: str) -> None:
    global _dtype
    if name not in _VALID:
        raise ValueError(f"query compute dtype must be f32|f64, got {name!r}")
    _dtype = _VALID[name]


def compute_dtype() -> np.dtype:
    return np.dtype(_dtype)
