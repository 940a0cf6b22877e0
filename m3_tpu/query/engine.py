"""Query engine: parse → evaluate → step-aligned block.

Equivalent of `src/query/executor` (`engine.ExecuteExpr` `engine.go:111`:
parse → logical plan → DAG of transforms pulling blocks).  The evaluator
walks the AST depth-first; leaves fetch raw series through a Storage
interface (the fanout/m3db adapter seam, `query/storage/fanout`), and
every interior node is a whole-block array op (`temporal.py`,
`functions.py`) instead of a per-step iterator chain.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Protocol

import jax.numpy as jnp
import numpy as np

from m3_tpu.instrument.tracing import NOOP_TRACER, Tracepoint
from m3_tpu.query import functions as fn
from m3_tpu.query import temporal as tp
from m3_tpu.x import deadline as xdeadline
from m3_tpu.query.block import Block, PaddedBlock, RawBlock, SeriesMeta
from m3_tpu.query.promql import (
    Subquery,
    Aggregation, BinaryOp, Call, Expr, LabelMatcher, NumberLiteral,
    StringLiteral, Unary, VectorSelector, parse,
)

LOOKBACK_NANOS = 5 * 60 * 10**9  # Prometheus default lookback delta

# Rows a range function's program takes in one call.  A selector that
# fetches more series is evaluated as ceil(S / R) calls of one (R, P, T)
# program, the last block filled with empty rows (reference
# functions/temporal/base.go batchProcess: series in batches), so a
# fleet whose series count moves compiles nothing new until its block
# count does.  Chosen by a sweep on a v5e at the fleet's shape
# (PERF.md section 6): 4,096 rows beat 8,192 and 16,384 there.
_RANGE_BLOCK_ROWS = 4096

_TEMPORAL_SUM = {"sum_over_time", "count_over_time", "avg_over_time",
                 "stddev_over_time", "stdvar_over_time"}
_TEMPORAL_MINMAXQ = {"min_over_time", "max_over_time", "quantile_over_time"}
_TEMPORAL_RATE = {"rate", "increase", "delta", "irate", "idelta"}
_TEMPORAL_REG = {"deriv", "predict_linear"}
_TEMPORAL_TRANS = {"resets", "changes"}
_TEMPORAL_ALL = (_TEMPORAL_SUM | _TEMPORAL_MINMAXQ | _TEMPORAL_RATE
                 | _TEMPORAL_REG | _TEMPORAL_TRANS
                 | {"last_over_time", "present_over_time",
                    "absent_over_time", "holt_winters"})


class Storage(Protocol):
    def fetch_raw(self, name: bytes | None, matchers: tuple[LabelMatcher, ...],
                  start_nanos: int, end_nanos: int) -> RawBlock: ...


@dataclass
class _Scalar:
    """A PromQL scalar: a float, or a per-step (T,) array (scalar(),
    time()).  Binary ops broadcast arrays across the series axis."""

    value: float | np.ndarray


class Engine:
    """reference `executor/engine.go:47 NewEngine`."""

    def __init__(self, storage: Storage, lookback_nanos: int = LOOKBACK_NANOS,
                 tracer=None):
        self.storage = storage
        self.lookback = lookback_nanos
        self.tracer = tracer if tracer is not None else NOOP_TRACER
        # Per-query (start, end) for @ start()/end() resolution: they
        # ALWAYS refer to the top-level query parameters (Prometheus),
        # never an inner subquery grid.  Thread-local because one
        # engine serves concurrent HTTP requests.
        self._query_bounds = threading.local()

    # -- public API --------------------------------------------------------

    def execute_range(self, query: str, start_nanos: int, end_nanos: int,
                      step_nanos: int, deadline=None) -> Block:
        """PromQL range query (reference api/v1 native read →
        ExecuteExpr).  ``deadline`` (an ``x/deadline.Deadline``) bounds
        the whole evaluation: checked between eval nodes and inside
        per-step loops, threaded to storage fetches through the context
        binding (callers that already bound one can omit it)."""
        with self.tracer.start_span(Tracepoint.ENGINE_EXECUTE,
                                    {"query": query}):
            with xdeadline.bind(deadline if deadline is not None
                                else xdeadline.current()):
                return self._execute_range(query, start_nanos, end_nanos,
                                           step_nanos)

    def _execute_range(self, query: str, start_nanos: int, end_nanos: int,
                       step_nanos: int) -> Block:
        ast = parse(query)
        steps = np.arange(start_nanos, end_nanos + 1, step_nanos, dtype=np.int64)
        self._query_bounds.range = (start_nanos, end_nanos)
        try:
            out = self._eval(ast, steps)
        finally:
            del self._query_bounds.range
        if isinstance(out, _Scalar):
            vals = np.broadcast_to(
                np.asarray(out.value, np.float64), (1, len(steps))
            ).copy()
            return Block(steps, vals, [SeriesMeta(())])
        # The ONE device->host sync: blocks stay device-resident between
        # pipeline stages (see Block docstring) and leave the engine as
        # host float64.
        return out.materialized()

    def execute_instant(self, query: str, time_nanos: int,
                        deadline=None) -> Block:
        return self.execute_range(query, time_nanos, time_nanos, 10**9,
                                  deadline=deadline)

    # -- evaluation --------------------------------------------------------

    def _eval(self, e: Expr, steps: np.ndarray):
        # Cooperative cancellation point between eval nodes: a deep AST
        # over a spent budget stops HERE, not after the next expensive
        # kernel (the per-step loops below check too).
        xdeadline.check_current("query eval")
        if isinstance(e, NumberLiteral):
            return _Scalar(e.value)
        if isinstance(e, StringLiteral):
            return e.value
        if isinstance(e, Unary):
            v = self._eval(e.expr, steps)
            if e.op == "+":
                return v
            if isinstance(v, _Scalar):
                return _Scalar(-v.value)
            return v.with_values(-v.values)
        if isinstance(e, VectorSelector):
            if e.range_nanos:
                raise ValueError("range selector outside temporal function")
            return self._eval_instant_selector(e, steps)
        if isinstance(e, Call):
            with self.tracer.start_span(Tracepoint.EVAL_CALL, {"fn": e.func}):
                return self._eval_call(e, steps)
        if isinstance(e, Aggregation):
            # topk/bottomk dispatch the mask kernel and then a select;
            # every other operator is one jitted program on a host plan
            with self.tracer.start_span(
                    Tracepoint.EVAL_AGGREGATION,
                    {"op": e.op, "n": 1,
                     "one_program": int(e.op not in ("topk", "bottomk"))}):
                return self._eval_aggregation(e, steps)
        if isinstance(e, BinaryOp):
            return self._eval_binary(e, steps)
        raise ValueError(f"cannot evaluate {e}")

    def _resolve_at(self, node, steps: np.ndarray) -> int | None:
        """The @ modifier's fixed evaluation time, or None.  start()/
        end() resolve to the TOP-LEVEL query range parameters — even
        inside a subquery, whose inner grid is wider and step-aligned —
        and to the true end timestamp even when the range is not a
        step multiple (Prometheus @ semantics)."""
        if node.at_edge in ("start", "end"):
            bounds = getattr(self._query_bounds, "range",
                             (int(steps[0]), int(steps[-1])))
            return bounds[0] if node.at_edge == "start" else bounds[1]
        return node.at_nanos

    def _fetch(self, sel: VectorSelector, steps: np.ndarray, range_nanos: int):
        at = self._resolve_at(sel, steps)
        if at is not None:
            # pinned evaluation computes ONE column; callers broadcast
            # the constant result across the output steps
            eval_steps = np.asarray([at - sel.offset_nanos], np.int64)
        else:
            eval_steps = steps - sel.offset_nanos
        start = int(eval_steps[0]) - range_nanos
        # +1: storage reads are end-EXCLUSIVE, but a sample exactly at
        # the final evaluation step belongs to it (Prometheus windows
        # are (t-range, t] — found by the comparator harness, which
        # caught the last step evaluating with the previous sample).
        end = int(eval_steps[-1]) + 1
        raw = self.storage.fetch_raw(sel.name, sel.matchers, start, end)
        return raw, eval_steps

    def _eval_subquery(self, sub: Subquery, steps: np.ndarray):
        """Evaluate ``expr[range:step]``: run the inner INSTANT
        expression on the subquery's absolute-aligned step grid, then
        hand the samples to the temporal kernels exactly like fetched
        raw datapoints (Prometheus subquery semantics: inner steps are
        aligned to multiples of the subquery step; NaN results are
        stale and yield no sample)."""
        step = sub.step_nanos
        if step == 0:
            # Prometheus uses the global evaluation interval as the
            # default resolution; the closest engine-native analogue is
            # the outer query's step, falling back to 60s for
            # single-step (instant) evaluations.  (Resolved BEFORE any
            # @ pinning collapses the grid to a constant.)
            step = (int(steps[1] - steps[0]) if len(steps) > 1
                    else 60 * 10**9)
        at = self._resolve_at(sub, steps)
        if at is not None:
            steps = np.asarray([at], np.int64)  # single pinned column
        end = int(steps[-1]) - sub.offset_nanos
        start = int(steps[0]) - sub.range_nanos - sub.offset_nanos
        first = -(-start // step) * step  # absolute alignment (ceil)
        inner = np.arange(first, end + 1, step, dtype=np.int64)
        if len(inner) == 0:
            inner = np.asarray([end], np.int64)
        b = self._eval(sub.expr, inner)
        if isinstance(b, _Scalar):
            # scalar-valued inner exprs (time(), literals) broadcast to
            # one anonymous series over the inner grid
            vals = np.broadcast_to(
                np.asarray(b.value, np.float64), (len(inner),))
            b = Block(inner, vals[None, :].copy(), [SeriesMeta(())])
        bvals = np.asarray(b.values)  # one sync, not one per row
        pts = []
        for i, row in enumerate(bvals):
            if i % 256 == 0:  # per-row loop over the inner grid
                xdeadline.check_current("subquery rows")
            pts.append([(int(t), float(v)) for t, v in zip(inner, row)
                        if not math.isnan(v)])
        raw = RawBlock.from_lists(pts, b.series)
        return raw, steps - sub.offset_nanos

    def _eval_instant_selector(self, sel: VectorSelector, steps: np.ndarray) -> Block:
        raw, eval_steps = self._fetch(sel, steps, self.lookback)
        # a pure selection: host numpy, bit-exact (see tp.last_over_time)
        vals = tp.last_over_time(raw.ts, raw.values, eval_steps,
                                 self.lookback)
        if vals.shape[1] != len(steps):  # @-pinned single column
            vals = np.repeat(vals, len(steps), axis=1)
        return Block(steps, vals, raw.series)

    def _eval_call(self, call: Call, steps: np.ndarray):
        f = call.func
        if f in _TEMPORAL_ALL:
            q = 0.0
            sel_arg = call.args[-1]
            extra = 0.0
            if f == "quantile_over_time":
                q = self._scalar_arg(call.args[0], steps)
                sel_arg = call.args[1]
            elif f == "predict_linear":
                sel_arg = call.args[0]
                extra = self._scalar_arg(call.args[1], steps)
            elif f == "holt_winters":
                sel_arg = call.args[0]
            if isinstance(sel_arg, Subquery):
                raw, eval_steps = self._eval_subquery(sel_arg, steps)
            elif (not isinstance(sel_arg, VectorSelector)
                    or sel_arg.range_nanos == 0):
                raise ValueError(
                    f"{f} requires a range selector or subquery")
            else:
                raw, eval_steps = self._fetch(sel_arg, steps,
                                              sel_arg.range_nanos)
            if len(raw.series) == 0:
                # No matched series: an empty instant vector, or for
                # absent_over_time a single empty-labelled series of 1s
                # (Prometheus semantics).  Must short-circuit BEFORE
                # the jitted stencils — a 0-row window gather cannot
                # even shape its reshape.
                if f == "absent_over_time":
                    return Block(steps, np.ones((1, len(steps))),
                                 [SeriesMeta(())])
                return Block(steps, np.empty((0, len(steps)),
                                             np.float64), [])
            if f == "last_over_time":
                # a pure selection: host numpy, bit-exact whatever the
                # device's f64 is (see tp.last_over_time)
                out = tp.last_over_time(raw.ts, np.nan_to_num(raw.values),
                                        eval_steps, sel_arg.range_nanos)
                if out.shape[1] != len(steps):  # @-pinned single column
                    out = np.repeat(out, len(steps), axis=1)
                return Block(steps, out, [m.drop_name() for m in raw.series])
            from m3_tpu.query import precision

            narrow = precision.compute_dtype() == np.float32
            # The policy dtype rides the value array: jitted stencils
            # follow vals.dtype, so f32 selection re-specializes every
            # kernel without any static plumbing (query/precision.py).
            # The rate family is the exception — it must difference
            # cumulative counters in f64 and narrows internally via its
            # static `narrow` flag — as is regression (f64-pinned).
            narrow_vals = f not in _TEMPORAL_RATE and f not in _TEMPORAL_REG
            vals_dtype = precision.compute_dtype() if narrow_vals else np.float64
            st_j = jnp.asarray(eval_steps)
            rng = sel_arg.range_nanos
            if f in _TEMPORAL_SUM:
                def family(ts_j, vals_j):
                    return tp.sum_count_family(ts_j, vals_j, st_j, rng, f)
            elif f in _TEMPORAL_MINMAXQ:
                W = tp.window_pad_for(raw.counts, raw.ts, rng)

                def family(ts_j, vals_j):
                    return tp.minmax_quantile_family(ts_j, vals_j, st_j, rng,
                                                     f, W, q)
            elif f in _TEMPORAL_RATE:
                def family(ts_j, vals_j):
                    return tp.rate_family(ts_j, vals_j, st_j, rng, f,
                                          narrow=narrow)
            elif f in _TEMPORAL_REG:
                def family(ts_j, vals_j):
                    return tp.regression_family(ts_j, vals_j, st_j, rng, f,
                                                extra)
            elif f in _TEMPORAL_TRANS:
                def family(ts_j, vals_j):
                    return tp.transitions_family(ts_j, vals_j, st_j, rng, f)
            elif f == "holt_winters":
                sfv = float(self._scalar_arg(call.args[1], steps))
                tfv = float(self._scalar_arg(call.args[2], steps))
                # Prometheus funcHoltWinters: sf in (0, 1), tf in (0, 1]
                if not (0.0 < sfv < 1.0) or not (0.0 < tfv <= 1.0):
                    raise ValueError(
                        "holt_winters smoothing factor must be in (0, 1) "
                        "and trend factor in (0, 1]")
                W = max(tp.window_pad_for(raw.counts, raw.ts, rng), 2)

                def family(ts_j, vals_j):
                    return tp.holt_winters(ts_j, vals_j, st_j, rng, W, sfv,
                                           tfv)
            else:  # absent_over_time, present_over_time: counts
                def family(ts_j, vals_j):
                    return tp.sum_count_family(ts_j, vals_j, st_j, rng,
                                               "count_over_time")
            out = self._range_rows(raw, vals_dtype, family)
            if f == "absent_over_time":
                # 1 for every step where NO matched series has samples
                # in the window (padding rows count nothing)
                cnt = np.asarray(out)
                any_present = (~np.isnan(cnt) & (cnt > 0)).any(axis=0)
                vals_out = np.where(any_present, np.nan, 1.0)[None, :]
                if vals_out.shape[1] != len(steps):  # @-pinned
                    vals_out = np.broadcast_to(
                        vals_out, (1, len(steps))).copy()
                return Block(steps, vals_out, [SeriesMeta(())])
            if f == "present_over_time":
                out = jnp.where(jnp.isnan(out), out, jnp.minimum(out, 1.0))
            metas = [m.drop_name() for m in raw.series]
            # Blocks stay f64 whatever the compute policy — downstream
            # code sees one dtype.  The cast happens ON DEVICE; the
            # block leaves the engine device-resident so a following
            # stage (histogram_quantile, aggregation) consumes it
            # without a host round-trip.
            out = out.astype(jnp.float64)
            if out.ndim == 2 and out.shape[1] != len(steps):
                # @-pinned: one computed column broadcast across steps
                out = jnp.broadcast_to(out, (out.shape[0], len(steps)))
            if out.shape[0] > len(metas):
                return PaddedBlock(steps, out, metas)
            return Block(steps, out, metas)

        if f == "histogram_quantile":
            q = self._scalar_arg(call.args[0], steps)
            block = self._eval(call.args[1], steps)
            return fn.histogram_quantile(block, q)
        if f in fn._UNARY:
            return fn.unary_math(self._eval(call.args[0], steps), f)
        if f == "pi":
            return _Scalar(math.pi)
        if f in fn._DATE_FNS:
            # date parts of the argument's unix-seconds values;
            # argument defaults to vector(time()) like Prometheus
            if call.args:
                b = self._eval(call.args[0], steps)
            else:
                b = Block(steps, (steps.astype(np.float64) / 1e9)[None, :],
                          [SeriesMeta(())])
            if isinstance(b, _Scalar):
                b = Block(steps, np.broadcast_to(
                    np.asarray(b.value, np.float64),
                    (1, len(steps))).copy(), [SeriesMeta(())])
            return fn.date_fn(b, f)
        if f == "round":
            nearest = (self._scalar_arg(call.args[1], steps)
                       if len(call.args) > 1 else 1.0)
            return fn.round_fn(self._eval(call.args[0], steps), nearest)
        if f == "clamp":
            return fn.clamp(self._eval(call.args[0], steps),
                            self._scalar_arg(call.args[1], steps),
                            self._scalar_arg(call.args[2], steps))
        if f == "clamp_min":
            return fn.clamp(self._eval(call.args[0], steps),
                            lo=self._scalar_arg(call.args[1], steps))
        if f == "clamp_max":
            return fn.clamp(self._eval(call.args[0], steps),
                            hi=self._scalar_arg(call.args[1], steps))
        if f == "scalar":
            b = self._eval(call.args[0], steps)
            if isinstance(b, _Scalar):
                return b
            if b.num_series == 1:
                return _Scalar(b.values[0].copy())
            return _Scalar(np.full(len(steps), np.nan))
        if f == "vector":
            v = self._eval(call.args[0], steps)
            if not isinstance(v, _Scalar):
                raise ValueError("vector() expects a scalar argument")
            # Per-step scalars stay per-step (Prometheus vector(time())
            # is the canonical example), device or host.
            val = np.asarray(v.value, np.float64)
            row = (np.broadcast_to(val, (len(steps),)) if val.ndim
                   else np.full(len(steps), float(val)))
            return Block(steps, row[None, :].copy(), [SeriesMeta(())])
        if f == "absent":
            b = self._eval(call.args[0], steps)
            present = (~np.isnan(b.values)).any(axis=0) if b.num_series else (
                np.zeros(len(steps), bool))
            vals = np.where(present, np.nan, 1.0)[None, :]
            return Block(steps, vals, [SeriesMeta(())])
        if f == "label_replace":
            return self._label_replace(call, steps)
        if f == "label_join":
            return self._label_join(call, steps)
        if f == "timestamp":
            b = self._eval(call.args[0], steps)
            tvals = np.broadcast_to(steps.astype(np.float64) / 1e9, b.values.shape)
            return b.with_values(np.where(np.isnan(b.values), np.nan, tvals),
                                 [m.drop_name() for m in b.series])
        if f == "time":
            return _Scalar(steps.astype(np.float64) / 1e9)
        if f in ("sort", "sort_desc"):
            # Prometheus sorts instant vectors by value; for a range
            # evaluation the order is taken at the final step (stable
            # for ties, NaNs last), matching how dashboards consume it.
            b = self._eval(call.args[0], steps)
            if isinstance(b, _Scalar):
                raise ValueError(f"{f} expects an instant vector")
            if b.num_series <= 1:
                return b
            key = b.values[:, -1]
            key = np.where(np.isnan(key), np.inf if f == "sort" else -np.inf,
                           key)
            order = np.argsort(key if f == "sort" else -key, kind="stable")
            return Block(steps, b.values[order],
                         [b.series[i] for i in order])
        raise ValueError(f"unsupported function {f!r}")

    def _range_rows(self, raw: RawBlock, vals_dtype, family):
        """``family(ts, vals)`` over the fetched rows, device-resident:
        one call as they are where they fit one block of
        ``_RANGE_BLOCK_ROWS``, else one call a block of exactly that
        many rows, dispatched in turn (the transfer of the next block
        runs while the device works on the last) and joined on the
        device, the last block filled with empty rows (no points: every
        family answers NaN there).  Every family is row-wise, so a row
        reads the same bits whichever way it was dispatched."""
        ts, vals = raw.ts, raw.values
        S, P = ts.shape
        R = S if S <= _RANGE_BLOCK_ROWS else _RANGE_BLOCK_ROWS
        outs = []
        for lo in range(0, S, R):
            n = min(R, S - lo)
            ts_b = ts[lo:lo + n]
            vals_b = np.nan_to_num(vals[lo:lo + n])
            if n < R:
                ts_b = np.concatenate(
                    [ts_b, np.full((R - n, P), np.iinfo(np.int64).max,
                                   np.int64)])
                vals_b = np.concatenate([vals_b, np.zeros((R - n, P))])
            with self.tracer.start_span(Tracepoint.EVAL_BLOCK) as sp:
                with self.tracer.start_span(Tracepoint.EVAL_TO_DEVICE) as tx:
                    ts_j = jnp.asarray(ts_b)
                    vals_j = jnp.asarray(vals_b, vals_dtype)
                    if tx.recording:
                        tx.set_tag("bytes", ts_j.nbytes + vals_j.nbytes)
                out = family(ts_j, vals_j)
                if sp.recording:
                    sp.set_tag("rows", n)
                    sp.set_tag("pad", R - n)
                    sp.set_tag("points", P)
                    sp.set_tag("steps", out.shape[-1])
            outs.append(out)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def _label_replace(self, call: Call, steps: np.ndarray) -> Block:
        import re as _re

        b = self._eval(call.args[0], steps)
        dst = self._string_arg(call.args[1]).encode()
        repl = self._string_arg(call.args[2])
        src = self._string_arg(call.args[3]).encode()
        regex = _re.compile(self._string_arg(call.args[4]))
        metas = []
        for m in b.series:
            tags = m.as_dict()
            val = tags.get(src, b"").decode()
            mm = regex.fullmatch(val)
            if mm:
                new = mm.expand(repl.replace("$", "\\")).encode()
                if new:
                    tags[dst] = new
                else:
                    tags.pop(dst, None)
            metas.append(SeriesMeta.from_dict(tags))
        return Block(b.step_times, b.values, metas)

    def _label_join(self, call: Call, steps: np.ndarray) -> Block:
        b = self._eval(call.args[0], steps)
        dst = self._string_arg(call.args[1]).encode()
        sep = self._string_arg(call.args[2]).encode()
        srcs = [self._string_arg(a).encode() for a in call.args[3:]]
        metas = []
        for m in b.series:
            tags = m.as_dict()
            joined = sep.join(tags.get(s, b"") for s in srcs)
            if joined:
                tags[dst] = joined
            else:
                tags.pop(dst, None)
            metas.append(SeriesMeta.from_dict(tags))
        return Block(b.step_times, b.values, metas)

    def _eval_aggregation(self, agg: Aggregation, steps: np.ndarray) -> Block:
        block = self._eval(agg.expr, steps)
        by = set(agg.by) if agg.by is not None else None
        without = set(agg.without) if agg.without is not None else None
        if agg.op in ("topk", "bottomk"):
            k = int(self._scalar_arg(agg.param, steps))
            return fn.topk_bottomk(block, k, agg.op, by, without)
        if agg.op == "quantile":
            q = self._scalar_arg(agg.param, steps)
            return fn.aggregate(block, "quantile", by, without, q)
        return fn.aggregate(block, agg.op, by, without)

    def _eval_binary(self, b: BinaryOp, steps: np.ndarray):
        lhs = self._eval(b.lhs, steps)
        rhs = self._eval(b.rhs, steps)
        sl, sr = isinstance(lhs, _Scalar), isinstance(rhs, _Scalar)
        if b.op in ("and", "or", "unless"):
            return self._set_op(b, lhs, rhs)
        if sl and sr:
            with np.errstate(all="ignore"):
                v = fn._BINOPS[b.op](lhs.value, rhs.value)
            if b.op in fn._COMPARISONS:
                v = np.asarray(v, np.float64) if isinstance(v, np.ndarray) \
                    else (1.0 if v else 0.0)
            return _Scalar(v if isinstance(v, np.ndarray) else float(v))
        if sr:
            return fn.scalar_binary(lhs, b.op, rhs.value, False, b.bool_mode)
        if sl:
            return fn.scalar_binary(rhs, b.op, lhs.value, True, b.bool_mode)
        return fn.vector_binary(
            lhs, rhs, b.op,
            set(b.on) if b.on is not None else None,
            set(b.ignoring) if b.ignoring is not None else None,
            b.bool_mode,
        )

    def _set_op(self, b: BinaryOp, lhs: Block, rhs: Block) -> Block:
        on = set(b.on) if b.on is not None else None
        ig = set(b.ignoring) if b.ignoring is not None else None
        # Host row-matching path: materialize both sides once up front
        # (device arrays reject list indexing, and the per-row loop
        # below would otherwise sync repeatedly).
        lvals = np.asarray(lhs.values)
        rvals = np.asarray(rhs.values)
        rkeys = {fn._match_key(m, on, ig): i for i, m in enumerate(rhs.series)}
        if b.op == "or":
            extra_rows = [i for i, m in enumerate(rhs.series)
                          if fn._match_key(m, on, ig) not in
                          {fn._match_key(x, on, ig) for x in lhs.series}]
            vals = np.concatenate([lvals, rvals[extra_rows]]) if extra_rows \
                else lvals
            metas = lhs.series + [rhs.series[i] for i in extra_rows]
            return Block(lhs.step_times, vals, metas)
        out = np.full_like(lvals, np.nan)
        for i, m in enumerate(lhs.series):
            if i % 256 == 0:  # per-series host loop: cancellable
                xdeadline.check_current("set-op rows")
            j = rkeys.get(fn._match_key(m, on, ig))
            if b.op == "and":
                if j is not None:
                    out[i] = np.where(~np.isnan(rvals[j]), lvals[i], np.nan)
            else:  # unless
                if j is None:
                    out[i] = lvals[i]
                else:
                    out[i] = np.where(np.isnan(rvals[j]), lvals[i], np.nan)
        return lhs.with_values(out)

    # -- helpers -----------------------------------------------------------

    def _scalar_arg(self, e: Expr, steps: np.ndarray) -> float:
        """A static float parameter (topk k, quantile q, clamp bounds…).
        Per-step scalars collapse to their first finite value."""
        v = self._eval(e, steps)
        if isinstance(v, _Scalar):
            # scalar() rows may be numpy OR device arrays now that
            # blocks stay device-resident: normalize through numpy
            # before collapsing (a device (T,) array must not escape
            # into int(k)/float() call sites).
            if getattr(v.value, "ndim", 0):
                arr = np.asarray(v.value)
                finite = arr[np.isfinite(arr)]
                return float(finite[0]) if len(finite) else float("nan")
            return v.value
        raise ValueError("expected scalar argument")

    def _string_arg(self, e: Expr) -> str:
        if isinstance(e, StringLiteral):
            return e.value
        raise ValueError("expected string argument")
