"""Instant-vector functions over step-aligned blocks.

Equivalents of `src/query/functions/{aggregation,linear,binary,tag}`:

* label-grouped aggregations (sum/avg/min/max/count/stddev/quantile/
  topk/bottomk by/without) — `aggregation/function.go`;
* `histogram_quantile` — `linear/histogram_quantile.go:38-54`, computed
  per (group, step) over the le-bucket axis as one segmented device op;
* scalar math (abs/ceil/floor/exp/ln/log2/log10/sqrt/round/clamp_*) —
  `linear/math.go`, `linear/clamp.go`;
* binary arithmetic/comparison with vector matching (on/ignoring) —
  `binary/binary.go`.

All operate on the (S, T) matrix; grouping is a host-computed partition of
series rows (tag work stays on host) followed by one device segmented
reduction over the group axis: the host sorts the group ids in numpy
and `device_fns.group_reduce` runs ONE jitted program on that plan (a
segmented scan over the rows in the host's permutation, read at each
group's end), the same program on every backend.
"""

from __future__ import annotations

import math
from collections import defaultdict

import jax.numpy as jnp
import numpy as np

from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.query.block import Block, SeriesMeta

NAN = float("nan")


# ---------------------------------------------------------------------------
# Label grouping (host): series rows -> group ids
# ---------------------------------------------------------------------------


def group_series(series: list[SeriesMeta], by: set[bytes] | None,
                 without: set[bytes] | None) -> tuple[np.ndarray, list[SeriesMeta]]:
    """Group assignment per series row + the output group metas.

    by=None, without=None → one global group (Prometheus `sum(x)`).
    A series' group key is kept on its meta: the metas of fetched
    series live as long as their index documents, so a dashboard's
    grouping costs a dict lookup a series after its first query.
    """
    with tracing.span(Tracepoint.EVAL_GROUP_KEYS) as sp:
        if by is None and without is None:
            gids = np.zeros(len(series), np.int32)
            metas = [SeriesMeta(())] if series else []
        else:
            how = ("by", frozenset(by)) if by is not None else (
                "without", frozenset(without | {b"__name__"}))
            groups: dict[tuple, int] = {}
            metas = []
            ids = []
            for m in series:
                keys = m.__dict__.get("_group_keys")
                if keys is None:
                    keys = {}
                    object.__setattr__(m, "_group_keys", keys)
                key_meta = keys.get(how)
                if key_meta is None:
                    key_meta = keys[how] = (m.keep(how[1]) if how[0] == "by"
                                            else m.drop(how[1]))
                g = groups.get(key_meta.tags)
                if g is None:
                    g = groups[key_meta.tags] = len(metas)
                    metas.append(key_meta)
                ids.append(g)
            gids = np.asarray(ids, np.int32)
        if sp.recording:
            sp.set_tag("n", len(series))
            sp.set_tag("groups", len(metas))
    return gids, metas


def _segment_reduce(values: np.ndarray, gids: np.ndarray, num_groups: int,
                    func: str, q: float = 0.0) -> np.ndarray:
    """(S, T) + group ids -> (G, T), device-resident (Block contract):
    one jitted program a call on a plan the host builds from ``gids``
    (`device_fns.group_reduce`, `device_fns.group_quantile`)."""
    from m3_tpu.query import device_fns

    if func == "quantile":
        return device_fns.group_quantile(values, gids, num_groups, q)
    return device_fns.group_reduce(values, gids, num_groups, func)


def aggregate(block: Block, func: str, by: set[bytes] | None = None,
              without: set[bytes] | None = None, param: float = 0.0) -> Block:
    gids, metas = group_series(block.series, by, without)
    vals = block.rows()
    if vals.shape[0] > len(gids):
        # a padded block's empty rows: a group past the last, which no
        # program reads, so the block's real row count shapes nothing
        gids = np.concatenate([gids, np.full(vals.shape[0] - len(gids),
                                             len(metas), np.int32)])
    vals = _segment_reduce(vals, gids, len(metas), func, param)
    return Block(block.step_times, vals, metas)


def topk_bottomk(block: Block, k: int, func: str,
                 by: set[bytes] | None = None,
                 without: set[bytes] | None = None) -> Block:
    """topk/bottomk keep original series, masking all but the k extreme
    per (group, step)."""
    from m3_tpu.query.device_fns import topk_mask

    gids, metas = group_series(block.series, by, without)
    import jax.numpy as jnp

    v = jnp.asarray(block.values)
    keep = topk_mask(v, gids, len(metas), int(k), func == "topk")
    out = jnp.where(jnp.asarray(keep), v, NAN)
    return block.with_values(out)


# ---------------------------------------------------------------------------
# histogram_quantile
# ---------------------------------------------------------------------------


def histogram_quantile(block: Block, q: float) -> Block:
    """Per-step quantile from cumulative `le` buckets (reference
    linear/histogram_quantile.go: group series by tags-minus-le, sort
    buckets by upper bound, linear interpolation within the bucket)."""
    groups: dict[tuple, list[tuple[float, int]]] = defaultdict(list)
    for i, m in enumerate(block.series):
        tags = m.as_dict()
        le = tags.get(b"le")
        if le is None:
            continue
        try:
            ub = float(le)
        except ValueError:
            continue
        key = m.drop({b"le", b"__name__"}).tags
        groups[key].append((ub, i))

    from m3_tpu.query.device_fns import histogram_quantile_groups

    T = block.num_steps
    metas: list[SeriesMeta] = []
    group_rows: list[list[int]] = []
    group_ubs: list[np.ndarray] = []
    nan_metas: list[SeriesMeta] = []
    for key, buckets in groups.items():
        buckets.sort()
        ubs = np.array([b[0] for b in buckets])
        if not np.isinf(ubs[-1]):
            # no +Inf bucket → undefined (Prometheus returns NaN)
            nan_metas.append(SeriesMeta(key))
            continue
        metas.append(SeriesMeta(key))
        group_rows.append([b[1] for b in buckets])
        group_ubs.append(ubs)
    vals = None
    if group_rows:
        # Stays device-resident — iterating rows here would sync each
        # of the G rows separately (Block contract: one boundary sync).
        vals = histogram_quantile_groups(block.rows(), group_rows,
                                         group_ubs, q)
    metas += nan_metas
    if vals is None and not nan_metas:
        return Block(block.step_times, np.zeros((0, T)), [])
    if nan_metas:
        import jax.numpy as jnp

        nan_blk = jnp.full((len(nan_metas), T), NAN, jnp.float64)
        vals = nan_blk if vals is None else jnp.concatenate([vals, nan_blk])
    return Block(block.step_times, vals, metas)


# ---------------------------------------------------------------------------
# Scalar math + binary ops
# ---------------------------------------------------------------------------

_UNARY = {
    "abs": np.abs,
    "ceil": np.ceil,
    "floor": np.floor,
    "exp": np.exp,
    "ln": np.log,
    "log2": np.log2,
    "log10": np.log10,
    "sqrt": np.sqrt,
    "sgn": np.sign,
    # trigonometric family (Prometheus 2.31+)
    "sin": np.sin, "cos": np.cos, "tan": np.tan,
    "asin": np.arcsin, "acos": np.arccos, "atan": np.arctan,
    "sinh": np.sinh, "cosh": np.cosh, "tanh": np.tanh,
    "asinh": np.arcsinh, "acosh": np.arccosh, "atanh": np.arctanh,
    "deg": np.degrees, "rad": np.radians,
}

# Date parts of a unix-seconds vector (Prometheus functions.go
# funcDaysInMonth..funcYear; UTC, like Prometheus).  Each function
# receives the precomputed (dt, Y, M, D) datetime64 casts ONCE.
_DATE_FNS = {
    "minute": lambda dt, Y, M, D: (dt.astype("datetime64[m]")
                                   - dt.astype("datetime64[h]")
                                   ).astype("int64"),
    "hour": lambda dt, Y, M, D: (dt.astype("datetime64[h]") - D
                                 ).astype("int64"),
    "day_of_week": lambda dt, Y, M, D: (D.astype("int64") + 4) % 7,
    "day_of_month": lambda dt, Y, M, D: (D - M).astype("int64") + 1,
    "day_of_year": lambda dt, Y, M, D: (D - Y).astype("int64") + 1,
    "days_in_month": lambda dt, Y, M, D: (
        (M + 1).astype("datetime64[D]") - M.astype("datetime64[D]")
    ).astype("int64"),
    "month": lambda dt, Y, M, D: (M - Y).astype("int64") + 1,
    "year": lambda dt, Y, M, D: Y.astype("int64") + 1970,
}


def date_fn(block: Block, func: str) -> Block:
    v = block.values
    finite = np.isfinite(v)
    secs = np.where(finite, v, 0.0).astype("int64")
    dt = secs.astype("datetime64[s]")
    Y = dt.astype("datetime64[Y]")
    M = dt.astype("datetime64[M]")
    D = dt.astype("datetime64[D]")
    with np.errstate(all="ignore"):
        out = _DATE_FNS[func](dt, Y, M, D).astype(np.float64)
    # non-finite inputs (NaN gaps AND +/-Inf poison) stay NaN — an
    # Inf-valued sample must not masquerade as the epoch's date parts
    out = np.where(finite, out, np.nan)
    return block.with_values(out, [m.drop_name() for m in block.series])


# Device-resident forms (Block contract), derived key-for-key from the
# numpy table so engine dispatch (`f in _UNARY`) can never drift from
# execution: every numpy ufunc here has a same-named jnp equivalent.
_J_UNARY = {name: getattr(jnp, f.__name__) for name, f in _UNARY.items()}


def unary_math(block: Block, func: str) -> Block:
    out = _J_UNARY[func](jnp.asarray(block.values, jnp.float64))
    return block.with_values(out, [m.drop_name() for m in block.series])


def round_fn(block: Block, to_nearest: float = 1.0) -> Block:
    # Prometheus round(): half UP (floor(v+0.5)); device-resident.
    v = jnp.asarray(block.values, jnp.float64)
    out = jnp.floor(v / to_nearest + 0.5) * to_nearest
    return block.with_values(out, [m.drop_name() for m in block.series])


def clamp(block: Block, lo: float = -math.inf, hi: float = math.inf) -> Block:
    return block.with_values(
        jnp.clip(jnp.asarray(block.values, jnp.float64), lo, hi),
        [m.drop_name() for m in block.series]
    )


_BINOPS = {
    "+": np.add,
    "-": np.subtract,
    "*": np.multiply,
    "/": np.divide,
    "%": np.mod,
    "^": np.power,
    "==": np.equal,
    "!=": np.not_equal,
    ">": np.greater,
    "<": np.less,
    ">=": np.greater_equal,
    "<=": np.less_equal,
}

from m3_tpu.query.device_fns import COMPARISONS as _COMPARISONS


# Derived key-for-key from _BINOPS (same drift guard as _J_UNARY).
_J_BINOPS = {op: getattr(jnp, f.__name__) for op, f in _BINOPS.items()}


def scalar_binary(block: Block, op: str, scalar: float,
                  scalar_left: bool = False, bool_mode: bool = False) -> Block:
    f = _J_BINOPS[op]
    v = jnp.asarray(block.values, jnp.float64)  # comparisons stay f64
    out = (f(scalar, v) if scalar_left else f(v, scalar)).astype(jnp.float64)
    if op in _COMPARISONS:
        if bool_mode:
            out = jnp.where(jnp.isnan(v), NAN, out)  # NaN stays missing
        else:
            out = jnp.where(out != 0, v, NAN)  # filter semantics
    series = block.series if op in _COMPARISONS and not bool_mode else [
        m.drop_name() for m in block.series
    ]
    return block.with_values(out, series)


def _match_key(meta: SeriesMeta, on: set[bytes] | None,
               ignoring: set[bytes] | None) -> tuple:
    if on is not None:
        return meta.keep(on).tags
    drop = {b"__name__"} | (ignoring or set())
    return meta.drop(drop).tags


def vector_binary(lhs: Block, rhs: Block, op: str,
                  on: set[bytes] | None = None,
                  ignoring: set[bytes] | None = None,
                  bool_mode: bool = False) -> Block:
    """One-to-one vector matching (reference binary/binary.go)."""
    rindex = { _match_key(m, on, ignoring): i for i, m in enumerate(rhs.series) }
    rows_l, rows_r, metas = [], [], []
    for i, m in enumerate(lhs.series):
        k = _match_key(m, on, ignoring)
        j = rindex.get(k)
        if j is None:
            continue
        rows_l.append(i)
        rows_r.append(j)
        metas.append(m.drop_name() if not (op in _COMPARISONS and not bool_mode) else m)
    if not rows_l:
        return Block(lhs.step_times, np.zeros((0, lhs.num_steps)), [])
    from m3_tpu.query.device_fns import vector_binary_matched

    out = vector_binary_matched(
        lhs.values, rhs.values, rows_l, rows_r, op, bool_mode
    )
    return Block(lhs.step_times, out, metas)
