"""Device kernels for the query engine's hot per-step functions.

The reference computes these per-series/per-step on the CPU with
goroutine fan-out (`src/query/functions/linear/histogram_quantile.go:38-54`,
`aggregation/function.go`, `binary/binary.go`); here each one is a
single jitted array program over the whole (series × step) block — the
TPU-shaped replacement for per-step loops.

Ragged group structure (different bucket/row counts per group) is
handled the TPU way: the host builds padded gather-index matrices once
(cheap tag work it owns anyway), and the device kernel runs on dense
(G, R_max, T) tensors with masks.  jit caches per shape, so repeated
queries over the same block geometry pay tracing once.  The plain
reductions (sum, avg, min, ...) need no padding: the host hands the
permutation that makes groups adjacent and one segmented scan runs over
the rows (`group_reduce`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from m3_tpu.parallel import segmented

NAN = float("nan")


# ---------------------------------------------------------------------------
# Group plans (host)
# ---------------------------------------------------------------------------


def group_plan(gids: np.ndarray, num_groups: int):
    """(row_idx (G, R_max), mask (G, R_max)) gathering each group's rows."""
    order = np.argsort(gids, kind="stable")
    sorted_g = gids[order]
    starts = np.searchsorted(sorted_g, np.arange(num_groups))
    ends = np.searchsorted(sorted_g, np.arange(num_groups), side="right")
    counts = ends - starts
    r_max = max(1, int(counts.max(initial=0)))
    idx = np.zeros((num_groups, r_max), np.int32)
    mask = np.zeros((num_groups, r_max), bool)
    for g in range(num_groups):
        c = counts[g]
        idx[g, :c] = order[starts[g] : ends[g]]
        mask[g, :c] = True
    return idx, mask


def _sorted_plan(gids: np.ndarray, num_groups: int):
    """(order (S,), is_start (S,), pos (G,), found (G,)): the STABLE
    permutation that makes each group's rows adjacent, each segment's
    head flag, and where each group's segment ends (clamped valid;
    ``found`` False for a group with no row).  The host owns it: gids
    is its own array."""
    order = np.argsort(gids, kind="stable")
    sorted_g = np.asarray(gids)[order]
    is_start = np.ones(len(order), bool)
    is_start[1:] = sorted_g[1:] != sorted_g[:-1]
    counts = np.bincount(sorted_g, minlength=num_groups)[:num_groups]
    pos = np.maximum(np.cumsum(counts) - 1, 0).astype(np.int32)
    return order.astype(np.int32), is_start, pos, counts > 0


# ---------------------------------------------------------------------------
# Grouped sum / count / avg / min / max / stddev / stdvar / group
# ---------------------------------------------------------------------------

REDUCE_FUNCS = ("sum", "count", "avg", "min", "max", "stddev", "stdvar",
                "group")


@functools.partial(jax.jit, static_argnames=("func",))
def _segment_reduce_kernel(values, order, is_start, pos, found, func: str):
    """(S, T) + the host's sorted plan -> (G, T): one segmented scan
    over the rows in group order, read at each segment's end.  Costs
    S x T whatever the groups' sizes.  NaN is "absent": a (group, step)
    with no present value answers NaN under every func."""
    with jax.named_scope("group_reduce"):
        v = values.astype(jnp.float64)[order]
        present = ~jnp.isnan(v)
        zero = jnp.where(present, v, 0.0)
        adds = [present.astype(jnp.int32)]  # the count rides every func
        if func in ("sum", "avg", "stddev", "stdvar"):
            adds.append(zero)
        if func in ("stddev", "stdvar"):
            adds.append(zero * zero)
        mins = (jnp.where(present, v, jnp.inf),) if func == "min" else ()
        maxs = (jnp.where(present, v, -jnp.inf),) if func == "max" else ()
        r_adds, r_mins, r_maxs = segmented.head_flag_scan(
            is_start, adds=tuple(adds), mins=mins, maxs=maxs)
        fm = found[:, None]

        def at_ends(seg, fill):
            return jnp.where(fm, seg[pos], fill)

        cnt = at_ends(r_adds[0], 0).astype(jnp.float64)
        empty = cnt == 0
        n = jnp.where(empty, 1.0, cnt)
        if func == "sum":
            out = at_ends(r_adds[1], 0.0)
        elif func == "count":
            out = cnt
        elif func == "group":
            out = jnp.ones_like(cnt)
        elif func == "avg":
            out = at_ends(r_adds[1], 0.0) / n
        elif func in ("stddev", "stdvar"):
            mean = at_ends(r_adds[1], 0.0) / n
            var = jnp.maximum(at_ends(r_adds[2], 0.0) / n - mean * mean, 0.0)
            out = jnp.sqrt(var) if func == "stddev" else var
        elif func == "min":
            out = at_ends(r_mins[0], jnp.inf)
            out = jnp.where(jnp.isposinf(out), NAN, out)
        else:
            out = at_ends(r_maxs[0], -jnp.inf)
            out = jnp.where(jnp.isneginf(out), NAN, out)
        return jnp.where(empty, NAN, out)


def group_reduce(values, gids: np.ndarray, num_groups: int, func: str):
    """(S, T) + host group ids -> (G, T) f64, device-resident (Block
    contract), as ONE jitted program: the host turns ``gids`` into the
    sorted plan in numpy and the device sorts and searches nothing."""
    if func not in REDUCE_FUNCS:
        raise ValueError(f"unknown aggregation {func}")
    v = jnp.asarray(values)  # cast to f64 inside the program
    if v.shape[0] == 0 or num_groups == 0:
        return jnp.full((num_groups, v.shape[1]), NAN, jnp.float64)
    return _segment_reduce_kernel(v, *_sorted_plan(gids, num_groups),
                                  func=func)


# ---------------------------------------------------------------------------
# Grouped quantile  (quantile(0.9, x) by (...))
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
def _group_quantile_kernel(values, idx, mask, q):
    """(S, T), (G, R), (G, R) -> (G, T) linear-interpolated quantile over
    present (non-NaN) rows — matches numpy nanquantile 'linear'."""
    rows = values[idx]  # (G, R, T)
    present = mask[:, :, None] & ~jnp.isnan(rows)
    big = jnp.where(present, rows, jnp.inf)
    s = jnp.sort(big, axis=1)  # present values first, inf after
    n = present.sum(axis=1)  # (G, T)
    # rank into the sorted axis: h = q*(n-1); linear interp between floor/ceil
    h = q * (n - 1).astype(values.dtype)
    lo = jnp.clip(jnp.floor(h).astype(jnp.int32), 0, s.shape[1] - 1)
    hi = jnp.clip(jnp.ceil(h).astype(jnp.int32), 0, s.shape[1] - 1)
    v_lo = jnp.take_along_axis(s, lo[:, None, :], axis=1)[:, 0, :]
    v_hi = jnp.take_along_axis(s, hi[:, None, :], axis=1)[:, 0, :]
    frac = h - jnp.floor(h)
    out = v_lo + (v_hi - v_lo) * frac
    return jnp.where(n > 0, out, jnp.nan)


def group_quantile(values: np.ndarray, gids: np.ndarray, num_groups: int,
                   q: float) -> np.ndarray:
    from m3_tpu.query import precision

    dt = precision.compute_dtype()
    idx, mask = group_plan(gids, num_groups)
    return _group_quantile_kernel(
        jnp.asarray(values, dt), jnp.asarray(idx), jnp.asarray(mask),
        jnp.asarray(q, dt),
    ).astype(jnp.float64)  # device-resident (Block contract)


# ---------------------------------------------------------------------------
# topk / bottomk
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "top"))
def _topk_mask_kernel(values, idx, mask, inv_g, inv_r, k: int, top: bool):
    """Keep-mask (S, T): True where the row is among the k extreme in its
    group at that step."""
    rows = values[idx]  # (G, R, T)
    # Present = in-group and not NaN; ±Inf are real sample values and
    # compete for rank slots (Prometheus topk keeps Inf).
    present = mask[:, :, None] & ~jnp.isnan(rows)
    key = jnp.where(present, rows, -jnp.inf if top else jnp.inf)
    s = jnp.sort(key, axis=1)
    R = s.shape[1]
    # kth extreme per (group, step); groups with < k present rows keep all
    kth = s[:, max(R - k, 0), :] if top else s[:, min(k - 1, R - 1), :]
    keep_g = (key >= kth[:, None, :]) if top else (key <= kth[:, None, :])
    keep_g = keep_g & present
    # (G, R, T) back to (S, T) by GATHER through the inverse mapping —
    # not scatter (~1us/element on TPU; round 5, window 3)
    return keep_g[inv_g, inv_r, :]


def topk_mask(values: np.ndarray, gids: np.ndarray, num_groups: int,
              k: int, top: bool) -> np.ndarray:
    idx, mask = group_plan(gids, num_groups)
    # Inverse of group_plan — derived from its OWN output so the two
    # can never drift: series idx[g, r] sits at rank r of group g.
    S = len(gids)
    rows, cols = np.nonzero(mask)
    inv_r = np.empty(S, np.int32)
    inv_r[idx[rows, cols]] = cols.astype(np.int32)
    return _topk_mask_kernel(jnp.asarray(values), jnp.asarray(idx),
                             jnp.asarray(mask),
                             jnp.asarray(np.asarray(gids, np.int32)),
                             jnp.asarray(inv_r), k=int(k), top=bool(top))


# ---------------------------------------------------------------------------
# histogram_quantile
# ---------------------------------------------------------------------------


@jax.jit
def _histogram_quantile_kernel(values, idx, nbuckets, ubs, q):
    """values (S, T); idx (G, B) row index per bucket rank (le-ascending,
    +Inf last when present); nbuckets (G,); ubs (G, B) upper bounds
    (inf-padded).  Returns (G, T).

    Mirrors the reference math (`linear/histogram_quantile.go`):
    cumulative counts clamped monotone, rank = q * total, linear
    interpolation inside the first bucket reaching the rank, +Inf bucket
    answered by the highest finite bound."""
    G, B = idx.shape
    rows = values[idx]  # (G, B, T)
    bpos = jnp.arange(B)[None, :]
    valid = bpos < nbuckets[:, None]  # (G, B)
    counts = jnp.where(valid[:, :, None], jnp.nan_to_num(rows), 0.0)
    counts = jax.lax.cummax(counts, axis=1)
    # total comes from the RAW +Inf-bucket sample: a NaN there must
    # propagate to a NaN result (a nan_to_num'd total would silently
    # substitute the previous bucket's cumulative count).
    last = jnp.clip(nbuckets - 1, 0, B - 1)
    total = jnp.take_along_axis(rows, last[:, None, None], axis=1)[:, 0, :]
    rank = q * total
    ge = (counts >= rank[:, None, :]) & valid[:, :, None]
    first = jnp.argmax(ge, axis=1)  # (G, T)
    take = lambda a, i: jnp.take_along_axis(a, i[:, None, :], axis=1)[:, 0, :]
    b_hi = jnp.take_along_axis(ubs, first, axis=1)
    prev = jnp.maximum(first - 1, 0)
    b_lo = jnp.where(first > 0, jnp.take_along_axis(ubs, prev, axis=1), 0.0)
    c_hi = take(counts, first)
    c_lo = jnp.where(first > 0, take(counts, prev), 0.0)
    frac = jnp.where(c_hi > c_lo, (rank - c_lo) / (c_hi - c_lo), 0.0)
    val = b_lo + (b_hi - b_lo) * frac
    # +Inf bucket → highest finite bound; a group with ONLY the +Inf
    # bucket has no finite bound and answers 0.0 (host-code parity).
    hf_idx = jnp.clip(nbuckets - 2, 0, B - 1)
    highest_finite = jnp.where(
        (nbuckets >= 2)[:, None],
        jnp.take_along_axis(ubs, hf_idx[:, None], axis=1),
        0.0,
    )
    val = jnp.where(jnp.isinf(b_hi), highest_finite, val)
    bad = (total == 0) | jnp.isnan(total)
    return jnp.where(bad, jnp.nan, val)


def histogram_quantile_groups(values: np.ndarray, group_rows: list,
                              group_ubs: list, q: float) -> np.ndarray:
    """group_rows[g] = row indices le-ascending (+Inf last); group_ubs[g]
    the matching upper bounds.  Returns (G, T)."""
    G = len(group_rows)
    B = max(len(r) for r in group_rows)
    idx = np.zeros((G, B), np.int32)
    ubs = np.full((G, B), np.inf)
    nb = np.zeros(G, np.int32)
    for g, (rows, u) in enumerate(zip(group_rows, group_ubs)):
        idx[g, : len(rows)] = rows
        ubs[g, : len(u)] = u
        nb[g] = len(rows)
    from m3_tpu.query import precision

    dt = precision.compute_dtype()
    return _histogram_quantile_kernel(
        jnp.asarray(values, dt), jnp.asarray(idx), jnp.asarray(nb),
        jnp.asarray(ubs, dt), jnp.asarray(q, dt),
    ).astype(jnp.float64)  # device-resident (Block contract)


# ---------------------------------------------------------------------------
# Binary ops with vector matching
# ---------------------------------------------------------------------------

COMPARISONS = {"==", "!=", ">", "<", ">=", "<="}


@functools.partial(jax.jit, static_argnames=("op", "bool_mode"))
def _vector_binary_kernel(lv, rv, op: str, bool_mode: bool):
    ops = {
        "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
        "/": jnp.divide, "%": jnp.mod, "^": jnp.power,
        "==": jnp.equal, "!=": jnp.not_equal, ">": jnp.greater,
        "<": jnp.less, ">=": jnp.greater_equal, "<=": jnp.less_equal,
    }
    out = ops[op](lv, rv).astype(lv.dtype)
    if op in COMPARISONS and not bool_mode:
        out = jnp.where(out != 0, lv, jnp.nan)
    miss = jnp.isnan(lv) | jnp.isnan(rv)
    return jnp.where(miss, jnp.nan, out)


def vector_binary_matched(l_values: np.ndarray, r_values: np.ndarray,
                          rows_l, rows_r, op: str,
                          bool_mode: bool) -> np.ndarray:
    """Gather matched rows on device and apply the op in one kernel.

    Comparisons are EXEMPT from the f32 policy: narrowing before ==/>/<
    discretely flips results for f64-distinct operands (16777217.0 vs
    16777216.0 collide in f32) — a boolean error no relative-error
    envelope covers.  Only the arithmetic ops narrow."""
    from m3_tpu.query import precision

    dt = np.float64 if op in COMPARISONS else precision.compute_dtype()
    lv = jnp.asarray(l_values, dt)[jnp.asarray(np.asarray(rows_l, np.int32))]
    rv = jnp.asarray(r_values, dt)[jnp.asarray(np.asarray(rows_r, np.int32))]
    return _vector_binary_kernel(
        lv, rv, op=op, bool_mode=bool_mode).astype(jnp.float64)
