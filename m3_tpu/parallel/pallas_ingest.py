"""Pallas TPU kernel for the aggregator's segmented ingest reduction.

SURVEY §7 phase 1 prescribes hand-written Pallas where XLA's cost model
fails; for this framework's hot ops the measured decisions are:

* **M3TSZ decode** — NOT Pallas.  The codec's per-lane dynamic bit
  cursors need per-lane gathers, which Mosaic lowers to the same
  O(S×W) masked reductions XLA does; the production formulation
  (encoding/m3tsz_jax.py) already avoids them with a carried register
  window, its HBM ceiling sits ~10× above the BASELINE target, and the
  host tail is covered by the threaded native codec (34M dp/s/core).
* **Rollup ingest** — the one op where XLA's lowering is known-risky:
  `at[idx].add` with colliding indices serializes on TPU.  The arena
  path uses XLA scatter (validated, exact); THIS module provides the
  hand-scheduled alternative — a sort-free, two-pass binned segment
  reduction shaped for the VPU — for hardware/XLA versions where the
  scatter dominates the north-star bench.

The kernel: ingest N (slot, value) pairs into C accumulator slots.
2-D grid over (slot tiles, batch slabs); each step loads one SLAB of
the batch into VMEM (BlockSpec does the slicing — the first live-TPU
run proved Mosaic rejects `lax.dynamic_slice` on VMEM values, so the
slab walk lives in the grid, not in a fori_loop) and accumulates
`value * (slot == lane_slot)` partial sums into its tile's output
block, which Pallas keeps revisiting across the inner slab dimension.
No scatter, no atomics, deterministic, and the slab copies pipeline
against compute.  Cost is O(N × C / tile) vector work: wins over
serialized scatter when the collision rate is high and C is moderate
(the downsampler's rollup arenas), loses for huge sparse C — callers
choose per shape.

Correctness is pinned against the XLA scatter path in
tests/test_pallas_ingest.py (interpret mode on CPU — semantics only).
THIS formulation does not compile for a chip: Mosaic refuses its
(1, N) blocks (the last two block dimensions must be multiples of
(8, 128)) and has no f64, which the arenas' value lanes are.  On a TPU
``arena.set_ingest_impl("pallas")`` / ``M3_ARENA_INGEST=pallas`` is
therefore refused with an error; off the chip the name selects this
kernel in interpret mode.  The block-shape repair the decode/encode
kernels got (parallel/pallas_decode.py) is the model for whoever picks
it up (ROADMAP C3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

TILE = 1024   # slots per grid step: 8 sublanes x 128 lanes of f32 work
SLAB = 512    # batch points per grid step: the (TILE, SLAB) hit mask
              # (2MB f32 / 4MB f64) is the kernel's VMEM high-water mark
MAX_BATCH = 1 << 18  # bounds npad so index arithmetic stays i32-safe;
                     # callers chunk bigger batches (the arenas already
                     # ingest in bounded device batches)


def _ingest_kernel(slots_ref, values_ref, out_sum_ref, out_cnt_ref,
                   *out_sq_ref):
    """One (i, j) grid step: accumulate batch slab j into slot tile i.
    slots/values arrive as (1, SLAB) VMEM blocks (BlockSpec slices the
    batch — Mosaic has no dynamic_slice, so the slab walk IS the inner
    grid dimension); outputs are (1, TILE) blocks of the (C/TILE, TILE)
    accumulators, revisited across j with explicit first-step
    initialization.  Everything is 2-D with the reduction over
    SUBLANES: the hit mask is (SLAB, TILE) — slab points down the
    sublane axis, slot lanes across — so the partial sums land
    lane-shaped, exactly the layout of the (1, TILE) output block.
    When invoked with a third output ref (the moments form), the SAME
    hit mask also accumulates the sum of squares — one batch sweep
    serves all three lanes (the arena hot path would otherwise pay the
    O(N x C/TILE) sweep twice)."""
    with_sq = bool(out_sq_ref)
    base = pl.program_id(0) * TILE
    j = pl.program_id(1)
    lane_slots = base + jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)
    sl = slots_ref[0, :]
    va = values_ref[0, :]
    hit = sl[:, None] == lane_slots                    # (SLAB, TILE)
    # select, not multiply-by-mask: `mask * NaN` would poison every
    # slot in the tile, where the scatter oracle poisons only the hit
    # slot (the arenas pre-mask NaNs, but the kernel's contract is
    # exact equivalence with xla_segment_ingest on ANY input)
    zero = jnp.zeros((), va.dtype)
    hv = jnp.where(hit, va[:, None], zero)
    p_sum = jnp.sum(hv, axis=0, keepdims=True)         # (1, TILE)
    # counts accumulate in int32 regardless of value dtype: a
    # low-precision value dtype (bf16) would saturate its counts
    # (dtype pinned — x64 mode would promote the sum to int64)
    p_cnt = jnp.sum(hit, axis=0, keepdims=True, dtype=jnp.int32)
    # hv*hv is the already-masked value squared — NaN-safe for free
    p_sq = jnp.sum(hv * hv, axis=0, keepdims=True) if with_sq else None

    @pl.when(j == 0)
    def _init():
        out_sum_ref[:, :] = p_sum
        out_cnt_ref[:, :] = p_cnt
        if with_sq:
            out_sq_ref[0][:, :] = p_sq

    @pl.when(j > 0)
    def _accumulate():
        out_sum_ref[:, :] = out_sum_ref[:, :] + p_sum
        out_cnt_ref[:, :] = out_cnt_ref[:, :] + p_cnt
        if with_sq:
            out_sq_ref[0][:, :] = out_sq_ref[0][:, :] + p_sq


@functools.partial(jax.jit,
                   static_argnames=("capacity", "interpret", "with_sq"))
def _segment_call(slots, values, capacity: int, interpret: bool,
                  with_sq: bool):
    """Shared padding + pallas_call for the 2- and 3-output forms."""
    C = capacity
    Cpad = ((C + TILE - 1) // TILE) * TILE
    n = values.shape[0]
    if n > MAX_BATCH:
        raise ValueError(
            f"batch of {n} exceeds MAX_BATCH={MAX_BATCH}: chunk the "
            "batch (segment_ingest_chunked / segment_moments_chunked)")
    npad = max(SLAB, ((n + SLAB - 1) // SLAB) * SLAB)  # >=1 slab (empty ok)
    # pad with an impossible slot: contributes to no tile
    slots_p = jnp.full(npad, Cpad, jnp.int32).at[:n].set(
        jnp.where((slots < 0) | (slots >= C), Cpad, slots).astype(jnp.int32))
    values_p = jnp.zeros(npad, values.dtype).at[:n].set(values)
    nslabs = npad // SLAB
    ntiles = Cpad // TILE
    # Everything 2-D: Mosaic's layout assignment wants (sublane, lane)
    # shapes (the 1-D form died in tiling on the first live-TPU run).
    slots_2d = slots_p.reshape(nslabs, SLAB)
    values_2d = values_p.reshape(nslabs, SLAB)

    # (slot tiles, batch slabs): j is the innermost (sequential)
    # dimension, so each tile's output block stays resident while the
    # whole batch streams past it slab by slab.
    grid = (ntiles, nslabs)
    out_specs = [
        pl.BlockSpec((1, TILE), lambda i, j: (i, 0)),
        pl.BlockSpec((1, TILE), lambda i, j: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((ntiles, TILE), values.dtype),
        jax.ShapeDtypeStruct((ntiles, TILE), jnp.int32),
    ]
    if with_sq:
        out_specs.append(pl.BlockSpec((1, TILE), lambda i, j: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((ntiles, TILE), values.dtype))
    outs = pl.pallas_call(
        _ingest_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, SLAB), lambda i, j: (j, 0)),
            pl.BlockSpec((1, SLAB), lambda i, j: (j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(slots_2d, values_2d)
    return tuple(o.reshape(-1)[:C] for o in outs)


def pallas_segment_ingest(slots: jnp.ndarray, values: jnp.ndarray,
                          capacity: int, interpret: bool = False):
    """Sum + count ``values`` grouped by ``slots`` into (capacity,)
    accumulators with a Pallas grid over slot tiles.

    ``slots`` out of [0, capacity) are dropped (the arena drop-sentinel
    contract).  The batch is padded to whole slabs with an
    out-of-range slot so the kernel needs no tail masking.
    """
    return _segment_call(slots, values, capacity, interpret, False)


def pallas_segment_moments(slots: jnp.ndarray, values: jnp.ndarray,
                           capacity: int, interpret: bool = False):
    """(sum, count, sum of squares) in ONE batch sweep — the arena hot
    path's shape (sum/sum²/count lanes share the hit mask)."""
    s, c, sq = _segment_call(slots, values, capacity, interpret, True)
    return s, c, sq


def _minmax_kernel(slots_ref, values_ref, out_min_ref, out_max_ref):
    """Min/max sibling of ``_ingest_kernel``: same (slot tile, batch
    slab) grid and hit mask, min/max accumulate instead of sum.  Serves
    the packed arena's min/max stage on TPU as the binned alternative
    to its segmented associative scan (aggregator/packed.py) — same
    two-pass structure as the moments form, so the flip decision can be
    measured per backend with the existing bench machinery."""
    base = pl.program_id(0) * TILE
    j = pl.program_id(1)
    lane_slots = base + jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)
    sl = slots_ref[0, :]
    va = values_ref[0, :]
    hit = sl[:, None] == lane_slots                    # (SLAB, TILE)
    if jnp.issubdtype(va.dtype, jnp.floating):
        lo = jnp.array(-jnp.inf, va.dtype)
        hi = jnp.array(jnp.inf, va.dtype)
    else:
        info = jnp.iinfo(va.dtype)
        lo = jnp.array(info.min, va.dtype)
        hi = jnp.array(info.max, va.dtype)
    p_min = jnp.min(jnp.where(hit, va[:, None], hi), axis=0,
                    keepdims=True)
    p_max = jnp.max(jnp.where(hit, va[:, None], lo), axis=0,
                    keepdims=True)

    @pl.when(j == 0)
    def _init():
        out_min_ref[:, :] = p_min
        out_max_ref[:, :] = p_max

    @pl.when(j > 0)
    def _accumulate():
        out_min_ref[:, :] = jnp.minimum(out_min_ref[:, :], p_min)
        out_max_ref[:, :] = jnp.maximum(out_max_ref[:, :], p_max)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def pallas_segment_minmax(slots, values, capacity: int,
                          interpret: bool = False):
    """Per-slot (min, max) with the binned Pallas grid.  Empty slots
    return the identities (+inf/-inf or integer extremes) — callers
    mask by their own counts, exactly the arena contract.  Slots out
    of [0, capacity) drop."""
    C = capacity
    Cpad = ((C + TILE - 1) // TILE) * TILE
    n = values.shape[0]
    if n > MAX_BATCH:
        raise ValueError(
            f"batch of {n} exceeds MAX_BATCH={MAX_BATCH}: chunk the "
            "batch (segment_minmax_chunked)")
    npad = max(SLAB, ((n + SLAB - 1) // SLAB) * SLAB)
    slots_p = jnp.full(npad, Cpad, jnp.int32).at[:n].set(
        jnp.where((slots < 0) | (slots >= C), Cpad, slots).astype(jnp.int32))
    # pad values are never selected: pad slots point at no tile
    values_p = jnp.zeros(npad, values.dtype).at[:n].set(values)
    nslabs = npad // SLAB
    ntiles = Cpad // TILE
    grid = (ntiles, nslabs)
    outs = pl.pallas_call(
        _minmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, SLAB), lambda i, j: (j, 0)),
            pl.BlockSpec((1, SLAB), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE), lambda i, j: (i, 0)),
            pl.BlockSpec((1, TILE), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ntiles, TILE), values.dtype),
            jax.ShapeDtypeStruct((ntiles, TILE), values.dtype),
        ],
        interpret=interpret,
    )(slots_p.reshape(nslabs, SLAB), values_p.reshape(nslabs, SLAB))
    return tuple(o.reshape(-1)[:C] for o in outs)


def segment_minmax_chunked(slots, values, capacity: int,
                           interpret: bool | None = None):
    """`pallas_segment_minmax` over arbitrarily large batches."""
    if interpret is None:
        interpret = auto_interpret()
    n = values.shape[0]
    mn = mx = None
    for lo in range(0, max(n, 1), MAX_BATCH):
        m1, x1 = pallas_segment_minmax(
            slots[lo:lo + MAX_BATCH], values[lo:lo + MAX_BATCH],
            capacity, interpret=interpret)
        mn = m1 if mn is None else jnp.minimum(mn, m1)
        mx = x1 if mx is None else jnp.maximum(mx, x1)
    return mn, mx


def auto_interpret() -> bool:
    """True off the chip: the kernel executes in interpret mode —
    identical semantics (it is plain jnp), orders of magnitude slower,
    which is why the arenas only flip to pallas by explicit config.  On
    a TPU the arenas refuse the impl before this is reached."""
    import jax

    return jax.default_backend() != "tpu"


def segment_ingest_chunked(slots, values, capacity: int,
                           interpret: bool | None = None):
    """`pallas_segment_ingest` over arbitrarily large batches: static
    MAX_BATCH chunks accumulated on device.  Shapes are static under
    jit, so the chunk loop unrolls at trace time."""
    if interpret is None:
        interpret = auto_interpret()
    n = values.shape[0]
    s = c = None
    for lo in range(0, max(n, 1), MAX_BATCH):
        s1, c1 = pallas_segment_ingest(
            slots[lo:lo + MAX_BATCH], values[lo:lo + MAX_BATCH],
            capacity, interpret=interpret)
        s = s1 if s is None else s + s1
        c = c1 if c is None else c + c1
    return s, c


def segment_moments_chunked(slots, values, capacity: int,
                            interpret: bool | None = None):
    """`pallas_segment_moments` over arbitrarily large batches."""
    if interpret is None:
        interpret = auto_interpret()
    n = values.shape[0]
    s = c = sq = None
    for lo in range(0, max(n, 1), MAX_BATCH):
        s1, c1, q1 = pallas_segment_moments(
            slots[lo:lo + MAX_BATCH], values[lo:lo + MAX_BATCH],
            capacity, interpret=interpret)
        s = s1 if s is None else s + s1
        c = c1 if c is None else c + c1
        sq = q1 if sq is None else sq + q1
    return s, c, sq


def xla_segment_ingest(slots, values, capacity: int):
    """The validated default: XLA scatter-add (what the arenas use)."""
    idx = jnp.where((slots < 0) | (slots >= capacity), capacity,
                    slots).astype(jnp.int32)
    s = jnp.zeros(capacity + 1, values.dtype).at[idx].add(
        values, mode="drop")[:capacity]
    c = jnp.zeros(capacity + 1, jnp.int32).at[idx].add(
        1, mode="drop")[:capacity]
    return s, c
