"""Pallas TPU kernel for the M3TSZ phase-2 branchless field gather.

The two-phase decode (encoding/m3tsz_jax.py, round 6) splits the codec
into a cheap sequential bit-boundary scan (phase 1: control bits only,
emitting per-datapoint ``(bit_offset, field_width)`` lanes) and a fully
parallel field-extraction pass (phase 2) that pulls timestamp-DoD and
value payloads out of the packed stream words.  Phase 2's only
non-elementwise op is the GATHER: every (series, datapoint) lane needs
the 3 consecutive int32-packed words covering its bit offset.  On
XLA-CPU a ``take_along_axis`` is cheap; on TPU per-lane dynamic gathers
lower to masked reductions whose cost model XLA gets wrong for this
shape.  THIS module is the hand-scheduled alternative:

* ``extract_fields``    — the public entry: (S, P) offsets/widths over
  (S, W32) uint32 words -> (S, P) uint64 field values.  Routes to the
  Pallas kernel or the jnp fallback via ``M3_DECODE_EXTRACT``
  (``pallas`` | ``jnp`` | ``auto``; auto = pallas only on a real TPU
  backend, everywhere else jnp — identical semantics, so CPU-only
  hosts fall back cleanly, which tier-1 pins in
  tests/test_pallas_decode.py).
* The kernel walks a grid over (128-series blocks, point tiles, word
  tiles) on (8, 128)-tiled blocks — 32-bit only: the hit masks are 2-D
  (points down sublanes, word lanes across), the three gathered words
  accumulate into revisited scan-major (PT, 128) output blocks, and
  the 64-bit funnel shift happens OUTSIDE the kernel as plain
  elementwise XLA (no 64-bit integer ops inside Mosaic).  Compiled by
  Mosaic for a v5e since PR 22 (tests/test_chip_compile.py).

The word representation is int32-packed on purpose (ISSUE 6 / the
packed32 timer-drain precedent, round 5: fixed-width 32-bit lanes
are the decode-friendly layout DeXOR-class codecs standardize on):
u32 word ``k`` holds stream bits ``[32k, 32k+32)`` MSB-first, i.e. the
big-endian halves of the encoder's u64 words in order.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

U32 = jnp.uint32
U64 = jnp.uint64
I32 = jnp.int32

PT = 512   # datapoints per grid step (sublane axis of the hit mask)
WT = 512   # stream words per grid step: the (PT, WT) hit mask is the
           # kernel's VMEM high-water mark (3 x 1MB i32 compares)
SB = 128   # series per grid step: the lane axis of the (PT, SB) blocks


def _shr64(v, s):
    """u64 >> s with s possibly >= 64 (yields 0)."""
    s = jnp.asarray(s, U64)
    return jnp.where(s >= jnp.asarray(64, U64), jnp.asarray(0, U64),
                     v >> jnp.minimum(s, jnp.asarray(63, U64)))


def _funnel64(w0, w1, w2, offs, widths):
    """The shared bit funnel: 3 consecutive u32 words -> the ``widths``-
    bit field starting at bit ``offs & 31`` of w0, right-aligned in u64.
    Pure elementwise; identical math on both impls so the Pallas path is
    bit-equal to the jnp path by construction."""
    r = (offs & jnp.asarray(31, I32)).astype(U64)
    big = (w0.astype(U64) << jnp.asarray(32, U64)) | w1.astype(U64)
    tail = jnp.where(
        r > jnp.asarray(0, U64),
        _shr64(w2.astype(U64), jnp.asarray(32, U64) - r),
        jnp.asarray(0, U64))
    funnel = ((big << r) | tail)
    return _shr64(funnel, jnp.asarray(64, U64)
                  - jnp.minimum(widths.astype(U64), jnp.asarray(64, U64)))


def _gather3_jnp(words32, offs):
    """(w0, w1, w2) at word index offs>>5 via take_along_axis — the
    XLA-CPU-fast path.  Indices clip into the caller's >=2-word zero
    pad, so out-of-range offsets read zeros, never OOB."""
    W32 = words32.shape[1]
    w = jnp.clip(offs >> jnp.asarray(5, I32), 0, max(W32 - 3, 0))
    return tuple(
        jnp.take_along_axis(words32, w + jnp.asarray(k, I32), axis=1)
        for k in range(3))


def _gather_kernel(offs_ref, words_ref, w0_ref, w1_ref, w2_ref):
    """One (s, p, j) grid step: accumulate word-tile j's contribution
    to the three gathered-word lanes of SB series x PT datapoints.
    Blocks are Mosaic-tiled — offsets and outputs (PT, SB) scan-major
    (series on lanes), words (SB, WT) — and all-i32 (callers bitcast
    the u32 words; exactly one lane hits, so i32 sums are exact).  The
    series walk is a fori_loop: series r's word indices come out of the
    block as a (PT, 1) column by a one-hot lane reduction (no dynamic
    lane slicing, no relayout), its words as a (1, WT) sublane row;
    the (PT, WT) hit mask reduces over lanes back to a (PT, 1) column
    that lands in lane r of the revisited output block."""
    j = pl.program_id(2)
    lane_ids = j * WT + jax.lax.broadcasted_iota(I32, (1, WT), 1)
    sb_ids = jax.lax.broadcasted_iota(I32, (1, SB), 1)
    offs_blk = offs_ref[...]
    zero = jnp.zeros((), I32)
    outs = (w0_ref, w1_ref, w2_ref)

    @pl.when(j == 0)
    def _init():
        for ref in outs:
            ref[...] = jnp.zeros((PT, SB), I32)

    def body(r, carry):
        sel = sb_ids == r                                     # (1, SB)
        widx = jnp.sum(jnp.where(sel, offs_blk, zero), axis=1,
                       keepdims=True, dtype=I32)              # (PT, 1)
        row = words_ref[pl.ds(r, 1), :]                       # (1, WT)
        for k, ref in enumerate(outs):
            hit = (widx + jnp.asarray(k, I32)) == lane_ids    # (PT, WT)
            col = jnp.sum(jnp.where(hit, row, zero), axis=1,
                          keepdims=True, dtype=I32)           # (PT, 1)
            ref[...] = ref[...] + jnp.where(sel, col, zero)
        return carry

    # i32 bounds: under x64 a python-int fori index is i64 (no Mosaic)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(SB), body,
                      jnp.asarray(0, I32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather3_pallas(words32, offs, interpret: bool):
    """The Pallas gather: same (w0, w1, w2) contract as _gather3_jnp."""
    S, W32 = words32.shape
    P = offs.shape[1]
    Wpad = ((W32 + WT - 1) // WT) * WT
    Ppad = ((P + PT - 1) // PT) * PT
    Spad = ((S + SB - 1) // SB) * SB
    wp = jnp.zeros((Spad, Wpad), I32).at[:S, :W32].set(
        jax.lax.bitcast_convert_type(words32, I32))
    # Clip like the jnp path so both impls read the same padded zeros
    # for out-of-range offsets (bit-parity is the contract).
    oc = jnp.clip(offs >> jnp.asarray(5, I32), 0, max(W32 - 3, 0))
    # Padding lanes carry an impossible word index (>= Wpad) so they
    # match no word lane and gather 0.
    op = jnp.full((Ppad, Spad), Wpad, I32).at[:P, :S].set(oc.T)
    grid = (Spad // SB, Ppad // PT, Wpad // WT)
    out_shape = [jax.ShapeDtypeStruct((Ppad, Spad), I32)] * 3
    spec_pt = pl.BlockSpec((PT, SB), lambda s, p, j: (p, s))
    outs = pl.pallas_call(
        _gather_kernel,
        grid=grid,
        in_specs=[
            spec_pt,
            pl.BlockSpec((SB, WT), lambda s, p, j: (s, j)),
        ],
        out_specs=[spec_pt] * 3,
        out_shape=out_shape,
        interpret=interpret,
    )(op, wp)
    return tuple(jax.lax.bitcast_convert_type(o[:P, :S].T, U32)
                 for o in outs)


_IMPLS = ("pallas", "jnp", "auto")


def configured_impl() -> str:
    impl = os.environ.get("M3_DECODE_EXTRACT", "auto").strip() or "auto"
    if impl not in _IMPLS:
        raise ValueError(
            f"M3_DECODE_EXTRACT={impl!r}: expected one of {_IMPLS}")
    return impl


def resolved_impl() -> str:
    """'pallas' only where Mosaic actually compiles (a real TPU
    backend); every other host resolves to the identical-semantics jnp
    path — the clean-fallback contract tier-1 guards."""
    impl = configured_impl()
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def auto_interpret() -> bool:
    """Compiled Mosaic needs a TPU; anywhere else the kernel runs in
    interpret mode (plain jnp semantics, slow — test-only)."""
    return jax.default_backend() != "tpu"


def extract_fields64_t(words_t, offs_t, widths_t):
    """Scan-major u64 variant of :func:`extract_fields_t` for the jnp
    path: ``words_t`` is the (W, S) uint64 stream-word array TRANSPOSED
    so the series axis is minor.  A 64-bit read at any bit offset spans
    at most 2 consecutive u64 words, so this needs one fewer gather per
    lane than the u32 path AND skips the int32 repack of the whole
    stream array — on XLA-CPU the repack (transpose + stack + reshape
    of (2W, S)) cost more than the gathers themselves (round-6
    measurement).  The Pallas kernel keeps the u32 contract (no 64-bit
    integer ops inside Mosaic); bit-parity between the two paths is
    pinned by tests/test_pallas_decode.py."""
    W = words_t.shape[0]
    w = jnp.clip(offs_t >> jnp.asarray(6, I32), 0, max(W - 2, 0))
    wa = jnp.take_along_axis(words_t, w, axis=0, mode="promise_in_bounds")
    wb = jnp.take_along_axis(words_t, w + jnp.asarray(1, I32), axis=0,
                             mode="promise_in_bounds")
    r = (offs_t & jnp.asarray(63, I32)).astype(U64)
    big = (wa << r) | jnp.where(
        r > jnp.asarray(0, U64),
        wb >> (jnp.asarray(64, U64) - jnp.maximum(r, jnp.asarray(1, U64))),
        jnp.asarray(0, U64))
    return _shr64(big, jnp.asarray(64, U64)
                  - jnp.minimum(widths_t.astype(U64), jnp.asarray(64, U64)))


def extract_fields_t(words32_t, offs_t, widths_t, impl: str | None = None,
                     interpret: bool | None = None):
    """Scan-major variant of :func:`extract_fields`: ``words32_t`` is
    (W32, S) — the int32-packed stream words TRANSPOSED so the series
    axis is minor — and ``offs_t``/``widths_t`` are (F, S), the layout
    ``lax.scan`` stacks lane tables in.  Returns (F, S) uint64.

    On the jnp path this gathers along axis 0 directly (no transposes
    of the F-sized arrays — on XLA-CPU the three transposes the
    row-major entry point would need cost more than the gather itself);
    the Pallas kernel keeps its proven row-major grid, so that impl
    transposes at the boundary where transposes are cheap (TPU).
    """
    if impl is None:
        impl = resolved_impl()
    if impl == "pallas":
        out = extract_fields(words32_t.T, offs_t.T, widths_t.T,
                             impl=impl, interpret=interpret)
        return out.T
    W32 = words32_t.shape[0]
    w = jnp.clip(offs_t >> jnp.asarray(5, I32), 0, max(W32 - 3, 0))
    w0, w1, w2 = (
        jnp.take_along_axis(words32_t, w + jnp.asarray(k, I32), axis=0,
                            mode="promise_in_bounds")
        for k in range(3))
    return _funnel64(w0, w1, w2, offs_t, widths_t)


def extract_fields(words32, offs, widths, impl: str | None = None,
                   interpret: bool | None = None):
    """Extract ``widths[s, p]``-bit fields at bit offsets ``offs[s, p]``
    from int32-packed stream words ``words32`` (S, W32).

    Words are MSB-first u32 lanes (bits [32k, 32k+32) in word k — the
    big-endian halves of the codec's u64 words).  Width 0 yields 0;
    offsets past the stream read the caller's zero padding (callers
    pad >= 2 words).  Returns (S, P) uint64, right-aligned fields.
    """
    if impl is None:
        impl = resolved_impl()
    if impl == "pallas":
        if interpret is None:
            interpret = auto_interpret()
        w0, w1, w2 = _gather3_pallas(words32, offs, interpret=interpret)
    else:
        w0, w1, w2 = _gather3_jnp(words32, offs)
    return _funnel64(w0, w1, w2, offs, widths)
