"""Pallas TPU kernel for the M3TSZ phase-2 word PLACEMENT.

The two-phase encode (encoding/m3tsz_jax.py, round 9) mirrors the
round-6 decode split: a cheap sequential scan resolves the format into
per-datapoint ``(value, bit offset, width)`` lanes, and phase 2
assembles the output stream words from the lane fragments.  Placement
is a SCATTER by construction — every fragment lands at its word index
— and TPU scatters measured ~1us/element (round 5), so
this kernel inverts it into the same masked-sum shape as the decode
gather kernel (parallel/pallas_decode.py): walk a grid over
(128-series blocks, word tiles, fragment tiles), compare each
fragment's word key against the tile's word lane ids, and accumulate
the hits into revisited (128, WT) output blocks.  Fragments at distinct bit ranges
never overlap, so the u32 partial sums are exact ORs.

32-bit only on purpose (no 64-bit integer ops inside Mosaic): the
caller splits each u64 fragment into big-endian u32 halves — half
``h`` of the fragment at u64 word ``k`` targets u32 word ``2k + h`` —
and recombines the (S, 2W) u32 output into u64 stream words outside
the kernel, exactly how the decode kernel funnels outside Mosaic.

``place_words`` is the jnp/Pallas seam used by ``M3_ENCODE_PLACE=
pallas`` (interpret mode anywhere without a real TPU backend — the
clean-fallback contract tier-1 pins); ``place_words_jnp`` is the
scatter-add reference the parity tests compare against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

U32 = jnp.uint32
U64 = jnp.uint64
I32 = jnp.int32

FT = 512   # fragments per grid step (sublane axis of the hit mask)
WT = 512   # output u32 words per grid step; the (FT, WT) hit mask is
           # the kernel's VMEM high-water mark
SB = 128   # series per grid step: the lane axis of the (FT, SB) blocks


def _place_kernel(keys_ref, vals_ref, out_ref):
    """One (s, w, f) grid step: accumulate fragment-tile f's
    contribution to word tile w of SB series.  Blocks are Mosaic-tiled
    — keys/fragments (FT, SB) scan-major (series on lanes), output
    (SB, WT) — and all-i32 (callers bitcast the u32 halves; disjoint
    bit ranges make the wrapping i32 sums exact ORs).  The series walk
    is a fori_loop: series r's keys and fragments come out of their
    blocks as (FT, 1) columns by a one-hot lane reduction, the
    (FT, WT) hit mask reduces over sublanes to a (1, WT) row that adds
    into sublane r of the revisited output block — the decode gather
    kernel with gather/scatter roles reversed."""
    w = pl.program_id(1)
    f = pl.program_id(2)
    lane_ids = w * WT + jax.lax.broadcasted_iota(I32, (1, WT), 1)
    sb_ids = jax.lax.broadcasted_iota(I32, (1, SB), 1)
    keys_blk = keys_ref[...]
    vals_blk = vals_ref[...]
    zero = jnp.zeros((), I32)

    @pl.when(f == 0)
    def _init():
        out_ref[...] = jnp.zeros((SB, WT), I32)

    def body(r, carry):
        sel = sb_ids == r                                     # (1, SB)
        keys = jnp.sum(jnp.where(sel, keys_blk, zero), axis=1,
                       keepdims=True, dtype=I32)              # (FT, 1)
        vals = jnp.sum(jnp.where(sel, vals_blk, zero), axis=1,
                       keepdims=True, dtype=I32)              # (FT, 1)
        hit = keys == lane_ids                                # (FT, WT)
        part = jnp.sum(jnp.where(hit, vals, zero), axis=0,
                       keepdims=True, dtype=I32)              # (1, WT)
        out_ref[pl.ds(r, 1), :] = out_ref[pl.ds(r, 1), :] + part
        return carry

    # i32 bounds: under x64 a python-int fori index is i64 (no Mosaic)
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(SB), body,
                      jnp.asarray(0, I32))


@functools.partial(jax.jit, static_argnames=("w32", "interpret"))
def _place_pallas(vals32, keys32, w32: int, interpret: bool):
    """(S, F) u32 fragments + u32-word keys -> (S, w32) u32 sums."""
    S, F = vals32.shape
    Fpad = ((F + FT - 1) // FT) * FT
    Wpad = ((w32 + WT - 1) // WT) * WT
    Spad = ((S + SB - 1) // SB) * SB
    # Padding fragments carry an impossible word key (>= Wpad) so they
    # match no word lane; real keys beyond w32 are dropped the same way
    # (the caller's fallback flag owns stream-overflow reporting).
    kp = jnp.full((Fpad, Spad), Wpad, I32).at[:F, :S].set(
        jnp.minimum(keys32, jnp.asarray(Wpad, I32)).T)
    vp = jnp.zeros((Fpad, Spad), I32).at[:F, :S].set(
        jax.lax.bitcast_convert_type(vals32, I32).T)
    grid = (Spad // SB, Wpad // WT, Fpad // FT)
    spec_f = pl.BlockSpec((FT, SB), lambda s, w, f: (f, s))
    out = pl.pallas_call(
        _place_kernel,
        grid=grid,
        in_specs=[spec_f, spec_f],
        out_specs=pl.BlockSpec((SB, WT), lambda s, w, f: (s, w)),
        out_shape=jax.ShapeDtypeStruct((Spad, Wpad), I32),
        interpret=interpret,
    )(kp, vp)
    return jax.lax.bitcast_convert_type(out[:S, :w32], U32)


def auto_interpret() -> bool:
    """Compiled Mosaic needs a TPU; anywhere else the kernel runs in
    interpret mode (plain jnp semantics, slow — test-only)."""
    return jax.default_backend() != "tpu"


def _split32(frags, keys):
    """u64 fragments -> interleaved big-endian u32 halves + u32 keys."""
    S, F = frags.shape
    vals32 = jnp.stack(
        [(frags >> jnp.asarray(32, U64)).astype(U32),
         (frags & jnp.asarray(0xFFFFFFFF, U64)).astype(U32)],
        axis=2).reshape(S, 2 * F)
    keys32 = jnp.stack(
        [keys * jnp.asarray(2, I32),
         keys * jnp.asarray(2, I32) + jnp.asarray(1, I32)],
        axis=2).reshape(S, 2 * F)
    return vals32, keys32


def place_words(frags, keys, out_words: int,
                interpret: bool | None = None):
    """Assemble (S, out_words) u64 stream-word contributions from u64
    ``frags`` at u64-word indices ``keys`` (both (S, F)).  Fragments
    with keys outside [0, out_words) are dropped (the encoder's
    fallback flag reports stream overflow); fragment bit ranges must
    be disjoint (the M3TSZ lane contract), making the u32 sums exact.
    """
    if interpret is None:
        interpret = auto_interpret()
    vals32, keys32 = _split32(frags, keys)
    out32 = _place_pallas(vals32, keys32, 2 * out_words,
                          interpret=interpret)
    return ((out32[:, 0::2].astype(U64) << jnp.asarray(32, U64))
            | out32[:, 1::2].astype(U64))


def place_words_jnp(frags, keys, out_words: int):
    """Scatter-add reference semantics for :func:`place_words` — the
    parity oracle (tests/test_encode_fuzz.py pins kernel == this)."""
    S, F = frags.shape
    sidx = jnp.broadcast_to(jnp.arange(S, dtype=I32)[:, None], (S, F))
    ok = (keys >= 0) & (keys < out_words)
    out = jnp.zeros((S, out_words), U64)
    return out.at[sidx, jnp.clip(keys, 0, out_words - 1)].add(
        jnp.where(ok, frags, jnp.zeros((), U64)))
