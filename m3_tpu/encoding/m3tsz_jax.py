"""Batched M3TSZ encode/decode as JAX array programs.

The reference codec is an inherently sequential per-series bit-stream
state machine (``src/dbnode/encoding/m3tsz/encoder.go``,
``iterator.go``).  The TPU-native formulation:

* **Encode** — two phases, the mirror of decode (round 9).  Phase 1 is
  a ``lax.scan`` over timesteps carrying ONLY the narrow codec control
  state (timestamp delta, XOR hysteresis, sig-bit tracker), emitting
  per-datapoint lane tables: four (value, width) fields per point,
  composed with static shift-ors — no bit assembly rides the scan.
  Phase 2 computes every datapoint's absolute output bit offset with
  ONE exclusive prefix sum over the widths and assembles output words
  scatter-free (cumsum-interval gathers, or the Pallas placement
  kernel on TPU — ``M3_ENCODE_PLACE``; disjoint bit ranges make add
  equivalent to or).
* **Decode** — ``lax.scan`` over datapoint slots operating on (S,)
  arrays, with a dynamic bit-cursor per series.  Bit reads never touch
  memory: each lane carries a 32-word (2048-bit) window of its stream
  in the scan carry, field reads are register-level selects/shifts
  against a 9-word buffer extracted once per step, and the window is
  refilled 16 words at a time by a block gather guarded by a scalar
  ``lax.cond`` (so the O(S*W) gather cost is paid only on the ~1/15th
  of steps where some lane runs low, not ~24x per step as a naive
  per-field gather formulation would).  100K series decode in parallel
  — the batched ReaderIterator configuration from BASELINE.json.
* All float64 arithmetic demanded by the format (int-optimization
  classification, ``m3tsz.go:78-118``) runs as exact integer emulation
  (``f64_emul.py``), so results are bit-identical on TPU, which has no
  float64 ALU.

Series that would exercise the reference's float64 *rounding* behavior on
values above 2^53, or that carry annotations, are flagged in the returned
``fallback`` mask; callers re-run those through the scalar host codec
(``m3tsz.py``).  This mirrors the host/device split the framework uses
throughout: the device owns the dense numeric 99.99%, the host owns the
long tail.
"""

from __future__ import annotations

import functools
import os
import threading

import numpy as np

import m3_tpu  # noqa: F401  (enables x64 at the framework root)
import jax
import jax.numpy as jnp
from jax import lax

from m3_tpu.core.xtime import Unit
from m3_tpu.encoding import f64_emul as fe
from m3_tpu.encoding.scheme import tail_bytes
from m3_tpu.x import devguard, membudget

U64 = jnp.uint64
I64 = jnp.int64
I32 = jnp.int32
MASK64 = (1 << 64) - 1

# Datapoints encoded per scan-loop iteration (lax.scan unroll): larger
# amortizes per-step overhead and keeps the carry fused between chained
# bodies, but MULTIPLIES compile time of the step body (unroll=4 took
# the S=2000 decode compile from ~40s to 9+ minutes on XLA-CPU —
# measured round 4; the round-5 "unroll=2 decodes 13x slower" spill
# was the old WIDE-carry formulations' — both are gone since the
# two-phase splits).  Round-9 measurement on the narrow-carry encode
# scan: unroll=2 is compile-slower and within noise at steady state on
# XLA-CPU, so the default stays 1; the TPU tradeoff is separately
# measured by the watcher's decode_u* stages.
try:
    _SCAN_UNROLL = max(1, int(os.environ.get("M3_SCAN_UNROLL", "1")))
except ValueError:
    _SCAN_UNROLL = 1
# The DECODE scan's unroll is tuned separately: its carry is a handful
# of narrow (S,) lanes (no word window since the round-6 two-phase
# split), so chaining two step bodies wins ~11% on XLA-CPU where the
# encode scan's wide carry still spills.
try:
    _DECODE_UNROLL = max(1, int(os.environ.get("M3_DECODE_UNROLL", "2")))
except ValueError:
    _DECODE_UNROLL = 2

# time-unit byte -> nanos (0 = invalid/None)
_UNIT_NANOS = np.zeros(16, dtype=np.int64)
for _u_ in Unit:
    _UNIT_NANOS[int(_u_)] = _u_.nanos()

_BITS_1E13 = np.frombuffer(np.float64(10.0**13).tobytes(), dtype=np.uint64)[0]
_BITS_2_63 = np.frombuffer(np.float64(2.0**63).tobytes(), dtype=np.uint64)[0]
_I64_MIN = -(2**63)
_PRECISION_LIMIT = 1 << 53  # beyond this the reference's f64 math rounds


def _c(x, dtype=U64):
    return jnp.asarray(x, dtype=dtype)


def _shl(v, s):
    """uint64 << s with s possibly >= 64 (yields 0)."""
    s = _c(s)
    return jnp.where(s >= _c(64), _c(0), v << jnp.minimum(s, _c(63)))


def _shr(v, s):
    s = _c(s)
    return jnp.where(s >= _c(64), _c(0), v >> jnp.minimum(s, _c(63)))


def _num_sig(v):
    """Number of significant bits of uint64 (0 for 0)."""
    return jnp.where(
        v == _c(0), _c(0, I32),
        (_c(64, I32) - lax.clz(v.astype(I64)).astype(I32)))


def _sign_extend(v, nbits):
    """Sign-extend the low ``nbits`` of uint64 v to int64 (nbits >= 1)."""
    shift = _c(64) - _c(nbits)
    return (_shl(v, shift)).astype(I64) >> jnp.minimum(shift, _c(63)).astype(I64)


# ---------------------------------------------------------------------------
# Value classification: exact convertToIntFloat (m3tsz.go:78-118)
# ---------------------------------------------------------------------------


def _mul10_me(mant, exp2):
    """Exact IEEE float64 multiply by 10 in the (mantissa, exp2)
    representation: value = mant * 2^exp2, mant < 2^53 (mant in
    [2^52, 2^53) for normals, unnormalized with exp2 == -1074 for
    subnormals).  Equivalent to ``fe.mul10(bits)`` without the
    pack/unpack round-trip through the bit representation — the
    classify loop below runs this 7 times per datapoint, and the
    full ``_pack`` (msb search, subnormal clamps, carry fixes) was
    ~3x the ops of this direct form (round-9 encode profiling)."""
    p = mant * _c(10)  # < 2^57: never overflows
    L = fe.msb_index(jnp.maximum(p, _c(1)))
    sh = jnp.maximum(L, _c(52)) - _c(52)
    # sh > 0 only when p >= 2^53, i.e. the result is normal and RNE
    # rounds at its 53-bit ulp; p < 2^53 stays exact at the carried
    # exp2 granularity (subnormals keep their fixed 2^-1074 ulp, and
    # exp2 + sh can never sink below -1074 since sh >= 0).
    q = fe._round_shift_right_even(p, sh)
    carried = q >= _c(1 << 53)
    q = jnp.where(carried, q >> _c(1), q)
    exp2p = exp2 + sh.astype(I64) + carried.astype(I64)
    return q, jnp.where(mant == _c(0), exp2, exp2p)


def classify_value(v_bits, cur_mult):
    """Returns (val int64 scaled, mult int32, is_float bool, precision_flag bool).

    ``precision_flag`` marks values whose downstream encoding would hit
    float64 rounding in the reference (|val| > 2^53): callers must fall
    back to the scalar codec for those series.
    """
    v_bits = _c(v_bits)
    sign = (v_bits >> _c(63)) != _c(0)
    abs_b = v_bits & _c(fe.MASK63)
    _, exp, _ = fe.split(abs_b)
    special = exp == _c(0x7FF)  # NaN / Inf never take the int paths

    # Quick path: already integral and v < 2^63 (float compare).
    ipart0, frac_zero0 = fe.floor_parts(abs_b)
    v_lt_maxint = sign | (abs_b < _c(_BITS_2_63))
    quick_ok = (cur_mult == _c(0, I32)) & v_lt_maxint & frac_zero0 & ~special
    # Go's uint64(int64(v)) saturation for out-of-range magnitudes.
    sat = abs_b >= _c(_BITS_2_63)
    quick_mag = jnp.where(sat, _c(_I64_MIN, I64), ipart0.astype(I64))
    quick_val = jnp.where(sign & ~sat, -quick_mag, quick_mag)

    # Multiplier loop: val = v * 10^cur, then *10 per iteration, looking
    # for a value within 1 ulp of an integer.  The loop runs in the
    # (mantissa, exp2) domain: with s = -exp2 and frac = mant & (2^s-1),
    # the reference's Modf/Nextafter conditions (see the scalar codec's
    # ulp reduction, and the bits-domain forms this replaced:
    # ``val_bits <= bits(ip)+1`` / ``val_bits+1 >= bits(ip+1)``) reduce
    # EXACTLY to ``frac <= 1`` / ``frac >= 2^s - 1``: positive float
    # bit patterns are value-ordered and increment across binades, so
    # "within one ulp of an integer" is a pure property of the fraction
    # field.  This cuts the two uint_to_f64_bits packs + floor_parts +
    # full mul10 per iteration (~110 ops) to ~50, and every byte is
    # still pinned by the oracle/corpus/fuzz suites.
    val_bits0 = fe.mul_pow10(abs_b, cur_mult)
    mant, exp2 = fe._mantissa_and_exp2(val_bits0)
    found = jnp.zeros_like(sign)
    res_i = jnp.zeros_like(abs_b)
    res_mult = jnp.zeros_like(cur_mult)
    for k in range(7):
        # current value's bit pattern (monotone compare key): normals
        # re-pack from (mant, exp2); subnormals (unnormalized mant,
        # exp2 == -1074) ARE their bit pattern.
        vb_cur = jnp.where(
            mant < _c(fe.IMPLICIT), mant,
            ((exp2 + _c(1075, I64)).astype(U64) << _c(52))
            | (mant & _c(fe.MASK52)))
        active = (~quick_ok) & (~found) & (_c(k, I32) >= cur_mult) & (
            vb_cur < _c(_BITS_1E13)) & ~special
        s = jnp.clip(-exp2, 0, 63).astype(U64)
        big_s = -exp2 > _c(63, I64)  # val << 1: ip == 0, frac == mant
        frac = mant & ((_c(1) << s) - _c(1))
        frac = jnp.where(big_s, mant, frac)
        ip = jnp.where(big_s, _c(0), mant >> s)
        # active lanes have val < 1e13 < 2^53 => exp2 <= 0, so the
        # s == -exp2 clamp only ever bites inactive lanes (discarded).
        take_i = frac <= _c(1)
        take_i1 = (~take_i) & (frac >= ((_c(1) << s) - _c(1)))
        hit = active & (take_i | take_i1)
        chosen = jnp.where(take_i, ip, ip + _c(1))
        res_i = jnp.where(hit, chosen, res_i)
        res_mult = jnp.where(hit, _c(k, I32), res_mult)
        found = found | hit
        advance = active & ~hit
        m10, e10 = _mul10_me(mant, exp2)
        mant = jnp.where(advance, m10, mant)
        exp2 = jnp.where(advance, e10, exp2)

    loop_val = jnp.where(sign, -(res_i.astype(I64)), res_i.astype(I64))

    is_float = ~quick_ok & ~found
    val = jnp.where(quick_ok, quick_val, jnp.where(found, loop_val, _c(0, I64)))
    mult = jnp.where(found & ~quick_ok, res_mult, _c(0, I32))
    # Signed compares (not jnp.abs) so INT64_MIN saturations are caught too.
    precision_flag = ~is_float & ((val > _c(_PRECISION_LIMIT, I64)) |
                                  (val < _c(-_PRECISION_LIMIT, I64)))
    return val, mult, is_float, precision_flag


# ---------------------------------------------------------------------------
# Encoder phase 1: branchless per-datapoint lane emission
# ---------------------------------------------------------------------------
#
# The round-9 mirror of the two-phase decode: the sequential scan no
# longer ASSEMBLES bits (the old formulation threaded a 4-word staging
# buffer through ~25 dynamic-offset `_bb_append` funnels per step —
# ~7.8K element-ops/datapoint, and the reason encode compiled in ~11s
# and ran at ~0.5M dp/s while decode did 7M).  Phase 1 only RESOLVES
# the format: each datapoint's emission is a concatenation of a
# bounded set of variable-width fields, and every path's fields fold
# into at most FOUR value lanes, each <= 64 bits, composed with plain
# shift-ors (static in-lane offsets — no funnel):
#
#   t0  timestamp control+payload: the dod opcode fused with its
#       payload when it fits a word (<= 36 bits), or the 19-bit
#       TU-marker prefix / 4-bit default-bucket opcode otherwise
#   t1  the 64-bit dod payload (TU path / default bucket), else empty
#   v0  value control: mode/update/sig/mult/sign or XOR opcode+lead/
#       meaningful fields (<= 16 bits)
#   v1  value payload: full float, XOR window, or int diff (<= 64)
#
# Widths ride four i32 lanes beside the values; the scan stacks both
# as (T, 4, S) tables whose (4T, S) stream-order reshape is free.
# Phase 2 turns the widths into absolute bit offsets with ONE
# exclusive prefix sum and assembles output words from the
# (value, offset, width) lanes — see `_encode_batch_device`.  The lane
# table is format-agnostic on purpose: a DeXOR-class codec (ROADMAP
# item 5) emits through the same (value, width) contract with its own
# field resolution.


def _cat(acc, add_val, add_n, enable=None):
    """Append the low ``add_n`` (< 64, possibly traced) bits of
    ``add_val`` to the (value, nbits) accumulator — MSB-first: earlier
    fields land in higher bits, matching OStream order."""
    val, n = acc
    add_n = _c(add_n, I32)
    if enable is not None:
        add_n = jnp.where(enable, add_n, _c(0, I32))
    sh = add_n.astype(U64)
    val = (val << sh) | (_c(add_val) & ((_c(1) << sh) - _c(1)))
    return val, n + add_n


# Non-default delta-of-delta buckets: (opcode, num_opcode_bits, num_value_bits).
_DOD_BUCKETS = ((0b10, 2, 7), (0b110, 3, 9), (0b1110, 4, 12))


def _dod_lanes(dod, default_unit_is_32bit: bool):
    """Bucketed delta-of-delta (timestamp_encoder.go:131-221) as lane
    fields: (t0, n_t0, need64, overflow).  Opcode and payload compose
    into the single <= 36-bit t0 field except the 64-bit default
    bucket, whose payload rides the t1 lane (``need64``); ``overflow``
    marks a dod outside the 32-bit default bucket of second/
    millisecond units (the reference raises OverflowError there)."""
    d = dod.astype(U64)
    is_zero = dod == _c(0, I64)
    fits = []
    for _, _, nvb in _DOD_BUCKETS:
        lo, hi = -(1 << (nvb - 1)), (1 << (nvb - 1)) - 1
        fits.append((dod >= _c(lo, I64)) & (dod <= _c(hi, I64)))
    t1_ = (~is_zero) & fits[0]
    t2_ = (~is_zero) & ~fits[0] & fits[1]
    t3_ = (~is_zero) & ~fits[1] & fits[2]
    take_def = (~is_zero) & ~fits[2]
    if default_unit_is_32bit:
        t0_def = (_c(0b1111) << _c(32)) | (d & _c(0xFFFFFFFF))
        n_def = _c(36, I32)
        need64 = jnp.zeros_like(is_zero)
        overflow = take_def & ((dod < _c(-(2**31), I64))
                               | (dod > _c(2**31 - 1, I64)))
    else:
        t0_def = _c(0b1111)
        n_def = _c(4, I32)
        need64 = take_def
        overflow = jnp.zeros_like(is_zero)
    t0 = jnp.where(
        is_zero, _c(0),
        jnp.where(t1_, (_c(0b10) << _c(7)) | (d & _c(0x7F)),
        jnp.where(t2_, (_c(0b110) << _c(9)) | (d & _c(0x1FF)),
        jnp.where(t3_, (_c(0b1110) << _c(12)) | (d & _c(0xFFF)), t0_def))))
    n_t0 = jnp.where(
        is_zero, _c(1, I32),
        jnp.where(t1_, _c(9, I32),
        jnp.where(t2_, _c(12, I32),
        jnp.where(t3_, _c(16, I32), n_def))))
    return t0, n_t0, need64, overflow


def _int_sig_mult_ctrl(acc, num_sig_st, max_mult, sig, mult, float_changed):
    """writeIntSigMult (encoder.go:235-250) as control-field
    composition onto ``acc``: the sig-change cascade
    (sb1 [sb2 sig6]) then the multiplier update (mb1 [mult3]).
    Returns (acc, new num_sig, new max_mult)."""
    sig_changed = num_sig_st != sig
    zero_sig = sig == _c(0, I32)
    acc = _cat(acc, jnp.where(sig_changed, _c(1), _c(0)), 1)
    acc = _cat(acc, jnp.where(zero_sig, _c(0), _c(1)), 1, enable=sig_changed)
    acc = _cat(acc, (sig - _c(1, I32)).astype(U64), 6,
               enable=sig_changed & ~zero_sig)
    mult_up = mult > max_mult
    # after WriteIntSig num_sig == sig, so condition reduces to:
    float_only = (~mult_up) & (max_mult == mult) & float_changed
    wr = mult_up | float_only
    acc = _cat(acc, jnp.where(wr, _c(1), _c(0)), 1)
    acc = _cat(acc, mult.astype(U64), 3, enable=wr)
    return acc, sig, jnp.where(mult_up, mult, max_mult)


def _track_new_sig(num_sig_st, cur_hl, num_lower, sig):
    """IntSigBitsTracker.TrackNewSig (int_sig_bits_tracker.go:68-91)."""
    new_sig = num_sig_st
    grow = sig > num_sig_st
    new_sig = jnp.where(grow, sig, new_sig)
    shrink = (~grow) & ((num_sig_st - sig) >= _c(3, I32))
    chl = jnp.where(shrink & (num_lower == _c(0, I32)), sig,
                    jnp.where(shrink & (sig > cur_hl), sig, cur_hl))
    # The lower-sig streak counter resets only on the NEITHER branch
    # (within-threshold sig): a GROW step leaves it intact — Go keeps
    # t.NumLowerSig untouched when numSig > t.NumSig
    # (int_sig_bits_tracker.go:68-91).  Resetting on grow desynced the
    # device encoder's shrink timing from the scalar oracle on
    # grow-interleaved streams (caught by the round-5 bench's
    # device-vs-native byte-identity stage, 22/2000 series).
    nl = jnp.where(shrink, num_lower + _c(1, I32),
                   jnp.where(grow, num_lower, _c(0, I32)))
    fire = shrink & (nl >= _c(5, I32))
    new_sig = jnp.where(fire, chl, new_sig)
    nl = jnp.where(fire, _c(0, I32), nl)
    return new_sig, chl, nl


def _encode_step(carry, xs, unit: int, default_unit_is_32bit: bool):
    """One datapoint for one series: resolve the format (field values
    and widths) WITHOUT assembling bits.  The carry is only the narrow
    codec control state; the step emits the four value lanes
    (t0, t1, v0, v1) plus their packed widths — see the lane-table
    comment above — and phase 2 (`_encode_batch_device`) places them
    into the output stream with one prefix sum.  The body is one
    branch-free straight line, mirroring the decode step's contract."""
    (prev_time, prev_delta, tu_none, int_val, max_mult, is_float,
     prev_fbits, prev_xor, num_sig_st, cur_hl, num_lower, is_first,
     fallback) = carry
    t, v_bits, valid = xs

    # ---- timestamp (timestamp_encoder.go:72-129) ----
    # first datapoint of the stream: 64-bit start already emitted by the
    # caller via the start word (prev_time holds start).  Time-unit
    # change marker if the initial unit was None (unaligned start):
    # 0x100 marker(9) + TU opcode(2) + unit byte(8) — one 19-bit static
    # constant — then the full 64-bit nanosecond dod on the t1 lane.
    emit_tu = is_first & tu_none
    time_delta = t - prev_time
    dod_ns = time_delta - prev_delta
    unit_nanos = int(Unit(unit).nanos())
    dod_units = dod_ns // _c(unit_nanos, I64)  # deltas divisible (checked below)
    div_ok = (dod_ns % _c(unit_nanos, I64)) == _c(0, I64)
    t0_b, n_t0_b, need64, dod_overflow = _dod_lanes(dod_units,
                                                    default_unit_is_32bit)
    tu_const = (0x100 << 10) | (0b10 << 8) | (unit & 0xFF)
    t0 = jnp.where(emit_tu, _c(tu_const), t0_b)
    n_t0 = jnp.where(emit_tu, _c(19, I32), n_t0_b)
    t1_64 = emit_tu | (need64 & ~emit_tu)
    t1 = jnp.where(emit_tu, dod_ns.astype(U64), dod_units.astype(U64))
    n_t1 = jnp.where(t1_64, _c(64, I32), _c(0, I32))
    new_prev_delta = jnp.where(emit_tu, _c(0, I64), time_delta)
    new_prev_time = t
    new_tu_none = tu_none & ~emit_tu

    # ---- value ----
    val, mult, v_is_float, prec = classify_value(v_bits, max_mult)
    acc0 = (_c(0), _c(0, I32))

    # ---------- first value (encoder.go:112-146) ----------
    # float mode: '1' + the raw 64 bits; int mode: '0' + sig/mult
    # cascade + sign on v0, the magnitude (sig_f bits) on v1.  The
    # cascade itself is emitted by the SHARED _int_sig_mult_ctrl call
    # below (first-value and to-int-update paths run the identical
    # writeIntSigMult; only the opcode prefix, the candidate sig and
    # the float_changed flag differ, so the inputs select per path
    # instead of running the ~60-op cascade twice).
    neg_diff = val >= _c(0, I64)  # inverted: diff = 0 - val
    mag = jnp.abs(val).astype(U64)
    sig_f = _num_sig(mag)

    # ---------- next value (encoder.go:148-231) ----------
    val_diff = int_val - val
    # float path trigger (diff overflow impossible: flagged by prec limit)
    go_float = v_is_float
    was_int = ~is_float

    # writeFloatVal: int->float '001'+float64; repeat '01'; else '1' +
    # Gorilla XOR (float_encoder_iterator.go:82-103) — zero '0',
    # contained '10'+window, uncontained '11'+lead6+meaningful6+window
    # (the leading '1' value bit fuses into each opcode below).
    repeat_f = is_float & (v_bits == prev_fbits)
    cur_xor = prev_fbits ^ v_bits
    xor_zero = cur_xor == _c(0)
    pl = jnp.where(prev_xor == _c(0), _c(64, I32),
                   lax.clz(prev_xor.astype(I64)).astype(I32))
    # trailing zeros = index of lowest set bit
    pt = jnp.where(prev_xor == _c(0), _c(0, I32),
                   (_num_sig(prev_xor & (~prev_xor + _c(1))) - _c(1, I32)))
    cl = lax.clz(jnp.maximum(cur_xor, _c(1)).astype(I64)).astype(I32)
    ct = _num_sig(cur_xor & (~cur_xor + _c(1))) - _c(1, I32)
    contained = (~xor_zero) & (cl >= pl) & (ct >= pt)
    meaningful = _c(64, I32) - cl - ct
    v0_unc = ((_c(0b111) << _c(12)) | (cl.astype(U64) << _c(6))
              | (meaningful - _c(1, I32)).astype(U64))
    v0_f = jnp.where(was_int, _c(0b001),
           jnp.where(repeat_f, _c(0b01),
           jnp.where(xor_zero, _c(0b10),
           jnp.where(contained, _c(0b110), v0_unc))))
    n_v0_f = jnp.where(was_int, _c(3, I32),
             jnp.where(repeat_f | xor_zero, _c(2, I32),
             jnp.where(contained, _c(3, I32), _c(15, I32))))
    v1_f = jnp.where(was_int, v_bits,
           jnp.where(contained, _shr(cur_xor, pt.astype(U64)),
                     _shr(cur_xor, ct.astype(U64))))
    n_v1_f = jnp.where(was_int, _c(64, I32),
             jnp.where(repeat_f | xor_zero, _c(0, I32),
             jnp.where(contained, _c(64, I32) - pl - pt, meaningful)))
    nxor = jnp.where(xor_zero, _c(0), cur_xor)
    st_float = dict(
        int_val=int_val,
        is_float=_c(True, jnp.bool_),
        max_mult_i=jnp.where(was_int, mult, max_mult),
        prev_fbits=v_bits,
        prev_xor=jnp.where(was_int, v_bits, jnp.where(repeat_f, prev_xor, nxor)),
        num_sig=num_sig_st, cur_hl=cur_hl, num_lower=num_lower,
    )

    # writeIntVal: repeat '01'; update '000'+cascade+sign+diff;
    # no-update '1'+sign+diff
    repeat_i = (val_diff == _c(0, I64)) & (~is_float) & (mult == max_mult)
    neg = val_diff < _c(0, I64)
    diff_mag = jnp.abs(val_diff).astype(U64)
    sig_n = _num_sig(diff_mag)
    new_sig, t_chl, t_nl = _track_new_sig(num_sig_st, cur_hl, num_lower, sig_n)
    float_changed = is_float  # is_float state true means mode changes to int
    need_update = (mult > max_mult) | (num_sig_st != new_sig) | float_changed

    # THE shared writeIntSigMult cascade: both opcode prefixes are
    # zero-valued ('0' first-value mode bit / '000' update escape), so
    # only the prefix WIDTH and the cascade inputs select per path.
    acc_sh = _cat(acc0, _c(0), jnp.where(is_first, _c(1, I32), _c(3, I32)))
    acc_sh, ns_sh, mm_sh = _int_sig_mult_ctrl(
        acc_sh, num_sig_st, max_mult,
        jnp.where(is_first, sig_f, new_sig), mult,
        (~is_first) & float_changed)
    acc_sh = _cat(acc_sh, jnp.where(jnp.where(is_first, neg_diff, neg),
                                    _c(1), _c(0)), 1)

    v0_first = jnp.where(v_is_float, _c(1), acc_sh[0])
    n_v0_first = jnp.where(v_is_float, _c(1, I32), acc_sh[1])
    v1_first = jnp.where(v_is_float, v_bits, mag)
    n_v1_first = jnp.where(v_is_float, _c(64, I32), ns_sh)
    st_first = dict(
        int_val=jnp.where(v_is_float, int_val, val),
        is_float=v_is_float,
        prev_fbits=jnp.where(v_is_float, v_bits, prev_fbits),
        prev_xor=jnp.where(v_is_float, v_bits, prev_xor),
        num_sig=jnp.where(v_is_float, num_sig_st, ns_sh),
        max_mult_i=jnp.where(v_is_float, mult, mm_sh),
        cur_hl=cur_hl, num_lower=num_lower,
    )

    ns_iu, mm_iu = ns_sh, mm_sh
    v0_i = jnp.where(repeat_i, _c(0b01),
           jnp.where(need_update, acc_sh[0],
                     _c(0b10) | jnp.where(neg, _c(1), _c(0))))
    n_v0_i = jnp.where(repeat_i | ~need_update, _c(2, I32), acc_sh[1])
    v1_i = diff_mag
    n_v1_i = jnp.where(repeat_i, _c(0, I32),
             jnp.where(need_update, ns_iu, num_sig_st))
    st_int = dict(
        int_val=jnp.where(repeat_i, int_val, val),
        is_float=jnp.where(repeat_i, is_float, _c(False, jnp.bool_)),
        max_mult_i=jnp.where(repeat_i, max_mult,
                             jnp.where(need_update, mm_iu, max_mult)),
        prev_fbits=prev_fbits, prev_xor=prev_xor,
        num_sig=jnp.where(repeat_i, num_sig_st,
                          jnp.where(need_update, ns_iu, num_sig_st)),
        cur_hl=jnp.where(repeat_i, cur_hl, t_chl),
        num_lower=jnp.where(repeat_i, num_lower, t_nl),
    )

    v0_next = jnp.where(go_float, v0_f, v0_i)
    n_v0_next = jnp.where(go_float, n_v0_f, n_v0_i)
    v1_next = jnp.where(go_float, v1_f, v1_i)
    n_v1_next = jnp.where(go_float, n_v1_f, n_v1_i)
    st_next = {
        k: jnp.where(go_float, st_float[k], st_int[k])
        for k in st_float
    }

    v0 = jnp.where(is_first, v0_first, v0_next)
    n_v0 = jnp.where(is_first, n_v0_first, n_v0_next)
    v1 = jnp.where(is_first, v1_first, v1_next)
    n_v1 = jnp.where(is_first, n_v1_first, n_v1_next)
    st = {
        k: jnp.where(is_first, st_first[k], st_next[k])
        for k in st_first
    }

    # inactive (padding) steps emit nothing (all widths 0) and keep state
    zero_w = _c(0, I32)
    n_t0 = jnp.where(valid, n_t0, zero_w)
    n_t1 = jnp.where(valid, n_t1, zero_w)
    n_v0 = jnp.where(valid, n_v0, zero_w)
    n_v1 = jnp.where(valid, n_v1, zero_w)

    def keep(new, old):
        return jnp.where(valid, new, old)

    fallback = (fallback | (valid & prec) | (valid & ~div_ok & ~emit_tu)
                | (valid & dod_overflow & ~emit_tu))
    new_carry = (
        keep(new_prev_time, prev_time),
        keep(new_prev_delta, prev_delta),
        keep(new_tu_none, tu_none),
        keep(st["int_val"], int_val),
        keep(st["max_mult_i"], max_mult),
        keep(st["is_float"], is_float),
        keep(st["prev_fbits"], prev_fbits),
        keep(st["prev_xor"], prev_xor),
        keep(st["num_sig"], num_sig_st),
        keep(st["cur_hl"], cur_hl),
        keep(st["num_lower"], num_lower),
        is_first & ~valid,
        fallback,
    )
    return new_carry, (t0, t1, v0, v1, n_t0, n_t1, n_v0, n_v1)


_PLACE_IMPLS = ("scatter", "gather", "pallas")


def resolved_place() -> str:
    """Which phase-2 word-placement formulation the encoder uses on
    this process' backend; ``M3_ENCODE_PLACE`` overrides (parity tests
    pin all of them).  Resolved on the HOST, outside the trace, and
    passed as a static argument — an env read under the tracer is
    frozen into the first compile and the seam silently stops
    responding (retrace-risk; exactly how the in-process override was
    broken until round 7).  auto = ``pallas`` only on a real TPU
    backend (the clean-fallback contract tier-1 pins, like
    M3_DECODE_EXTRACT), ``gather`` everywhere else."""
    place = os.environ.get("M3_ENCODE_PLACE", "").strip()
    if place:
        if place not in _PLACE_IMPLS:
            raise ValueError(
                f"M3_ENCODE_PLACE={place!r}: expected one of {_PLACE_IMPLS}")
        return place
    return "pallas" if jax.default_backend() == "tpu" else "gather"


def fallback_place(place: str) -> str:
    """The devguard stepping-down rule for the encode placement seam,
    owned ONCE (encode_batch_device + parallel/sharded_encode): a
    classified device failure re-runs through the cheap-compile jnp
    scatter tail, or gather when scatter IS the primary — every tail
    is byte-identical, so the choice is purely about compile cost."""
    return "scatter" if place != "scatter" else "gather"


def _lane_frags(valq, pos, n):
    """One (value, bit offset, width) lane class -> its two word
    fragments.  ``valq`` holds the field right-aligned (low ``n``
    bits); the MSB-aligned 64-bit image splits across stream words
    ``pos >> 6`` and ``pos >> 6 + 1``.  Returns (hi, lo, gw)."""
    vm = jnp.where(n > _c(0, I32),
                   valq << ((_c(64, I32) - n) & _c(63, I32)).astype(U64),
                   _c(0))
    sh = (pos & _c(63, I32)).astype(U64)
    hi = vm >> sh
    lo = jnp.where(sh > _c(0), vm << ((_c(64) - sh) & _c(63)), _c(0))
    return hi, lo, pos >> _c(6, I32)


def encode_batch_device(timestamps, value_bits, start, valid, unit: int = 1,
                        out_words: int = 0, prefix_bits=None,
                        place: str = "auto"):
    """Encode (S, T) series on device (host wrapper: resolves the
    placement seam outside the trace, then dispatches to the jitted
    implementation with ``place`` as a static argument).

    Args:
      timestamps: (S, T) int64 UnixNanos, padded entries arbitrary.
      value_bits: (S, T) uint64 float64 bit patterns.
      start: (S,) int64 encoder start times.
      valid: (S, T) bool mask of real datapoints (prefix True).
      unit: static time unit (wire byte value).
      out_words: static output width in 64-bit words per series
        (0 -> T * 16 bits / 64 + 4).
      prefix_bits: optional (S,) int32 — bits reserved after the start
        word for a host-composed prefix (the first datapoint's
        annotation marker+varint+bytes, spliced in by ``encode_batch``);
        all emitted fields shift right by this amount.
      place: phase-2 placement impl (see ``resolved_place``); "auto"
        resolves per backend/env here on the host.

    Returns dict with packed words (S, W) uint64 (starting with the 64-bit
    start time), total_bits (S,), fallback (S,) bool.
    """
    if place == "auto":
        place = resolved_place()
    if place not in _PLACE_IMPLS:
        raise ValueError(f"place={place!r}: expected one of "
                         f"{_PLACE_IMPLS + ('auto',)}")
    S, T = timestamps.shape
    ow = out_words if out_words else (T * 16) // 64 + 4

    def _run(p: str):
        # the jitted program with the placement as a STATIC argument —
        # the guard's fallback is just a different static value, so
        # nothing retraces and the happy path stays transfer-free
        # (hops --check)
        return _encode_batch_device(
            timestamps, value_bits, start, valid, unit=unit,
            out_words=out_words, prefix_bits=prefix_bits, place=p)

    # device-guard seam: a classified device failure re-runs the SAME
    # batch through the cheap-compile jnp scatter tail (or gather when
    # scatter IS the primary) — all placements are byte-identical
    # (PINNED_ENCODE_DIGEST + the fuzz suite pin every tail).  Budget
    # admission for the transient lane tables happens ONCE, outside
    # the guard, at the WORSE of the primary/fallback tails' footprints
    # (the formulas are per-tail since round 13, XLA-verified by the
    # costs artifact): an admission reject is not a device fault the
    # fallback can relieve — it raises typed here without touching the
    # stage breaker.
    lane_bytes = max(
        membudget.encode_lane_bytes(S, T, ow, place=place),
        membudget.encode_lane_bytes(S, T, ow, place=fallback_place(place)))
    with membudget.transient("encode.lanes", lane_bytes):
        return devguard.run_guarded("encode", lambda: _run(place),
                                    lambda: _run(fallback_place(place)))


def _encode_carry0(S: int, start, unit: int):
    """Phase-1 initial carry (shared with the profile harness — the
    decode side's ``_decode_carry0`` precedent: one owner for the
    carry layout, so a layout change can't silently desync a proxy)."""
    tu_none = (start % jnp.asarray(int(Unit(unit).nanos()), I64)) != 0
    return (
        start.astype(I64),                      # prev_time
        jnp.zeros(S, I64),                      # prev_delta
        tu_none,                                # initial unit None?
        jnp.zeros(S, I64),                      # int_val
        jnp.zeros(S, I32),                      # max_mult
        jnp.zeros(S, jnp.bool_),                # is_float
        jnp.zeros(S, U64),                      # prev_fbits
        jnp.zeros(S, U64),                      # prev_xor
        jnp.zeros(S, I32),                      # num_sig
        jnp.zeros(S, I32),                      # cur_highest_lower_sig
        jnp.zeros(S, I32),                      # num_lower_sig
        jnp.ones(S, jnp.bool_),                 # is_first
        jnp.zeros(S, jnp.bool_),                # fallback
    )


@functools.partial(jax.jit, static_argnames=("unit", "out_words", "place"))
def _encode_batch_device(timestamps, value_bits, start, valid, unit: int = 1,
                         out_words: int = 0, prefix_bits=None,
                         place: str = "gather"):
    S, T = timestamps.shape
    if out_words == 0:
        out_words = (T * 16) // 64 + 4
    u = Unit(unit)
    default_32 = u in (Unit.SECOND, Unit.MILLISECOND)

    carry0 = _encode_carry0(S, start, unit)

    step = functools.partial(_encode_step, unit=unit,
                             default_unit_is_32bit=default_32)
    vstep = jax.vmap(step)

    def scan_fn(carry, xs):
        c2, (t0, t1, v0, v1, n0, n1, n2, n3) = vstep(carry, xs)
        # Stack the four lanes in STREAM ORDER: the scan then yields
        # (T, 4, S) tables whose (4T, S) reshape is free, and in that
        # interleaved order the fragment word keys are GLOBALLY
        # non-decreasing per series — the property the scatter-free
        # placement below rides.
        return c2, (jnp.stack([t0, t1, v0, v1]),
                    jnp.stack([n0, n1, n2, n3]))

    xs = (timestamps.T, value_bits.T, valid.T)  # scan over T
    carry, (lv, lw) = lax.scan(scan_fn, carry0, xs, unroll=_SCAN_UNROLL)
    # Lane tables stay SCAN-MAJOR — (T, 4, S), no transpose.  All
    # offset arithmetic is pinned i32 (sum/cumsum would silently
    # promote to i64 — double the traffic of the placement stages).
    lens = lw.sum(axis=1, dtype=I32)  # (T, S) per-datapoint bit counts

    # Absolute bit offsets: ONE exclusive prefix sum over per-datapoint
    # bit counts (the only cross-datapoint dependence left after the
    # scan), based at the 64-bit start word (+ any host prefix); each
    # lane's offset adds its in-datapoint exclusive width sum.
    base = _c(64, I32) if prefix_bits is None else (
        _c(64, I32) + prefix_bits.astype(I32)[None, :])
    off_dp = jnp.cumsum(lens, axis=0, dtype=I32) - lens + base
    total_bits = (off_dp[-1] + lens[-1]).astype(jnp.int64)
    pos = off_dp[:, None, :] + (jnp.cumsum(lw, axis=1, dtype=I32) - lw)

    F = 4 * T
    val4 = lv.reshape(F, S)
    pos4 = pos.reshape(F, S)
    n4 = lw.reshape(F, S)
    hi, lo, gw = _lane_frags(val4, pos4, n4)  # (F, S), gw non-decreasing

    out = jnp.zeros((S, out_words), U64)
    # start word first
    out = out.at[:, 0].set(start.astype(U64))

    # Word placement: every lane contributes (hi, lo) word fragments at
    # per-series word indices gw / gw+1 (disjoint bit ranges make add
    # equivalent to or).  Three formulations behind the static seam:
    #   scatter — two scatter-adds over the (F, S) fragments; the
    #             XLA-CPU scatter floor (~43ns/elt, round 7) makes it
    #             the SLOW tail at corpus scale but the cheapest
    #             compile.
    #   gather  — scatter-free: the stream-order fragment keys are
    #             NON-DECREASING along F, so each output word's
    #             contribution is a rank interval of the fragment
    #             cumsum — exact even under u64 wraparound ((A+B)-A ==
    #             B mod 2^64).  One branchless binary search serves
    #             both classes: the lo-class keys are gw+1, so its
    #             rank table is the hi-class's shifted one query down.
    #             The same segmented idiom as parallel/segmented.py.
    #   pallas  — the hand-scheduled TPU kernel: the masked-sum
    #             scatter inversion of the decode gather kernel
    #             (parallel/pallas_encode.py); interpret mode off-TPU.
    # ``place`` is STATIC, resolved by the encode_batch_device wrapper
    # (resolved_place: backend default, M3_ENCODE_PLACE override).
    if place == "pallas":
        from m3_tpu.parallel import pallas_encode

        frags = jnp.concatenate([hi.T, lo.T], axis=1)   # (S, 2F)
        keys = jnp.concatenate([gw.T, gw.T + _c(1, I32)], axis=1)
        out = out + pallas_encode.place_words(frags, keys, out_words)
    elif place == "gather":
        # Series-major for the gather stages: axis-1 gathers walk
        # contiguous rows; the axis-0 formulation's column-strided
        # accesses measured ~3x slower on XLA-CPU.
        zero_col = jnp.zeros((S, 1), U64)

        def _lane_cumsum_t(frag):
            # Inclusive lane cumsum, HIERARCHICALLY: 3 adds within
            # each datapoint's 4 lanes + one 4x-shorter dp-level
            # cumsum (XLA-CPU lowers a long cumsum to log-depth
            # full-array passes, so the (F, S) form paid ~4x this
            # traffic; exact either way — u64 adds commute).
            r = frag.reshape(T, 4, S)
            within = jnp.cumsum(r, axis=1)
            dp_sums = within[:, 3]
            dp_pre = jnp.cumsum(dp_sums, axis=0) - dp_sums
            return (dp_pre[:, None, :] + within).reshape(F, S).T

        cum_hi = jnp.concatenate([zero_col, _lane_cumsum_t(hi)], axis=1)
        cum_lo = jnp.concatenate([zero_col, _lane_cumsum_t(lo)], axis=1)
        keys = gw.T  # (S, F), non-decreasing rows
        # rank[s, w] = #lanes with key <= w, all output words at once:
        # one branchless binary search (cand-1 stays in range via the
        # min; the cand <= F guard rejects the clamped probes).
        wq = jnp.arange(out_words, dtype=I32)[None, :]  # (1, W)
        rank = jnp.zeros((S, out_words), I32)
        # 2^k > F so the greedy bit descent can reach rank == F exactly
        # (every lane before the word): (F-1).bit_length() tops out at
        # 2^k - 1 = F - 1 and silently drops the LAST lane's fragment
        # from the final stream word.
        for b in reversed(range(max(F, 1).bit_length())):
            cand = rank + _c(1 << b, I32)
            kv = jnp.take_along_axis(
                keys, jnp.minimum(cand, _c(F, I32)) - _c(1, I32), axis=1)
            rank = jnp.where((cand <= _c(F, I32)) & (kv <= wq), cand, rank)
        # Contiguous integer queries: rank(w-1) is rank shifted one
        # column (keys are >= 1 — offsets start at base >= 64 — so
        # rank(0) == 0 and the shifted-in zero column is exact).  The
        # lo-class keys are gw+1, so its rank table is the hi-class's
        # shifted once more: no second search.
        zc = jnp.zeros((S, 1), I32)
        rank_m1 = jnp.concatenate([zc, rank[:, :-1]], axis=1)
        rank_m2 = jnp.concatenate([zc, rank_m1[:, :-1]], axis=1)
        out = out + (jnp.take_along_axis(cum_hi, rank, axis=1)
                     - jnp.take_along_axis(cum_hi, rank_m1, axis=1)
                     + jnp.take_along_axis(cum_lo, rank_m1, axis=1)
                     - jnp.take_along_axis(cum_lo, rank_m2, axis=1))
    else:
        series_idx = jnp.broadcast_to(jnp.arange(S, dtype=I32)[None, :],
                                      (F, S))
        out = out.at[series_idx, jnp.clip(gw, 0, out_words - 1)].add(
            jnp.where(gw < out_words, hi, _c(0)))
        out = out.at[series_idx, jnp.clip(gw + 1, 0, out_words - 1)].add(
            jnp.where(gw + 1 < out_words, lo, _c(0)))

    fallback = carry[12] | (total_bits > (out_words * 64))
    return {"words": out, "total_bits": total_bits, "fallback": fallback}


def finalize_streams(words: np.ndarray, total_bits: np.ndarray,
                     counts=None) -> list[bytes]:
    """Host finalization: trim to byte length and append the EOS tail."""
    out = []
    words = np.asarray(words)
    total_bits = np.asarray(total_bits)
    for i in range(words.shape[0]):
        nbits = int(total_bits[i])
        raw = words[i].astype(">u8").tobytes()
        nbytes = (nbits + 7) // 8
        head = raw[:nbytes]
        pos = nbits - (nbytes - 1) * 8  # bits used in last byte, 1..8
        out.append(head[:-1] + tail_bytes(head[-1], pos))
    return out


def pack_streams(streams: list[bytes], pad_words: int = 0):
    """Pack finalized byte streams into the decoder's input layout:
    (S, pad_words) big-endian uint64 word arrays + per-stream bit lengths.

    ``pad_words`` of 0 sizes the array to the longest stream plus two
    slack words (the decoder pads further — ``_PAD_WORDS`` zero words —
    so its register-file gathers and phase-2 funnels never read OOB).
    """
    S = len(streams)
    if pad_words == 0:
        pad_words = max((len(s) for s in streams), default=0) // 8 + 2
    words = np.zeros((S, pad_words), np.uint64)
    nbits = np.zeros(S, np.int64)
    for i, s in enumerate(streams):
        nbits[i] = len(s) * 8
        padded = s + b"\x00" * (-len(s) % 8)
        w = np.frombuffer(padded, dtype=">u8").astype(np.uint64)
        words[i, : len(w)] = w
    return words, nbits


def _annotation_prefix(ann: bytes):
    """The first-datapoint annotation wire prefix (marker + varint +
    bytes) as (uint64 big-endian words, bit length) — composed with the
    scalar OStream so the bit layout is definitionally identical to the
    scalar encoder's (_write_annotation)."""
    from m3_tpu.encoding.bitstream import OStream
    from m3_tpu.encoding.m3tsz import _put_varint
    from m3_tpu.encoding.scheme import ANNOTATION_MARKER, write_special_marker

    os_ = OStream()
    write_special_marker(os_, ANNOTATION_MARKER)
    os_.write_bytes(_put_varint(len(ann) - 1))
    os_.write_bytes(ann)
    raw, _ = os_.raw_bytes()
    padded = raw + b"\x00" * (-len(raw) % 8)
    return np.frombuffer(padded, dtype=">u8").astype(np.uint64), os_.bit_length


def encode_batch(timestamps, values, start, counts=None, unit: Unit = Unit.SECOND,
                 out_words: int = 0, annotations=None, place: str = "auto"):
    """Host-facing batched encode.

    Returns (streams: list[bytes], fallback: np.ndarray[bool]); fallback
    series contain b"" and must be encoded with the scalar codec.

    ``annotations`` (optional list[bytes|None], len S) attaches an
    annotation to each series' FIRST datapoint — the proto-schema /
    tag-payload shape (`timestamp_encoder.go:99-116` writes it before
    the first time-unit marker).  The device scan shifts its output by
    the prefix width and the host splices the marker+varint+bytes in;
    mid-stream annotation CHANGES stay on the scalar path.
    """
    timestamps = np.asarray(timestamps, dtype=np.int64)
    values = np.asarray(values, dtype=np.float64)
    S, T = timestamps.shape
    if counts is None:
        counts = np.full(S, T, dtype=np.int64)
    valid = np.arange(T, dtype=np.int64)[None, :] < np.asarray(counts)[:, None]
    vb = values.view(np.uint64)

    prefix_bits = None
    prefix_words: dict[int, np.ndarray] = {}
    if annotations is not None:
        pb = np.zeros(S, np.int32)
        for i, ann in enumerate(annotations):
            if ann:
                prefix_words[i], pb[i] = _annotation_prefix(ann)
        prefix_bits = jnp.asarray(pb) if prefix_words else None

    res = encode_batch_device(
        jnp.asarray(timestamps), jnp.asarray(vb), jnp.asarray(start, dtype=jnp.int64),
        jnp.asarray(valid), unit=int(unit), out_words=out_words,
        prefix_bits=prefix_bits, place=place)
    fallback = np.asarray(res["fallback"])
    words_out = np.asarray(res["words"])
    if prefix_words:
        # Splice each prefix in after the start word (bit 64 is a word
        # boundary, so this is a plain OR into untouched zero bits).
        words_out = words_out.copy()
        for i, pw in prefix_words.items():
            words_out[i, 1:1 + len(pw)] |= pw
    streams = finalize_streams(words_out, np.asarray(res["total_bits"]))
    counts_arr = np.asarray(counts)
    # An empty series encodes to b"" (the reference encoder's Stream() returns
    # no segment when nothing was written), not a bare start-word stream.
    streams = [b"" if (fallback[i] or counts_arr[i] == 0) else streams[i]
               for i in range(S)]
    return streams, fallback


# ---------------------------------------------------------------------------
# Batched decode
# ---------------------------------------------------------------------------


def _peek(words, cursor, n):
    """Read ``n`` (<=64, may be 0 or traced) bits at bit position cursor from a
    (W+1,) uint64 word array (extra zero pad word)."""
    w = (cursor >> _c(6, I32))
    off = (cursor & _c(63, I32)).astype(U64)
    W = words.shape[0] - 1
    w = jnp.clip(w, 0, W - 1)
    w0 = words[w]
    w1 = words[w + 1]
    window = _shl(w0, off) | jnp.where(off > _c(0), _shr(w1, _c(64) - off), _c(0))
    return _shr(window, _c(64) - _c(n, I32).astype(U64))


# -- Register-file bit reader -----------------------------------------------
#
# Phase 1 reads at most 229 bits per step — 64 (start) + 11+8+64
# (marker + unit byte + full dod) + 16 (value control prefix) + 64
# (payload peek) — and every read starts within 102 bits of the
# post-start cursor ``c0``.  One 4-word gather at the word index below
# c0 therefore covers the whole step: bits [b0, b0+256) with
# c0 - b0 <= 63, so reads end at most at c0+166 <= b0+229 < b0+256.
# Earlier rounds carried a 32-word window in the scan carry instead
# (per-lane gathers lowered to O(S*W) masked reductions on TPU,
# round-2), but with phase 2 owning ALL wide payload extraction the
# per-step demand collapsed to these 4 words, and round-6 CPU profiling
# showed the window machinery (16-word refills + 9-word select funnels)
# costing ~5x the single tiny gather it avoided.  The padded stream
# array keeps >= 4 zero words past the longest stream, so the gather
# never clips in range.
#
# How the four words are read is the ``regfile`` seam, resolved on the
# host by backend (``resolved_regfile``), as ``chains`` is:
#
# ``gather``  one ``take_along_axis``: a few ns a lane on XLA-CPU, but
#             on a TPU a per-element gather of ~9.7 ns an element, and
#             with each u64 split into two u32 halves this is 8 of the
#             9 gathered elements a row pays per step.
# ``select``  a select against the word index under an OR over W:
#             O(W) fused vector work a lane, no gather.  Exactly one
#             column matches each k (the padding keeps ``w0i + 3`` in
#             bounds), so the OR is that word, by bits.

_PAD_WORDS = 16          # zero padding after the longest stream (words)


def _regfile4(words, w0i, regfile: str = "gather"):
    """The 4 consecutive u64 stream words starting at per-lane word
    index ``w0i`` of the padded (S, W) array."""
    if regfile == "select":
        d = jnp.arange(words.shape[1], dtype=I32)[None, :] - w0i[:, None]
        return tuple(
            lax.reduce(jnp.where(d == _c(k, I32), words, _c(0)), _c(0),
                       lax.bitwise_or, (1,))
            for k in range(4))
    idx = w0i[:, None] + jnp.arange(4, dtype=I32)[None, :]
    R = jnp.take_along_axis(words, idx, axis=1, mode="promise_in_bounds")
    return R[:, 0], R[:, 1], R[:, 2], R[:, 3]


# -- Value-control lookup table ---------------------------------------------
#
# The value section's control prefix — mode / update-opcode / sig / mult
# / XOR-class flags — is a pure function of (first-value pending,
# int-or-float mode, next 16 stream bits): every branch's control bits
# fit inside a 16-bit window, and only the *payload* beyond it is wider.
# Round-6 profiling: the original 13-read flag cascade was ~250 fused
# element-ops per lane per scan step, while an XLA-CPU gather costs a
# few ns per lane — so the whole cascade collapses into ONE gather into
# this precomputed 2^18-entry table plus ~30 unpack ops.  Table rows are
# u32-packed:
#
#   bits  0-4   ctrl: control bits consumed before the payload/diff
#               field (the field itself starts at ``value_cursor+ctrl``)
#   bits  5-11  sig7: new significand width 0..64, 127 = keep carried
#   bits 12-14  mult3: new decimal multiplier (valid when bit 15 clear)
#   bit   15    mult_keep: no multiplier field, keep carried
#   bit   16    sign: the int-diff sign bit's value
#   bit   17    got_float_full: 64-bit raw float payload follows
#   bit   18    xor_nz: nonzero XOR (contained or uncontained)
#   bit   19    contained: XOR payload width = 64 - pl - pt (carried)
#   bit   20    uncont: explicit lead6/meaningful6 then payload
#   bit   21    diff_active: signed int-diff payload of eff-sig bits
#   bit   22    nfloat_set: mode becomes float after this point
#   bit   23    nfloat_keep: mode unchanged (neither set nor clear)
#   bit   24    mult_err: multiplier field decoded > max (stream error)
#   bit   25    xor_zero: zero-XOR repeat (no payload)
#
# For the uncontained path the lead/meaningful fields also sit inside
# the 16-bit window (bits 3..14) and are re-extracted with two shifts —
# cheaper than widening the table rows to u64.

_VC_KEEP_SIG = 127


def _build_value_ctrl_table() -> np.ndarray:
    """Precompute the (2^18,) u32 value-control table (numpy, import
    time).  Index = first << 17 | is_float << 16 | next-16-bits
    (MSB-first).  Mirrors the reference decoder's branch structure
    (m3tsz.py readIntSigMult / XOR paths) exactly; the jit path's
    correctness against the scalar decoder is pinned by the round-trip
    and sha256 corpus tests."""
    idx = np.arange(1 << 18, dtype=np.int64)
    X = idx & 0xFFFF
    isf = ((idx >> 16) & 1) == 1
    first = ((idx >> 17) & 1) == 1

    def bit(k):  # k-th stream bit of the window, 0 = first read
        return (X >> (15 - k)) & 1

    def bit_at(pos):  # data-dependent bit position (numpy array)
        return (X >> (15 - pos)) & 1

    def cascade(k0: int):
        """The sig/mult update cascade starting at control offset k0:
        sb1 [sb2 sig6] mb1 [mult3] sign."""
        sb1 = bit(k0)
        sb2 = bit(k0 + 1)
        sig6 = np.zeros_like(X)
        for j in range(6):
            sig6 = (sig6 << 1) | bit(k0 + 2 + j)
        sig = np.where(sb1 == 0, _VC_KEEP_SIG,
                       np.where(sb2 == 0, 0, sig6 + 1))
        k_m = np.where(sb1 == 0, k0 + 1,
                       np.where(sb2 == 0, k0 + 2, k0 + 8))
        mb1 = bit_at(k_m)
        m3 = (bit_at(k_m + 1) << 2) | (bit_at(k_m + 2) << 1) | bit_at(k_m + 3)
        mult = np.where(mb1 == 1, m3, 0)
        mult_keep = mb1 == 0
        mult_err = (mb1 == 1) & (m3 > 6)  # MAX_MULT (m3tsz.py)
        k_s = k_m + np.where(mb1 == 1, 4, 1)
        sign = bit_at(k_s)
        ctrl = k_s + 1
        return ctrl, sig, mult, mult_keep, mult_err, sign

    c1 = cascade(1)   # first-value int: after the mode bit
    c3 = cascade(3)   # next-value to-int-update: after nb1 nb2 nb3

    p_a2 = first & (bit(0) == 1)                                # full float
    p_a1 = first & (bit(0) == 0)                                # first int
    nfirst = ~first
    p_rep = nfirst & (bit(0) == 0) & (bit(1) == 1)              # repeat
    p_tofl = nfirst & (bit(0) == 0) & (bit(1) == 0) & (bit(2) == 1)
    p_toint = nfirst & (bit(0) == 0) & (bit(1) == 0) & (bit(2) == 0)
    p_xz = nfirst & (bit(0) == 1) & isf & (bit(1) == 0)         # zero XOR
    p_cont = nfirst & (bit(0) == 1) & isf & (bit(1) == 1) & (bit(2) == 0)
    p_unc = nfirst & (bit(0) == 1) & isf & (bit(1) == 1) & (bit(2) == 1)
    p_ino = nfirst & (bit(0) == 1) & ~isf                       # int no-upd

    def sel(pairs, default):
        out = np.full_like(X, default)
        for mask, val in pairs:
            out = np.where(mask, val, out)
        return out

    ctrl = sel([(p_a2, 1), (p_a1, c1[0]), (p_rep, 2), (p_tofl, 3),
                (p_toint, c3[0]), (p_xz, 2), (p_cont, 3), (p_unc, 15),
                (p_ino, 2)], 0)
    sig7 = sel([(p_a1, c1[1]), (p_toint, c3[1])], _VC_KEEP_SIG)
    mult3 = sel([(p_a1, c1[2]), (p_toint, c3[2])], 0)
    mult_keep = ~((p_a1 & ~c1[3]) | (p_toint & ~c3[3]))
    mult_err = (p_a1 & c1[4]) | (p_toint & c3[4])
    sign = sel([(p_a1, c1[5]), (p_toint, c3[5]), (p_ino, bit(1))], 0)

    flags = ((p_a2 | p_tofl).astype(np.int64) << 17
             | (p_cont | p_unc).astype(np.int64) << 18
             | p_cont.astype(np.int64) << 19
             | p_unc.astype(np.int64) << 20
             | (p_a1 | p_toint | p_ino).astype(np.int64) << 21
             | (p_a2 | p_tofl).astype(np.int64) << 22
             | (p_rep | p_xz | p_cont | p_unc | p_ino).astype(np.int64) << 23
             | mult_err.astype(np.int64) << 24
             | p_xz.astype(np.int64) << 25)
    packed = (ctrl | (sig7 << 5) | (mult3 << 12)
              | mult_keep.astype(np.int64) << 15 | (sign << 16) | flags)
    return packed.astype(np.uint32)


_VALUE_CTRL_TBL = _build_value_ctrl_table()


@functools.lru_cache(maxsize=1)
def value_ctrl_table():
    """The 2^18-entry value-control table as a DEVICE array, uploaded
    once per process and threaded through the decode entry points as an
    ARGUMENT.  Referencing the numpy module global under the tracer
    instead would constant-fold ~1MB of table into the HLO of every
    decode compilation — per shape, per chains tail, per backend
    (constant-bloat; the finding that motivated the rule).  Uncommitted
    (plain jnp.asarray, no device pin) so the sharded paths can
    replicate it across the mesh without a resharding error."""
    global _CTRL_TBL_RESERVED
    # lru_cache does not serialize concurrent first calls — the lock
    # keeps two first decoders from double-reserving the ledger entry
    with _CTRL_TBL_LOCK:
        if not _CTRL_TBL_RESERVED:
            # one permanent ~1MiB ledger entry for the resident control
            # table (x/membudget admission; never released — the table
            # lives for the process)
            membudget.reserve("decode.ctrl_table", _VALUE_CTRL_TBL.nbytes)
            _CTRL_TBL_RESERVED = True
    return jnp.asarray(_VALUE_CTRL_TBL, dtype=jnp.uint32)


_CTRL_TBL_RESERVED = False
_CTRL_TBL_LOCK = threading.Lock()


def _decode_step(carry, _, words, nbits, unit0, ctrl_tbl,
                 emit_chains: bool = False, regfile: str = "gather"):
    """Phase 1 of the two-phase decode: ONE datapoint slot for every
    series at once ((S,) array ops), resolving ONLY the data-dependent
    minimum — control bits, field widths and the bit cursor — and
    emitting a per-datapoint lane table for the parallel phase-2 field
    gather (``_phase2``).  No timestamps, no value reconstruction, no
    wide XOR/int state rides the scan: the carry is the cursor plus a
    handful of narrow i32 lanes (sig width, time unit, and the previous
    XOR's leading/trailing-zero counts, which decide the 'contained'
    field width).

    ``words`` is the padded (S, W) stream array (closure, not carry);
    ``nbits`` the per-series stream bit lengths.  All bit reads come
    from a 4-word register file read once per step (``_regfile4``).
    The body is deliberately ONE branch-free straight line — no
    ``lax.cond`` anywhere (round-6 profiling: every cond is a thunk
    boundary on XLA-CPU, and the buffer round-trips at those boundaries
    cost more than the work the cond skipped).
    """
    (cursor, done, err, need_start, first_val, saw_ann, unit_idx,
     sig, mult, is_float, pl, pt) = carry[:12]
    chain_carry = carry[12:]
    active = (~done) & (~err)

    # ---- first: 64-bit start timestamp (only its ALIGNMENT matters —
    # it decides the initial time unit; phase 2 re-reads the value
    # directly from word 0).  need_start implies cursor == 0 (the
    # encoder splices annotation prefixes AFTER the start word and
    # every other step consumes it).  ``unit0`` — the per-series
    # initial unit derived from that alignment — is loop-invariant, so
    # the caller computes it ONCE and closes over it (the i64 rem it
    # needs is division, ~20x an add per lane; round-6 profiling caught
    # it riding every step). ----
    rd_first = jnp.where(active & need_start, _c(64, I32), _c(0, I32))
    cur = cursor + rd_first
    unit_eff = jnp.where(need_start, unit0, unit_idx)
    first = first_val  # value-mode branch key (first value still pending)

    # ---- register file: ONE 4-word read at the word index below
    # `cur` covers every read this step makes (see _regfile4).  The
    # 64-bit funnel W0 at `cur` serves the marker peek (11), the
    # annotation varint bytes (<= 43 bits in), the time-unit byte
    # (<= 19 + 8) and the dod opcode (<= 19 + 4) as in-register shifts
    # — they all start within 64 bits of `cur` on whichever path a
    # lane takes; the value-section reads (<= 102 bits in) use the
    # full 3-word funnel ``rd3``. ----
    c0 = cur
    w0i = c0 >> _c(6, I32)
    r0, r1, r2, r3 = _regfile4(words, w0i, regfile)
    rf_base = w0i << _c(6, I32)

    # All shifts below are UNGUARDED (no _shl/_shr >=64 clamps): every
    # data-dependent amount is < 64 by construction, and the one
    # 64-minus case (a funnel's low word at offset 0) masks the shift
    # to (64-r)&63 and discards the r==0 lane with the select — its
    # clamped value is never read, so the result stays deterministic.
    def _funnel(hi, lo, r):
        return (hi << r) | jnp.where(
            r > _c(0), lo >> ((_c(64) - r) & _c(63)), _c(0))

    off0 = (c0 - rf_base).astype(U64)
    W0 = _funnel(r0, r1, off0)

    def rd0(cur_abs, n: int):
        # n is a STATIC width (1..64); cur_abs - c0 <= 43 < 64.
        off = (cur_abs - c0).astype(U64)
        chunk = W0 << off
        return chunk >> _c(64 - n) if n < 64 else chunk

    def rd3(cur_abs, n: int):
        """Up to 64 STATIC-width bits anywhere in [c0, rf_base+192):
        3-way funnel over the register file."""
        o = cur_abs - rf_base
        k = o >> _c(6, I32)                       # 0..2
        r = (o & _c(63, I32)).astype(U64)
        hi = jnp.where(k == _c(0, I32), r0,
                       jnp.where(k == _c(1, I32), r1, r2))
        lo = jnp.where(k == _c(0, I32), r1,
                       jnp.where(k == _c(1, I32), r2, r3))
        chunk = _funnel(hi, lo, r)
        return chunk >> _c(64 - n) if n < 64 else chunk

    # ---- marker peek (11 bits) ----
    can_peek = (cur + _c(11, I32)) <= nbits
    peek11 = jnp.where(active & can_peek, rd0(cur, 11), _c(0))
    is_marker = (peek11 >> _c(2)) == _c(0x100)
    mval = (peek11 & _c(3)).astype(I32)
    eos = active & is_marker & (mval == _c(0, I32))
    ann = active & is_marker & (mval == _c(1, I32))
    is_tu = active & is_marker & (mval == _c(2, I32))
    done = done | eos
    proceed = active & ~eos & ~ann

    # ---- annotation skip (timestamp_encoder.go:99-116) ----
    # marker + zigzag-LEB128 varint of (len-1) + len bytes.  The step
    # consumes the marker and varint from W0 (<= 43 bits) and jumps the
    # cursor over the payload.  The annotation slot emits no datapoint
    # — callers size max_points accordingly.  All four varint bytes sit
    # at FIXED offsets inside W0, so they are four shifts plus a
    # continuation-chain mask — no data-dependent read offsets.
    acur = cur + _c(11, I32)

    vb = [rd0(acur + _c(8 * k, I32), 8) for k in range(4)]
    t1 = (vb[0] & _c(0x80)) != _c(0)
    t2 = t1 & ((vb[1] & _c(0x80)) != _c(0))
    t3 = t2 & ((vb[2] & _c(0x80)) != _c(0))
    ux = ((vb[0] & _c(0x7F))
          | jnp.where(t1, _shl(vb[1] & _c(0x7F), _c(7)), _c(0))
          | jnp.where(t2, _shl(vb[2] & _c(0x7F), _c(14)), _c(0))
          | jnp.where(t3, _shl(vb[3] & _c(0x7F), _c(21)), _c(0)))
    abits = (_c(8, I32)
             + jnp.where(t1, _c(8, I32), _c(0, I32))
             + jnp.where(t2, _c(8, I32), _c(0, I32))
             + jnp.where(t3, _c(8, I32), _c(0, I32)))
    ann_len = (ux >> _c(1)).astype(I32) + _c(1, I32)
    err = err | (ann & t3 & ((vb[3] & _c(0x80)) != _c(0)))  # varint > 4B
    ann_end = acur + abits + ann_len * _c(8, I32)
    err = err | (ann & (ann_end > nbits))
    saw_ann = saw_ann | (ann & ~err)

    cur = cur + jnp.where(is_tu, _c(11, I32), _c(0, I32))
    ub = jnp.where(is_tu, rd0(cur, 8), _c(0)).astype(I32)
    cur = cur + jnp.where(is_tu, _c(8, I32), _c(0, I32))
    ub_valid = (ub >= _c(1, I32)) & (ub <= _c(8, I32))
    tu_changed = is_tu & ub_valid & (ub != unit_eff)
    new_unit = jnp.where(is_tu, ub, unit_eff)
    # _UNIT_NANOS is nonzero exactly on 1..8: a range check, not a gather
    unit_ok = (new_unit >= _c(1, I32)) & (new_unit <= _c(8, I32))
    err = err | (proceed & ~unit_ok & ~tu_changed)

    # ---- delta of delta: widths only (payload bits are phase 2's) ----
    full64 = tu_changed
    rd_dod64 = jnp.where(proceed & full64, _c(64, I32), _c(0, I32))
    cur = cur + rd_dod64
    dod64_off = cur - rd_dod64

    # bucketed path: peek 4 opcode bits, classify
    bucket_active = proceed & ~full64
    op4 = jnp.where(bucket_active, rd0(cur, 4), _c(0))
    b3 = (op4 >> _c(3)) & _c(1)
    b2 = (op4 >> _c(2)) & _c(1)
    b1 = (op4 >> _c(1)) & _c(1)
    b0 = op4 & _c(1)
    default_is32 = (new_unit == _c(1, I32)) | (new_unit == _c(2, I32))
    nop = jnp.where(b3 == _c(0), _c(1, I32),
          jnp.where(b2 == _c(0), _c(2, I32),
          jnp.where(b1 == _c(0), _c(3, I32), _c(4, I32))))
    nv = jnp.where(b3 == _c(0), _c(0, I32),
         jnp.where(b2 == _c(0), _c(7, I32),
         jnp.where(b1 == _c(0), _c(9, I32),
         jnp.where(b0 == _c(0), _c(12, I32),
                   jnp.where(default_is32, _c(32, I32), _c(64, I32))))))
    nop = jnp.where(bucket_active, nop, _c(0, I32))
    nv = jnp.where(bucket_active, nv, _c(0, I32))
    cur = cur + nop
    ts_off = jnp.where(full64, dod64_off, cur)
    ts_w = jnp.where(full64, _c(64, I32), nv)
    cur = cur + nv

    # ---- value section: ONE 16-bit funnel read + ONE table gather ----
    # Every value path's control bits fit in the next 16 stream bits
    # (see _build_value_ctrl_table): the 13-read flag cascade of the
    # previous formulation collapses into a single precomputed-table
    # gather plus unpack shifts.  Only the *payload* beyond the control
    # prefix is wider, and the only payload LOOKED AT here is the
    # full-float / contained-XOR word, whose bit pattern decides the
    # next leading/trailing counts.
    v0 = cur
    X = rd3(v0, 16).astype(I32)
    tidx = (X | jnp.where(is_float, _c(1 << 16, I32), _c(0, I32))
              | jnp.where(first, _c(1 << 17, I32), _c(0, I32)))
    tv = ctrl_tbl[tidx].astype(I32)

    ctrl = tv & _c(0x1F, I32)
    sig7 = (tv >> _c(5, I32)) & _c(0x7F, I32)
    mult3 = (tv >> _c(12, I32)) & _c(0x7, I32)
    mult_keep = (tv & _c(1 << 15, I32)) != _c(0, I32)
    sign_v = (tv & _c(1 << 16, I32)) != _c(0, I32)
    got_float_full = proceed & ((tv & _c(1 << 17, I32)) != _c(0, I32))
    xor_nz = proceed & ((tv & _c(1 << 18, I32)) != _c(0, I32))
    contained = proceed & ((tv & _c(1 << 19, I32)) != _c(0, I32))
    uncont = proceed & ((tv & _c(1 << 20, I32)) != _c(0, I32))
    diff_active = proceed & ((tv & _c(1 << 21, I32)) != _c(0, I32))
    nfloat_set = (tv & _c(1 << 22, I32)) != _c(0, I32)
    nfloat_keep = (tv & _c(1 << 23, I32)) != _c(0, I32)
    xor_zero = proceed & ((tv & _c(1 << 25, I32)) != _c(0, I32))
    err = err | (proceed & ((tv & _c(1 << 24, I32)) != _c(0, I32)))

    eff_sig = jnp.where(sig7 == _c(_VC_KEEP_SIG, I32), sig, sig7)
    meaningful_c = _c(64, I32) - pl - pt
    u_lead = (X >> _c(7, I32)) & _c(0x3F, I32)
    u_meaningful = ((X >> _c(1, I32)) & _c(0x3F, I32)) + _c(1, I32)
    u_trail = _c(64, I32) - u_lead - u_meaningful
    # lead + meaningful > 64 never leaves a valid encoder; route such
    # streams to the scalar path instead of desyncing pl/pt.
    err = err | (uncont & (u_trail < _c(0, I32)))

    val_w = jnp.where(got_float_full, _c(64, I32),
            jnp.where(contained, meaningful_c,
            jnp.where(uncont, u_meaningful,
            jnp.where(diff_active, eff_sig, _c(0, I32)))))
    val_off = v0 + ctrl
    cur = v0 + jnp.where(proceed, ctrl + val_w, _c(0, I32))

    # ---- leading/trailing update for the next step ----
    # Full-float and contained-XOR writes set the float-chain word to a
    # value whose clz/ctz depend on PAYLOAD bits, so those two (and
    # only those two) paths read it.  Uncontained writes are canonical
    # (top and bottom meaningful bits set — phase 2 verifies), so their
    # counts come straight from the explicit lead/meaningful fields.
    # Exactly one payload can be live per lane and all of them start at
    # ``val_off``, so ONE funnel read serves every path: the full-float
    # word is the raw 64 bits, the contained window is its top
    # ``meaningful_c`` bits.
    need_payload = got_float_full | contained
    c_w = jnp.where(contained, meaningful_c, _c(0, I32))
    raw = rd3(val_off, 64)
    cb = _shr(raw, _c(64) - jnp.clip(c_w, 0, 64).astype(U64))
    nx = jnp.where(got_float_full, raw, _shl(cb, pt.astype(U64)))
    nx_zero = nx == _c(0)
    pl_c = jnp.where(nx_zero, _c(64, I32),
                     lax.clz(nx.astype(I64)).astype(I32))
    pt_c = jnp.where(nx_zero, _c(0, I32),
                     _num_sig(nx & (~nx + _c(1))) - _c(1, I32))
    n_pl = jnp.where(need_payload, pl_c,
            jnp.where(uncont, u_lead,
            jnp.where(xor_zero, _c(64, I32), pl)))
    n_pt = jnp.where(need_payload, pt_c,
            jnp.where(uncont, u_trail,
            jnp.where(xor_zero, _c(0, I32), pt)))

    # ---- narrow state update (self-gating: every update predicate is
    # already ANDed with ``proceed``) ----
    n_is_float = jnp.where(proceed,
                           nfloat_set | (nfloat_keep & is_float), is_float)
    n_sig = jnp.where(proceed & (sig7 != _c(_VC_KEEP_SIG, I32)), sig7, sig)
    n_mult = jnp.where(proceed & ~mult_keep, mult3, mult)

    err = err | (proceed & (cur > nbits))
    emit = proceed & ~err

    # ---- cursor update ----
    # Normal datapoint steps advance to `cur`; annotation steps jump the
    # cursor past the payload (consuming this scan slot without a
    # datapoint); the start word still counts as consumed for them.
    ann_ok = ann & ~err
    new_cursor = jnp.where(ann_ok, ann_end,
                           jnp.where(proceed, cur, cursor))

    consumed = proceed | ann_ok
    new_carry = (
        new_cursor,
        done, err,
        need_start & ~consumed,
        first_val & ~proceed,
        saw_ann,
        jnp.where(proceed, new_unit,
                  jnp.where(ann_ok & need_start, unit0, unit_idx)),
        n_sig, n_mult, n_is_float, n_pl, n_pt,
    )

    if not emit_chains:
        # ---- GATHER tail: lane-table emission (see _phase2) ----
        shift = jnp.where(contained, pt,
                jnp.where(uncont, jnp.clip(u_trail, 0, 63), _c(0, I32)))
        U32c = lambda b, n: jnp.where(b, jnp.uint32(1 << n), jnp.uint32(0))
        out_p1 = (jnp.where(emit, ts_w, _c(0, I32)).astype(jnp.uint32)
                  | U32c(emit & full64, 7)
                  | (jnp.clip(new_unit, 0, 15).astype(jnp.uint32)
                     << jnp.uint32(8))
                  | U32c(emit, 12))
        out_p2 = (jnp.where(emit, val_w, _c(0, I32)).astype(jnp.uint32)
                  | (jnp.clip(shift, 0, 63).astype(jnp.uint32)
                     << jnp.uint32(7))
                  | (jnp.clip(n_mult, 0, 7).astype(jnp.uint32)
                     << jnp.uint32(13))
                  | U32c(n_is_float, 16)
                  | U32c(emit & xor_nz, 17)
                  | U32c(emit & got_float_full, 18)
                  | U32c(emit & diff_active, 19)
                  | U32c(sign_v, 20)
                  | U32c(emit & uncont, 21))
        return new_carry, (ts_off, out_p1, val_off, out_p2)

    # ---- FUSED tail: the three value chains ride THIS scan, consuming
    # the payload words already in registers (``raw`` was read for the
    # pl/pt update; the dod word is one more register-file funnel).
    # Bit-identical to the gather tail by the parity tests; see
    # decode_batch_device for when each tail is selected. ----
    (time, csum, csum_rst, fb, iv, prec, err2) = chain_carry
    unit_tbl = jnp.asarray(_UNIT_NANOS, I64)

    # timestamp chain: running delta = csum - csum@(last unit reset)
    draw = rd3(ts_off, 64)
    dmag = _shr(draw, _c(64) - jnp.clip(ts_w, 0, 64).astype(U64))
    dod = _sign_extend(dmag, ts_w)
    un = unit_tbl[jnp.clip(new_unit, 0, 15)]
    d_k = jnp.where(emit, jnp.where(full64, dod, dod * un), _c(0, I64))
    csum2 = csum + d_k
    time2 = time + jnp.where(emit, csum2 - csum_rst, _c(0, I64))
    csum_rst2 = jnp.where(emit & full64, csum2, csum_rst)

    # float-bits chain (running XOR with full-write resets); nx already
    # equals the XOR word for the full-float and contained paths
    pay_unc = raw >> (_c(64) - jnp.clip(u_meaningful, 1, 64).astype(U64))
    xv_unc = pay_unc << jnp.clip(u_trail, 0, 63).astype(U64)
    xv = jnp.where(xor_nz & emit,
                   jnp.where(uncont, xv_unc, nx), _c(0))
    fb2 = jnp.where(emit & got_float_full, raw, fb ^ xv)

    # int chain; sign: opcodeNegative(1) -> +, opcodePositive(0) -> -
    dv = _shr(raw, _c(64) - jnp.clip(eff_sig, 0, 64).astype(U64))
    sd = jnp.where(emit & diff_active,
                   jnp.where(sign_v, dv.astype(I64), -(dv.astype(I64))),
                   _c(0, I64))
    iv2 = iv + sd
    prec2 = prec | (emit & diff_active
                    & ((dv > _c(_PRECISION_LIMIT))
                       | (jnp.abs(iv2) > _c(_PRECISION_LIMIT, I64))))

    # Canonical-XOR guard (the gather tail's phase-2 epilogue check)
    top_ok = (pay_unc >> jnp.clip(u_meaningful - _c(1, I32), 0, 63)
              .astype(U64)) == _c(1)
    bot_ok = (pay_unc & _c(1)) == _c(1)
    err2_2 = err2 | (emit & uncont & ~(top_ok & bot_ok))

    ts_o = jnp.where(emit, time2, _c(0, I64))
    pay_o = jnp.where(n_is_float, fb2, iv2.astype(U64))
    meta_o = (jnp.where(emit, _c(16, I32), _c(0, I32))
              | jnp.where(n_is_float, _c(8, I32), _c(0, I32))
              | jnp.clip(n_mult, 0, 7)).astype(jnp.uint8)
    return (new_carry + (time2, csum2, csum_rst2, fb2, iv2, prec2, err2_2),
            (ts_o, pay_o, meta_o))


def _decode_carry0(S: int, base_time=None):
    """Phase-1 initial carry (shared with tools/decode_profile.py).
    ``base_time`` (the start words as int64) arms the fused-chains tail:
    when given, the seven chain lanes ride the carry too."""
    base = (
        jnp.zeros(S, I32), jnp.zeros(S, jnp.bool_), jnp.zeros(S, jnp.bool_),
        jnp.ones(S, jnp.bool_), jnp.ones(S, jnp.bool_),
        jnp.zeros(S, jnp.bool_), jnp.zeros(S, I32),
        jnp.zeros(S, I32), jnp.zeros(S, I32), jnp.zeros(S, jnp.bool_),
        jnp.full(S, 64, I32), jnp.zeros(S, I32),  # pl/pt of prev_xor == 0
    )
    if base_time is None:
        return base
    z64 = jnp.zeros(S, I64)
    return base + (base_time.astype(I64), z64, z64, jnp.zeros(S, U64), z64,
                   jnp.zeros(S, jnp.bool_), jnp.zeros(S, jnp.bool_))


def _phase2(wpad, ts_off, p1, val_off, p2, extract_impl: str = "jnp"):
    """Phase 2: fully parallel, branchless field extraction + chain
    reconstruction over the phase-1 lane table.

    All lane tables arrive SCAN-MAJOR — (P, S), straight off the
    ``lax.scan`` stack with no transpose.  The sequential scan resolved
    every bit boundary; everything left is data-parallel over (P, S):
    gather the timestamp-DoD and value payloads out of the int32-packed
    stream words (shift/mask funnels — a Pallas kernel on TPU,
    ``take_along_axis`` elsewhere; see parallel/pallas_decode.py), then
    rebuild the three value chains in ONE cheap ``lax.scan`` over the
    point axis with (S,) lanes (~8 fused element-ops per step — round-6
    profiling: the previous O(log P) associative-scan formulation paid
    five full (S, P) array passes PER LEVEL on XLA-CPU and dominated
    phase 2):

      timestamps — running delta + running sum, the delta segmented at
        time-unit changes (where the reference resets it);
      float bits — running XOR, reset at full-float writes;
      int values — running sum of the signed significand diffs.

    Returns (ts, payload, meta, prec, err2) — outputs (S, P) — where
    err2 flags streams whose uncontained XOR fields are non-canonical
    (top/bottom meaningful bit clear — impossible from a valid encoder;
    phase 1's width bookkeeping assumes canonical, so such streams must
    take the scalar path instead of silently diverging from it).
    """
    from m3_tpu.parallel import pallas_decode

    P, S = ts_off.shape
    U32 = jnp.uint32
    base_time = wpad[:, 0].astype(I64)

    # ---- the gather: both fields of every datapoint in one call ----
    # Scan-major throughout: the lane tables arrive (P, S) and the
    # stream array is transposed ONCE so the gather and every later
    # pass run in the (point, series) layout.  The Pallas path gathers
    # from the int32-packed view (big-endian u32 halves of the u64
    # stream words — u32 word k holds stream bits [32k, 32k+32)
    # MSB-first, the fixed-lane layout Mosaic needs); the jnp path
    # reads the u64 words directly (one fewer gather, no repack).
    ts_w = (p1 & jnp.uint32(0x7F)).astype(I32)
    val_w = (p2 & jnp.uint32(0x7F)).astype(I32)
    offs = jnp.concatenate([ts_off, val_off], axis=0)
    widths = jnp.concatenate([ts_w, val_w], axis=0)
    # ``extract_impl`` arrives as a STATIC from the decode wrapper
    # (resolved on the host — an env/backend read at trace time is
    # frozen into the first compile; retrace-risk).
    impl = extract_impl
    wpad_t = wpad.T
    if impl == "pallas":
        w32_t = jnp.stack([(wpad_t >> _c(32)).astype(U32),
                           (wpad_t & _c(0xFFFFFFFF)).astype(U32)],
                          axis=1).reshape(-1, S)
        fields = pallas_decode.extract_fields_t(w32_t, offs, widths,
                                                impl=impl)
    else:
        fields = pallas_decode.extract_fields64_t(wpad_t, offs, widths)
    dod_bits = fields[:P]
    payload = fields[P:]

    # ---- the chain scan: three running chains over the point axis
    # with (S,) lanes, lane tables unpacked IN the step body (the
    # tables are the scan's xs — unpacking inside costs a few u32 ops
    # per step on data already in registers, while precomputing the
    # unpacked lanes outside materializes three more (P, S) arrays of
    # memory-bound traffic; round-6 measured both, as well as the
    # O(log P) associative-scan formulation that paid five full-array
    # passes per level).  Everything derivable from the chain OUTPUTS
    # (emit/float masking, meta, the precision and canonical-XOR
    # reductions) runs vectorized in the epilogue instead.  Time-unit
    # changes reset the carried delta AFTER applying their full 64-bit
    # dod: the running delta is csum - csum@(last reset strictly before
    # this point), tracked incrementally. ----
    unit_tbl = jnp.asarray(_UNIT_NANOS, I64)

    def bit(p, n):
        return (p & jnp.uint32(1 << n)) != jnp.uint32(0)

    def _chain_step(carry, x):
        time, csum, csum_rst, fb, iv = carry
        p1_i, p2_i, dod_i, pay_i = x
        tsw = (p1_i & jnp.uint32(0x7F)).astype(I32)
        full_i = bit(p1_i, 7)
        unit_i = ((p1_i >> jnp.uint32(8)) & jnp.uint32(0xF)).astype(I32)
        emit_i = bit(p1_i, 12)
        sh = ((p2_i >> jnp.uint32(7)) & jnp.uint32(0x3F)).astype(I32)
        xnz_i = bit(p2_i, 17)
        ff_i = bit(p2_i, 18)
        diff_i = bit(p2_i, 19)
        sign_i = bit(p2_i, 20)

        dod = jnp.where(tsw > _c(0, I32),
                        _sign_extend(dod_i, jnp.maximum(tsw, _c(1, I32))),
                        _c(0, I64))
        d_k = jnp.where(full_i, dod,
                        dod * unit_tbl[jnp.clip(unit_i, 0, 15)])
        csum2 = csum + d_k
        time2 = time + jnp.where(emit_i, csum2 - csum_rst, _c(0, I64))
        csum_rst2 = jnp.where(full_i, csum2, csum_rst)

        xv_k = jnp.where(ff_i, pay_i,
                         jnp.where(xnz_i, _shl(pay_i, sh.astype(U64)),
                                   _c(0)))
        fb2 = jnp.where(ff_i, xv_k, fb ^ xv_k)  # XOR chain, full resets

        # int diff; sign: opcodeNegative(1) -> +, opcodePositive(0) -> -
        sd_k = jnp.where(diff_i,
                         jnp.where(sign_i, pay_i.astype(I64),
                                   -(pay_i.astype(I64))), _c(0, I64))
        iv2 = iv + sd_k
        return (time2, csum2, csum_rst2, fb2, iv2), (time2, fb2, iv2)

    z64 = jnp.zeros(S, I64)
    _, (time_o, fb_o, iv_o) = lax.scan(
        _chain_step, (base_time, z64, z64, jnp.zeros(S, U64), z64),
        (p1, p2, dod_bits, payload))

    # ---- vectorized epilogue over (P, S) ----
    emit = bit(p1, 12)
    isf = bit(p2, 16)
    diff = bit(p2, 19)
    unc = bit(p2, 21)
    vw = (p2 & jnp.uint32(0x7F)).astype(I32)

    # Canonical-XOR guard: a valid encoder always sets the top and
    # bottom bits of an uncontained meaningful window (the explicit
    # lead/trail fields ARE its clz/ctz); anything else desyncs the
    # carried pl/pt, so route such streams to the scalar path.
    top_ok = _shr(payload, jnp.maximum(vw - _c(1, I32), _c(0, I32))
                  .astype(U64)) == _c(1)
    bot_ok = (payload & _c(1)) == _c(1)
    err2 = jnp.any(unc & ~(top_ok & bot_ok), axis=0)
    prec = jnp.any(diff & ((payload > _c(_PRECISION_LIMIT))
                           | (jnp.abs(iv_o) > _c(_PRECISION_LIMIT, I64))),
                   axis=0)
    ts = jnp.where(emit, time_o, _c(0, I64))
    out_payload = jnp.where(isf, fb_o, iv_o.astype(U64))
    meta = (jnp.where(emit, _c(16, I32), _c(0, I32))
            | jnp.where(isf, _c(8, I32), _c(0, I32))
            | ((p2 >> jnp.uint32(13)) & jnp.uint32(0x7)).astype(I32)
            ).astype(jnp.uint8)

    return ts, out_payload, meta, prec, err2  # scan-major (P, S)


_CHAIN_IMPLS = ("fused", "gather")


def resolved_chains() -> str:
    """Which tail ``chains='auto'`` resolves to on this process'
    backend.  ``M3_DECODE_CHAINS`` overrides (parity tests pin both)."""
    impl = os.environ.get("M3_DECODE_CHAINS", "").strip()
    if impl:
        if impl not in _CHAIN_IMPLS:
            raise ValueError(
                f"M3_DECODE_CHAINS={impl!r}: expected one of {_CHAIN_IMPLS}")
        return impl
    return "gather" if jax.default_backend() == "tpu" else "fused"


def fallback_chains(chains: str) -> str:
    """The devguard stepping-down rule for the decode chains seam,
    owned ONCE (decode_batch_device + parallel/sharded_decode): step
    down to the OTHER tail (the fused tail also pins extract="jnp",
    so a failing Pallas extraction kernel steps down with it)."""
    return "fused" if chains != "fused" else "gather"


def resolved_regfile() -> str:
    """The register-file formulation for this process' backend (see
    ``_regfile4``): ``select`` on a TPU, where a gather costs ~9.7 ns
    an element, ``gather`` elsewhere.  Read on the host, never under a
    tracer; no option sets it."""
    return "select" if jax.default_backend() == "tpu" else "gather"


_REGFILE_RAN = threading.local()


def last_regfile() -> str | None:
    """The register-file formulation of the calling thread's last
    ``decode_batch_device`` call: what ran, a devguard fallback
    (always ``gather``) included; None before its first call."""
    return getattr(_REGFILE_RAN, "impl", None)


def _resolved_extract(chains: str) -> str:
    """The phase-2 field-extraction impl for a chains tail, resolved on
    the host: only the gather tail runs the extraction pass, so the
    fused tail pins "jnp" (keeps M3_DECODE_EXTRACT flips from
    needlessly splitting the fused jit cache)."""
    if chains != "gather":
        return "jnp"
    from m3_tpu.parallel import pallas_decode

    return pallas_decode.resolved_impl()


def decode_batch_device(words, nbits, max_points: int, default_unit: int = 1,
                        chains: str = "auto", scan_major: bool = False):
    """Decode (S, W+1) padded word arrays in parallel, in two phases:
    a sequential bit-boundary scan (``_decode_step``) that resolves
    control bits into a per-datapoint lane table, then branchless field
    extraction + chain reconstruction.  Where the second phase runs is
    the ``chains`` seam (same shape as M3_ENCODE_PLACE / the arena's
    ingest impls — one contract, backend-measured formulations,
    parity-pinned):

    ``gather``  phase 2 is a separate parallel pass (``_phase2``): lane
                tables -> payload gather (Pallas kernel on TPU, see
                parallel/pallas_decode.py) -> vectorized chain scan.
                The TPU shape: the boundary scan stays minimal and the
                heavy field traffic runs as wide fixed-lane gathers.
    ``fused``   the three value chains ride the boundary scan itself
                (``_decode_step(emit_chains=True)``), consuming payload
                words already in the step's register file.  The XLA-CPU
                shape: round-6 measured the separate chain scan paying
                more in (P, S) lane-table materialization + scan
                mechanics than the ~10 fused element-ops it saves.
    ``auto``    (default) fused on CPU, gather on TPU; override with
                M3_DECODE_CHAINS.  Both tails are bit-identical — pinned
                by the corpus sha256 + fuzz parity tests.

    The boundary scan's register file is the ``regfile`` seam
    (``_regfile4``), resolved here by backend (``resolved_regfile``)
    with no override: ``select`` on a TPU, ``gather`` elsewhere.  The
    devguard fallback runs ``gather``, the proven formulation, with
    the other chains tail; ``last_regfile`` names what ran.

    Returns (ts (S, max_points) int64, payload (S, max_points) uint64,
    meta (S, max_points) uint8, err (S,), prec (S,), ann (S,)).
    meta: bit4 = valid, bit3 = is_float, bits0-2 = multiplier.
    ``ann`` marks series whose stream carried annotation markers: their
    datapoints are decoded (each annotation consumes one scan slot) but
    the annotation bytes are skipped — callers needing them re-read via
    the scalar iterator.

    ``scan_major=True`` returns ts/payload/meta as (max_points, S) —
    the layout the scan produces — skipping the three (P, S)->(S, P)
    transposes.  As the TERMINAL ops of this jit they materialize full
    passes XLA cannot fuse into anything (round-6 CPU profiling: 30% of
    total decode wall-time); host callers flip axes with free numpy
    views instead, and in-jit callers compose the decode so XLA folds
    the layout change into their own downstream ops.

    This is the HOST wrapper: the chains/extract/regfile seams resolve here
    (env + backend reads are host state — under the tracer they would
    freeze into the first compile and the env override would silently
    stop responding), and the value-control table is fetched as a
    device ARGUMENT (constant-bloat: referenced as a module global it
    would be re-baked into every compiled HLO).  In-jit callers use
    ``_decode_batch_device`` (via ``__wrapped__``) and thread the
    table/statics themselves — see parallel/sharded_decode.py.
    """
    if chains == "auto":
        chains = resolved_chains()
    if chains not in _CHAIN_IMPLS:
        raise ValueError(f"chains={chains!r}: expected one of "
                         f"{_CHAIN_IMPLS + ('auto',)}")
    S, Wp = words.shape

    def _run(ch: str, regfile: str):
        _REGFILE_RAN.impl = regfile
        return _decode_batch_device(
            words, nbits, value_ctrl_table(), max_points=max_points,
            default_unit=default_unit, chains=ch,
            scan_major=scan_major, extract=_resolved_extract(ch),
            regfile=regfile)

    # device-guard seam: the fallback rides the OTHER chains tail as a
    # static argument (the fused tail also pins extract="jnp", so a
    # failing Pallas extraction kernel steps down with it) — both tails
    # are bit-identical, corpus sha256 + fuzz pinned.  Lane-table
    # admission is ONCE, outside the guard, at the worse of the
    # primary/fallback tails (encode_batch_device's rationale: an
    # admission reject is not a fault the fallback can relieve — typed
    # raise, no breaker).
    fb = fallback_chains(chains)
    lane_bytes = max(
        membudget.decode_lane_bytes(S, Wp, max_points, chains=chains,
                                    extract=_resolved_extract(chains)),
        membudget.decode_lane_bytes(S, Wp, max_points, chains=fb,
                                    extract=_resolved_extract(fb)))
    with membudget.transient("decode.lanes", lane_bytes):
        return devguard.run_guarded(
            "decode", lambda: _run(chains, resolved_regfile()),
            lambda: _run(fb, "gather"))


@functools.partial(jax.jit,
                   static_argnames=("max_points", "default_unit", "chains",
                                    "scan_major", "extract", "regfile"))
def _decode_batch_device(words, nbits, ctrl_tbl, max_points: int,
                         default_unit: int = 1, chains: str = "fused",
                         scan_major: bool = False, extract: str = "jnp",
                         regfile: str = "gather"):
    S, Wp = words.shape
    # Pad the stream with zero words so the phase-1 register file
    # (4 words at the cursor) and phase 2's 3-word funnels never read
    # out of bounds.
    wpad = jnp.pad(words, ((0, 0), (0, _PAD_WORDS)))
    nbits32 = nbits.astype(I32)

    # The per-series initial time unit depends only on the start
    # word's alignment — computed once here, not per scan step (i64
    # rem is division).
    d_ns = jnp.asarray(int(Unit(default_unit).nanos()), I64)
    aligned = (lax.rem(wpad[:, 0].astype(I64), d_ns)) == _c(0, I64)
    unit0 = jnp.where(aligned, _c(default_unit, I32), _c(0, I32))

    fused = chains == "fused"
    base_time = wpad[:, 0].astype(I64)
    carry0 = _decode_carry0(S, base_time if fused else None)
    step = functools.partial(_decode_step, words=wpad, nbits=nbits32,
                             unit0=unit0, ctrl_tbl=ctrl_tbl,
                             emit_chains=fused, regfile=regfile)

    # Decode k datapoints per loop iteration.  Unrolling chains k step
    # bodies inside one iteration, so the narrow carry stays fused
    # between them instead of round-tripping memory every datapoint,
    # and the loop's fixed dispatch overhead is paid T/k times.
    # (Round-5's unroll=1 pin predates the two-phase split: with the
    # 32-word window gone from the carry, unroll=2 measured ~11% faster
    # on XLA-CPU, round 6.)
    carry, lanes = lax.scan(step, carry0, None, length=max_points,
                            unroll=_DECODE_UNROLL)

    # A stream whose EOS marker sits exactly after max_points datapoints never
    # sets done inside the scan; peek once more for it.
    cursor, done = carry[0], carry[1]
    can = (cursor + 11) <= nbits32
    peek11 = jax.vmap(lambda w, c: _peek(w, c, _c(11, I32)))(wpad, cursor)
    eos_tail = can & ((peek11 >> _c(2)) == _c(0x100)) & ((peek11 & _c(3)) == _c(0))
    done = done | eos_tail
    err = carry[2] | (~done)  # not done after max_points -> error
    ann = carry[5]  # series whose stream carried annotation markers

    if fused:
        ts, payload, meta = lanes  # scan-major (P, S)
        prec, err2 = carry[17], carry[18]
    else:
        ts_off, p1, val_off, p2 = lanes  # scan-major (P, S) — no transpose
        ts, payload, meta, prec, err2 = _phase2(wpad, ts_off, p1, val_off,
                                                p2, extract_impl=extract)
    if not scan_major:
        ts, payload, meta = ts.T, payload.T, meta.T
    return ts, payload, meta, err | err2, prec, ann


def payload_value_bits(payload: np.ndarray, meta: np.ndarray) -> np.ndarray:
    """Host-side float64 BIT reconstruction from raw decode outputs.

    Float payloads (meta bit 3) ARE the bits; int payloads divide by
    10^mult (meta bits 0-2) in numpy's IEEE f64 — bit-identical to the
    reference's own f64 division, so the result upholds the codec's
    lossless-bits contract.  Elementwise/layout-blind: works on (S, P)
    or scan-major (P, S) arrays.  THE one home of the meta-layout
    knowledge on the host side — decode_batch and bench validation both
    call it.
    """
    isf = (meta & 8) != 0
    mult = (meta & 7).astype(np.int64)
    ivals = (payload.astype(np.int64).astype(np.float64)
             / np.power(10.0, mult))
    return np.where(isf, payload, ivals.view(np.uint64))


def decode_batch(streams: list[bytes], max_points: int,
                 default_unit: Unit = Unit.SECOND,
                 annotations_fallback: bool = True,
                 chains: str = "auto"):
    """Host-facing batched decode.

    Returns (timestamps (S, P) int64, values (S, P) float64,
    counts (S,), fallback (S,) bool).  Fallback series (>2^53
    magnitudes, errors) must use the scalar ReaderIterator.

    Annotated streams decode on device (timestamps/values come back
    correct; each annotation consumes one max_points slot) but their
    annotation BYTES are skipped, so by default they still flag
    fallback for callers that need the bytes (tag payloads, proto
    schemas); pass annotations_fallback=False when only the numeric
    series matters.
    """
    words, nbits = pack_streams(streams)
    return _decoded_to_host(
        decode_batch_device(jnp.asarray(words), jnp.asarray(nbits),
                            max_points=max_points,
                            default_unit=int(default_unit), chains=chains,
                            scan_major=True),
        annotations_fallback)


def _decoded_to_host(decoded, annotations_fallback: bool):
    """``decode_batch``'s host half: a scan-major device decode's six
    outputs -> (timestamps, values, counts, fallback)."""
    ts, payload, meta, err, prec, ann = decoded
    # Scan-major on device (the terminal transposes were 30% of decode
    # wall-time on CPU); the value reconstruction (payload_value_bits)
    # is elementwise (layout-blind), so it runs on the contiguous
    # (P, S) arrays and the (S, P) flip happens ONCE on the two
    # results, where numpy's tiled copy is cheaper than three XLA
    # passes.  .T.copy() (not ascontiguousarray) so the result is
    # ALWAYS a writable host copy — for S == 1 the transposed view is
    # already C-contiguous and ascontiguousarray would return the
    # read-only device buffer itself, breaking the in-place compaction
    # below.
    payload_pm = np.asarray(payload)            # (P, S), contiguous
    meta_pm = np.asarray(meta)
    valid_pm = (meta_pm & 16) != 0
    ts = np.asarray(ts).T.copy()
    values = payload_value_bits(payload_pm, meta_pm).view(np.float64).T.copy()
    valid = valid_pm.T
    counts = valid_pm.sum(axis=0)
    ann_np = np.asarray(ann)
    if ann_np.any():
        # Annotation slots leave holes in annotated rows; compact each
        # row's valid datapoints to a prefix (the contract counts rely
        # on).  ts/values are fresh writable host copies (the .T.copy()
        # above), so in-place edits are safe.
        for i in np.nonzero(ann_np)[0]:
            m = valid[i]
            k = int(m.sum())
            ts[i, :k] = ts[i, m]
            values[i, :k] = values[i, m]
            ts[i, k:] = 0
            values[i, k:] = 0.0
    fallback = np.asarray(err) | np.asarray(prec)
    if annotations_fallback:
        fallback = fallback | ann_np
    return ts, values, counts, fallback
