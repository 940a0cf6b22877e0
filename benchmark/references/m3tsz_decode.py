"""A plain scalar M3TSZ reader, written from the format: what the bytes
of a flushed block hold, decoded with nothing of the program.

The format (upstream M3 ``src/dbnode/encoding/m3tsz`` and ``scheme.go``,
as its documents and ``PAPER.md`` lay it out), MSB first:

- 64 bits: the stream's start, UnixNano.  The time unit starts as
  seconds if the start is a whole second, else as "none".
- per datapoint a timestamp, then a value.
- a timestamp is, after any markers, a delta-of-delta in the current
  unit: ``0`` = 0; ``10`` + 7 bits; ``110`` + 9; ``1110`` + 12; ``1111``
  + 32 bits (seconds, milliseconds) or 64 (micro-, nanoseconds), each
  two's complement.  A marker is the 9 bits ``100000000`` and 2 more:
  ``00`` end of stream, ``01`` annotation, ``10`` time unit (one byte,
  the new unit; if it differs, THIS datapoint's delta-of-delta is 64
  bits of nanoseconds and the running delta restarts at 0 after it).
- the first value: ``1`` + 64 bits (float mode) or ``0`` + sig/mult
  update + a diff (int mode).  Every later value: ``1`` = no update:
  a float XOR in float mode, a diff in int mode; ``0`` = update, then
  ``1`` repeat the last value, or ``0`` and the mode bit as for the
  first value.
- a float XOR: ``0`` = same bits; ``10`` = the meaningful bits of the
  XOR inside the previous XOR's leading/trailing zeros; ``11`` + 6 bits
  of leading zeros + 6 bits (meaningful - 1) + the meaningful bits.
- sig/mult update: ``1`` then (``0`` = sig 0 | ``1`` + 6 bits = sig - 1),
  or ``0`` keep; then ``1`` + 3 bits of multiplier (at most 6), or ``0``.
- a diff: a sign bit (``1`` adds, ``0`` subtracts) and ``sig`` bits.
  The value is the running integer over 10^mult, as an IEEE double.

Departures from upstream's iterator: an annotation marker is REFUSED
(ValueError): the flush writes none, and a reference that skipped one
could not say so.  Int optimisation is always on and the default unit
is the second (upstream's defaults, and this deployment's).  The
running integer is a Python float, as upstream's is a float64: beyond
2^53 it rounds as upstream's does.

numpy only for the arrays it returns.
"""

from __future__ import annotations

import struct

import numpy as np

_UNIT_NANOS = {1: 10**9, 2: 10**6, 3: 10**3, 4: 1, 5: 60 * 10**9,
               6: 3600 * 10**9, 7: 86400 * 10**9, 8: 365 * 86400 * 10**9}
_DEFAULT_DOD_BITS = {1: 32, 2: 32, 3: 64, 4: 64}
_MASK64 = (1 << 64) - 1


def _signed(v: int, bits: int) -> int:
    return v - (1 << bits) if v >> (bits - 1) else v


class _Bits:
    """The stream as one big integer, read from its most significant
    bit."""

    def __init__(self, data: bytes):
        self.v = int.from_bytes(data, "big")
        self.n = len(data) * 8
        self.pos = 0

    def peek(self, k: int):
        if self.pos + k > self.n:
            return None
        return (self.v >> (self.n - self.pos - k)) & ((1 << k) - 1)

    def read(self, k: int) -> int:
        if k == 0:
            return 0
        out = self.peek(k)
        if out is None:
            raise EOFError(f"stream ends inside a {k}-bit field at bit "
                           f"{self.pos} of {self.n}")
        self.pos += k
        return out


def decode(data: bytes):
    """One stream -> (timestamps (n,) int64 UnixNano, values' BITS (n,)
    uint64), in the stream's order."""
    ts, vals = [], []
    if not data:
        return np.empty(0, np.int64), np.empty(0, np.uint64)
    s = _Bits(data)
    time = _signed(s.read(64), 64)
    unit = 1 if time % _UNIT_NANOS[1] == 0 else 0
    delta = 0
    # value state
    is_float, fbits, prev_xor = False, 0, 0
    ival, sig, mult = 0.0, 0, 0
    first = True
    while True:
        # -- timestamp: markers, then the delta-of-delta ---------------
        unit_changed = False
        done = False
        while s.peek(11) is not None and s.peek(11) >> 2 == 0x100:
            marker = s.read(11) & 3
            if marker == 0:
                done = True
                break
            if marker == 1:
                raise ValueError("annotation marker: this reference reads "
                                 "no annotated stream")
            if marker == 2:
                new = s.read(8)
                new = new if new in _UNIT_NANOS else 0
                if new != 0 and new != unit:
                    unit_changed = True
                unit = new
            else:
                raise ValueError(f"unknown marker {marker}")
        if done:
            break
        if unit_changed:
            dod = _signed(s.read(64), 64)
        else:
            if unit not in _DEFAULT_DOD_BITS:
                raise ValueError(f"no delta-of-delta scheme for unit {unit}")
            if s.read(1) == 0:
                dod = 0
            else:
                for width in (7, 9, 12):
                    if s.read(1) == 0:
                        dod = _signed(s.read(width), width)
                        break
                else:
                    width = _DEFAULT_DOD_BITS[unit]
                    dod = _signed(s.read(width), width)
                dod *= _UNIT_NANOS[unit]
        delta += dod
        time += delta
        if unit_changed:
            delta = 0
        # -- value ------------------------------------------------------
        if first or s.read(1) == 0:         # an update (the first always is)
            if not first and s.read(1) == 1:
                pass                        # repeat: every state stays
            elif s.read(1) == 1:            # float mode: the full 64 bits
                fbits = prev_xor = s.read(64)
                is_float = True
            else:                           # int mode: sig, mult, a diff
                if s.read(1) == 1:
                    sig = s.read(6) + 1 if s.read(1) == 1 else 0
                if s.read(1) == 1:
                    mult = s.read(3)
                    if mult > 6:
                        raise ValueError(f"multiplier {mult} > 6")
                ival = _diff(s, ival, sig)
                is_float = False
        elif is_float:
            if s.read(1) == 0:
                prev_xor = 0
            else:
                if s.read(1) == 0:          # inside the previous window
                    lead = 64 - prev_xor.bit_length() if prev_xor else 64
                    trail = ((prev_xor & -prev_xor).bit_length() - 1
                             if prev_xor else 0)
                    prev_xor = (s.read(64 - lead - trail) << trail) & _MASK64
                else:
                    lead = s.read(6)
                    meaningful = s.read(6) + 1
                    prev_xor = (s.read(meaningful)
                                << (64 - lead - meaningful)) & _MASK64
                fbits ^= prev_xor
        else:
            ival = _diff(s, ival, sig)
        first = False
        ts.append(time)
        if is_float:
            vals.append(fbits)
        else:
            v = ival if mult == 0 else ival / 10.0 ** mult
            vals.append(struct.unpack("<Q", struct.pack("<d", v))[0])
    return np.asarray(ts, np.int64), np.asarray(vals, np.uint64)


def _diff(s: _Bits, ival: float, sig: int) -> float:
    sign = 1.0 if s.read(1) == 1 else -1.0
    return ival + sign * float(s.read(sig))
