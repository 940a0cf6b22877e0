"""Every acked sample is there: a sample of series read back from the
open buffer and compared bit for bit with the arrays the generator made
from the seed.

Copied from ``chip_smoke.py`` (``check_readback``, ``_same_bits``) at
commit d4ba90b; this copy, not the original, is the yardstick from now
on.  Change: it counts the samples that are wrong or missing instead of
returning a verdict.
"""

from __future__ import annotations

import numpy as np



def read_raw(asm, namespace: str, data, idx: np.ndarray, points: int) -> np.ndarray:
    """(len(idx), points) f64 of what the node holds at the written
    timestamps; NaN-boxed sentinel where a point is missing."""
    lo, hi = int(data.ts[0]), int(data.ts[points - 1]) + 1
    pts = asm.db.read_batch(namespace, [data.ids[i] for i in idx], lo, hi)
    want_ts = data.ts[:points]
    out = np.full((len(idx), points), np.nan)
    out.view(np.uint64)[:] = MISSING
    for r, p in enumerate(pts):
        if not p:
            continue
        t = np.fromiter((x[0] for x in p), np.int64, len(p))
        v = np.fromiter((x[1] for x in p), np.float64, len(p))
        pos = np.searchsorted(want_ts, t)
        hit = (pos < points) & (want_ts[np.minimum(pos, points - 1)] == t)
        out[r, pos[hit]] = v[hit]
    return out


# a NaN payload no generator emits: marks a point the node did not return
MISSING = np.uint64(0x7FF8DEADBEEF0001)


def wrong_or_missing(got: np.ndarray, want: np.ndarray) -> int:
    """Samples whose bits differ from what was written (a missing point
    differs by construction)."""
    return int((np.ascontiguousarray(got).view(np.uint64)
                != np.ascontiguousarray(want).view(np.uint64)).sum())

