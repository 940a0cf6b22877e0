"""Generic segmented-reduction primitive over sorted batches.

Sort + head-flag segmented ``associative_scan`` + a gather at segment
ends is the scatter-free reduction idiom on accelerators: reduce within
segments of an already-sorted batch in one pass, then gather each key's
segment total from the last position of its segment.
query/device_fns.py runs its grouped PromQL aggregations through it
(`_segment_reduce_kernel`); the sort and the segment ends are the host's
there (`_sorted_plan`), since the group ids are a host array.

(The packed aggregation arenas, aggregator/packed.py, carry their own
segmented scan over the sorted batch; this helper is generic and serves
the query engine.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def head_flag_scan(is_start, adds=(), mins=(), maxs=()):
    """Inclusive segmented reduction via one associative scan.

    ``is_start`` (N,) bool marks segment heads of the already-sorted
    batch.  Each array in ``adds``/``mins``/``maxs`` — shape (N,) or
    (N, ...) with any trailing lane dims — is reduced with +/min/max
    within segments; position i of a result holds the reduction of its
    segment's prefix up to i, so the LAST position of a segment holds
    the full segment total.  Returns (adds, mins, maxs) tuples in the
    caller's order.
    """
    n_adds, n_mins = len(adds), len(mins)

    def comb(a, b):
        fa, fb = a[0], b[0]
        out = [fa | fb]
        j = 1

        def sel(flag, yes, no):
            # broadcast the (k,) head flag across any trailing lane dims
            return jnp.where(
                flag.reshape(flag.shape + (1,) * (yes.ndim - 1)), yes, no)

        for _ in range(n_adds):
            out.append(sel(fb, b[j], a[j] + b[j]))
            j += 1
        for _ in range(n_mins):
            out.append(sel(fb, b[j], jnp.minimum(a[j], b[j])))
            j += 1
        for _ in range(len(maxs)):
            out.append(sel(fb, b[j], jnp.maximum(a[j], b[j])))
            j += 1
        return tuple(out)

    res = jax.lax.associative_scan(
        comb, (is_start,) + tuple(adds) + tuple(mins) + tuple(maxs))
    return (res[1:1 + n_adds], res[1 + n_adds:1 + n_adds + n_mins],
            res[1 + n_adds + n_mins:])
