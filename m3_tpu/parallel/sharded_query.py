"""Sharded storage/query path over the device mesh.

The reference scatters a range query across shard-owning hosts and
merges replica streams on the coordinator
(`src/query/storage/fanout/storage.go:110`, dbnode `FetchTagged` per
shard owner, `encoding/multi_reader_iterator.go`).  The TPU-native
equivalent keeps the (shard × series × time) layout resident on a
`jax.sharding.Mesh` and runs the whole storage→query pipeline as one
SPMD program under ``shard_map``:

  1. **Sharded batched decode** — each device decodes only its own
     shard's packed M3TSZ streams (the window-carry scan from
     ``encoding/m3tsz_jax.py``), zero cross-device traffic.
  2. **Temporal stencil** — `rate()` with Prometheus extrapolation over
     the decoded (series × step) matrix, still local
     (`query/temporal.py`, reference `functions/temporal/rate.go`).
  3. **Cross-shard reduction** — per-shard partial `sum by (le)` bucket
     matrices combine with a single ``psum`` over the shard axis (XLA
     lowers it to a tree/ring all-reduce riding ICI), then
     `histogram_quantile` runs replicated on the reduced (bucket × step)
     matrix (`query/device_fns.py`, reference
     `functions/linear/histogram_quantile.go`).

This is the fan-out/merge query of SURVEY §2.7 with the network hop
replaced by a collective: the query
``histogram_quantile(q, sum by (le) (rate(bucket[R])))`` evaluated
end-to-end from compressed bytes to quantiles without leaving the mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from m3_tpu.encoding import m3tsz_jax as codec
from m3_tpu.parallel.mesh import SHARD_AXIS, MeshTopology, shard_map_compat
from m3_tpu.query import device_fns
from m3_tpu.query import temporal

_I64_MAX = np.iinfo(np.int64).max


def _raw(fn):
    return getattr(fn, "__wrapped__", fn)


def decode_to_step_series(words, nbits, max_points: int, ctrl_tbl,
                          chains: str = "fused", extract: str = "jnp"):
    """Device decode of packed streams -> padded (ts, float64 values)
    ready for the temporal stencils: invalid slots carry ts = i64 max
    (beyond every window edge the bounds compare against) and NaN values.

    Query math runs in the backend's native f64 (emulated on TPU):
    range-function output is not part of the bit-exactness contract the
    codec upholds — only the decoded payload integers are, and those
    stay exact.  ``ctrl_tbl`` is the codec's value-control table
    threaded as an argument (``codec.value_ctrl_table()``) and
    ``chains``/``extract`` are host-resolved statics — the
    constant-bloat/retrace-risk contract.
    """
    ts, payload, meta, err, prec, _ann = _raw(codec._decode_batch_device)(
        words, nbits, ctrl_tbl, max_points, chains=chains, extract=extract
    )
    valid = (meta & 16) != 0
    isf = (meta & 8) != 0
    mult = (meta & 7).astype(jnp.int64)
    fvals = jax.lax.bitcast_convert_type(payload, jnp.float64)
    ivals = payload.astype(jnp.int64).astype(jnp.float64) / (
        10.0 ** mult.astype(jnp.float64)
    )
    vals = jnp.where(isf, fvals, ivals)
    ts_p = jnp.where(valid, ts, _I64_MAX)
    vals_p = jnp.where(valid, vals, jnp.nan)
    return ts_p, vals_p, err | prec


def sharded_decode_rate_hq(
    topo: MeshTopology,
    words: jnp.ndarray,        # u64 (D, S, W) packed streams, shard-sharded
    nbits: jnp.ndarray,        # i64 (D, S)
    bucket_ids: jnp.ndarray,   # i32 (D, S) le-bucket index per series
    step_times: jnp.ndarray,   # i64 (T,) replicated
    ubs: jnp.ndarray,          # f64 (B,) ascending upper bounds, +Inf last
    range_nanos: int,
    q: float,
    max_points: int,
    num_buckets: int,
):
    """histogram_quantile(q, sum by (le) (rate(bucket[range]))) over the
    mesh.  Returns (rates (D, S, T) shard-sharded, hq (T,) replicated,
    errs (D, S)).  Host wrapper: resolves the codec's chains/extract
    seams and fetches the value-control table as a replicated argument
    (constant-bloat/retrace-risk contract), then dispatches to the
    jitted SPMD program."""
    chains = codec.resolved_chains()
    return _sharded_decode_rate_hq(
        topo, words, nbits, bucket_ids, step_times, ubs,
        codec.value_ctrl_table(), range_nanos=range_nanos, q=q,
        max_points=max_points, num_buckets=num_buckets, chains=chains,
        extract=codec._resolved_extract(chains))


@functools.partial(
    jax.jit,
    static_argnames=("topo", "max_points", "num_buckets", "q", "range_nanos",
                     "chains", "extract"),
)
def _sharded_decode_rate_hq(
    topo: MeshTopology,
    words: jnp.ndarray,
    nbits: jnp.ndarray,
    bucket_ids: jnp.ndarray,
    step_times: jnp.ndarray,
    ubs: jnp.ndarray,
    ctrl_tbl: jnp.ndarray,     # u32 (2^18,) codec value-control table
    range_nanos: int,
    q: float,
    max_points: int,
    num_buckets: int,
    chains: str,
    extract: str,
):
    mesh = topo.mesh

    def local(words, nbits, bucket_ids, step_times, ubs, ctrl_tbl):
        w, nb, bid = words[0], nbits[0], bucket_ids[0]
        ts_p, vals_p, errs = decode_to_step_series(
            w, nb, max_points, ctrl_tbl, chains=chains, extract=extract)
        rates = _raw(temporal.rate_family)(
            ts_p, vals_p, step_times, range_nanos, "rate"
        )  # (S, T)
        # Partial sum-by-bucket, then one all-reduce over the shard axis.
        # Bucket counts are small and static, so the by-bucket sum is an
        # unrolled masked reduction — exact f64 adds, no scatter (TPU
        # scatter measured ~1us/element, round 5, window 3).
        r0 = jnp.nan_to_num(rates)
        bidc = jnp.clip(bid, 0, num_buckets - 1)
        if num_buckets <= 64:
            part = jnp.stack([
                jnp.sum(jnp.where((bidc == b)[:, None], r0, 0.0), axis=0)
                for b in range(num_buckets)
            ])
        else:  # degenerate many-bucket case: keep the scatter form
            part = jnp.zeros((num_buckets, step_times.shape[0]),
                             dtype=jnp.float64)
            part = part.at[bidc].add(r0)
        total = jax.lax.psum(part, SHARD_AXIS)
        hq = device_fns._histogram_quantile_kernel(
            total,
            jnp.arange(num_buckets, dtype=jnp.int32)[None, :],
            jnp.asarray([num_buckets], jnp.int32),
            ubs[None, :],
            q,
        )[0]
        return rates[None], hq, errs[None]

    return shard_map_compat(
        local,
        mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(), P(),
                  P()),
        out_specs=(P(SHARD_AXIS), P(), P(SHARD_AXIS)),
    )(words, nbits, bucket_ids, step_times, ubs, ctrl_tbl)


def single_device_reference(words, nbits, bucket_ids, step_times, ubs,
                            range_nanos, q, max_points, num_buckets):
    """The same pipeline on one device over the flattened series axis —
    the equality oracle for the sharded path."""
    D, S = nbits.shape
    flat_w = words.reshape(D * S, -1)
    flat_nb = nbits.reshape(D * S)
    flat_bid = np.asarray(bucket_ids).reshape(D * S)
    chains = codec.resolved_chains()
    ts_p, vals_p, errs = decode_to_step_series(
        jnp.asarray(flat_w), jnp.asarray(flat_nb), max_points,
        codec.value_ctrl_table(), chains=chains,
        extract=codec._resolved_extract(chains)
    )
    rates = temporal.rate_family(ts_p, vals_p, jnp.asarray(step_times),
                                 range_nanos, "rate")
    total = np.zeros((num_buckets, len(step_times)), dtype=np.float64)
    r = np.nan_to_num(np.asarray(rates))
    np.add.at(total, np.clip(flat_bid, 0, num_buckets - 1), r)
    hq = device_fns._histogram_quantile_kernel(
        jnp.asarray(total),
        jnp.arange(num_buckets, dtype=jnp.int32)[None, :],
        jnp.asarray([num_buckets], jnp.int32),
        jnp.asarray(ubs)[None, :],
        q,
    )[0]
    return np.asarray(rates).reshape(D, S, -1), np.asarray(hq), np.asarray(errs).reshape(D, S)
