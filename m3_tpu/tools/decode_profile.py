"""Codec hot-loop cost breakdown: where does JAX decode/ENCODE time go?

Round 9 made this the SHARED profile harness: ``--mode decode`` (the
default, unchanged) decomposes the two-phase decoder exactly as in
round 6; ``--mode encode`` decomposes the round-9 two-phase encoder
into its three structural stages — the phase-1 lane-emission scan
(with a ``carry``/``classify`` sub-attribution: the scan skeleton vs
the convertToIntFloat decimal search that dominates it), the phase-2
exclusive prefix sum + fragment computation, and the word PLACEMENT
tail (M3_ENCODE_PLACE) — so a round's acceptance accounting can say
exactly where the time went.

    JAX_PLATFORMS=cpu python -m m3_tpu.tools.decode_profile \
        --mode encode [-S 10000] [-T 720] [-o PROFILE_encode.json]

The decode attribution method, unchanged since round 4:

Round-4 VERDICT weak #1/#3 established the method: decompose the decode
into structural layers by timing PROXY scans that share the real
decoder's carry topology and replay the TRUE per-step cursor advances
captured from a real decode — each layer adds one structural cost, and
deltas between consecutive layers attribute the time.  Round 5 measured
the OLD single-scan decoder with it (PROFILE_decode_r05.json: 82.4% in
``parse_arithmetic_and_outputs``, 1972 element-ops/datapoint, 2.18M
dp/s CPU — the numbers that motivated ISSUE 6).  THIS version profiles
the round-6 two-phase decoder that replaced it:

  carry    scan loop + carry round-trip only — the narrow (S,) lanes of
           the fused production carry (cursor, 11 control lanes, 7
           chain lanes; the 32-word window of the old decoder is GONE)
  reads    + the step's real read machinery: the 4-word register-file
           gather, the W0/rd3 funnels behind its ~8 in-register bit
           reads, the 2^18-entry value-control table gather, and the
           two 64-bit payload funnels
  full     the production decoder (adds control resolution, the three
           fused value chains, lane outputs) — ``chains='fused'``,
           scan-major, exactly what the host decode_batch runs on CPU

``window_refill`` from the r05 attribution no longer exists (no window
rides the carry); the gather tail's phase-2 stages are timed separately
(``gather_tail_s``).  Run:

    JAX_PLATFORMS=cpu python -m m3_tpu.tools.decode_profile \
        [-S 10000] [-T 720] [-o PROFILE_decode.json]

The same harness runs unmodified on a chip (drop the env pin).

Reference hot loop being chased: src/dbnode/encoding/m3tsz/iterator.go
:47-106 (~24ns/point/core on the Go side's 12-thread dev box).
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np

import os

import m3_tpu  # noqa: F401  (x64 config)
import jax

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    # One virtual device per core: XLA-CPU runs the decode's (S,)
    # element ops single-threaded (below its intra-op parallelization
    # threshold), so the machine number needs the series axis sharded
    # across cores — the native C++ yardstick threads across them too.
    from m3_tpu.parallel.mesh import enable_cpu_core_devices

    enable_cpu_core_devices()

import jax.numpy as jnp
from jax import lax

from m3_tpu.encoding import m3tsz_jax as mj

I32 = mj.I32
I64 = mj.I64
U64 = mj.U64


START = 1_600_000_000 * 10**9


def _corpus(S: int, T: int, seed: int = 42):
    """Realistic gauge series: 2h of 10s-spaced samples with jitter in
    value but regular timestamps (the common Prometheus shape)."""
    rng = np.random.default_rng(seed)
    ts = np.tile(START + np.arange(1, T + 1) * 10 * 10**9,
                 (S, 1)).astype(np.int64)
    base = rng.uniform(10, 1000, (S, 1))
    vals = np.round(base + rng.normal(0, base * 0.05, (S, T)), 2)
    starts = np.full(S, START, np.int64)
    return ts, vals, starts


def _encode(S: int, T: int):
    from m3_tpu import native

    ts, vals, starts = _corpus(S, T)
    out = native.encode_batch(ts, vals, starts)
    if out is None or out[1].any():
        raise RuntimeError("native encoder unavailable; profile needs it")
    return out[0]


def _prep(words, nbits):
    wpad = jnp.pad(words, ((0, 0), (0, mj._PAD_WORDS)))
    nbits32 = nbits.astype(I32)
    d_ns = jnp.asarray(10**9, I64)
    aligned = (lax.rem(wpad[:, 0].astype(I64), d_ns)) == jnp.asarray(0, I64)
    unit0 = jnp.where(aligned, jnp.asarray(1, I32), jnp.asarray(0, I32))
    return wpad, nbits32, unit0


@functools.partial(jax.jit, static_argnames=("max_points",))
def _capture_cursors(words, nbits, ctrl_tbl, max_points: int):
    """Run the real phase-1 step capturing the cursor after every step."""
    S = words.shape[0]
    wpad, nbits32, unit0 = _prep(words, nbits)
    inner = functools.partial(mj._decode_step, words=wpad, nbits=nbits32,
                              unit0=unit0, ctrl_tbl=ctrl_tbl)

    def step(c, x):
        c2, _ = inner(c, x)
        return c2, c2[0]

    _, cursors = lax.scan(step, mj._decode_carry0(S), None,
                          length=max_points)
    return cursors  # (T, S)


@functools.partial(jax.jit, static_argnames=("mode", "fused"))
def _proxy_scan(wpad, advances, base_time, tbl, mode: str, fused: bool):
    """Structural proxy: replays true cursor advances through the real
    carry topology (mode='carry') plus the real read machinery
    (mode='reads').  ``fused`` selects the PROFILED decoder's carry
    shape — the 7 chain lanes ride only when the fused tail does (on
    the gather tail the production phase-1 carry is the 12 narrow
    lanes; carrying the extra 7 would overstate the carry layer).
    ``tbl`` is the codec's value-control table threaded as an argument
    (mj.value_ctrl_table() — referencing the module global here baked
    ~1MB of constants into this proxy's HLO; constant-bloat)."""
    S = wpad.shape[0]
    carry0 = mj._decode_carry0(S, base_time if fused else None)

    def body(carry, adv):
        cursor = carry[0]
        # the narrow lanes ride the carry untouched: the layer measures
        # the scan's structural round-trip, which r05 already showed is
        # nearly free on CPU (0.1%) — the point of keeping them is the
        # identical carry SIGNATURE, not synthetic per-lane work
        new_rest = carry[1:]
        if mode == "reads":
            # the real step's read machinery, at the true cursor
            c0 = cursor
            w0i = c0 >> jnp.asarray(6, I32)
            r0, r1, r2, r3 = mj._regfile4(wpad, w0i)
            rf_base = w0i << jnp.asarray(6, I32)
            off0 = (c0 - rf_base).astype(U64)
            W0 = (r0 << off0) | jnp.where(
                off0 > mj._c(0), r1 >> ((mj._c(64) - off0) & mj._c(63)),
                mj._c(0))
            # ~8 in-register reads (marker, 4 varint bytes, unit byte,
            # opcode) are shifts of W0; two 64-bit rd3 payload funnels
            # and the 16-bit control read use the full register file.
            a = W0
            for k, w in enumerate((11, 8, 8, 8, 8, 8, 4)):
                a = a ^ (W0 << mj._c(3 * k).astype(U64)) >> mj._c(64 - w)
            x16 = a & mj._c(0xFFFF)
            tv = tbl[x16.astype(I32)]  # the value-control table gather
            # the step's TWO 64-bit rd3 payload funnels (raw at the
            # value offset, draw at the dod offset), full select chains
            def rd3(o):
                k2 = o >> jnp.asarray(6, I32)
                r = (o & jnp.asarray(63, I32)).astype(U64)
                hi = jnp.where(k2 == jnp.asarray(0, I32), r0,
                               jnp.where(k2 == jnp.asarray(1, I32), r1, r2))
                lo = jnp.where(k2 == jnp.asarray(0, I32), r1,
                               jnp.where(k2 == jnp.asarray(1, I32), r2, r3))
                return (hi << r) | jnp.where(
                    r > mj._c(0), lo >> ((mj._c(64) - r) & mj._c(63)),
                    mj._c(0))

            raw = rd3((c0 + jnp.asarray(35, I32)) - rf_base)
            draw = rd3((c0 + jnp.asarray(19, I32)) - rf_base)
            a = a ^ raw ^ draw ^ tv.astype(U64)
            # fold into a carried lane (keeps the chain live)
            new_rest = new_rest[:-2] + (
                new_rest[-2] | (a == mj._c(1)), new_rest[-1])
        new_cursor = cursor + adv
        return (new_cursor,) + new_rest, None

    carry, _ = lax.scan(body, carry0, advances,
                        unroll=mj._DECODE_UNROLL)
    return carry[0], carry[-2]


def _time(fn, reps: int = 4) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def profile(S: int, T: int) -> dict:
    streams = _encode(S, T)
    words_np, nbits_np = mj.pack_streams(streams)
    words = jnp.asarray(words_np)
    nbits = jnp.asarray(nbits_np)
    max_points = T + 1

    dev = jax.devices()[0]
    chains = mj.resolved_chains()
    out: dict = {
        "S": S, "T": T, "platform": dev.platform,
        "device_kind": dev.device_kind,
        "total_datapoints": S * T,
        "decoder": "two-phase (round 6)",
        "chains": chains,
        "layout": "scan_major (the production decode_batch path)",
    }

    # Real decode — the canonical path: auto chains tail, scan-major,
    # series-sharded over every local device (parallel/sharded_decode:
    # one scan per core; outputs bit-identical to single-device).  The
    # single-device run is timed too — the structural attribution below
    # decomposes it, and it is the number methodologically comparable
    # to r05 (which was single-device).
    from m3_tpu.parallel.sharded_decode import decode_batch_device_sharded

    n_dev = jax.device_count()
    full1 = lambda: mj.decode_batch_device(words, nbits, max_points,
                                           chains=chains, scan_major=True)
    t_compile0 = time.perf_counter()
    jax.block_until_ready(full1())
    out["full_compile_s"] = round(time.perf_counter() - t_compile0, 1)
    t_full1 = _time(full1)
    if n_dev > 1:
        fullN = lambda: decode_batch_device_sharded(
            words, nbits, max_points, chains=chains, scan_major=True)
        jax.block_until_ready(fullN())
        t_full = _time(fullN)
        out["devices"] = n_dev
    else:
        t_full = t_full1

    # The back-compat (S, P) contract and the other chains tail, for
    # the old-vs-new and seam-flip comparisons.
    sm = lambda: mj.decode_batch_device(words, nbits, max_points,
                                        chains=chains, scan_major=False)
    jax.block_until_ready(sm())
    t_series_major = _time(sm, reps=2)
    other = "gather" if chains == "fused" else "fused"
    ot = lambda: mj.decode_batch_device(words, nbits, max_points,
                                        chains=other, scan_major=True)
    jax.block_until_ready(ot())
    t_other = _time(ot, reps=2)

    # True per-step advances, replayed by every proxy.
    cursors = np.asarray(_capture_cursors(words, nbits,
                                          mj.value_ctrl_table(), max_points))
    adv = np.diff(np.concatenate(
        [np.zeros((1, cursors.shape[1]), cursors.dtype), cursors]), axis=0)
    advances = jnp.asarray(adv.astype(np.int32))

    wpad = jnp.pad(words, ((0, 0), (0, mj._PAD_WORDS)))
    base_time = wpad[:, 0].astype(I64)

    layers = {}
    for mode in ("carry", "reads"):
        fn = lambda m=mode: _proxy_scan(wpad, advances, base_time,
                                        mj.value_ctrl_table(), m,
                                        fused=(chains == "fused"))
        jax.block_until_ready(fn())  # compile
        layers[mode] = _time(fn)
    layers["full"] = t_full1  # attribution decomposes the 1-device run

    # Per-layer attribution (seconds and share of the single-device
    # full — the run the proxies structurally mirror).
    t_carry = layers["carry"]
    t_reads = layers["reads"] - layers["carry"]
    t_arith = layers["full"] - layers["reads"]
    out["seconds"] = {k: round(v, 4) for k, v in layers.items()}
    out["seconds"]["full_all_devices"] = round(t_full, 4)
    out["seconds"]["full_series_major"] = round(t_series_major, 4)
    out["seconds"][f"full_{other}_tail"] = round(t_other, 4)
    out["attribution_s"] = {
        "scan_carry_roundtrip": round(t_carry, 4),
        "bit_read_funnels": round(t_reads, 4),
        "parse_arithmetic_and_outputs": round(t_arith, 4),
    }
    out["attribution_pct"] = {
        k: round(100 * v / t_full1, 1)
        for k, v in (("scan_carry_roundtrip", t_carry),
                     ("bit_read_funnels", t_reads),
                     ("parse_arithmetic_and_outputs", t_arith))
    }
    out["attribution_note"] = (
        "window_refill (12.8% in r05) no longer exists: the two-phase "
        "split removed the 32-word window from the carry; reads = the "
        "4-word register file + funnels + value-control table gather. "
        "NOTE on the r06 target 'parse arithmetic < 40%': the ratio "
        "stays arith-dominant because the rewrite shrank the READ "
        "layers even harder than the arithmetic (r05 -> r06 absolute "
        "seconds: reads+refill 0.58 -> ~0.11, arith 2.72 -> ~0.85); "
        "the decision-relevant flip DID happen — the old formulation's "
        "arith-free ceiling was 12.4M dps, the new decoder runs past "
        "it and its own ceiling is the ceiling_if_arith_free below.")
    out["dps"] = {
        "full": round(S * T / t_full),
        "full_1device": round(S * T / t_full1),
        "full_series_major": round(S * T / t_series_major),
        f"full_{other}_tail": round(S * T / t_other),
        "ceiling_if_arith_free": round(S * T / max(layers["reads"], 1e-9)),
        "ceiling_if_only_carry": round(S * T / max(t_carry, 1e-9)),
        "old_r05_single_scan": 2_182_331,
    }
    out["dps"]["vs_old_r05"] = round(
        out["dps"]["full"] / out["dps"]["old_r05_single_scan"], 2)
    out["dps_note"] = (
        "full = series-sharded across all local devices (one scan per "
        "core, bit-identical outputs; parallel/sharded_decode.py) — "
        "the machine number, comparable to the THREADED native_cpp_dps "
        "yardstick; full_1device is the r05-methodology-comparable "
        "single-core number")

    # Native C++ single-core yardstick on the same corpus.
    try:
        from m3_tpu import native

        t0 = time.perf_counter()
        native.decode_batch(streams, max_points)
        out["native_cpp_dps"] = round(S * T / (time.perf_counter() - t0))
    except Exception:
        pass

    # Structural op counts: the formulation executes EVERY lane through
    # EVERY branch (branchless SIMD), so ops-per-datapoint × lanes is
    # the compute the backend must sustain — the C++ decoder takes only
    # the ~100 ops of the branch each point actually needs.
    try:
        S_ = words.shape[0]
        wz = jnp.zeros_like(wpad)
        dstep = functools.partial(
            mj._decode_step, words=wz, nbits=nbits.astype(I32),
            unit0=jnp.zeros(S_, I32), ctrl_tbl=mj.value_ctrl_table(),
            emit_chains=(chains == "fused"))
        carry0 = mj._decode_carry0(
            S_, base_time if chains == "fused" else None)
        jx = jax.make_jaxpr(dstep)(carry0, None)
        ops = _count_ops(jx.jaxpr)
        out["step_ops"] = ops
        out["element_ops_per_datapoint"] = ops
        out["element_ops_r05"] = 1972
        out["sustained_element_ops_per_sec"] = round(
            ops * S * max_points / t_full)
    except Exception as exc:  # noqa: BLE001 — analysis is best-effort
        out["step_ops_error"] = f"{type(exc).__name__}: {exc}"
    return out


def _count_ops(j):
    """One home: x/costwatch owns the jaxpr equation counter — the
    costs artifact cross-checks THESE hand counts against the
    HLO-derived numbers every run (opsdp_crosscheck), which only means
    something if both sides count the same way."""
    from m3_tpu.x.costwatch import count_jaxpr_ops

    return count_jaxpr_ops(j)


def profile_encode(S: int, T: int) -> dict:
    """Two-phase ENCODE attribution: phase-1 scan (carry/classify
    sub-layers) -> prefix-sum+fragments -> placement.  Each proxy jit
    is a PREFIX of the real pipeline (same scan, same lane tables), so
    consecutive deltas attribute the stages; the final layer is the
    production encode_batch_device."""
    import jax.numpy as jnp

    ts_np, vals_np, _starts = _corpus(S, T)
    starts = np.full(S, ts_np[0, 0] - 10 * 10**9, np.int64)
    out_words = T * 40 // 64 + 8
    jts = jnp.asarray(ts_np)
    jvb = jnp.asarray(vals_np.view(np.uint64))
    jst = jnp.asarray(starts)
    jva = jnp.asarray(np.ones((S, T), bool))

    dev = jax.devices()[0]
    place = mj.resolved_place()
    out: dict = {
        "S": S, "T": T, "platform": dev.platform,
        "device_kind": dev.device_kind,
        "total_datapoints": S * T,
        "encoder": "two-phase lane emission (round 9)",
        "place": place,
    }

    step = functools.partial(mj._encode_step, unit=1,
                             default_unit_is_32bit=True)
    vstep = jax.vmap(step)
    # THE codec's own carry initializer (one owner for the layout —
    # a carry change must not silently desync these proxies).
    carry0 = lambda: mj._encode_carry0(S, jst, 1)

    @functools.partial(jax.jit, static_argnames=("mode",))
    def proxy(a, b, v, mode):
        def body_carry(c, x):
            # scan skeleton: the narrow carry round-trips untouched;
            # the lane outputs are live (folded from the inputs) so
            # XLA cannot DCE the output buffers.
            t, vb, _va = x
            z = (t + vb.astype(I64)).astype(U64)
            zi = jnp.zeros(S, I32)
            return c, (jnp.stack([z, z, z, z]),
                       jnp.stack([zi, zi, zi, zi]))

        def body_classify(c, x):
            t, vb, _va = x
            val, mult, isf, prec = mj.classify_value(vb, c[4])
            z = (t + val).astype(U64)
            zi = mult + jnp.where(isf | prec, 1, 0)
            return c, (jnp.stack([z, z, z, z]),
                       jnp.stack([zi, zi, zi, zi]))

        body = {"carry": body_carry, "classify": body_classify,
                "phase1": lambda c, x: (lambda c2, l:
                    (c2, (jnp.stack(l[:4]), jnp.stack(l[4:]))))(
                        *vstep(c, x))}[mode]
        carry, (lv, lw) = lax.scan(body, carry0(),
                                   (a.T, b.T, v.T), unroll=mj._SCAN_UNROLL)
        return lv.astype(U64).sum() + lw.sum(dtype=I32) + carry[0].sum()

    layers: dict = {}
    compile_s: dict = {}
    for mode in ("carry", "classify", "phase1"):
        fn = lambda m=mode: proxy(jts, jvb, jva, m)
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        compile_s[mode] = round(time.perf_counter() - t0, 1)
        layers[mode] = _time(fn, reps=3)

    # prefix+frags: the real pipeline minus placement — phase 1 plus
    # the exclusive prefix sums and fragment computation.
    @jax.jit
    def prefix_frags(a, b, v):
        def scan_fn(c, x):
            c2, (t0_, t1_, v0_, v1_, n0, n1, n2, n3) = vstep(c, x)
            return c2, (jnp.stack([t0_, t1_, v0_, v1_]),
                        jnp.stack([n0, n1, n2, n3]))
        carry, (lv, lw) = lax.scan(scan_fn, carry0(), (a.T, b.T, v.T),
                                   unroll=mj._SCAN_UNROLL)
        lens = lw.sum(axis=1, dtype=I32)
        off_dp = jnp.cumsum(lens, axis=0, dtype=I32) - lens + jnp.asarray(64, I32)
        pos = off_dp[:, None, :] + (jnp.cumsum(lw, axis=1, dtype=I32) - lw)
        F = 4 * T
        hi, lo, gw = mj._lane_frags(lv.reshape(F, S), pos.reshape(F, S),
                                    lw.reshape(F, S))
        return hi.sum() + lo.sum() + gw.sum(dtype=I32)

    t0 = time.perf_counter()
    jax.block_until_ready(prefix_frags(jts, jvb, jva))
    compile_s["prefix_frags"] = round(time.perf_counter() - t0, 1)
    layers["prefix_frags"] = _time(lambda: prefix_frags(jts, jvb, jva),
                                   reps=3)

    # the production encode, single device (the run the attribution
    # decomposes) and series-sharded (the machine number).
    full1 = lambda p=place: mj.encode_batch_device(
        jts, jvb, jst, jva, out_words=out_words, place=p)
    t0 = time.perf_counter()
    res = jax.block_until_ready(full1())
    compile_s["full"] = round(time.perf_counter() - t0, 1)
    assert not np.asarray(res["fallback"]).any()
    layers["full"] = _time(full1, reps=3)

    from m3_tpu.parallel.sharded_encode import encode_batch_device_sharded

    n_dev = jax.device_count()
    if n_dev > 1:
        fullN = lambda: encode_batch_device_sharded(
            jts, jvb, jst, jva, out_words=out_words, place=place)
        jax.block_until_ready(fullN())
        t_full = _time(fullN, reps=3)
        out["devices"] = n_dev
    else:
        t_full = layers["full"]

    # the other placement tails, for the seam's flip decision (pallas
    # is skipped off-TPU: interpret mode has no perf meaning)
    for other in mj._PLACE_IMPLS:
        if other == place or (other == "pallas"
                              and dev.platform != "tpu"):
            continue
        try:
            jax.block_until_ready(full1(other))
            layers[f"full_{other}"] = _time(lambda: full1(other), reps=2)
        except Exception as exc:  # noqa: BLE001 — record, keep going
            out[f"full_{other}_error"] = f"{type(exc).__name__}: {exc}"

    t_carry = layers["carry"]
    t_classify = layers["classify"] - layers["carry"]
    t_emit = layers["phase1"] - layers["classify"]
    t_prefix = layers["prefix_frags"] - layers["phase1"]
    t_place = layers["full"] - layers["prefix_frags"]
    out["seconds"] = {k: round(v, 4) for k, v in layers.items()}
    out["seconds"]["full_all_devices"] = round(t_full, 4)
    out["compile_s"] = compile_s
    out["attribution_s"] = {
        "scan_carry_roundtrip": round(t_carry, 4),
        "classify_decimal_search": round(t_classify, 4),
        "lane_emission_rest_of_step": round(t_emit, 4),
        "prefix_sum_and_fragments": round(t_prefix, 4),
        "word_placement": round(t_place, 4),
    }
    out["attribution_pct"] = {
        k: round(100 * v / layers["full"], 1)
        for k, v in (("scan_carry_roundtrip", t_carry),
                     ("classify_decimal_search", t_classify),
                     ("lane_emission_rest_of_step", t_emit),
                     ("prefix_sum_and_fragments", t_prefix),
                     ("word_placement", t_place))
    }
    out["dps"] = {
        "full": round(S * T / t_full),
        "full_1device": round(S * T / layers["full"]),
        "ceiling_if_placement_free": round(S * T / layers["prefix_frags"]),
        "ceiling_if_scan_only": round(S * T / layers["phase1"]),
        "ceiling_if_classify_free": round(
            S * T / max(layers["phase1"] - t_classify, 1e-9)),
    }
    for k, v in layers.items():
        if k.startswith("full_"):
            out["dps"][k] = round(S * T / v)
    out["dps_note"] = (
        "full = series-sharded across all local devices "
        "(parallel/sharded_encode.py), comparable to the THREADED "
        "native yardstick; full_1device is the r07-methodology-"
        "comparable single-core number (r07 measured the old scan at "
        "S=512 — its per-dp cost was batch-size-flat)")

    # native C++ yardstick on the same corpus
    try:
        from m3_tpu import native

        t0 = time.perf_counter()
        enc = native.encode_batch(ts_np, vals_np, starts)
        if enc is not None and not enc[1].any():
            out["native_cpp_dps"] = round(
                S * T / (time.perf_counter() - t0))
    except Exception:
        pass

    # structural op counts (branchless SIMD: every lane pays every path)
    try:
        xs1 = (jts.T[0], jvb.T[0], jva.T[0])
        jx = jax.make_jaxpr(step)(carry0(), xs1)
        ops = _count_ops(jx.jaxpr)
        out["step_ops"] = ops
        out["element_ops_per_datapoint_phase1"] = ops
        jc = jax.make_jaxpr(
            lambda vb, m: mj.classify_value(vb, m))(jvb[:, 0],
                                                    jnp.zeros(S, I32))
        out["classify_ops"] = _count_ops(jc.jaxpr)
        out["element_ops_r07_wide_carry"] = 7800  # ~25 _bb_append funnels
    except Exception as exc:  # noqa: BLE001 — analysis is best-effort
        out["step_ops_error"] = f"{type(exc).__name__}: {exc}"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("decode", "encode"),
                    default="decode")
    ap.add_argument("-S", type=int, default=10_000)
    ap.add_argument("-T", type=int, default=720)
    ap.add_argument("-o", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    res = (profile(args.S, args.T) if args.mode == "decode"
           else profile_encode(args.S, args.T))
    line = json.dumps(res, indent=2)
    print(line)
    if args.o:
        with open(args.o, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
