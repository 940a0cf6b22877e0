"""Machine-independent perf fingerprints from XLA cost/memory analysis.

Every wall-clock number this repo has ever committed came from a
1-physical-core box with no chip attached — the formulation work those rounds shipped (decode 1972→670
ops/dp, encode 7.8K→1485) is tracked only by hand-counted proxies
(tools/decode_profile.py) and timing loops noisy enough that the soak
gate had to quarantine its own setup phase.  XLA already computes what
a formulation-regression gate needs, at COMPILE time, deterministically,
on any box:

* ``jit(f).lower(args).compile().cost_analysis()`` — flops,
  transcendentals, bytes accessed of the optimized HLO;
* ``.memory_analysis()`` — argument/output/temp bytes (peak derives);
* the compiled module text — an HLO op-class histogram.

This module is the registry + extractor: every hot-path device program
is named as a **stage** with its pinned canonical shapes (the artifact
is only comparable at fixed shape — the ``cli hops`` precedent), and
:func:`run_stages` lowers + compiles each one (ShapeDtypeStructs only:
no data, no transfers, no timed loops) and extracts a fingerprint with
per-datapoint normalizations (flops/dp, bytes/dp, peak-bytes/dp) that
are comparable across boxes and backends.  ``cli costs`` commits the
artifact (COSTS_r13.json) and ``cli costs --check`` is the multiset
ratchet over it — the one perf trend line that needs no chip, and
the regression instrument ROADMAP items 1 and 2 are
judged against.

Honesty notes:

* The numbers are COST-MODEL numbers, not measurements: XLA's
  HloCostAnalysis counts a while-loop body ONCE (a ``lax.scan`` over T
  steps reports one body's flops), and counts only the op classes it
  models (integer/bitwise ops — most of a codec — are not "flops").
  That is exactly why they make a good ratchet (deterministic, box-
  independent) and a bad throughput predictor; the drift between these
  counts and the jaxpr-level hand counts is recorded in the artifact
  (``opsdp_crosscheck``), not papered over.
* Fingerprints are pinned per (platform, jax version): an XLA upgrade
  or a backend change legitimately moves them, which is a re-baseline,
  not a regression — the check refuses cross-platform comparison.
* Pallas stages lower in interpret mode off-TPU (the kernels' own
  clean-fallback contract), so their CPU fingerprints describe the
  interpreter's HLO; tests/test_chip_compile.py compiles the Mosaic
  form for a described chip.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple

from m3_tpu.x import hlotext

__all__ = [
    "CANONICAL", "CompiledStage", "DOCUMENTED_OPS_PER_DP", "GATED_METRICS",
    "STAGES", "Stage", "clear_stage_cache", "compiled_stage",
    "compiled_stages", "count_jaxpr_ops", "fingerprint_compiled",
    "fingerprint_lowered", "hlo_op_histogram", "run_stages",
    "stage_names", "step_ops_crosscheck",
]


# ---------------------------------------------------------------------------
# Canonical shapes — the registry's pinned geometry.  Small enough that
# the full registry compiles in well under a minute (tier-1 runs the
# gate every round), large enough that XLA's layout/fusion choices are
# the hot path's, not a toy's.  CHANGING ANY OF THESE IS A RE-BASELINE.
# ---------------------------------------------------------------------------

CANONICAL = {
    "S": 256,           # codec series axis
    "T": 128,           # codec datapoints per series
    "W": 4,             # arena window ring
    "C": 4096,          # arena slot capacity
    "SCAP": 16384,      # timer sample capacity
    "N": 8192,          # arena ingest batch size
    "QUANTILES": (0.5, 0.95, 0.99),   # engine default
    "SHARD_DEVICES": 2,  # sharded-wrapper mesh width (needs >= 2 devices)
}

# The hand-counted per-datapoint element-op attributions the profile
# harness reports (jaxpr equation counts of one scan step — see
# tools/decode_profile.py).  Recorded here so the HLO-derived counts the
# costs artifact carries are CROSS-CHECKED against them every run: the
# two attributions drifting silently would invalidate both.
DOCUMENTED_OPS_PER_DP = {
    "decode_step": 670,    # PROFILE_decode_r06 (fused chains tail)
    "encode_step": 1485,   # round 8 profile (phase-1 lane emission)
}

# Per-stage metrics the ratchet gates (growth OR shrinkage past
# tolerance fails — improvements re-baseline, the lint/hops tradition).
# argument/output bytes only move when the program's interface changes
# (shapes are pinned by the config equality check), which is precisely
# the constant-bloat class: the 1MB decode control table sliding from
# an argument into the HLO shows up here first.
GATED_METRICS = (
    "flops", "transcendentals", "bytes_accessed", "hlo_op_total",
    "memory.argument_bytes", "memory.output_bytes",
    "memory.temp_bytes", "memory.peak_bytes",
)


# ---------------------------------------------------------------------------
# Extractors
# ---------------------------------------------------------------------------


def count_jaxpr_ops(jaxpr) -> int:
    """Total equation count of a jaxpr including nested sub-jaxprs —
    THE one home of the profile harness' "element ops per datapoint"
    counter (tools/decode_profile.py imports it; a drifted second copy
    would let the two attributions diverge silently)."""
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            if hasattr(v, "jaxpr"):
                n += count_jaxpr_ops(v.jaxpr)
    return n


# The instruction grammar moved to its one home (x/hlotext.py) when
# irlint grew a second reader of the same texts; this name stays as the
# seam costwatch's callers import.
_HLO_INSTR_RE = hlotext.HLO_INSTR_RE


def hlo_op_histogram(hlo_text: str) -> Dict[str, int]:
    """Opcode-class histogram of a compiled HLO module — delegates to
    :func:`m3_tpu.x.hlotext.op_histogram`, the shared parsing home."""
    return hlotext.op_histogram(hlo_text)


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def fingerprint_compiled(compiled, datapoints: int, hlo_text=None) -> dict:
    """Extract one stage's fingerprint from a compiled executable.

    ``peak_bytes`` is the derived live-set bound argument + output +
    temp − alias (donated inputs alias their outputs and must not be
    double-counted); XLA exposes no finer peak on this seam, and the
    bound is the number an admission check needs — what the program
    can touch at once.  ``hlo_text`` lets a caller that already holds
    ``compiled.as_text()`` (the stage cache) skip re-rendering it."""
    ca = _cost_dict(compiled)
    ma = compiled.memory_analysis()
    hist = hlo_op_histogram(compiled.as_text() if hlo_text is None
                            else hlo_text)
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    peak = arg + out + temp - alias
    flops = int(ca.get("flops", 0) or 0)
    by = int(ca.get("bytes accessed", 0) or 0)
    dp = max(int(datapoints), 1)
    return {
        "datapoints": int(datapoints),
        "flops": flops,
        "transcendentals": int(ca.get("transcendentals", 0) or 0),
        "bytes_accessed": by,
        "flops_per_dp": round(flops / dp, 4),
        "bytes_per_dp": round(by / dp, 4),
        "hlo_ops": hist,
        "hlo_op_total": sum(hist.values()),
        "memory": {
            "argument_bytes": arg,
            "output_bytes": out,
            "temp_bytes": temp,
            "alias_bytes": alias,
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
            "peak_bytes": peak,
        },
        "peak_bytes_per_dp": round(peak / dp, 2),
    }


def fingerprint_lowered(lowered, datapoints: int) -> dict:
    """Compile a ``jit(...).lower(...)`` result and fingerprint it —
    the seam bench.py's per-stage ``cost`` blocks use."""
    return fingerprint_compiled(lowered.compile(), datapoints)


# ---------------------------------------------------------------------------
# Stage registry
# ---------------------------------------------------------------------------


class Stage(NamedTuple):
    """One named hot-path device program at pinned canonical shapes.

    ``build()`` returns ``(lowered, datapoints, config)``: the AOT-
    lowered program (``.compile()`` not yet called — the caller owns
    the one compile), the per-datapoint normalization divisor, and the
    config dict the check gate pins (shapes + statics: two artifacts
    are only comparable when their configs are equal)."""

    name: str
    build: Callable[[], tuple]


def _sds(shape, dtype):
    import jax
    import numpy as np

    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def _codec_shapes():
    import numpy as np

    S, T = CANONICAL["S"], CANONICAL["T"]
    W = T * 24 // 64 + 4  # stream words/series at the corpus bit rate
    return {
        "S": S, "T": T, "max_points": T + 1, "stream_words": W,
        "words": _sds((S, W + 1), np.uint64),
        "nbits": _sds((S,), np.int64),
        "tbl": _sds((1 << 18,), np.uint32),
        "ts": _sds((S, T), np.int64),
        "vbits": _sds((S, T), np.uint64),
        "start": _sds((S,), np.int64),
        "valid": _sds((S, T), np.bool_),
        "out_words": T * 16 // 64 + 4,
    }


def _build_decode(chains: str, extract: str):
    from m3_tpu.encoding import m3tsz_jax as mj

    g = _codec_shapes()
    lowered = mj._decode_batch_device.lower(
        g["words"], g["nbits"], g["tbl"], max_points=g["max_points"],
        default_unit=1, chains=chains, scan_major=True, extract=extract)
    cfg = {"S": g["S"], "T": g["T"], "max_points": g["max_points"],
           "stream_words": g["stream_words"], "chains": chains,
           "extract": extract, "scan_major": True}
    return lowered, g["S"] * g["T"], cfg


def _build_decode_sharded():
    import jax

    from m3_tpu.encoding import m3tsz_jax as mj  # noqa: F401 (codec import)
    from m3_tpu.parallel import sharded_decode

    g = _codec_shapes()
    n_dev = min(CANONICAL["SHARD_DEVICES"], jax.device_count())
    lowered = sharded_decode._sharded_fn(
        n_dev, g["max_points"], 1, "fused", True, "jnp").lower(
            g["words"], g["nbits"], g["tbl"])
    cfg = {"S": g["S"], "T": g["T"], "max_points": g["max_points"],
           "stream_words": g["stream_words"], "chains": "fused",
           "extract": "jnp", "devices": n_dev}
    return lowered, g["S"] * g["T"], cfg


def _build_encode(place: str):
    from m3_tpu.encoding import m3tsz_jax as mj

    g = _codec_shapes()
    lowered = mj._encode_batch_device.lower(
        g["ts"], g["vbits"], g["start"], g["valid"], unit=1,
        out_words=g["out_words"], prefix_bits=None, place=place)
    cfg = {"S": g["S"], "T": g["T"], "out_words": g["out_words"],
           "place": place}
    return lowered, g["S"] * g["T"], cfg


def _build_encode_sharded():
    import jax

    from m3_tpu.parallel import sharded_encode

    g = _codec_shapes()
    n_dev = min(CANONICAL["SHARD_DEVICES"], jax.device_count())
    lowered = sharded_encode._sharded_fn(
        n_dev, 1, g["out_words"], "gather", False).lower(
            g["ts"], g["vbits"], g["start"], g["valid"])
    cfg = {"S": g["S"], "T": g["T"], "out_words": g["out_words"],
           "place": "gather", "devices": n_dev}
    return lowered, g["S"] * g["T"], cfg


def _arena_shapes():
    import numpy as np

    N = CANONICAL["N"]
    return {
        "idx": _sds((N,), np.int64),
        "slots": _sds((N,), np.int32),
        "windows": _sds((N,), np.int32),
        "ivals": _sds((N,), np.int64),
        "fvals": _sds((N,), np.float64),
        "times": _sds((N,), np.int64),
        "window": _sds((), np.int64),
    }


def _arena_cfg(**extra) -> dict:
    cfg = {"W": CANONICAL["W"], "C": CANONICAL["C"], "N": CANONICAL["N"]}
    cfg.update(extra)
    return cfg


def _state_shape(initfn, *args):
    """Abstract state pytree of an arena init — no allocation (the
    registry never materializes data; eval_shape keeps the int
    geometry static by closing over it)."""
    import jax

    return jax.eval_shape(lambda: initfn(*args))


def _build_rollup_ingest_packed():
    from m3_tpu.aggregator import packed

    W, C = CANONICAL["W"], CANONICAL["C"]
    a = _arena_shapes()
    cs = _state_shape(packed.counter_init, W, C)
    gs = _state_shape(packed.gauge_init, W, C)
    lowered = packed.rollup_ingest.lower(
        cs, gs, a["idx"], a["ivals"], a["fvals"], a["ivals"], a["times"],
        num_windows=W, capacity=C)
    return lowered, CANONICAL["N"], _arena_cfg(layout="packed",
                                               op="rollup_ingest")


def _build_arena_f64(kind: str, op: str):
    from m3_tpu.aggregator import arena

    W, C, SCAP = CANONICAL["W"], CANONICAL["C"], CANONICAL["SCAP"]
    a = _arena_shapes()
    if kind == "counter":
        st = _state_shape(arena.counter_init, W, C)
        if op == "ingest":
            lowered = arena.counter_ingest.lower(
                st, a["idx"], a["slots"], a["ivals"], a["times"])
        else:
            lowered = arena.counter_consume.lower(st, a["window"],
                                                  capacity=C)
    elif kind == "gauge":
        st = _state_shape(arena.gauge_init, W, C)
        if op == "ingest":
            lowered = arena.gauge_ingest.lower(
                st, a["idx"], a["slots"], a["fvals"], a["times"])
        else:
            lowered = arena.gauge_consume.lower(st, a["window"], capacity=C)
    else:  # timer
        st = _state_shape(arena.timer_init, W, C, SCAP)
        if op == "ingest":
            lowered = arena.timer_ingest.lower(
                st, a["windows"], a["slots"], a["fvals"], a["times"],
                capacity=C)
        else:
            lowered = arena.timer_consume.lower(
                st, a["window"], capacity=C,
                quantiles=CANONICAL["QUANTILES"], packed32=False)
    dp = CANONICAL["N"] if op == "ingest" else (
        SCAP if kind == "timer" else C)
    cfg = _arena_cfg(layout="f64", op=f"{kind}_{op}")
    if kind == "timer":
        cfg["SCAP"] = SCAP
        if op == "consume":
            cfg["quantiles"] = list(CANONICAL["QUANTILES"])
    return lowered, dp, cfg


def _build_arena_packed(kind: str, op: str):
    from m3_tpu.aggregator import packed

    W, C, SCAP = CANONICAL["W"], CANONICAL["C"], CANONICAL["SCAP"]
    a = _arena_shapes()
    if kind == "counter":
        st = _state_shape(packed.counter_init, W, C)
        lowered = packed.counter_consume.lower(st, a["window"], capacity=C)
    elif kind == "gauge":
        st = _state_shape(packed.gauge_init, W, C)
        lowered = packed.gauge_consume.lower(st, a["window"], capacity=C)
    else:  # timer
        st = _state_shape(packed.timer_init, W, C, SCAP)
        if op == "ingest":
            lowered = packed.timer_ingest.lower(
                st, a["windows"], a["slots"], a["fvals"], a["times"],
                capacity=C)
        else:
            lowered = packed.timer_consume.lower(
                st, a["window"], True, capacity=C,
                quantiles=CANONICAL["QUANTILES"])
    dp = CANONICAL["N"] if op == "ingest" else (
        SCAP if kind == "timer" else C)
    cfg = _arena_cfg(layout="packed", op=f"{kind}_{op}")
    if kind == "timer":
        cfg["SCAP"] = SCAP
        if op == "consume":
            cfg["quantiles"] = list(CANONICAL["QUANTILES"])
    return lowered, dp, cfg


# Every hot-path device program, by name.  Order is evidence priority
# (a budgeted walk reaches the first entries first).
STAGES: tuple = (
    # decode: both chains tails and both extract impls
    Stage("decode/fused",
          functools.partial(_build_decode, "fused", "jnp")),
    Stage("decode/gather",
          functools.partial(_build_decode, "gather", "jnp")),
    Stage("decode/gather_pallas",
          functools.partial(_build_decode, "gather", "pallas")),
    Stage("decode/sharded", _build_decode_sharded),
    # encode: all three placement tails
    Stage("encode/gather", functools.partial(_build_encode, "gather")),
    Stage("encode/scatter", functools.partial(_build_encode, "scatter")),
    Stage("encode/pallas", functools.partial(_build_encode, "pallas")),
    Stage("encode/sharded", _build_encode_sharded),
    # arena hot path: packed (the production layout) and f64 (oracle)
    Stage("arena/rollup_ingest_packed", _build_rollup_ingest_packed),
    Stage("arena/counter_ingest_f64",
          functools.partial(_build_arena_f64, "counter", "ingest")),
    Stage("arena/gauge_ingest_f64",
          functools.partial(_build_arena_f64, "gauge", "ingest")),
    Stage("arena/counter_consume_packed",
          functools.partial(_build_arena_packed, "counter", "consume")),
    Stage("arena/counter_consume_f64",
          functools.partial(_build_arena_f64, "counter", "consume")),
    Stage("arena/gauge_consume_packed",
          functools.partial(_build_arena_packed, "gauge", "consume")),
    Stage("arena/gauge_consume_f64",
          functools.partial(_build_arena_f64, "gauge", "consume")),
    # timer ingest/drain, both layouts
    Stage("timer/ingest_packed",
          functools.partial(_build_arena_packed, "timer", "ingest")),
    Stage("timer/ingest_f64",
          functools.partial(_build_arena_f64, "timer", "ingest")),
    Stage("timer/consume_packed",
          functools.partial(_build_arena_packed, "timer", "consume")),
    Stage("timer/consume_f64",
          functools.partial(_build_arena_f64, "timer", "consume")),
)


def stage_names() -> tuple:
    return tuple(s.name for s in STAGES)


# ---------------------------------------------------------------------------
# Lowering cache — ONE compile per registered program per process.
#
# Two tier-1 gates walk the full registry every round (``cli costs
# --check`` fingerprints it, ``cli irlint --check`` lints its IR), and
# round-14 tier-1 ran 856s against the 870s envelope: a second
# full-registry lowering does not fit.  The cache is keyed by stage
# name only, which is sound because CANONICAL is module-constant and
# builders are pure functions of it — same process, same program.
# ---------------------------------------------------------------------------


class CompiledStage(NamedTuple):
    """One registry stage, lowered + compiled once, with both module
    texts rendered once (irlint's rules and costwatch's histogram read
    the same strings instead of re-rendering per consumer)."""

    name: str
    lowered: Any       # jax .lower(...) result
    compiled: Any      # .compile() executable
    stablehlo: str     # lowered.as_text() — formulation-level MLIR
    hlo: str           # compiled.as_text() — post-optimization HLO
    datapoints: int
    config: dict


_STAGE_CACHE: Dict[str, CompiledStage] = {}


def clear_stage_cache() -> None:
    """Drop all cached executables (tests that reconfigure devices)."""
    _STAGE_CACHE.clear()


def compiled_stage(name: str) -> CompiledStage:
    """The cached :class:`CompiledStage` for one registry stage,
    building + compiling it on first use."""
    cs = _STAGE_CACHE.get(name)
    if cs is not None:
        return cs
    by_name = {s.name: s for s in STAGES}
    if name not in by_name:
        raise KeyError(f"unknown costwatch stage(s): {[name]}; "
                       f"known: {list(stage_names())}")
    lowered, datapoints, cfg = by_name[name].build()
    compiled = lowered.compile()
    cs = CompiledStage(name=name, lowered=lowered, compiled=compiled,
                       stablehlo=lowered.as_text(), hlo=compiled.as_text(),
                       datapoints=int(datapoints), config=dict(cfg))
    _STAGE_CACHE[name] = cs
    return cs


def compiled_stages(names=None, on_stage=None) -> Dict[str, CompiledStage]:
    """Cached :class:`CompiledStage` map in registry order (or a
    subset).  Unknown names fail in milliseconds, before any compile.
    ``on_stage(name, seconds)`` reports per-stage wall of THIS call —
    near-zero on cache hits, which is the observable proof the
    costs/irlint gates share one lowering."""
    import time

    want = set(names) if names is not None else None
    if want is not None:
        missing = want - set(stage_names())
        if missing:
            raise KeyError(f"unknown costwatch stage(s): {sorted(missing)}; "
                           f"known: {list(stage_names())}")
    out: Dict[str, CompiledStage] = {}
    for stage in STAGES:
        if want is not None and stage.name not in want:
            continue
        t0 = time.perf_counter()
        out[stage.name] = compiled_stage(stage.name)
        if on_stage is not None:
            on_stage(stage.name, time.perf_counter() - t0)
    return out


def run_stages(names=None, on_stage=None) -> Dict[str, dict]:
    """Lower + compile + fingerprint the registry (or a subset).

    Compile-only by construction: builders hand ``.lower()``
    ShapeDtypeStructs, so no data is materialized, nothing transfers,
    and nothing executes — immune to box noise, safe under the tier-1
    envelope.  Programs come from the process-wide stage cache, so a
    later ``cli irlint`` pass (or a repeated costs run) pays zero
    additional compiles.  ``on_stage(name, seconds)`` reports per-stage
    compile wall (observability of the gate's own cost, not part of
    any fingerprint)."""
    out: Dict[str, dict] = {}
    for name, cs in compiled_stages(names, on_stage=on_stage).items():
        fp = fingerprint_compiled(cs.compiled, cs.datapoints,
                                  hlo_text=cs.hlo)
        fp["config"] = dict(cs.config)
        out[name] = fp
    return out


# ---------------------------------------------------------------------------
# ops/dp cross-check: the profile harness' jaxpr hand counts vs the
# HLO-derived numbers, recorded so neither attribution drifts silently.
# ---------------------------------------------------------------------------


def _decode_step_jaxpr_ops() -> int:
    import jax
    import jax.numpy as jnp

    from m3_tpu.encoding import m3tsz_jax as mj

    S = CANONICAL["S"]
    W = CANONICAL["T"] * 24 // 64 + 4
    wpad = jnp.zeros((S, W + 1 + mj._PAD_WORDS), jnp.uint64)
    step = functools.partial(
        mj._decode_step, words=wpad, nbits=jnp.zeros(S, mj.I32),
        unit0=jnp.zeros(S, mj.I32),
        ctrl_tbl=jnp.zeros(1 << 18, jnp.uint32), emit_chains=True)
    carry0 = mj._decode_carry0(S, jnp.zeros(S, mj.I64))
    return count_jaxpr_ops(jax.make_jaxpr(step)(carry0, None).jaxpr)


def _encode_step_jaxpr_ops() -> int:
    import jax
    import jax.numpy as jnp

    from m3_tpu.encoding import m3tsz_jax as mj

    S = CANONICAL["S"]
    step = functools.partial(mj._encode_step, unit=1,
                             default_unit_is_32bit=True)
    carry0 = mj._encode_carry0(S, jnp.zeros(S, mj.I64), 1)
    xs = (jnp.zeros(S, mj.I64), jnp.zeros(S, mj.U64),
          jnp.ones(S, jnp.bool_))
    return count_jaxpr_ops(jax.make_jaxpr(step)(carry0, xs).jaxpr)


def step_ops_crosscheck(stage_fps: Dict[str, dict]) -> dict:
    """The two attributions side by side, with the drift explained.

    ``jaxpr_step_ops`` is the live hand-count (decode_profile's method:
    equations in one scan step's jaxpr); ``documented_ops_per_dp`` is
    the number the committed PROFILE artifacts report; ``hlo_flops_per
    _dp`` is XLA's own count from the compiled module.  They measure
    different things BY DESIGN — the explanation string is part of the
    artifact so the gap can't be misread as a bug."""
    out: dict = {}
    for key, live_fn, stage in (
            ("decode", _decode_step_jaxpr_ops, "decode/fused"),
            ("encode", _encode_step_jaxpr_ops, "encode/gather")):
        doc = DOCUMENTED_OPS_PER_DP[f"{key}_step"]
        live = live_fn()
        rec = {
            "documented_ops_per_dp": doc,
            "jaxpr_step_ops": live,
            "jaxpr_vs_documented": round(live / doc, 3),
        }
        fp = stage_fps.get(stage)
        if fp:
            rec["hlo_flops_per_dp"] = fp["flops_per_dp"]
            rec["hlo_bytes_per_dp"] = fp["bytes_per_dp"]
            rec["hlo_flops_vs_jaxpr_ops"] = round(
                fp["flops_per_dp"] / max(live, 1), 4)
        out[key] = rec
    out["explanation"] = (
        "jaxpr_step_ops counts EVERY equation in one scan step's jaxpr "
        "(integer/bitwise/select/gather included — the branchless "
        "formulation's real per-datapoint element work, the number the "
        "PROFILE artifacts attribute); XLA's cost analysis counts a "
        "lax.scan's while-body ONCE for the whole program and models "
        "only the op classes it prices (flops ~ floating/elementwise "
        "arithmetic; gathers and bit ops are bytes, not flops).  The "
        "ratio between them is therefore a FINGERPRINT to ratchet, not "
        "a unit conversion; jaxpr_vs_documented near 1.0 is the "
        "cross-check that the hand-counted attribution still describes "
        "the live step.")
    return out
