"""irlint: typed StableHLO/HLO-level rules over the device-program registry.

The stack already guards three layers — m3lint reads source AST,
tracewatch/hopwatch watch runtime, costwatch reduces compiled modules
to numeric fingerprints — and the bug classes that slipped through all
of them were IR-shaped: the silent i32→i64 cumsum promotion of PR 9
(a ±5%% costwatch bytes drift, not a named finding), the 1MB
``_VALUE_CTRL_TBL`` const-folded into every decode HLO in PR 7
(invisible to AST constant-bloat once a builder fn folds it), and
scatter ops creeping back into the "zero hot-path scatter" packed
arena of PR 8.  This pass closes the layer: it lowers every stage in
the costwatch registry (ShapeDtypeStructs only — no data, no
execution, no transfers; needs no chip by construction) through
the shared stage cache and runs typed rule families over the module
texts, reporting lint-shaped findings under the same empty-baseline
multiset ratchet as m3lint.

Rule families
-------------

* ``transfer-free``   — host custom-calls / infeed / outfeed / send /
  recv / host callbacks in any hot-path program.  The host-call
  whitelist is EMPTY; only known device directives (SPMD partitioner
  markers, Mosaic kernels) are exempt, so an unknown custom-call
  target is a finding until it is classified.
* ``scatter-budget``  — per-stage StableHLO scatter-op budget.  The
  packed arena allows only its bounded ``lax.cond`` promotion
  scatters, the encode ``scatter`` placement tail is whitelisted by
  stage name, everything else is 0.  Counted on the StableHLO
  (formulation level): CPU XLA happens to rewrite every scatter out of
  the optimized HLO, which would make a compiled-HLO census vacuously
  pass — and the formulation is what a TPU backend will lower.
* ``width-discipline`` — 64-bit tensor-type census (i64/ui64/f64
  tokens in the StableHLO) vs each stage's declared width contract;
  codec stages additionally forbid f64 outright.  A silent i32→i64 or
  f32→f64 promotion moves the census even when the op count does not.
* ``ir-const-bloat``  — constants ≥ threshold elements that XLA kept
  in the compiled module AFTER folding — the class AST constant-bloat
  cannot see once a builder fn folds them.
* ``residency-composition`` — the ROADMAP item-1 gate: the declared
  seam chain arena_ingest → window_drain → encode phase 1 → placement
  is probed as COMPOSED programs under ``jax.eval_shape`` (a host
  materialization in the glue raises ``TracerArrayConversionError`` —
  a typed, zero-execution proof of a host crossing), and every host
  crossing between adjacent stages is a finding.  The CURRENT
  crossings (e.g. the 583KB drain→encode re-upload recorded in
  PIPELINE_r13) are committed in the baseline artifact
  ``IRLINT_r17.json``; a new crossing FAILS; item 1 burns the list
  down to empty, re-baselining each win.

Honesty notes: scatter/width censuses are taken on the StableHLO the
CURRENT backend lowers — pallas stages lower in interpret mode off-TPU
(their clean-fallback contract), so their CPU budgets describe the
interpreter's formulation; the artifact pins (platform, jax version)
and the check refuses cross-platform comparison; the Mosaic side is
compiled by tests/test_chip_compile.py.

Run: ``python -m m3_tpu.tools.cli irlint [--json|--check [BASELINE]|
--explain RULE]``; see TESTING.md "IR lint & residency composition".
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple

from m3_tpu.x import hlotext
from m3_tpu.x.lint.core import Finding

__all__ = [
    "CONST_BLOAT_MIN_ELEMENTS", "CONST_WHITELIST", "Crossing",
    "DEVICE_DIRECTIVE_TARGETS", "EXPLAIN", "PIPE", "ProgramIR", "RULES",
    "SCATTER_BUDGETS", "SCHEMA", "Seam", "SEAMS", "WIDE_FORBIDDEN",
    "WIDTH_CONTRACTS", "analyze_program", "build_artifact",
    "check_against_baseline", "check_artifact", "default_baseline_path",
    "program_ir", "residency_report",
]

SCHEMA = 1

RULES = ("transfer-free", "scatter-budget", "width-discipline",
         "ir-const-bloat", "residency-composition")


def default_baseline_path() -> Path:
    return Path(__file__).resolve().parents[2] / "IRLINT_r17.json"


# ---------------------------------------------------------------------------
# Contracts.  Every registered stage MUST have a row in SCATTER_BUDGETS
# and WIDTH_CONTRACTS (tests pin table keys == costwatch.stage_names());
# a program that is NOT in the tables gets the zero contract — new
# stages start maximally strict and declare their budgets explicitly.
# All numbers are measured on (cpu, the pinned jax) at the costwatch
# canonical shapes; the artifact records both so the check can refuse
# a cross-platform comparison instead of mis-ratcheting it.
# ---------------------------------------------------------------------------

# Per-stage StableHLO scatter budgets (exact ceilings, census > budget
# is a finding).  Non-zero rows are the REVIEWED allowances:
#
# * arena/timer ingest stages: BATCH-sized scatters are the packed
#   layout's design since PR 28 — one per state lane at the sorted
#   batch's segment tails (11 for the fused rollup), one scatter-max
#   per (C,) expiry column (2), the overflow pool's free-row list (1)
#   and its bounded lax.cond promotion / delta scatters (11): 25 ops,
#   each counted twice by the census (the op and its dimension-numbers
#   attribute).  An ARENA-sized scatter or update is the finding there
#   (tests/test_arena_packed.py::TestBatchDomain holds the jaxpr to
#   it); the f64 oracle's slot-update scatters are per-lane,
#   capacity-bounded;
# * timer/consume_packed: the two sorted segment sums of the drained
#   window's moments (sum, sum_sq), each counted twice by the census:
#   they replaced a three-lane associative_scan that the TPU compiler
#   needed minutes for at a deployment's buffer (PR 31);
# * encode/*: the stream-word placement tail — ``place="scatter"`` is
#   whitelisted by stage name per the costwatch registry, and every
#   placement variant carries the 2-scatter bounded carry promotion;
# * decode/gather_pallas: pallas interpret-mode internals on CPU (the
#   kernel itself has no scatter).
SCATTER_BUDGETS: Dict[str, int] = {
    "decode/fused": 0,
    "decode/gather": 0,
    "decode/gather_pallas": 4,
    "decode/sharded": 0,
    "encode/gather": 2,
    "encode/scatter": 6,
    "encode/pallas": 6,
    "encode/sharded": 2,
    "arena/rollup_ingest_packed": 50,
    "arena/counter_ingest_f64": 12,
    "arena/gauge_ingest_f64": 16,
    "arena/counter_consume_packed": 0,
    "arena/counter_consume_f64": 0,
    "arena/gauge_consume_packed": 0,
    "arena/gauge_consume_f64": 0,
    "timer/ingest_packed": 4,
    "timer/ingest_f64": 12,
    "timer/consume_packed": 4,
    "timer/consume_f64": 0,
}

# Per-stage 64-bit tensor-type token ceilings ({} entries implicitly 0
# for every wide type).  The codec's i64/ui64 budget is its DESIGN
# (i64 timestamps, u64 stream words); the contract catches the census
# GROWING — the shape a silent promotion takes.
WIDTH_CONTRACTS: Dict[str, Dict[str, int]] = {
    "decode/fused": {"i64": 229, "ui64": 661},
    "decode/gather": {"i64": 286, "ui64": 693},
    "decode/gather_pallas": {"i64": 301, "ui64": 710},
    "decode/sharded": {"i64": 248, "ui64": 674},
    "encode/gather": {"i64": 755, "ui64": 1701},
    "encode/scatter": {"i64": 734, "ui64": 1616},
    "encode/pallas": {"i64": 739, "ui64": 1620},
    "encode/sharded": {"i64": 773, "ui64": 1720},
    # (re-censused in PR 28 under jax 0.9.0, unlike its neighbours:
    # the batch-domain merge; i64 includes the ordered-key lanes)
    "arena/rollup_ingest_packed": {"i64": 4909, "ui64": 62, "f64": 935},
    "arena/counter_ingest_f64": {"i64": 118},
    "arena/gauge_ingest_f64": {"i64": 173, "f64": 77},
    "arena/counter_consume_packed": {"i64": 162, "ui64": 11, "f64": 89},
    "arena/counter_consume_f64": {"i64": 84, "f64": 89},
    "arena/gauge_consume_packed": {"i64": 69, "f64": 105},
    "arena/gauge_consume_f64": {"i64": 65, "f64": 107},
    "timer/ingest_packed": {"i64": 125, "ui64": 40, "f64": 2},
    "timer/ingest_f64": {"i64": 148, "f64": 35},
    "timer/consume_packed": {"i64": 187, "ui64": 41, "f64": 1059},
    "timer/consume_f64": {"i64": 207, "f64": 170},
}

# Wide types a stage may not use AT ALL, regardless of ceiling: the
# codec's bit-exactness contract is integer/bit ops end to end — one
# f64 token in a decode/encode module is a correctness smell (an
# accidental float path through timestamps or value bits), not a
# budget question.
WIDE_FORBIDDEN: Dict[str, tuple] = {
    name: ("f64",) for name in WIDTH_CONTRACTS
    if name.startswith(("decode/", "encode/"))
}

WIDE_TYPES = ("i64", "ui64", "f64")

# Custom-call targets that are DEVICE directives, not host calls: the
# SPMD partitioner's sharding markers and the Mosaic/TPU kernel call.
# Everything else — including every callback flavor this jax emits
# (xla_python_cpu_callback etc.) — is a transfer-free finding.  The
# HOST whitelist is deliberately empty.
DEVICE_DIRECTIVE_TARGETS = frozenset({
    "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
    "tpu_custom_call", "annotate_device_placement",
})

_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv")

CONST_BLOAT_MIN_ELEMENTS = 4096

# (stage, "dtype[shape]") -> reviewed rationale.  The irlint analogue
# of an m3lint inline suppression: the literal is load-bearing, the
# reason is recorded here AND in the artifact's suppressions section.
CONST_WHITELIST: Dict[tuple, str] = {
    ("arena/gauge_ingest_f64", "s32[8192]"):
        "descending-iota tie-breaker operand of the last-wins stable "
        "sort over the N=8192 ingest batch (gauge semantics: later "
        "sample wins the slot) — 32KB, batch-shaped not capacity-"
        "shaped, folded at trace time by design; reformulating it as a "
        "computed iota would move the frozen COSTS_r13 fingerprints "
        "for zero functional gain (reviewed round 17)",
}


# ---------------------------------------------------------------------------
# Rule engines.  Each takes a ProgramIR (any object with .name,
# .stablehlo, .hlo — costwatch.CompiledStage qualifies) and returns
# lint-core Findings keyed (rule, path=stage-name, message): line
# numbers are meaningless in generated IR, so key stability lives in
# the message strings, which are built ONLY from census numbers and
# contract values (deterministic per platform+jax pin).
# ---------------------------------------------------------------------------


class ProgramIR(NamedTuple):
    """One lowered program's texts, decoupled from the registry so the
    corpus tests can lint ad-hoc jitted programs."""

    name: str
    stablehlo: str
    hlo: str


def program_ir(name: str, lowered) -> ProgramIR:
    """Build a :class:`ProgramIR` from a ``jit(f).lower(...)`` result
    (compiles it — the corpus-test seam; registry programs come from
    the costwatch stage cache instead and compile once per process)."""
    return ProgramIR(name=name, stablehlo=lowered.as_text(),
                     hlo=lowered.compile().as_text())


def _find(rule: str, path: str, message: str) -> Finding:
    return Finding(rule, path, 0, message)


def rule_transfer_free(p) -> List[Finding]:
    out: List[Finding] = []
    targets: Dict[str, int] = {}
    for src in (hlotext.stablehlo_custom_call_targets(p.stablehlo),
                hlotext.custom_call_targets(p.hlo)):
        for t, n in src.items():
            targets[t] = max(targets.get(t, 0), n)
    for t in sorted(targets):
        if t in DEVICE_DIRECTIVE_TARGETS:
            continue
        out.append(_find(
            "transfer-free", p.name,
            f"host-side custom call target '{t}' in a hot-path program "
            "(host-call whitelist is empty; a device directive must be "
            "classified in DEVICE_DIRECTIVE_TARGETS)"))
    hist = hlotext.op_histogram(p.hlo, include_tuple_shaped=True)
    for op in _TRANSFER_OPS:
        n = hist.get(op, 0) + hlotext.stablehlo_op_count(p.stablehlo, op)
        if n:
            out.append(_find(
                "transfer-free", p.name,
                f"host transfer op '{op}' x{n} in a hot-path program"))
    return out


def rule_scatter_budget(p, budget=None) -> List[Finding]:
    if budget is None:
        budget = SCATTER_BUDGETS.get(p.name, 0)
    n = hlotext.stablehlo_op_count(p.stablehlo, "scatter")
    if n <= budget:
        return []
    return [_find(
        "scatter-budget", p.name,
        f"stablehlo.scatter census {n} exceeds the stage budget "
        f"{budget} (only reviewed bounded-promotion scatters are "
        "budgeted; everything else is 0)")]


def rule_width_discipline(p, contract=None, forbidden=None) -> List[Finding]:
    if contract is None:
        contract = WIDTH_CONTRACTS.get(p.name, {})
    if forbidden is None:
        forbidden = WIDE_FORBIDDEN.get(p.name, ())
    census = hlotext.stablehlo_type_census(p.stablehlo)
    out: List[Finding] = []
    for t in WIDE_TYPES:
        n = census.get(t, 0)
        if t in forbidden and n:
            out.append(_find(
                "width-discipline", p.name,
                f"forbidden wide type {t} present (census {n}) — this "
                "stage's contract is no-{t} (codec bit-exactness is "
                "integer/bit ops end to end)".replace("{t}", t)))
            continue
        ceil = int(contract.get(t, 0))
        if n > ceil:
            out.append(_find(
                "width-discipline", p.name,
                f"64-bit census {t} = {n} exceeds the declared width "
                f"contract {ceil} — a silent promotion "
                "(i32-to-i64 / f32-to-f64) widens the census before it "
                "moves any costwatch byte metric past tolerance"))
    return out


def rule_ir_const_bloat(p, min_elements=CONST_BLOAT_MIN_ELEMENTS,
                        whitelist=None):
    """Returns (findings, suppressions) — whitelisted literals are
    reported as applied suppressions, never silently dropped."""
    if whitelist is None:
        whitelist = CONST_WHITELIST
    out: List[Finding] = []
    sups: List[dict] = []
    for c in hlotext.folded_constants(p.hlo, min_elements):
        what = f"{c['dtype']}[{c['shape']}]"
        rationale = whitelist.get((p.name, what))
        if rationale is not None:
            sups.append({"rule": "ir-const-bloat", "stage": p.name,
                         "what": what, "elements": c["elements"],
                         "rationale": rationale})
            continue
        out.append(_find(
            "ir-const-bloat", p.name,
            f"folded constant {what} ({c['elements']} elements >= "
            f"{min_elements}) embedded in the compiled module — big "
            "literals belong in arguments (the PR 7 ctrl-table class), "
            "or in CONST_WHITELIST with a reviewed rationale"))
    return out, sups


def analyze_program(p, **overrides):
    """All four IR rules over one program: (findings, suppressions).
    ``overrides`` (budget / contract / forbidden / min_elements /
    whitelist) are the corpus-test seam."""
    findings = list(rule_transfer_free(p))
    findings += rule_scatter_budget(p, budget=overrides.get("budget"))
    findings += rule_width_discipline(
        p, contract=overrides.get("contract"),
        forbidden=overrides.get("forbidden"))
    cb, sups = rule_ir_const_bloat(
        p, min_elements=overrides.get(
            "min_elements", CONST_BLOAT_MIN_ELEMENTS),
        whitelist=overrides.get("whitelist"))
    findings += cb
    return findings, sups


# ---------------------------------------------------------------------------
# Residency composition — the item-1 gate.
#
# The declared chain is probed, not asserted: each seam's probe
# composes producer → glue → consumer under ``jax.eval_shape`` (shapes
# only — zero data, zero execution).  If the glue materializes a
# tracer on the host (the ``np.asarray`` in engine._emit / the hops
# tmat assembly), jax raises TracerArrayConversionError — a TYPED
# static proof of a host crossing.  A non-composed seam contributes
# its transfer ledger as findings: avals from eval_shape on the
# producer's outputs, multiplied by the PIPE window count, byte-exact
# against PIPELINE_r13's hop ledger (tests pin the equality).
# ---------------------------------------------------------------------------

# The `cli hops` pipeline geometry the crossings are declared at (NOT
# the costwatch canonical shapes: crossings are cross-checked against
# the committed PIPELINE artifact, which runs this geometry).
PIPE = {
    "S": 1024,              # series
    "T": 320,               # datapoints per series
    "resolution_s": 10,     # rollup window seconds
    "windows_drained": 33,  # closed windows the pass drains
    "W": 4,                 # arena window ring
    "C": 1024,              # arena slot capacity (1 << ceil(log2 S))
    "quantiles": [0.5, 0.95, 0.99],
}


class Crossing(NamedTuple):
    """One host crossing at a seam: a named array that leaves (d2h) or
    re-enters (h2d) the device between two chain stages."""

    direction: str      # "d2h" | "h2d"
    name: str           # e.g. "counter.lanes"
    dtype: str          # numpy dtype name
    shape: tuple
    bytes_each: int
    transfers: int      # per full pipeline pass
    via: str            # the glue site that forces the crossing

    @property
    def total_bytes(self) -> int:
        return self.bytes_each * self.transfers

    @property
    def message(self) -> str:
        dims = "x".join(str(d) for d in self.shape) or "scalar"
        return (f"{self.direction} {self.name} {self.dtype}[{dims}] "
                f"{self.bytes_each}B x{self.transfers} = "
                f"{self.total_bytes}B via {self.via}")


class Seam(NamedTuple):
    """One adjacency in the declared chain.  ``probe()`` returns
    ``(composed, evidence)``; ``crossings()`` is the transfer ledger
    charged when the probe says NOT composed (a composed seam charges
    nothing — that is how item 1 burns the list down)."""

    name: str
    producer: str
    consumer: str
    probe: Callable[[], tuple]
    crossings: Callable[[], List[Crossing]]


def _sds(shape, dtype):
    import jax
    import numpy as np

    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def _aval_crossing(direction, name, aval, transfers, via) -> Crossing:
    import numpy as np

    dt = np.dtype(aval.dtype)
    size = int(dt.itemsize)
    for d in aval.shape:
        size *= int(d)
    return Crossing(direction=direction, name=name, dtype=dt.name,
                    shape=tuple(int(d) for d in aval.shape),
                    bytes_each=size, transfers=int(transfers), via=via)


def _probe_ingest_to_drain():
    """arena_ingest → window_drain: ingest's output STATE is consume's
    input state — composing them under eval_shape succeeds iff the
    ring stays device-resident across the seam (it does; the arena
    classes thread jax arrays, engine only materializes on emit)."""
    import jax

    from m3_tpu.aggregator import packed

    W, C, B = PIPE["W"], PIPE["C"], PIPE["S"]
    cs = jax.eval_shape(lambda: packed.counter_init(W, C))
    gs = jax.eval_shape(lambda: packed.gauge_init(W, C))

    def composed(cs, gs, idx, iv, fv, tm, w):
        cs2, gs2 = packed.rollup_ingest(cs, gs, idx, iv, fv, iv, tm,
                                        num_windows=W, capacity=C)
        return (packed.counter_consume(cs2, w, capacity=C),
                packed.gauge_consume(gs2, w, capacity=C))

    try:
        jax.eval_shape(composed, cs, gs, _sds((B,), "int64"),
                       _sds((B,), "int64"), _sds((B,), "float64"),
                       _sds((B,), "int64"), _sds((), "int64"))
    except jax.errors.TracerArrayConversionError as e:
        return False, f"TracerArrayConversionError: {e}"
    return True, ("rollup_ingest -> consume composes under eval_shape: "
                  "the arena state pytree stays device-resident across "
                  "the seam")


def _probe_drain_to_encode():
    """window_drain → encode phase 1: the glue mirrors what the live
    pipeline does between them — engine._emit materializes drained
    lanes/counts with np.asarray, hops assembles host tmat/vmat
    matrices, encode_batch re-uploads.  Under eval_shape that
    np.asarray raises on the tracer: the typed proof this seam is NOT
    composed today (the exact gap ROADMAP item 1 closes)."""
    import jax
    import numpy as np

    from m3_tpu.aggregator import packed

    W, C = PIPE["W"], PIPE["C"]
    st = jax.eval_shape(lambda: packed.counter_init(W, C))

    def glued(st, w):
        lanes, counts = packed.counter_consume(st, w, capacity=C)
        # the live glue: engine._emit's host materialization, then the
        # hops-pass host matrix assembly feeding encode_batch
        lanes = np.asarray(lanes)
        counts = np.asarray(counts)
        return lanes.sum() + counts.sum()

    try:
        jax.eval_shape(glued, st, _sds((), "int64"))
    except jax.errors.TracerArrayConversionError:
        return False, ("TracerArrayConversionError composing consume "
                       "-> emit glue -> encode: engine._emit "
                       "np.asarray(lanes/counts) materializes the "
                       "drain on the host, and encode_batch re-uploads "
                       "host tmat/vmat (m3_tpu/tools/hops.py _run_pass)")
    return True, ("drain -> encode composes under eval_shape: the emit "
                  "glue no longer materializes on the host — "
                  "re-baseline the burned-down crossings")


def _probe_encode_to_placement():
    """encode phase 1 → placement: both phases live in ONE jitted
    program (``_encode_batch_device`` with its ``place=`` tail), so the
    seam is composed by construction; the probe lowers it at PIPE
    shapes to keep that an observation, not an assumption."""
    import jax

    from m3_tpu.encoding import m3tsz_jax as mj

    S, nw = PIPE["S"], PIPE["windows_drained"]
    out_words = max(16, nw * 40 // 64 + 8)

    def composed(ts, vb, start, valid):
        return mj._encode_batch_device(ts, vb, start, valid, unit=1,
                                       out_words=out_words,
                                       prefix_bits=None, place="gather")

    try:
        jax.eval_shape(composed, _sds((S, nw), "int64"),
                       _sds((S, nw), "uint64"), _sds((S,), "int64"),
                       _sds((S, nw), "bool"))
    except jax.errors.TracerArrayConversionError as e:
        return False, f"TracerArrayConversionError: {e}"
    return True, ("lane emission and word placement are one jitted "
                  "program (_encode_batch_device place tail)")


def _drain_crossings() -> List[Crossing]:
    """The drain→encode transfer ledger, derived (not hand-typed): d2h
    avals come from eval_shape on the consume programs at PIPE
    geometry × the drained-window count; h2d avals are the host
    matrices the hops pass assembles for encode_batch.  Tests pin the
    totals byte-exact against PIPELINE_r13's hop ledger."""
    import jax

    from m3_tpu.aggregator import packed

    W, C, nw = PIPE["W"], PIPE["C"], PIPE["windows_drained"]
    S = PIPE["S"]
    via_d2h = "engine._emit np.asarray on drained lanes/counts"
    via_h2d = "hops _run_pass encode_batch(host tmat/vmat) re-upload"
    # engine drains COUNTER, GAUGE, TIMER per closed window
    emitters = (
        ("counter", lambda: packed.counter_init(W, C),
         lambda st, w: packed.counter_consume(st, w, capacity=C)),
        ("gauge", lambda: packed.gauge_init(W, C),
         lambda st, w: packed.gauge_consume(st, w, capacity=C)),
        ("timer", lambda: packed.timer_init(W, C, 1 << 24),
         lambda st, w: packed.timer_consume(
             st, w, True, capacity=C, quantiles=tuple(PIPE["quantiles"]))),
    )
    out: List[Crossing] = []
    for kind, init, consume in emitters:
        st = jax.eval_shape(init)
        lanes, counts = jax.eval_shape(consume, st, _sds((), "int64"))
        out.append(_aval_crossing("d2h", f"{kind}.lanes", lanes, nw,
                                  via_d2h))
        out.append(_aval_crossing("d2h", f"{kind}.counts", counts, nw,
                                  via_d2h))
    for name, shape, dtype in (
            ("encode.ts", (S, nw), "int64"),
            ("encode.vbits", (S, nw), "uint64"),
            ("encode.valid", (S, nw), "bool"),
            ("encode.start", (S,), "int64")):
        out.append(_aval_crossing("h2d", name, _sds(shape, dtype), 1,
                                  via_h2d))
    return out


def _no_crossings() -> List[Crossing]:
    return []


SEAMS: tuple = (
    Seam("arena_ingest->window_drain", "arena_ingest", "window_drain",
         _probe_ingest_to_drain, _no_crossings),
    Seam("window_drain->encode_phase1", "window_drain", "encode_phase1",
         _probe_drain_to_encode, _drain_crossings),
    Seam("encode_phase1->placement", "encode_phase1", "placement",
         _probe_encode_to_placement, _no_crossings),
)

CHAIN = ("arena_ingest", "window_drain", "encode_phase1", "placement")


def residency_report():
    """(findings, seam_records): probe every declared seam; a
    non-composed seam charges its crossing ledger as findings."""
    findings: List[Finding] = []
    records: List[dict] = []
    for seam in SEAMS:
        composed, evidence = seam.probe()
        crossings = [] if composed else seam.crossings()
        for c in crossings:
            findings.append(_find("residency-composition",
                                  f"seam:{seam.name}", c.message))
        records.append({
            "seam": seam.name,
            "producer": seam.producer,
            "consumer": seam.consumer,
            "composed": bool(composed),
            "evidence": evidence,
            "crossings": [c._asdict() for c in crossings],
            "transfers": sum(c.transfers for c in crossings),
            "bytes": sum(c.total_bytes for c in crossings),
        })
    return findings, records


# ---------------------------------------------------------------------------
# Artifact + ratchet (the costs refusal discipline over the m3lint
# multiset diff)
# ---------------------------------------------------------------------------


def _platform() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "devices": jax.device_count(), "jax": jax.__version__}


def build_artifact(stage_names=None, log=None) -> dict:
    """Lint the registry's IR (or a subset) + probe the residency
    chain, and assemble the IRLINT document.  Programs come from the
    costwatch stage cache: after a ``cli costs`` run in the same
    process this performs ZERO additional compiles."""
    from m3_tpu.x import costwatch

    def on_stage(name, seconds):
        if log is not None:
            log(f"irlint: {name} lowered in {seconds:.1f}s")

    findings: List[Finding] = []
    suppressions: List[dict] = []
    stages = costwatch.compiled_stages(stage_names, on_stage=on_stage)
    for name, cs in stages.items():
        f, s = analyze_program(cs)
        findings += f
        suppressions += s
    res_findings, seam_records = residency_report()
    findings += res_findings
    counts = {rule: 0 for rule in RULES}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return {
        "artifact": "IRLINT",
        "schema": SCHEMA,
        "generated_by": "python -m m3_tpu.tools.cli irlint",
        "config": dict(_platform(), canonical={
            k: (list(v) if isinstance(v, tuple) else v)
            for k, v in costwatch.CANONICAL.items()}, pipe=dict(PIPE)),
        "rules": list(RULES),
        "stages": sorted(stages),
        "counts": counts,
        "findings": [
            {"rule": f.rule, "path": f.path, "message": f.message}
            for f in sorted(findings)],
        "suppressions": suppressions,
        "residency": {"chain": list(CHAIN), "seams": seam_records},
    }


def _finding_objs(artifact: dict) -> List[Finding]:
    return [Finding(f["rule"], f["path"], 0, f["message"])
            for f in artifact.get("findings", [])]


def check_artifact(artifact: dict, baseline: dict) -> list:
    """The ratchet: typed refusals first (comparing across a schema /
    platform / jax / geometry change would mis-attribute legitimate IR
    movement to a rule violation), then the m3lint multiset diff over
    finding keys — a new finding fails, a stale baseline entry fails
    the other way (an improvement must re-baseline so the ratchet only
    ever tightens; item 1 burns the residency section down this way)."""
    from m3_tpu.x.lint.core import diff_baseline

    errs: list = []

    def err(kind, msg, **extra):
        errs.append(dict({"kind": kind, "message": msg}, **extra))

    if baseline.get("schema") != artifact.get("schema"):
        err("schema", f"schema mismatch: baseline "
            f"{baseline.get('schema')} vs current "
            f"{artifact.get('schema')} — regenerate the baseline")
        return errs
    for key, kind, why in (
            ("platform", "platform",
             "IR censuses only ratchet within one backend (the Mosaic "
             "lowering of the same registry is a head-to-head, not a "
             "ratchet)"),
            ("jax", "jax-version",
             "an XLA/jaxlib upgrade legitimately moves lowered IR; "
             "re-baseline (cli irlint --out) in a dedicated PR")):
        b = baseline.get("config", {}).get(key)
        c = artifact.get("config", {}).get(key)
        if b != c:
            err(kind, f"{key} mismatch: baseline {b!r} vs current {c!r}"
                f" — {why}")
            return errs
    for key in ("canonical", "pipe"):
        b = baseline.get("config", {}).get(key)
        c = artifact.get("config", {}).get(key)
        if b != c:
            err("config", f"{key} geometry changed: baseline {b} vs "
                f"current {c} — pinned shapes moved; re-baseline "
                "deliberately")
            return errs

    new, fixed = diff_baseline(_finding_objs(artifact),
                               _finding_objs(baseline))
    for f in new:
        err("new-finding", f"[{f.rule}] {f.path}: {f.message}",
            rule=f.rule, path=f.path)
    for f in fixed:
        err("stale-baseline", f"[{f.rule}] {f.path}: baseline entry no "
            f"longer fires ({f.message}) — commit the improvement: cli "
            "irlint --out and re-baseline", rule=f.rule, path=f.path)
    return errs


def check_against_baseline(artifact: dict, baseline_path) -> list:
    base = json.loads(Path(baseline_path).read_text())
    return check_artifact(artifact, base)


# ---------------------------------------------------------------------------
# --explain
# ---------------------------------------------------------------------------

EXPLAIN = {
    "transfer-free": {
        "why": (
            "The hot path's contract is device-resident end to end: a "
            "host callback, infeed/outfeed, or send/recv inside a "
            "registered program is a synchronous host round-trip per "
            "dispatch — the exact class hopwatch meters at runtime, "
            "caught here at lower time with the whitelist EMPTY.  Only "
            "classified device directives (SPMD partitioner markers, "
            "Mosaic kernel calls) are exempt."),
        "bad": ("jax.pure_callback(np_fn, aval, x) inside a registered "
                "stage -> custom-call target 'xla_python_cpu_callback' "
                "in both module texts"),
        "good": ("keep host work outside the jitted program (the "
                 "engine drain/emit seam), or land it as a device "
                 "kernel and classify the target"),
    },
    "scatter-budget": {
        "why": (
            "PR 8 rebuilt the arena around 'zero hot-path scatter'; "
            "the survivors are the bounded lax.cond promotion "
            "scatters, and encode's scatter placement tail is "
            "whitelisted by stage name.  Budgets are exact ceilings on "
            "the StableHLO census — compiled CPU HLO is vacuous here "
            "(XLA rewrites every scatter away on cpu), and the "
            "formulation is what a TPU backend lowers."),
        "bad": ("state.at[idx].add(v) creeping into a consume stage: "
                "stablehlo.scatter census 1 > budget 0"),
        "good": ("dense one-hot/segment formulations (the PR 8 "
                 "rewrite), or a reviewed budget row in "
                 "irlint.SCATTER_BUDGETS with the bound's rationale"),
    },
    "width-discipline": {
        "why": (
            "PR 9's i32->i64 cumsum promotion cost a silent 2x on a "
            "lane buffer and surfaced only as a costwatch bytes drift "
            "within tolerance.  Each stage declares its 64-bit census "
            "ceiling (i64/ui64/f64 tensor-type tokens in the "
            "StableHLO); codec stages forbid f64 outright — timestamps "
            "and value bits are integer/bit ops end to end, so ANY f64 "
            "token there is an accidental float path."),
        "bad": ("jnp.cumsum(i32_lanes) without dtype= -> i64 census "
                "jumps past the stage ceiling"),
        "good": ("jnp.cumsum(x, dtype=jnp.int32), explicit dtypes at "
                 "every accumulation seam (the m3lint explicit-dtype "
                 "rule's IR-level twin)"),
    },
    "ir-const-bloat": {
        "why": (
            "PR 7 found the 1MB decode control table const-folded into "
            "every decode module.  AST-level constant-bloat cannot see "
            "a literal once a builder fn folds it; this rule censuses "
            "the COMPILED module's constants >= 4096 elements, so the "
            "class is caught wherever it is produced.  Whitelisting is "
            "by (stage, dtype[shape]) with a reviewed rationale, "
            "recorded in the artifact's suppressions section."),
        "bad": ("tbl = jnp.asarray(np.arange(65536)) inside a jitted "
                "builder -> s32[65536] constant in the compiled HLO"),
        "good": ("pass big tables as arguments (device-placed once, "
                 "like _VALUE_CTRL_TBL after PR 7), or whitelist with "
                 "rationale in irlint.CONST_WHITELIST"),
    },
    "residency-composition": {
        "why": (
            "ROADMAP item 1 rebuilds wire->rollup->encode->flush "
            "device-resident.  This rule declares that chain as seams "
            "and PROBES each one under jax.eval_shape: composing "
            "producer -> live glue -> consumer either traces through "
            "(composed: state never leaves the device) or raises "
            "TracerArrayConversionError at the host materialization — "
            "a typed, zero-execution proof of a crossing.  Current "
            "crossings (the drain's 8.1MB d2h and the 583KB encode "
            "re-upload, byte-exact vs PIPELINE_r13) are committed in "
            "IRLINT_r17.json; new crossings FAIL; item 1 burns the "
            "list to empty, re-baselining each win."),
        "bad": ("lanes = np.asarray(consume(state, w)) between two "
                "chain stages -> every drained array becomes a d2h "
                "crossing finding"),
        "good": ("feed consume's output avals straight into the next "
                 "stage's jitted program (one composed module, the "
                 "item-1 shape) and re-baseline the burned-down list"),
    },
}
