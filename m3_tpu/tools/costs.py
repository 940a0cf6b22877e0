"""``cli costs``: machine-independent per-stage cost fingerprints.

Builds the COSTS artifact — one fingerprint per registered hot-path
device program (x/costwatch.py: decode under both chains tails and both
extract impls, encode under all three placement tails, the packed AND
f64 arena ingest/consume programs, the timer path, the sharded
wrappers), extracted compile-only from XLA's cost/memory analysis at
pinned canonical shapes — plus two cross-checks:

* ``opsdp_crosscheck`` — the profile harness' hand-counted ops/dp
  (decode 670, encode 1485) against the live jaxpr and the HLO-derived
  flops/dp, drift recorded with its explanation;
* ``membudget_crosscheck`` — every x/membudget footprint formula
  against ``memory_analysis()`` actuals (arena formulas vs the init
  programs' output bytes; codec lane formulas vs the codec programs'
  argument+output+temp), the PR 12 "≥ actual and ≤ 2× actual" contract
  now verified against XLA instead of hand-derived lane nbytes.

``--check BASELINE`` is the regression gate: a multiset ratchet in the
lint/hops tradition.  A stage vanishing, a new stage, a config (shape)
change, or ANY gated metric moving past tolerance — in EITHER direction
— fails; improvements re-baseline (``--out`` the new artifact and
commit it with the PR that earned them).  It only compiles, so it is
immune to box noise, runs identically with or without a chip, and
fits tier-1.
"""

from __future__ import annotations

import json
from pathlib import Path

SCHEMA = 1
DEFAULT_TOLERANCE = 0.05
# Dimensionless count metrics get an absolute floor so a ±1-op jitter
# on a tiny program can't trip the relative gate.
_ABS_SLACK = {"hlo_op_total": 4}


def default_baseline_path() -> Path:
    return Path(__file__).resolve().parents[2] / "COSTS_r13.json"


def _platform() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": jax.device_count(),
        "jax": jax.__version__,
    }


def membudget_arena_cases() -> list:
    """The (name, init_fn, formula_fn) membudget-vs-XLA case table at
    the costwatch canonical shapes — ONE home, shared by the artifact's
    crosscheck below and tests/test_membudget_xla.py (a new arena
    kind/layout added to one consumer but not the other would silently
    drop the check on that side)."""
    from m3_tpu.aggregator import arena, packed
    from m3_tpu.x import costwatch, membudget

    W, C = costwatch.CANONICAL["W"], costwatch.CANONICAL["C"]
    SCAP = costwatch.CANONICAL["SCAP"]
    return [
        ("counter/f64", lambda: arena.counter_init(W, C),
         lambda: membudget.counter_arena_bytes("f64", W, C)),
        ("gauge/f64", lambda: arena.gauge_init(W, C),
         lambda: membudget.gauge_arena_bytes("f64", W, C)),
        ("timer/f64", lambda: arena.timer_init(W, C, SCAP),
         lambda: membudget.timer_arena_bytes("f64", W, C, SCAP)),
        ("counter/packed", lambda: packed.counter_init(W, C),
         lambda: membudget.counter_arena_bytes("packed", W, C)),
        ("gauge/packed", lambda: packed.gauge_init(W, C),
         lambda: membudget.gauge_arena_bytes("packed", W, C)),
        ("timer/packed", lambda: packed.timer_init(W, C, SCAP),
         lambda: membudget.timer_arena_bytes("packed", W, C, SCAP)),
    ]


def _membudget_crosscheck() -> dict:
    """Formula-vs-XLA at the registry's canonical shapes.

    Arena formulas admit LONG-LIVED state, so their actual is the init
    program's output bytes (exactly the state lanes as XLA lays them
    out).  Codec lane formulas admit one PASS's transient footprint, so
    their actual is the codec program's argument+output+temp.  The
    contract both ways: formula ≥ actual and ≤ 2× actual — tests pin
    it (tests/test_membudget_xla.py); the artifact carries the measured
    ratios so a drift is visible before the bound trips."""
    import jax

    out: dict = {"arena": {}, "codec": {}}
    for name, initfn, formula_fn in membudget_arena_cases():
        ma = jax.jit(initfn).lower().compile().memory_analysis()
        actual = int(ma.output_size_in_bytes)
        formula = formula_fn()
        out["arena"][name] = {
            "formula_bytes": int(formula),
            "xla_output_bytes": actual,
            "ratio": round(formula / max(actual, 1), 4),
        }
    out["contract"] = ("formula >= xla actual and <= 2x xla actual at "
                       "canonical shapes (pinned by "
                       "tests/test_membudget_xla.py)")
    return out


def _codec_membudget_entries(stage_fps: dict) -> dict:
    """Codec-formula entries derived from already-compiled stage
    fingerprints (no extra compiles)."""
    from m3_tpu.x import costwatch, membudget

    S, T = costwatch.CANONICAL["S"], costwatch.CANONICAL["T"]
    out: dict = {}
    for stage, formula in (
            ("decode/fused",
             membudget.decode_lane_bytes(S, T * 24 // 64 + 4 + 1, T + 1,
                                         chains="fused")),
            ("decode/gather",
             membudget.decode_lane_bytes(S, T * 24 // 64 + 4 + 1, T + 1,
                                         chains="gather")),
            ("decode/gather_pallas",
             membudget.decode_lane_bytes(S, T * 24 // 64 + 4 + 1, T + 1,
                                         chains="gather", extract="pallas")),
            ("encode/gather",
             membudget.encode_lane_bytes(S, T, T * 16 // 64 + 4,
                                         place="gather")),
            ("encode/scatter",
             membudget.encode_lane_bytes(S, T, T * 16 // 64 + 4,
                                         place="scatter")),
            ("encode/pallas",
             membudget.encode_lane_bytes(S, T, T * 16 // 64 + 4,
                                         place="pallas")),
    ):
        fp = stage_fps.get(stage)
        if fp is None:
            continue
        mem = fp["memory"]
        actual = (mem["argument_bytes"] + mem["output_bytes"]
                  + mem["temp_bytes"])
        out[stage] = {
            "formula_bytes": int(formula),
            "xla_arg_out_temp_bytes": int(actual),
            "ratio": round(formula / max(actual, 1), 4),
        }
    return out


def build_artifact(stage_names=None, log=None) -> dict:
    """Run the registry and assemble the COSTS document."""
    from m3_tpu.x import costwatch

    def on_stage(name, seconds):
        if log is not None:
            log(f"costs: {name} compiled in {seconds:.1f}s")

    stages = costwatch.run_stages(stage_names, on_stage=on_stage)
    artifact = {
        "artifact": "COSTS",
        "schema": SCHEMA,
        "generated_by": "python -m m3_tpu.tools.cli costs",
        "config": dict(_platform(), canonical={
            k: (list(v) if isinstance(v, tuple) else v)
            for k, v in costwatch.CANONICAL.items()}),
        "stages": stages,
        "opsdp_crosscheck": costwatch.step_ops_crosscheck(stages),
    }
    if stage_names is None:
        mb = _membudget_crosscheck()
        mb["codec"] = _codec_membudget_entries(stages)
        artifact["membudget_crosscheck"] = mb
    return artifact


# ---------------------------------------------------------------------------
# The ratchet
# ---------------------------------------------------------------------------


def _metric(fp: dict, path: str):
    cur = fp
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def check_artifact(artifact: dict, baseline: dict,
                   tolerance: float = DEFAULT_TOLERANCE) -> list:
    """Multiset ratchet: violations as structured dicts (empty = pass).

    Refuses cross-platform/cross-schema comparison (a TPU artifact
    checked against the CPU baseline is a head-to-head, not a
    regression); a stage vanishing, appearing, or changing its pinned
    config fails; every gated metric must stay within ±tolerance of
    the baseline — shrinkage past tolerance is a REAL improvement that
    must re-baseline (the ratchet only ever tightens)."""
    errs: list = []

    def err(kind, msg, **extra):
        errs.append(dict({"kind": kind, "message": msg}, **extra))

    if baseline.get("schema") != artifact.get("schema"):
        err("schema", f"schema mismatch: baseline "
            f"{baseline.get('schema')} vs current {artifact.get('schema')}"
            " — regenerate the baseline")
        return errs
    bplat = baseline.get("config", {}).get("platform")
    cplat = artifact.get("config", {}).get("platform")
    if bplat != cplat:
        err("platform", f"platform mismatch: baseline {bplat!r} vs current "
            f"{cplat!r} — cost fingerprints only ratchet within one "
            "backend (cross-backend numbers are a head-to-head, "
            "not a ratchet)")
        return errs
    bjax = baseline.get("config", {}).get("jax")
    cjax = artifact.get("config", {}).get("jax")
    if bjax != cjax:
        # fingerprints are pinned per (platform, jax version): an
        # XLA/jaxlib upgrade legitimately moves them, and attributing
        # that to a formulation regression would be a lie — refuse
        # typed, re-baseline as its own PR (TESTING.md protocol)
        err("jax-version", f"jax version mismatch: baseline {bjax!r} vs "
            f"current {cjax!r} — an XLA upgrade moves fingerprints "
            "legitimately; re-baseline (cli costs --out) in a dedicated "
            "PR with the artifact diff as review evidence")
        return errs
    bcanon = baseline.get("config", {}).get("canonical")
    ccanon = artifact.get("config", {}).get("canonical")
    if bcanon != ccanon:
        err("config", f"canonical geometry changed: baseline {bcanon} vs "
            f"current {ccanon} — the registry's pinned shapes moved; "
            "re-baseline deliberately")
        return errs

    from m3_tpu.x import costwatch

    base_stages = baseline.get("stages", {})
    cur_stages = artifact.get("stages", {})
    for name in base_stages:
        if name not in cur_stages:
            err("stage-vanished", f"{name}: stage present in baseline but "
                "not produced by the registry — a deleted stage must "
                "re-baseline", stage=name)
    for name in cur_stages:
        if name not in base_stages:
            err("stage-new", f"{name}: stage not in baseline — a new "
                "registered stage must re-baseline", stage=name)
    for name, cur in sorted(cur_stages.items()):
        base = base_stages.get(name)
        if base is None:
            continue
        if base.get("config") != cur.get("config"):
            err("config", f"{name}: pinned config changed "
                f"({base.get('config')} -> {cur.get('config')}) — "
                "canonical shapes moved; re-baseline deliberately",
                stage=name)
            continue
        for metric in costwatch.GATED_METRICS:
            b = _metric(base, metric)
            c = _metric(cur, metric)
            if b is None and c is None:
                continue
            b = b or 0
            c = c or 0
            if b == c:
                continue
            slack = _ABS_SLACK.get(metric, 0)
            if abs(c - b) <= slack:
                continue
            if b == 0:
                err("regression", f"{name}: {metric} appeared "
                    f"(0 -> {c})", stage=name, metric=metric,
                    baseline=b, current=c)
                continue
            ratio = c / b
            if ratio > 1.0 + tolerance:
                err("regression", f"{name}: {metric} regressed "
                    f"{b} -> {c} ({ratio:.3f}x, tolerance "
                    f"+{tolerance:.0%})", stage=name, metric=metric,
                    baseline=b, current=c, ratio=round(ratio, 4))
            elif ratio < 1.0 - tolerance:
                err("improvement", f"{name}: {metric} improved "
                    f"{b} -> {c} ({ratio:.3f}x) — past tolerance; "
                    "commit the win: cli costs --out and re-baseline",
                    stage=name, metric=metric, baseline=b, current=c,
                    ratio=round(ratio, 4))
    return errs


def check_against_baseline(artifact: dict, baseline_path: str,
                           tolerance: float = DEFAULT_TOLERANCE) -> list:
    base = json.loads(Path(baseline_path).read_text())
    return check_artifact(artifact, base, tolerance=tolerance)
