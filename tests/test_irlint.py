"""irlint: typed StableHLO/HLO-level rules over the device-program
registry + the residency-composition gate (``cli irlint --check
IRLINT_r17.json``).

Tier-1 runs the REAL gate here, mirroring test_costwatch.py: the
module-scoped fixture builds the full artifact once through the SHARED
costwatch stage cache (after test_costwatch's registry run in the same
pytest process, that is zero additional compiles — the satellite's
one-lowering contract) and asserts it checks green against the
committed baseline with rules 1–4 EMPTY.  The seeded-violation corpus
then pins that each rule family actually fires: a host callback, a
stray scatter, an f64 leak into an integer contract, a ≥4096-element
folded constant, and an injected seam crossing each flip ``--check``
to FAIL — all on lowered text alone, zero device execution."""

from __future__ import annotations

import io
import json
from contextlib import redirect_stdout
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from m3_tpu.x import costwatch, hlotext, irlint

REPO = Path(__file__).resolve().parent.parent
BASELINE = REPO / "IRLINT_r17.json"
PIPELINE = REPO / "PIPELINE_r13.json"


@pytest.fixture(scope="module")
def artifact():
    """One full irlint run shared by every test in this module.  The
    compiles come from costwatch's stage cache — shared with
    test_costwatch's registry run in the same process."""
    return irlint.build_artifact()


# ---------------------------------------------------------------------------
# Shared lowering cache (the one-compile-per-program satellite)
# ---------------------------------------------------------------------------


class TestSharedCache:
    def test_compiled_stage_is_cached_identity(self):
        a = costwatch.compiled_stage("arena/counter_consume_f64")
        b = costwatch.compiled_stage("arena/counter_consume_f64")
        assert a is b
        assert a.stablehlo and a.hlo  # both texts captured once

    def test_run_stages_reuses_the_cache(self):
        """The costs gate and the irlint gate read the SAME lowering:
        a cached stage reports ~zero wall through on_stage."""
        walls = {}
        costwatch.run_stages(["arena/counter_consume_f64"],
                             on_stage=lambda n, s: walls.update({n: s}))
        assert walls["arena/counter_consume_f64"] < 0.5

    def test_unknown_stage_message_preserved(self):
        with pytest.raises(KeyError, match="unknown costwatch stage"):
            costwatch.compiled_stages(["no/such_stage"])


class TestHlotext:
    def test_tuple_shaped_ops_counted_only_when_asked(self):
        """The frozen COSTS fingerprints pin the tuple-skipping census;
        irlint's transfer hunt needs the tuple-shaped ops (infeed and
        recv results ARE tuples) — the one parser serves both."""
        txt = ("ENTRY %main () -> (s32[8], token[]) {\n"
               "  %tok = token[] after-all()\n"
               "  ROOT %i = (s32[8], token[]) infeed(token[] %tok)\n"
               "}\n")
        legacy = hlotext.op_histogram(txt)
        assert legacy == {"after-all": 1}
        full = hlotext.op_histogram(txt, include_tuple_shaped=True)
        assert full == {"after-all": 1, "infeed": 1}

    def test_folded_constants_census(self):
        txt = ("ENTRY %m (x: s32[4096]) -> s32[4096] {\n"
               "  %x = s32[4096]{0} parameter(0)\n"
               "  %c = s32[4096]{0} constant({...})\n"
               "  %small = s32[16]{0} constant({...})\n"
               "  ROOT %r = s32[4096]{0} add(%x, %c)\n"
               "}\n")
        out = hlotext.folded_constants(txt, 4096)
        assert out == [{"dtype": "s32", "shape": "4096",
                        "elements": 4096}]


# ---------------------------------------------------------------------------
# Contract tables cover the registry exactly
# ---------------------------------------------------------------------------


class TestContractTables:
    def test_scatter_budgets_cover_registry_exactly(self):
        assert set(irlint.SCATTER_BUDGETS) == set(costwatch.stage_names())

    def test_width_contracts_cover_registry_exactly(self):
        assert set(irlint.WIDTH_CONTRACTS) == set(costwatch.stage_names())

    def test_codec_stages_forbid_f64_outright(self):
        codec = {n for n in costwatch.stage_names()
                 if n.startswith(("decode/", "encode/"))}
        assert set(irlint.WIDE_FORBIDDEN) == codec
        for name in codec:
            assert irlint.WIDE_FORBIDDEN[name] == ("f64",)

    def test_every_rule_has_an_explain_entry(self):
        assert set(irlint.EXPLAIN) == set(irlint.RULES)


# ---------------------------------------------------------------------------
# The committed baseline — the tier-1 gate itself
# ---------------------------------------------------------------------------


class TestCommittedBaseline:
    def test_committed_artifact_is_wellformed(self):
        art = json.loads(BASELINE.read_text())
        assert art["artifact"] == "IRLINT"
        assert art["schema"] == irlint.SCHEMA
        assert art["config"]["platform"] == "cpu"
        assert art["rules"] == list(irlint.RULES)
        assert set(art["stages"]) == set(costwatch.stage_names())

    def test_committed_rules_1_to_4_are_empty(self):
        """The acceptance pin: the registry's IR is CLEAN under the
        four program rules — only residency crossings are baselined
        (the item-1 burn-down list)."""
        art = json.loads(BASELINE.read_text())
        for rule in ("transfer-free", "scatter-budget",
                     "width-discipline", "ir-const-bloat"):
            assert art["counts"][rule] == 0, rule
        assert art["counts"]["residency-composition"] > 0
        assert all(f["rule"] == "residency-composition"
                   for f in art["findings"])

    def test_check_against_committed_baseline_green(self, artifact):
        errs = irlint.check_artifact(artifact,
                                     json.loads(BASELINE.read_text()))
        assert errs == [], "\n".join(e["message"] for e in errs)

    def test_live_artifact_rules_1_to_4_empty(self, artifact):
        for rule in ("transfer-free", "scatter-budget",
                     "width-discipline", "ir-const-bloat"):
            assert artifact["counts"][rule] == 0, rule

    def test_const_whitelist_is_applied_and_recorded(self, artifact):
        """The one reviewed folded constant (the gauge sort
        tie-breaker) is a recorded suppression, never a silent drop."""
        sups = artifact["suppressions"]
        assert len(sups) == len(irlint.CONST_WHITELIST) == 1
        s = sups[0]
        assert s["stage"] == "arena/gauge_ingest_f64"
        assert s["what"] == "s32[8192]"
        assert "tie-breaker" in s["rationale"]


# ---------------------------------------------------------------------------
# Residency composition — the item-1 gate
# ---------------------------------------------------------------------------


class TestResidency:
    def test_chain_and_seam_shape(self, artifact):
        res = artifact["residency"]
        assert res["chain"] == ["arena_ingest", "window_drain",
                                "encode_phase1", "placement"]
        assert [s["seam"] for s in res["seams"]] == [
            "arena_ingest->window_drain",
            "window_drain->encode_phase1",
            "encode_phase1->placement"]

    def test_composed_seams_charge_nothing(self, artifact):
        seams = {s["seam"]: s for s in artifact["residency"]["seams"]}
        for name in ("arena_ingest->window_drain",
                     "encode_phase1->placement"):
            s = seams[name]
            assert s["composed"] is True, s["evidence"]
            assert s["crossings"] == []
            assert s["bytes"] == 0

    def test_drain_seam_not_composed_with_typed_evidence(self, artifact):
        seams = {s["seam"]: s for s in artifact["residency"]["seams"]}
        s = seams["window_drain->encode_phase1"]
        assert s["composed"] is False
        assert "TracerArrayConversionError" in s["evidence"]
        assert len(s["crossings"]) == 10  # 3 kinds x lanes+counts + 4 h2d

    def test_crossings_byte_exact_vs_pipeline_ledger(self, artifact):
        """The derived ledger equals what `cli hops` MEASURED at the
        same geometry (PIPELINE_r13's window_drain d2h and encode h2d
        steady-state rows) — the static gate and the runtime meter
        describe the same seam."""
        hops = json.loads(PIPELINE.read_text())["hops"]
        seams = {s["seam"]: s for s in artifact["residency"]["seams"]}
        xs = seams["window_drain->encode_phase1"]["crossings"]
        d2h = [c for c in xs if c["direction"] == "d2h"]
        h2d = [c for c in xs if c["direction"] == "h2d"]
        wd = hops["window_drain"]["steady"]
        en = hops["encode"]["steady"]
        assert sum(c["transfers"] for c in d2h) == wd["d2h_count"] == 198
        assert sum(c["bytes_each"] * c["transfers"] for c in d2h) \
            == wd["d2h_bytes"] == 8110080
        assert sum(c["transfers"] for c in h2d) == en["h2d_count"] == 4
        assert sum(c["bytes_each"] * c["transfers"] for c in h2d) \
            == en["h2d_bytes"] == 582656

    def test_residency_findings_mirror_the_crossings(self, artifact):
        res_findings = [f for f in artifact["findings"]
                        if f["rule"] == "residency-composition"]
        assert len(res_findings) == 10
        assert {f["path"] for f in res_findings} \
            == {"seam:window_drain->encode_phase1"}

    def test_probe_is_zero_execution_typed_proof(self):
        """The drain->encode probe raises TracerArrayConversionError
        under eval_shape — shapes only, nothing runs."""
        composed, evidence = irlint._probe_drain_to_encode()
        assert composed is False
        assert "TracerArrayConversionError" in evidence

    def test_injected_crossing_fails_the_ratchet(self, monkeypatch):
        """Seeded violation 5: a NEW host crossing (a seam pair glued
        through np.asarray) appears in the findings and flips --check
        to FAIL against the committed baseline."""
        leak = irlint.Crossing(
            direction="d2h", name="rollup.leak", dtype="float64",
            shape=(1024,), bytes_each=8192, transfers=33,
            via="seeded np.asarray glue")
        seeded = irlint.Seam(
            "window_drain->encode_phase1", "window_drain",
            "encode_phase1",
            lambda: (False, "TracerArrayConversionError: seeded"),
            lambda: list(irlint._drain_crossings()) + [leak])
        others = tuple(s for s in irlint.SEAMS
                       if s.name != seeded.name)
        monkeypatch.setattr(irlint, "SEAMS", others + (seeded,))
        findings, _ = irlint.residency_report()
        assert any("rollup.leak" in f.message for f in findings)
        base = json.loads(BASELINE.read_text())
        cur = json.loads(BASELINE.read_text())
        cur["findings"] = cur["findings"] + [{
            "rule": "residency-composition",
            "path": f"seam:{seeded.name}", "message": leak.message}]
        errs = irlint.check_artifact(cur, base)
        assert [e["kind"] for e in errs] == ["new-finding"]
        assert "rollup.leak" in errs[0]["message"]


# ---------------------------------------------------------------------------
# Seeded violations — each rule family fires on a real lowered program
# ---------------------------------------------------------------------------


_SEED_N = 256


class TestSeededViolations:
    def test_host_callback_fires_transfer_free(self):
        """Seeded violation 1: a pure_callback inside a jitted program
        surfaces as an unclassified custom-call target."""
        def f(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v),
                jax.ShapeDtypeStruct((_SEED_N,), np.float64), x)
            return y.sum()

        p = irlint.program_ir("seed/callback", jax.jit(f).lower(
            jax.ShapeDtypeStruct((_SEED_N,), np.float64)))
        findings = irlint.rule_transfer_free(p)
        assert findings, "host callback must be a transfer-free finding"
        assert all(f.rule == "transfer-free" for f in findings)
        assert any("callback" in f.message for f in findings)

    def test_clean_program_is_transfer_free(self):
        p = irlint.program_ir("seed/clean", jax.jit(
            lambda x: jnp.sin(x).sum()).lower(
                jax.ShapeDtypeStruct((_SEED_N,), np.float64)))
        assert irlint.rule_transfer_free(p) == []

    def test_stray_scatter_fires_scatter_budget(self):
        """Seeded violation 2: an .at[].add creeping into a
        zero-budget stage."""
        def f(x, idx):
            return x.at[idx].add(1.0)

        p = irlint.program_ir("seed/scatter", jax.jit(f).lower(
            jax.ShapeDtypeStruct((_SEED_N,), np.float64),
            jax.ShapeDtypeStruct((8,), np.int32)))
        findings = irlint.rule_scatter_budget(p)  # unknown name -> 0
        assert len(findings) == 1
        assert "exceeds the stage budget 0" in findings[0].message
        # a reviewed budget row absorbs exactly the bounded allowance
        assert irlint.rule_scatter_budget(p, budget=8) == []

    def test_f64_leak_fires_width_discipline(self):
        """Seeded violation 3: an f64 path through an integer-contract
        stage — both the forbidden-type form and the zero-ceiling
        census form fire."""
        def f(x):
            return x.astype(jnp.float64).sum()

        p = irlint.program_ir("seed/i32stage", jax.jit(f).lower(
            jax.ShapeDtypeStruct((_SEED_N,), np.int32)))
        forbidden = irlint.rule_width_discipline(p, forbidden=("f64",))
        assert any("forbidden wide type f64" in f.message
                   for f in forbidden)
        ceiling = irlint.rule_width_discipline(p)  # unknown name -> 0
        assert any("exceeds the declared width contract 0" in f.message
                   for f in ceiling)
        # the same program under its honest contract is clean
        census = {t: _SEED_N * 4 for t in irlint.WIDE_TYPES}
        assert irlint.rule_width_discipline(p, contract=census,
                                            forbidden=()) == []

    def test_folded_constant_fires_ir_const_bloat(self):
        """Seeded violation 4: a >=4096-element literal folded into the
        compiled module (scrambled so XLA cannot rewrite it to iota)."""
        tbl = jnp.asarray(
            (np.arange(4096, dtype=np.int64) * 2654435761) % 4093,
            dtype=jnp.int32)

        def f(x):
            return x + tbl

        p = irlint.program_ir("seed/const", jax.jit(f).lower(
            jax.ShapeDtypeStruct((4096,), np.int32)))
        findings, sups = irlint.rule_ir_const_bloat(p)
        assert sups == []
        assert len(findings) == 1
        assert "s32[4096]" in findings[0].message
        # whitelisting records a suppression instead of a finding
        findings, sups = irlint.rule_ir_const_bloat(
            p, whitelist={("seed/const", "s32[4096]"): "reviewed seed"})
        assert findings == []
        assert len(sups) == 1 and sups[0]["rationale"] == "reviewed seed"

    def test_analyze_program_aggregates_all_families(self):
        """One seeded program through the full per-program analysis:
        the scatter and the width leak both fire through the same
        seam the registry run uses."""
        def f(x, idx):
            return x.at[idx].add(1.0).astype(jnp.float64).sum()

        p = irlint.program_ir("seed/multi", jax.jit(f).lower(
            jax.ShapeDtypeStruct((_SEED_N,), np.float32),
            jax.ShapeDtypeStruct((8,), np.int32)))
        findings, _ = irlint.analyze_program(p)
        rules = {f.rule for f in findings}
        assert "scatter-budget" in rules
        assert "width-discipline" in rules

    def test_seeded_finding_flips_check_to_fail(self):
        """The acceptance pin in gate terms: any seeded finding added
        to an otherwise-identical artifact is a new-finding error."""
        base = json.loads(BASELINE.read_text())
        cur = json.loads(BASELINE.read_text())
        cur["findings"] = cur["findings"] + [{
            "rule": "scatter-budget", "path": "seed/scatter",
            "message": "stablehlo.scatter census 1 exceeds the stage "
                       "budget 0"}]
        errs = irlint.check_artifact(cur, base)
        assert [e["kind"] for e in errs] == ["new-finding"]
        assert errs[0]["rule"] == "scatter-budget"


# ---------------------------------------------------------------------------
# Gate mechanics (pure — fabricated artifacts, no compiles)
# ---------------------------------------------------------------------------


def _mini(findings=(), **cfg) -> dict:
    config = {"platform": "cpu", "jax": jax.__version__,
              "canonical": {"S": 8}, "pipe": {"W": 4}}
    config.update(cfg)
    return {"artifact": "IRLINT", "schema": irlint.SCHEMA,
            "config": config,
            "findings": [{"rule": r, "path": p, "message": m}
                         for r, p, m in findings]}


_F = ("scatter-budget", "stage/x", "census 2 exceeds budget 0")


class TestCheckGateMechanics:
    def test_identical_passes(self):
        assert irlint.check_artifact(_mini([_F]), _mini([_F])) == []

    def test_new_finding_fails(self):
        errs = irlint.check_artifact(_mini([_F]), _mini())
        assert [e["kind"] for e in errs] == ["new-finding"]

    def test_stale_baseline_fails_ratchet(self):
        """An improvement must RE-BASELINE (cli irlint --out), never
        silently raise the bar for nobody — the burn-down mechanic
        item 1 rides."""
        errs = irlint.check_artifact(_mini(), _mini([_F]))
        assert [e["kind"] for e in errs] == ["stale-baseline"]
        assert "re-baseline" in errs[0]["message"]

    def test_duplicate_findings_are_multiset_counted(self):
        errs = irlint.check_artifact(_mini([_F, _F]), _mini([_F]))
        assert [e["kind"] for e in errs] == ["new-finding"]

    def test_schema_mismatch_refused(self):
        base = _mini()
        base["schema"] = irlint.SCHEMA + 1
        errs = irlint.check_artifact(_mini(), base)
        assert [e["kind"] for e in errs] == ["schema"]

    def test_platform_mismatch_refused(self):
        errs = irlint.check_artifact(_mini(), _mini(platform="tpu"))
        assert [e["kind"] for e in errs] == ["platform"]
        assert "head-to-head" in errs[0]["message"]

    def test_jax_version_mismatch_refused(self):
        base = _mini(jax="0.4.36")
        cur = _mini([_F])  # would otherwise be a new finding
        errs = irlint.check_artifact(cur, base)
        assert [e["kind"] for e in errs] == ["jax-version"]
        assert "re-baseline" in errs[0]["message"]

    def test_canonical_geometry_change_refused(self):
        errs = irlint.check_artifact(_mini(canonical={"S": 16}), _mini())
        assert [e["kind"] for e in errs] == ["config"]
        assert "canonical" in errs[0]["message"]

    def test_pipe_geometry_change_refused(self):
        errs = irlint.check_artifact(_mini(pipe={"W": 8}), _mini())
        assert [e["kind"] for e in errs] == ["config"]
        assert "pipe" in errs[0]["message"]


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------


class TestCli:
    def _run(self, argv):
        from m3_tpu.tools.cli import main

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = main(argv)
        lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
        return rc, lines

    def test_explain_every_rule(self):
        for rule in irlint.RULES:
            rc, lines = self._run(["irlint", "--explain", rule])
            assert rc == 0
            assert lines[0] == f"[{rule}]"

    def test_explain_unknown_rule_fails_fast(self):
        rc, _ = self._run(["irlint", "--explain", "no-such-rule"])
        assert rc == 2

    def test_check_missing_baseline_fails_fast(self):
        rc, _ = self._run(["irlint", "--check", "/no/such/file.json"])
        assert rc == 2

    def test_subset_json_run(self, artifact):
        """A single-stage run through the real CLI — free after the
        module fixture populated the shared stage cache."""
        rc, lines = self._run(["irlint", "--stage",
                               "arena/counter_consume_f64", "--json"])
        assert rc == 0
        rep = json.loads(lines[-1])
        assert rep["ok"] is True and rep["artifact"] == "IRLINT"
        for rule in ("transfer-free", "scatter-budget",
                     "width-discipline", "ir-const-bloat"):
            assert rep["counts"][rule] == 0
        # the residency probe runs regardless of the stage subset
        assert rep["counts"]["residency-composition"] == 10

    def test_out_writes_artifact(self, tmp_path, artifact):
        out = tmp_path / "IRLINT_test.json"
        rc, _ = self._run(["irlint", "--stage",
                           "arena/gauge_consume_f64", "--out", str(out)])
        assert rc == 0
        art = json.loads(out.read_text())
        assert art["artifact"] == "IRLINT"
        assert art["stages"] == ["arena/gauge_consume_f64"]
        assert art["residency"]["chain"][0] == "arena_ingest"
