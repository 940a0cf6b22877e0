"""costwatch: machine-independent cost fingerprints + the compile-only
regression gate (``cli costs`` / ``cli costs --check COSTS_r13.json``).

Tier-1 runs the REAL gate here: the module-scoped fixture builds the
full registry artifact once (~30s of compiles, no execution) and the
committed-baseline test asserts it checks green — plus the seeded
regression class the gate exists to catch: an i32→i64 promotion in the
encode offsets and the decode control table reverting to a trace-time
constant both flip ``--check`` to FAIL with zero wall-clock measurement
involved."""

from __future__ import annotations

import io
import json
from contextlib import redirect_stdout
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from m3_tpu.tools import costs as costs_tool
from m3_tpu.x import costwatch

REPO = Path(__file__).resolve().parent.parent
BASELINE = REPO / "COSTS_r13.json"


@pytest.fixture(scope="module")
def full_artifact():
    """One full registry run shared by every test in this module (the
    compiles are the cost; every assertion below reads the result)."""
    return costs_tool.build_artifact()


# ---------------------------------------------------------------------------
# Extractors
# ---------------------------------------------------------------------------


class TestCounters:
    def test_count_jaxpr_ops_includes_nested(self):
        def f(x):
            def body(c, _):
                return c * 2 + 1, c
            return jax.lax.scan(body, x, None, length=4)

        jx = jax.make_jaxpr(f)(jnp.int64(3))
        n = costwatch.count_jaxpr_ops(jx.jaxpr)
        # the scan eqn itself plus the body's mul+add at minimum
        assert n >= 3

    def test_profile_harness_uses_the_one_home(self):
        """decode_profile's hand counter IS costwatch's — the artifact
        cross-check is meaningless if the two sides count
        differently."""
        from m3_tpu.tools import decode_profile

        jx = jax.make_jaxpr(lambda x: x * x + 1)(jnp.float64(2.0))
        assert decode_profile._count_ops(jx.jaxpr) == \
            costwatch.count_jaxpr_ops(jx.jaxpr)


class TestHloHistogram:
    def test_parses_instruction_lines(self):
        txt = (
            "HloModule jit_f\n\n"
            "%region_0.4 (a: f32[], b: f32[]) -> f32[] {\n"
            "  %a = f32[] parameter(0)\n"
            "  %b = f32[] parameter(1)\n"
            "  ROOT %add.1 = f32[] add(f32[] %a, f32[] %b)\n"
            "}\n\n"
            "ENTRY %main (x: f32[8]) -> f32[] {\n"
            "  %x = f32[8]{0} parameter(0)\n"
            "  %c = f32[] constant(0)\n"
            "  ROOT %r = f32[] reduce(%x, %c), to_apply=%region_0.4\n"
            "}\n")
        hist = costwatch.hlo_op_histogram(txt)
        assert hist["parameter"] == 3
        assert hist["add"] == 1
        assert hist["reduce"] == 1
        assert hist["constant"] == 1

    def test_real_compiled_module(self):
        c = jax.jit(lambda x: jnp.sin(x).sum()).lower(
            jax.ShapeDtypeStruct((64,), np.float64)).compile()
        hist = costwatch.hlo_op_histogram(c.as_text())
        assert sum(hist.values()) > 0
        assert "parameter" in hist


class TestFingerprint:
    def test_fields_and_normalizations(self):
        lowered = jax.jit(lambda x: jnp.sin(x).sum()).lower(
            jax.ShapeDtypeStruct((128,), np.float64))
        fp = costwatch.fingerprint_lowered(lowered, datapoints=128)
        assert fp["datapoints"] == 128
        assert fp["transcendentals"] >= 128  # one sine per element
        assert fp["flops"] > 0
        assert fp["flops_per_dp"] == pytest.approx(fp["flops"] / 128,
                                                   abs=1e-4)
        assert fp["bytes_per_dp"] == pytest.approx(
            fp["bytes_accessed"] / 128, abs=1e-4)
        mem = fp["memory"]
        assert mem["argument_bytes"] == 128 * 8
        assert mem["output_bytes"] == 8
        assert mem["peak_bytes"] == (
            mem["argument_bytes"] + mem["output_bytes"]
            + mem["temp_bytes"] - mem["alias_bytes"])
        assert fp["hlo_op_total"] == sum(fp["hlo_ops"].values())


# ---------------------------------------------------------------------------
# Registry coverage
# ---------------------------------------------------------------------------


REQUIRED_STAGES = {
    # decode: both chains tails AND both extract impls
    "decode/fused", "decode/gather", "decode/gather_pallas",
    "decode/sharded",
    # encode: all three placement tails + the sharded wrapper
    "encode/gather", "encode/scatter", "encode/pallas", "encode/sharded",
    # arena ingest/consume, packed AND f64
    "arena/rollup_ingest_packed", "arena/counter_ingest_f64",
    "arena/gauge_ingest_f64", "arena/counter_consume_packed",
    "arena/counter_consume_f64", "arena/gauge_consume_packed",
    "arena/gauge_consume_f64",
    # the timer ingest/drain path, both layouts
    "timer/ingest_packed", "timer/ingest_f64",
    "timer/consume_packed", "timer/consume_f64",
}


class TestRegistry:
    def test_registry_names_every_hot_path_stage(self):
        assert REQUIRED_STAGES <= set(costwatch.stage_names())

    def test_unknown_stage_raises(self):
        with pytest.raises(KeyError, match="unknown costwatch stage"):
            costwatch.run_stages(["no/such_stage"])

    def test_every_stage_fingerprinted(self, full_artifact):
        stages = full_artifact["stages"]
        assert REQUIRED_STAGES <= set(stages)
        for name, fp in stages.items():
            assert fp["datapoints"] > 0, name
            assert fp["bytes_accessed"] > 0, name
            assert fp["hlo_op_total"] > 0, name
            assert fp["memory"]["peak_bytes"] > 0, name
            assert "config" in fp, name

    def test_sharded_stages_pin_two_device_mesh(self, full_artifact):
        for name in ("decode/sharded", "encode/sharded"):
            assert full_artifact["stages"][name]["config"]["devices"] == 2

    def test_compile_only_no_execution(self, full_artifact):
        """The artifact records a compile-only run: lowering consumed
        ShapeDtypeStructs, so there is nothing a timed loop could have
        produced — pinned by the absence of any wall/throughput field
        in every stage record."""
        for name, fp in full_artifact["stages"].items():
            assert not ({"wall_s", "dps", "samples_per_sec", "seconds"}
                        & set(fp)), name


class TestOpsDpCrosscheck:
    def test_jaxpr_counts_track_documented_hand_counts(self, full_artifact):
        """THE can't-silently-diverge pin: the live jaxpr step count
        must stay within 10% of the documented PROFILE attribution
        (decode 670, encode 1485).  A formulation change that moves the
        step cost must update DOCUMENTED_OPS_PER_DP (and the PROFILE
        artifact) in the same PR."""
        cc = full_artifact["opsdp_crosscheck"]
        for key in ("decode", "encode"):
            rec = cc[key]
            assert 0.9 <= rec["jaxpr_vs_documented"] <= 1.1, rec
        assert "explanation" in cc

    def test_hlo_numbers_recorded_with_drift(self, full_artifact):
        rec = full_artifact["opsdp_crosscheck"]["decode"]
        assert rec["hlo_flops_per_dp"] > 0
        assert rec["hlo_flops_vs_jaxpr_ops"] > 0


class TestMembudgetCrosscheckInArtifact:
    def test_arena_formulas_within_contract(self, full_artifact):
        mb = full_artifact["membudget_crosscheck"]
        assert len(mb["arena"]) == 6  # 3 kinds x 2 layouts
        for name, rec in mb["arena"].items():
            assert 1.0 <= rec["ratio"] <= 2.0, (name, rec)

    def test_codec_formulas_within_contract(self, full_artifact):
        """The codec lane-table admission formulas (per-tail since
        round 13) against XLA's argument+output+temp at canonical
        shapes — the satellite's [1x, 2x] bound."""
        mb = full_artifact["membudget_crosscheck"]
        assert len(mb["codec"]) == 6  # 3 decode tails + 3 encode tails
        for name, rec in mb["codec"].items():
            assert 1.0 <= rec["ratio"] <= 2.0, (name, rec)


# ---------------------------------------------------------------------------
# The committed baseline — the tier-1 gate itself
# ---------------------------------------------------------------------------


class TestCommittedBaseline:
    def test_committed_artifact_is_wellformed(self):
        art = json.loads(BASELINE.read_text())
        assert art["artifact"] == "COSTS"
        assert art["schema"] == costs_tool.SCHEMA
        assert art["config"]["platform"] == "cpu"
        assert REQUIRED_STAGES <= set(art["stages"])
        for fp in art["stages"].values():
            assert fp["memory"]["peak_bytes"] > 0
        assert art["opsdp_crosscheck"]["decode"]["documented_ops_per_dp"] \
            == 670
        assert art["opsdp_crosscheck"]["encode"]["documented_ops_per_dp"] \
            == 1485

    def test_check_against_committed_baseline_green(self, full_artifact):
        """`cli costs --check COSTS_r13.json` green — the gate every
        tier-1 run exercises against the live registry."""
        errs = costs_tool.check_artifact(
            full_artifact, json.loads(BASELINE.read_text()))
        assert errs == [], "\n".join(e["message"] for e in errs)


# ---------------------------------------------------------------------------
# Gate mechanics (pure — fabricated artifacts, no compiles)
# ---------------------------------------------------------------------------


def _mini(stage_fp: dict, platform: str = "cpu") -> dict:
    return {
        "artifact": "COSTS", "schema": costs_tool.SCHEMA,
        "config": {"platform": platform},
        "stages": {"stage/x": stage_fp},
    }


def _fp(flops=1000, by=10000, temp=5000, arg=2000, outb=500,
        ops=100, cfg=None) -> dict:
    return {
        "datapoints": 100, "flops": flops, "transcendentals": 0,
        "bytes_accessed": by, "flops_per_dp": flops / 100,
        "bytes_per_dp": by / 100, "hlo_ops": {"add": ops},
        "hlo_op_total": ops,
        "memory": {"argument_bytes": arg, "output_bytes": outb,
                   "temp_bytes": temp, "alias_bytes": 0,
                   "generated_code_bytes": 0,
                   "peak_bytes": arg + outb + temp},
        "peak_bytes_per_dp": (arg + outb + temp) / 100,
        "config": dict(cfg or {"S": 1}),
    }


class TestCheckGateMechanics:
    def test_identical_passes(self):
        assert costs_tool.check_artifact(_mini(_fp()), _mini(_fp())) == []

    def test_within_tolerance_passes(self):
        assert costs_tool.check_artifact(
            _mini(_fp(flops=1040)), _mini(_fp(flops=1000)),
            tolerance=0.05) == []

    def test_regression_past_tolerance_fails(self):
        errs = costs_tool.check_artifact(
            _mini(_fp(flops=1200)), _mini(_fp(flops=1000)),
            tolerance=0.05)
        assert [e["kind"] for e in errs] == ["regression"]
        assert errs[0]["metric"] == "flops"

    def test_improvement_past_tolerance_fails_ratchet(self):
        """Improvements must RE-BASELINE, not silently raise the bar
        for nobody (the lint stale-entry rule, applied to metrics)."""
        errs = costs_tool.check_artifact(
            _mini(_fp(by=8000)), _mini(_fp(by=10000)), tolerance=0.05)
        assert [e["kind"] for e in errs] == ["improvement"]
        assert "re-baseline" in errs[0]["message"]

    def test_stage_vanished_fails(self):
        cur = _mini(_fp())
        cur["stages"] = {}
        errs = costs_tool.check_artifact(cur, _mini(_fp()))
        assert [e["kind"] for e in errs] == ["stage-vanished"]

    def test_new_stage_fails(self):
        base = _mini(_fp())
        base["stages"] = {}
        errs = costs_tool.check_artifact(_mini(_fp()), base)
        assert [e["kind"] for e in errs] == ["stage-new"]

    def test_config_change_fails_before_metrics(self):
        errs = costs_tool.check_artifact(
            _mini(_fp(flops=9999, cfg={"S": 2})),
            _mini(_fp(cfg={"S": 1})))
        assert [e["kind"] for e in errs] == ["config"]

    def test_platform_mismatch_refused(self):
        errs = costs_tool.check_artifact(
            _mini(_fp(), platform="tpu"), _mini(_fp(), platform="cpu"))
        assert [e["kind"] for e in errs] == ["platform"]
        assert "head-to-head" in errs[0]["message"]

    def test_schema_mismatch_refused(self):
        base = _mini(_fp())
        base["schema"] = costs_tool.SCHEMA + 1
        errs = costs_tool.check_artifact(_mini(_fp()), base)
        assert [e["kind"] for e in errs] == ["schema"]

    def test_jax_version_mismatch_refused(self):
        """An XLA upgrade moves fingerprints legitimately — the gate
        must refuse typed (re-baseline PR), never misattribute the
        move to a formulation regression."""
        base = _mini(_fp())
        base["config"]["jax"] = "0.4.36"
        cur = _mini(_fp(flops=5000))  # would otherwise be a regression
        cur["config"]["jax"] = "0.4.37"
        errs = costs_tool.check_artifact(cur, base)
        assert [e["kind"] for e in errs] == ["jax-version"]
        assert "re-baseline" in errs[0]["message"]

    def test_canonical_geometry_change_refused(self):
        base = _mini(_fp())
        base["config"]["canonical"] = {"S": 256}
        cur = _mini(_fp())
        cur["config"]["canonical"] = {"S": 128}
        errs = costs_tool.check_artifact(cur, base)
        assert [e["kind"] for e in errs] == ["config"]
        assert "canonical geometry" in errs[0]["message"]

    def test_hlo_op_total_absolute_slack(self):
        """±4 ops of jitter on a tiny program must not trip the
        relative gate (the _ABS_SLACK floor)."""
        assert costs_tool.check_artifact(
            _mini(_fp(ops=12)), _mini(_fp(ops=10)), tolerance=0.05) == []
        errs = costs_tool.check_artifact(
            _mini(_fp(ops=20)), _mini(_fp(ops=10)), tolerance=0.05)
        assert errs and errs[0]["metric"] == "hlo_op_total"

    def test_metric_appearing_from_zero_fails(self):
        errs = costs_tool.check_artifact(
            _mini(_fp(flops=100)), _mini(_fp(flops=0)))
        assert errs and "appeared" in errs[0]["message"]


# ---------------------------------------------------------------------------
# Seeded regressions — the acceptance pin: a REAL formulation
# regression flips the gate with zero wall-clock measurement involved.
# ---------------------------------------------------------------------------


_SEED_S, _SEED_T = 8, 16


def _seed_artifact(name: str, fp: dict) -> dict:
    return {"artifact": "COSTS", "schema": costs_tool.SCHEMA,
            "config": {"platform": jax.devices()[0].platform},
            "stages": {name: dict(fp, config={"S": _SEED_S, "T": _SEED_T})}}


class TestSeededRegressions:
    def _encode_fp(self):
        from m3_tpu.encoding import m3tsz_jax as mj

        S, T = _SEED_S, _SEED_T
        sds = jax.ShapeDtypeStruct
        ow = T * 16 // 64 + 4
        raw = mj._encode_batch_device.__wrapped__
        # a FRESH jit wrapper per call: the module-level jit caches
        # traces on the underlying function, and the seeded variant
        # must re-trace under the patched module global
        fn = jax.jit(lambda a, b, c, d: raw(
            a, b, c, d, unit=1, out_words=ow, prefix_bits=None,
            place="scatter"))
        lowered = fn.lower(
            sds((S, T), np.int64), sds((S, T), np.uint64),
            sds((S,), np.int64), sds((S, T), np.bool_))
        return costwatch.fingerprint_lowered(lowered, S * T)

    def test_i64_cumsum_promotion_flips_check_to_fail(self, monkeypatch):
        """Reverting the encoder's pinned-i32 offset arithmetic to i64
        (the silent-promotion class round 9 pinned against) moves
        bytes-accessed ~1.5x — the gate FAILS on fingerprints alone."""
        from m3_tpu.encoding import m3tsz_jax as mj

        baseline = _seed_artifact("encode/seeded", self._encode_fp())
        monkeypatch.setattr(mj, "I32", jnp.int64)
        seeded = _seed_artifact("encode/seeded", self._encode_fp())
        errs = costs_tool.check_artifact(seeded, baseline, tolerance=0.05)
        kinds = {e["kind"] for e in errs}
        assert "regression" in kinds, errs
        assert any(e["metric"] == "bytes_accessed" for e in errs), errs
        # and the un-seeded program still checks green against itself
        monkeypatch.undo()
        again = _seed_artifact("encode/seeded", self._encode_fp())
        assert costs_tool.check_artifact(again, baseline,
                                         tolerance=0.05) == []

    def test_ctrl_table_as_constant_flips_check_to_fail(self):
        """Reverting the decode value-control table from a device
        ARGUMENT to a trace-time constant (the exact pre-round-7
        constant-bloat bug) collapses argument bytes by ~1MiB — the
        gate FAILS without running a single decode."""
        from m3_tpu.encoding import m3tsz_jax as mj

        S, T = _SEED_S, _SEED_T
        W = T * 24 // 64 + 4
        sds = jax.ShapeDtypeStruct
        words = sds((S, W + 1), np.uint64)
        nbits = sds((S,), np.int64)
        raw = mj._decode_batch_device.__wrapped__
        good = jax.jit(lambda w, n, t: raw(
            w, n, t, max_points=T + 1, default_unit=1, chains="fused",
            scan_major=True, extract="jnp"))
        fp_good = costwatch.fingerprint_lowered(
            good.lower(words, nbits, sds((1 << 18,), np.uint32)), S * T)
        const_tbl = jnp.zeros(1 << 18, jnp.uint32)
        bad = jax.jit(lambda w, n: raw(
            w, n, const_tbl, max_points=T + 1, default_unit=1,
            chains="fused", scan_major=True, extract="jnp"))
        fp_bad = costwatch.fingerprint_lowered(
            bad.lower(words, nbits), S * T)
        assert fp_good["memory"]["argument_bytes"] > 1 << 20
        assert fp_bad["memory"]["argument_bytes"] < 1 << 20
        errs = costs_tool.check_artifact(
            _seed_artifact("decode/seeded", fp_bad),
            _seed_artifact("decode/seeded", fp_good), tolerance=0.05)
        assert errs, "constant-bloat revert must fail the gate"
        assert any(e["metric"] == "memory.argument_bytes" for e in errs)


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

    def test_costs_json_subset(self):
        rc, lines = self._run(["costs", "--stage",
                               "arena/counter_consume_f64", "--json"])
        assert rc == 0
        rep = json.loads(lines[-1])
        assert rep["ok"] is True and rep["stages"] == 1

    def test_costs_check_subset_reports_vanished_stages(self):
        """A subset run checked against the full baseline is the gate's
        own stage-vanished mechanics, exercised through the real CLI."""
        rc, lines = self._run([
            "costs", "--stage", "arena/counter_consume_f64",
            "--check", str(BASELINE), "--json"])
        assert rc == 1
        rep = json.loads(lines[-1])
        assert rep["ok"] is False
        assert all(v["kind"] == "stage-vanished" for v in rep["violations"])

    def test_costs_check_missing_baseline_fails_fast(self):
        rc, _ = self._run(["costs", "--check", "/no/such/file.json"])
        assert rc == 2

    def test_costs_out_writes_artifact(self, tmp_path):
        out = tmp_path / "COSTS_test.json"
        rc, _ = self._run(["costs", "--stage", "arena/gauge_consume_f64",
                           "--out", str(out)])
        assert rc == 0
        art = json.loads(out.read_text())
        assert art["artifact"] == "COSTS"
        assert set(art["stages"]) == {"arena/gauge_consume_f64"}
