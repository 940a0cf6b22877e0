"""chip_smoke.py off the chip: it must refuse to pass, and its phases —
plain functions taking sizes — must hold their checks at a tiny size on
the CPU (the builder's end-to-end rehearsal, guide §2.1)."""

import json

import chip_smoke


def test_no_chip_no_pass(capsys):
    """JAX finds no accelerator here (conftest holds tests to the CPU):
    whatever else happens, the last line says ok: false and the exit
    code is not 0."""
    rc = chip_smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    verdict = json.loads(last)
    assert rc != 0
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"


def test_sizes_are_arguments_platform_is_not():
    """No option lets the script itself succeed off the chip."""
    import inspect

    src = inspect.getsource(chip_smoke.main)
    assert "--series" in src and "--seed" in src
    assert "JAX_PLATFORMS" not in inspect.getsource(chip_smoke).replace(
        "``JAX_PLATFORMS``", "")


def test_phases_rehearsed_small(tmp_path, capsys):
    """4 histograms x 10 buckets + 24 gauges (16 of them the extreme
    family), 240 points each, through the same phases the chip run
    drives: boot via run_node, history + HTTP writes, drains, queries
    before and after the flush, and checks (a)-(d) and (f)."""
    from m3_tpu.x import devguard

    # process-global counters: earlier tests of this worker may have
    # forced fallbacks on purpose
    devguard.reset_counters()
    devguard.reset_stages()
    checks = chip_smoke.run_smoke(histograms=4, gauges=24, seed=7,
                                  root=str(tmp_path), sample=24)
    out = capsys.readouterr().out
    assert '"boot"' in out and "run_node" in out

    def ok(name):
        v = checks[name]
        return v["ok"] if isinstance(v, dict) else v

    assert ok("a_readback_buffer") and ok("a_readback_fileset")
    assert ok("a_queries"), checks["a_queries"]
    # the CPU's f64 is IEEE: raw selectors are bit-exact here
    for name in ("raw_gauge", "raw_extreme"):
        sel = checks["a_queries"][name]
        assert sel["bit_exact"] == sel["samples"] > 0
    assert ok("b_filesets"), checks["b_filesets"]
    assert ok("c_rollups"), checks["c_rollups"]
    assert ok("d_devguard"), checks["d_devguard"]
    assert ok("f_no_recompile"), checks["f_no_recompile"]
    assert checks["windows_drained"]["count"] >= 3
    assert checks["http_batches"]["count"] >= 8
    assert checks["b_filesets"]["byte_identical"] == \
        checks["b_filesets"]["series"] >= 24
