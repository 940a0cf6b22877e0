"""Test environment: force an 8-device virtual CPU mesh before jax imports.

Chip runs go through chip_smoke.py; tests exercise the multi-device
sharded paths on virtual CPU devices.
"""

import os

# Force CPU for tests, in the env (for subprocesses) and the live config.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

DATA_DIR = pathlib.Path(__file__).resolve().parent / "data"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process dtest scenarios (fresh JAX per node)"
    )


# -- lock-order sanitizer (race/dtest tiers) --------------------------------

import pytest  # noqa: E402

_LOCKCHECK_FILES = {"test_race.py", "test_dtest.py"}


@pytest.fixture(autouse=True)
def _lockcheck_race_tiers(request):
    """Arm m3_tpu.x.lockcheck for the race and dtest tiers: every lock
    the test constructs is order-checked, an inversion raises in the
    acquiring thread, and any recorded finding fails the test even if
    no thread happened to die.  The env var is set so dtest node
    subprocesses inherit arming (NodeProcess snapshots os.environ).

    A user who armed the WHOLE suite (``M3_LOCKCHECK=1 pytest ...``)
    keeps their arming and mode: the fixture restores the prior env
    value and leaves the sanitizer installed on exit, and honors
    ``record`` mode instead of forcing raise mode."""
    if request.node.path.name not in _LOCKCHECK_FILES:
        yield
        return
    from m3_tpu.x import lockcheck

    prev_env = os.environ.get("M3_LOCKCHECK")
    was_installed = lockcheck.installed()
    if prev_env is None:
        os.environ["M3_LOCKCHECK"] = "1"
    lockcheck.reset()
    lockcheck.install(raise_on_cycle=prev_env != "record")
    try:
        yield
        found = lockcheck.findings()
        assert not found, "lock-order inversions detected:\n" + "\n".join(
            str(f) for f in found)
    finally:
        if not was_installed:
            lockcheck.uninstall()
        if prev_env is None:
            os.environ.pop("M3_LOCKCHECK", None)


# -- retrace/transfer sanitizer (race/dtest tiers) ---------------------------

_TRACEWATCH_FILES = {"test_race.py", "test_dtest.py"}


@pytest.fixture(autouse=True)
def _tracewatch_race_tiers(request):
    """Arm m3_tpu.x.tracewatch for the race and dtest tiers (the
    lockcheck pattern): every XLA compile in the test process is
    counted per function, a budget violation raises in the offending
    call, and any recorded finding fails the test even if nothing
    raised.  The env var is set so dtest NODE subprocesses inherit
    arming (NodeProcess snapshots os.environ) — a retrace storm inside
    a node dies loudly there instead of masquerading as a slow node.

    A user who armed the WHOLE suite (``M3_TRACEWATCH=1 pytest ...``)
    keeps their arming and mode, exactly like the lockcheck fixture."""
    if request.node.path.name not in _TRACEWATCH_FILES:
        yield
        return
    from m3_tpu.x import tracewatch

    prev_env = os.environ.get("M3_TRACEWATCH")
    was_installed = tracewatch.installed()
    if prev_env is None:
        os.environ["M3_TRACEWATCH"] = "1"
    tracewatch.reset()
    tracewatch.install(raise_on_violation=prev_env != "record")
    try:
        yield
        found = tracewatch.findings()
        assert not found, "retrace budget violations:\n" + "\n".join(
            str(f) for f in found)
    finally:
        if not was_installed:
            tracewatch.uninstall()
        if prev_env is None:
            os.environ.pop("M3_TRACEWATCH", None)
