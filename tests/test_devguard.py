"""Device-boundary resilience tier: x/devguard + x/membudget.

Four halves mirroring the module split:

* **Classification matrix** — :func:`devguard.classify` over the
  jax/XLA exception *shapes* (class name + grpc-style status
  vocabulary): RESOURCE_EXHAUSTED/OOM strings → DeviceOOM, compile
  shapes → CompileFailure, unavailable/lost → DeviceLost, any other
  XlaRuntimeError → DeviceStateError, and — load-bearing — programming
  errors (TypeError, shape ValueError) → None so a bug can never trip
  a stage breaker.  One ``slow``-marked subprocess test provokes a
  REAL XLA-CPU OOM to pin the classifier against the live exception
  type, not our imitation of it.
* **The guarded seam** — :func:`devguard.run_guarded` fallback/raise
  semantics, per-stage counters, breaker trip → open (primary skipped)
  → half-open probe → closed, and the ``device.compile`` /
  ``device.dispatch`` / ``device.transfer`` faultpoints firing typed.
* **Memory budget** — x/membudget admission (typed
  ``DeviceBudgetExceeded`` + rejected counter), resize deltas,
  owner-gc auto-release, and the acceptance criterion: over-budget
  ``make_arenas`` / ``ShardBuffer`` reject typed at ADMISSION instead
  of dying inside XLA.
* **Hot-path integration** — arena ingest and the storage buffer
  degrade through their fallbacks bit-identically under injected
  device faults, and the buffer's host staging keeps warm samples
  readable (the zero-acked-loss contract's unit-level half).
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from m3_tpu.x import devguard, fault, membudget
from m3_tpu.x.breaker import BreakerOpenError, all_breakers, reset_registry
from m3_tpu.x.devguard import (
    CompileFailure,
    DeviceError,
    DeviceLost,
    DeviceOOM,
    DeviceStateError,
    classify,
    run_guarded,
    transfer_point,
)
from m3_tpu.x.membudget import DeviceBudgetExceeded


@pytest.fixture(autouse=True)
def _clean_device_state():
    """Every test sees a fresh guard: no armed faults, no counters, no
    stage breakers, default budget."""
    fault.disarm()
    devguard.reset_stages()
    reset_registry()
    gc.collect()  # release dropped owners BEFORE zeroing the ledger
    membudget.reset()
    membudget.set_budget(0)
    yield
    fault.disarm()
    devguard.reset_stages()
    reset_registry()
    gc.collect()
    membudget.reset()
    membudget.set_budget(0)
    devguard.configure(failures=5, reset_s=10.0)


# ---------------------------------------------------------------------------
# Classification matrix
# ---------------------------------------------------------------------------


class XlaRuntimeError(RuntimeError):
    """Shape-compatible stand-in: the classifier matches on the CLASS
    NAME (jaxlib moves the real class between releases)."""


class TestClassify:
    @pytest.mark.parametrize("msg,expected", [
        # the live XLA-CPU shape (pinned for real in TestRealOOM)
        ("RESOURCE_EXHAUSTED: Out of memory allocating 17592186044416 "
         "bytes.", DeviceOOM),
        ("Out of memory while trying to allocate 1073741824 bytes",
         DeviceOOM),
        ("RESOURCE_EXHAUSTED: Failed to allocate request for 2.0GiB",
         DeviceOOM),
        ("XLA allocation failure: OOM when allocating tensor", DeviceOOM),
        # compile family
        ("Compilation failure: Mosaic lowering failed", CompileFailure),
        ("UNIMPLEMENTED: dynamic-slice fusion not supported",
         CompileFailure),
        ("INVALID_ARGUMENT: Unsupported HLO instruction", CompileFailure),
        # a compile-time RESOURCE_EXHAUSTED is still an OOM (first
        # family wins)
        ("RESOURCE_EXHAUSTED: while compiling cluster", DeviceOOM),
        # lost-device family
        ("UNAVAILABLE: socket closed", DeviceLost),
        ("ABORTED: device lost", DeviceLost),
        ("DATA_LOSS: truncated transfer from device", DeviceLost),
        ("FAILED_PRECONDITION: device disconnected", DeviceLost),
        # anything else the runtime says about itself degrades, never
        # crashes
        ("INTERNAL: something novel went wrong", DeviceStateError),
    ])
    def test_xla_message_matrix(self, msg, expected):
        assert classify(XlaRuntimeError(msg)) is expected

    def test_xla_subclass_matches_via_mro(self):
        class Derived(XlaRuntimeError):
            pass

        assert classify(Derived("RESOURCE_EXHAUSTED: oom")) is DeviceOOM

    def test_host_state_shapes(self):
        # the packed arena's sticky overflow raise and jax's
        # deleted-buffer error are host-raised RuntimeErrors
        assert classify(RuntimeError(
            "packed counter arena overflow-pool error: pool exhausted"
        )) is DeviceStateError
        assert classify(RuntimeError(
            "Array has been deleted with shape=float64[8].".lower()
        )) is DeviceStateError

    def test_device_errors_classify_to_themselves(self):
        assert classify(DeviceOOM("s")) is DeviceOOM
        assert classify(CompileFailure("s")) is CompileFailure
        assert classify(DeviceBudgetExceeded("c", 1, 1, 1)) is \
            DeviceBudgetExceeded

    @pytest.mark.parametrize("exc", [
        TypeError("unhashable static arg"),
        ValueError("operands could not be broadcast"),
        KeyError("missing"),
        OSError("connection reset by peer"),
        # a generic RuntimeError without a device-state shape is a
        # programming bug, not a device failure
        RuntimeError("dictionary changed size during iteration"),
    ])
    def test_programming_errors_propagate_raw(self, exc):
        assert classify(exc) is None

    def test_budget_exceeded_is_an_oom(self):
        e = DeviceBudgetExceeded("arena", 100, 50, 10)
        assert isinstance(e, DeviceOOM)
        assert isinstance(e, DeviceError)
        assert e.kind == "budget"


# ---------------------------------------------------------------------------
# run_guarded: fallback, counters, breakers, faultpoints
# ---------------------------------------------------------------------------


class TestRunGuarded:
    def test_happy_path_counts_and_returns(self):
        out = run_guarded("t.stage", lambda: 41 + 1, lambda: -1)
        assert out == 42
        c = devguard.counters()
        assert c["device.t.stage.calls"] == 1
        assert "device.t.stage.fallback_calls" not in c

    def test_classified_failure_runs_fallback_same_batch(self):
        batch = []

        def primary():
            batch.append("primary")
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: oom")

        def fallback():
            batch.append("fallback")
            return "degraded"

        assert run_guarded("t.fb", primary, fallback) == "degraded"
        assert batch == ["primary", "fallback"]
        c = devguard.counters()
        assert c["device.t.fb.errors.oom"] == 1
        assert c["device.t.fb.fallback_calls"] == 1

    def test_no_fallback_raises_typed(self):
        def primary():
            raise XlaRuntimeError("UNAVAILABLE: device lost")

        with pytest.raises(DeviceLost) as ei:
            run_guarded("t.nofb", primary)
        assert ei.value.stage == "t.nofb"
        assert isinstance(ei.value.cause, XlaRuntimeError)

    def test_unclassified_propagates_raw_and_breaker_untouched(self):
        def primary():
            raise TypeError("a bug")

        with pytest.raises(TypeError):
            run_guarded("t.bug", primary, lambda: "never")
        assert devguard.stage_breaker("t.bug").state == "closed"
        assert "device.t.bug.errors" not in str(devguard.counters())

    def test_classified_fallback_failure_raises_typed(self):
        """A device failure that PERSISTS through the fallback (e.g.
        jax's deleted-buffer error after the primary donated its input)
        raises typed — and never failure-bumps the breaker, which
        tracks the device path only."""
        devguard.configure(failures=5, reset_s=10.0)

        def primary():
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: oom")

        def fallback():
            raise RuntimeError("Array has been deleted")

        with pytest.raises(DeviceStateError):
            run_guarded("t.fbdead", primary, fallback)
        c = devguard.counters()
        assert c["device.t.fbdead.errors.oom"] == 1      # primary
        assert c["device.t.fbdead.errors.state"] == 1    # fallback
        # one device failure recorded, not two: breaker still closed
        assert devguard.stage_breaker("t.fbdead").state == "closed"
        # an unclassified fallback exception still propagates raw
        with pytest.raises(ZeroDivisionError):
            run_guarded("t.fbbug", primary, lambda: 1 // 0)

    def test_breaker_trips_then_half_open_recovers(self):
        devguard.configure(failures=2, reset_s=0.05)
        calls = {"primary": 0}

        def bad_primary():
            calls["primary"] += 1
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: oom")

        # two classified failures trip the stage breaker open
        for _ in range(2):
            assert run_guarded("t.trip", bad_primary, lambda: "fb") == "fb"
        br = devguard.stage_breaker("t.trip")
        assert br.state == "open" and br.kind == "stage"
        # open: the primary is SKIPPED entirely
        assert run_guarded("t.trip", bad_primary, lambda: "fb") == "fb"
        assert calls["primary"] == 2
        # after the cool-down, the half-open probe retries the device
        # path and a success closes the breaker
        time.sleep(0.06)
        assert br.state == "half_open"
        assert run_guarded("t.trip", lambda: "device-ok",
                           lambda: "fb") == "device-ok"
        assert br.state == "closed"

    def test_unclassified_during_half_open_probe_releases_slot(self):
        """A Python bug raised during the half-open probe must not
        wedge the breaker with the probe slot taken forever — the
        device answered, so the app-error rule closes it (the
        CircuitBreaker.call semantics)."""
        devguard.configure(failures=1, reset_s=0.05)

        def dev_bad():
            raise XlaRuntimeError("UNAVAILABLE: gone")

        run_guarded("t.wedge", dev_bad, lambda: "fb")
        time.sleep(0.06)
        assert devguard.stage_breaker("t.wedge").state == "half_open"

        def bug():
            raise TypeError("a bug, not a device failure")

        with pytest.raises(TypeError):
            run_guarded("t.wedge", bug, lambda: "fb")
        # the probe slot released; the device path serves again
        assert devguard.stage_breaker("t.wedge").state == "closed"
        assert run_guarded("t.wedge", lambda: "dev", lambda: "fb") == "dev"

    def test_half_open_failure_reopens(self):
        devguard.configure(failures=1, reset_s=0.05)

        def bad():
            raise XlaRuntimeError("UNAVAILABLE: gone")

        run_guarded("t.reopen", bad, lambda: "fb")
        time.sleep(0.06)
        assert devguard.stage_breaker("t.reopen").state == "half_open"
        run_guarded("t.reopen", bad, lambda: "fb")
        assert devguard.stage_breaker("t.reopen").state == "open"

    def test_open_breaker_without_fallback_raises_typed(self):
        devguard.configure(failures=1, reset_s=30.0)

        def bad():
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: oom")

        with pytest.raises(DeviceOOM):
            run_guarded("t.open_nofb", bad)
        # without a fallback the guard never consults allow(): the
        # typed error surfaces to the caller each time (admission
        # shape), it does not turn into BreakerOpenError
        with pytest.raises(DeviceOOM):
            run_guarded("t.open_nofb", bad)

    def test_dispatch_faultpoint_injects_oom(self):
        with fault.armed("device.dispatch", "error"):
            assert run_guarded("t.inj", lambda: "dev",
                               lambda: "fb") == "fb"
        c = devguard.counters()
        assert c["device.t.inj.errors.oom"] == 1
        # disarmed: the device path serves again
        assert run_guarded("t.inj", lambda: "dev", lambda: "fb") == "dev"

    def test_compile_faultpoint_gates_first_device_call(self):
        with fault.armed("device.compile", "error", n=1):
            # compile fails → fallback; the stage is NOT marked
            # compiled (a failed compile retries on the next call)
            assert run_guarded("t.cmp", lambda: "dev", lambda: "fb") == "fb"
            # spec exhausted → compile succeeds → stage marked compiled
            assert run_guarded("t.cmp", lambda: "dev", lambda: "fb") == "dev"
        # once compiled, a freshly armed compile fault no longer fires
        # for this stage — only dispatch/transfer do
        with fault.armed("device.compile", "error"):
            assert run_guarded("t.cmp", lambda: "dev", lambda: "fb") == "dev"
        assert devguard.counters()["device.t.cmp.errors.compile"] == 1

    def test_transfer_point_classifies_lost(self):
        def primary():
            transfer_point("t.xfer")
            return "dev"

        with fault.armed("device.transfer", "error"):
            assert run_guarded("t.xfer", primary, lambda: "fb") == "fb"
        assert devguard.counters()["device.t.xfer.errors.lost"] == 1
        assert run_guarded("t.xfer", primary, lambda: "fb") == "dev"

    def test_status_document_shape(self):
        devguard.configure(failures=1, reset_s=30.0)

        def bad():
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: oom")

        run_guarded("arena.ingest", lambda: 1, lambda: 2)
        run_guarded("arena.ingest", bad, lambda: 2)
        st = devguard.status()["stages"]["arena.ingest"]
        assert st["calls"] == 1
        assert st["fallback_calls"] == 1
        assert st["errors"] == {"oom": 1}
        assert st["breaker"] == "open"
        assert all_breakers()["stage:arena.ingest"].kind == "stage"


# ---------------------------------------------------------------------------
# Memory budget
# ---------------------------------------------------------------------------


class TestMembudget:
    @pytest.mark.parametrize("raw,expected", [
        (1048576, 1048576),
        ("512", 512),
        ("512M", 512 << 20),
        ("2GiB", 2 << 30),
        ("1.5K", 1536),
        ("4T", 4 << 40),
        ("0", 0),
    ])
    def test_parse_bytes(self, raw, expected):
        assert membudget.parse_bytes(raw) == expected

    def test_parse_bytes_rejects_garbage(self):
        with pytest.raises(ValueError):
            membudget.parse_bytes("lots")

    def test_reserve_release_and_snapshot(self):
        membudget.set_budget("1K")
        r = membudget.reserve("t.a", 600)
        snap = membudget.snapshot()
        assert snap["used_bytes"] == 600
        assert snap["components"] == {"t.a": 600}
        with pytest.raises(DeviceBudgetExceeded) as ei:
            membudget.reserve("t.b", 600)
        assert ei.value.nbytes == 600 and ei.value.budget == 1024
        assert membudget.snapshot()["rejected_total"] == 1
        r.release()
        r.release()  # idempotent
        snap = membudget.snapshot()
        assert snap["used_bytes"] == 0 and snap["components"] == {}
        assert snap["peak_bytes"] == 600

    def test_resize_admits_the_delta(self):
        membudget.set_budget(1000)
        r = membudget.reserve("t.grow", 400)
        r.resize(800)
        assert membudget.used() == 800
        with pytest.raises(DeviceBudgetExceeded):
            r.resize(1200)
        # failed grow leaves the reservation unchanged
        assert r.nbytes == 800 and membudget.used() == 800
        r.resize(100)
        assert membudget.used() == 100
        r.release()

    def test_owner_gc_releases(self):
        class Owner:
            pass

        o = Owner()
        membudget.reserve("t.gc", 256, owner=o)
        assert membudget.used() == 256
        del o
        gc.collect()
        assert membudget.used() == 0

    def test_transient_context(self):
        membudget.set_budget(1000)
        with membudget.transient("t.lanes", 900):
            assert membudget.used() == 900
            with pytest.raises(DeviceBudgetExceeded):
                membudget.reserve("t.other", 200)
        assert membudget.used() == 0

    def test_zero_budget_admits_everything(self):
        r = membudget.reserve("t.unlimited", 1 << 50)
        assert membudget.snapshot()["rejected_total"] == 0
        r.release()


class TestBudgetAdmission:
    """The acceptance criterion: over-budget construction rejects
    TYPED at admission instead of dying inside XLA."""

    def test_make_arenas_over_budget_rejects_typed(self):
        from m3_tpu.aggregator.arena import make_arenas

        membudget.set_budget("64K")
        with pytest.raises(DeviceBudgetExceeded):
            make_arenas(4, 4096, 1024, (0.5,), layout="packed")
        with pytest.raises(DeviceBudgetExceeded):
            make_arenas(4, 4096, 1024, (0.5,), layout="f64")
        assert membudget.snapshot()["rejected_total"] >= 2
        membudget.set_budget(0)
        c, g, t = make_arenas(2, 64, 32, (0.5,), layout="packed")
        assert c is not None and g is not None and t is not None

    def test_shard_buffer_over_budget_rejects_typed(self):
        from m3_tpu.storage.buffer import ShardBuffer

        membudget.set_budget("4K")
        with pytest.raises(DeviceBudgetExceeded):
            ShardBuffer(3_600_000_000_000, 4, 4096, 1024)
        membudget.set_budget(0)

    def test_encode_admission_reject_counts_once_breaker_closed(self):
        """An over-budget encode is an ADMISSION reject, not a device
        fault: the lane reservation happens once outside the guard, so
        rejected_total bumps exactly once per call and the encode stage
        breaker never records a failure (a fallback reserving the same
        bytes could never relieve it)."""
        import jax.numpy as jnp

        from m3_tpu.encoding.m3tsz_jax import encode_batch_device

        S, T = 4, 512
        ts = jnp.asarray(
            1_600_000_000_000_000_000
            + np.arange(S * T, dtype=np.int64).reshape(S, T)
            * 10_000_000_000)
        vb = jnp.asarray(
            np.float64(np.arange(S * T).reshape(S, T)).view(np.uint64))
        start = jnp.asarray(
            np.full(S, 1_600_000_000_000_000_000, np.int64))
        valid = jnp.ones((S, T), bool)
        membudget.set_budget("32K")
        with pytest.raises(DeviceBudgetExceeded):
            encode_batch_device(ts, vb, start, valid)
        assert membudget.snapshot()["rejected_total"] == 1
        assert devguard.stage_breaker("encode").state == "closed"
        assert "device.encode.errors" not in str(devguard.counters())

    def test_timer_grow_reject_leaves_arena_usable(self):
        """A budget-rejected sample-buffer grow must not desync the
        host shadow of state.sample_n: batches that FIT afterwards
        still ingest (commit-after-success, the ShardBuffer.write
        pattern)."""
        from m3_tpu.aggregator.arena import make_arenas

        for layout in ("packed", "f64"):
            gc.collect()
            membudget.reset()
            membudget.set_budget(0)
            _, _, timer = make_arenas(2, 8, 32, (0.5,), layout=layout)
            # budget pinned to exactly what is reserved now: any grow
            # rejects, in-capacity ingest still admits
            membudget.set_budget(membudget.used())
            big = 128  # > sample_capacity -> _grow -> reject
            with pytest.raises(DeviceBudgetExceeded):
                timer.ingest(
                    np.zeros(big, np.int32), np.zeros(big, np.int32),
                    np.ones(big), np.zeros(big, np.int64))
            for _ in range(2):  # re-reject must not creep the shadow
                with pytest.raises(DeviceBudgetExceeded):
                    timer.ingest(
                        np.zeros(big, np.int32), np.zeros(big, np.int32),
                        np.ones(big), np.zeros(big, np.int64))
            n = 16  # fits sample_capacity=32 — must succeed
            timer.ingest(np.zeros(n, np.int32), np.zeros(n, np.int32),
                         np.ones(n), np.zeros(n, np.int64))
            assert int(np.asarray(timer.state.sample_n)[0]) == n
            assert timer._sample_n_host[0] == n
            membudget.set_budget(0)

    def test_footprint_formulas_track_state_nbytes(self):
        """The admission constants stay honest: each formula must be
        within 2x of (and at least) the live lanes' actual bytes."""
        from m3_tpu.aggregator.arena import make_arenas

        for layout in ("packed", "f64"):
            arenas = make_arenas(3, 128, 64, (0.5, 0.99), layout=layout)
            names = ("counter", "gauge", "timer")
            for name, arena in zip(names, arenas):
                actual = sum(
                    np.asarray(getattr(arena.state, f)).nbytes
                    for f in arena.state._fields)
                if name == "counter":
                    est = membudget.counter_arena_bytes(layout, 3, 128)
                elif name == "gauge":
                    est = membudget.gauge_arena_bytes(layout, 3, 128)
                else:
                    est = membudget.timer_arena_bytes(layout, 3, 128, 64)
                assert est >= actual, (layout, name, est, actual)
                assert est <= 2 * actual + 4096, (layout, name, est, actual)


# ---------------------------------------------------------------------------
# Hot-path integration: arenas + storage buffer degrade bit-identically
# ---------------------------------------------------------------------------


class TestArenaFallback:
    def _ingest(self, layout):
        import jax.numpy as jnp

        from m3_tpu.aggregator.arena import make_arenas

        counter, gauge, timer = make_arenas(2, 8, 32, (0.5,), layout=layout)
        w = jnp.asarray(np.zeros(6, np.int32))
        s = jnp.asarray(np.array([0, 1, 2, 0, 1, 2], np.int32))
        v = jnp.asarray(np.array([1, 2, 3, 4, 5, 6], np.float64))
        t = jnp.asarray(np.arange(6, dtype=np.int64) + 1)
        counter.ingest(w, s, v, t)
        gauge.ingest(w, s, v, t)
        timer.ingest(w, s, v, t)
        return counter, gauge, timer

    @pytest.mark.parametrize("layout", ["f64", "packed"])
    def test_injected_fault_degrades_bit_identically(self, layout):
        # control: no faults
        ctl = self._ingest(layout)
        devguard.reset_stages()
        reset_registry()
        # faulted: every arena.ingest dispatch fails typed → fallback
        with fault.armed("device.dispatch", "error"):
            deg = self._ingest(layout)
        c = devguard.counters()
        assert c["device.arena.ingest.errors.oom"] == 3
        assert c["device.arena.ingest.fallback_calls"] == 3
        for a, b in zip(ctl, deg):
            for f in a.state._fields:
                np.testing.assert_array_equal(np.asarray(getattr(a.state, f)),
                                              np.asarray(getattr(b.state, f)),
                                              err_msg=f"{layout}.{f}")

    @pytest.mark.parametrize("layout", ["f64", "packed"])
    def test_consume_guard_covers_window_drain(self, layout):
        ctl_counter, _, _ = self._ingest(layout)
        devguard.reset_stages()
        reset_registry()
        with fault.armed("device.dispatch", "error"):
            out = ctl_counter.consume(0)
        c = devguard.counters()
        assert c["device.arena.consume.fallback_calls"] == 1
        assert out is not None


class TestBufferFallback:
    BLOCK = 3_600_000_000_000

    def _buffer(self):
        from m3_tpu.storage.buffer import ShardBuffer

        return ShardBuffer(self.BLOCK, 4, 64, 32)

    def test_host_drain_parity(self):
        """The degraded-mode numpy drain is bit-identical to the device
        sort (same (slot, ts, arrival-desc) order, same first mask)."""
        b = self._buffer()
        rng = np.random.default_rng(7)
        slots = rng.integers(0, 8, 40).astype(np.int32)
        ts = (rng.integers(0, 50, 40) * 1_000_000).astype(np.int64)
        vals = rng.normal(size=40)
        b.write(slots, ts, vals, {0})
        row = b.open_blocks[0]
        dev = b._drain_row(row)
        host = b._host_drain(row)
        for d, h, name in zip(dev, host, ("slot", "ts", "val", "first")):
            np.testing.assert_array_equal(np.asarray(d), np.asarray(h),
                                          err_msg=name)

    def test_degraded_append_stages_on_host_and_recovers(self):
        b = self._buffer()
        slots = np.arange(5, dtype=np.int32)
        ts = np.full(5, 1_000_000, np.int64)
        vals = np.ones(5)
        with fault.armed("device.dispatch", "error"):
            ncold = b.write(slots, ts, vals, {0})
        assert ncold == 0  # warm samples: degraded, NOT cold-counted
        assert b.degraded_staged == 5
        # staged on the host overflow lists (snapshot-covered, merged
        # by the post-seal cold flush) — and the ring got nothing
        assert 0 in b.cold and len(b.cold[0][0][0]) == 5
        assert int(np.asarray(b.state.n).sum()) == 0
        c = devguard.counters()
        assert c["device.storage.buffer_append.fallback_calls"] == 1
        # disarmed: the device ring serves again
        b.write(slots, ts + 1, vals, {0})
        assert int(np.asarray(b.state.n).sum()) == 5
        assert b.degraded_staged == 5

    def test_over_budget_grow_degrades_instead_of_oom(self):
        from m3_tpu.x.membudget import buffer_bytes

        b = self._buffer()
        # allow the current ring, refuse any growth
        membudget.set_budget(membudget.used() + 64)
        n = b.sample_capacity + 8  # forces _grow inside the guarded append
        slots = np.zeros(n, np.int32)
        ts = np.arange(n, dtype=np.int64)
        vals = np.ones(n)
        b.write(slots, ts, vals, {0})
        # the batch staged on the host path, ring capacity unchanged
        assert b.degraded_staged == n
        assert b.sample_capacity == 64
        assert buffer_bytes(4, 64) == b._mem.nbytes
        membudget.set_budget(0)


class TestCodecFallback:
    def test_encode_falls_back_byte_identical(self):
        import jax.numpy as jnp

        from m3_tpu.encoding.m3tsz_jax import encode_batch_device

        S, T = 2, 16
        ts = jnp.asarray(
            1_600_000_000_000_000_000
            + np.arange(S * T, dtype=np.int64).reshape(S, T) * 10_000_000_000)
        vb = jnp.asarray(
            np.float64(np.arange(S * T).reshape(S, T)).view(np.uint64))
        start = jnp.asarray(np.full(S, 1_600_000_000_000_000_000, np.int64))
        valid = jnp.ones((S, T), bool)
        ctl = encode_batch_device(ts, vb, start, valid)
        devguard.reset_stages()
        reset_registry()
        with fault.armed("device.dispatch", "error", n=1):
            deg = encode_batch_device(ts, vb, start, valid)
        assert devguard.counters()["device.encode.fallback_calls"] == 1
        np.testing.assert_array_equal(np.asarray(ctl["words"]),
                                      np.asarray(deg["words"]))
        np.testing.assert_array_equal(np.asarray(ctl["total_bits"]),
                                      np.asarray(deg["total_bits"]))

    def test_decode_falls_back_bit_identical(self):
        import jax.numpy as jnp

        from m3_tpu.encoding.m3tsz_jax import (
            decode_batch_device, encode_batch_device)

        S, T = 2, 16
        ts = jnp.asarray(
            1_600_000_000_000_000_000
            + np.arange(S * T, dtype=np.int64).reshape(S, T) * 10_000_000_000)
        vb = jnp.asarray(
            np.float64(np.arange(S * T).reshape(S, T)).view(np.uint64))
        start = jnp.asarray(np.full(S, 1_600_000_000_000_000_000, np.int64))
        valid = jnp.ones((S, T), bool)
        enc = encode_batch_device(ts, vb, start, valid)
        ctl = decode_batch_device(enc["words"], enc["total_bits"], T + 2)
        devguard.reset_stages()
        reset_registry()
        with fault.armed("device.dispatch", "error", n=1):
            deg = decode_batch_device(enc["words"], enc["total_bits"], T + 2)
        assert devguard.counters()["device.decode.fallback_calls"] == 1
        names = ("ts", "payload", "meta", "err", "prec", "ann")
        for name, a, b in zip(names, ctl, deg):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)


# ---------------------------------------------------------------------------
# The live exception type (slow: fresh JAX subprocess, real OOM)
# ---------------------------------------------------------------------------


_OOM_SCRIPT = r"""
import json, sys
import jax.numpy as jnp
from m3_tpu.x import devguard

out = {}
try:
    jnp.zeros((1 << 45,), dtype=jnp.uint8).block_until_ready()
    out["raised"] = False
except BaseException as e:
    cls = devguard.classify(e)
    out = {
        "raised": True,
        "type": type(e).__name__,
        "classified": None if cls is None else cls.__name__,
        "msg": str(e)[:160],
    }

# and the guard end-to-end: the real OOM must degrade, not crash
def primary():
    return jnp.zeros((1 << 45,), dtype=jnp.uint8).block_until_ready()

out["guarded"] = devguard.run_guarded("t.realoom", primary, lambda: "fb")
out["counters"] = devguard.counters()
print(json.dumps(out))
"""


@pytest.mark.slow
class TestRealOOM:
    def test_live_xla_cpu_oom_classifies_as_device_oom(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _OOM_SCRIPT], capture_output=True,
            text=True, timeout=300, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["raised"], "32TiB allocation unexpectedly succeeded"
        # pin the LIVE class name against the classifier's vocabulary
        assert out["type"] in ("XlaRuntimeError", "JaxRuntimeError"), out
        assert out["classified"] == "DeviceOOM", out
        assert out["guarded"] == "fb"
        assert out["counters"]["device.t.realoom.errors.oom"] == 1
