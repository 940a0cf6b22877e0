"""Device-boundary guard: typed device errors + per-stage fallback.

ROADMAP item 1 moves the whole ingest hot path into device-resident
buffers, which turns every device failure — XLA OOM, compile error,
lost device, poisoned arena state — into a process crash unless the
device boundary gets the same detect → degrade → keep-serving →
recover contract the wire (PR 1), disk (PR 3), and query (PR 5) edges
already have.  This module is that contract's seam:

* **Typed errors** — :class:`DeviceError` hierarchy mirroring
  ``persist.CorruptionError``'s role for the disk edge:
  :class:`DeviceOOM` (RESOURCE_EXHAUSTED / allocation failures),
  :class:`CompileFailure` (XLA/Mosaic compilation),
  :class:`DeviceLost` (runtime/transport to the accelerator gone),
  :class:`DeviceStateError` (resident state unusable — e.g. the packed
  arena's sticky overflow flag).  :func:`classify` maps raw jax/XLA
  exception *shapes* (class name + status substrings — jaxlib moves the
  class between releases, the grpc-style status vocabulary is stable)
  to these types; anything it cannot place is NOT a device error and
  propagates raw (a programming bug must never trip a breaker).

* **The guarded seam** — :func:`run_guarded(stage, primary, fallback)`
  wraps every hot-path device entry point (arena ingest/consume, the
  series buffer append/drain, ``encode_batch_device`` /
  ``decode_batch_device`` and their sharded variants).  A classified
  failure is counted per (stage, kind), recorded on the stage's
  circuit breaker (``x.breaker`` with ``kind="stage"``), and the SAME
  batch re-runs through ``fallback`` — the stage's host/jnp
  implementation riding the already-static seams (``M3_ENCODE_PLACE``,
  ``M3_DECODE_CHAINS`` resolve in host wrappers since PR 7, so the
  fallback choice is a static argument: zero retraces, bit-parity
  already pinned; a stage with one formulation, as the arenas',
  re-runs the same program with the faultpoints skipped).  Once the breaker trips open
  the primary is skipped entirely; after the cool-down ONE half-open
  probe re-tries the device path and success closes the breaker.

* **Faultpoints** — ``device.compile`` (fired before a stage's first
  device call in this process), ``device.dispatch`` (before every
  device call), ``device.transfer`` (at declared device→host
  materialization boundaries, via :func:`transfer_point`).  Error-mode
  triggers raise the class a real failure at that boundary would
  classify to (compile → CompileFailure, dispatch → DeviceOOM,
  transfer → DeviceLost), so synthetic OOM/compile failures are
  injectable on LIVE nodes through ``POST /api/v1/debug/faults`` — no
  real TPU needed to exercise any of this.  Faultpoints fire ONLY on
  the primary (device) path: the fallback is by definition not the
  device boundary, which is what makes the zero-acked-loss dtest
  meaningful on a CPU-only box.

Happy-path cost is observation only: one registry dict lookup per
faultpoint (free while nothing is armed) plus counter/breaker
bookkeeping — no device work, no transfers, no retraces (``cli hops
--check`` against PIPELINE_r09.json is the enforcement hook).

Stage-breaker knobs: ``M3_DEVICE_BREAKER_FAILURES`` (consecutive
classified failures to trip, default 5) and
``M3_DEVICE_BREAKER_RESET_S`` (open → half-open cool-down, default 10)
read on the HOST at stage creation; :func:`configure` is the config
plumbing (`device:` section) and applies to stages created after it —
the same create-time semantics as ``breaker_for``.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict

from m3_tpu.instrument import tracing
from m3_tpu.x import fault
from m3_tpu.x.breaker import BreakerOpenError, breaker_for

__all__ = [
    "DeviceError", "DeviceOOM", "CompileFailure", "DeviceLost",
    "DeviceStateError", "classify", "run_guarded", "transfer_point",
    "configure", "counters", "reset_counters", "reset_stages", "status",
    "stage_breaker", "force_fallback", "fallback_forced",
]


class DeviceError(RuntimeError):
    """A classified accelerator-boundary failure.  ``RuntimeError`` (not
    OSError) so the wire retry classifier never treats a device fault
    as a transport blip to retry into."""

    kind = "device"

    def __init__(self, stage: str, message: str = "",
                 cause: BaseException | None = None):
        detail = message or (f"{type(cause).__name__}: {cause}" if cause
                             else "")
        super().__init__(
            f"device {self.kind} at stage {stage!r}"
            + (f": {detail}" if detail else ""))
        self.stage = stage
        self.cause = cause


class DeviceOOM(DeviceError):
    """Device memory exhausted (RESOURCE_EXHAUSTED / failed allocation)."""

    kind = "oom"


class CompileFailure(DeviceError):
    """XLA/Mosaic compilation failed for this program."""

    kind = "compile"


class DeviceLost(DeviceError):
    """The accelerator (or its runtime) went away mid-flight."""

    kind = "lost"


class DeviceStateError(DeviceError):
    """Device-resident state is unusable (poisoned arena, failed
    restore) — the caller should restore from checkpoint or reset."""

    kind = "state"


# Classifier vocabulary: grpc-style status words + the stable message
# fragments jax/XLA emit.  Matched lowercase, FIRST family wins — OOM
# before compile (a compile-time RESOURCE_EXHAUSTED is still an OOM).
_OOM_PAT = ("resource_exhausted", "out of memory", "failed to allocate",
            "allocation failure", "oom")
_COMPILE_PAT = ("compil",  # compile / compilation / compiler
                "mosaic", "unimplemented", "unsupported hlo",
                "invalid_argument")
_LOST_PAT = ("unavailable", "device lost", "data_loss", "data loss",
             "aborted", "connection to device", "device disconnected",
             "failed_precondition")
# Host-raised device-state shapes (not XlaRuntimeError): the packed
# arena's sticky overflow raise, and jax's deleted-buffer error (a
# donated input invalidated by a failed dispatch — the state is gone).
_STATE_HOST_PAT = ("overflow-pool error", "arena state",
                   "array has been deleted")

_XLA_CLASS_NAMES = ("XlaRuntimeError", "JaxRuntimeError")


def classify(exc: BaseException) -> type | None:
    """The DeviceError subclass a raw exception maps to, or None when
    it is not a device failure (programming errors — tracing
    TypeErrors, shape ValueErrors — propagate raw and never count
    toward a stage breaker)."""
    if isinstance(exc, DeviceError):
        return type(exc)
    name = type(exc).__name__
    msg = str(exc).lower()
    if name in _XLA_CLASS_NAMES or any(
            base.__name__ in _XLA_CLASS_NAMES
            for base in type(exc).__mro__):
        if any(p in msg for p in _OOM_PAT):
            return DeviceOOM
        if any(p in msg for p in _COMPILE_PAT):
            return CompileFailure
        if any(p in msg for p in _LOST_PAT):
            return DeviceLost
        # An XlaRuntimeError we cannot place more precisely: the device
        # answered with a runtime error about ITS state, not a Python
        # bug — degrade, don't crash.
        return DeviceStateError
    if isinstance(exc, RuntimeError) and any(
            p in msg for p in _STATE_HOST_PAT):
        return DeviceStateError
    return None


# ---------------------------------------------------------------------------
# Stage registry + counters (the x/fault.py shape: thread-safe, cheap,
# counters survive everything short of reset_counters()).
# ---------------------------------------------------------------------------

_FAILURES = int(os.environ.get("M3_DEVICE_BREAKER_FAILURES", "") or 5)
_RESET_S = float(os.environ.get("M3_DEVICE_BREAKER_RESET_S", "") or 10.0)

_lock = threading.Lock()
_counters: Dict[str, int] = {}
_compiled: Dict[str, bool] = {}  # stage -> first device call done
_forced = False  # controller-imposed evacuation: all stages on fallback


def configure(failures: int | None = None,
              reset_s: float | None = None) -> None:
    """Config plumbing for the stage-breaker knobs.  Applies to stage
    breakers created AFTER the call (breaker_for create-time semantics)
    — run_node calls this before any guarded stage runs."""
    global _FAILURES, _RESET_S
    if failures is not None:
        _FAILURES = int(failures)
    if reset_s is not None:
        _RESET_S = float(reset_s)


def force_fallback(on: bool) -> None:
    """Controller-imposed device evacuation (the x/controller
    ``device_fallback`` actuator — the ONLY legal caller outside
    tests; the actuator-typed lint rule enforces that).

    Engaging sets the module flag AND force-opens every EXISTING stage
    breaker, so in-flight guard decisions and /metrics breaker state
    agree with the evacuation.  Disengaging clears only the flag: the
    breakers recover through their own half-open probes — forced
    entry, earned exit (x/breaker's half-open discipline)."""
    global _forced
    with _lock:
        _forced = bool(on)
    if on:
        from m3_tpu.x.breaker import all_breakers

        for name, br in all_breakers().items():
            if name.startswith("stage:"):
                br.force_open()


def fallback_forced() -> bool:
    with _lock:
        return _forced


def _bump(key: str, n: int = 1) -> None:
    with _lock:
        _counters[key] = _counters.get(key, 0) + n


def counters() -> Dict[str, int]:
    """Flat ``{"device.<stage>.calls": n, ".fallback_calls": n,
    ".errors.<kind>": n}`` — mirrored onto /metrics by
    ``m3_tpu.x.register_metrics``."""
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        _counters.clear()


def reset_stages() -> None:
    """Test hygiene: forget per-stage compile markers and counters.
    (Stage breakers live in the x.breaker registry — reset that too
    for full isolation.)"""
    global _forced
    with _lock:
        _counters.clear()
        _compiled.clear()
        _forced = False


def stage_breaker(stage: str):
    """The process-wide breaker for a guarded stage (shared via the
    x.breaker registry under ``stage:<name>``, kind="stage" — surfaces
    as ``breaker_state{kind="stage"}`` on /metrics)."""
    return breaker_for(f"stage:{stage}", failure_threshold=_FAILURES,
                       reset_timeout_s=_RESET_S, kind="stage")


def _fire_faultpoints(stage: str) -> None:
    """Evaluate the device faultpoints for one primary-path call,
    raising the typed class a real failure at that boundary would
    classify to."""
    if not _compiled.get(stage):
        try:
            fault.fire("device.compile")
        except fault.FaultInjected as e:
            raise CompileFailure(stage, cause=e) from e
        with _lock:
            _compiled[stage] = True
    try:
        fault.fire("device.dispatch")
    except fault.FaultInjected as e:
        raise DeviceOOM(stage, cause=e) from e


def transfer_point(stage: str) -> None:
    """The ``device.transfer`` faultpoint: call at a declared
    device→host materialization boundary INSIDE a guarded primary, so
    an injected (or classified real) transfer failure counts against
    the stage and falls back like any other device error."""
    try:
        fault.fire("device.transfer")
    except fault.FaultInjected as e:
        raise DeviceLost(stage, cause=e) from e


def run_guarded(stage: str, primary: Callable[[], object],
                fallback: Callable[[], object] | None = None):
    """:func:`_run_guarded` under the span ``device.<stage>``: the HOST
    time of one guarded device call, fallback included (staging,
    dispatch, any blocking transfer — not the device's own time, which
    the profiler's trace has).  Every device entry point of the served
    path passes here, so this one span sees every dispatch."""
    with tracing.span(tracing.Tracepoint.DEVICE + stage):
        return _run_guarded(stage, primary, fallback)


def _run_guarded(stage: str, primary: Callable[[], object],
                 fallback: Callable[[], object] | None = None):
    """``primary()`` behind the stage's device guard.

    Closed breaker (or no fallback): faultpoints fire, ``primary``
    runs; a classified failure is counted + recorded on the breaker,
    then the SAME batch re-runs through ``fallback`` (or the typed
    error raises when there is none — admission/typed-reject shape).
    Open breaker with a fallback: ``primary`` is skipped entirely
    until the half-open probe.  Unclassified exceptions propagate raw.

    ``primary``/``fallback`` are zero-arg closures so the static-seam
    choice (place/chains/impl) rides as an ordinary static argument of
    the jitted callee — nothing retraces, nothing reads env under a
    tracer."""
    br = stage_breaker(stage)
    on_device = True
    if fallback is not None:
        if fallback_forced():
            # Controller-imposed evacuation: skip the primary without
            # consuming a half-open probe slot.
            on_device = False
        else:
            try:
                br.allow()
            except BreakerOpenError:
                on_device = False
    if on_device:
        try:
            _fire_faultpoints(stage)
            result = primary()
        except BaseException as e:
            cls = classify(e)
            if cls is None:
                # Not a device failure: the device answered and OUR
                # code raised.  Record success (CircuitBreaker.call's
                # app-error rule) so a half-open probe that hit a
                # Python bug releases its probe slot instead of
                # wedging the breaker half-open forever.
                if fallback is not None:
                    br.record_success()
                raise
            err = e if isinstance(e, DeviceError) else cls(stage, cause=e)
            _bump(f"device.{stage}.errors.{err.kind}")
            br.record_failure()
            if fallback is None:
                raise err from (e if err is not e else None)
        else:
            br.record_success()
            _bump(f"device.{stage}.calls")
            return result
    _bump(f"device.{stage}.fallback_calls")
    try:
        return fallback()
    except BaseException as e:
        # A failure that persists through the fallback raises TYPED to
        # the engine (e.g. jax's deleted-buffer error when the primary
        # donated its input before dying) — but never touches the
        # breaker: it tracks the device path, and this is the host one.
        cls = classify(e)
        if cls is None:
            raise
        err = e if isinstance(e, DeviceError) else cls(stage, cause=e)
        _bump(f"device.{stage}.errors.{err.kind}")
        raise err from (e if err is not e else None)


def status() -> dict:
    """The /health ``device`` document: per-stage breaker state +
    counters (stages appear after their first guarded call)."""
    from m3_tpu.x.breaker import all_breakers

    cnt = counters()
    stages: Dict[str, dict] = {}
    for key, n in cnt.items():
        # device.<stage>.<what...> — stage names themselves contain
        # dots (arena.ingest), so split on the KNOWN suffixes
        rest = key[len("device."):]
        for suffix in ("calls", "fallback_calls"):
            if rest.endswith("." + suffix):
                st = rest[: -len(suffix) - 1]
                stages.setdefault(st, {})[suffix] = n
                break
        else:
            st, _, kind = rest.rpartition(".errors.")
            if st:
                stages.setdefault(st, {}).setdefault(
                    "errors", {})[kind] = n
    for name, br in all_breakers().items():
        if name.startswith("stage:"):
            stages.setdefault(name[len("stage:"):], {})["breaker"] = br.state
    out = {"stages": stages}
    if fallback_forced():
        out["forced_fallback"] = True
    return out
