"""Runtime retrace/transfer sanitizer: the dynamic half of jaxlint.

The static jax families (``m3_tpu/x/lint/jaxlint.py``) catch the
*patterns* that cause silent recompiles and hidden host↔device copies;
this module catches the *events*.  A jitted function that retraces per
call — a Python scalar riding a shape-affecting position, a weak-type
flip, an unhashable static — costs 100-10000x its steady-state time and
shows up in a benchmark as "the kernel got slower", which is how perf
regressions hide (the exact failure mode ISSUE 7 exists for).  While
armed:

* **Compile counting** — every XLA compile in the process is observed
  through the ``jax_log_compiles`` seam (a logging handler on the
  ``Compiling <fn> with global shapes and types [...]`` record jax's
  pjit path emits once per real cache miss) and counted per function
  name, with the abstract argument shapes/dtypes of each compile
  recorded.  When a function compiles past its budget the handler
  raises :class:`RetraceError` *inside the offending call* — the
  traceback points at the callsite and the message carries every
  distinct signature seen, so the shape/dtype that churned is named,
  not guessed.  Because the seam observes the process, functions jitted
  BEFORE arming are covered too (unlike a ``jax.jit`` wrapper alone).
* **jit/pjit wrapping** — while armed, ``jax.jit``/``jax.pjit`` are
  swapped for a transparent factory that registers each new function's
  declared budget (``@tracewatch.retrace_budget(n)``) before delegating
  to the real jit; the returned object IS jax's jitted callable
  (``__wrapped__``, ``clear_cache``, ``lower`` all intact).
* **Transfer guard** — :func:`no_transfers` arms ``jax.transfer_guard``
  ("disallow") for real device backends AND a tracewatch-level guard
  that intercepts ``jax.Array.__array__`` (the ``np.asarray`` /
  ``np.array`` device→host seam) and ``jax.device_get``, raising
  :class:`TransferError` with the array's shape/dtype.  The software
  half exists because the CPU backend has no device boundary, so
  ``jax.transfer_guard`` never fires under ``JAX_PLATFORMS=cpu`` — the
  tier the test suite runs on.  :func:`allow_transfers` re-opens the
  guard for a declared host boundary inside a guarded region.

Arming (mirrors ``x/lockcheck.py``):

* code — ``tracewatch.install()`` / ``uninstall()`` (the race/dtest
  conftest fixture; bench children install in record mode);
* env — ``M3_TRACEWATCH=1`` arms at import with fail-fast raises,
  ``M3_TRACEWATCH=record`` counts without raising (``m3_tpu.x``
  imports this module, so dtest node subprocesses inherit arming
  through their environment exactly like lockcheck/faultpoints).
  ``M3_TRACEWATCH_BUDGET`` overrides the default per-function compile
  budget (default 32 — roomy: legit recompiles happen per distinct
  shape, and a shape-churning callsite blows past it immediately).

Honesty notes:

* Budgets are per *function name* as jax reports it: two same-named
  lambdas share a count.  Name real hot-path functions.
* A persistent-compilation-cache hit still counts as a compile here:
  the trace ran and a new executable was installed — the per-shape
  event the sanitizer exists to surface.  This module counts events
  and keeps no times: what a program cost (seconds of trace, lower,
  backend compile or cache read), whether the cache answered, and which
  request waited for it is in the compile log of the process's tracer
  (``m3_tpu/instrument/tracing.py``: ``Tracer.compile_log()``, the
  ``runtime.compile`` span, ``jit_compile_*`` on ``/metrics``).
* The ``__array__`` patch is process-global while installed but checks
  a thread-local arm flag, so only threads inside ``no_transfers()``
  are guarded.
"""

from __future__ import annotations

import contextlib
import logging
import os
import re
import threading
from dataclasses import dataclass, field
from typing import Dict, List

__all__ = [
    "RetraceError", "TransferError", "RetraceFinding", "install",
    "uninstall", "installed", "reset", "compiles", "total_compiles",
    "compile_signatures", "findings", "set_budget", "retrace_budget",
    "no_transfers", "allow_transfers", "snapshot", "retraces_since",
]

DEFAULT_BUDGET = 32

# The record reads "Compiling jit(fn) with global shapes and types
# (ShapedArray(f64[2,2]), ShapedArray(i32[5])). Argument mapping: (...)".
# The avals tuple carries one ')' per array argument, so its end is
# anchored on the ". Argument mapping" that follows it, not on the
# first ')'.
_COMPILE_RE = re.compile(r"^Compiling (?:jit\()?([^\s]+?)\)? with global "
                         r"shapes and types (\(.*?\))\. Argument mapping",
                         re.S)

_installed = False
_raise_on_violation = True
_mu = threading.Lock()
_counts: Dict[str, int] = {}
_signatures: Dict[str, List[str]] = {}
_budgets: Dict[str, int] = {}
_total = 0
_findings: List["RetraceFinding"] = []

_tls = threading.local()

_ORIG = {}


class RetraceError(RuntimeError):
    """A jitted function compiled past its retrace budget.  Raised
    inside the offending call, carrying every distinct argument
    signature the function compiled for."""


class TransferError(RuntimeError):
    """A device→host transfer happened inside a ``no_transfers()``
    guarded region (e.g. np.asarray on a device array in a timed
    loop)."""


@dataclass
class RetraceFinding:
    """One budget violation: ``name`` compiled ``count`` times against
    a budget of ``budget``; ``signatures`` lists the distinct abstract
    shapes/dtypes observed — the churning axis is the one that differs
    between entries."""

    name: str
    count: int
    budget: int
    signatures: List[str] = field(default_factory=list)

    def __str__(self) -> str:
        sigs = "\n  ".join(self.signatures) or "<none recorded>"
        return (
            f"{self.name} compiled {self.count}x (budget {self.budget}) — "
            f"a shape/dtype/static is churning per call.  Signatures "
            f"seen:\n  {sigs}\n"
            f"Fix the unstable axis (pad shapes, mark the argument "
            f"static, pin the dtype) or declare a budget with "
            f"tracewatch.set_budget({self.name!r}, n)."
        )


def _default_budget() -> int:
    try:
        return max(1, int(os.environ.get("M3_TRACEWATCH_BUDGET",
                                         str(DEFAULT_BUDGET))))
    except ValueError:
        return DEFAULT_BUDGET


class _CompileHandler(logging.Handler):
    """Counts the one-per-cache-miss pxla "Compiling <fn> ..." record.

    Raising from ``emit`` is deliberate: ``Logger.callHandlers`` does
    not catch handler exceptions (the swallowing convention lives in
    the stdlib emit() implementations), so a budget violation
    propagates out of jax's own logging call and surfaces AT the
    callsite that triggered the compile — fail fast, like lockcheck
    raising before the deadlocking acquire."""

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if not m:
            return
        name, avals = m.group(1), m.group(2)
        global _total
        with _mu:
            _total += 1
            n = _counts[name] = _counts.get(name, 0) + 1
            sigs = _signatures.setdefault(name, [])
            if avals not in sigs:
                sigs.append(avals)
            budget = _budgets.get(name, _default_budget())
            over = n > budget
            if over:
                finding = RetraceFinding(name, n, budget, list(sigs))
                _findings.append(finding)
        if over and _raise_on_violation:
            raise RetraceError(str(finding))


_handler = _CompileHandler(level=logging.WARNING)
# The one logger that emits the per-cache-miss record.
_PXLA_LOGGER = "jax._src.interpreters.pxla"


# numpy module entry points wrapped by the guard: np.asarray on a jax
# array does NOT route through a patchable ``__array__`` (numpy takes
# the C buffer-protocol fast path), so the interception must happen at
# the numpy call itself.  Each wrapper delegates untouched unless the
# calling thread is inside no_transfers() AND the operand is a jax
# device array.
_NP_SEAMS = ("asarray", "array", "ascontiguousarray", "asanyarray")


def _patch_array_seam() -> None:
    """Swap in the transfer-guard seams (idempotent)."""
    import jax
    import numpy as np

    if "device_get" in _ORIG:
        return
    _ORIG["device_get"] = jax.device_get

    def guarded_device_get(x):
        _check_transfer("jax.device_get", x)
        return _ORIG["device_get"](x)

    jax.device_get = guarded_device_get

    try:
        import jaxlib.xla_extension as xe

        _ORIG["_array_cls"] = xe.ArrayImpl
    except Exception:  # pragma: no cover - exotic jaxlib layout
        _ORIG["_array_cls"] = jax.Array

    def _wrap_np(name: str):
        orig = getattr(np, name)

        def guarded(a, *args, **kw):
            if (getattr(_tls, "guard_depth", 0) > 0
                    and isinstance(a, _ORIG["_array_cls"])):
                _check_transfer(f"np.{name}", a)
            return orig(a, *args, **kw)

        guarded.__name__ = name
        guarded.__wrapped__ = orig
        return orig, guarded

    for name in _NP_SEAMS:
        orig, guarded = _wrap_np(name)
        _ORIG[f"np.{name}"] = orig
        setattr(np, name, guarded)

    # ``.item()``/dunder-driven conversions still route through the
    # per-class __array__ where numpy's fast path does not apply.
    try:
        arr = _ORIG["_array_cls"]
        _ORIG["__array__"] = arr.__array__

        def guarded_array(self, *a, **kw):
            _check_transfer("__array__", self)
            return _ORIG["__array__"](self, *a, **kw)

        arr.__array__ = guarded_array
    except Exception:  # pragma: no cover
        _ORIG.pop("__array__", None)


def _unpatch_array_seam() -> None:
    import jax
    import numpy as np

    if "device_get" in _ORIG:
        jax.device_get = _ORIG.pop("device_get")
    for name in _NP_SEAMS:
        orig = _ORIG.pop(f"np.{name}", None)
        if orig is not None:
            setattr(np, name, orig)
    if "__array__" in _ORIG:
        _ORIG["_array_cls"].__array__ = _ORIG.pop("__array__")
    _ORIG.pop("_array_cls", None)


def _check_transfer(kind: str, x) -> None:
    if getattr(_tls, "guard_depth", 0) <= 0:
        return
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", "?")
    desc = f"{dtype}{list(shape)}" if shape is not None else repr(type(x))
    raise TransferError(
        f"device->host transfer ({kind}) of {desc} inside a "
        f"no_transfers() region — move it out of the timed/guarded "
        f"section or wrap the host boundary in "
        f"tracewatch.allow_transfers()")


def _wrap_jit_factories() -> None:
    import jax

    if "jit" in _ORIG:
        return
    _ORIG["jit"] = jax.jit
    _ORIG["pjit"] = getattr(jax, "pjit", None)

    def _register(fun) -> None:
        budget = getattr(fun, "_tracewatch_budget", None)
        if budget is not None:
            name = getattr(fun, "__name__", None)
            if name:
                with _mu:
                    _budgets[name] = int(budget)

    def watched_jit(fun=None, **kw):
        if fun is None:  # jax.jit(static_argnames=...) usage
            def deco(f):
                _register(f)
                return _ORIG["jit"](f, **kw)
            return deco
        _register(fun)
        return _ORIG["jit"](fun, **kw)

    jax.jit = watched_jit
    if _ORIG["pjit"] is not None:
        def watched_pjit(fun=None, **kw):
            if fun is None:
                def deco(f):
                    _register(f)
                    return _ORIG["pjit"](f, **kw)
                return deco
            _register(fun)
            return _ORIG["pjit"](fun, **kw)

        jax.pjit = watched_pjit


def _unwrap_jit_factories() -> None:
    import jax

    if "jit" in _ORIG:
        jax.jit = _ORIG.pop("jit")
        pjit = _ORIG.pop("pjit")
        if pjit is not None:
            jax.pjit = pjit


def retrace_budget(n: int):
    """Decorator declaring a per-function compile budget, read by the
    armed jit factory: ``@tracewatch.retrace_budget(2)`` above the
    ``@jax.jit``-decorated def.  Inert when tracewatch is not armed."""
    def deco(fun):
        fun._tracewatch_budget = int(n)
        name = getattr(fun, "__name__", None)
        if name:
            with _mu:
                _budgets[name] = int(n)
        return fun
    return deco


def set_budget(name: str, n: int) -> None:
    """Declare the compile budget for the jit-reported function name."""
    with _mu:
        _budgets[name] = int(n)


def install(raise_on_violation: bool = True) -> None:
    """Arm the sanitizer: count every compile, enforce budgets, swap
    the jit factories, and stage the transfer-guard seams.  Idempotent."""
    global _installed, _raise_on_violation
    import jax

    _raise_on_violation = raise_on_violation
    if _installed:
        return
    _ORIG["log_compiles"] = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    pxla = logging.getLogger(_PXLA_LOGGER)
    pxla.addHandler(_handler)
    # jax_log_compiles also flips the dispatch module's per-phase
    # timing logs ("Finished tracing + transforming ...") to WARNING —
    # 3+ stderr lines per compile that nobody consumes and that drown
    # the armed process' real output (bench stage logs, dtest node
    # stderr).  Only the pxla "Compiling" record feeds the counter:
    # quiet the dispatch logger and keep the pxla record from
    # propagating to the root last-resort printer while armed.
    dispatch = logging.getLogger("jax._src.dispatch")
    _ORIG["dispatch_level"] = dispatch.level
    dispatch.setLevel(logging.ERROR)
    _ORIG["pxla_propagate"] = pxla.propagate
    pxla.propagate = False
    _wrap_jit_factories()
    _patch_array_seam()
    _installed = True


def uninstall() -> None:
    """Disarm and restore every seam (counters/findings survive for
    inspection; ``reset()`` clears them)."""
    global _installed
    if not _installed:
        return
    import jax

    pxla = logging.getLogger(_PXLA_LOGGER)
    pxla.removeHandler(_handler)
    if "pxla_propagate" in _ORIG:
        pxla.propagate = _ORIG.pop("pxla_propagate")
    if "dispatch_level" in _ORIG:
        logging.getLogger("jax._src.dispatch").setLevel(
            _ORIG.pop("dispatch_level"))
    if "log_compiles" in _ORIG:
        jax.config.update("jax_log_compiles", _ORIG.pop("log_compiles"))
    _unwrap_jit_factories()
    _unpatch_array_seam()
    _installed = False


def installed() -> bool:
    return _installed


def reset() -> None:
    """Clear counters, signatures, findings and ad-hoc budgets
    (per-test hygiene, mirrors lockcheck.reset)."""
    global _total
    with _mu:
        _counts.clear()
        _signatures.clear()
        _findings.clear()
        _budgets.clear()
        _total = 0


def compiles() -> Dict[str, int]:
    with _mu:
        return dict(_counts)


def total_compiles() -> int:
    with _mu:
        return _total


def compile_signatures() -> Dict[str, List[str]]:
    with _mu:
        return {k: list(v) for k, v in _signatures.items()}


def findings() -> List[RetraceFinding]:
    with _mu:
        return list(_findings)


def snapshot() -> int:
    """Opaque marker for :func:`retraces_since` — bench timed regions
    bracket their steady-state loops with these two calls and assert
    the delta is ZERO, so a retrace regression fails the stage instead
    of masquerading as a throughput change."""
    return total_compiles()


def retraces_since(snap: int) -> int:
    return total_compiles() - snap


@contextlib.contextmanager
def no_transfers():
    """Forbid device→host transfers in this thread for the duration:
    ``np.asarray``/``np.array`` on device arrays and ``jax.device_get``
    raise :class:`TransferError`; on a real device backend
    ``jax.transfer_guard("disallow")`` additionally covers the implicit
    paths jax itself can see.  Installs the seams on demand if
    tracewatch is not armed."""
    import jax

    if "device_get" not in _ORIG:
        _patch_array_seam()
    _tls.guard_depth = getattr(_tls, "guard_depth", 0) + 1
    try:
        with jax.transfer_guard("disallow"):
            yield
    finally:
        _tls.guard_depth -= 1
        if not _installed and _tls.guard_depth <= 0:
            _unpatch_array_seam()


@contextlib.contextmanager
def allow_transfers():
    """Escape hatch for a declared host boundary inside a
    ``no_transfers()`` region (e.g. fetching a final result after the
    timed loop closed)."""
    import jax

    prev = getattr(_tls, "guard_depth", 0)
    _tls.guard_depth = 0
    try:
        with jax.transfer_guard("allow"):
            yield
    finally:
        _tls.guard_depth = prev


# dtest node subprocesses inherit arming through their environment,
# exactly like M3_LOCKCHECK/M3_FAULTPOINTS (m3_tpu.x imports this
# module).
if os.environ.get("M3_TRACEWATCH"):
    install(raise_on_violation=os.environ.get("M3_TRACEWATCH") != "record")
