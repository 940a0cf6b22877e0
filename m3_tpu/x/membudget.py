"""Process-level device-memory (HBM) budget: admission, not autopsy.

``make_arenas`` at C=10M on a real chip OOM-crashes inside XLA with the
allocation half-landed; nothing upstream can catch it usefully because
the failure arrives as a runtime abort mid-dispatch.  This ledger moves
the failure to ADMISSION time, exactly like PR 11's SlotAllocator
contract for series capacity: every long-lived device structure — the
aggregation arenas (24B/slot packed counter, 40B/slot f64 — footprints
are compile-time constants of the layout), the series buffer ring, the
decode control table — and the big transient stage buffers (encoder
lane tables, decoder lane tables) REGISTER a byte reservation before
any XLA allocation happens.  Over budget raises the typed
:class:`DeviceBudgetExceeded` (a :class:`~m3_tpu.x.devguard.DeviceOOM`,
so the device guard classifies and counts it) and bumps the rejected
counter — reject-and-count, never die-in-XLA.

The budget is **advisory accounting, host-side only**: it tracks the
bytes THIS process asked for through the seam, not the allocator's
ground truth (XLA workspaces, compiled executables and framework
overhead are outside it).  Size the budget with headroom; the gauges
(``device_mem_budget_bytes`` / ``device_mem_used_bytes`` /
``device_mem_rejected_total`` on /metrics) make the high-water mark
visible.

Configuration: ``M3_DEVICE_MEM_BUDGET`` ("0"/unset = unlimited; plain
bytes or K/M/G/T suffix, binary units) read at import, or the node
config's ``device.mem_budget`` applied by run_node via
:func:`set_budget` before any reservation is taken.

Reservations release on ``release()``/context-manager exit, or
automatically when their ``owner`` object is garbage-collected (a
``weakref.finalize``, the lockcheck registry's pattern) — arena and
buffer objects have no close() and must not leak ledger bytes when an
engine drops them.
"""

from __future__ import annotations

import os
import re
import threading
import weakref
from typing import Dict

from m3_tpu.x.devguard import DeviceOOM

__all__ = [
    "DeviceBudgetExceeded", "Reservation", "budget", "used", "parse_bytes",
    "reserve", "transient", "set_budget", "snapshot", "counters",
    "reset", "arena_bytes", "buffer_bytes", "counter_arena_bytes",
    "gauge_arena_bytes", "timer_arena_bytes",
]


class DeviceBudgetExceeded(DeviceOOM):
    """Typed admission reject: the reservation would exceed
    ``M3_DEVICE_MEM_BUDGET``.  A DeviceOOM subclass so the devguard
    classifier/breakers treat it as the OOM it prevents."""

    kind = "budget"

    def __init__(self, component: str, nbytes: int, budget: int, used: int):
        super().__init__(
            component,
            f"reserving {nbytes} bytes would exceed the device memory "
            f"budget ({used} of {budget} in use) — raise "
            "M3_DEVICE_MEM_BUDGET/device.mem_budget or shrink the "
            "arena/buffer geometry")
        self.component = component
        self.nbytes = nbytes
        self.budget = budget
        self.used = used


_SIZE_RE = re.compile(r"^(\d+(?:\.\d+)?)\s*([KMGT]i?)?B?$", re.IGNORECASE)
_SIZE_MULT = {None: 1, "K": 1 << 10, "M": 1 << 20, "G": 1 << 30,
              "T": 1 << 40}


def parse_bytes(v) -> int:
    """"512M" / "2GiB" / 1048576 → bytes (binary units; 0 = unlimited)."""
    if isinstance(v, (int, float)):
        return int(v)
    m = _SIZE_RE.match(str(v).strip())
    if not m:
        raise ValueError(
            f"bad byte size {v!r} (want e.g. '512M', '2GiB', or bytes)")
    suffix = m.group(2)
    mult = _SIZE_MULT[suffix[0].upper() if suffix else None]
    return int(float(m.group(1)) * mult)


# Re-entrant: a cyclic GC pass can start inside _admit (any allocation
# under the lock) and run an owner's weakref finalizer, which releases
# through _admit on the same thread — with a plain Lock that thread
# deadlocks on itself (seen: a test worker hung for 12 minutes, PR 22).
_lock = threading.RLock()
_budget = parse_bytes(os.environ.get("M3_DEVICE_MEM_BUDGET", "") or 0)
_used = 0
_peak = 0
_rejected = 0
_by_component: Dict[str, int] = {}


def set_budget(nbytes) -> None:
    """Set the process budget (bytes or suffixed string; 0 disables
    admission).  Existing reservations stay — shrinking below current
    use only affects NEW reservations."""
    global _budget
    _budget = parse_bytes(nbytes)


def budget() -> int:
    return _budget


def used() -> int:
    with _lock:
        return _used


def counters() -> Dict[str, int]:
    with _lock:
        return {"membudget.used_bytes": _used,
                "membudget.peak_bytes": _peak,
                "membudget.rejected_total": _rejected}


def snapshot() -> dict:
    """The /health view: budget/used/peak/rejected + per-component
    bytes currently reserved."""
    with _lock:
        return {
            "budget_bytes": _budget,
            "used_bytes": _used,
            "peak_bytes": _peak,
            "rejected_total": _rejected,
            "components": dict(_by_component),
        }


def reset() -> None:
    """Test hygiene: zero the ledger (live Reservations become no-ops
    for the bytes they release — only use between isolated tests)."""
    global _used, _peak, _rejected
    with _lock:
        _used = 0
        _peak = 0
        _rejected = 0
        _by_component.clear()


class Reservation:
    """One admitted byte reservation; release is idempotent."""

    def __init__(self, component: str, nbytes: int):
        self.component = component
        self.nbytes = int(nbytes)
        self._released = False
        self._finalizer = None

    def resize(self, nbytes: int) -> None:
        """Grow/shrink in place (buffer ``_grow`` paths): the DELTA is
        admitted against the budget; an over-budget grow raises typed
        and leaves the reservation unchanged."""
        nbytes = int(nbytes)
        delta = nbytes - self.nbytes
        if self._released or delta == 0:
            return
        _admit(self.component, delta)
        self.nbytes = nbytes

    def release(self) -> None:
        global _used
        if self._released:
            return
        self._released = True
        if self._finalizer is not None:
            self._finalizer.detach()
            self._finalizer = None
        _admit(self.component, -self.nbytes, count_reject=False)

    def __enter__(self) -> "Reservation":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


def _admit(component: str, delta: int, count_reject: bool = True) -> None:
    global _used, _peak, _rejected
    with _lock:
        if delta > 0 and _budget > 0 and _used + delta > _budget:
            if count_reject:
                _rejected += 1
            raise DeviceBudgetExceeded(component, delta, _budget, _used)
        _used += delta
        _peak = max(_peak, _used)
        _by_component[component] = _by_component.get(component, 0) + delta
        if _by_component[component] <= 0:
            del _by_component[component]


def reserve(component: str, nbytes: int, owner=None) -> Reservation:
    """Admit ``nbytes`` for ``component`` or raise
    :class:`DeviceBudgetExceeded` (counted).  With ``owner`` given the
    reservation auto-releases when the owner is collected."""
    _admit(component, int(nbytes))
    res = Reservation(component, nbytes)
    if owner is not None:
        res._finalizer = weakref.finalize(owner, _finalize_release, res)
    return res


def _finalize_release(res: Reservation) -> None:
    # module-level (not a bound method) so the finalizer holds no cycle
    res._finalizer = None
    res.release()


def transient(component: str, nbytes: int) -> Reservation:
    """Context-managed reservation for a stage's transient device
    buffers (encoder/decoder lane tables): admitted for the duration
    of the call, released on exit either way."""
    return reserve(component, nbytes)


# ---------------------------------------------------------------------------
# Footprint formulas — the known constants the admission check uses.
# These mirror the state NamedTuples field-by-field; a layout change
# that alters a dtype/lane set must update its formula (the checkpoint
# round-trip tests cover the same shapes).  Since round 13 the formulas
# are verified against XLA's OWN memory_analysis() at the costwatch
# canonical shapes (cli costs: membudget_crosscheck;
# tests/test_membudget_xla.py pins formula >= actual and <= 2x actual)
# instead of hand-derived lane nbytes alone.
# ---------------------------------------------------------------------------

# XLA's memory_analysis() reports a few dozen bytes of tuple/alignment
# overhead per state pytree beyond the raw lane nbytes (measured 24-104B
# across the six arena states at canonical shapes).  The formulas fold
# a flat allowance in so "formula >= XLA actual" holds exactly, not
# approximately.
_XLA_STATE_OVERHEAD = 512


def counter_arena_bytes(layout: str, num_windows: int, capacity: int,
                        pool_capacity: int | None = None) -> int:
    """packed: 24B/slot (base u64 + sq i64 + minmax u32 + pool_idx i32)
    + 44B per overflow-pool row (default P = max(64, W*C/16)) + the two
    i32 scalar lanes (pool_n, err); f64: 40B/slot (5 i64 lanes).  Both
    carry the per-slot i64 last_at."""
    wc = num_windows * capacity
    if layout == "packed":
        P = pool_capacity if pool_capacity is not None else max(64, wc // 16)
        return 24 * wc + 44 * P + 8 * capacity + 8 + _XLA_STATE_OVERHEAD
    return 40 * wc + 8 * capacity + _XLA_STATE_OVERHEAD


def gauge_arena_bytes(layout: str, num_windows: int, capacity: int) -> int:
    """56B/slot on both layouts (7 f64/i64 lanes) + per-slot last_at."""
    return 56 * num_windows * capacity + 8 * capacity + _XLA_STATE_OVERHEAD


def timer_arena_bytes(layout: str, num_windows: int, capacity: int,
                      sample_capacity: int) -> int:
    """packed: one u64 word per buffered sample; f64: 24B/slot moments
    + 12B (i32 slot + f64 value) per buffered sample.  Plus the
    per-window write heads and per-slot last_at."""
    W, C, S = num_windows, capacity, sample_capacity
    if layout == "packed":
        return 8 * W * S + 8 * W + 8 * C + _XLA_STATE_OVERHEAD
    return 24 * W * C + 12 * W * S + 8 * W + 8 * C + _XLA_STATE_OVERHEAD


def arena_bytes(layout: str, num_windows: int, capacity: int,
                sample_capacity: int) -> int:
    """Total device bytes of one (counter, gauge, timer) arena triple —
    the sum of the per-arena formulas above (the admission constants
    ISSUE 13 names: 24B/slot packed counter, 40B/slot f64)."""
    return (counter_arena_bytes(layout, num_windows, capacity)
            + gauge_arena_bytes(layout, num_windows, capacity)
            + timer_arena_bytes(layout, num_windows, capacity,
                                sample_capacity))


def buffer_bytes(num_windows: int, sample_capacity: int) -> int:
    """Series-buffer ring bytes: slot i32 + ts i64 + val u64 per
    (window, sample) plus the per-window i64 write heads."""
    return 20 * num_windows * sample_capacity + 8 * num_windows


# Per-datapoint TEMP coefficients for the codec passes, by placement /
# chains tail.  Derived from XLA memory_analysis temp bytes at the
# costwatch canonical shapes (S=256, T=128: encode gather 204 B/dp,
# scatter 168, pallas 216; decode fused 11, gather+jnp 85,
# gather+pallas 128) with ~25-30% headroom — the admission contract is
# formula >= XLA actual and <= 2x actual, pinned by
# tests/test_membudget_xla.py and surfaced per run in the COSTS
# artifact's membudget_crosscheck.
_ENCODE_TEMP_PER_DP = {"gather": 260, "scatter": 220, "pallas": 280}
_DECODE_TEMP_PER_DP = {"fused": 16, "gather": 110, "gather_pallas": 170}


def encode_lane_bytes(S: int, T: int, out_words: int,
                      place: str = "gather") -> int:
    """Transient device bytes of one encode pass through placement tail
    ``place``: the exact argument footprint (ts i64 + value bits u64 +
    valid bool + start i64), the exact output (words + total_bits +
    fallback), and a per-tail temp coefficient covering the (T, 4, S)
    lane tables, offset cumsums and (4T, S) fragment planes XLA
    actually materializes."""
    args = 17 * S * T + 8 * S
    out = 8 * S * out_words + 9 * S
    per_dp = _ENCODE_TEMP_PER_DP.get(place, _ENCODE_TEMP_PER_DP["pallas"])
    return args + out + per_dp * S * T


def decode_lane_bytes(S: int, W: int, max_points: int,
                      chains: str = "fused", extract: str = "jnp") -> int:
    """Transient device bytes of one decode pass through the ``chains``
    tail (``W`` = padded stream words per series): exact arguments
    (words + nbits + the 1MiB value-control table), exact outputs
    (ts i64 + payload u64 + meta u8 per point, plus err/prec/ann), and
    a per-tail temp coefficient for the phase-2 lane tables the gather
    tails materialize (the fused tail carries its chains in the scan
    and pays almost none)."""
    args = 8 * S * W + 8 * S + (1 << 20)
    out = 17 * S * max_points + 24 * S
    key = ("fused" if chains == "fused"
           else ("gather_pallas" if extract == "pallas" else "gather"))
    return args + out + _DECODE_TEMP_PER_DP[key] * S * max_points
