"""Cross-cutting substrate (the reference's ``src/x`` tree).

* ``m3_tpu.x.fault`` — process-global fault-injection registry: named
  faultpoints at every socket/disk boundary, armed via code or the
  ``M3_FAULTPOINTS`` env var, with deterministic seeding and per-point
  trigger counters.
* ``m3_tpu.x.retry`` — the reference ``src/x/retry`` equivalent:
  exponential backoff + jitter + attempt caps + a shared retry budget,
  adopted by every wire client in the tree.
* ``m3_tpu.x.deadline`` — end-to-end query deadlines + cooperative
  cancellation: one absolute expiry threaded HTTP → engine → fanout →
  wire (context-bound, serialized into the query/rpc frames), raising
  typed ``DeadlineExceeded`` the API maps to 504.
* ``m3_tpu.x.admission`` — bounded concurrent-query slots + wait queue
  with queue timeout; saturation sheds typed ``QueryShedError``
  (HTTP 503 + Retry-After) instead of queueing unboundedly.
* ``m3_tpu.x.breaker`` — per-peer circuit breakers
  (closed/open/half-open on consecutive transport failures or deadline
  blowouts) shared by the remote-query client, the session read
  fan-out, and the rpc client through one process registry.
* ``m3_tpu.x.lockcheck`` — runtime lock-order sanitizer: wraps
  ``threading.Lock``/``RLock`` behind an env-armed seam
  (``M3_LOCKCHECK``, like ``M3_FAULTPOINTS``) and fails fast on
  acquisition-order cycles; armed by the race/dtest conftest fixture.
* ``m3_tpu.x.tracewatch`` — runtime retrace/transfer sanitizer: counts
  XLA compiles per function through the ``jax_log_compiles`` seam and
  fails fast (with the offending shapes/dtypes) when a jitted function
  retraces past its budget; ``no_transfers()`` forbids device→host
  copies in timed/guarded regions.  Env-armed via ``M3_TRACEWATCH``
  (like lockcheck); bench steady-state loops assert zero retraces
  through it.
* ``m3_tpu.x.hopwatch`` — tracewatch's counting sibling: per-named-hop
  host↔device transfer (count + bytes), compile and dispatch
  accounting behind the same env-seam arming (``M3_HOPWATCH``);
  ``cli hops`` drives the wire→arena→drain→encode→fileset path under
  it and commits the PIPELINE artifact ROADMAP item 1 rebuilds
  against.
* ``m3_tpu.x.devguard`` — the device-boundary resilience seam: typed
  ``DeviceError`` classification over jax/XLA exception shapes,
  per-stage fallback breakers (``run_guarded``), and the
  ``device.compile``/``device.dispatch``/``device.transfer``
  faultpoints so synthetic device failures are injectable on live
  nodes through ``/api/v1/debug/faults``.
* ``m3_tpu.x.membudget`` — process-level device-memory ledger: arenas,
  series buffers and big transient stage buffers reserve bytes BEFORE
  XLA allocates; over ``M3_DEVICE_MEM_BUDGET`` rejects typed
  (``DeviceBudgetExceeded``) instead of dying inside the runtime.
* ``m3_tpu.x.diskbudget`` — membudget's disk twin: a per-root byte
  ledger (filesets / commitlog / snapshots / quarantine / checkpoints
  + statvfs or quota headroom) with OK/LOW/CRITICAL watermarks and a
  reserved flush-headroom band; LOW triggers eager cleanup, CRITICAL
  sheds NEW ingest typed (``DiskCapacityError``) while flush/WAL ride
  the reserve.
* ``m3_tpu.x.costwatch`` — machine-independent cost fingerprints: a
  registry of every hot-path device program at pinned canonical
  shapes, fingerprinted compile-only from XLA's cost/memory analysis
  (flops/bytes/op-histogram/peak per datapoint); ``cli costs --check``
  ratchets the committed COSTS artifact, box-noise-immune and
  chip-independent.  (Imported lazily — it pulls the codec/arena
  modules in, so it is not part of the m3_tpu.x import set.)
* ``m3_tpu.x.lint`` — m3lint, the codebase-aware static analyzer
  (``python -m m3_tpu.tools.cli lint``); its rule families are the
  static mirror of what fault/retry/lockcheck/tracewatch enforce at
  runtime (the jax families — retrace-risk, transfer-hygiene,
  dtype-stability, constant-bloat — are tracewatch's static twin).

``register_metrics(registry)`` mirrors the fault and retry counters
into an instrument registry at scrape time, so a node's ``/metrics``
exposes ``fault_*`` and ``retry_*`` series dtest scenarios can assert
on.
"""

from __future__ import annotations

# lockcheck first: importing it evaluates the M3_LOCKCHECK env seam, so
# a node subprocess wraps its locks before fault/retry (or anything
# else) constructs one.  tracewatch next, for the same reason: its
# M3_TRACEWATCH seam must swap the jit factories before any module
# decorates a hot-path function.  hopwatch (the counting sibling,
# M3_HOPWATCH) follows the same rule: its jit proxy only sees functions
# jitted after arming.
from m3_tpu.x import lockcheck  # noqa: F401  (env-armed seam)
from m3_tpu.x import tracewatch  # noqa: F401  (env-armed seam)
from m3_tpu.x import hopwatch  # noqa: F401  (env-armed seam)
from m3_tpu.x import breaker, deadline, fault, retry
from m3_tpu.x import devguard, membudget  # noqa: F401  (device guard)


def register_metrics(registry, prefix: str = "") -> object:
    """Register a scrape-time collector mirroring the fault, retry,
    deadline and breaker counters into ``registry`` gauges (tagged by
    point/retrier/peer name).  Returns the collector so callers with a
    shutdown path can ``registry.unregister_collector`` it."""
    scope = registry.scope(prefix)

    def collect() -> None:
        for name, value in fault.counters().items():
            point, _, key = name.rpartition(".")
            scope.tagged({"point": point}).gauge(f"fault.{key}").update(value)
        for name, value in retry.counters().items():
            rname, _, key = name.rpartition(".")
            scope.tagged({"retrier": rname}).gauge(f"retry.{key}").update(value)
        dl = deadline.counters()
        scope.gauge("query_deadline_exceeded_total").update(
            dl.get("deadline.exceeded", 0))
        scope.gauge("query_cancelled_total").update(
            dl.get("deadline.cancelled", 0))
        for peer, br in breaker.all_breakers().items():
            scope.tagged({"peer": peer, "kind": br.kind}).gauge(
                "breaker_state").update(br.state_code)
        for name, value in breaker.counters().items():
            peer, _, key = name.rpartition(".")
            scope.tagged({"peer": peer}).gauge(f"breaker.{key}").update(value)
        # device-guard stage counters: device.<stage>.calls /
        # .fallback_calls / .errors.<kind> (stage names contain dots —
        # split on the known suffixes, the devguard.status() rule)
        for name, value in devguard.counters().items():
            rest = name[len("device."):]
            if rest.endswith(".calls") and not rest.endswith(
                    ".fallback_calls"):
                scope.tagged({"stage": rest[:-len(".calls")]}).gauge(
                    "device_guard_calls").update(value)
            elif rest.endswith(".fallback_calls"):
                scope.tagged(
                    {"stage": rest[:-len(".fallback_calls")]}).gauge(
                    "device_fallback_total").update(value)
            else:
                st, _, kind = rest.rpartition(".errors.")
                if st:
                    scope.tagged({"stage": st, "kind": kind}).gauge(
                        "device_error_total").update(value)
        mb = membudget.snapshot()
        scope.gauge("device_mem_budget_bytes").update(mb["budget_bytes"])
        scope.gauge("device_mem_used_bytes").update(mb["used_bytes"])
        scope.gauge("device_mem_peak_bytes").update(mb["peak_bytes"])
        scope.gauge("device_mem_rejected_total").update(
            mb["rejected_total"])
        # disk ledger + typed-capacity counters (lazy: diskbudget pulls
        # persist.capacity in, and most registry users never touch disk)
        from m3_tpu.persist import capacity
        from m3_tpu.x import diskbudget
        db = diskbudget.snapshot()
        if db["enabled"]:
            scope.gauge("disk_total_bytes").update(db["total_bytes"])
            scope.gauge("disk_used_bytes").update(db["used_bytes"])
            scope.gauge("disk_free_bytes").update(db["free_bytes"])
            scope.gauge("disk_free_ratio").update(db["free_ratio"])
            scope.gauge("disk_reserve_bytes").update(db["reserve_bytes"])
            scope.gauge("disk_level").update(db["level_value"])
            scope.gauge("disk_ingest_shed_total").update(db["shed_total"])
            for comp, nbytes in db["components"].items():
                scope.tagged({"component": comp}).gauge(
                    "disk_component_bytes").update(nbytes)
        for name, value in capacity.counters().items():
            comp, _, _key = name.rpartition(".")
            scope.tagged({"component": comp}).gauge(
                "disk_capacity_errors_total").update(value)

    registry.register_collector(collect)
    return collect
