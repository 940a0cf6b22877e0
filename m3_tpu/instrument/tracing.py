"""Tracing: spans with a tracepoint registry + cross-process context.

Equivalent of the reference's opentracing layer
(`src/x/opentracing/tracing.go:31-59` pluggable backends) and its
tracepoint name registries (`src/dbnode/tracepoint/tracepoint.go`,
`src/query/tracepoint`): spans started at RPC/storage boundaries, named
from a central registry so dashboards can rely on stable names.  The
jaeger/lightstep reporter plumbing collapses to a bounded in-memory
ring (zero egress environment) exposed over ``/api/v1/debug/traces`` —
the Tracer interface is the seam a real exporter would plug into.

Cross-process propagation (W3C traceparent, struct-packed): a
:class:`TraceContext` is (trace_id, span_id, sampled) — 17 bytes on the
wire (``<QQB``).  The context seam mirrors ``x/deadline.py`` exactly:

* ``bind(ctx)`` installs a remote parent for the current thread of
  execution (contextvars); ``current()`` reads it.  Server frame loops
  decode the context off the wire and ``bind`` it around dispatch, so
  every span the dispatch opens joins the caller's trace.
* Entering a recorded span ALSO binds its own context, so wire clients
  (rpc, query federation, the aggregator client) need no tracer handle
  — they read ``current()`` and serialize it into the frame: the
  RPC_REQ_TR header, the QUERY_FETCH trailer, the INGEST_TRACE
  preamble frame.  New threads never inherit the binding; fan-out
  workers re-bind explicitly (same rule as deadlines).
* **Sampling** rides the context: an unsampled request propagates no
  context and costs only a contextvar read per hop.  Root spans sample
  via the tracer's ``sample_rate`` (1.0 = everything, the debug-ring
  default); a bound remote context's decision always wins — the
  coordinator decides once, every downstream process obeys.

Span ids are drawn from a per-process random 64-bit space (not a
counter) so ids minted by different processes in one trace cannot
collide.

**One switch, two ways to turn it on.**  A span records iff its tracer
is ``enabled`` (the operator's ``coordinator.tracing``) OR a JAX
profiler session is live (``jax.profiler.TraceAnnotation.is_enabled()``
— capturing a profile turns the node's spans on for as long as the
capture lasts).  A recorded span goes to the ring AND stands as an
``m3:<name>`` annotation in the profiler's own trace, on plane
``/host:CPU`` of the same ``.xplane.pb`` as the device's ops: that is
how the node's host time and the device's idle gaps meet on one clock.
Not recording costs one call, one flag and one contextvar read per
site.  ``install``
makes a node's tracer the process's tracer, so code with no handle
(devguard, the namespace's write tail, the downsampler, the mediator)
opens spans through module-level :func:`span`.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import random
import struct
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import jax

# True inside a jax.profiler session (start_trace .. stop_trace, or a
# capture through the profiler server), False outside: a static C++ flag
_profiling = jax.profiler.TraceAnnotation.is_enabled


class Tracepoint:
    """Stable span names (reference dbnode/tracepoint/tracepoint.go)."""

    DB_WRITE_BATCH = "db.writeBatch"
    DB_INDEX_WRITE = "db.index.write"        # series lookup / insert
    DB_BUFFER_WRITE = "db.buffer.write"      # shard hash, staging, append
    DB_COMMITLOG_WRITE = "db.commitlog.write"
    DB_LOCK_WAIT = "db.lock.wait"            # acquisition of Database._mu
    DB_READ = "db.read"
    # under db.read, the sealed part of a batch fetch: one span a fetch
    # and flushed block (storage/database.py Namespace._decode_block);
    # tags n (series asked), device / scalar (series the device decoded
    # / rows through the scalar iterator), words, points, and the
    # decode's padded shape: rows, steps.  Below it
    # `.segments` (reader lookups and packing), the guarded
    # device.decode, `.to_host` (the copy and the values' bits)
    DB_READ_FILESET = "db.read.fileset"
    DB_READ_FILESET_SEGMENTS = "db.read.fileset.segments"
    DB_READ_FILESET_TO_HOST = "db.read.fileset.to_host"
    DB_QUERY_IDS = "db.queryIDs"
    DB_BOOTSTRAP = "db.bootstrap"
    DB_TICK = "db.tick"
    DB_FLUSH_ENCODE = "db.flush.encode"      # a warm flush's batch encode
    DB_FLUSH_WRITE = "db.flush.write"        # ... and its fileset volume
    DB_SNAPSHOT = "db.snapshot"
    ENGINE_EXECUTE = "query.engine.execute"
    EVAL_CALL = "query.eval.call"                # tag fn: the function
    # tags op: the operator; one_program of n: the operator is one
    # jitted program a call (query/engine.Engine._eval)
    EVAL_AGGREGATION = "query.eval.aggregation"
    FETCH_COMPRESSED = "query.storage.fetchCompressed"
    API_QUERY_RANGE = "api.queryRange"           # the whole read handler
    API_QUERY_RENDER = "api.queryRange.render"   # values loop + JSON
    API_WRITE = "api.write"                      # the whole write handler
    API_WRITE_DECODE = "api.write.decode"        # body, parse, Documents
    API_WRITE_SNAPPY = "api.write.decode.snappy"      # remote write only
    API_WRITE_PROTOBUF = "api.write.decode.protobuf"  # remote write only
    # the aggregator's TCP front door (server/ingest_tcp.py): one root
    # per frame from "received" to "acked", parented on the sender's
    # span where an INGEST_TRACE preamble carried one
    INGEST_FRAME = "ingest.frame"                # tag n: samples
    INGEST_FRAME_DECODE = "ingest.frame.decode"
    INGEST_QUEUE_WAIT = "ingest.queue.wait"      # enqueue -> worker
    AGG_LOCK_WAIT = "aggregator.lock.wait"       # the sink's lock
    AGG_RESOLVE = "aggregator.resolve"           # ids -> slots
    AGG_ADD = "aggregator.add"                   # window routing, staging
    AGG_FLUSH = "aggregator.flush"               # one flush-manager tick
    AGG_CONSUME = "aggregator.consume"
    # under it, one span per arena drained, named by metric type:
    # aggregator.drain.counter|gauge|timer (aggregator/engine.py
    # MetricList._drain); tags slots (that held a sample), bytes (lanes
    # copied to the host) and, where the arena knows, samples (the
    # timer buffer's).  Its own time is _emit's masks and the window
    # reset; below it stand the guarded device call, `<name>.wait` (the
    # wait for the arena's consume program, taken by bringing its counts
    # to the host), `<name>.to_host` (the copy of its finished lanes)
    # and the emission
    AGG_DRAIN = "aggregator.drain"
    AGG_FLUSH_EMIT = "aggregator.flush.emit"     # slots -> ids, encode, publish
    AGG_FLUSH_PERSIST = "flush.persist"          # flush times -> KV
    DOWNSAMPLE_LOCK_WAIT = "downsample.lock.wait"
    DOWNSAMPLE_MATCH = "downsample.match"        # per-doc rule match loop
    DOWNSAMPLE_ADD = "downsample.add"            # arena staging
    DOWNSAMPLE_FLUSH = "downsample.flush"
    DOWNSAMPLE_WRITEBACK = "downsample.writeback"
    MEDIATOR_RUN_ONCE = "mediator.runOnce"
    # host time inside one guarded device call (staging, dispatch, any
    # blocking transfer; NOT device time): "device." + devguard's stage
    DEVICE = "device."
    RUNTIME_GC = "runtime.gc"                    # tag generation (>= 1)
    # cross-process hops (round 10): the server-side spans each wire
    # protocol opens around dispatch, and the client-side fan-out span
    RPC_SERVER = "rpc.server"
    REMOTE_FETCH = "query.remote.fetch"
    SESSION_WRITE = "session.writeReplica"


# -- cross-process context ---------------------------------------------------


_WIRE = struct.Struct("<QQB")  # trace_id, parent span_id, flags


@dataclass(frozen=True)
class TraceContext:
    """What crosses a process boundary: which trace, which parent span,
    and whether the trace is sampled (W3C traceparent, packed)."""

    trace_id: int
    span_id: int
    sampled: bool = True

    WIRE_SIZE = _WIRE.size  # 17 bytes

    def to_wire(self) -> bytes:
        return _WIRE.pack(self.trace_id & (2**64 - 1),
                          self.span_id & (2**64 - 1),
                          1 if self.sampled else 0)

    @classmethod
    def from_wire(cls, raw: bytes, pos: int = 0) -> "TraceContext":
        tid, sid, flags = _WIRE.unpack_from(raw, pos)
        return cls(tid, sid, bool(flags & 1))


_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "m3_trace_context", default=None)


def current() -> TraceContext | None:
    """The trace context bound to this thread of execution, or None."""
    return _current.get()


@contextlib.contextmanager
def bind(ctx: TraceContext | None):
    """Install ``ctx`` for the scope (None = no-op scope, so callers
    need no conditional).  New threads never inherit the binding."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def current_wire(default: bytes = b"") -> bytes:
    """Wire form of the bound context for frame trailers/headers;
    ``default`` (empty = no trace) when none is bound or the bound
    trace is unsampled — unsampled requests cost nothing downstream."""
    ctx = _current.get()
    if ctx is None or not ctx.sampled:
        return default
    return ctx.to_wire()


@dataclass
class Span:
    name: str
    trace_id: int
    span_id: int
    parent_id: int | None
    start_ns: int
    end_ns: int = 0
    tags: dict = field(default_factory=dict)
    error: str | None = None
    # CPU nanoseconds of the span's own thread between start and end
    # (while open: the thread's CPU clock at start).  Under one GIL the
    # wall time of a span includes the wait for the GIL; this does not,
    # so CPU self times of concurrent requests add up to wall time.
    cpu_ns: int = 0

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def to_dict(self) -> dict:
        return {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "start_ns": self.start_ns, "duration_ns": self.duration_ns,
            "cpu_ns": self.cpu_ns, "tags": self.tags, "error": self.error,
        }

    @property
    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id, sampled=True)


class _ActiveSpan:
    __slots__ = ("_tracer", "span", "_token", "_annotation")
    recording = True    # a tag that costs something is worth computing

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self.span = span
        self._token = None
        self._annotation = None

    def set_tag(self, key: str, value) -> None:
        self.span.tags[key] = value

    def __enter__(self) -> "_ActiveSpan":
        # the active span IS the current trace context: in-process
        # children parent on it via the tracer stack, wire clients
        # serialize it via tracing.current()/current_wire()
        self._token = _current.set(self.span.context)
        if _profiling():
            # the same span in the profiler's own trace, beside the
            # device's ops (plane /host:CPU, this thread's line)
            self._annotation = jax.profiler.TraceAnnotation(
                "m3:" + self.span.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if exc is not None:
            self.span.error = f"{type(exc).__name__}: {exc}"
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        self._tracer._finish(self.span)
        return False


class _NoopSpan:
    recording = False

    def set_tag(self, key: str, value) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


NOOP_SPAN = _NoopSpan()

UNSAMPLED = TraceContext(0, 0, sampled=False)


class _UnsampledSpan:
    """Returned when a ROOT span loses the sampling roll, or opens
    while nothing records: records nothing, but BINDS a not-sampled
    context for its scope so every descendant (and every wire hop)
    inherits the negative decision — otherwise each child would
    re-roll as a fresh root and litter the ring with unparented
    fragment traces (the children of a request that was in flight
    when a profiler session opened, for one)."""

    __slots__ = ("_token",)
    recording = False

    def set_tag(self, key: str, value) -> None:
        pass

    def __enter__(self):
        self._token = _current.set(UNSAMPLED)
        return self

    def __exit__(self, *exc) -> bool:
        _current.reset(self._token)
        return False


class Tracer:
    """Span factory + bounded finished-span ring; parentage flows
    through a thread-local active-span stack in-process and through the
    bound :class:`TraceContext` across processes.

    ``enabled`` is the operator's switch; a live profiler session turns
    recording on as well (module docstring).  The ring holds the newest
    ``max_finished`` spans: ``dropped`` counts those it pushed out and
    ``dropped_until_ns`` is the latest end among them, so a reader
    knows its account of an interval is whole iff nothing was dropped
    or the interval began after that instant."""

    def __init__(self, max_finished: int = 65536, enabled: bool = True,
                 sample_rate: float = 1.0):
        self.enabled = enabled
        self.sample_rate = float(sample_rate)
        self.max_finished = int(max_finished)
        self._ring: deque[Span] = deque()
        self.dropped = 0
        self.dropped_until_ns = 0
        # reentrant: the collector's hook (install) opens and finishes
        # runtime.gc spans on whatever thread collects, which may be
        # inside one of this lock's (few-bytecode) sections
        self._lock = threading.RLock()
        self._tls = threading.local()
        # Random 64-bit ids: two processes in one trace must not mint
        # colliding span ids the way a shared counter would.
        self._rng = random.Random()

    @property
    def recording(self) -> bool:
        return self.enabled or _profiling()

    @property
    def oldest_start_ns(self) -> int | None:
        """Start of the oldest span the ring still holds."""
        with self._lock:
            return self._ring[0].start_ns if self._ring else None

    def _ids(self) -> int:
        with self._lock:
            return self._rng.getrandbits(64) or 1

    def _sample(self) -> bool:
        if self.sample_rate >= 1.0:
            return True
        with self._lock:
            return self._rng.random() < self.sample_rate

    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def start_span(self, name: str, tags: dict | None = None):
        """Context manager: `with tracer.start_span(Tracepoint.DB_READ):`.

        Parent resolution: the innermost active LOCAL span, else the
        bound remote :class:`TraceContext` (a server dispatch joining
        its caller's trace), else a fresh root — sampled per
        ``sample_rate`` (a bound context's sampled flag always wins)."""
        if not self.recording:
            # not recording.  A would-be root still binds the negative
            # decision for its scope: were a profiler session to open
            # mid-request, the request's later spans find it and stay
            # out of the ring instead of entering it as orphan roots
            return (NOOP_SPAN if _current.get() is not None
                    else _UnsampledSpan())
        stack = self._stack()
        parent = stack[-1] if stack else None
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            remote = _current.get()
            if remote is not None:
                if not remote.sampled:
                    return NOOP_SPAN
                trace_id, parent_id = remote.trace_id, remote.span_id
            else:
                if not self._sample():
                    # the negative decision is bound for the scope so
                    # in-process descendants don't re-roll as roots
                    return _UnsampledSpan()
                trace_id, parent_id = self._ids(), None
        span = Span(
            name=name,
            trace_id=trace_id,
            span_id=self._ids(),
            parent_id=parent_id,
            start_ns=time.monotonic_ns(),
            tags=dict(tags or {}),
            cpu_ns=time.thread_time_ns(),
        )
        stack.append(span)
        return _ActiveSpan(self, span)

    def _finish(self, span: Span) -> None:
        span.cpu_ns = time.thread_time_ns() - span.cpu_ns
        span.end_ns = time.monotonic_ns()
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        self._keep(span)

    # -- spans whose ends lie on different threads --------------------------

    def reserve(self, parent: TraceContext | None = None
                ) -> TraceContext | None:
        """Ids for a span that one thread begins and another ends (a
        frame from receipt to ack): bind the result around the work on
        either thread and its spans parent on it; :meth:`record` puts
        the span itself into the ring once its end is known.  None
        when nothing records or ``parent`` says not sampled: bind
        :data:`UNSAMPLED` then, so the work's spans stay out of the
        ring instead of entering it as roots."""
        if not self.recording or (parent is not None and not parent.sampled):
            return None
        return TraceContext(
            parent.trace_id if parent is not None else self._ids(),
            self._ids())

    def record(self, name: str, start_ns: int, end_ns: int,
               tags: dict | None = None, ctx: TraceContext | None = None,
               parent: TraceContext | None = None) -> None:
        """A finished span from times the caller observed (monotonic
        ns): under the ids ``ctx`` that :meth:`reserve` gave, or with
        fresh ids as a child of ``parent``.  No CPU time: the interval
        belongs to no one thread."""
        if ctx is None and parent is None:
            return
        self._keep(Span(
            name=name,
            trace_id=(ctx or parent).trace_id,
            span_id=ctx.span_id if ctx is not None else self._ids(),
            parent_id=parent.span_id if parent is not None else None,
            start_ns=start_ns, end_ns=end_ns, tags=dict(tags or {})))

    def _keep(self, span: Span) -> None:
        with self._lock:
            ring = self._ring
            ring.append(span)
            while len(ring) > self.max_finished:
                self.dropped += 1
                # max: threads append a little out of the order of end
                self.dropped_until_ns = max(self.dropped_until_ns,
                                            ring.popleft().end_ns)

    # -- the collector ------------------------------------------------------

    def gc_hook(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry (registered by :func:`install`): a
        collection of generation >= 1 is a ``runtime.gc`` span under
        whatever is open on the collecting thread.  It holds the GIL,
        so every thread of the node stalls for its length."""
        if info["generation"] < 1:
            return
        if phase == "start":
            if self.recording:
                active = self.start_span(
                    Tracepoint.RUNTIME_GC, {"generation": info["generation"]})
                self._tls.gc = active.__enter__()
        else:
            active = getattr(self._tls, "gc", None)
            if active is not None:
                self._tls.gc = None
                active.__exit__(None, None, None)

    # -- introspection -----------------------------------------------------

    def finished(self, name: str | None = None) -> list[Span]:
        with self._lock:
            spans = list(self._ring)
        return [s for s in spans if name is None or s.name == name]

    def traces(self) -> dict[int, list[Span]]:
        out: dict[int, list[Span]] = {}
        for s in self.finished():
            out.setdefault(s.trace_id, []).append(s)
        return out

    def inventory(self) -> list[dict]:
        """Ring inventory for the debug endpoint: one row per trace —
        id, span count, distinct tracepoint names, wall span."""
        out = []
        for tid, spans in sorted(self.traces().items()):
            start = min(s.start_ns for s in spans)
            end = max(s.end_ns or s.start_ns for s in spans)
            out.append({
                "trace_id": tid,
                "spans": len(spans),
                "names": sorted({s.name for s in spans}),
                "duration_ns": end - start,
            })
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self.dropped_until_ns = 0


class _NoopTracer(Tracer):
    """The tracer of code that was handed none: never records, profiler
    session or not."""

    recording = False

    def start_span(self, name: str, tags: dict | None = None):
        return NOOP_SPAN


NOOP_TRACER = _NoopTracer(enabled=False)


# -- the process's tracer ----------------------------------------------------

_installed: Tracer = NOOP_TRACER


def install(tracer: Tracer) -> None:
    """Make ``tracer`` the process's tracer (what module-level
    :func:`span` opens spans on) and hand it the collector's hook.  A
    node does this once, in ``run_node``; the last node installed in a
    process wins."""
    global _installed
    uninstall(_installed)
    _installed = tracer
    gc.callbacks.append(tracer.gc_hook)


def uninstall(tracer: Tracer) -> None:
    """Undo :func:`install` if ``tracer`` is still the one installed;
    its ring stays readable."""
    global _installed
    if tracer is _installed and tracer is not NOOP_TRACER:
        gc.callbacks.remove(tracer.gc_hook)
        _installed = NOOP_TRACER


def span(name: str, tags: dict | None = None):
    """``start_span`` on the process's tracer, for code with no handle."""
    return _installed.start_span(name, tags)


class SpanLock:
    """A lock whose every acquisition stands under a span named
    ``name``: the wait for it, which a span opened inside the ``with``
    cannot see.  ``with`` and acquire/release as the wrapped lock."""

    __slots__ = ("_lock", "_name", "_tracer")

    def __init__(self, lock, name: str, tracer: Tracer | None = None):
        self._lock, self._name, self._tracer = lock, name, tracer

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        with (self._tracer or _installed).start_span(self._name):
            return self._lock.acquire(blocking, timeout)

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> "SpanLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self._lock.release()
        return False


# -- cross-process trace assembly -------------------------------------------


def traces_response(tracer: "Tracer", trace_id=None,
                    name: str | None = None) -> dict:
    """The ``/api/v1/debug/traces`` response document — ONE
    implementation shared by the main HTTP API and the admin API (the
    dtest harness collects through either port; the two handlers must
    not drift).  ``trace_id`` → that trace's spans parent-before-child;
    ``name`` → spans of one tracepoint; default → ring inventory + raw
    spans (``dropped`` > 0 = the ring has pushed spans out: whatever
    ended at or before ``dropped_until_ns`` may be missing)."""
    if trace_id is not None:
        tid = int(trace_id)
        spans = [s.to_dict() for s in tracer.finished()
                 if s.trace_id == tid]
        return {"status": "success",
                "data": join_traces(spans).get(tid, [])}
    return {"status": "success",
            "inventory": tracer.inventory() if name is None else None,
            "dropped": tracer.dropped,
            "dropped_until_ns": tracer.dropped_until_ns,
            "data": [s.to_dict() for s in tracer.finished(name)]}


def join_traces(span_dicts: list[dict]) -> dict[int, list[dict]]:
    """Group span dicts (``Span.to_dict`` rows, typically collected
    from several processes' debug endpoints) by trace_id, each trace's
    spans ordered parent-before-child where links allow."""
    by_trace: dict[int, list[dict]] = {}
    for s in span_dicts:
        by_trace.setdefault(int(s["trace_id"]), []).append(s)
    for spans in by_trace.values():
        by_id = {s["span_id"]: s for s in spans}

        def depth(s, _seen=None) -> int:
            seen = _seen or set()
            d = 0
            while s.get("parent_id") in by_id and s["span_id"] not in seen:
                seen.add(s["span_id"])
                s = by_id[s["parent_id"]]
                d += 1
            return d

        spans.sort(key=lambda s: (depth(s), s["start_ns"]))
    return by_trace
