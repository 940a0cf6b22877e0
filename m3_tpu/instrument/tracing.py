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

**The runtime beneath every span** is hooked in the same place:
:func:`install` hands the process's tracer the collector's callback
(``runtime.gc``), JAX's compile events and the interpreter-lock probe,
and :func:`uninstall` takes all three back.

* ``runtime.gil.probe`` — how busy the interpreter is and how long the
  queue for it.  A daemon thread that lives only while something
  records (the first recorded span starts it; it ends itself at its
  next wake once nothing records) sleeps ``PROBE_SLEEP_NS`` (5 ms: one
  switch interval), notes how late it got the interpreter back, and
  every ``PROBE_EMIT_NS`` (100 ms) puts ONE root span on the ring with
  tags ``n`` (probes), ``contended`` (probes late by more than
  ``PROBE_LATE_NS``, 0.5 ms), ``wait_us`` (their summed lateness) and
  ``max_us``.  Measured with CPython 3.12 on an idle machine (3 s a
  row): no other thread 2.3 % contended, mean wait 0.21 ms; one
  pure-Python spinner 100 %, 5.2 ms (one switch interval); four
  spinners 100 %, 24.0 ms (five); two ``np.sort`` loops, which release
  the lock, 1.6 %, 0.18 ms at 5.57 s of process CPU; a spinner and two
  sorts 94.7 %, 3.9 ms.  So ``contended / n`` is the share of time the
  interpreter is held, and the mean wait over the switch interval is
  the number of threads queued for it: what thread CPU cannot say.  It
  samples the queue, not the holder; it cannot tell the wait for the
  interpreter from the wait for a core; it sees nothing under 0.5 ms;
  and it asks for the interpreter itself up to 200 times a second.
* the **compile log** — one :class:`CompileRow` per program the process
  compiles or reads from the persistent cache, from ``jax.monitoring``'s
  events (they fire on the compiling thread at the end of each phase:
  the start is now minus the duration).  Always on: a path that does
  not compile never reaches it.  It begins at :func:`install` and
  keeps the newest ``COMPILE_LOG_MAX`` rows (``compiles_dropped``
  counts the rest).  A trace that no compile followed (``eval_shape``,
  a retrace that found its executable) is not a program and not in it.
  Where the tracer records, a row is also a ``runtime.compile`` span
  under whatever span is open on the compiling thread: the request
  that waited for the compiler is charged for it.
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

# the interpreter-lock probe (module docstring); constants, not knobs
PROBE_SLEEP_NS = 5_000_000      # CPython's default switch interval
PROBE_LATE_NS = 500_000         # later than this: somebody held the lock
PROBE_EMIT_NS = 100_000_000     # one runtime.gil.probe span about so often
PROBE_THREAD = "m3-gil-probe"

COMPILE_LOG_MAX = 4096          # rows the compile log keeps
# jax.monitoring's duration events -> the phase of a CompileRow
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
    "/jax/compilation_cache/compile_time_saved_sec": "saved",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class Tracepoint:
    """Stable span names (reference dbnode/tracepoint/tracepoint.go)."""

    DB_WRITE_BATCH = "db.writeBatch"
    DB_INDEX_WRITE = "db.index.write"        # series lookup / insert
    DB_BUFFER_WRITE = "db.buffer.write"      # shard hash, staging, append
    DB_COMMITLOG_WRITE = "db.commitlog.write"
    DB_LOCK_WAIT = "db.lock.wait"            # acquisition of Database._mu
    DB_READ = "db.read"
    # under db.read, the part of a batch fetch that holds Database._mu
    # (storage/database.py Database._fetch): the read plan's segments
    # (`db.read.fileset.segments`, one a flushed block: reader lookups,
    # bloom, index walk, checksums), slots and buffer snapshots; tags n
    # (series asked), streams (segments read)
    DB_READ_LOCKED = "db.read.locked"
    # the device sort of an open window whose sorted snapshot missed
    # (storage/buffer.py ShardBuffer._sorted_window): under
    # db.read.locked in a fetch; tags points (the window's entries),
    # stale (1: a write or drain invalidated an existing snapshot of
    # this window; 0: there was none).  A hit opens no span
    DB_BUFFER_SNAPSHOT = "db.buffer.snapshot"
    # under db.read, after the release, the sealed part of a batch
    # fetch: one span a fetch and flushed block (Namespace._decode_block);
    # tags n (series asked), device / scalar (series the device decoded
    # / rows through the scalar iterator), regfile (select | gather:
    # how the boundary scan read its register file), words, points,
    # and the decode's padded shape: rows, steps.  Below it the guarded
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
    # under query.eval.call, one per dispatched call of a range
    # function's program (query/engine.py Engine._range_rows); tags rows
    # (series), pad (empty rows that fill the last block), points,
    # steps.  Below it `.to_device`: the block's columns to the device,
    # tag bytes
    EVAL_BLOCK = "query.eval.block"
    EVAL_TO_DEVICE = "query.eval.to_device"
    # under query.eval.aggregation, the host's grouping of the series
    # by their labels (query/functions.group_series); tags n, groups
    EVAL_GROUP_KEYS = "query.eval.group_keys"
    FETCH_COMPRESSED = "query.storage.fetchCompressed"
    # under it, before db.read: the fetched ids' sort and their labels
    # as the block's series (query/storage_adapter.py); tag n
    STORAGE_METAS = "query.storage.metas"
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
    # a root about every 100 ms while something records; tags n,
    # contended, wait_us, max_us (module docstring)
    RUNTIME_GIL_PROBE = "runtime.gil.probe"
    # one per program compiled or read from the cache while something
    # records, under the span that waited; tags fn, cache (hit | miss |
    # off) and the phases' seconds trace_s, lower_s, compile_s,
    # cache_read_s
    RUNTIME_COMPILE = "runtime.compile"
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


@dataclass
class CompileRow:
    """One program the process compiled or read from the persistent
    cache.  ``fn`` is the program's name as JAX reports it
    (``jit(rate_family)``); ``compile_s`` is the backend's time less the
    cache read inside it, so the four phases add up to ``seconds``;
    ``saved_s`` is what the cache says the hit saved; ``cache`` is
    ``hit``, ``miss`` (compiled and written) or ``off`` (the cache took
    no part: disabled, or the program is under its thresholds)."""

    fn: str
    thread: str
    start_ns: int
    end_ns: int
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0
    cache_read_s: float = 0.0
    saved_s: float = 0.0
    cache: str = "off"

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s + self.cache_read_s


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
        # the runtime hooks (install): only the process's tracer has them
        self._hooked = False
        self._probe: threading.Thread | None = None
        self.gil_probes = 0          # cumulative, for /metrics
        self.gil_contended = 0
        self.gil_wait_ns = 0
        self._compiles: deque[CompileRow] = deque()
        self.compiles_dropped = 0
        self.compile_count = 0       # cumulative, dropped rows included
        self.compile_cache_hits = 0
        self.compile_cache_misses = 0
        self.compile_seconds = {"trace": 0.0, "lower": 0.0, "compile": 0.0,
                                "cache_read": 0.0}

    @property
    def recording(self) -> bool:
        return self.enabled or _profiling()

    @property
    def oldest_start_ns(self) -> int | None:
        """Start of the oldest span the ring still holds."""
        with self._lock:
            return self._ring[0].start_ns if self._ring else None

    def _ids(self) -> int:
        # no lock: getrandbits is one C call, atomic under the interpreter's
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
        if self._hooked and self._probe is None:
            self._probe_start()
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

    # -- the interpreter lock ------------------------------------------------

    def _probe_start(self) -> None:
        with self._lock:
            if self._probe is None and self._hooked:
                self._probe = threading.Thread(
                    target=self._probe_run, name=PROBE_THREAD, daemon=True)
                self._probe.start()

    def _probe_run(self) -> None:
        """Sleep one switch interval, note how late the interpreter came
        back, and about every ``PROBE_EMIT_NS`` put the account on the
        ring as one root span; until nothing records."""
        clock, sleep_s = time.monotonic_ns, PROBE_SLEEP_NS / 1e9
        began = clock()
        n = contended = wait = worst = 0
        while True:
            t = clock()
            time.sleep(sleep_s)
            now = clock()
            live = self._hooked and self.recording
            if live:
                late = now - t - PROBE_SLEEP_NS
                n += 1
                if late > PROBE_LATE_NS:
                    contended += 1
                    wait += late
                    worst = max(worst, late)
            if not live or now - began >= PROBE_EMIT_NS:
                with self._lock:
                    self.gil_probes += n
                    self.gil_contended += contended
                    self.gil_wait_ns += wait
                    if not live:
                        self._probe = None
                        return
                self.record(
                    Tracepoint.RUNTIME_GIL_PROBE, began, now,
                    {"n": n, "contended": contended, "wait_us": wait // 1000,
                     "max_us": worst // 1000}, ctx=self.reserve())
                began, n, contended, wait, worst = now, 0, 0, 0, 0

    # -- the compiler --------------------------------------------------------

    def _on_compile_duration(self, event: str, duration_secs: float,
                             **kw) -> None:
        """``jax.monitoring`` duration listener: a phase of a compile
        ended on this thread just now.  The backend's event closes the
        program's row."""
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        now = time.monotonic_ns()
        start = now - int(duration_secs * 1e9)
        row = getattr(self._tls, "compiling", None)
        if row is None or (phase == "trace" and start > row.start_ns):
            # (a row still open that a new trace does not contain is a
            # trace that no compile followed: dropped)
            row = self._tls.compiling = CompileRow(
                "", threading.current_thread().name, start, 0)
        row.start_ns = min(row.start_ns, start)
        if phase == "trace":
            # an outer function's trace contains its inner functions',
            # which ended first: it takes their place
            row.trace_s = duration_secs
        elif phase == "lower":
            row.lower_s += duration_secs
        elif phase == "cache_read":
            row.cache_read_s += duration_secs
        elif phase == "saved":
            row.saved_s += duration_secs
        else:
            self._tls.compiling = None
            row.fn, row.end_ns = str(kw.get("fun_name", "")), now
            row.compile_s = max(0.0, duration_secs - row.cache_read_s)
            self._keep_compile(row)

    def _on_compile_event(self, event: str, **kw) -> None:
        """``jax.monitoring`` event listener: the persistent cache's
        verdict on the program this thread is compiling."""
        verdict = _CACHE_EVENTS.get(event)
        row = getattr(self._tls, "compiling", None)
        if verdict is not None and row is not None:
            row.cache = verdict

    def _keep_compile(self, row: CompileRow) -> None:
        with self._lock:
            self._compiles.append(row)
            if len(self._compiles) > COMPILE_LOG_MAX:
                self._compiles.popleft()
                self.compiles_dropped += 1
            self.compile_count += 1
            self.compile_cache_hits += row.cache == "hit"
            self.compile_cache_misses += row.cache == "miss"
            secs = self.compile_seconds
            secs["trace"] += row.trace_s
            secs["lower"] += row.lower_s
            secs["compile"] += row.compile_s
            secs["cache_read"] += row.cache_read_s
        parent = _current.get()
        if self.recording and parent is not None and parent.sampled:
            self.record(
                Tracepoint.RUNTIME_COMPILE, row.start_ns, row.end_ns,
                {"fn": row.fn, "cache": row.cache, "trace_s": row.trace_s,
                 "lower_s": row.lower_s, "compile_s": row.compile_s,
                 "cache_read_s": row.cache_read_s}, parent=parent)

    def compile_log(self) -> list[CompileRow]:
        """The programs compiled since :func:`install`, oldest first
        (the newest ``COMPILE_LOG_MAX``; ``compiles_dropped`` counts
        what it pushed out)."""
        with self._lock:
            return list(self._compiles)

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
    jax.monitoring.register_event_duration_secs_listener(
        tracer._on_compile_duration)
    jax.monitoring.register_event_listener(tracer._on_compile_event)
    tracer._hooked = True


def uninstall(tracer: Tracer) -> None:
    """Undo :func:`install` if ``tracer`` is still the one installed;
    its ring and its compile log stay readable."""
    global _installed
    if tracer is _installed and tracer is not NOOP_TRACER:
        tracer._hooked = False
        gc.callbacks.remove(tracer.gc_hook)
        for undo, listener in (
                (jax.monitoring.unregister_event_duration_listener,
                 tracer._on_compile_duration),
                (jax.monitoring.unregister_event_listener,
                 tracer._on_compile_event)):
            # (somebody cleared jax.monitoring's listeners: nothing to undo)
            with contextlib.suppress(AssertionError, ValueError):
                undo(listener)
        probe = tracer._probe
        if probe is not None:
            probe.join(timeout=1.0)     # it ends at its next wake
        _installed = NOOP_TRACER


def process_tracer() -> Tracer:
    """The tracer :func:`install` made the process's (``NOOP_TRACER``
    before any): whose runtime counters ``/metrics`` shows."""
    return _installed


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
