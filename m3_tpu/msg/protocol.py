"""Framed binary wire protocol: the socket data plane.

Equivalent of the reference's two TCP wire stacks: the aggregator's
rawtcp ingest protocol (protobuf `UnaggregatedIterator` loop,
`src/aggregator/server/rawtcp/server.go:125`, messages encoded by
`src/metrics/encoding/protobuf/unaggregated_iterator.go`) and m3msg's
size-prefixed protobuf framing (`src/msg/protocol/proto/encoder.go:49-52`,
`decoder.go:64`).  Protobuf collapses to struct-packed little-endian
frames (SURVEY.md §7: msgpack/protobuf wire codecs deliberately do not
carry over); the framing contract is the same: length prefix, checksum,
typed payload, resynchronization-free streams.

Frame layout:   [len u32][type u8][crc u32][payload: len bytes]
                crc = adler32(type byte + payload) — a torn/corrupt frame
                kills the connection (sender retries), never desyncs.

Payload codecs:
  METRIC_BATCH  untimed metric batch for aggregator ingest
  BUS_*         publish/deliver/ack for the message bus transport
"""

from __future__ import annotations

import socket
import struct
from dataclasses import dataclass

import numpy as np

from m3_tpu.core.idbytes import PackedIds
from m3_tpu.persist.digest import digest

_HDR = struct.Struct("<IBI")
MAX_FRAME = 64 << 20

# frame types
METRIC_BATCH = 1
BUS_HELLO = 2
BUS_PUBLISH = 3
BUS_DELIVER = 4
BUS_ACK = 5
OK = 6
ERROR = 7
# (8/9 are the query federation frames in query/remote.py;
#  16-18 dbnode RPC in server/rpc.py; 24-26 the KV control plane.)
TIMED_BATCH = 11        # MetricBatch payload; samples land by own time
PASSTHROUGH_BATCH = 12  # pre-aggregated, carries a storage policy
FORWARDED_BATCH = 13    # stage-N pipeline outputs for the next stage
INGEST_HELLO = 10       # client opts into per-frame acks (flags u32)
INGEST_ACK = 14         # server: frame fully ingested (sample count u32)
INGEST_BACKOFF = 15     # server shed the frame: retry after (ms u32)
INGEST_TRACE = 21       # trace-context preamble: applies to the NEXT
                        # batch frame on this connection (17-byte
                        # instrument.tracing.TraceContext wire form)


class ProtocolError(ConnectionError):
    pass


def connect(address, timeout: float = 5.0) -> socket.socket:
    """Dial a wire peer: create_connection + TCP_NODELAY with the
    close-on-setup-failure contract every client needs (a raise after
    the connect must not leak the half-set-up socket).  The one shared
    implementation of the pattern m3lint's resource-hygiene rule
    polices at call sites."""
    s = socket.create_connection(address, timeout=timeout)
    try:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except BaseException:
        s.close()
        raise
    return s


def send_frame(sock: socket.socket, ftype: int, payload: bytes) -> None:
    crc = digest(bytes([ftype]) + payload)
    sock.sendall(_HDR.pack(len(payload), ftype, crc) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except (socket.timeout, TimeoutError):
            if buf:
                # A timeout after partial data would desync the stream —
                # fatal; a timeout at a frame boundary is a clean poll.
                raise ProtocolError("timeout mid-frame") from None
            raise
        if not chunk:
            return None  # clean EOF only before a frame starts
        buf.extend(chunk)
    return bytes(buf)


def recv_frame(sock: socket.socket) -> tuple[int, bytes] | None:
    """(type, payload) or None on EOF.  Raises ProtocolError on a torn
    or corrupt frame — callers drop the connection (the reference's
    decoder errors close the rawtcp conn the same way)."""
    hdr = _recv_exact(sock, _HDR.size)
    if hdr is None:
        return None
    plen, ftype, crc = _HDR.unpack(hdr)
    if plen > MAX_FRAME:
        raise ProtocolError(f"frame too large: {plen}")
    payload = _recv_exact(sock, plen)
    if payload is None:
        raise ProtocolError("EOF mid-frame")
    if digest(bytes([ftype]) + payload) != crc:
        raise ProtocolError("frame checksum mismatch")
    return ftype, payload


# -- metric batch codec (the unaggregated wire form) ------------------------


@dataclass(frozen=True)
class MetricBatch:
    """One ingest batch: parallel arrays + per-sample metric type.

    metric_types: uint8 array (MetricType values); ids: a sequence of
    bytes (a list, or core.idbytes.PackedIds from the columnar reader);
    values/times: float64/int64 arrays; agg_id: compressed aggregation
    bitmask applied to the whole batch (0 = default per-type)."""

    metric_types: np.ndarray
    ids: list
    values: np.ndarray
    times: np.ndarray
    agg_id: int = 0


def encode_metric_batch(b: MetricBatch) -> bytes:
    parts = [struct.pack("<IQ", len(b.ids), b.agg_id)]
    for i, sid in enumerate(b.ids):
        parts.append(struct.pack("<BH", int(b.metric_types[i]), len(sid)))
        parts.append(sid)
        parts.append(struct.pack("<qd", int(b.times[i]), float(b.values[i])))
    return b"".join(parts)


def decode_metric_batch(raw: bytes) -> MetricBatch:
    n, agg_id = struct.unpack_from("<IQ", raw, 0)
    pos = 12
    mts = np.empty(n, np.uint8)
    ids = []
    values = np.empty(n, np.float64)
    times = np.empty(n, np.int64)
    for i in range(n):
        mt, idlen = struct.unpack_from("<BH", raw, pos)
        pos += 3
        ids.append(raw[pos : pos + idlen])
        pos += idlen
        t, v = struct.unpack_from("<qd", raw, pos)
        pos += 16
        mts[i] = mt
        times[i] = t
        values[i] = v
    if pos != len(raw):
        raise ProtocolError("metric batch trailing bytes")
    return MetricBatch(mts, ids, values, times, agg_id)


_TV = np.arange(16)


def decode_metric_columns(raw: bytes) -> MetricBatch:
    """``decode_metric_batch`` as columns: one pass over the records for
    their offsets (a record's length is known only from its own idlen
    field), then numpy for the types, times and values; the ids stay
    where the frame has them, as ``PackedIds`` runs (no ``bytes`` object
    per sample).  Same layout, same errors; the scalar reader stays as the
    oracle (tests/test_aggregator_service.py holds the two equal)."""
    n, agg_id = struct.unpack_from("<IQ", raw, 0)
    starts = [0] * n
    pos = 12
    try:
        for i in range(n):
            starts[i] = pos
            # type u8 + idlen u16 + time i64 + value f64 = 19, and the id
            pos += 19 + raw[pos + 1] + (raw[pos + 2] << 8)
    except IndexError:
        raise ProtocolError("metric batch truncated") from None
    if pos != len(raw):
        raise ProtocolError("metric batch trailing bytes")
    u8 = np.frombuffer(raw, np.uint8)
    st = np.asarray(starts, np.int64)
    idlens = u8[st + 1].astype(np.int64) | (u8[st + 2].astype(np.int64) << 8)
    # (n, 16) bytes: time then value of every record, as two i64 columns
    tv = u8[(st + 3 + idlens)[:, None] + _TV].view("<i8")
    return MetricBatch(u8[st], PackedIds(u8, st + 3, idlens),
                       tv[:, 1].copy().view(np.float64), tv[:, 0].copy(),
                       agg_id)


# -- aggregated batch codec (the aggregator's output over m3msg) -------------

_AGG_HDR = struct.Struct("<BHqII")


def encode_aggregated_batch(metric_type: int, policy: str, timestamp: int,
                            ids, row_ids: np.ndarray, row_types: np.ndarray,
                            values: np.ndarray) -> bytes:
    """One m3msg payload of flushed aggregates (reference
    aggregator/handler/writer protobuf ``AggregatedMetric``, batched):
    the ids of the chunk's series once, then one row per (series,
    aggregation type): index into the id table u32, type u8, value f64
    bits; every row is at ``timestamp`` (the window's end) under
    ``policy``."""
    p = policy.encode()
    lens = np.fromiter(map(len, ids), "<u2", len(ids))
    return b"".join((
        _AGG_HDR.pack(metric_type, len(p), timestamp, len(ids), len(values)),
        p, lens.tobytes(), b"".join(ids),
        np.ascontiguousarray(row_ids, "<u4").tobytes(),
        np.ascontiguousarray(row_types, np.uint8).tobytes(),
        np.ascontiguousarray(values, "<f8").tobytes()))


def decode_aggregated_batch(raw: bytes):
    """-> (metric_type, policy str, timestamp, ids list of bytes,
    row_ids u32, row_types u8, values f64)."""
    mt, lp, ts, n_ids, n_rows = _AGG_HDR.unpack_from(raw, 0)
    pos = _AGG_HDR.size
    policy = raw[pos:pos + lp].decode()
    pos += lp
    lens = np.frombuffer(raw, "<u2", n_ids, pos)
    pos += 2 * n_ids
    ends = pos + np.cumsum(lens, dtype=np.int64)
    ids = [raw[a:b] for a, b in zip((ends - lens).tolist(), ends.tolist())]
    pos = int(ends[-1]) if n_ids else pos
    row_ids = np.frombuffer(raw, "<u4", n_rows, pos)
    pos += 4 * n_rows
    row_types = np.frombuffer(raw, np.uint8, n_rows, pos)
    pos += n_rows
    values = np.frombuffer(raw, "<f8", n_rows, pos)
    if pos + 8 * n_rows != len(raw):
        raise ProtocolError("aggregated batch trailing bytes")
    return mt, policy, ts, ids, row_ids, row_types, values


def encode_passthrough_batch(policy: str, ids, values, times) -> bytes:
    """PASSTHROUGH_BATCH payload: storage policy string + parallel
    (id, time, value) entries (reference aggregator.go:86 AddPassthrough
    carries metric + policy)."""
    p = policy.encode()
    parts = [struct.pack("<HI", len(p), len(ids)), p]
    for i, sid in enumerate(ids):
        parts.append(struct.pack("<H", len(sid)))
        parts.append(sid)
        parts.append(struct.pack("<qd", int(times[i]), float(values[i])))
    return b"".join(parts)


def decode_passthrough_batch(raw: bytes):
    lp, n = struct.unpack_from("<HI", raw, 0)
    pos = 6
    policy = raw[pos:pos + lp].decode()
    pos += lp
    ids = []
    values = np.empty(n, np.float64)
    times = np.empty(n, np.int64)
    for i in range(n):
        (idlen,) = struct.unpack_from("<H", raw, pos)
        pos += 2
        ids.append(raw[pos:pos + idlen])
        pos += idlen
        t, v = struct.unpack_from("<qd", raw, pos)
        pos += 16
        times[i] = t
        values[i] = v
    if pos != len(raw):
        raise ProtocolError("passthrough batch trailing bytes")
    return policy, ids, values, times


def encode_forwarded_batch(policy: str, entries) -> bytes:
    """FORWARDED_BATCH payload (reference forwarded_writer.go wire
    role): storage policy + per-entry (ForwardSpec, value, ts).  The
    spec's remaining tail is flattened as op records: kind 0 =
    transformation (type byte), kind 1 = applied rollup (id +
    aggregation mask) — enough to reconstruct the next stages."""
    from m3_tpu.metrics.pipeline import AppliedRollupOp, TransformationOp

    p = policy.encode()
    parts = [struct.pack("<HI", len(p), len(entries)), p]
    for spec, v, ts in entries:
        parts.append(struct.pack("<H", len(spec.id)))
        parts.append(spec.id)
        parts.append(struct.pack("<QqdB", int(spec.aggregation_id),
                                 int(ts), float(v), len(spec.tail)))
        for op in spec.tail:
            if isinstance(op, TransformationOp):
                parts.append(struct.pack("<BB", 0, int(op.type)))
            elif isinstance(op, AppliedRollupOp):
                parts.append(struct.pack("<BH", 1, len(op.id)))
                parts.append(op.id)
                parts.append(struct.pack("<Q", int(op.aggregation_id)))
            else:
                raise ProtocolError(f"unencodable forwarded op {op!r}")
    return b"".join(parts)


def decode_forwarded_batch(raw: bytes):
    """Returns (policy str, entries list of (ForwardSpec, value, ts))."""
    from m3_tpu.aggregator.engine import ForwardSpec
    from m3_tpu.metrics.aggregation import AggregationID
    from m3_tpu.metrics.pipeline import AppliedRollupOp, TransformationOp
    from m3_tpu.metrics.transformation import TransformationType

    lp, n = struct.unpack_from("<HI", raw, 0)
    pos = 6
    policy = raw[pos:pos + lp].decode()
    pos += lp
    entries = []
    for _ in range(n):
        (idlen,) = struct.unpack_from("<H", raw, pos)
        pos += 2
        sid = raw[pos:pos + idlen]
        pos += idlen
        agg, ts, v, nops = struct.unpack_from("<QqdB", raw, pos)
        pos += 25
        tail = []
        for _ in range(nops):
            (kind,) = struct.unpack_from("<B", raw, pos)
            pos += 1
            if kind == 0:
                (tt,) = struct.unpack_from("<B", raw, pos)
                pos += 1
                tail.append(TransformationOp(TransformationType(tt)))
            elif kind == 1:
                (oplen,) = struct.unpack_from("<H", raw, pos)
                pos += 2
                oid = raw[pos:pos + oplen]
                pos += oplen
                (oagg,) = struct.unpack_from("<Q", raw, pos)
                pos += 8
                tail.append(AppliedRollupOp(oid, AggregationID(oagg)))
            else:
                raise ProtocolError(f"bad forwarded op kind {kind}")
        entries.append((ForwardSpec(sid, AggregationID(agg), tuple(tail)),
                        v, ts))
    if pos != len(raw):
        raise ProtocolError("forwarded batch trailing bytes")
    return policy, entries


# -- ingest ack / load-shed payloads ----------------------------------------

HELLO_WANT_ACKS = 1  # INGEST_HELLO flag: reply ACK/BACKOFF per frame


def encode_ingest_hello(flags: int = HELLO_WANT_ACKS) -> bytes:
    return struct.pack("<I", flags)


def decode_ingest_hello(raw: bytes) -> int:
    return struct.unpack_from("<I", raw, 0)[0]


def encode_ingest_ack(n_samples: int) -> bytes:
    return struct.pack("<I", n_samples)


def decode_ingest_ack(raw: bytes) -> int:
    return struct.unpack_from("<I", raw, 0)[0]


def encode_ingest_backoff(retry_after_ms: int) -> bytes:
    return struct.pack("<I", retry_after_ms)


def decode_ingest_backoff(raw: bytes) -> int:
    return struct.unpack_from("<I", raw, 0)[0]


def encode_ingest_trace(ctx_wire: bytes) -> bytes:
    """INGEST_TRACE payload: the packed TraceContext itself.  Sent by a
    sampled client immediately BEFORE a batch frame; a preamble frame
    (rather than a batch-payload trailer) keeps the four batch codecs'
    exact-length contracts untouched.  NOTE a pre-round-10 SERVER still
    drops the connection on the unknown frame type (and would equally
    reject a batch trailer — the batch decoders raise on trailing
    bytes), so there is no fully-compatible in-band carrier: upgrade
    servers before enabling sampled ingest tracing, and the client
    (InstanceQueue) auto-disables its preamble on a connection that
    dies after one — a mixed fleet degrades to untraced, never to a
    reconnect loop."""
    return bytes(ctx_wire)


def decode_ingest_trace(raw: bytes):
    from m3_tpu.instrument.tracing import TraceContext

    if len(raw) < TraceContext.WIRE_SIZE:
        raise ProtocolError("short ingest trace frame")
    return TraceContext.from_wire(raw, 0)


# -- bus transport payloads -------------------------------------------------


def encode_bus_hello(service: str, instance_id: str) -> bytes:
    s, i = service.encode(), instance_id.encode()
    return struct.pack("<HH", len(s), len(i)) + s + i


def decode_bus_hello(raw: bytes) -> tuple[str, str]:
    ls, li = struct.unpack_from("<HH", raw, 0)
    s = raw[4 : 4 + ls].decode()
    i = raw[4 + ls : 4 + ls + li].decode()
    return s, i


def encode_bus_publish(shard: int, payload: bytes) -> bytes:
    return struct.pack("<I", shard) + payload


def decode_bus_publish(raw: bytes) -> tuple[int, bytes]:
    (shard,) = struct.unpack_from("<I", raw, 0)
    return shard, raw[4:]


def encode_bus_deliver(mid: int, shard: int, payload: bytes) -> bytes:
    return struct.pack("<QI", mid, shard) + payload


def decode_bus_deliver(raw: bytes) -> tuple[int, int, bytes]:
    mid, shard = struct.unpack_from("<QI", raw, 0)
    return mid, shard, raw[12:]


def encode_bus_ack(mid: int) -> bytes:
    return struct.pack("<Q", mid)


def decode_bus_ack(raw: bytes) -> int:
    return struct.unpack_from("<Q", raw, 0)[0]
