"""Prometheus remote write/read: snappy + protobuf wire handling.

Equivalent of the reference's remote handlers
(`src/query/api/v1/handler/prometheus/remote/{write.go,read.go}`):
POST bodies are snappy-compressed `prompb.WriteRequest`/`ReadRequest`
messages.  No protobuf runtime is required — the prompb subset is four
tiny messages hand-decoded from the wire format (the schema is frozen
by the Prometheus remote-storage spec):

    WriteRequest { repeated TimeSeries timeseries = 1; }
    TimeSeries   { repeated Label labels = 1; repeated Sample samples = 2; }
    Label        { string name = 1; string value = 2; }
    Sample       { double value = 1; int64 timestamp = 2; }  # ms!

    ReadRequest  { repeated Query queries = 1; }
    Query        { int64 start_timestamp_ms = 1; int64 end_timestamp_ms = 2;
                   repeated LabelMatcher matchers = 3; }
    LabelMatcher { Type type = 1 (EQ/NEQ/RE/NRE); string name = 2;
                   string value = 3; }
    ReadResponse { repeated QueryResult results = 1; }
    QueryResult  { repeated TimeSeries timeseries = 1; }

A WriteRequest is decoded by :func:`decode_write_request`, which the
write handler calls: columns (one `Document`, one int64 nanosecond
timestamp, one f64 value per sample, in wire order).  It walks each
series by field LENGTH only: the bytes of its leading run of label
fields are the key of a :class:`SeriesCache` that holds the series'
`Document` (a sender's label sets repeat scrape after scrape, so after
the first request nothing of a label is parsed), and each sample field
leaves its offset.  After the walk numpy reads every sample body of the
shape `[0x09 value]? [0x10 timestamp]?` at once (what a canonical
proto3 encoder emits: a zero field is omitted).  What has another shape
goes through the scalar reader, field by field: a series with unknown
fields, samples before labels or a sample of 128 bytes or more through
`_parse_timeseries`; a sample with the timestamp before the value, an
unknown field or a timestamp that is not one in-range varint through
`_parse_sample`.  The scalar reader also parses a cache miss's labels
and a read response's series.

:func:`parse_write_request` is the scalar reader over a whole body: no
handler calls it; it stays for clients and as the oracle the tests hold
the columns to.
"""

from __future__ import annotations

import struct
import threading
from dataclasses import dataclass, field

import numpy as np

from m3_tpu.instrument import tracing
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.server import snappy

# ---------------------------------------------------------------------------
# Minimal protobuf wire reader/writer
# ---------------------------------------------------------------------------


class ProtoError(ValueError):
    pass


def _uvarint(data: bytes, pos: int) -> tuple[int, int]:
    out = 0
    shift = 0
    while True:
        if pos >= len(data):
            raise ProtoError("truncated varint")
        b = data[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7
        if shift > 70:
            raise ProtoError("varint too long")


def _field_at(data: bytes, pos: int):
    """The field that starts at ``pos`` -> (field_number, wire_type,
    value, position after it)."""
    key, pos = _uvarint(data, pos)
    fnum, wtype = key >> 3, key & 7
    if wtype == 0:  # varint
        val, pos = _uvarint(data, pos)
    elif wtype == 1:  # 64-bit
        val = data[pos : pos + 8]
        pos += 8
    elif wtype == 2:  # length-delimited
        ln, pos = _uvarint(data, pos)
        val = data[pos : pos + ln]
        if len(val) != ln:
            raise ProtoError("truncated length-delimited field")
        pos += ln
    elif wtype == 5:  # 32-bit
        val = data[pos : pos + 4]
        pos += 4
    else:
        raise ProtoError(f"unsupported wire type {wtype}")
    return fnum, wtype, val, pos


def _fields(data: bytes):
    """Yield (field_number, wire_type, value) triples."""
    pos = 0
    while pos < len(data):
        fnum, wtype, val, pos = _field_at(data, pos)
        yield fnum, wtype, val


def _emit_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _emit_field(fnum: int, wtype: int, payload: bytes) -> bytes:
    return _emit_varint((fnum << 3) | wtype) + payload


def _emit_len(fnum: int, payload: bytes) -> bytes:
    return _emit_field(fnum, 2, _emit_varint(len(payload)) + payload)


def _signed(v: int) -> int:
    """protobuf int64 varints are two's-complement in 64 bits."""
    return v - (1 << 64) if v >= 1 << 63 else v


# ---------------------------------------------------------------------------
# prompb messages
# ---------------------------------------------------------------------------


@dataclass
class PromTimeSeries:
    labels: dict            # bytes -> bytes
    samples: list           # [(timestamp_nanos, value)]


def _parse_label(data: bytes) -> tuple[bytes, bytes]:
    name = value = b""
    for fnum, _wt, val in _fields(data):
        if fnum == 1:
            name = val
        elif fnum == 2:
            value = val
    return name, value


def _parse_sample(data: bytes) -> tuple[int, float]:
    value = 0.0
    ts_ms = 0
    for fnum, wt, val in _fields(data):
        if fnum == 1 and wt == 1:
            value = struct.unpack("<d", val)[0]
        elif fnum == 2 and wt == 0:
            ts_ms = _signed(val)
    return ts_ms * 10**6, value  # ms → nanos


def _parse_timeseries(data: bytes) -> PromTimeSeries:
    labels = {}
    samples = []
    for fnum, _wt, val in _fields(data):
        if fnum == 1:
            n, v = _parse_label(val)
            labels[n] = v
        elif fnum == 2:
            samples.append(_parse_sample(val))
    return PromTimeSeries(labels, samples)


def parse_write_request(body: bytes) -> list[PromTimeSeries]:
    """snappy-compressed WriteRequest → series list, every series
    through the scalar reader.  For clients and tests: the write
    handler calls :func:`decode_write_request`."""
    with tracing.span(Tracepoint.API_WRITE_SNAPPY):
        raw = snappy.decompress(body)
    out = []
    with tracing.span(Tracepoint.API_WRITE_PROTOBUF):
        for fnum, _wt, val in _fields(raw):
            if fnum == 1:
                out.append(_parse_timeseries(val))
    return out


# ---------------------------------------------------------------------------
# WriteRequest -> columns
# ---------------------------------------------------------------------------

# Bytes a SeriesCache may pin before it is cleared whole, counted as if
# nothing else held its Documents.  While the index holds them too (a
# sender whose label sets repeat) an entry costs its key and its slot,
# about an eighth of what it is charged.  A series the new-series
# limiter refused or the index expired is pinned by the cache alone, so
# the charge is the whole of it: a churning sender, whatever the sizes
# of its labels, fills 1 GiB (~400,000 series of 11 labels) and no more.
MAX_CACHE_BYTES = 1 << 30

# What an entry is charged: against tracemalloc over 1 to 100 labels of
# 1 to 70,000 bytes and 171 to 20,000 entries this reads 0-8 % above
# what the entry holds (the label bytes three times: key, id, fields; a
# Field and two bytes objects per label; the Document, its tuple and
# the dict slot).
_ENTRY_BYTES, _LABEL_BYTES, _PER_KEY_BYTE = 224, 160, 3

# milliseconds beyond which ms * 10**6 leaves int64
_MAX_MS = (2**63 - 1) // 10**6


class SeriesCache:
    """Label-field bytes of a TimeSeries -> its Document.

    The mapping is a pure function of the key (``document_of`` applied
    to the labels the scalar reader parses from those bytes), so there
    is nothing to invalidate; two byte orders of one label set are two
    keys for equal Documents.  The handler threads read ``docs`` without
    a lock (a dict read is atomic under the GIL); a miss takes the lock,
    so the bytes charged are those held."""

    def __init__(self, document_of):
        self.document_of = document_of  # labels dict -> Document
        self.docs: dict = {}
        self.bytes = 0                  # charged for what docs holds
        self._mu = threading.Lock()

    def add(self, key: bytes) -> object:
        labels = _parse_timeseries(key).labels
        cost = (_ENTRY_BYTES + _LABEL_BYTES * len(labels)
                + _PER_KEY_BYTE * len(key))
        with self._mu:
            doc = self.docs.get(key)
            if doc is None:
                if self.bytes + cost > MAX_CACHE_BYTES:
                    self.docs.clear()
                    self.bytes = 0
                doc = self.docs[key] = self.document_of(labels)
                self.bytes += cost
        return doc


class _NotFast(Exception):
    """A series whose shape the column walk does not know."""


def decode_write_request(body: bytes, cache: SeriesCache):
    """snappy-compressed WriteRequest -> (docs, ts, vals, n_series,
    n_hits): one Document per sample in wire order, nanosecond
    timestamps (int64), values (float64: the wire's 64 bits, NaN
    payloads included), the series the body holds and how many of them
    the cache already knew.  Equal to :func:`parse_write_request` and
    ``cache.document_of`` per series on every input."""
    with tracing.span(Tracepoint.API_WRITE_SNAPPY):
        raw = snappy.decompress(body)
    with tracing.span(Tracepoint.API_WRITE_PROTOBUF):
        return _decode_columns(raw, cache)


def _decode_columns(raw: bytes, cache: SeriesCache):
    docs = []    # one per sample
    soff = []    # where each fast-walked sample field starts; -1: scalar
    scalar = []  # (sample, nanos, value) read field by field
    n_series = n_hits = 0
    known = cache.docs.get
    pos, n = 0, len(raw)
    while pos < n:
        mark = len(soff)
        try:  # the walk over the wire bytes, and nothing else
            if raw[pos] != 0x0A:
                raise _NotFast
            # a TimeSeries at [p, end): tag and length inline
            ln = raw[pos + 1]
            p = pos + 2
            if ln >= 0x80:
                ln, p = _uvarint(raw, pos + 1)
            end = p + ln
            if end > n:
                raise ProtoError("truncated length-delimited field")
            # step over the leading label fields by their lengths
            q = p
            while q < end and raw[q] == 0x0A:
                ln = raw[q + 1]
                if ln >= 0x80:
                    ln, q = _uvarint(raw, q + 1)
                    q += ln
                else:
                    q += 2 + ln
            labels_end = q
            # then sample fields with a one-byte length, to the end
            while q < end:
                if raw[q] != 0x12:
                    raise _NotFast
                ln = raw[q + 1]
                if ln >= 0x80:
                    raise _NotFast
                soff.append(q)
                q += 2 + ln
            if q > end:
                raise _NotFast
        except (_NotFast, IndexError):
            del soff[mark:]
            fnum, _wt, val, pos = _field_at(raw, pos)
            if fnum != 1:
                continue
            n_series += 1
            series = _parse_timeseries(val)
            doc = cache.document_of(series.labels)
            for t_nanos, v in series.samples:
                scalar.append((len(docs), t_nanos, v))
                docs.append(doc)
                soff.append(-1)
            continue
        key = raw[p:labels_end]
        doc = known(key)
        if doc is None:
            doc = cache.add(key)
        else:
            n_hits += 1
        n_series += 1
        k = len(soff) - mark
        if k == 1:
            docs.append(doc)
        else:
            docs += [doc] * k
        pos = end
    ts, vals, odd = _gather(raw, soff)
    # a sample of another shape than the gather reads: through the
    # scalar reader, which says what is wrong with it if anything is
    for i in odd:
        body = soff[i] + 2
        scalar.append((i, *_parse_sample(raw[body:body + raw[body - 1]])))
    for i, t_nanos, v in scalar:
        ts[i] = t_nanos
        vals[i] = v
    return docs, ts, vals, n_series, n_hits


def _gather(raw: bytes, soff: list):
    """Offsets of Sample fields (tag, one length byte, body) in the wire
    bytes -> (nanoseconds int64, values float64, odd).  Reads a body of
    `[0x09 value]? [0x10 timestamp]?`: a field the encoder omitted is
    zero.  ``odd`` lists the samples of any other shape, or whose
    timestamp leaves int64 as nanoseconds; they and offsets of -1 read
    0 here."""
    # (a short body's reads run past it: keep them inside the buffer)
    buf = np.frombuffer(raw + bytes(24), np.uint8)
    s = np.asarray(soff, np.int64)
    walked = s >= 0
    s = np.where(walked, s, len(raw))       # zeros: an empty body
    ln = buf[s + 1].astype(np.int64)
    s += 2
    end = s + ln
    valued = (ln >= 9) & (buf[s] == 0x09)
    vals = np.zeros(len(s))
    rows = s if valued.all() else s[valued]
    vals[valued] = buf[rows[:, None] + np.arange(1, 9)].view("<f8").reshape(-1)
    t = s + 9 * valued                      # the timestamp field, if any
    w = end - t - 1                         # its varint's width; -1: none
    timed = w >= 0
    odd = timed & ((w == 0) | (w > 10) | (buf[t] != 0x10)
                   | (buf[end - 1] >= 0x80))
    ms = np.zeros(len(s), np.uint64)
    for width in np.unique(w[timed & ~odd]):  # one width in practice
        rows = np.nonzero((w == width) & ~odd)[0]
        m = buf[t[rows, None] + np.arange(1, width + 1)]
        odd[rows] = (m[:, :-1] < 0x80).any(axis=1)
        if width == 10:
            odd[rows] |= m[:, 9] > 1
        acc = np.zeros(len(rows), np.uint64)
        for j in range(width):
            acc |= (m[:, j] & 0x7F).astype(np.uint64) << np.uint64(7 * j)
        ms[rows] = acc
    ms = ms.view(np.int64)
    odd |= (ms > _MAX_MS) | (ms < -_MAX_MS)
    ms[odd] = 0
    return ms * 10**6, vals, np.nonzero(odd)[0].tolist()


@dataclass
class PromMatcher:
    type: int  # 0 EQ, 1 NEQ, 2 RE, 3 NRE
    name: bytes
    value: bytes


@dataclass
class PromQuery:
    start_nanos: int
    end_nanos: int
    matchers: list = field(default_factory=list)


def _parse_matcher(data: bytes) -> PromMatcher:
    t = 0
    name = value = b""
    for fnum, wt, val in _fields(data):
        if fnum == 1 and wt == 0:
            t = val
        elif fnum == 2:
            name = val
        elif fnum == 3:
            value = val
    return PromMatcher(t, name, value)


def parse_read_request(body: bytes) -> list[PromQuery]:
    raw = snappy.decompress(body)
    queries = []
    for fnum, _wt, val in _fields(raw):
        if fnum != 1:
            continue
        q = PromQuery(0, 0)
        for f2, w2, v2 in _fields(val):
            if f2 == 1 and w2 == 0:
                q.start_nanos = _signed(v2) * 10**6
            elif f2 == 2 and w2 == 0:
                q.end_nanos = _signed(v2) * 10**6
            elif f2 == 3:
                q.matchers.append(_parse_matcher(v2))
        queries.append(q)
    return queries


def _emit_timeseries(ts: PromTimeSeries) -> bytes:
    parts = []
    for name, value in sorted(ts.labels.items()):
        parts.append(_emit_len(1, _emit_len(1, name) + _emit_len(2, value)))
    for t_nanos, v in ts.samples:
        sample = _emit_field(1, 1, struct.pack("<d", v)) + _emit_field(
            2, 0, _emit_varint((t_nanos // 10**6) & ((1 << 64) - 1))
        )
        parts.append(_emit_len(2, sample))
    return b"".join(parts)


def build_read_response(results: list[list[PromTimeSeries]]) -> bytes:
    """QueryResult per query → snappy-compressed ReadResponse."""
    out = []
    for series_list in results:
        qr = b"".join(_emit_len(1, _emit_timeseries(s)) for s in series_list)
        out.append(_emit_len(1, qr))
    return snappy.compress(b"".join(out))


def build_write_request(series_list: list[PromTimeSeries]) -> bytes:
    """For clients/tests: series → snappy-compressed WriteRequest."""
    body = b"".join(_emit_len(1, _emit_timeseries(s)) for s in series_list)
    return snappy.compress(body)
