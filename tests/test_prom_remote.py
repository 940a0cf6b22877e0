"""Prometheus remote write/read: snappy codec, prompb wire, endpoints.

Reference model: `src/query/api/v1/handler/prometheus/remote` and the
prompb remote-storage protocol (snappy-compressed protobuf bodies).
"""

import gc
import json
import random
import struct
import sys
import threading
import time
import tracemalloc
import urllib.error
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from m3_tpu.index.doc import Document
from m3_tpu.instrument.tracing import Tracepoint
from m3_tpu.server import prom_remote, snappy
from m3_tpu.server.http_api import ApiContext, _Handler, serve_background
from m3_tpu.server.prom_remote import (
    PromMatcher, PromQuery, PromTimeSeries, SeriesCache, _emit_field,
    _emit_len, _emit_varint, build_read_response, build_write_request,
    decode_write_request, parse_read_request, parse_write_request,
)
from m3_tpu.storage.database import Database, DatabaseOptions, NamespaceOptions

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import wire  # noqa: E402

BLOCK = 2 * 3600 * 10**9
START = (1_700_000_000 * 10**9) // BLOCK * BLOCK
NS = NamespaceOptions(num_shards=2, slot_capacity=1 << 10,
                      sample_capacity=1 << 12)


class TestSnappy:
    def test_roundtrip(self):
        for payload in (b"", b"a", b"hello world" * 100, bytes(range(256)) * 40):
            assert snappy.decompress(snappy.compress(payload)) == payload

    def test_decodes_real_copies(self):
        """A stream with back-reference copies (what real snappy
        encoders emit for repeated data): literal 'abcd' then a copy of
        it, plus an overlapping RLE-style copy."""
        # uncompressed: b"abcdabcdx" + b"x"*6 (15 bytes)
        body = bytearray()
        body += snappy._write_uvarint(15)
        body += bytes([3 << 2]) + b"abcd"          # literal len 4
        body += bytes([(0 << 5) | (0 << 2) | 1, 4])  # copy1: len 4, off 4
        body += bytes([0 << 2]) + b"x"             # literal len 1
        body += bytes([(2 << 2) | 1, 1])           # copy1: len 6? no — len=(2)+4=6 off 1 → xxxxxx
        out = snappy.decompress(bytes(body))
        assert out == b"abcdabcdx" + b"x" * 6  # overlapping copy extends run

    def test_corrupt_raises(self):
        good = snappy.compress(b"hello world")
        with pytest.raises(snappy.SnappyError):
            snappy.decompress(good[:-3])
        with pytest.raises(snappy.SnappyError):
            # bad offset: copy before any output
            snappy.decompress(snappy._write_uvarint(4) + bytes([1, 9]))


class TestPrompb:
    def _series(self):
        return [
            PromTimeSeries(
                {b"__name__": b"up", b"host": b"a"},
                [(START + 10**9, 1.0), (START + 2 * 10**9, 0.5)],
            ),
            PromTimeSeries({b"__name__": b"up", b"host": b"b"},
                           [(START + 10**9, 2.0)]),
        ]

    def test_write_request_roundtrip(self):
        body = build_write_request(self._series())
        out = parse_write_request(body)
        assert len(out) == 2
        assert out[0].labels == {b"__name__": b"up", b"host": b"a"}
        assert out[0].samples == [(START + 10**9, 1.0), (START + 2 * 10**9, 0.5)]

    def test_read_response_parses_as_write_shape(self):
        # ReadResponse{results.timeseries} uses the same TimeSeries shape
        body = build_read_response([self._series()])
        raw = snappy.decompress(body)
        # outer field 1 (QueryResult), inner field 1 (TimeSeries)
        from m3_tpu.server.prom_remote import _fields, _parse_timeseries

        results = [v for f, _w, v in _fields(raw) if f == 1]
        assert len(results) == 1
        series = [
            _parse_timeseries(v) for f, _w, v in _fields(results[0]) if f == 1
        ]
        assert series[1].labels[b"host"] == b"b"

    def test_ms_precision_roundtrip(self):
        # remote protocol carries milliseconds; nanos round to ms
        ts = PromTimeSeries({b"x": b"y"}, [(1_700_000_000_123 * 10**6, 7.5)])
        out = parse_write_request(build_write_request([ts]))
        assert out[0].samples[0] == (1_700_000_000_123 * 10**6, 7.5)


class TestEndpoints:
    def test_remote_write_then_remote_read(self, tmp_path):
        db = Database(DatabaseOptions(root=str(tmp_path)),
                      namespaces={"default": NS})
        srv = serve_background(ApiContext(db))
        port = srv.server_address[1]

        series = [
            PromTimeSeries(
                {b"__name__": b"reqs", b"host": b"h%d" % i},
                [(START + k * 10**9, float(i * 100 + k)) for k in range(5)],
            )
            for i in range(3)
        ]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/v1/prom/remote/write",
            data=build_write_request(series),
            headers={"Content-Encoding": "snappy",
                     "Content-Type": "application/x-protobuf"},
        )
        assert urllib.request.urlopen(req).status == 204

        # remote read with an EQ matcher; the end timestamp is INCLUSIVE
        # per prompb semantics — the last sample sits exactly at end
        read_req = self._read_request(
            START, START + 4 * 10**9,
            [PromMatcher(0, b"__name__", b"reqs"),
             PromMatcher(2, b"host", b"h[01]")],
        )
        r = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/v1/prom/remote/read", data=read_req
        )
        resp = urllib.request.urlopen(r)
        assert resp.status == 200
        body = resp.read()
        raw = snappy.decompress(body)
        from m3_tpu.server.prom_remote import _fields, _parse_timeseries

        results = [v for f, _w, v in _fields(raw) if f == 1]
        series_out = [
            _parse_timeseries(v) for f, _w, v in _fields(results[0]) if f == 1
        ]
        hosts = {s.labels[b"host"] for s in series_out}
        assert hosts == {b"h0", b"h1"}
        s0 = [s for s in series_out if s.labels[b"host"] == b"h0"][0]
        assert [v for _, v in s0.samples] == [0.0, 1.0, 2.0, 3.0, 4.0]
        # PromQL over remote-written data works too
        t0 = START // 10**9
        q = (f"http://127.0.0.1:{port}/api/v1/query_range?"
             f"query=sum(reqs)&start={t0}&end={t0 + 4}&step=1s")
        out = json.load(urllib.request.urlopen(q))
        assert out["data"]["result"]
        srv.shutdown()
        db.close()

    @staticmethod
    def _read_request(start, end, matchers):
        from m3_tpu.server.prom_remote import (
            _emit_field, _emit_len, _emit_varint,
        )

        mparts = b"".join(
            _emit_len(3, _emit_field(1, 0, _emit_varint(m.type)) +
                      _emit_len(2, m.name) + _emit_len(3, m.value))
            for m in matchers
        )
        q = (_emit_field(1, 0, _emit_varint(start // 10**6)) +
             _emit_field(2, 0, _emit_varint(end // 10**6)) + mparts)
        return snappy.compress(_emit_len(1, q))


# -- the column decoder against the scalar reader ----------------------------

T_MS = 1_790_000_000_000          # a 6-byte varint, as from 2004 to 2248
STALE_NAN = 0x7FF0000000000002    # Prometheus' stale marker


def _document(tags: dict) -> Document:
    """The Document the write handlers build for a label set."""
    return Document.from_tags(_Handler._series_id(tags), tags)


def _oracle(body: bytes):
    """The scalar reader and the handler's per-sample loop, as they
    stood before the column decoder."""
    docs, ts, vals = [], [], []
    for s in parse_write_request(body):
        doc = _document(s.labels)
        for t_nanos, v in s.samples:
            docs.append(doc)
            ts.append(t_nanos)
            vals.append(v)
    return docs, np.asarray(ts, np.int64), np.asarray(vals, np.float64)


def _label(name: bytes, value: bytes) -> bytes:
    return _emit_len(1, _emit_len(1, name) + _emit_len(2, value))


def _labels(tags: dict) -> bytes:
    return b"".join(_label(k, v) for k, v in tags.items())


def _value(v) -> bytes:
    """Field 1 of a Sample from a float, or from 64 bits as an int."""
    return b"\x09" + (struct.pack("<Q", v) if isinstance(v, int)
                      else struct.pack("<d", v))


def _time(ms: int) -> bytes:
    return b"\x10" + _emit_varint(ms & ((1 << 64) - 1))


def _sample(*fields: bytes) -> bytes:
    return _emit_len(2, b"".join(fields))


def _request(*series: bytes) -> bytes:
    return snappy.compress(b"".join(_emit_len(1, s) for s in series))


def _padded_len(fnum: int, payload: bytes) -> bytes:
    """A length-delimited field whose length varint has a spare byte."""
    assert len(payload) < 128
    return bytes([(fnum << 3) | 2, len(payload) | 0x80, 0]) + payload


def _snappy_with_copies(raw: bytes) -> bytes:
    """`raw` as literals of its first half and back-references (copy
    with a 16-bit offset) to an earlier occurrence where there is one."""
    out = bytearray(snappy._write_uvarint(len(raw)))
    pos = 0
    while pos < len(raw):
        chunk = raw[pos:pos + 32]
        at = raw.rfind(chunk, 0, pos)
        if len(chunk) >= 4 and at >= 0 and pos - at < 1 << 16:
            out += bytes([((len(chunk) - 1) << 2) | 2])
            out += (pos - at).to_bytes(2, "little")
        else:
            out += bytes([(len(chunk) - 1) << 2]) + chunk
        pos += len(chunk)
    assert snappy.decompress(bytes(out)) == raw
    return bytes(out)


def _generator_body() -> bytes:
    tags = [{b"__name__": b"cpu_usage_user", b"hostname": b"host_%d" % i,
             b"region": b"eu-west-1", b"rack": b"%d" % (i % 7)}
            for i in range(50)]
    return wire.Template(tags, T_MS * 10**6).body(
        T_MS * 10**6, np.arange(50, dtype=np.float64) / 7)


UP_A = {b"__name__": b"up", b"host": b"a"}
UP_B = {b"__name__": b"up", b"host": b"b"}

BODIES = {
    "generator_shape": _generator_body,
    "several_samples_per_series": lambda: _request(
        _labels(UP_A) + b"".join(
            _sample(_value(0.5 * k), _time(T_MS + 15_000 * k))
            for k in range(5)),
        _labels(UP_B) + _sample(_value(7.0), _time(T_MS))),
    "zero_value_omitted": lambda: _request(
        _labels(UP_A) + _sample(_time(T_MS)),
        _labels(UP_B) + _sample(_value(3.0), _time(T_MS))),
    "zero_timestamp_omitted": lambda: _request(
        _labels(UP_A) + _sample(_value(3.0)) + _sample(),
        _labels(UP_B) + _sample(_value(4.0), _time(T_MS))),
    "timestamp_before_value": lambda: _request(
        _labels(UP_A) + _sample(_time(T_MS), _value(3.0)),
        _labels(UP_B) + _sample(_value(4.0), _time(T_MS))),
    "negative_timestamp": lambda: _request(
        _labels(UP_A) + _sample(_value(1.0), _time(-1))
        + _sample(_value(2.0), _time(-(1 << 40)))),
    "two_timestamp_widths": lambda: _request(
        _labels(UP_A) + _sample(_value(1.0), _time(T_MS))
        + _sample(_value(2.0), _time(127)) + _sample(_value(3.0), _time(-5)),
        _labels(UP_B) + _sample(_value(4.0), _time(1 << 42))),
    "label_over_127_bytes": lambda: _request(
        _labels({b"__name__": b"up", b"path": b"/x" * 100})
        + _sample(_value(1.0), _time(T_MS))),
    "series_over_127_bytes": lambda: _request(
        _labels({b"__name__": b"up", **{b"l%d" % k: b"v%d" % k
                                        for k in range(40)}})
        + _sample(_value(1.0), _time(T_MS))),
    "duplicate_label_names": lambda: _request(
        _label(b"__name__", b"up") + _label(b"host", b"a")
        + _label(b"host", b"z") + _sample(_value(1.0), _time(T_MS))),
    "one_label_set_in_two_byte_orders": lambda: _request(
        _label(b"__name__", b"up") + _label(b"host", b"a")
        + _sample(_value(1.0), _time(T_MS)),
        _label(b"host", b"a") + _label(b"__name__", b"up")
        + _sample(_value(2.0), _time(T_MS + 1))),
    "unknown_fields_3_and_4": lambda: _request(
        _labels(UP_A) + _sample(_value(1.0), _time(T_MS))
        + _emit_len(3, b"exemplar") + _emit_len(4, b"histogram"),
        _labels(UP_B) + _sample(_value(2.0), _time(T_MS))),
    "samples_before_labels": lambda: _request(
        _sample(_value(1.0), _time(T_MS)) + _labels(UP_A),
        _label(b"__name__", b"up") + _sample(_value(2.0), _time(T_MS))
        + _label(b"host", b"b")),
    "nan_with_payload": lambda: _request(
        _labels(UP_A) + _sample(_value(STALE_NAN), _time(T_MS))
        + _sample(_value(0xFFF8000000000123), _time(T_MS + 1)),
        # and through the scalar reader
        _labels(UP_B) + _sample(_time(T_MS), _value(STALE_NAN))),
    "infinities": lambda: _request(
        _labels(UP_A) + _sample(_value(float("inf")), _time(T_MS))
        + _sample(_value(float("-inf")), _time(T_MS + 1))
        + _sample(_value(-0.0), _time(T_MS + 2))),
    "empty_body": lambda: snappy.compress(b""),
    "series_without_samples_or_labels": lambda: _request(
        _labels(UP_A), _sample(_value(1.0), _time(T_MS)), b""),
    "unknown_top_level_field": lambda: snappy.compress(
        _emit_len(3, b"metadata") + _emit_len(1, _labels(UP_A) + _sample(
            _value(1.0), _time(T_MS))) + _emit_field(2, 0, _emit_varint(9))),
    "unknown_field_in_a_sample": lambda: _request(
        _labels(UP_A) + _sample(_value(1.0), _time(T_MS),
                                _emit_field(3, 0, _emit_varint(1))),
        _labels(UP_B) + _sample(_value(2.0), _time(5), b"\x18\x81\x01")),
    "sample_over_127_bytes": lambda: _request(
        _labels(UP_A) + _sample(_value(1.0), _time(T_MS),
                                _emit_len(5, b"x" * 200))),
    "non_canonical_varints": lambda: snappy.compress(
        # a timestamp padded to 10 and to 11 bytes; a label length and
        # a series length in two bytes
        _emit_len(1, _labels(UP_A)
                  + _sample(_value(1.0), b"\x10\x85" + b"\x80" * 8 + b"\x00")
                  + _sample(_value(2.0), b"\x10\x86" + b"\x80" * 9 + b"\x00"))
        + _padded_len(1, _padded_len(1, _label(b"a", b"b")[2:])
                      + _sample(_value(3.0), _time(T_MS)))),
    "odd_sample_shapes": lambda: _request(
        # two values (the last wins), a fixed64 where the timestamp's
        # varint belongs, a value of the wrong wire type, fields 3 and 2
        _labels(UP_A) + _sample(_value(1.0), _value(2.0))
        + _sample(_value(3.0), b"\x11" + struct.pack("<q", T_MS))
        + _sample(b"\x08\x05", _time(T_MS))
        + _sample(_emit_field(3, 0, _emit_varint(7)), _time(T_MS + 1)),
        _labels(UP_B) + _sample(_value(4.0), _time(T_MS), _time(T_MS + 2))),
    "empty_sample_at_the_end": lambda: _request(
        _labels(UP_A) + _sample(_value(1.0), _time(T_MS)),
        _labels(UP_B) + _sample(_value(2.0)) + _sample()),
    "snappy_with_back_references": lambda: _snappy_with_copies(
        snappy.decompress(_generator_body())),
}


def _outcome(decode, body):
    try:
        return decode(body), None
    except Exception as e:  # noqa: BLE001 - the type is what is compared
        return None, type(e)


def _assert_same(got, want) -> None:
    (docs, ts, vals), (wdocs, wts, wvals) = got[:3], want
    assert [d.id for d in docs] == [d.id for d in wdocs]
    assert docs == wdocs
    assert ts.dtype == wts.dtype == np.int64
    assert ts.tolist() == wts.tolist()
    assert vals.dtype == wvals.dtype == np.float64
    assert vals.view(np.uint64).tolist() == wvals.view(np.uint64).tolist()


def _decode_cold_and_warm(body):
    cache = SeriesCache(_document)
    cold = decode_write_request(body, cache)
    warm = decode_write_request(body, cache)
    _assert_same(warm, cold[:3])
    assert warm[3] == cold[3] and warm[4] >= cold[4]
    return warm


class TestColumnDecoder:
    @pytest.mark.parametrize("case", sorted(BODIES))
    def test_equal_to_the_scalar_reader(self, case):
        body = BODIES[case]()
        want = _oracle(body)
        got = _decode_cold_and_warm(body)
        _assert_same(got, want)
        assert got[3] == len(parse_write_request(body))
        if case == "generator_shape":
            assert got[3] == got[4] == 50 and len(got[0]) == 50

    def test_fast_shapes_are_cache_hits_and_the_rest_are_not(self):
        hits = {case: _decode_cold_and_warm(BODIES[case]())[3:]
                for case in BODIES}
        for case in ("generator_shape", "several_samples_per_series",
                     "zero_value_omitted", "zero_timestamp_omitted",
                     "negative_timestamp", "two_timestamp_widths",
                     "label_over_127_bytes", "series_over_127_bytes",
                     "duplicate_label_names", "nan_with_payload",
                     "one_label_set_in_two_byte_orders", "infinities",
                     "snappy_with_back_references"):
            n_series, n_hits = hits[case]
            assert n_hits == n_series > 0, case
        # a sample of another shape is read alone by the scalar reader:
        # its series is walked, and cached, all the same
        assert hits["timestamp_before_value"] == (2, 2)
        assert hits["unknown_field_in_a_sample"] == (2, 2)
        # a series of another shape is not
        assert hits["unknown_fields_3_and_4"] == (2, 1)
        assert hits["samples_before_labels"] == (2, 0)
        assert hits["sample_over_127_bytes"] == (1, 0)

    @pytest.mark.parametrize("case", [
        "generator_shape", "several_samples_per_series",
        "unknown_fields_3_and_4", "label_over_127_bytes",
        "non_canonical_varints"])
    def test_truncated_at_every_offset(self, case):
        """Same columns or the same exception type, wherever the
        protobuf or the snappy frame is cut."""
        raw = snappy.decompress(BODIES[case]())[:600]
        raised = set()
        for cut in range(len(raw)):
            for body in (snappy.compress(raw[:cut]),
                         snappy.compress(raw)[:cut]):
                want, want_exc = _outcome(_oracle, body)
                got, got_exc = _outcome(
                    lambda b: decode_write_request(b, SeriesCache(_document)),
                    body)
                assert got_exc is want_exc, (cut, got_exc, want_exc)
                if want is not None:
                    _assert_same(got, want)
                raised.add(want_exc)
        assert prom_remote.ProtoError in raised
        assert snappy.SnappyError in raised

    @pytest.mark.parametrize("sample, exc", [
        (b"\x09abc", struct.error),                     # a value cut short
        (b"\x10", prom_remote.ProtoError),              # no varint at all
        (_value(1.0) + b"\x10\x80", prom_remote.ProtoError),  # unterminated
        (_value(1.0) + b"\x10" + b"\xff" * 11, prom_remote.ProtoError),
        (_value(1.0) + b"\x1a\x7f", prom_remote.ProtoError),  # field 3 cut
    ], ids=["short_value", "bare_timestamp_tag", "unterminated_varint",
            "varint_too_long", "truncated_unknown_field"])
    def test_a_malformed_sample_raises_as_the_scalar_reader_does(
            self, sample, exc):
        body = _request(
            _labels(UP_A) + _sample(_value(1.0), _time(T_MS)),
            _labels(UP_B) + _sample(sample) + _sample(_value(2.0)))
        _, want_exc = _outcome(_oracle, body)
        _, got_exc = _outcome(
            lambda b: decode_write_request(b, SeriesCache(_document)), body)
        assert got_exc is want_exc is exc

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_mutated_bodies_decode_or_fail_as_the_scalar_reader_does(
            self, seed):
        """Bytes changed, cut and inserted at random: the same columns
        (cold and warm), or an exception where the scalar reader raises
        one.  (Which of two faults in one body is reported first may
        differ: the handler answers 400 to either.)"""
        rng = random.Random(seed)
        raws = [snappy.decompress(BODIES[c]())[:400] for c in sorted(BODIES)]
        raws = [r for r in raws if r]
        marks = [0, 1, 8, 9, 0x0A, 0x10, 0x11, 0x12, 0x1A, 0x7F, 0x80, 0xFF]
        decoded = 0
        for _ in range(3000):
            r = bytearray(rng.choice(raws))
            for _ in range(rng.randint(1, 3)):
                i, op = rng.randrange(len(r)), rng.random()
                if op < 0.5:
                    r[i] = rng.choice(marks + [rng.randrange(256)])
                elif op < 0.75 and len(r) > 8:
                    del r[i:i + rng.randint(1, 4)]
                else:
                    r[i:i] = bytes(rng.choice(marks)
                                   for _ in range(rng.randint(1, 3)))
            body = snappy.compress(bytes(r))
            cache = SeriesCache(_document)
            want, want_exc = _outcome(_oracle, body)
            got, got_exc = _outcome(
                lambda b: decode_write_request(b, cache), body)
            assert (got_exc is None) == (want_exc is None), bytes(r)
            if want is not None:
                _assert_same(got, want)
                _assert_same(decode_write_request(body, cache), want)
                decoded += 1
        assert 150 < decoded < 2850

    @pytest.mark.parametrize("ms", [(1 << 63) // 10**6 + 1, -(1 << 62)],
                             ids=["past_int64_nanos", "far_negative"])
    def test_timestamp_beyond_int64_nanoseconds(self, ms):
        body = _request(_labels(UP_A) + _sample(_value(1.0), _time(ms)))
        _, want_exc = _outcome(_oracle, body)
        _, got_exc = _outcome(
            lambda b: decode_write_request(b, SeriesCache(_document)), body)
        assert got_exc is want_exc is OverflowError


def _churn_body(first: int, n: int) -> bytes:
    """`n` series no body before held, 11 labels each."""
    return _request(*(
        _labels({b"__name__": b"cpu_usage_user", b"pod": b"pod-%08d" % i,
                 **{b"label_%d" % k: b"value_%d" % k for k in range(9)}})
        + _sample(_value(1.0), _time(T_MS)) for i in range(first, first + n)))


class TestSeriesCache:
    def test_bound_clears_the_cache_whole_and_answers_do_not_change(
            self, monkeypatch):
        def ups(*ids):
            return _request(*(
                _labels({b"__name__": b"up", b"i": b"%d" % i})
                + _sample(_value(1.0), _time(T_MS)) for i in ids))

        small = ups(*range(6))
        cache = SeriesCache(_document)
        assert decode_write_request(small, cache)[4] == 0
        assert decode_write_request(small, cache)[4] == 6
        assert len(cache.docs) == 6
        each = cache.bytes // 6           # what one such series is charged
        assert cache.bytes == 6 * each and each > 0
        monkeypatch.setattr(prom_remote, "MAX_CACHE_BYTES", 8 * each)
        # two more of that size fill it; the next new series empties it
        decode_write_request(ups(6, 7), cache)
        assert len(cache.docs) == 8 and cache.bytes == 8 * each
        decode_write_request(ups(8), cache)
        assert len(cache.docs) == 1 and cache.bytes == each
        # ... and it refills
        got = decode_write_request(small, cache)
        assert got[4] == 0 and len(cache.docs) == 7
        _assert_same(got, _oracle(small))
        # a body of more series than the bound holds: all misses, the
        # same columns, scrape after scrape
        body = _generator_body()
        want = _oracle(body)
        cache = SeriesCache(_document)
        for _ in range(3):
            got = decode_write_request(body, cache)
            _assert_same(got, want)
            assert got[4] == 0
            assert 0 < cache.bytes <= 8 * each

    def test_a_churning_sender_pins_no_more_than_the_bound(self, monkeypatch):
        """Every body brings series no body before held (pods that come
        and go); nothing else holds their Documents, as when the
        new-series limiter refused them.  What the cache holds stays
        under MAX_CACHE_BYTES, by the allocator's count and not only by
        the cache's own."""
        bound = 1 << 20
        monkeypatch.setattr(prom_remote, "MAX_CACHE_BYTES", bound)
        bodies = [_churn_body(100 * k, 100) for k in range(48)]
        # (what a first call allocates for good is not the cache's)
        decode_write_request(bodies[0], SeriesCache(_document))
        cache = SeriesCache(_document)
        gc.collect()
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            held = []
            for body in bodies:
                cols = decode_write_request(body, cache)
                assert cols[3:] == (100, 0)
                del cols
                gc.collect()
                held.append(tracemalloc.get_traced_memory()[0] - base)
                # the charge is an upper estimate of what is held
                assert held[-1] <= cache.bytes <= bound
        finally:
            tracemalloc.stop()
        # 4,800 series of ~2.5 KB would be 12 MB: the cache was cleared
        # on the way, and held most of its bound before it was
        assert len(cache.docs) < 4800 and max(held) > bound // 2
        # the sender that stops churning is served from the cache again
        assert decode_write_request(bodies[-1], cache)[4] > 0

    def test_a_long_label_is_charged_by_its_bytes(self):
        cache = SeriesCache(_document)
        decode_write_request(_request(
            _labels({b"__name__": b"up", b"path": b"/x" * 50_000})
            + _sample(_value(1.0), _time(T_MS))), cache)
        assert cache.bytes > 200_000

    def test_a_fault_in_the_miss_path_is_not_taken_for_a_slow_shape(self):
        """Only the reads of the wire bytes may send a series to the
        scalar reader: an IndexError from building a Document is the
        caller's to see."""
        def broken(tags):
            return [][0]
        with pytest.raises(IndexError):
            decode_write_request(_generator_body(), SeriesCache(broken))

    def test_four_threads_over_one_cache_decode_as_one(self):
        bodies = [BODIES[c]() for c in sorted(BODIES)] * 3
        want = [decode_write_request(b, SeriesCache(_document))[:3]
                for b in bodies]
        cache = SeriesCache(_document)
        got: dict = {}
        errors = []

        def work(k: int) -> None:
            try:
                for r in range(3):
                    for i in range(k, len(bodies), 2):  # two threads a body
                        got[k, r, i] = decode_write_request(bodies[i], cache)
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work, args=(k % 2,))
                       for k in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads) and not errors
        assert len(got) == 2 * 3 * len(bodies) // 2
        for (_k, _r, i), cols in got.items():
            _assert_same(cols, want[i])


class _Recorder:
    """A database or a downsampler that keeps what it was handed."""

    def __init__(self, keep=None):
        self.calls, self.keep = [], keep

    def write_tagged_batch(self, namespace, docs, ts, vals):
        self.calls.append((docs, ts, vals))
        return 0

    def write_batch(self, docs, ts, vals):
        self.calls.append((docs, ts, vals))
        return np.ones(len(docs), bool) if self.keep is None else self.keep


class TestIngestTail:
    def _handler(self, downsampler=None):
        db = _Recorder()
        ctx = SimpleNamespace(db=db, namespace="default", hist_ingest=None,
                              downsampler=downsampler)
        return SimpleNamespace(ctx=ctx), db

    @pytest.mark.parametrize("downsampled", [False, True])
    def test_every_sample_kept_hands_over_the_same_objects(self, downsampled):
        docs, ts, vals, _, _ = decode_write_request(
            _generator_body(), SeriesCache(_document))
        down = _Recorder() if downsampled else None
        handler, db = self._handler(down)
        assert _Handler._ingest_tagged(handler, docs, ts, vals) == (50, 0)
        for seen in ([db] + ([down] if down else [])):
            (got,) = seen.calls
            assert got[0] is docs and got[1] is ts and got[2] is vals

    def test_dropped_samples_and_lists_reach_the_database_as_before(self):
        keep = np.arange(50) % 3 != 0
        docs, ts, vals, _, _ = decode_write_request(
            _generator_body(), SeriesCache(_document))
        handler, db = self._handler(_Recorder(keep))
        written, rejected = _Handler._ingest_tagged(
            handler, docs, ts.tolist(), vals.tolist())
        assert (written, rejected) == (int(keep.sum()), 0)
        ((wdocs, wts, wvals),) = db.calls
        assert wdocs == [d for d, k in zip(docs, keep) if k]
        assert wts.dtype == np.int64 and wts.tolist() == ts[keep].tolist()
        assert wvals.dtype == np.float64 and (wvals == vals[keep]).all()


class TestRemoteWriteHandler:
    def test_tags_counters_and_status(self, tmp_path):
        """First body all misses, second all hits, on the span and on
        /metrics; a malformed body is a 400 and writes nothing."""
        from m3_tpu.server.assembly import run_node

        asm = run_node(f"""
db:
  root: {tmp_path / "data"}
  namespaces:
    default: {{num_shards: 2}}
coordinator: {{listen_port: 0, tracing: true}}
mediator: {{enabled: false}}
""")
        try:
            url = f"http://127.0.0.1:{asm.port}/api/v1/prom/remote/write"

            def post(body):
                try:
                    return urllib.request.urlopen(
                        urllib.request.Request(url, data=body)).status
                except urllib.error.HTTPError as e:
                    return e.code

            now_ms = int(time.time() * 1000)
            body = _request(*(
                _labels({b"__name__": b"up", b"i": b"%d" % i})
                + _sample(_value(float(i)), _time(now_ms))
                + _sample(_value(float(-i)), _time(now_ms + 1))
                for i in range(7)))
            assert post(body) == 204 and post(body) == 204
            raw = snappy.decompress(body)
            assert post(snappy.compress(raw[:-3])) == 400
            assert post(body[:-3]) == 400
            assert post(_request(_labels(UP_A) + _sample(
                _value(1.0), _time(1 << 62)))) == 400
            # a span ends after its reply was sent: order by start
            def tags_of(name):
                return [s.tags for s in sorted(
                    asm.tracer.finished(name), key=lambda s: s.start_ns)]

            assert [(t.get("series"), t.get("hits")) for t in tags_of(
                Tracepoint.API_WRITE_DECODE)] == [(7, 0), (7, 7)] + [
                    (None, None)] * 3
            assert [t.get("n") for t in tags_of(Tracepoint.API_WRITE)] == [
                14, 14, None, None, None]
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{asm.port}/metrics").read().decode()
            counted = {
                line.split()[0].split("{")[0]: float(line.split()[-1])
                for line in metrics.splitlines()
                if "decode_cache" in line and not line.startswith("#")}
            (hits,) = [v for k, v in counted.items() if k.endswith("_hits")]
            (misses,) = [v for k, v in counted.items()
                         if k.endswith("_misses")]
            assert (hits, misses) == (7, 7)
            pts = asm.db.read("default", b"up{i=3}", 0, 1 << 62)
            assert [v for _, v in pts] == [3.0, -3.0]
        finally:
            asm.close()
