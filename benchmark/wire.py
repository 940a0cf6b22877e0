"""Prometheus remote-write bodies for the load generator.

A sender's label sets never change between scrapes, so each request is
encoded ONCE into a template (protobuf WriteRequest, one sample per
series) with the byte offsets of every sample's value (8 bytes, little-
endian f64) and timestamp (varint of milliseconds, fixed width for the
whole run).  Inside the window a request is made by patching those
bytes with numpy and framing the result as all-literal snappy — no
per-sample Python runs on the node's GIL.

Encoding copied from ``m3_tpu/server/prom_remote.py``
(``_emit_timeseries`` / ``build_write_request``) and
``m3_tpu/server/snappy.py`` (``compress``) at commit d4ba90b; this copy,
not the original, is the yardstick from now on.
"""

from __future__ import annotations

import http.client

import numpy as np


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len_field(fnum: int, payload: bytes) -> bytes:
    return _varint((fnum << 3) | 2) + _varint(len(payload)) + payload


def ts_varint(t_nanos: int, width: int) -> bytes:
    """The sample timestamp (ms) as a varint of exactly `width` bytes."""
    v = _varint(t_nanos // 10**6)
    if len(v) != width:
        raise ValueError(f"timestamp varint is {len(v)} bytes, template has {width}")
    return v


def snappy_literal(data: bytes) -> bytes:
    """All-literal snappy block: valid for every decoder."""
    out = [_varint(len(data))]
    for pos in range(0, len(data), 65536):
        chunk = data[pos:pos + 65536]
        n = len(chunk) - 1
        if n < 60:
            out.append(bytes([n << 2]))
        elif n < 256:
            out.append(bytes([60 << 2, n]))
        else:
            out.append(bytes([61 << 2]) + n.to_bytes(2, "little"))
        out.append(chunk)
    return b"".join(out)


class Template:
    """One request's WriteRequest with patchable sample bytes."""

    def __init__(self, label_sets: list[dict], t_nanos: int):
        ts = _varint(t_nanos // 10**6)
        self.ts_width = len(ts)
        sample = b"\x09" + bytes(8) + b"\x10" + ts      # value f64, timestamp
        sample_field = _len_field(2, sample)
        val_in_sample = len(sample_field) - len(sample) + 1
        parts, val_off, pos = [], [], 0
        for tags in label_sets:
            labels = b"".join(
                _len_field(1, _len_field(1, k) + _len_field(2, v))
                for k, v in sorted(tags.items()))
            series = _len_field(1, labels + sample_field)
            val_off.append(pos + len(series) - len(sample_field) + val_in_sample)
            parts.append(series)
            pos += len(series)
        self.raw = np.frombuffer(b"".join(parts), np.uint8).copy()
        self.n = len(label_sets)
        v = np.asarray(val_off, np.int64)
        self._val_idx = (v[:, None] + np.arange(8)).ravel()
        self._ts_idx = (v[:, None] + 9 + np.arange(self.ts_width)).ravel()

    def body(self, t_nanos: int, vals: np.ndarray) -> bytes:
        """The snappy-framed request with every sample at t_nanos."""
        raw = self.raw
        raw[self._val_idx] = np.ascontiguousarray(
            vals, "<f8").view(np.uint8)
        raw[self._ts_idx] = np.tile(
            np.frombuffer(ts_varint(t_nanos, self.ts_width), np.uint8), self.n)
        return snappy_literal(raw.tobytes())


def series_id(tags: dict) -> bytes:
    """The id the node's HTTP handlers mint for a label set (copied from
    ``http_api._Handler._series_id``): where to look an acked sample up."""
    name = tags.get(b"__name__", b"")
    return name + b"{" + b",".join(
        k + b"=" + v for k, v in sorted(tags.items()) if k != b"__name__"
    ) + b"}"


def post_write(port: int, body: bytes) -> int:
    """One remote-write request on a connection of its own (the node
    speaks HTTP/1.0) -> status; 204 is the ack."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/api/v1/prom/remote/write", body)
        resp = conn.getresponse()
        resp.read()
        return resp.status
    finally:
        conn.close()
