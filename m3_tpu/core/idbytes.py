"""Metric IDs of one batch as byte runs of one buffer.

The columnar wire reader (``msg/protocol.decode_metric_columns``) leaves
the IDs where the frame has them and notes each one's start and length;
the native resolver (``native/idmap.NativeIdMap.resolve``) reads the
runs in place.  Everything that expects a sequence of ``bytes`` (the
Python resolver, error messages, the slot -> ID table) can index or
iterate it: a ``bytes`` object is made only for the IDs that are asked
for, which on the hot path are the series seen for the first time.
"""

from __future__ import annotations

import numpy as np


class PackedIds:
    """ID i is ``buf[starts[i]:starts[i] + lens[i]]`` (``buf`` u8,
    ``starts`` and ``lens`` i64)."""

    __slots__ = ("buf", "starts", "lens")

    def __init__(self, buf: np.ndarray, starts: np.ndarray, lens: np.ndarray):
        self.buf, self.starts, self.lens = buf, starts, lens

    @classmethod
    def from_ids(cls, ids) -> "PackedIds":
        n = len(ids)
        lens = np.fromiter(map(len, ids), np.int64, n)
        starts = np.zeros(n, np.int64)
        np.cumsum(lens[:-1], out=starts[1:])
        return cls(np.frombuffer(b"".join(ids), np.uint8), starts, lens)

    def __len__(self) -> int:
        return len(self.starts)

    def __getitem__(self, i) -> bytes:
        a = int(self.starts[i])
        return self.buf[a:a + int(self.lens[i])].tobytes()

    def __iter__(self):
        raw = self.buf.tobytes()
        a = self.starts.tolist()
        return (raw[s:s + n] for s, n in zip(a, self.lens.tolist()))

    def take(self, sel: np.ndarray) -> "PackedIds":
        """The IDs at positions ``sel``, in that order (no bytes move)."""
        return PackedIds(self.buf, self.starts[sel], self.lens[sel])
