"""ctypes binding for the native ID->slot resolver (native/idmap.cc).

The aggregator ingest hot path's host half (reference metricMap
find-or-create, `map.go:149`): batches of metric IDs resolve to dense
arena slots in one native call instead of one Python dict probe per
sample.  Built from source like the other native modules
(native/_build.py): a failed build raises, it does not fall back.
"""

from __future__ import annotations

import ctypes

import numpy as np

from m3_tpu.core.idbytes import PackedIds
from m3_tpu.native._build import load_native

_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    lib = load_native("idmap.cc", "libidmap.so", ("-std=c++20",))
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")

    lib.idmap_new.restype = ctypes.c_void_p
    lib.idmap_new.argtypes = [ctypes.c_int64]
    lib.idmap_del.argtypes = [ctypes.c_void_p]
    lib.idmap_len.restype = ctypes.c_int64
    lib.idmap_len.argtypes = [ctypes.c_void_p]
    lib.idmap_resolve_batch.restype = ctypes.c_int64
    lib.idmap_resolve_batch.argtypes = [
        ctypes.c_void_p, u8p, i64p, i64p, ctypes.c_int64, ctypes.c_uint64,
        i32p, i64p,
    ]
    lib.idmap_release.restype = ctypes.c_int32
    lib.idmap_release.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
    ]
    _lib = lib
    return lib


class NativeIdMap:
    """Find-or-create slot resolution over packed ID batches."""

    def __init__(self, capacity: int):
        self._lib = _load()
        self._h = self._lib.idmap_new(capacity)
        self.capacity = capacity

    def __len__(self) -> int:
        return self._lib.idmap_len(self._h)

    def resolve(self, ids, mask: int):
        """(slots int32 (n,), new_positions int64 (k,)) — find-or-create
        for every id (a sequence of bytes, or a PackedIds whose buffer
        is read in place) under the given aggregation mask.  Raises
        RuntimeError when capacity would be exceeded."""
        n = len(ids)
        if not isinstance(ids, PackedIds):
            ids = PackedIds.from_ids(ids)
        slots = np.empty(n, np.int32)
        new_idx = np.empty(n, np.int64)
        n_new = self._lib.idmap_resolve_batch(
            self._h, ids.buf if ids.buf.size else np.zeros(1, np.uint8),
            np.ascontiguousarray(ids.starts, np.int64),
            np.ascontiguousarray(ids.lens, np.int64), n, mask, slots,
            new_idx,
        )
        if n_new < 0:
            raise RuntimeError(f"idmap capacity {self.capacity} exhausted")
        return slots, new_idx[:n_new]

    def release(self, sid: bytes, mask: int) -> bool:
        return bool(self._lib.idmap_release(self._h, sid, len(sid), mask))

    def __del__(self):
        try:
            if self._lib is not None:
                self._lib.idmap_del(self._h)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
