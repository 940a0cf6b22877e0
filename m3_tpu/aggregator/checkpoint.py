"""Bit-exact aggregation-arena checkpoint/restore.

The PR 8 packed arena made aggregator state *checkpointable*: every
lane is a fixed-width device tensor (SALSA/Counter-Pools discipline —
arXiv:2102.12531, arXiv:2502.14699), so "the aggregator's state" is a
finite list of named arrays plus host bookkeeping, not a heap of
per-metric objects.  This module cashes that in before ROADMAP item 1
makes device residency mandatory: open aggregation windows survive a
SIGKILL instead of silently losing up to a full resolution window of
acked samples.

Serialization contract:

* **Arrays are raw bytes** — every arena lane (packed AND f64 layouts)
  is dumped device→host and written verbatim, each with its own
  adler32 through the persist layer's digest helper.  Restore is
  therefore BIT-exact by construction: save → SIGKILL → restore →
  consume equals uninterrupted consume for all bit-exact lanes (the
  checkpoint parity tests pin sha256 over the drained lanes; gauge
  sums stay inside the documented 1e-6 packed envelope only when
  comparing *across* layouts, never across a checkpoint).
* **Host bookkeeping is pickled** — slot maps (exact slot→id
  assignment, free lists), window watermarks (``consumed_until``),
  pipeline tails + transform state, reject counters, the
  downsampler's series-tag registry.  The pickle rides inside the same
  checksummed envelope.
* **Corruption is typed** — a bad magic/schema raises
  :class:`~m3_tpu.persist.corruption.FormatCorruption`, a digest
  mismatch :class:`~m3_tpu.persist.corruption.ChecksumMismatch`
  (persist's detect → quarantine → keep-serving discipline: the
  restoring node moves the rotten file aside and boots fresh rather
  than crash-looping).
* **Writes are atomic** — temp file + rename, checkpoint-last: a
  SIGKILL mid-save leaves the previous checkpoint intact.

Drivers: :class:`AggregatorCheckpointer` is saved by the mediator every
``coordinator.checkpoint_every`` ticks and by ``Assembly.drain``
(SIGTERM), and restored by ``run_node`` before the node starts serving.
"""

from __future__ import annotations

import os
import pickle
import struct
import tempfile
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from m3_tpu.persist.capacity import capacity_guard, inject
from m3_tpu.persist.corruption import ChecksumMismatch, FormatCorruption
from m3_tpu.persist.digest import digest

MAGIC = b"M3AGGCKPT"
SCHEMA = 1

__all__ = ["AggregatorCheckpointer", "save_lists", "load_lists",
           "restore_lists", "list_state", "restore_list_state"]


# ---------------------------------------------------------------------------
# MetricList <-> (meta, arrays)
# ---------------------------------------------------------------------------


def list_state(ml) -> Tuple[dict, List[Tuple[str, np.ndarray]]]:
    """One MetricList as (host meta, named device lanes).  Lane names
    are ``<arena>.<field>`` over the state NamedTuple's fields — the
    format follows the STATE, so a layout's field-set change
    (packed vs f64) needs no format change."""
    arrays: List[Tuple[str, np.ndarray]] = []
    arena_meta: Dict[str, dict] = {}
    for aname, arena in (("counter", ml.counters), ("gauge", ml.gauges),
                         ("timer", ml.timers)):
        st = arena.state
        arena_meta[aname] = {
            "state_cls": type(st).__name__,
            "fields": list(st._fields),
            "sample_capacity": getattr(arena, "sample_capacity", None),
            "sample_n_host": getattr(arena, "_sample_n_host", None),
        }
        for f in st._fields:
            arrays.append((f"{aname}.{f}", np.asarray(getattr(st, f))))
    maps = {}
    for mt, m in ml.maps.items():
        maps[int(mt)] = m.to_entries()
    meta = {
        "policy": str(ml.policy),
        "layout": type(ml.counters).__name__,  # Packed* vs plain
        "opts": {
            "capacity": ml.opts.capacity,
            "num_windows": ml.opts.num_windows,
            "timer_sample_capacity": ml.timers.sample_capacity,
            "quantiles": tuple(ml.opts.quantiles),
            "timer_packed32": ml.opts.timer_packed32,
            "layout": ml.counters.layout,
        },
        "consumed_until": ml.consumed_until,
        "drops": ml.drops,
        "timed_rejects": dict(ml.timed_rejects),
        "new_series_rejected": ml.new_series_rejected,
        "forward_errors": ml.forward_errors,
        "maps": maps,
        "pipelines": dict(ml._pipelines),
        "tf_state": dict(ml._tf_state),
        "tail_sigs": dict(ml._tail_sigs),
        "forward_buffer": list(ml._forward_buffer),
        "arenas": arena_meta,
    }
    return meta, arrays


def restore_list_state(ml, meta: dict, arrays: Dict[str, np.ndarray]) -> None:
    """Install a saved state into a freshly constructed MetricList of
    the SAME geometry (the loader builds it from the checkpoint's own
    opts).  Array dtypes/shapes are validated against the live state —
    a geometry mismatch is format corruption, not a crash deep in
    XLA."""
    import jax.numpy as jnp

    for aname, arena in (("counter", ml.counters), ("gauge", ml.gauges),
                         ("timer", ml.timers)):
        st = arena.state
        am = meta["arenas"][aname]
        if list(st._fields) != am["fields"]:
            raise FormatCorruption(
                f"checkpoint arena {aname!r} fields {am['fields']} do not "
                f"match this build's {list(st._fields)}",
                component="aggregator.checkpoint")
        vals = {}
        for f in st._fields:
            live = np.asarray(getattr(st, f))
            saved = arrays[f"{aname}.{f}"]
            if saved.shape != live.shape or saved.dtype != live.dtype:
                raise FormatCorruption(
                    f"checkpoint lane {aname}.{f}: {saved.dtype}"
                    f"{saved.shape} vs live {live.dtype}{live.shape}",
                    component="aggregator.checkpoint")
            vals[f] = jnp.asarray(saved)
        arena.state = type(st)(**vals)
        if am.get("sample_n_host") is not None:
            arena._sample_n_host = np.asarray(am["sample_n_host"]).copy()
    from m3_tpu.metrics.types import MetricType

    for mt_val, entries in meta["maps"].items():
        ml.maps[MetricType(mt_val)].load_entries(entries)
    ml.consumed_until = meta["consumed_until"]
    ml.drops = meta["drops"]
    ml.timed_rejects = dict(meta["timed_rejects"])
    ml.new_series_rejected = meta["new_series_rejected"]
    ml.forward_errors = meta["forward_errors"]
    ml._pipelines = dict(meta["pipelines"])
    ml._tf_state = dict(meta["tf_state"])
    ml._tail_sigs = dict(meta["tail_sigs"])
    ml._forward_buffer = list(meta["forward_buffer"])


# ---------------------------------------------------------------------------
# File envelope: MAGIC | u8 schema | u64 header_len | pickle(header)
#                | raw array blob   (array digests live in the header;
#                the header's own digest rides a trailing u32)
# ---------------------------------------------------------------------------


def save_lists(lists: dict, path, extra_meta: dict | None = None) -> int:
    """Write ``{StoragePolicy: MetricList}`` (+ optional extra host
    meta, e.g. the downsampler's series tags) to ``path`` atomically.
    Returns bytes written."""
    entries = []
    blobs: List[bytes] = []
    offset = 0
    for sp, ml in lists.items():
        meta, arrays = list_state(ml)
        arr_meta = []
        for name, a in arrays:
            a = np.asarray(a)
            # NOTE: ascontiguousarray would promote 0-d lanes (pool_n,
            # err) to (1,); record the true shape, serialize the bytes
            raw = np.ascontiguousarray(a).tobytes()
            arr_meta.append({
                "name": name, "dtype": str(a.dtype), "shape": a.shape,
                "offset": offset, "nbytes": len(raw),
                "digest": digest(raw),
            })
            blobs.append(raw)
            offset += len(raw)
        meta["arrays"] = arr_meta
        entries.append(meta)
    header = {
        "schema": SCHEMA,
        "lists": entries,
        "extra": extra_meta or {},
    }
    hbytes = pickle.dumps(header, protocol=4)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                               prefix=path.name + ".tmp")
    try:
        # capacity_guard also unlinks tmp on ENOSPC; the outer
        # BaseException handler keeps covering every OTHER failure
        # (serialization bugs, KeyboardInterrupt mid-save).
        with capacity_guard(path=path, component="checkpoint", op="write",
                            cleanup=(tmp,)):
            inject("checkpoint.write")
            with os.fdopen(fd, "wb") as f:
                f.write(MAGIC)
                f.write(struct.pack("<BQ", SCHEMA, len(hbytes)))
                f.write(struct.pack("<I", digest(hbytes)))
                f.write(hbytes)
                for raw in blobs:
                    f.write(raw)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return len(MAGIC) + 13 + len(hbytes) + offset


def load_lists(path):
    """Parse + verify a checkpoint → (header dict, arrays-by-list).
    Typed failures: FormatCorruption (magic/schema/truncation),
    ChecksumMismatch (header or lane digest)."""
    path = Path(path)
    data = path.read_bytes()
    if len(data) < len(MAGIC) + 13 or not data.startswith(MAGIC):
        raise FormatCorruption("aggregator checkpoint: bad magic/truncated",
                               path=str(path),
                               component="aggregator.checkpoint")
    off = len(MAGIC)
    schema, hlen = struct.unpack_from("<BQ", data, off)
    off += 9
    (hdig,) = struct.unpack_from("<I", data, off)
    off += 4
    if schema != SCHEMA:
        raise FormatCorruption(
            f"aggregator checkpoint schema {schema} != {SCHEMA}",
            path=str(path), component="aggregator.checkpoint")
    hbytes = data[off:off + hlen]
    if len(hbytes) != hlen:
        raise FormatCorruption("aggregator checkpoint: truncated header",
                               path=str(path),
                               component="aggregator.checkpoint")
    if digest(hbytes) != hdig:
        raise ChecksumMismatch(
            "aggregator checkpoint header digest mismatch",
            path=str(path), component="aggregator.checkpoint",
            check="adler32")
    header = pickle.loads(hbytes)
    blob = data[off + hlen:]
    per_list: List[Dict[str, np.ndarray]] = []
    for meta in header["lists"]:
        arrays: Dict[str, np.ndarray] = {}
        for am in meta["arrays"]:
            raw = blob[am["offset"]:am["offset"] + am["nbytes"]]
            if len(raw) != am["nbytes"]:
                raise FormatCorruption(
                    f"aggregator checkpoint: truncated lane {am['name']}",
                    path=str(path), component="aggregator.checkpoint")
            if digest(raw) != am["digest"]:
                raise ChecksumMismatch(
                    f"aggregator checkpoint lane {am['name']} digest "
                    "mismatch", path=str(path),
                    component="aggregator.checkpoint", check="adler32")
            arrays[am["name"]] = np.frombuffer(
                raw, dtype=np.dtype(am["dtype"])).reshape(am["shape"])
        per_list.append(arrays)
    return header, per_list


def restore_lists(path, make_list):
    """Load a checkpoint and rebuild every MetricList through
    ``make_list(policy_str, opts_dict)`` (the caller owns list
    construction so engine/downsampler geometry knobs stay theirs).
    Returns (``{policy_str: MetricList}``, extra meta)."""
    header, per_list = load_lists(path)
    out = {}
    for meta, arrays in zip(header["lists"], per_list):
        ml = make_list(meta["policy"], meta["opts"])
        restore_list_state(ml, meta, arrays)
        out[meta["policy"]] = ml
    return out, header.get("extra", {})


# ---------------------------------------------------------------------------
# Driver: mediator-tick + drain checkpointing of a Downsampler
# ---------------------------------------------------------------------------


class AggregatorCheckpointer:
    """Owns one checkpoint file for a coordinator Downsampler.

    ``save()`` snapshots every (policy, MetricList) under the
    downsampler's lock (a torn snapshot racing the ingest path would
    not be bit-exact); ``restore()`` rebuilds them on boot, moving a
    corrupt file aside (``<path>.corrupt``) and starting fresh — the
    persist quarantine discipline, never a crash loop."""

    def __init__(self, downsampler, path, instrument=None):
        self.downsampler = downsampler
        self.path = Path(path)
        self.saves = 0
        self.save_errors = 0
        self.restores = 0
        self.corrupt = 0
        self._scope = (instrument.scope("aggregator.checkpoint")
                       if instrument is not None else None)

    def save(self) -> dict:
        try:
            nbytes = self.downsampler.checkpoint_to(self.path)
        except Exception:  # noqa: BLE001 — a failed save must not kill
            # the mediator loop; counted + logged by the caller's tick
            self.save_errors += 1
            if self._scope is not None:
                self._scope.counter("save_errors").inc()
            raise
        self.saves += 1
        if self._scope is not None:
            self._scope.counter("saves").inc()
            self._scope.gauge("bytes").update(nbytes)
        return {"bytes": nbytes, "path": str(self.path)}

    def restore(self) -> bool:
        if not self.path.exists():
            return False
        from m3_tpu.persist.corruption import CorruptionError

        try:
            self.downsampler.restore_from(self.path)
        except CorruptionError:
            self.corrupt += 1
            if self._scope is not None:
                self._scope.counter("corrupt").inc()
            # quarantine-in-place: keep the bytes for forensics, never
            # crash-loop the node on them
            try:
                with capacity_guard(path=self.path, component="checkpoint",
                                    op="sideline"):
                    os.replace(self.path, str(self.path) + ".corrupt")
            except OSError:
                pass
            return False
        self.restores += 1
        if self._scope is not None:
            self._scope.counter("restores").inc()
        return True

    def status(self) -> dict:
        return {
            "path": str(self.path),
            "saves": self.saves,
            "save_errors": self.save_errors,
            "restores": self.restores,
            "corrupt": self.corrupt,
        }
