"""``cli hops``: profile the node ingest pipeline's host↔device hops.

Drives a pinned synthetic gauge corpus through the node hot path —
**wire parse → arena ingest → window drain → encode → fileset bytes**
— under ``x/hopwatch`` and reports, per named hop: wall time (cold pass
with compiles vs steady pass), host↔device transfer count and bytes,
XLA compiles and dispatches, and each hop's share of the steady
pipeline wall time.  ROADMAP item 1 claims this path pays five host
hops; the committed artifact (PIPELINE_r09.json) is the measured
before-state its device-resident rebuild will be judged against.

The pipeline mirrors the aggregator node's real cadence: frames decode
off the wire shape (``msg/protocol.decode_metric_batch``), batches
ingest into the aggregator arenas per window, the flush tick drains
each closed window back to host, the drained aggregates re-upload into
the two-phase device encoder, and the streams land as a fileset volume.

Two passes over the same corpus: pass 1 pays every XLA compile (the
``cold`` numbers), pass 2 is steady state (the committed numbers) —
the same compile-vs-steady split bench.py reports per stage.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

HOPS = ("wire_parse", "arena_ingest", "window_drain", "encode",
        "fileset_write")

# pinned corpus geometry (the artifact is only comparable at fixed
# shape): S series x T 1s-spaced samples, 10s windows -> T/10 drains
S_DEFAULT = 1024
T_DEFAULT = 320
RESOLUTION_S = 10
BLOCK_NANOS = 2 * 3600 * 10**9
START = (1_700_000_000 * 10**9) // BLOCK_NANOS * BLOCK_NANOS


def _corpus(S: int, T: int, seed: int = 42):
    """Gauge rows: one wire frame per timestamp (all S series sampled
    together — the common scrape shape)."""
    rng = np.random.default_rng(seed)
    ids = [b"hop-series-%06d" % i for i in range(S)]
    base = rng.uniform(10, 1000, S)
    ts = START + np.arange(1, T + 1, dtype=np.int64) * 10**9
    vals = np.round(base[None, :] + rng.normal(0, base * 0.05, (T, S)), 2)
    return ids, ts, vals


def _encode_frames(ids, ts, vals):
    """Pre-encode the wire payloads (client-side work, never part of
    the server pipeline being profiled)."""
    from m3_tpu.msg import protocol as wire

    T, S = vals.shape
    mts = np.full(S, 3, np.uint8)  # MetricType.GAUGE
    frames = []
    for t in range(T):
        batch = wire.MetricBatch(
            mts, ids, vals[t].astype(np.float64),
            np.full(S, ts[t], np.int64))
        frames.append(wire.encode_metric_batch(batch))
    return frames


def _run_pass(frames, policy, opts, root: Path, volume: int):
    """One full wire→fileset pass; returns (per-hop ledgers for this
    pass, samples processed)."""
    from m3_tpu.aggregator.engine import Aggregator
    from m3_tpu.encoding.m3tsz_jax import encode_batch
    from m3_tpu.metrics.types import MetricType
    from m3_tpu.msg import protocol as wire
    from m3_tpu.persist.fs import DataFileSetWriter
    from m3_tpu.x import hopwatch

    res_nanos = RESOLUTION_S * 10**9
    agg = Aggregator(num_shards=1, opts=opts)
    hopwatch.reset()
    n_samples = 0

    # ingest/drain interleave at window cadence (the flush manager's
    # tick), batching decode per window like the ingest queue worker
    flushed = []
    rows_per_window = RESOLUTION_S  # 1s spacing
    for lo in range(0, len(frames), rows_per_window):
        window_frames = frames[lo:lo + rows_per_window]
        batches = []
        with hopwatch.hop("wire_parse"):
            for payload in window_frames:
                batches.append(wire.decode_metric_batch(payload))
        with hopwatch.hop("arena_ingest"):
            for b in batches:
                agg.add_untimed_batch(MetricType.GAUGE, b.ids, b.values,
                                      b.times)
                n_samples += len(b.ids)
        last_t = int(batches[-1].times[0])
        with hopwatch.hop("window_drain"):
            flushed.extend(agg.consume(
                (last_t // res_nanos) * res_nanos + res_nanos))

    # drained aggregates -> per-series window series (host reshape is
    # part of the drain hop's host tax in the real node too, but kept
    # outside the ledger: the artifact measures the five named hops)
    ml = agg.shards[0].lists[policy]
    id_of = ml.maps[MetricType.GAUGE].id_of
    series: dict = {}
    for fm in flushed:
        for slot, v in zip(fm.slots.tolist(), fm.values.tolist()):
            series.setdefault(id_of(int(slot)),
                              []).append((fm.timestamp_nanos, v))
    sids = sorted(series)
    W = max(len(p) for p in series.values())
    tmat = np.zeros((len(sids), W), np.int64)
    vmat = np.zeros((len(sids), W), np.float64)
    counts = np.zeros(len(sids), np.int64)
    for r, sid in enumerate(sids):
        pts = sorted(series[sid])
        counts[r] = len(pts)
        tmat[r, :len(pts)] = [t for t, _ in pts]
        vmat[r, :len(pts)] = [v for _, v in pts]
        if len(pts) < W:
            tmat[r, len(pts):] = tmat[r, len(pts) - 1]
            vmat[r, len(pts):] = vmat[r, len(pts) - 1]

    with hopwatch.hop("encode"):
        streams, fallback = encode_batch(
            tmat, vmat, np.full(len(sids), START, np.int64), counts=counts,
            out_words=max(16, W * 40 // 64 + 8))

    with hopwatch.hop("fileset_write"):
        out = [(sid, streams[r]) for r, sid in enumerate(sids)
               if not fallback[r]]
        DataFileSetWriter(str(root), "default", 0, START, BLOCK_NANOS,
                          volume=volume).write_all(out)

    return hopwatch.stats(), n_samples


def run_pipeline(S: int = S_DEFAULT, T: int = T_DEFAULT,
                 root: str | None = None) -> dict:
    """Two-pass profile; returns the PIPELINE artifact document."""
    import tempfile

    import jax

    from m3_tpu.aggregator.service import aggregator_options
    from m3_tpu.core.config import AggregatorConfig
    from m3_tpu.x import hopwatch

    # the served aggregator's own constructor path (run_aggregator
    # builds its engine from the same section); the timer buffer stays
    # at the size the pinned PIPELINE baseline was taken with
    opts = aggregator_options(AggregatorConfig(
        capacity=1 << max(10, (S - 1).bit_length()),
        num_windows=4,
        timer_sample_capacity=1 << 24,
        storage_policies=[f"{RESOLUTION_S}s:2d"],
    ))
    (policy,) = opts.storage_policies
    ids, ts, vals = _corpus(S, T)
    frames = _encode_frames(ids, ts, vals)
    wire_bytes = sum(len(f) for f in frames)

    was_installed = hopwatch.installed()
    hopwatch.install()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            base = Path(root) if root else Path(tmp)
            # _run_pass is host-synced by construction: the drain pulls
            # lanes to numpy and the fileset writer consumes host bytes
            # before returning, so the wall pair measures completed
            # work, not an async enqueue.
            # m3lint: disable=transfer-hygiene
            t0 = time.perf_counter()
            cold, n = _run_pass(frames, policy, opts, base / "cold", 0)
            cold_wall = time.perf_counter() - t0
            t0 = time.perf_counter()
            steady, _ = _run_pass(frames, policy, opts, base / "steady", 0)
            steady_wall = time.perf_counter() - t0
    finally:
        if not was_installed:
            hopwatch.uninstall()

    total_steady = sum(steady[h]["wall_s"] for h in HOPS if h in steady)
    hops = {}
    for h in HOPS:
        st = steady.get(h, {})
        hops[h] = {
            "steady": st,
            "cold": cold.get(h, {}),
            "host_time_fraction": round(
                st.get("wall_s", 0.0) / total_steady, 4) if total_steady
            else 0.0,
            "transfers": (st.get("h2d_count", 0) + st.get("d2h_count", 0)),
            "bytes_moved": (st.get("h2d_bytes", 0) + st.get("d2h_bytes", 0)),
            # steady per-hop dispatch count, promoted to a first-class
            # artifact field (round 13): dispatch growth is the leading
            # indicator of a hop splitting into more device programs —
            # it shows up before the transfer-byte gate moves, because
            # the extra dispatches initially shuttle the same bytes.
            "dispatches": st.get("dispatches", 0),
        }
    transfer_bytes = sum(h["bytes_moved"] for h in hops.values())
    artifact = {
        "artifact": "PIPELINE",
        "generated_by": "python -m m3_tpu.tools.cli hops",
        "config": {
            "S": S, "T": T, "resolution_s": RESOLUTION_S,
            "samples": n, "wire_bytes": wire_bytes,
            "platform": jax.default_backend(),
            "devices": jax.device_count(),
        },
        "hops": hops,
        "pipeline": {
            "wall_cold_s": round(cold_wall, 3),
            "wall_steady_s": round(steady_wall, 3),
            "samples_per_s_wire_to_bytes": round(n / steady_wall)
            if steady_wall else 0,
            "transfer_bytes_steady": transfer_bytes,
            "transfers_steady": sum(h["transfers"] for h in hops.values()),
            "dispatches_steady": sum(
                h["dispatches"] for h in hops.values()),
            "compiles_cold": sum(
                h["cold"].get("compiles", 0) for h in hops.values()),
            "compiles_steady": sum(
                h["steady"].get("compiles", 0) for h in hops.values()),
        },
    }
    artifact["findings"] = derive_findings(artifact)
    return artifact


def derive_findings(artifact: dict) -> list[str]:
    """Concrete host-hop findings from the ledger — the artifact must
    name the tax, not just tabulate it."""
    findings = []
    hops = artifact["hops"]
    pipe = artifact["pipeline"]
    cfg = artifact["config"]
    dominant = max(hops, key=lambda h: hops[h]["host_time_fraction"])
    frac = hops[dominant]["host_time_fraction"]
    if frac > 0.5:
        findings.append(
            f"{dominant} is {frac:.0%} of steady pipeline wall — "
            + ("the per-window consume pays a full-arena drain "
               "(sort/segment over capacity C, ~6 dispatches + a "
               "lanes-to-host copy per policy window) regardless of "
               "window occupancy; the device-resident pipeline "
               "(ROADMAP item 1) should drain windows without leaving "
               "the chip and emit once per flush tick"
               if dominant == "window_drain" else
               f"the top target for the device-resident pipeline"))
    if cfg.get("wire_bytes"):
        amp = pipe["transfer_bytes_steady"] / cfg["wire_bytes"]
        if amp > 1.0:
            findings.append(
                f"host<->device traffic is {amp:.1f}x the wire volume "
                f"({pipe['transfer_bytes_steady']:,} bytes moved across "
                f"{pipe['transfers_steady']} transfers for "
                f"{cfg['wire_bytes']:,} wire bytes): every stage "
                f"round-trips through host numpy — the five-host-hop "
                f"tax itemized")
    enc = hops.get("encode", {})
    if enc.get("steady", {}).get("h2d_bytes", 0) > 0:
        findings.append(
            f"encoder re-upload: {enc['steady']['h2d_bytes']:,} bytes "
            f"pushed back to device that were device-resident at drain "
            f"time one hop earlier — the drain->encode seam is the "
            f"cheapest fusion in the rebuild")
    return findings


def _hop_dispatches(hop: dict) -> int:
    """Baseline compat: the r13 artifacts carry a top-level per-hop
    ``dispatches``; older artifacts (r09) only have the steady ledger's
    count — same number, different nesting."""
    if "dispatches" in hop:
        return hop["dispatches"]
    return hop.get("steady", {}).get("dispatches", 0)


def check_against_baseline(artifact: dict, baseline_path: str,
                           tolerance: float = 0.25,
                           dispatch_tolerance: float = 0.10) -> list[str]:
    """Regression gate for ``cli hops --check``: the steady pipeline
    must not move MORE transfer bytes, add steady-state compiles, or
    grow any hop's steady DISPATCH count past tolerance vs the
    committed baseline.  Dispatch growth is the leading indicator the
    transfer gate misses: a hop splitting into more device programs
    pays per-dispatch overhead first and often moves the same bytes —
    by the time transfer bytes regress, the dispatch count has usually
    been climbing for rounds.  Returns violation strings (empty =
    pass)."""
    base = json.loads(Path(baseline_path).read_text())
    errs = []
    b = base["pipeline"]["transfer_bytes_steady"]
    cur = artifact["pipeline"]["transfer_bytes_steady"]
    if cur > b * (1.0 + tolerance):
        errs.append(
            f"steady transfer bytes regressed: {cur} > baseline {b} "
            f"(+{tolerance:.0%} tolerance)")
    b = base["pipeline"].get("compiles_steady", 0)
    cur = artifact["pipeline"].get("compiles_steady", 0)
    if cur > b:
        errs.append(
            f"steady-state compiles regressed: {cur} > baseline {b} "
            f"(a hop is retracing)")
    # per-hop dispatch gate (dispatch counts are deterministic for a
    # pinned corpus shape; the tolerance only absorbs baseline-era
    # jitter like conditional warm-up dispatches)
    for h, bh in base.get("hops", {}).items():
        bd = _hop_dispatches(bh)
        ch = artifact.get("hops", {}).get(h)
        if ch is None:
            errs.append(f"hop {h} present in baseline but missing from "
                        "this run — the pipeline lost a named stage")
            continue
        cd = _hop_dispatches(ch)
        if cd > bd * (1.0 + dispatch_tolerance) and cd > bd:
            errs.append(
                f"hop {h}: steady dispatches regressed {bd} -> {cd} "
                f"(+{dispatch_tolerance:.0%} tolerance) — the hop is "
                "splitting into more device programs")
    return errs
