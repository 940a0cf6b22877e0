"""Batched JAX codec vs the scalar oracle (which is golden-validated)."""

import base64
import json
import math

import numpy as np
import pytest

from tests.conftest import DATA_DIR
from m3_tpu.encoding.m3tsz import decode_series, encode_series
from m3_tpu.encoding.m3tsz_jax import decode_batch, encode_batch
from tests.test_decode_fuzz import FORMS, decode_as

START = 1_600_000_000 * 10**9


def _mk_batch(T=200, seed=0):
    rng = np.random.default_rng(seed)
    S = 8
    ts = np.tile(START + (np.arange(1, T + 1) * 10 * 10**9), (S, 1)).astype(np.int64)
    vals = np.zeros((S, T))
    starts = np.full(S, START, np.int64)
    vals[0] = np.arange(T) % 50
    vals[1] = 42.0
    vals[2] = np.round(rng.normal(100, 10, T), 2)
    vals[3] = rng.normal(0, 1, T)
    vals[4] = np.where(np.arange(T) % 3 == 0, 1.5, 7.0)
    vals[5] = np.cumsum(rng.integers(0, 100, T)).astype(float)
    vals[6] = 0.0
    vals[7] = rng.choice([1e9, 2.25, -5.0, 0.001], T)
    return ts, vals, starts


def test_encode_batch_bit_exact_vs_oracle():
    ts, vals, starts = _mk_batch()
    streams, fb = encode_batch(ts, vals, starts, out_words=400)
    assert not fb.any()
    for s in range(len(streams)):
        want = encode_series(list(zip(ts[s].tolist(), vals[s].tolist())), start=START)
        assert streams[s] == want, f"series {s} not bit-exact"


def test_encode_sig_tracker_grow_keeps_lower_streak():
    """Regression (round-5 bench device-vs-native byte stage): the sig
    hysteresis tracker must NOT reset its lower-sig streak counter on a
    GROW step — Go's TrackNewSig (int_sig_bits_tracker.go:68-91) only
    resets on the within-threshold branch.  Resetting on grow desynced
    the shrink timing on grow-interleaved diff streams (22/2000 corpus
    series encoded valid-but-different bytes)."""
    # The start of the corpus series that exposed it: 2-decimal gauge
    # jitter whose scaled diffs alternate 12/13-bit sigs with occasional
    # small (shrink-eligible) diffs.
    v = [788.5, 788.3, 781.61, 809.0, 772.39, 737.82, 818.48, 763.77,
         791.88, 811.21, 780.2, 768.78, 804.75, 749.49, 793.32, 782.65,
         776.91, 749.03, 772.37, 772.22, 781.1, 821.35, 796.27, 817.2,
         761.17, 771.68, 795.72, 798.38, 801.82, 773.14, 819.55, 745.29]
    T = len(v)
    ts = (START + np.arange(1, T + 1) * 10 * 10**9)[None, :].astype(np.int64)
    vals = np.asarray(v)[None, :]
    streams, fb = encode_batch(ts, vals, np.full(1, START, np.int64),
                               out_words=120)
    assert not fb.any()
    want = encode_series(list(zip(ts[0].tolist(), vals[0].tolist())),
                         start=START)
    assert streams[0] == want


def test_encode_batch_hard_cases():
    T = 120
    rng = np.random.default_rng(3)
    S = 6
    ts = np.tile(START + (np.arange(1, T + 1) * 10**9), (S, 1)).astype(np.int64)
    starts = np.full(S, START, np.int64)
    vals = np.zeros((S, T))
    vals[0] = np.where(np.arange(T) % 7 == 0, np.nan, 3.0)
    vals[1] = rng.choice([0.1, 0.25, 1 / 3, 123456.789], T)
    ts[2] = START + np.cumsum(rng.choice([10**9, 2 * 10**9, 60 * 10**9], T))
    vals[2] = 5.0
    starts[3] = START + 123  # unaligned start -> TU marker on first datapoint
    ts[3] = starts[3] + np.cumsum(np.full(T, 10**9))
    vals[3] = np.arange(T).astype(float)
    ts[4, 50:] -= 5 * 10**9  # negative delta-of-delta
    vals[4] = 17.0
    vals[5] = np.repeat(rng.normal(50, 5, T // 4).round(4), 4)[:T]
    streams, fb = encode_batch(ts, vals, starts, out_words=400)
    assert not fb.any()
    for s in range(S):
        want = encode_series(list(zip(ts[s].tolist(), vals[s].tolist())),
                             start=int(starts[s]))
        assert streams[s] == want, f"hard series {s} not bit-exact"


def test_encode_variable_counts():
    ts, vals, starts = _mk_batch(T=100)
    counts = np.array([100, 50, 10, 99, 1, 100, 3, 77])
    streams, fb = encode_batch(ts, vals, starts, counts=counts, out_words=400)
    assert not fb.any()
    for s in range(len(streams)):
        n = counts[s]
        want = encode_series(list(zip(ts[s, :n].tolist(), vals[s, :n].tolist())),
                             start=START)
        assert streams[s] == want


def test_encode_overflow_flags_fallback():
    # random floats at ~70 bits/pt cannot fit a 16-bit/pt budget
    rng = np.random.default_rng(1)
    T = 500
    ts = np.tile(START + np.arange(1, T + 1) * 10**9, (2, 1)).astype(np.int64)
    vals = rng.normal(0, 1, (2, T))
    streams, fb = encode_batch(ts, vals, np.full(2, START, np.int64))
    assert fb.all()
    assert streams[0] == b""


def test_encode_precision_limit_flags_fallback():
    T = 4
    ts = np.tile(START + np.arange(1, T + 1) * 10**9, (1, 1)).astype(np.int64)
    vals = np.full((1, T), float(2**60))
    _, fb = encode_batch(ts, vals, np.full(1, START, np.int64), out_words=50)
    assert fb.all()


def test_decode_batch_golden_corpus():
    with open(DATA_DIR / "m3tsz_sample_series.json") as f:
        streams = [base64.b64decode(s) for s in json.load(f)]
    ts, vals, counts, fb = decode_batch(streams, max_points=1500)
    assert not fb.any()
    for i, s in enumerate(streams):
        want = decode_series(s)
        n = int(counts[i])
        assert n == len(want)
        assert ts[i][:n].tolist() == [d.timestamp for d in want]
        for a, b in zip(vals[i][:n].tolist(), (d.value for d in want)):
            assert a == b or (math.isnan(a) and math.isnan(b))


def test_roundtrip_batched():
    ts, vals, starts = _mk_batch(T=150, seed=5)
    streams, fb = encode_batch(ts, vals, starts, out_words=400)
    assert not fb.any()
    ts2, vals2, counts, fb2 = decode_batch(streams, max_points=200)
    assert not fb2.any()
    assert (counts == 150).all()
    assert (ts2[:, :150] == ts).all()
    assert np.allclose(vals2[:, :150], vals, rtol=0, atol=0, equal_nan=True)


@pytest.mark.parametrize("chains,regfile", FORMS)
def test_decode_formulations_golden_and_roundtrip(chains, regfile):
    """The reference encoder's golden streams and a batch of the
    batched encoder's, under each chains tail x register-file
    formulation: bit-identical to the scalar decoder."""
    with open(DATA_DIR / "m3tsz_sample_series.json") as f:
        golden = [base64.b64decode(s) for s in json.load(f)]
    ts, vals, starts = _mk_batch(T=150, seed=5)
    batch, fb = encode_batch(ts, vals, starts, out_words=400)
    assert not fb.any()
    streams = golden + [bytes(s) for s in batch]
    ts2, vals2, counts, fb2 = decode_as(streams, 1500, chains, regfile)
    assert not fb2.any()
    for i, s in enumerate(streams):
        want = decode_series(s)
        n = int(counts[i])
        assert n == len(want)
        assert ts2[i][:n].tolist() == [d.timestamp for d in want]
        np.testing.assert_array_equal(
            vals2[i][:n].view(np.uint64),
            np.array([d.value for d in want], np.float64).view(np.uint64))


def test_decode_annotation_stream_default_flags_fallback():
    """By default annotated streams still flag fallback (the annotation
    BYTES are skipped on device and callers may need them)."""
    from m3_tpu.core.xtime import Unit
    from m3_tpu.encoding.m3tsz import Datapoint, Encoder

    enc = Encoder(START)
    enc.encode(Datapoint(START + 10**9, 1.0, Unit.SECOND, b"schema"))
    enc.encode(Datapoint(START + 2 * 10**9, 2.0, Unit.SECOND))
    _, _, _, fb = decode_batch([enc.stream()], max_points=10)
    assert fb.all()


def test_decode_annotation_stream_rides_device_path():
    """With annotations_fallback=False, annotated streams decode on
    device: values/timestamps exact, each annotation eats one slot."""
    from m3_tpu.core.xtime import Unit
    from m3_tpu.encoding.m3tsz import Datapoint, Encoder

    enc = Encoder(START)
    enc.encode(Datapoint(START + 10**9, 1.5, Unit.SECOND, b"schema-v1"))
    enc.encode(Datapoint(START + 2 * 10**9, 2.5, Unit.SECOND))
    # mid-stream annotation CHANGE plus more points
    enc.encode(Datapoint(START + 3 * 10**9, 3.5, Unit.SECOND, b"schema-v2"))
    enc.encode(Datapoint(START + 4 * 10**9, 4.5, Unit.SECOND))
    ts, vals, counts, fb = decode_batch(
        [enc.stream()], max_points=12, annotations_fallback=False)
    assert not fb.any()
    n = int(counts[0])
    assert n == 4
    assert ts[0][:n].tolist() == [START + (k + 1) * 10**9 for k in range(4)]
    assert vals[0][:n].tolist() == [1.5, 2.5, 3.5, 4.5]


def test_decode_large_annotation_window_jump():
    """An annotation bigger than the decoder's 2048-bit window forces
    the full window-reload path; the stream must still decode."""
    from m3_tpu.core.xtime import Unit
    from m3_tpu.encoding.m3tsz import Datapoint, Encoder

    big = bytes(range(256)) * 3  # 768 bytes = 6144 bits >> window
    enc = Encoder(START)
    enc.encode(Datapoint(START + 10**9, 7.25, Unit.SECOND, big))
    for k in range(2, 40):
        enc.encode(Datapoint(START + k * 10**9, float(k), Unit.SECOND))
    ts, vals, counts, fb = decode_batch(
        [enc.stream()], max_points=50, annotations_fallback=False)
    assert not fb.any()
    assert int(counts[0]) == 39
    assert vals[0][0] == 7.25 and vals[0][38] == 39.0


def test_encode_first_datapoint_annotation_bit_exact():
    """encode_batch(annotations=...) must produce byte-identical streams
    to the scalar encoder writing the same first-dp annotation."""
    from m3_tpu.core.xtime import Unit
    from m3_tpu.encoding.m3tsz import Datapoint, Encoder

    T = 30
    ts = np.tile(START + np.arange(1, T + 1) * 10**9, (3, 1)).astype(np.int64)
    vals = np.round(np.arange(3)[:, None] + np.arange(T)[None, :] * 0.5, 1)
    anns = [b"proto-schema-A", None, b"x" * 100]
    streams, fb = encode_batch(ts, vals, np.full(3, START, np.int64),
                               out_words=200, annotations=anns)
    assert not fb.any()
    for i in range(3):
        enc = Encoder(START)
        for k in range(T):
            enc.encode(Datapoint(int(ts[i, k]), float(vals[i, k]),
                                 Unit.SECOND, anns[i] or b""))
        assert streams[i] == enc.stream(), f"series {i} not bit-exact"
    # and the scalar decoder returns the annotation from the batched bytes
    from m3_tpu.encoding.m3tsz import decode_series as _ds
    pts = _ds(streams[0])
    assert pts[0].annotation == b"proto-schema-A"


def test_saturated_int64_values_flag_fallback():
    # Integral |v| >= 2^63 saturates to INT64_MIN and aliases distinct values;
    # must be routed to the scalar codec (regression).
    T = 3
    ts = np.tile(START + np.arange(1, T + 1) * 10**9, (1, 1)).astype(np.int64)
    vals = np.array([[-1e300, -2e300, -1e300]])
    _, fb = encode_batch(ts, vals, np.full(1, START, np.int64), out_words=50)
    assert fb.all()


def test_dod_32bit_overflow_flags_fallback():
    # > 2^31 seconds between points overflows the 32-bit default bucket; the
    # reference raises OverflowError, the device path must flag fallback.
    ts = np.array([[START + 10**9, START + 10**9 + (2**32) * 10**9]])
    vals = np.ones((1, 2))
    _, fb = encode_batch(ts, vals, np.full(1, START, np.int64), out_words=80)
    assert fb.all()


def test_decode_exactly_max_points_not_flagged():
    dps = [(START + (i + 1) * 10**9, float(i)) for i in range(5)]
    stream = encode_series(dps, start=START)
    ts, vals, counts, fb = decode_batch([stream], max_points=5)
    assert not fb.any()
    assert counts[0] == 5
    assert ts[0].tolist() == [t for t, _ in dps]


def test_encode_zero_count_series_empty():
    ts, vals, starts = _mk_batch(T=10)
    counts = np.array([10, 0, 5, 0, 10, 10, 10, 10])
    streams, fb = encode_batch(ts, vals, starts, counts=counts, out_words=50)
    assert not fb.any()
    assert streams[1] == b"" and streams[3] == b""
    assert streams[0] != b""


def test_batched_decode_mixed_unit_streams():
    """Streams produced by the per-datapoint-unit encoder (round-4
    precision fix: TU markers mid-stream) decode exactly on the device
    path too — the is_tu branch handles every switch."""
    from m3_tpu.encoding.m3tsz import encode_series
    from m3_tpu.encoding.m3tsz_jax import decode_batch

    SEC = 10**9
    start = 1_699_992_000 * SEC
    pts = [(start + 10**10, 1.0), (start + 2 * 10**10 + 7, 2.0),
           (start + 3 * 10**10, 3.0), (start + 4 * 10**10 + 7000, 4.5)]
    blob = encode_series(pts, start=start)
    ts, vals, counts, fb = decode_batch([blob], max_points=16)
    assert not fb[0] and counts[0] == 4
    got = list(zip(ts[0, :4].tolist(), vals[0, :4].tolist()))
    assert got == pts


def test_encode_gather_placement_byte_identical(monkeypatch):
    """The TPU (gather/cumsum) word-placement form must produce the
    SAME bytes as the scatter form, validated against the scalar
    oracle.  u64 cumsum-diff is exact under wraparound, so identity
    must hold bit for bit.  This used to need a SUBPROCESS because
    M3_ENCODE_PLACE was read under the tracer and in-process flips
    were silently frozen at the first compile; round 7 moved the
    resolution into the host wrapper (resolved_place -> static arg),
    so the same coverage now runs in-process with a monkeypatched
    env."""
    import numpy as np

    from m3_tpu.encoding.m3tsz import encode_series
    from m3_tpu.encoding.m3tsz_jax import encode_batch, resolved_place

    monkeypatch.setenv("M3_ENCODE_PLACE", "gather")
    assert resolved_place() == "gather"
    rng = np.random.default_rng(2)
    S, T = 16, 360
    start = 1_700_000_000 * 10**9
    ts = start + np.cumsum(rng.integers(1, 3, (S, T)), axis=1) * 10**10
    vals = np.round(rng.normal(50, 20, (S, T)), 2)
    streams, fb = encode_batch(ts, vals, np.full(S, start, np.int64),
                               out_words=T * 40 // 64 + 8)
    assert not fb.any()
    for i in range(S):
        oracle = encode_series(list(zip(ts[i].tolist(), vals[i].tolist())),
                               start=start)
        assert streams[i] == oracle, f"series {i} diverged"


def test_encode_place_env_flip_works_in_process(monkeypatch):
    """Round-7 retrace-risk regression: M3_ENCODE_PLACE used to be
    read UNDER the tracer, so an in-process env flip after the first
    encode changed NOTHING (the jit cache keyed on the static args,
    not the env).  The seam now resolves in the host wrapper and rides
    as a static argument: flipping the env must actually select the
    other placement (observable as a fresh compile cache entry) and
    stay byte-identical."""
    import numpy as np

    from m3_tpu.encoding import m3tsz_jax as mj

    rng = np.random.default_rng(5)
    S, T = 4, 48
    start = 1_700_000_000 * 10**9
    ts = start + np.cumsum(rng.integers(1, 3, (S, T)), axis=1) * 10**10
    vals = np.round(rng.normal(50, 20, (S, T)), 2)
    starts = np.full(S, start, np.int64)

    monkeypatch.delenv("M3_ENCODE_PLACE", raising=False)
    # tests pin the CPU backend: auto = the scatter-free gather form
    # (pallas only ever auto-resolves on a real TPU backend)
    assert mj.resolved_place() == "gather"
    a, fb_a = mj.encode_batch(ts, vals, starts, out_words=T * 40 // 64 + 8)
    size_gather = mj._encode_batch_device._cache_size()

    monkeypatch.setenv("M3_ENCODE_PLACE", "scatter")
    assert mj.resolved_place() == "scatter"
    b, fb_b = mj.encode_batch(ts, vals, starts, out_words=T * 40 // 64 + 8)
    # the flip actually took: the scatter form is a new static signature
    assert mj._encode_batch_device._cache_size() > size_gather
    assert not fb_a.any() and not fb_b.any()
    assert a == b  # placement forms are byte-identical by contract

    monkeypatch.setenv("M3_ENCODE_PLACE", "bogus")
    import pytest

    with pytest.raises(ValueError, match="M3_ENCODE_PLACE"):
        mj.resolved_place()


def test_encoder_bytes_pinned_across_dtype_hardening():
    """Bit-identity pin for the m3lint explicit-dtype hardening: this
    fixed batch was verified byte-identical before/after dtype= was
    made explicit in m3tsz_jax.py, and the digest pins it forever.
    Any change to a constructor's effective dtype — including a future
    x64-default flip the explicit dtypes now guard against — shows up
    here as a byte diff, not as a silent re-encode.

    Inputs are pure integer/dyadic arithmetic (no RNG, no libm): every
    value is exactly representable, so the batch is bit-stable across
    NumPy versions and platforms — the digest depends on the encoder
    alone."""
    import hashlib

    from m3_tpu.encoding.m3tsz_jax import pack_streams

    SEC = 10**9
    S0 = 1_600_000_000 * SEC
    S, T = 8, 64
    i = np.arange(S, dtype=np.int64)[:, None]
    j = np.arange(T, dtype=np.int64)[None, :]
    deltas = ((i * 37 + j * 11) % 29 + 1) * SEC        # 1..29s steps
    ts = S0 + np.cumsum(deltas, axis=1)
    vals = ((i * 131 + j * 17) % 4001 - 2000) / 8.0    # dyadic: exact f64
    vals[2] = np.float64((j[0] * 7) % 1000)            # int-optimized lane
    vals[5, 10:] = vals[5, 9]                          # repeated-value lane
    streams, fb = encode_batch(ts, vals, np.full(S, S0, np.int64),
                               out_words=200)
    assert not fb.any(), fb
    words, nbits = pack_streams(streams)
    assert words.dtype == np.uint64 and nbits.dtype == np.int64
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(words).tobytes())
    h.update(np.ascontiguousarray(nbits).tobytes())
    assert h.hexdigest() == PINNED_ENCODE_DIGEST


# sha256 over (packed words || nbits) of the arithmetic batch above,
# captured on BOTH the pre-dtype-hardening tree (HEAD file) and the
# hardened tree — identical, proving the hardening was a no-op on the
# bytes.
PINNED_ENCODE_DIGEST = (
    "27ea67c4b75585a1e2bffa6cfeae5e5faeefbaca75de4d5c4c559f15d89ccc18")
