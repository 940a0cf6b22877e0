"""Native C++ codec vs the golden-validated Python scalar codec:
byte-identical encode, identical decode, correct fallback signaling."""

import numpy as np
import pytest

from m3_tpu import native
from m3_tpu.encoding.m3tsz import Datapoint, decode_series, encode_series

START = 1_700_000_000 * 10**9


def _cases():
    rng = np.random.default_rng(11)
    T = 300
    ts_reg = START + np.arange(1, T + 1) * 10 * 10**9
    out = []
    out.append(("int-ramp", ts_reg, (np.arange(T) % 97).astype(float)))
    out.append(("const", ts_reg, np.full(T, 42.0)))
    out.append(("decimal2", ts_reg, np.round(rng.normal(100, 10, T), 2)))
    out.append(("floats", ts_reg, rng.normal(0, 1, T)))
    out.append(("mixed", ts_reg, np.where(np.arange(T) % 7 == 0,
                                          rng.normal(0, 1, T),
                                          np.round(rng.uniform(0, 50, T), 1))))
    out.append(("big-counter", ts_reg, np.cumsum(rng.integers(0, 10**6, T)).astype(float)))
    out.append(("negative", ts_reg, -np.round(rng.uniform(0, 1000, T), 3)))
    # irregular timestamps crossing every dod bucket
    gaps = np.concatenate([
        np.full(50, 10), rng.integers(1, 60, 50), rng.integers(60, 2000, 30),
        rng.integers(2000, 300000, 10),
    ]) * 10**9
    ts_irr = START + np.cumsum(gaps)
    v = rng.normal(10, 1, len(ts_irr))
    out.append(("irregular-ts", ts_irr, v))
    out.append(("single", ts_reg[:1], np.array([3.5])))
    return out


@pytest.mark.parametrize("name,ts,vals", _cases(), ids=[c[0] for c in _cases()])
def test_encode_byte_identical(name, ts, vals):
    want = encode_series(list(zip(ts.tolist(), vals.tolist())), start=START)
    got = native.encode_series(ts, vals, START)
    assert got == want, f"{name}: native encode differs"


@pytest.mark.parametrize("name,ts,vals", _cases(), ids=[c[0] for c in _cases()])
def test_decode_matches(name, ts, vals):
    blob = encode_series(list(zip(ts.tolist(), vals.tolist())), start=START)
    out = native.decode_series(blob)
    assert out is not None
    dts, dvals = out
    np.testing.assert_array_equal(dts, ts)
    np.testing.assert_array_equal(dvals, vals)


def test_misaligned_start_falls_back():
    ts = START + 5 + np.arange(1, 10) * 10**10
    assert native.encode_series(ts, np.ones(9), START + 5) is None


def test_annotation_stream_falls_back():
    from m3_tpu.encoding.m3tsz import Encoder
    enc = Encoder(START)
    enc.encode(Datapoint(START + 10**10, 1.0, annotation=b"schema1"))
    enc.encode(Datapoint(START + 2 * 10**10, 2.0))
    assert native.decode_series(enc.stream()) is None


def test_corrupt_stream_raises():
    blob = encode_series([(START + 10**10, 1.0)], start=START)
    with pytest.raises(ValueError):
        native.decode_series(blob[:6])


def test_roundtrip_fuzz():
    rng = np.random.default_rng(7)
    for trial in range(25):
        n = int(rng.integers(1, 200))
        gaps = rng.integers(1, 100, n) * 10**9
        ts = START + np.cumsum(gaps)
        kind = trial % 3
        if kind == 0:
            vals = rng.integers(-(10**6), 10**6, n).astype(float)
        elif kind == 1:
            vals = np.round(rng.normal(0, 100, n), int(rng.integers(0, 5)))
        else:
            vals = rng.normal(0, 1e9, n)
        want = encode_series(list(zip(ts.tolist(), vals.tolist())), start=START)
        got = native.encode_series(ts, vals, START)
        assert got == want, f"trial {trial}"
        dts, dvals = native.decode_series(got)
        np.testing.assert_array_equal(dts, ts)
        # Contract: identical to the Python decoder.  (Not to the raw
        # input: the int optimization's nextafter tolerance may snap a
        # near-decimal float by 1 ulp — reference m3tsz.go:78-118 — and
        # both decoders must agree on that snapped value.)
        py_vals = np.array([d.value for d in decode_series(got)])
        np.testing.assert_array_equal(dvals, py_vals)


def test_batch_roundtrip_matches_single():
    """Batched encode/decode agree with the single-series entry points
    (and therefore with the Python oracle) across mixed value shapes."""
    rng = np.random.default_rng(13)
    S, T = 64, 97
    ts = np.tile(START + np.arange(1, T + 1) * 10 * 10**9, (S, 1)).astype(np.int64)
    vals = np.empty((S, T))
    vals[0::3] = rng.integers(-(10**6), 10**6, ((S + 2) // 3, T)).astype(float)
    vals[1::3] = np.round(rng.normal(0, 100, ((S + 1) // 3, T)), 2)
    vals[2::3] = rng.normal(0, 1e9, (S // 3, T))
    starts = np.full(S, START, np.int64)
    counts = rng.integers(1, T + 1, S)

    streams, fb = native.encode_batch(ts, vals, starts, counts=counts)
    assert not fb.any()
    for i in (0, 1, 2, 31, S - 1):
        n = int(counts[i])
        assert streams[i] == native.encode_series(ts[i, :n], vals[i, :n], START)

    dts, dvals, dcounts, dfb = native.decode_batch(streams, T + 1)
    assert not dfb.any()
    np.testing.assert_array_equal(dcounts, counts)
    for i in range(S):
        n = int(counts[i])
        sts, svals = native.decode_series(streams[i], max_points=T + 1)
        np.testing.assert_array_equal(dts[i, :n], sts)
        np.testing.assert_array_equal(dvals[i, :n], svals)


def test_batch_flags_bad_streams_and_continues():
    """A rejected or truncated stream flags fallback without poisoning
    its neighbours."""
    ts = START + np.arange(1, 9) * 10**10
    good = native.encode_series(ts, np.arange(8.0), START)

    from m3_tpu.encoding.m3tsz import Encoder
    enc = Encoder(START)
    enc.encode(Datapoint(START + 10**10, 1.0, annotation=b"s1"))
    annotated = enc.stream()

    streams = [good, annotated, good[:5], good]
    dts, dvals, counts, fb = native.decode_batch(streams, 16)
    assert list(fb) == [False, True, True, False]
    assert counts[0] == 8 and counts[3] == 8
    np.testing.assert_array_equal(dts[0, :8], ts)
    np.testing.assert_array_equal(dts[3, :8], ts)


def test_batch_threaded_matches_inline():
    rng = np.random.default_rng(5)
    S, T = 40, 50
    ts = np.tile(START + np.arange(1, T + 1) * 10**10, (S, 1)).astype(np.int64)
    vals = np.round(rng.normal(0, 50, (S, T)), 1)
    starts = np.full(S, START, np.int64)
    s1, _ = native.encode_batch(ts, vals, starts, nthreads=1)
    s4, _ = native.encode_batch(ts, vals, starts, nthreads=4)
    assert s1 == s4
    out1 = native.decode_batch(s1, T + 1, nthreads=1)
    out4 = native.decode_batch(s1, T + 1, nthreads=4)
    for a, b in zip(out1, out4):
        np.testing.assert_array_equal(a, b)
