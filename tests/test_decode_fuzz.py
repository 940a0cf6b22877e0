"""Round-6 two-phase decode: fuzz/property suite + sha256-pinned corpus.

Three layers of bit-identity evidence for the two-phase rewrite
(ISSUE 6), all against the golden-validated scalar codec (m3tsz.py):

* corpus — committed real-shape streams (tests/data/decode_corpus.json,
  regenerate with gen_decode_corpus.py) whose scalar-decoded output is
  sha256-pinned IN the file; both chains tails must reproduce the exact
  digest, covering NaN/±Inf, a mid-stream time-unit change and
  annotated streams.
* fuzz — random series families through the batched encoder, decoded by
  BOTH chains tails, exact (timestamp, value-bits) equality vs
  decode_series.
* properties — targeted edges: every dod bucket width, XOR
  contained/uncontained flips, int<->float mode churn, a cursor that
  reads into the padding, an annotation jump, the served path's
  smallest word bucket.

Each runs under every formulation in ``FORMS``: both chains tails as
the host wrapper resolves them on this backend (the ``gather``
register file off a TPU), and both tails with the ``select`` register
file, the TPU's, by calling the jitted program with the static.

Timestamp equality is on int64s; value equality is on the raw float64
BIT PATTERNS (``.view(uint64)``) — the decoder's contract is
bit-identity, and float compares would pass NaN-payload or -0.0 drift.
"""

import base64
import hashlib
import json
import os

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from tests.conftest import DATA_DIR  # noqa: E402
from m3_tpu.core.xtime import Unit  # noqa: E402
from m3_tpu.encoding.m3tsz import Datapoint, Encoder, decode_series  # noqa: E402
from m3_tpu.encoding import m3tsz_jax as mj  # noqa: E402
from m3_tpu.encoding.m3tsz_jax import decode_batch, encode_batch  # noqa: E402

START = 1_600_000_000 * 10**9
SEC = 10**9
# (chains, regfile): regfile None = the host wrapper's own resolution
FORMS = [pytest.param("fused", None, id="fused"),
         pytest.param("gather", None, id="gather"),
         pytest.param("fused", "select", id="fused-select"),
         pytest.param("gather", "select", id="gather-select")]
_FORM_VALUES = [p.values for p in FORMS]


def decode_as(streams, max_points, chains, regfile=None, pad_words=0,
              annotations_fallback=False):
    """``decode_batch`` under one formulation: through the host wrapper
    where ``regfile`` is None, else the jitted program called with the
    ``regfile`` static (the wrapper resolves it by backend alone)."""
    if regfile is None and not pad_words:
        return decode_batch(streams, max_points=max_points,
                            annotations_fallback=annotations_fallback,
                            chains=chains)
    words, nbits = mj.pack_streams(streams, pad_words=pad_words)
    return mj._decoded_to_host(mj._decode_batch_device(
        jnp.asarray(words), jnp.asarray(nbits), mj.value_ctrl_table(),
        max_points=max_points, default_unit=int(Unit.SECOND),
        chains=chains, scan_major=True,
        extract=mj._resolved_extract(chains),
        regfile=regfile or "gather"), annotations_fallback)


def _digest(ts_list, bits_list):
    """Must match gen_decode_corpus.canonical_digest."""
    h = hashlib.sha256()
    for ts, bits in zip(ts_list, bits_list):
        h.update(np.int64(len(ts)).tobytes())
        h.update(np.asarray(ts, np.int64).tobytes())
        h.update(np.asarray(bits, np.uint64).tobytes())
    return h.hexdigest()


def _scalar_ts_bits(stream):
    pts = decode_series(stream)
    return (np.array([p.timestamp for p in pts], np.int64),
            np.array([p.value for p in pts], np.float64).view(np.uint64))


def _assert_batched_matches_scalar(streams, max_points, chains,
                                   regfile=None, pad_words=0, pad_rows=0):
    ts, vals, counts, fb = decode_as(streams + [b""] * pad_rows, max_points,
                                     chains, regfile, pad_words)
    assert not fb[:len(streams)].any(), (f"unexpected fallback under chains={chains} "
                          f"regfile={regfile}")
    for i, s in enumerate(streams):
        want_ts, want_bits = _scalar_ts_bits(s)
        n = int(counts[i])
        assert n == len(want_ts), f"series {i}: count {n} != {len(want_ts)}"
        np.testing.assert_array_equal(ts[i, :n], want_ts,
                                      err_msg=f"series {i} timestamps")
        got_bits = vals[i, :n].copy().view(np.uint64)
        np.testing.assert_array_equal(got_bits, want_bits,
                                      err_msg=f"series {i} value bits")


class TestPinnedCorpus:
    @pytest.fixture(scope="class")
    def corpus(self):
        with open(DATA_DIR / "decode_corpus.json") as f:
            doc = json.load(f)
        return doc, [base64.b64decode(s) for s in doc["streams"]]

    def test_scalar_decoder_matches_pin(self, corpus):
        """The committed digest IS the scalar decoder's output — if this
        fails the corpus file drifted (or the scalar codec changed),
        and the batched assertions below would be pinning the wrong
        thing."""
        doc, streams = corpus
        ts_list, bits_list = zip(*(_scalar_ts_bits(s) for s in streams))
        assert _digest(ts_list, bits_list) == doc["sha256"]

    @pytest.mark.parametrize("chains,regfile", FORMS)
    def test_batched_decode_matches_pin(self, corpus, chains, regfile):
        doc, streams = corpus
        ts, vals, counts, fb = decode_as(streams, doc["max_points"], chains,
                                         regfile)
        assert not fb.any()
        ts_list = [ts[i, :int(n)] for i, n in enumerate(counts)]
        bits_list = [vals[i, :int(n)].copy().view(np.uint64)
                     for i, n in enumerate(counts)]
        assert _digest(ts_list, bits_list) == doc["sha256"]


def _fuzz_batch(seed, S, T):
    """One (S, T) batch mixing the series families that hit different
    control paths: ints (diff chain), decimals (multiplier), floats
    (XOR chain), constants (repeat), spikes (uncontained XOR), NaN/Inf
    (special exponents), jittered cadence (all dod buckets)."""
    rng = np.random.default_rng(seed)
    cad_s = int(rng.integers(2, 30))
    ts = START + np.arange(1, T + 1) * (cad_s * SEC)
    ts = np.tile(ts, (S, 1)).astype(np.int64)
    # Jitter in WHOLE seconds so the time unit stays SECOND: sub-second
    # offsets would force the NANOS unit, whose deltas overflow the
    # 32-bit dod escape and legitimately flag encoder fallback.
    jit_rows = rng.random(S) < 0.5
    ts[jit_rows] += rng.integers(-(cad_s // 2), cad_s // 2,
                                 (int(jit_rows.sum()), T)) * SEC
    ts.sort(axis=1)
    vals = np.zeros((S, T))
    for i in range(S):
        fam = rng.integers(0, 6)
        if fam == 0:
            vals[i] = np.cumsum(rng.integers(-100, 100, T))
        elif fam == 1:
            vals[i] = np.round(rng.normal(0, 50, T),
                               int(rng.integers(0, 5)))
        elif fam == 2:
            vals[i] = rng.normal(0, 1, T)  # raw floats
        elif fam == 3:
            vals[i] = float(rng.integers(-5, 5))  # constant
        elif fam == 4:
            v = np.full(T, 7.25)
            v[rng.integers(0, T, max(1, T // 20))] = rng.choice(
                [1e8, -3e7, 0.0001])
            vals[i] = v
        else:
            v = np.round(rng.normal(10, 2, T), 2)
            v[rng.random(T) < 0.05] = np.nan
            v[rng.random(T) < 0.02] = np.inf * rng.choice([-1, 1])
            vals[i] = v
    starts = np.full(S, START, np.int64)
    return ts, vals, starts


class TestFuzzRoundtrip:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_encode_decode_vs_scalar(self, seed):
        S, T = 12, 120
        ts, vals, starts = _fuzz_batch(seed, S, T)
        streams, fb = encode_batch(ts, vals, starts, out_words=256)
        assert not fb.any()
        for chains, regfile in _FORM_VALUES:
            _assert_batched_matches_scalar(
                [bytes(s) for s in streams], T + 1, chains, regfile)

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(4, 16))
    def test_encode_decode_vs_scalar_deep(self, seed):
        S, T = 12, 120
        ts, vals, starts = _fuzz_batch(seed, S, T)
        streams, fb = encode_batch(ts, vals, starts, out_words=256)
        assert not fb.any()
        for chains, regfile in _FORM_VALUES:
            _assert_batched_matches_scalar(
                [bytes(s) for s in streams], T + 1, chains, regfile)


class TestDecodeProperties:
    def _encode_scalar(self, pts):
        enc = Encoder(START)
        for dp in pts:
            enc.encode(dp)
        return enc.stream()

    def test_every_dod_bucket_width(self):
        """Deltas hitting each timestamp opcode bucket (0/7/9/12-bit
        and the 32-bit default escape) in one stream."""
        t, pts = START, []
        for i, d in enumerate([10, 10, 10, 25, 10, 300, 10, 4000, 10,
                               2_000_000, 10, 10]):
            t += d * SEC
            pts.append(Datapoint(t, float(i)))
        streams = [self._encode_scalar(pts)]
        for chains, regfile in _FORM_VALUES:
            _assert_batched_matches_scalar(streams, len(pts) + 1, chains,
                                           regfile)

    def test_xor_contained_uncontained_flips(self):
        """Value sequence engineered to flip between contained and
        uncontained XOR windows and through zero-XOR repeats."""
        vs = [1.5, 1.5, 1.25, 1.2500000001, -1.25, 1.5e300, 1.5e-300,
              0.1, 0.1, 0.30000000000000004, 2.0**52, 1.0]
        pts = [Datapoint(START + (i + 1) * SEC, v)
               for i, v in enumerate(vs)]
        streams = [self._encode_scalar(pts)]
        for chains, regfile in _FORM_VALUES:
            _assert_batched_matches_scalar(streams, len(pts) + 1, chains,
                                           regfile)

    def test_int_float_mode_churn(self):
        """int -> float -> int transitions exercise the to-float /
        to-int-update control paths and the multiplier updates."""
        vs = [3.0, 4.0, 4.5, 4.75, 5.0, 6.0, 0.125, 7.0, 7.25, 8.0]
        pts = [Datapoint(START + (i + 1) * SEC, v)
               for i, v in enumerate(vs)]
        streams = [self._encode_scalar(pts)]
        for chains, regfile in _FORM_VALUES:
            _assert_batched_matches_scalar(streams, len(pts) + 1, chains,
                                           regfile)

    def test_single_point_and_two_point_streams(self):
        streams = [
            self._encode_scalar([Datapoint(START + SEC, 1.0)]),
            self._encode_scalar([Datapoint(START + SEC, np.nan),
                                 Datapoint(START + 2 * SEC, np.nan)]),
        ]
        for chains, regfile in _FORM_VALUES:
            _assert_batched_matches_scalar(streams, 4, chains, regfile)

    @pytest.mark.parametrize("chains,regfile", FORMS)
    def test_cursor_reads_last_word_and_pad(self, chains, regfile):
        """Streams that end on their array's last real word, packed with
        no slack word: the final steps' register file reaches past it
        into the decoder's own zero padding."""
        rng = np.random.default_rng(7)
        streams = []
        for n in (5, 17, 40, 63):
            vals = rng.normal(0, 1, n)      # raw floats: long steps
            pts = [Datapoint(START + (i + 1) * SEC, float(v))
                   for i, v in enumerate(vals)]
            streams.append(self._encode_scalar(pts))
        width = max(-(-len(s) // 8) for s in streams)
        _assert_batched_matches_scalar(streams, 64, chains, regfile,
                                       pad_words=width)

    @pytest.mark.parametrize("chains,regfile", FORMS)
    def test_annotation_jump(self, chains, regfile):
        """An annotation of 6,144 bits mid-stream: one step moves the
        cursor across many words at once."""
        enc = Encoder(START)
        enc.encode(Datapoint(START + SEC, 1.5, Unit.SECOND, b"schema-v1"))
        enc.encode(Datapoint(START + 2 * SEC, 2.5, Unit.SECOND))
        enc.encode(Datapoint(START + 3 * SEC, 3.5, Unit.SECOND,
                             bytes(range(256)) * 3))
        for k in range(4, 30):
            enc.encode(Datapoint(START + k * SEC, k * 0.25, Unit.SECOND))
        _assert_batched_matches_scalar([enc.stream()], 40, chains, regfile)

    @pytest.mark.parametrize("chains,regfile", FORMS)
    def test_min_word_bucket_of_64(self, chains, regfile):
        """The served read's smallest shape (storage/database.py
        `_decode_streams`): 128 rows, 64 words, empty padding rows."""
        rng = np.random.default_rng(11)
        streams = []
        for i in range(100):
            n = int(rng.integers(1, 120))
            vals = np.cumsum(rng.integers(0, 9, n)).astype(float)
            pts = [Datapoint(START + (k + 1) * 15 * SEC, float(v))
                   for k, v in enumerate(vals)]
            streams.append(self._encode_scalar(pts))
        assert max(len(s) for s in streams) // 8 + 2 <= 64
        _assert_batched_matches_scalar(streams, 128, chains, regfile,
                                       pad_words=64, pad_rows=28)


class TestRegfileResolution:
    """The host wrapper picks the register file by backend alone
    (``select`` on a TPU, ``gather`` elsewhere), says what ran, and its
    devguard fallback steps down to ``gather``."""

    @pytest.fixture
    def clean_guard(self):
        from m3_tpu.x import devguard, fault
        from m3_tpu.x.breaker import reset_registry

        def reset():
            fault.disarm()
            devguard.reset_stages()
            reset_registry()

        reset()
        yield
        reset()

    @pytest.mark.parametrize("backend,want", [("cpu", "gather"),
                                              ("tpu", "select")])
    def test_wrapper_resolves_by_backend(self, monkeypatch, clean_guard,
                                         backend, want):
        from m3_tpu.parallel import pallas_decode
        from m3_tpu.x import devguard, fault

        ts, vals, starts = _fuzz_batch(5, 6, 40)
        streams, fb = encode_batch(ts, vals, starts, out_words=128)
        assert not fb.any()
        streams = [bytes(s) for s in streams]
        monkeypatch.setattr(mj.jax, "default_backend", lambda: backend)
        # the Pallas extraction a "tpu" resolves runs interpreted here
        monkeypatch.setattr(pallas_decode, "auto_interpret", lambda: True)
        assert mj.resolved_regfile() == want
        _assert_batched_matches_scalar(streams, 41, "auto")
        assert mj.last_regfile() == want
        with fault.armed("device.dispatch", "error", n=1):
            _assert_batched_matches_scalar(streams, 41, "auto")
        assert devguard.counters()["device.decode.fallback_calls"] == 1
        assert mj.last_regfile() == "gather"
