"""Phase-2 field-gather kernel: clean CPU fallback + impl bit-parity.

ISSUE 6's CI guard: on a CPU-only host the decode path must never try
to compile Mosaic — ``auto`` resolves to the jnp gather — and the
Pallas kernel (exercised here in interpret mode) must be bit-equal to
the jnp funnel on the same inputs, so flipping M3_DECODE_EXTRACT on a
real TPU cannot change decoded bytes.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from m3_tpu.parallel import pallas_decode as pd  # noqa: E402


def _rand_words(rng, S, W32):
    return jnp.asarray(
        rng.integers(0, 1 << 32, (S, W32), dtype=np.uint64).astype(np.uint32))


def _rand_lanes(rng, S, P, total_bits):
    offs = jnp.asarray(rng.integers(0, total_bits, (S, P), dtype=np.int64)
                       .astype(np.int32))
    widths = jnp.asarray(rng.integers(0, 65, (S, P), dtype=np.int64)
                         .astype(np.int32))
    return offs, widths


class TestFallbackResolution:
    def test_auto_resolves_jnp_off_tpu(self):
        """THE tier-1 guard: a CPU-only host must fall back cleanly —
        no Mosaic compile attempt anywhere in the decode path."""
        assert jax.default_backend() != "tpu"  # tier-1 runs on CPU
        assert pd.resolved_impl() == "jnp"

    def test_env_override_validated(self, monkeypatch):
        monkeypatch.setenv("M3_DECODE_EXTRACT", "jnp")
        assert pd.resolved_impl() == "jnp"
        monkeypatch.setenv("M3_DECODE_EXTRACT", "magic")
        with pytest.raises(ValueError, match="M3_DECODE_EXTRACT"):
            pd.configured_impl()

    def test_auto_interpret_off_tpu(self):
        assert pd.auto_interpret() is True

    def test_decode_batch_device_runs_on_cpu_host(self):
        """End-to-end: the full two-phase decode works on a CPU-only
        host with no env pins at all (the production import path)."""
        from m3_tpu.encoding.m3tsz_jax import decode_batch, encode_batch

        START = 1_600_000_000 * 10**9
        ts = np.tile(START + np.arange(1, 21) * 10**9, (2, 1)).astype(np.int64)
        vals = np.tile(np.arange(20, dtype=np.float64), (2, 1))
        streams, fb = encode_batch(ts, vals, np.full(2, START, np.int64),
                                   out_words=40)
        assert not fb.any()
        _, _, counts, fb2 = decode_batch([bytes(s) for s in streams], 21)
        assert not fb2.any() and (counts == 20).all()


class TestExtractParity:
    """jnp gather vs Pallas kernel (interpret mode = Mosaic semantics
    without a TPU): bit-equal on random words/offsets/widths, including
    width 0, width 64, and offsets past the stream (zero padding)."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_pallas_interpret_matches_jnp(self, seed):
        rng = np.random.default_rng(seed)
        S, W32, P = 3, 40, 17
        words = _rand_words(rng, S, W32)
        # >= 2 zero pad words is the documented caller contract
        words = jnp.pad(words, ((0, 0), (0, 4)))
        offs, widths = _rand_lanes(rng, S, P, total_bits=W32 * 32 + 96)
        a = pd.extract_fields(words, offs, widths, impl="jnp")
        b = pd.extract_fields(words, offs, widths, impl="pallas",
                              interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_edge_widths_and_offsets(self):
        words = jnp.asarray(
            np.array([[0xDEADBEEF, 0x01234567, 0x89ABCDEF, 0, 0, 0]],
                     np.uint32))
        offs = jnp.asarray(np.array([[0, 31, 32, 64, 95, 300]], np.int32))
        widths = jnp.asarray(np.array([[0, 1, 64, 33, 1, 64]], np.int32))
        a = pd.extract_fields(words, offs, widths, impl="jnp")
        b = pd.extract_fields(words, offs, widths, impl="pallas",
                              interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # spot-check the funnel semantics: width 0 -> 0; full first word
        got = np.asarray(a)[0]
        assert got[0] == 0
        assert got[2] == (0x01234567_89ABCDEF + (0xDEADBEEF << 64)) % (1 << 64)

    def test_u64_scan_major_matches_u32(self):
        """extract_fields64_t (the jnp fast path over u64 words) must
        agree with the u32 funnel on the packed32 view of the same
        stream — the two word representations are interchangeable."""
        rng = np.random.default_rng(7)
        S, W, F = 4, 20, 31
        w64 = rng.integers(0, 1 << 63, (S, W), dtype=np.uint64)
        w64 = np.pad(w64, ((0, 0), (0, 2)))
        w32 = np.stack([(w64 >> 32).astype(np.uint32),
                        (w64 & 0xFFFFFFFF).astype(np.uint32)],
                       axis=2).reshape(S, -1)
        offs = rng.integers(0, W * 64, (F, S), dtype=np.int64).astype(np.int32)
        widths = rng.integers(0, 65, (F, S), dtype=np.int64).astype(np.int32)
        a = pd.extract_fields64_t(jnp.asarray(w64.T), jnp.asarray(offs),
                                  jnp.asarray(widths))
        b = pd.extract_fields_t(jnp.asarray(w32.T), jnp.asarray(offs),
                                jnp.asarray(widths), impl="jnp")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestShardedDecodeParity:
    """parallel/sharded_decode: the series-sharded decode (one scan per
    local device) must be bit-identical to the single-device jit, on an
    uneven S that exercises the zero-pad path (conftest provides 8
    virtual CPU devices)."""

    @pytest.mark.parametrize("scan_major", [False, True])
    def test_bit_identical_with_padding(self, scan_major):
        from m3_tpu.encoding.m3tsz_jax import (
            decode_batch_device, encode_batch, pack_streams)
        from m3_tpu.parallel.sharded_decode import (
            decode_batch_device_sharded)

        assert jax.device_count() > 1  # conftest's virtual mesh
        START = 1_600_000_000 * 10**9
        S, T = 11, 40  # 11 % 8 != 0 -> pad rows decode + get sliced
        rng = np.random.default_rng(3)
        ts = np.tile(START + np.arange(1, T + 1) * 10**9,
                     (S, 1)).astype(np.int64)
        vals = np.round(rng.normal(50, 5, (S, T)), 2)
        streams, fb = encode_batch(ts, vals, np.full(S, START, np.int64),
                                   out_words=60)
        assert not fb.any()
        words, nbits = pack_streams([bytes(s) for s in streams])
        words = jnp.asarray(words)
        nbits = jnp.asarray(nbits)
        a = decode_batch_device(words, nbits, T + 1,
                                scan_major=scan_major)
        b = decode_batch_device_sharded(words, nbits, T + 1,
                                        scan_major=scan_major)
        for i, name in enumerate(("ts", "payload", "meta", "err",
                                  "prec", "ann")):
            np.testing.assert_array_equal(np.asarray(a[i]),
                                          np.asarray(b[i]), err_msg=name)


class TestChainsSeamSubprocess:
    @pytest.mark.slow
    def test_bad_chains_env_rejected(self):
        """M3_DECODE_CHAINS typos must raise, not silently run a
        default (the measurement-integrity contract)."""
        code = (
            "import os; os.environ['M3_DECODE_CHAINS']='magic';"
            "os.environ['JAX_PLATFORMS']='cpu';"
            "from m3_tpu.encoding.m3tsz_jax import resolved_chains;"
            "resolved_chains()"
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True)
        assert r.returncode != 0
        assert "M3_DECODE_CHAINS" in r.stderr
