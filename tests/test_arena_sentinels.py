"""Arena ingest sentinel/drop contract + reference-semantics oracle.

These tests predate round 6 as the scatter half of the sorted-vs-
scatter parity suite (tests/test_sorted_ingest.py).  The sorted impl
was deleted (round 5: 0.45-0.50x of scatter on CPU, never validated
faster on real TPU), but the CONTRACT it was parity-tested against is
package-wide and stays pinned here: invalid indices DROP (negative
slots must not numpy-wrap under mode='drop', slot >= C must not alias
window w+1's region), window-dropped samples still bump per-slot
expiry, and gauge semantics match a pure-Python reference oracle
(gauge.go: count NaN, sum/min/max skip NaN, last = max time with
first-arrival tie-break, strictly-newer replacement).
"""

import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from m3_tpu.aggregator import arena  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent


class TestScatterSentinels:
    def test_negative_slot_drops_not_wraps_via_flat_window_index(self):
        """Production call shape: negative and >=C slots through
        flat_window_index must DROP — including the last_at expiry
        column, where the raw scatter used to numpy-wrap slot -1 onto
        slot C-1."""
        W, C = 2, 8
        windows = jnp.asarray([0, 1, 0, 1], jnp.int32)
        slots = jnp.asarray([-1, -2, C, C + 2], jnp.int32)
        idx = arena.flat_window_index(windows, slots, W, C)
        st = arena.counter_ingest(
            arena.counter_init(W, C), idx, slots,
            jnp.asarray([5, 6, 7, 8], jnp.int64),
            jnp.asarray([100, 200, 300, 400], jnp.int64))
        assert int(np.asarray(st.count).sum()) == 0
        assert int(np.asarray(st.last_at).sum()) == 0

    def test_window_dropped_still_bumps_last_at(self):
        """A sample with an out-of-ring window is dropped from the
        arena lanes but must still advance its slot's last-write time
        (last_at updates by slot, unconditionally)."""
        W, C = 2, 16
        idx = jnp.asarray([W * C], jnp.int64)  # sentinel: window-dropped
        st = arena.counter_ingest(
            arena.counter_init(W, C), idx, jnp.asarray([7], jnp.int32),
            jnp.asarray([123], jnp.int64), jnp.asarray([999_999], jnp.int64))
        assert int(st.count.sum()) == 0
        assert int(st.last_at[7]) == 999_999

    def test_empty_batch_is_noop(self):
        # counter_ingest donates its state arg: compare the result
        # against a FRESH init, not the (now-invalidated) input.
        W, C = 2, 16
        st = arena.counter_ingest(arena.counter_init(W, C),
                                  jnp.zeros(0, jnp.int64),
                                  jnp.zeros(0, jnp.int32),
                                  jnp.zeros(0, jnp.int64),
                                  jnp.zeros(0, jnp.int64))
        for name in st._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(st, name)),
                np.asarray(getattr(arena.counter_init(W, C), name)),
                err_msg=name)

    def test_timer_dropped_samples_do_not_leak_into_buffer(self):
        """A slot-dropped sample must not consume quantile-buffer
        capacity or inflate sample_n: valid samples pack densely and
        counts reflect only what was appended."""
        W, C, S = 2, 8, 64
        st = arena.timer_ingest(
            arena.timer_init(W, C, S),
            jnp.asarray([0, 0, 0, 0], jnp.int32),
            jnp.asarray([C + 1, 3, -1, 5], jnp.int32),
            jnp.asarray([9.0, 1.0, 9.0, 2.0]),
            jnp.asarray([100] * 4, jnp.int64), C)
        assert int(st.sample_n[0]) == 2  # only the two valid slots
        np.testing.assert_array_equal(
            np.asarray(st.sample_slot[0][:2]), [3, 5])
        np.testing.assert_array_equal(
            np.asarray(st.sample_val[0][:2]), [1.0, 2.0])
        # moment lanes agree with the buffer: nothing from drops
        assert float(np.asarray(st.sum).sum()) == 3.0
        assert int(np.asarray(st.count).sum()) == 2
        assert int(st.last_at[3]) == 100 and int(st.last_at[5]) == 100
        assert int(np.asarray(st.last_at).sum()) == 200

    def test_timer_out_of_range_slot_drops_not_next_window(self):
        """slot >= C with a VALID window must DROP, not land in window
        w+1's region (w*C + slot aliasing — fuzz-caught)."""
        W, C, S = 3, 8, 64
        st = arena.timer_ingest(
            arena.timer_init(W, C, S), jnp.zeros(2, jnp.int32),
            jnp.asarray([C + 2, -1], jnp.int32),
            jnp.asarray([5.0, 7.0]),
            jnp.asarray([100, 101], jnp.int64), C)
        assert int(np.asarray(st.count).sum()) == 0
        assert float(np.asarray(st.sum).sum()) == 0.0


class TestOneFormulation:
    """No environment variable, setter or module chooses an arena's
    device program or the query engine's dtype."""

    @pytest.mark.parametrize("var,value", [
        ("M3_ARENA_INGEST", "pallas"), ("M3_ARENA_LAYOUT", "f64"),
        ("M3_QUERY_DTYPE", "f32")])
    def test_retired_env_var_selects_nothing(self, var, value):
        code = (
            "from m3_tpu.aggregator import arena\n"
            "from m3_tpu.query import precision\n"
            "print(*(type(a).__name__ for a in "
            "arena.make_arenas(1, 8, 32, (0.5,))),"
            " precision.compute_dtype())\n")
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu", var: value},
            cwd=REPO)
        assert r.returncode == 0, r.stderr
        assert r.stdout.split() == [
            "PackedCounterArena", "PackedGaugeArena", "PackedTimerArena",
            "float64"]

    def test_no_module_names_a_retired_seam(self):
        gone = ("M3_ARENA_INGEST", "M3_ARENA_LAYOUT", "M3_QUERY_DTYPE",
                "set_ingest_impl", "set_arena_layout", "pallas_ingest")
        files = [*REPO.joinpath("m3_tpu").rglob("*.py"),
                 REPO / "chip_smoke.py", REPO / "__graft_entry__.py"]
        assert len(files) > 100
        hits = [(str(f.relative_to(REPO)), name) for f in files
                for name in gone if name in f.read_text()]
        assert not hits, hits


class TestGaugeOracleFuzz:
    """Scatter impl vs a pure-Python reference-semantics oracle
    (gauge.go: count NaN, sum/min/max skip NaN, last = max time with
    first-arrival tie-break, strictly-newer replacement) under heavy
    time-tie pressure.  Trimmed from the 30-config round-5 fuzz
    (0 fails)."""

    def test_matches_python_oracle(self):
        rng = np.random.default_rng(55)
        for _ in range(4):
            W = int(rng.integers(1, 4))
            C = int(rng.integers(3, 60))
            N = int(rng.integers(1, 600))
            batches = []
            for _b in range(int(rng.integers(1, 3))):
                wd = rng.integers(0, W, N).astype(np.int32)
                sl = rng.integers(0, C, N).astype(np.int32)
                ts = (1000 + rng.integers(0, 40, N)).astype(np.int64)
                vl = np.round(rng.normal(0, 10, N), 4)
                vl[rng.random(N) < 0.08] = np.nan
                batches.append((wd, sl, ts, vl))
            st = arena.gauge_init(W, C)
            for wd, sl, ts, vl in batches:
                idx = arena.flat_window_index(
                    jnp.asarray(wd), jnp.asarray(sl), W, C)
                st = arena.gauge_ingest(st, idx, jnp.asarray(sl),
                                        jnp.asarray(vl),
                                        jnp.asarray(ts))
            o_sum = np.zeros(W * C)
            o_cnt = np.zeros(W * C, np.int64)
            o_last = np.zeros(W * C)
            o_lt = np.zeros(W * C, np.int64)
            for wd, sl, ts, vl in batches:
                for k in range(N):
                    i = wd[k] * C + sl[k]
                    o_cnt[i] += 1
                    if not np.isnan(vl[k]):
                        o_sum[i] += vl[k]
                    if ts[k] > o_lt[i]:
                        o_last[i] = vl[k]
                        o_lt[i] = ts[k]
            np.testing.assert_allclose(np.asarray(st.sum), o_sum,
                                       atol=1e-6)
            np.testing.assert_array_equal(np.asarray(st.count), o_cnt)
            np.testing.assert_array_equal(np.asarray(st.last), o_last)
            np.testing.assert_array_equal(np.asarray(st.last_time), o_lt)
