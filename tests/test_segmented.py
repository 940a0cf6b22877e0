"""Direct coverage for parallel/segmented.py (head_flag_scan) and the
host plan that feeds it (query/device_fns._sorted_plan) — property
tests against numpy oracles.

Both are otherwise exercised only transitively through the query
engine's group-by; these tests pin their contracts directly: inclusive
within-segment prefix reductions for +/min/max (with trailing lane
dims), and clamped segment-end gather positions with a found mask."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from m3_tpu.parallel.segmented import head_flag_scan
from m3_tpu.query.device_fns import _sorted_plan


def _oracle_prefix(is_start: np.ndarray, x: np.ndarray, op):
    """Inclusive within-segment prefix reduction, position by position."""
    out = np.empty_like(x)
    seg = np.cumsum(is_start.astype(np.int64))
    for i in range(len(x)):
        mask = (seg == seg[i]) & (np.arange(len(x)) <= i)
        out[i] = op(x[mask], axis=0)
    return out


def _random_heads(rng, n: int) -> np.ndarray:
    is_start = rng.random(n) < 0.3
    if n:
        is_start[0] = True  # the contract: a sorted batch starts a segment
    return is_start


class TestHeadFlagScan:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_adds_mins_maxs_vs_oracle(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 200))
        is_start = _random_heads(rng, n)
        a = rng.integers(-1000, 1000, n).astype(np.int64)
        b = rng.normal(0, 50, n)
        c = rng.normal(0, 50, n)
        (sa, sb), (mn,), (mx,) = head_flag_scan(
            jnp.asarray(is_start), adds=(jnp.asarray(a), jnp.asarray(b)),
            mins=(jnp.asarray(c),), maxs=(jnp.asarray(c),))
        np.testing.assert_array_equal(np.asarray(sa),
                                      _oracle_prefix(is_start, a, np.sum))
        np.testing.assert_allclose(np.asarray(sb),
                                   _oracle_prefix(is_start, b, np.sum),
                                   rtol=1e-12)
        np.testing.assert_array_equal(np.asarray(mn),
                                      _oracle_prefix(is_start, c, np.min))
        np.testing.assert_array_equal(np.asarray(mx),
                                      _oracle_prefix(is_start, c, np.max))

    def test_trailing_lane_dims_broadcast(self):
        rng = np.random.default_rng(7)
        n, lanes = 64, 5
        is_start = _random_heads(rng, n)
        x = rng.normal(0, 10, (n, lanes))
        (s,), _, _ = head_flag_scan(jnp.asarray(is_start),
                                    adds=(jnp.asarray(x),))
        want = np.stack([
            _oracle_prefix(is_start, x[:, k], np.sum) for k in range(lanes)
        ], axis=1)
        np.testing.assert_allclose(np.asarray(s), want, rtol=1e-12)

    def test_single_segment_is_plain_prefix_scan(self):
        n = 37
        is_start = np.zeros(n, bool)
        is_start[0] = True
        x = np.arange(1, n + 1, dtype=np.int64)
        (s,), _, _ = head_flag_scan(jnp.asarray(is_start),
                                    adds=(jnp.asarray(x),))
        np.testing.assert_array_equal(np.asarray(s), np.cumsum(x))

    def test_every_position_a_head_is_identity(self):
        x = np.array([5, -2, 9], np.int64)
        (s,), (mn,), (mx,) = head_flag_scan(
            jnp.ones(3, bool), adds=(jnp.asarray(x),),
            mins=(jnp.asarray(x),), maxs=(jnp.asarray(x),))
        for got in (s, mn, mx):
            np.testing.assert_array_equal(np.asarray(got), x)

    def test_segment_totals_at_last_position(self):
        """The documented consumption pattern: the LAST position of a
        segment holds the full segment total (what the plan's ``pos``
        gathers)."""
        is_start = np.array([1, 0, 0, 1, 0, 1], bool)
        x = np.array([1, 2, 3, 10, 20, 100], np.int64)
        (s,), _, _ = head_flag_scan(jnp.asarray(is_start),
                                    adds=(jnp.asarray(x),))
        s = np.asarray(s)
        assert s[2] == 6 and s[4] == 30 and s[5] == 100


class TestSortedPlan:
    """The gather side of the scan, owned by the host since the group
    ids are its own array (`query/device_fns._sorted_plan`): where each
    group's segment ends in the stable-sorted order, clamped valid, with
    a found mask."""

    @staticmethod
    def _plan(gids, num_groups):
        return _sorted_plan(np.asarray(gids, np.int32), num_groups)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_vs_numpy_oracle(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 100))
        G = 50
        gids = rng.integers(0, 40, n)
        order, is_start, pos, found = self._plan(gids, G)
        keys = gids[order]
        assert (np.diff(keys) >= 0).all()
        # stable: equal keys keep their input order
        assert all(a < b for a, b, same in
                   zip(order[:-1], order[1:], keys[1:] == keys[:-1]) if same)
        np.testing.assert_array_equal(
            is_start, np.r_[True, keys[1:] != keys[:-1]])
        for g, p, f in zip(range(G), pos, found):
            hits = np.nonzero(keys == g)[0]
            assert f == bool(hits.size), (g, f)
            if hits.size:
                assert p == hits[-1], (g, p, hits)
            else:
                assert 0 <= p < n  # clamped valid for unconditional gather

    def test_no_groups(self):
        _, is_start, pos, found = self._plan([], 0)
        assert is_start.shape == pos.shape == found.shape == (0,)

    def test_single_key(self):
        order, is_start, pos, found = self._plan([4], 6)
        np.testing.assert_array_equal(
            found, [False, False, False, False, True, False])
        assert pos[4] == 0 and ((pos >= 0) & (pos < 1)).all()
        assert order.tolist() == [0] and is_start.tolist() == [True]

    def test_duplicates_pick_last(self):
        _, is_start, pos, found = self._plan([2, 5, 2, 5, 2], 6)
        assert pos[2] == 2 and pos[5] == 4
        assert found.tolist() == [False, False, True, False, False, True]
        assert is_start.tolist() == [True, False, False, True, False]
