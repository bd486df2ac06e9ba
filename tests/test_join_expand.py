"""The join expansion's lane -> row map and the scans it is built on.

``expand_runs`` gives every output lane the left row whose run of
candidates holds it, by a scatter of row marks and a running max
(``prefix_max``).  It is checked against the binary search of the run
starts that it replaced, and the two bounded joins built on it against
a numpy join, lane for lane, pad lanes included.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mergejoin.ops import (expand_runs,
                                         merge_join_bounded,
                                         merge_join_gather_bounded)
from repro.kernels.prefix import prefix_max, prefix_sum

I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min

SCAN_LENGTHS = [1, 1023, 1024, 1025, 3 * 1024 + 7]


def _counts(pattern: str, rng):
    """(counts, out_cap) for one count pattern."""
    if pattern == "random_many_zeros":
        c = rng.randint(1, 5, 300) * (rng.rand(300) < 0.3)
        return c, 3000
    if pattern == "all_zeros":
        return np.zeros(300, np.int64), 512
    if pattern == "zero_runs_at_both_ends":
        c = rng.randint(0, 6, 300)
        c[:40] = 0
        c[-40:] = 0
        return c, 2048
    if pattern == "one_row":
        return np.asarray([7]), 16
    if pattern == "total_past_out_cap":
        return rng.randint(0, 10, 300), 256
    if pattern == "out_cap_below_cap_l":
        return rng.randint(1, 3, 2000) * (rng.rand(2000) < 0.1), 512
    raise ValueError(pattern)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def _expand(lo, counts, out_cap):
    return expand_runs(lo, counts, out_cap)


@pytest.mark.parametrize("pattern", [
    "random_many_zeros", "all_zeros", "zero_runs_at_both_ends", "one_row",
    "total_past_out_cap", "out_cap_below_cap_l"])
def test_expand_runs_matches_binary_search(pattern):
    rng = np.random.RandomState(7)
    counts, out_cap = _counts(pattern, rng)
    counts = counts.astype(np.int32)
    lo = rng.randint(0, 1000, counts.shape[0]).astype(np.int32)
    row, pos, valid, total = (np.asarray(a) for a in _expand(
        jnp.asarray(lo), jnp.asarray(counts), out_cap))
    # the map it replaced: binary search of each lane in the run starts
    starts = np.cumsum(counts, dtype=np.int64) - counts
    lane = np.arange(out_cap)
    old_row = np.clip(np.searchsorted(starts, lane, side="right") - 1,
                      0, counts.shape[0] - 1)
    old_pos = lo[old_row] + (lane - starts[old_row])
    assert int(total) == int(counts.sum())
    np.testing.assert_array_equal(valid, lane < counts.sum())
    live = lane < min(int(total), out_cap)
    np.testing.assert_array_equal(row[live], old_row[live])
    np.testing.assert_array_equal(pos[live], old_pos[live])
    assert row.dtype == np.int32 and pos.dtype == np.int32


@pytest.mark.parametrize("n", SCAN_LENGTHS)
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_prefix_max_matches_numpy(n, dtype):
    x = np.random.RandomState(n).randint(-10**6, 10**6, n).astype(dtype)
    got = np.asarray(jax.jit(prefix_max)(jnp.asarray(x)))
    assert got.dtype == dtype
    np.testing.assert_array_equal(got, np.maximum.accumulate(x))


@pytest.mark.parametrize("n", SCAN_LENGTHS)
def test_prefix_sum_matches_numpy(n):
    x = np.random.RandomState(n).randint(-10**6, 10**6, n).astype(np.int64)
    got = np.asarray(jax.jit(prefix_sum)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.cumsum(x))
    b = x > 0
    np.testing.assert_array_equal(
        np.asarray(jax.jit(prefix_sum)(jnp.asarray(b))), np.cumsum(b))


def _pairs(lk, rk):
    """Candidate pairs in the joins' emission order: left rows in order,
    each row's right lanes in lane order (the right side is sorted by
    key, then lane)."""
    return [(i, j) for i in range(len(lk))
            for j in np.flatnonzero(rk == lk[i])]


@pytest.mark.parametrize("algo", ["MJ", "HJ"])
@pytest.mark.parametrize("verify", [False, True])
@pytest.mark.parametrize("out_cap", [4096, 300])
def test_gather_bounded_matches_numpy_join(algo, verify, out_cap):
    rng = np.random.RandomState(11)
    cap_l, cap_r, n_l, n_r = 256, 128, 230, 100
    # pad lanes hold garbage that would join if the program read it
    l_keys = rng.randint(0, 20, cap_l).astype(np.int64)
    r_keys = rng.randint(0, 20, cap_r).astype(np.int64)
    l_pay = (np.arange(cap_l, dtype=np.int64) * 3 + 1,
             rng.randint(-50, 50, cap_l).astype(np.int64))
    r_pay = (np.arange(cap_r, dtype=np.int64) * 5 + 2,)
    vl = rng.randint(0, 3, cap_l).astype(np.int64)
    vr = rng.randint(0, 3, cap_r).astype(np.int64)
    ver_l, ver_r = ((vl,), (vr,)) if verify else ((), ())
    l_out, r_out, stats = merge_join_gather_bounded(
        *(jnp.asarray(a) for a in (l_keys, r_keys)), n_l, n_r,
        tuple(map(jnp.asarray, l_pay)), tuple(map(jnp.asarray, r_pay)),
        tuple(map(jnp.asarray, ver_l)), tuple(map(jnp.asarray, ver_r)),
        out_cap=out_cap, hash_keys=algo == "HJ")
    cands = _pairs(l_keys[:n_l], r_keys[:n_r])
    kept = [(i, j) for i, j in cands[:out_cap]
            if not verify or vl[i] == vr[j]]
    assert len(cands) > 300  # the small cap overflows
    want_stats = [len(kept), len(cands), 0]
    np.testing.assert_array_equal(np.asarray(stats), want_stats)
    for got, pay, side in ((l_out, l_pay, 0), (r_out, r_pay, 1)):
        for g, p in zip(got, pay):
            want = np.zeros(out_cap, np.int64)
            want[:len(kept)] = p[[pr[side] for pr in kept]]
            np.testing.assert_array_equal(np.asarray(g), want)


@pytest.mark.parametrize("out_cap", [4096, 300])
@pytest.mark.parametrize("pallas", [False, True])
def test_merge_join_bounded_matches_numpy_join(out_cap, pallas):
    rng = np.random.RandomState(13)
    # array-tier inputs are padded by the caller: left MAX, right MIN
    l = np.concatenate([rng.randint(0, 20, 200), [I64_MAX] * 56])
    r = np.concatenate([rng.randint(0, 20, 100), [I64_MIN] * 28])
    l, r = l.astype(np.int64), r.astype(np.int64)
    li, ri, valid, total = (np.asarray(a) for a in merge_join_bounded(
        jnp.asarray(l), jnp.asarray(r), out_cap=out_cap, block=64,
        force_pallas=pallas, interpret=pallas))
    cands = _pairs(l, r)
    assert len(cands) > 300
    k = min(len(cands), out_cap)
    want_li = np.full(out_cap, -1, np.int32)
    want_ri = np.full(out_cap, -1, np.int32)
    want_li[:k] = [i for i, _ in cands[:k]]
    want_ri[:k] = [j for _, j in cands[:k]]
    assert int(total) == len(cands)
    np.testing.assert_array_equal(valid, np.arange(out_cap) < len(cands))
    np.testing.assert_array_equal(li, want_li)
    if not pallas:
        np.testing.assert_array_equal(ri, want_ri)
        return
    # the Pallas bitonic sort orders equal right keys by no fixed rule:
    # each lane reads a right lane of the same key, and the pair sets agree
    np.testing.assert_array_equal(ri[k:], want_ri[k:])
    np.testing.assert_array_equal(r[ri[:k]], r[want_ri[:k]])
    if k == len(cands):
        assert sorted(zip(li[:k], ri[:k])) == cands
