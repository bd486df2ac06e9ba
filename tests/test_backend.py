"""Cross-backend parity: JaxOps ≡ NumpyOps, primitive and end-to-end.

The execution backend swaps the hot-path primitives (kernels -> backend ->
core joins/store -> engine config); both implementations must stay
oracle-equivalent.  Join pair *order* is unspecified, but sorts and the
SU dedup are now **stable on every backend** (the device path packs the
lane index into the bitonic sort's keys — tagged-key trick), so
permutations and surviving-duplicate choices are compared bit-exactly.
End-to-end runs compare inference fixpoints and query result sets over
the Table-1 config grid, and the device-residency suite asserts the
``JaxOps`` transfer counter: cached index state costs zero transfers at
an unchanged table version and delta-only uploads on append.
"""

import dataclasses
import itertools

import numpy as np
import pytest

from repro.backend import BACKENDS, get_backend
from repro.backend.jax_ops import JaxOps
from repro.backend.numpy_ops import NumpyOps
from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
from repro.core.conditions import AddAction, cond, term
from repro.core.rulesets import rdfs_plus_rules

HOST = NumpyOps()
RNG = np.random.RandomState(1234)


def device_backends():
    # jax[auto] exercises the wrappers' portable XLA lowering (Pallas on
    # TPU); jax[interpret] forces the Pallas kernel code path on CPU.
    return [pytest.param(get_backend("jax"), id="jax-auto"),
            pytest.param(JaxOps(mode="interpret", block=256),
                         id="jax-interpret")]


def pair_set(li, ri):
    return sorted(zip(li.tolist(), ri.tolist()))


# ---------------------------------------------------------------------------
# Primitive parity


@pytest.mark.parametrize("ops", device_backends())
def test_sort_kv_parity(ops):
    keys = RNG.randint(-1 << 40, 1 << 40, 500).astype(np.int64)
    vals = np.arange(500, dtype=np.int64)
    gk, gv = ops.sort_kv(keys, vals)
    wk, wv = HOST.sort_kv(keys, vals)
    np.testing.assert_array_equal(gk, wk)
    assert set(zip(gk.tolist(), gv.tolist())) == set(zip(wk.tolist(),
                                                         wv.tolist()))


@pytest.mark.parametrize("ops", device_backends())
@pytest.mark.parametrize("algo", ["MJ", "HJ"])
def test_join_pairs_parity(ops, algo):
    l = RNG.randint(0, 40, 300).astype(np.int64) * (1 << 33)  # true 64-bit
    r = RNG.randint(0, 40, 170).astype(np.int64) * (1 << 33)
    gli, gri = ops.join(l, r, algo)
    wli, wri = HOST.join(l, r, algo)
    assert pair_set(gli, gri) == pair_set(wli, wri)
    assert (l[gli] == r[gri]).all()


@pytest.mark.parametrize("ops", device_backends())
def test_join_pairs_overflow_rerun(ops):
    # all-equal keys: n*m pairs overflow the initial capacity bucket and
    # force the exact-total re-run
    l = np.zeros(80, np.int64)
    r = np.zeros(80, np.int64)
    gli, gri = ops.join_pairs(l, r)
    assert len(gli) == 80 * 80
    assert pair_set(gli, gri) == pair_set(*HOST.join_pairs(l, r))


@pytest.mark.parametrize("ops", device_backends())
def test_unique_mask_parity(ops):
    s = np.sort(RNG.randint(-20, 20, 400).astype(np.int64))
    np.testing.assert_array_equal(ops.unique_mask(s), HOST.unique_mask(s))


@pytest.mark.parametrize("ops", device_backends())
def test_semi_join_parity(ops):
    keys = RNG.randint(-15, 15, 250).astype(np.int64)
    bound = RNG.randint(-15, 15, 60).astype(np.int64)
    np.testing.assert_array_equal(ops.semi_join(keys, bound),
                                  HOST.semi_join(keys, bound))
    np.testing.assert_array_equal(
        ops.semi_join(keys, np.empty(0, np.int64)), np.zeros(250, bool))


@pytest.mark.parametrize("ops", device_backends())
@pytest.mark.parametrize("ncols", [1, 3])
def test_dedup_rows_parity(ops, ncols):
    cols = [RNG.randint(0, 6, 200).astype(np.int64) for _ in range(ncols)]
    got = ops.dedup_rows(cols)
    want = HOST.dedup_rows(cols)
    assert len(got) == len(want)
    assert sorted(zip(*(c[got] for c in cols))) == \
        sorted(zip(*(c[want] for c in cols)))
    # ascending indices, no duplicates selected twice
    assert (np.diff(got) > 0).all()


@pytest.mark.parametrize("name", BACKENDS[:2])  # numpy, jax
def test_empty_inputs(name):
    ops = get_backend(name)
    e = np.empty(0, np.int64)
    assert ops.sort_kv(e, e)[0].shape == (0,)
    assert ops.join_pairs(e, np.asarray([1], np.int64))[0].shape == (0,)
    assert ops.unique_mask(e).shape == (0,)
    assert ops.semi_join(e, e).shape == (0,)
    assert ops.dedup_rows([e]).shape == (0,)


# (the semi_join_rows empty-bound regression lives in tests/test_joins.py,
#  next to the function under test)


# ---------------------------------------------------------------------------
# Tagged-key stable sort: exact (not just set-wise) parity


@pytest.mark.parametrize("ops", device_backends())
def test_sort_perm_stable_exact(ops):
    """The tagged-key bitonic sort is stable: the permutation matches
    numpy's stable argsort bit-exactly, duplicates and all."""
    keys = RNG.randint(-30, 30, 700).astype(np.int64)  # many duplicates
    sk, perm = ops.sort_perm(keys)
    np.testing.assert_array_equal(perm, np.argsort(keys, kind="stable"))
    np.testing.assert_array_equal(sk, np.sort(keys, kind="stable"))


@pytest.mark.parametrize("ops", device_backends())
def test_sort_kv_stable_exact(ops):
    keys = RNG.randint(0, 10, 400).astype(np.int64)
    vals = np.arange(400, dtype=np.int64) * 7
    gk, gv = ops.sort_kv(keys, vals)
    wk, wv = HOST.sort_kv(keys, vals)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)  # stability -> exact payloads


@pytest.mark.parametrize("ops", device_backends())
@pytest.mark.parametrize("ncols", [1, 2, 4])
def test_dedup_rows_stable_exact(ops, ncols):
    """Multi-column dedup runs the chained tagged-key Pallas sorts — the
    surviving representative of each duplicate row is exactly the one
    numpy's stable lexsort keeps."""
    cols = [RNG.randint(-5, 6, 300).astype(np.int64) for _ in range(ncols)]
    np.testing.assert_array_equal(ops.dedup_rows(cols),
                                  HOST.dedup_rows(cols))


# ---------------------------------------------------------------------------
# Sentinel-collision host fallbacks and tagged-width overflow


INT64_MAX = np.iinfo(np.int64).max
INT64_MIN = np.iinfo(np.int64).min


@pytest.mark.parametrize("ops", device_backends())
def test_sentinel_collision_join(ops):
    # real keys equal to the pad sentinels must not fabricate or drop
    # pairs: MAX on the right collides with left pads, MIN on the left
    # with right pads -> exact host path
    l = np.asarray([5, INT64_MIN, 5, 9], np.int64)
    r = np.asarray([5, 9, INT64_MAX, INT64_MIN], np.int64)
    gli, gri = ops.join_pairs(l, r)
    assert pair_set(gli, gri) == pair_set(*HOST.join_pairs(l, r))


@pytest.mark.parametrize("ops", device_backends())
def test_sentinel_collision_semi_join(ops):
    keys = np.asarray([1, INT64_MAX, 3, INT64_MIN], np.int64)
    bound = np.asarray([INT64_MAX, 3], np.int64)
    np.testing.assert_array_equal(ops.semi_join(keys, bound),
                                  HOST.semi_join(keys, bound))


@pytest.mark.parametrize("ops", device_backends())
def test_sentinel_keys_sort(ops):
    # the tagged path re-tags pad lanes by position, so MAX/MIN are legal
    # *key values* for sorts — no host fallback needed, still stable
    keys = np.asarray([INT64_MAX, 0, INT64_MAX, INT64_MIN, 0], np.int64)
    vals = np.arange(5, dtype=np.int64)
    gk, gv = ops.sort_kv(keys, vals)
    wk, wv = HOST.sort_kv(keys, vals)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)


@pytest.mark.parametrize("ops", device_backends())
def test_tagged_width_overflow_fallback(ops):
    """Keys spanning (almost) the whole int64 range cannot be tagged —
    sort_perm/dedup_rows fall back to the XLA stable composite with the
    same exact-stability contract."""
    from repro.kernels.sortmerge.ops import fits_tagged_width
    keys = RNG.choice([INT64_MIN + 2, -7, 0, 7, INT64_MAX - 2],
                      200).astype(np.int64)
    assert not fits_tagged_width(int(keys.min()), int(keys.max()), 1024)
    sk, perm = ops.sort_perm(keys)
    np.testing.assert_array_equal(perm, np.argsort(keys, kind="stable"))
    np.testing.assert_array_equal(sk, np.sort(keys))
    cols = [keys, RNG.randint(0, 3, 200).astype(np.int64)]
    np.testing.assert_array_equal(ops.dedup_rows(cols),
                                  HOST.dedup_rows(cols))


@pytest.mark.parametrize("ops", device_backends())
def test_width_overflow_and_sentinel_dedup_host(ops):
    # width overflow AND a sentinel collision: genuinely adversarial keys
    # take the exact host path
    cols = [np.asarray([INT64_MAX, INT64_MIN, INT64_MAX, 0], np.int64),
            np.asarray([1, 2, 1, 2], np.int64)]
    np.testing.assert_array_equal(ops.dedup_rows(cols),
                                  HOST.dedup_rows(cols))


# ---------------------------------------------------------------------------
# Device residency: the transfer counter is the measurement, not vibes


def fresh_jax_ops():
    return JaxOps(mode="interpret", block=256)


def test_sort_perm_cache_zero_transfer_on_repeat():
    ops = fresh_jax_ops()
    col = RNG.randint(0, 1000, 2000).astype(np.int64)
    s1, p1 = ops.sort_perm(col, cache_key=("t", 1), version=1)
    snap = ops.transfers.snapshot()
    s2, p2 = ops.sort_perm(col, cache_key=("t", 1), version=1)
    d = ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(p1, p2)


def test_sort_perm_cache_delta_upload_on_append():
    ops = fresh_jax_ops()
    col = RNG.randint(0, 1000, 4000).astype(np.int64)
    ops.sort_perm(col, cache_key=("t", 2), version=1)
    delta = RNG.randint(0, 1000, 64).astype(np.int64)
    col2 = np.concatenate([col, delta])
    snap = ops.transfers.snapshot()
    _, perm = ops.sort_perm(col2, cache_key=("t", 2), version=2)
    d = ops.transfers.delta(snap)
    # only the appended tail's (bucketed) bytes went up, not the column
    assert 0 < d.h2d_bytes < col.nbytes // 4, d
    np.testing.assert_array_equal(perm, np.argsort(col2, kind="stable"))


def test_join_pairs_resident_right_side():
    ops = fresh_jax_ops()
    r = RNG.randint(0, 500, 3000).astype(np.int64)
    l = RNG.randint(0, 500, 40).astype(np.int64)
    ops.join_pairs(l, r, rkeys_key=("pk", 3), rkeys_version=1)
    snap = ops.transfers.snapshot()
    gli, gri = ops.join_pairs(l, r, rkeys_key=("pk", 3), rkeys_version=1)
    d = ops.transfers.delta(snap)
    # second probe re-uploads only the (small) left batch
    assert d.h2d_bytes < r.nbytes // 4, d
    assert pair_set(gli, gri) == pair_set(*HOST.join_pairs(l, r))


def test_engine_device_resident_index_state():
    """Acceptance: an infer()+query() cycle on backend=jax-interpret keeps
    index state device-resident — a second (fixpoint) infer and repeated
    index lookups issue zero transfers."""
    from repro.core.store import Component

    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend="jax-interpret"))
    rule = Rule("trans", (cond("T", "?x", "next", "?y"),
                          cond("T", "?y", "next", "?z")),
                (AddAction("T", term("?x"), "next", term("?z")),))
    e.add_rule(rule)
    e.insert_facts([Fact("T", f"n{i}", "next", f"n{i+1}") for i in range(6)])
    stats = e.infer()
    assert stats.facts_inferred > 0

    snap = e.ops.transfers.snapshot()
    e.infer()  # already at fixpoint: rules skipped-unchanged
    d = e.ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d

    t = e.store.tables["T"]
    snap = e.ops.transfers.snapshot()
    for v in range(32):  # rank-1 lookups run on the cached host mirrors
        t.index.lookup(t, Component.ID, v)
        t.index.count(t, Component.VAL, v)
    d = e.ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d


def test_engine_append_uploads_delta_not_table():
    """Repeated infer iterations extend the resident packed-key buffer
    instead of re-uploading the whole table each write."""
    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend="jax-interpret"))
    e.insert_facts([Fact("T", f"n{i}", "next", f"n{i+1}")
                    for i in range(2000)])
    t = e.store.tables["T"]
    key = ("colbuf", ("pk", t.uid), np.iinfo(np.int64).min)
    # first write-side dedup uploads the packed keys...
    e.insert_facts([Fact("T", "a0", "next", "b0")])
    assert e.ops.cache.get_any(key) is not None
    snap = e.ops.transfers.snapshot()
    # ...subsequent small batches extend it with tail-bucket uploads only
    for i in range(5):
        e.insert_facts([Fact("T", f"a{i+1}", "next", f"b{i+1}")])
    d = e.ops.transfers.delta(snap)
    full = t.n * 8 * 5
    assert d.h2d_bytes < full // 4, (d, full)


# ---------------------------------------------------------------------------
# End-to-end engine parity over the Table-1 config grid


def kg_facts():
    return [
        Fact("Schema", "A", "subClassOf", "B"),
        Fact("Schema", "B", "subClassOf", "C"),
        Fact("Schema", "C", "subClassOf", "D"),
        Fact("Schema", "knows", "characteristic", "symmetric"),
        Fact("Schema", "partOf", "characteristic", "transitive"),
        Fact("Data", "x", "type", "A"),
        Fact("Data", "y", "type", "B"),
        Fact("Data", "x", "knows", "y"),
        Fact("Data", "p1", "partOf", "p2"),
        Fact("Data", "p2", "partOf", "p3"),
        Fact("Data", "p3", "partOf", "p4"),
    ]


QUERIES = [
    [cond("Data", "?x", "type", "D")],
    [cond("Data", "?a", "partOf", "?b")],
    [cond("Data", "?x", "type", "?t"), cond("Data", "?x", "knows", "?y")],
]


def query_sets(engine):
    return [{tuple(sorted(r.items())) for r in engine.query(q)}
            for q in QUERIES]


def run_engine(cfg):
    e = HiperfactEngine(cfg)
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(kg_facts())
    stats = e.infer()
    return e, stats


GRID = [(j, u, la) for j in ("MJ", "HJ") for u in ("SU", "HU")
        for la in ("CR", "RR")]


@pytest.mark.parametrize("join,unique,layout", GRID,
                         ids=lambda v: v if isinstance(v, str) else str(v))
def test_engine_backend_parity_grid(join, unique, layout):
    base = EngineConfig(index_backend="AI", join=join, unique=unique,
                        layout=layout)
    e_np, s_np = run_engine(dataclasses.replace(base, backend="numpy"))
    e_jx, s_jx = run_engine(dataclasses.replace(base, backend="jax"))
    assert s_jx.facts_inferred == s_np.facts_inferred
    assert e_jx.store.num_facts() == e_np.store.num_facts()
    assert query_sets(e_jx) == query_sets(e_np)


@pytest.mark.parametrize("preset", ["infer1", "query1"])
def test_engine_backend_parity_presets(preset):
    make = getattr(EngineConfig, preset)
    e_np, s_np = run_engine(make(backend="numpy"))
    e_jx, s_jx = run_engine(make(backend="jax"))
    assert s_jx.facts_inferred == s_np.facts_inferred
    assert query_sets(e_jx) == query_sets(e_np)
    assert make(backend="jax").label().endswith("@jax")


# ---------------------------------------------------------------------------
# Handle tier: device-resident intermediates bit-match the numpy host twins


@pytest.mark.parametrize("ops", device_backends())
@pytest.mark.parametrize("algo", ["MJ", "HJ"])
def test_handle_join_gather_parity(ops, algo):
    l = RNG.randint(0, 25, 260).astype(np.int64) * (1 << 33)
    r = RNG.randint(0, 25, 140).astype(np.int64) * (1 << 33)
    lv = RNG.randint(0, 4, 260).astype(np.int64)
    rv = RNG.randint(0, 4, 140).astype(np.int64)
    # build operands per backend, run the fused join, compare row sets
    out = {}
    for o in (ops, HOST):
        hk, hr = o.upload(l), o.upload(r)
        hlv, hrv = o.upload(lv), o.upload(rv)
        lout, rout, n = o.join_gather_h(hk, hr, [hk, hlv], [hrv],
                                        [(hlv, hrv)], algo)
        out[o.name] = (n, sorted(zip(lout[0].host().tolist(),
                                     lout[1].host().tolist(),
                                     rout[0].host().tolist())))
    (n1, rows1), (n2, rows2) = out.values()
    assert n1 == n2 and rows1 == rows2
    # oracle: pair join + verify + gather by hand
    li, ri = HOST.join(l, r, algo)
    ok = lv[li] == rv[ri]
    assert n1 == int(ok.sum())


@pytest.mark.parametrize("ops", device_backends())
@pytest.mark.parametrize("algo", ["MJ", "HJ"])
def test_handle_join_gather_empty(ops, algo):
    e = np.empty(0, np.int64)
    some = np.asarray([1, 2, 3], np.int64)
    for l, r in ((e, some), (some, e), (e, e)):
        lk, rk = ops.upload(l), ops.upload(r)
        lout, rout, n = ops.join_gather_h(lk, rk, [lk], [rk], [], algo)
        assert n == 0
        assert lout[0].host().shape == (0,)
        assert rout[0].host().shape == (0,)


@pytest.mark.parametrize("ops", device_backends())
def test_handle_join_gather_sentinel(ops):
    # real keys equal to the pad sentinels: right MAX is harmless (left
    # pad counts are zeroed in-program), left MIN takes the exact host
    # fallback via the handle bounds guard — either way, parity
    l = np.asarray([5, INT64_MIN, 5, 9], np.int64)
    r = np.asarray([5, 9, INT64_MAX, INT64_MIN], np.int64)
    for a, b in ((l, r), (r, l), (l[:3], r)):
        for o in (ops,):
            lk, rk = o.upload(a), o.upload(b)
            lout, rout, n = o.join_gather_h(lk, rk, [lk], [rk], [], "MJ")
            li, ri = HOST.join_pairs(a, b)
            assert n == len(li)
            assert sorted(zip(lout[0].host().tolist(),
                              rout[0].host().tolist())) == \
                sorted(zip(a[li].tolist(), b[ri].tolist()))


@pytest.mark.parametrize("ops", device_backends())
def test_handle_dedup_select_parity(ops):
    cols = [RNG.randint(0, 6, 300).astype(np.int64) for _ in range(3)]
    hs = [ops.upload(c) for c in cols]
    idx, n = ops.dedup_select_h(hs)
    want = HOST.dedup_rows(cols)
    assert n == len(want)
    np.testing.assert_array_equal(idx.host(), want)
    # gather through the kept index reproduces the distinct rows
    g = ops.gather_h(hs[0], idx, n)
    np.testing.assert_array_equal(g.host(), cols[0][want])


@pytest.mark.parametrize("ops", device_backends())
def test_handle_dedup_select_width_overflow(ops):
    # key span too wide to tag -> flag-based XLA path, same representative
    cols = [RNG.choice([INT64_MIN + 2, -7, 0, 7, INT64_MAX - 2],
                       200).astype(np.int64),
            RNG.randint(0, 3, 200).astype(np.int64)]
    idx, n = ops.dedup_select_h([ops.upload(c) for c in cols])
    want = HOST.dedup_rows(cols)
    assert n == len(want)
    np.testing.assert_array_equal(idx.host(), want)


@pytest.mark.parametrize("ops", device_backends())
def test_handle_semi_join_select_parity(ops):
    keys = np.asarray([1, INT64_MAX, 3, INT64_MIN] +
                      RNG.randint(-15, 15, 120).tolist(), np.int64)
    bound = np.asarray([INT64_MAX, 3, -2], np.int64)
    kh, bh = ops.upload(keys), ops.upload(bound)
    mask = ops.semi_join_h(kh, bh)
    (sel,), kept = ops.select_mask_h([kh], mask)
    want = keys[HOST.semi_join(keys, bound)]
    assert kept == len(want)
    np.testing.assert_array_equal(sel.host(), want)
    # empty bound -> nothing selected
    m0 = ops.semi_join_h(kh, ops.upload(np.empty(0, np.int64)))
    _, k0 = ops.select_mask_h([kh], m0)
    assert k0 == 0


@pytest.mark.parametrize("ops", device_backends())
def test_handle_fresh_mask_parity(ops):
    old_k = RNG.randint(0, 40, 400).astype(np.int64)
    old_v = RNG.randint(0, 3, 400).astype(np.int64)
    new_k = RNG.randint(0, 50, 90).astype(np.int64)
    new_v = RNG.randint(0, 3, 90).astype(np.int64)
    got = ops.fresh_mask_h(ops.upload(new_k), ops.upload(new_v),
                           old_k, old_v, cache_uid=("t", 1), version=3)
    want = HOST.fresh_mask_h(HOST.upload(new_k), HOST.upload(new_v),
                             old_k, old_v)
    np.testing.assert_array_equal(got.host(), want.host())


@pytest.mark.parametrize("ops", device_backends())
def test_handle_concat_pack_const(ops):
    a = RNG.randint(0, 99, 70).astype(np.int64)
    b = RNG.randint(0, 99, 30).astype(np.int64)
    cat = ops.concat_h([ops.upload(a), ops.upload(np.empty(0, np.int64)),
                        ops.upload(b)])
    np.testing.assert_array_equal(cat.host(), np.concatenate([a, b]))
    ids = RNG.randint(0, 1000, 50).astype(np.int64)
    attrs = RNG.randint(0, 7, 50).astype(np.int64)
    p = ops.pack_pairs_h(ops.upload(ids), ops.upload(attrs))
    np.testing.assert_array_equal(p.host(), (ids << 32) | attrs)
    c = ops.const_h(42, 17)
    np.testing.assert_array_equal(c.host(), np.full(17, 42, np.int64))
    np.testing.assert_array_equal(ops.iota_h(9).host(), np.arange(9))


def test_handle_memo_repeat_is_free():
    """Repeating a handle-tier op with the same operand handles is a
    uid-keyed memo hit: same output handles, zero transfers."""
    ops = fresh_jax_ops()
    l = RNG.randint(0, 30, 400).astype(np.int64)
    r = RNG.randint(0, 30, 200).astype(np.int64)
    lk, rk = ops.upload(l), ops.upload(r)
    lout, _, n = ops.join_gather_h(lk, rk, [lk], [rk], [], "MJ")
    _ = lout[0].host()  # materialization is cached on the handle
    snap = ops.transfers.snapshot()
    lout2, _, n2 = ops.join_gather_h(lk, rk, [lk], [rk], [], "MJ")
    assert lout2[0] is lout[0] and n2 == n
    _ = lout2[0].host()
    d = ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d


# ---------------------------------------------------------------------------
# Write-side row lookup: the last table row holding each (key, val)


def last_equal_rows(old_k, old_v, new_k, new_v):
    """Brute-force oracle of ``match_rows``."""
    want = np.full(len(new_k), -1, np.int64)
    for i, (k, v) in enumerate(zip(new_k.tolist(), new_v.tolist())):
        hit = np.flatnonzero((old_k == k) & (old_v == v))
        if len(hit):
            want[i] = hit[-1]
    return want


MATCH_UIDS = itertools.count()


def check_match_rows(ops, old_k, old_v, new_k, new_v, uid=None,
                     version=1):
    # a (uid, version) names one table state for the whole process
    uid = ("match", next(MATCH_UIDS)) if uid is None else uid
    got = ops.match_rows(new_k, new_v, old_k, old_v, cache_uid=uid,
                         version=version)
    want = HOST.match_rows(new_k, new_v, old_k, old_v)
    assert got.dtype == want.dtype == np.int64
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(want, last_equal_rows(old_k, old_v,
                                                        new_k, new_v))
    return got


def tc_table(n_vals=249):
    """One (id, attr) key with ``n_vals`` values, as every ``path(x, to,
    ?)`` fact of a tc closure, beside a few other keys."""
    k = np.concatenate([np.full(n_vals, 5 << 32 | 3, np.int64),
                        RNG.randint(0, 9, 60).astype(np.int64) << 32])
    v = np.concatenate([RNG.permutation(n_vals).astype(np.int64),
                        RNG.randint(0, 40, 60).astype(np.int64)])
    order = RNG.permutation(len(k))
    return k[order], v[order]


def match_tc_runs(ops):
    old_k, old_v = tc_table()
    new_v = np.arange(0, 2 * 249, 2, dtype=np.int64)  # half present
    new_k = np.full(len(new_v), 5 << 32 | 3, np.int64)
    got = check_match_rows(ops, old_k, old_v, new_k, new_v)
    assert (got >= 0).sum() == 125


def match_random(ops):
    old_k = RNG.randint(-30, 30, 700).astype(np.int64) * (1 << 33)
    old_v = RNG.randint(0, 12, 700).astype(np.int64)
    new_k = RNG.randint(-35, 35, 300).astype(np.int64) * (1 << 33)
    new_v = RNG.randint(0, 14, 300).astype(np.int64)
    check_match_rows(ops, old_k, old_v, new_k, new_v)


def match_empty_table(ops):
    e = np.empty(0, np.int64)
    got = check_match_rows(ops, e, e, np.arange(9, dtype=np.int64),
                           np.zeros(9, np.int64))
    assert (got == -1).all()


def match_empty_probe(ops):
    old_k, old_v = tc_table()
    e = np.empty(0, np.int64)
    assert len(check_match_rows(ops, old_k, old_v, e, e)) == 0


def match_int64_extremes(ops):
    old_k = np.asarray([INT64_MIN, INT64_MIN, 0, INT64_MAX, INT64_MAX, 7],
                       np.int64)
    old_v = np.asarray([INT64_MAX, 0, INT64_MAX, INT64_MAX, INT64_MIN,
                        INT64_MAX], np.int64)
    new_k = np.asarray([INT64_MIN, INT64_MIN, INT64_MIN, 0, INT64_MAX,
                        INT64_MAX, 7, 7], np.int64)
    new_v = np.asarray([INT64_MAX, 0, 1, INT64_MAX, INT64_MAX, INT64_MIN,
                        INT64_MIN, INT64_MAX], np.int64)
    got = check_match_rows(ops, old_k, old_v, new_k, new_v)
    np.testing.assert_array_equal(got, [0, 1, -1, 2, 3, 4, -1, 5])


def match_duplicate_rows(ops):
    # (key, val) pairs written several times: the answer is the last row
    old_k = np.asarray([1, 2, 1, 1, 2, 3, 1], np.int64) << 32
    old_v = np.asarray([9, 4, 9, 8, 4, 0, 9], np.int64)
    new_k = np.asarray([1, 2, 1, 3, 3], np.int64) << 32
    new_v = np.asarray([9, 4, 8, 0, 1], np.int64)
    got = check_match_rows(ops, old_k, old_v, new_k, new_v)
    np.testing.assert_array_equal(got, [6, 4, 3, 5, -1])


def match_coded_pk_entry(ops):
    """``join_pairs`` dict-codes the shared ``("pk", uid)`` column under
    compression; the lookup decodes it (hit, then append-extend)."""
    ops = JaxOps(mode=ops.mode, block=256, compress=True)
    hub = np.full(60, 1 << 32, np.int64)
    old_k = np.concatenate([hub, np.arange(2, 62, dtype=np.int64) << 32])
    old_v = np.arange(120, dtype=np.int64) % 50
    ops.join_pairs(old_k[:10], old_k, rkeys_key=("pk", 3), rkeys_version=1)
    entry = ops.cache.get_any(("colbuf", ("pk", 3), INT64_MIN))
    assert entry.value["codec"] is not None
    new_k = np.concatenate([old_k[::3], [9 << 32]])
    new_v = np.concatenate([old_v[::3], [0]])
    check_match_rows(ops, old_k, old_v, new_k, new_v, uid=3, version=1)
    more_k = np.concatenate([old_k, old_k[:7]])  # appended duplicates
    more_v = np.concatenate([old_v, old_v[:7]])
    got = check_match_rows(ops, more_k, more_v, new_k, new_v, uid=3,
                           version=2)
    assert got[0] == 120
    assert ops.cache.get_any(
        ("colbuf", ("pk", 3), INT64_MIN)).value["codec"] is not None


def match_cache_hit_then_rebuild(ops):
    """The table's columns stay resident: a repeat at the same version
    uploads only the probes, and an append uploads only the tail."""
    ops = JaxOps(mode=ops.mode, block=256)
    old_k, old_v = tc_table(1000)
    new_k, new_v = old_k[::20].copy(), old_v[::20].copy()
    check_match_rows(ops, old_k, old_v, new_k, new_v, uid=4, version=1)
    hits = ops.cache.hits
    snap = ops.transfers.snapshot()
    check_match_rows(ops, old_k, old_v, new_k, new_v, uid=4, version=1)
    d = ops.transfers.delta(snap)
    assert d.h2d_calls == 2 and d.d2h_calls == 1, d
    assert d.h2d_bytes < old_k.nbytes, d
    assert ops.cache.hits == hits + 2  # the key and value columns
    more_k = np.concatenate([old_k, new_k[:4]])
    more_v = np.concatenate([old_v, new_v[:4]])
    snap = ops.transfers.snapshot()
    got = check_match_rows(ops, more_k, more_v, new_k, new_v, uid=4,
                           version=2)
    d = ops.transfers.delta(snap)
    np.testing.assert_array_equal(got[:4], len(old_k) + np.arange(4))
    assert d.h2d_bytes < old_k.nbytes, d


def match_shares_fresh_mask_mirror(ops):
    """``fresh_mask_h`` keeps its sorted mirror under ``("pkv", uid)``,
    and ``match_rows`` at the same version searches it without sorting;
    ``match_rows`` keeps none of its own.  The mask stays the host's."""
    old_k = RNG.randint(0, 40, 400).astype(np.int64)
    old_v = RNG.randint(0, 3, 400).astype(np.int64)
    new_k = RNG.randint(0, 50, 90).astype(np.int64)
    new_v = RNG.randint(0, 3, 90).astype(np.int64)
    want = HOST.fresh_mask_h(HOST.upload(new_k), HOST.upload(new_v),
                             old_k, old_v).host()
    for first in ("fresh", "match"):
        ops = JaxOps(mode=ops.mode, block=256)
        if first == "match":
            rows = check_match_rows(ops, old_k, old_v, new_k, new_v,
                                    uid=5, version=1)
            assert ops.cache.get_any(("pkv", 5)) is None
        got = ops.fresh_mask_h(ops.upload(new_k), ops.upload(new_v),
                               old_k, old_v, cache_uid=5, version=1)
        np.testing.assert_array_equal(got.host(), want)
        mirror = ops.cache.get_any(("pkv", 5)).value
        if first == "fresh":
            sorts = ops.route_stats()["xla"]
            rows = check_match_rows(ops, old_k, old_v, new_k, new_v,
                                    uid=5, version=1)
            assert ops.route_stats()["xla"] == sorts
            assert ops.cache.get_any(("pkv", 5)).value is mirror
        np.testing.assert_array_equal(rows < 0, want)


MATCH_CASES = {f.__name__[len("match_"):].replace("_", "-"): f for f in (
    match_tc_runs, match_random, match_empty_table, match_empty_probe,
    match_int64_extremes, match_duplicate_rows, match_coded_pk_entry,
    match_cache_hit_then_rebuild, match_shares_fresh_mask_mirror)}


@pytest.mark.parametrize("case", list(MATCH_CASES))
@pytest.mark.parametrize("ops", device_backends())
def test_match_rows_parity(ops, case):
    MATCH_CASES[case](ops)


# ---------------------------------------------------------------------------
# Batched rank-1 probes


@pytest.mark.parametrize("ops", device_backends())
def test_batch_probe_parity(ops):
    s = np.sort(RNG.randint(0, 200, 1000).astype(np.int64))
    probes = RNG.randint(-10, 220, 128).astype(np.int64)
    lo, hi = ops.batch_probe(s, probes, cache_key=("bp", 1), version=1)
    wlo, whi = HOST.batch_probe(s, probes)
    np.testing.assert_array_equal(lo, wlo)
    np.testing.assert_array_equal(hi, whi)


def test_batch_probe_resident_mirror():
    """Repeated batched probes at a fixed version upload only the probe
    batch (one transfer up, one down) — never the sorted mirror."""
    ops = fresh_jax_ops()
    s = np.sort(RNG.randint(0, 500, 4000).astype(np.int64))
    probes = RNG.randint(0, 500, 64).astype(np.int64)
    ops.batch_probe(s, probes, cache_key=("bp", 2), version=1)
    snap = ops.transfers.snapshot()
    ops.batch_probe(s, probes, cache_key=("bp", 2), version=1)
    d = ops.transfers.delta(snap)
    assert d.h2d_calls == 1 and d.d2h_calls == 1, d
    assert d.h2d_bytes < s.nbytes // 4, d


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
def test_store_lookup_many(backend):
    from repro.core.store import Component

    e = HiperfactEngine(EngineConfig(index_backend="AI", backend=backend))
    e.insert_facts([Fact("T", f"n{i % 7}", "attr", f"v{i}")
                    for i in range(40)])
    t = e.store.tables["T"]
    values = np.concatenate([t.ids[:10].astype(np.int64),
                             np.asarray([10**6], np.int64)])
    rows, offs = e.store.lookup_many("T", Component.ID, values)
    assert len(offs) == len(values) + 1
    for i, v in enumerate(values):
        got = sorted(rows[offs[i]:offs[i + 1]].tolist())
        want = sorted(t.index.lookup(t, Component.ID, int(v)).tolist())
        assert got == want
    # after a delete, tombstoned rows drop out and offsets stay aligned
    e._delete_matching("T", t.ids[:1], t.attrs[:1], t.vals[:1])
    rows2, offs2 = e.store.lookup_many("T", Component.ID, values)
    assert t.alive[rows2].all()
    assert len(offs2) == len(values) + 1


# ---------------------------------------------------------------------------
# Acceptance: zero transfers inside the join core of a fixed-version
# multi-condition island fixpoint


def island_rule():
    return Rule("r3", (cond("T", "?x", "type", "?t"),
                       cond("T", "?x", "knows", "?y"),
                       cond("T", "?y", "type", "?u")),
                (AddAction("T", term("?x"), "sees", term("?u")),))


def island_facts():
    facts = [Fact("T", f"n{i}", "type", f"c{i % 3}") for i in range(12)]
    facts += [Fact("T", f"n{i}", "knows", f"n{(i + 1) % 12}")
              for i in range(12)]
    return facts


def test_island_fixpoint_zero_transfers_join_core():
    """A 3-condition island chain re-evaluated at a fixed table version:
    lookups hit the cached binding handles, the fused joins / AR
    semi-joins / dedup hit the uid-keyed memos — zero host<->device
    transfers inside the join core."""
    from repro.core.islands import evaluate_rule

    # eval_mode="full": this asserts the fixed-version memo property of
    # the full-evaluation chain (the semi-naive delta rounds leave
    # different — smaller — memo chains behind; tests/test_delta.py
    # holds the delta-mode transfer assertions)
    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend="jax-interpret",
                                     eval_mode="full"))
    rule = island_rule()
    e.add_rule(rule)
    e.insert_facts(island_facts())
    stats = e.infer()
    assert stats.facts_inferred > 0
    snap = e.ops.transfers.snapshot()
    b = evaluate_rule(e.store, rule, join_algo="MJ", rnl_mode="AR",
                      layout="CR", distinct=True, ops=e.ops, pipeline=True)
    d = e.ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d
    # ... and the result matches the host backend bit-for-bit
    e_np = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                        unique="SU", backend="numpy"))
    e_np.add_rule(rule)
    e_np.insert_facts(island_facts())
    e_np.infer()
    b_np = evaluate_rule(e_np.store, rule, join_algo="MJ", rnl_mode="AR",
                         layout="CR", distinct=True, ops=e_np.ops)
    assert b.n == b_np.n
    rows = sorted(zip(*(b.col(k).tolist() for k in sorted(b.names()))))
    rows_np = sorted(zip(*(b_np.col(k).tolist()
                           for k in sorted(b_np.names()))))
    assert rows == rows_np


def test_island_fixpoint_zero_transfers_full_sweep():
    """Stronger form: force a full rule re-evaluation sweep (joins +
    actions + write-side dedup/anti-join) at fixed versions — still zero
    transfers end to end."""
    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend="jax-interpret",
                                     eval_mode="full"))
    e.add_rule(island_rule())
    e.insert_facts(island_facts())
    e.infer()
    snap = e.ops.transfers.snapshot()
    e._rule_seen_versions.clear()  # forces re-evaluation of every rule
    s2 = e.infer()
    d = e.ops.transfers.delta(snap)
    assert s2.facts_inferred == 0
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d


def test_pipeline_off_matches_pipeline_on():
    """The per-primitive path (device_pipeline=off) and the fused handle
    pipeline produce identical engine results."""
    results = {}
    for mode in ("on", "off"):
        e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                         unique="SU", backend="jax",
                                         device_pipeline=mode))
        e.add_rules(rdfs_plus_rules())
        e.insert_facts(kg_facts())
        s = e.infer()
        results[mode] = (s.facts_inferred, query_sets(e))
    assert results["on"] == results["off"]


def test_forced_pipeline_mixed_compute_actions():
    """device_pipeline="on" forced onto the host backend, with one plain
    and one computed action on the same fact type: handle and ndarray
    columns meet in the write-side concat (regression: base concat_h must
    normalize mixed parts)."""
    from repro.core.facts import ValueType

    for backend in ("numpy", "jax-interpret"):
        e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                         unique="SU", backend=backend,
                                         device_pipeline="on"))
        rule = Rule("mix", (cond("T", "?x", "v", "?a",
                                 valtype=ValueType.INT64),),
                    (AddAction("T", term("?x"), "plain", term("?a"),
                               ValueType.INT64),
                     AddAction("T", term("?x"), "twice", None,
                               ValueType.INT64,
                               compute=lambda b: b["a"] * 2)))
        e.add_rule(rule)
        e.insert_facts([Fact("T", f"n{i}", "v", i, ValueType.INT64)
                        for i in range(5)])
        stats = e.infer()
        assert stats.facts_inferred == 10
        got = {(r["x"], r["b"]) for r in
               e.query([cond("T", "?x", "twice", "?b",
                             valtype=ValueType.INT64)])}
        assert got == {(f"n{i}", 2 * i) for i in range(5)}


def test_device_cache_refresh_spill():
    from repro.backend.device_cache import DeviceArrayCache

    c = DeviceArrayCache(1 << 20)
    c.put("a", 1, "A", 100)
    c.put("b", 1, "B", 100)
    r = c.refresh()  # both touched this generation -> kept
    assert r["spilled"] == 0 and r["kept"] == 2
    assert c.get("a", 1) == "A"  # touch a, not b
    r = c.refresh()
    r = c.refresh()  # b now idle for 2 cycles > max_idle=1 -> spilled
    assert c.get("b", 1) is None
    assert c.stats()["spilled"] >= 1
    # spill hook pins everything regardless of idleness
    c.put("c", 1, "C", 100)
    c.spill_hook = lambda key, e: True
    for _ in range(4):
        c.refresh()
    assert c.get("c", 1) == "C"
    assert 0.0 <= c.stats()["hit_rate"] <= 1.0


def test_engine_interpret_mode_smoke():
    """One tiny fixpoint through the Pallas kernels under the interpreter:
    the full kernel code path runs on CPU, end to end."""
    facts = [Fact("T", "a", "next", "b"), Fact("T", "b", "next", "c"),
             Fact("T", "c", "next", "d")]
    rule = Rule("trans", (cond("T", "?x", "next", "?y"),
                          cond("T", "?y", "next", "?z")),
                (AddAction("T", term("?x"), "next", term("?z")),))
    results = {}
    for backend in ("numpy", "jax-interpret"):
        e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                         unique="SU", backend=backend))
        e.add_rule(rule)
        e.insert_facts(facts)
        e.infer()
        results[backend] = {tuple(sorted(r.items())) for r in
                            e.query([cond("T", "?x", "next", "?y")])}
    assert results["numpy"] == results["jax-interpret"]
    assert len(results["numpy"]) == 6  # transitive closure of a 4-chain
