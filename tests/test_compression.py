"""Compressed device-resident columns: code-domain parity vs the
uncompressed host oracle (PR 8).

Three layers under test:

* ``backend/codecs.py`` — codec choice + roundtrips on host;
* ``JaxOps`` with ``compress=True`` — coded resident columns feeding
  sorts, joins, probes, and write-side dedup, bit-identical to numpy;
* the engine config matrix (MJ/HJ x SU/HU x numpy/jax-interpret) with
  compression on — decoded results identical to the uncompressed
  baseline;
* ``FrontierExchange`` lane narrowing — sharded transport stays exact.
"""

import numpy as np
import pytest

from repro.backend import codecs
from repro.backend.jax_ops import JaxOps
from repro.backend.numpy_ops import NumpyOps
from repro.core import EngineConfig, Fact, HiperfactEngine
from repro.core.rulesets import rdfs_plus_rules

RNG = np.random.RandomState(8)
HOST = NumpyOps()
INT64_MAX = np.iinfo(np.int64).max
INT64_MIN = np.iinfo(np.int64).min


def fresh_ops(compress=True):
    return JaxOps(mode="interpret", block=256, compress=compress)


# -- columns that force each codec kind -------------------------------------

def dict_col(n=600):
    """Low cardinality, wide span -> dict codec."""
    vals = np.array([7, 10**12, 3 * 10**12, 9 * 10**14], np.int64)
    return vals[RNG.randint(0, len(vals), n)]


def for_col(n=600):
    """Dense range far from zero -> frame-of-reference codec."""
    return (10**10 + RNG.randint(0, 200, n)).astype(np.int64)


def rle_col(n=600):
    """Run-heavy (grouped join output shape) -> RLE codec."""
    return np.repeat(np.arange(n // 50, dtype=np.int64) * 10**9, 50)[:n]


# -- codec unit layer --------------------------------------------------------

def test_choose_codec_kinds():
    assert codecs.choose_codec(dict_col())[0].kind == "dict"
    assert codecs.choose_codec(for_col())[0].kind == "for"
    assert codecs.choose_codec(rle_col(), allow_rle=True)[0].kind == "rle"
    wide = RNG.randint(-2**60, 2**60, 600).astype(np.int64)
    assert codecs.choose_codec(wide) == (None, None)  # raw wins


@pytest.mark.parametrize("col_fn", [dict_col, for_col, rle_col])
def test_codec_roundtrip(col_fn):
    col = col_fn()
    c, payload = codecs.choose_codec(col, allow_rle=True)
    np.testing.assert_array_equal(codecs.decode(c, payload), col)
    # rle capacity is counted in runs, flat codecs in rows
    cap = c.nruns if c.kind == "rle" else len(col)
    assert c.coded_nbytes(cap) < col.nbytes


def test_encode_probes_out_of_domain():
    col = dict_col()
    c, _ = codecs.choose_codec(col)
    probes = np.array([7, 55, 10**12, -3], np.int64)  # 55, -3 absent
    enc = codecs.encode_probes(c, probes)
    assert enc[1] == c.no_match_code and enc[3] == c.no_match_code
    assert enc[0] != enc[2] and enc[0] != c.no_match_code


# -- JaxOps resident layer ---------------------------------------------------

@pytest.mark.parametrize("col_fn", [dict_col, for_col, rle_col])
def test_upload_resident_coded_roundtrip(col_fn):
    ops = fresh_ops()
    col = col_fn()
    h = ops.upload_resident(("rt", col_fn.__name__), 1, col)
    np.testing.assert_array_equal(np.asarray(h.data)[:h.n], col)
    st = ops.residency_stats()
    assert st["compress"] and st["resident_bytes_coded"] > 0
    assert st["resident_bytes_coded"] < st["resident_bytes_raw"]


@pytest.mark.parametrize("col_fn", [dict_col, for_col, rle_col])
def test_sort_perm_coded_parity(col_fn):
    ops = fresh_ops()
    col = col_fn()
    sk, perm = ops.sort_perm(col, cache_key=("sp", col_fn.__name__),
                             version=1)
    np.testing.assert_array_equal(perm, np.argsort(col, kind="stable"))
    np.testing.assert_array_equal(sk, np.sort(col))


def test_zero_transfer_repeat_with_compression():
    """Fixed-version sweep: cached coded state costs zero transfers."""
    ops = fresh_ops()
    col = dict_col(2000)
    s1, p1 = ops.sort_perm(col, cache_key=("zt", 1), version=1)
    snap = ops.transfers.snapshot()
    s2, p2 = ops.sort_perm(col, cache_key=("zt", 1), version=1)
    d = ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(p1, p2)
    assert ops.residency_stats()["codecs"]["dict"] >= 1


def test_dict_append_extension_keeps_cid():
    """In-order fresh values extend the dictionary without a rebuild."""
    ops = fresh_ops()
    vals = np.array([10**12, 3 * 10**12], np.int64)
    col = vals[RNG.randint(0, 2, 400)]
    ops.sort_perm(col, cache_key=("dx", 1), version=1)
    col2 = np.concatenate([col, np.full(40, 9 * 10**14, np.int64)])
    _, perm = ops.sort_perm(col2, cache_key=("dx", 1), version=2)
    np.testing.assert_array_equal(perm, np.argsort(col2, kind="stable"))
    st = ops.residency_stats()["codecs"]
    assert st["dict_extends"] >= 1 and st["recode_rebuilds"] == 0


def test_dict_overflow_recode_rebuild():
    """Fresh values below the dictionary max break append-only order:
    the column recodes from scratch (counted) and stays correct."""
    ops = fresh_ops()
    vals = np.array([10**12, 3 * 10**12], np.int64)
    col = vals[RNG.randint(0, 2, 400)]
    ops.sort_perm(col, cache_key=("ov", 1), version=1)
    col2 = np.concatenate([col, np.full(40, 5, np.int64)])  # < dict min
    _, perm = ops.sort_perm(col2, cache_key=("ov", 1), version=2)
    np.testing.assert_array_equal(perm, np.argsort(col2, kind="stable"))
    assert ops.residency_stats()["codecs"]["recode_rebuilds"] >= 1


def test_sentinel_keys_stay_correct():
    """Keys at the int64 extremes: low-cardinality columns still dict
    (the extremes live in the dictionary, codes stay narrow); wide
    high-cardinality columns fall back to raw.  Both sort bit-exactly."""
    ops = fresh_ops()
    col = np.array([5, INT64_MAX, 9, INT64_MIN, 5] * 20, np.int64)
    assert codecs.choose_codec(col)[0].kind == "dict"
    sk, perm = ops.sort_perm(col, cache_key=("sx", 1), version=1)
    np.testing.assert_array_equal(perm, np.argsort(col, kind="stable"))
    np.testing.assert_array_equal(sk, np.sort(col))
    # fully distinct + wide span: dict (8B/distinct) and FoR both lose
    wide = np.arange(300, dtype=np.int64) * (1 << 53)
    RNG.shuffle(wide)
    wide[0] = INT64_MAX
    wide[1] = INT64_MIN
    assert codecs.choose_codec(wide) == (None, None)
    sk2, perm2 = ops.sort_perm(wide, cache_key=("sx", 2), version=1)
    np.testing.assert_array_equal(perm2, np.argsort(wide, kind="stable"))
    np.testing.assert_array_equal(sk2, np.sort(wide))


def test_empty_and_tiny_columns_stay_raw():
    ops = fresh_ops()
    h = ops.upload_resident(("e", 1), 1, np.empty(0, np.int64))
    assert h.n == 0
    tiny = np.array([10**12, 3 * 10**12], np.int64)  # below min_n gate
    h2 = ops.upload_resident(("e", 2), 1, tiny)
    assert h2.codec is None
    np.testing.assert_array_equal(np.asarray(h2.data)[:2], tiny)


@pytest.mark.parametrize("algo", ["MJ", "HJ"])
def test_code_domain_join_shared_dict(algo):
    """Both sides resident with the same dictionary content: the join
    runs over narrow codes (counted) and matches the host oracle."""
    ops = fresh_ops()
    vals = np.array([7, 10**12, 3 * 10**12, 9 * 10**14], np.int64)
    l = vals[RNG.randint(0, 4, 300)]
    r = vals[RNG.randint(0, 4, 200)]
    lk = ops.upload_resident(("cj-l", algo), 1, l)
    rk = ops.upload_resident(("cj-r", algo), 1, r)
    lout, rout, n = ops.join_gather_h(lk, rk, [lk], [rk], [], algo)
    li, ri = HOST.join_pairs(l, r)
    assert n == len(li)
    assert sorted(zip(lout[0].host().tolist(), rout[0].host().tolist())) \
        == sorted(zip(l[li].tolist(), r[ri].tolist()))
    assert ops.residency_stats()["codecs"]["code_joins"] >= 1


@pytest.mark.parametrize("algo", ["MJ", "HJ"])
def test_cross_dict_recode_join(algo):
    """Different dictionaries: smaller side recodes on device (counted),
    never decodes to host."""
    ops = fresh_ops()
    lv = np.array([7, 10**12, 3 * 10**12], np.int64)
    rv = np.array([10**12, 9 * 10**14], np.int64)  # overlaps on 10**12
    l = lv[RNG.randint(0, 3, 300)]
    r = rv[RNG.randint(0, 2, 150)]
    lk = ops.upload_resident(("xd-l", algo), 1, l)
    rk = ops.upload_resident(("xd-r", algo), 1, r)
    lout, rout, n = ops.join_gather_h(lk, rk, [lk], [rk], [], algo)
    li, ri = HOST.join_pairs(l, r)
    assert n == len(li)
    assert sorted(zip(lout[0].host().tolist(), rout[0].host().tolist())) \
        == sorted(zip(l[li].tolist(), r[ri].tolist()))
    assert ops.residency_stats()["codecs"]["cross_recodes"] >= 1


def test_batch_probe_coded_counts():
    """Probe counts (what lookup_batch consumes) match raw searchsorted
    spans even when the resident sorted run is stored coded."""
    ops = fresh_ops()
    col = np.sort(for_col(2000))
    probes = np.concatenate([col[RNG.randint(0, 2000, 50)],
                             np.array([99, 10**10 + 10**6], np.int64)])
    lo, hi = ops.batch_probe(col, probes, cache_key=("bp", 1), version=1)
    rlo = np.searchsorted(col, probes, "left")
    rhi = np.searchsorted(col, probes, "right")
    np.testing.assert_array_equal(hi - lo, rhi - rlo)
    nz = (rhi - rlo) > 0
    np.testing.assert_array_equal(lo[nz], rlo[nz])


# -- engine config matrix ----------------------------------------------------

def _matrix_facts():
    facts = [
        Fact("Schema", "A", "subClassOf", "B"),
        Fact("Schema", "B", "subClassOf", "C"),
        Fact("Schema", "partOf", "characteristic", "transitive"),
        Fact("Schema", "knows", "characteristic", "symmetric"),
    ]
    for i in range(80):
        facts.append(Fact("Data", f"n{i}", "type", "A"))
        facts.append(Fact("Data", f"n{i}", "knows", f"n{(i + 1) % 80}"))
    for i in range(30):
        facts.append(Fact("Data", f"p{i}", "partOf", f"p{i + 1}"))
    return facts


def _run_engine(join, unique, backend, compress):
    e = HiperfactEngine(EngineConfig(
        index_backend="AI", join=join, rnl="AR", layout="CC",
        unique=unique, backend=backend, compress=compress))
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(_matrix_facts())
    e.infer()
    from repro.core.sharded import decoded_fact_checksum
    return e.store.num_facts(), decoded_fact_checksum(e)


BASELINE = None


def _baseline():
    global BASELINE
    if BASELINE is None:
        BASELINE = _run_engine("MJ", "SU", "numpy", False)
    return BASELINE


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
@pytest.mark.parametrize("unique", ["SU", "HU"])
@pytest.mark.parametrize("join", ["MJ", "HJ"])
def test_engine_matrix_compressed_parity(join, unique, backend):
    assert _run_engine(join, unique, backend, True) == _baseline()


def test_write_side_dedup_with_coded_pk_column():
    """Regression: ``join_pairs`` dict-codes the shared ``("pk", uid)``
    resident column (a retraction join builds it cold); ``fresh_mask_h``
    then hit (or append-extended) that entry and read the narrow *codes*
    as raw packed keys, so the write-side anti-join reported existing
    (key, val) pairs as fresh — duplicate rows under compress=True."""
    import dataclasses
    from collections import Counter
    from repro.core import Rule
    from repro.core.conditions import AddAction, cond, term
    from repro.core.sharded import decoded_fact_checksum

    rules = [
        # re-derives every existing fact: all must be dedup-filtered
        Rule("echo", (cond("Data", "?x", "link", "?y"),),
             (AddAction("Data", term("?x"), "link", term("?y")),)),
        Rule("rec", (cond("Data", "?x", "link", "?y"),
                     cond("Data", "?y", "link", "?z")),
             (AddAction("Data", term("?x"), "link", term("?z")),)),
    ]
    # hub fan-out: one packed (id, attr) key repeated 60x -> dict codec
    batch1 = [Fact("Data", "hub", "link", f"s{i}") for i in range(60)]
    batch2 = [Fact("Data", f"s{i}", "link", f"t{i}") for i in range(60)]

    def run(backend, compress):
        cfg = dataclasses.replace(EngineConfig.infer1(backend),
                                  compress=compress)
        e = HiperfactEngine(cfg)
        e.add_rules(rules)
        e.insert_facts(batch1 + batch2)
        # a retraction that matches no row: its join_pairs builds the
        # pk colbuf cold and dict-codes it
        assert e.delete_facts([Fact("Data", "hub", "link", "absent")]) == 0
        e.infer()
        return e

    want = run("numpy", False)
    got = run("jax-interpret", True)
    t = got.store.tables["Data"]
    rows = Counter(zip(t.ids[:t.n].tolist(), t.attrs[:t.n].tolist(),
                       t.vals[:t.n].tolist()))
    assert all(c == 1 for c in rows.values()), "duplicate rows written"
    assert got.store.num_facts() == want.store.num_facts()
    assert decoded_fact_checksum(got) == decoded_fact_checksum(want)
    # the regression path must actually be exercised: a dict codec was
    # chosen for some resident column (the hub fan-out pk column)
    assert got.ops._res_counts["dict"] > 0


# -- frontier-exchange lane narrowing ---------------------------------------

def test_frontier_exchange_wire_parity():
    from repro.distributed.pipeline import FrontierExchange
    fx = FrontierExchange(4, prefer_device=False, compress=True)
    fx0 = FrontierExchange(4, prefer_device=False, compress=False)
    dest = [RNG.randint(0, 4, 60).astype(np.int32) for _ in range(4)]
    key = [RNG.randint(1000, 5000, 60).astype(np.int64) for _ in range(4)]
    val = [RNG.randint(-2**40, 2**40, 60).astype(np.int64)
           for _ in range(4)]
    meta = [RNG.randint(-150, 150, 60).astype(np.int64) for _ in range(4)]
    out, st = fx.exchange(dest, key, val, meta)
    out0, st0 = fx0.exchange(dest, key, val, meta)
    for a, b in zip(out, out0):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.sort(x), np.sort(y))
    assert st["payload_bytes_wire"] < st["payload_bytes"]
    assert st0["payload_bytes_wire"] == st0["payload_bytes"]
