"""Compile the engine's main-path kernels for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a
described ``v5e:2x2`` topology at real widths (2^20 rows) with the
dtypes the engine passes, through ``kernels/routing.py`` as it decides
on a TPU.  A route to Pallas must leave a ``tpu_custom_call`` in the
compiled program; a route to XLA must leave none.

Each entry point dispatches its jitted stages around the shared sort
programs (``device_sort`` / ``device_sort_kv``), so the tests compile
exactly those programs: each sort once, then every other stage.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  All of these tests stay in this one file.
Kernels whose compile is known to abort the process are never compiled
here (see the kernel READMEs).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import routing
from repro.kernels.mergejoin import ops as mj
from repro.kernels.sortmerge import ops as sm
from repro.kernels.uniquefilter.uniquefilter import unique_mask_sorted

N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch, one_chip, no_cache):
    """Route as the routing function would on a TPU (this process runs
    on the CPU, so ``jax.default_backend()`` would say ``cpu``)."""
    monkeypatch.setattr(routing, "platform", lambda: "tpu")
    return one_chip


def _spec(sharding, dtype, shape=(N,)):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    with jax.enable_x64(True):
        return jax.jit(fn).lower(*args).compile()


def _assert_route(compiled, kernel: str, dtype) -> None:
    text = compiled.as_text()
    if routing.use_pallas(kernel, dtype):
        assert "tpu_custom_call" in text, kernel
    else:
        assert "tpu_custom_call" not in text, kernel


def _i64(sh, shape=(N,)):
    return _spec(sh, jnp.int64, shape)


def test_sort_compiles(on_tpu):
    _assert_route(_compile(sm.device_sort, _i64(on_tpu)), "sort", jnp.int64)


def test_sort_kv_compiles(on_tpu):
    c = _compile(sm.device_sort_kv, _i64(on_tpu), _spec(on_tpu, jnp.int32))
    _assert_route(c, "sort_kv", jnp.int64)


def test_stable_sort_perm_stages_compile(on_tpu):
    tb = sm.tag_bits_for(N)
    one = _i64(on_tpu, ())
    for stage in (lambda k, n, m: sm._tag_keys(k, n, m, tag_bits=tb),
                  lambda s, n, m: sm._detag_keys(s, n, m, tag_bits=tb)):
        _assert_route(_compile(stage, _i64(on_tpu), one, one), "sort",
                      jnp.int64)


def test_dedup_rows_stages_compile(on_tpu):
    tb = sm.tag_bits_for(N)
    one = _i64(on_tpu, ())
    for fn, args in [
            (lambda c, o, n, m: sm._tag_column(c, o, n, m, ci=1,
                                               tag_bits=tb),
             (_i64(on_tpu), _i64(on_tpu), one, _i64(on_tpu, (3,)))),
            (lambda o, s: sm._reorder(o, s, tag_bits=tb),
             (_i64(on_tpu), _i64(on_tpu))),
            (lambda a, b, c, o, n: sm._first_of_runs((a, b, c), o, n),
             (_i64(on_tpu),) * 4 + (one,))]:
        _assert_route(_compile(fn, *args), "sort", jnp.int64)


def test_merge_join_bounded_stages_compile(on_tpu):
    _assert_route(_compile(mj._widen_with_lanes, _i64(on_tpu)), "sort_kv",
                  jnp.int64)
    c = _compile(lambda lk, rs, rp: mj._expand_pairs(
        lk, rs, rp, out_cap=N, block=1024,
        pallas=routing.use_pallas("probe", jnp.int64), interpret=False),
        _i64(on_tpu), _i64(on_tpu), _spec(on_tpu, jnp.int32))
    _assert_route(c, "probe", jnp.int64)


def test_merge_join_gather_bounded_stages_compile(on_tpu):
    one = _i64(on_tpu, ())
    for hashed in (False, True):
        c = _compile(lambda lk, rk, nl, nr: mj._join_domain(
            lk, rk, nl, nr, hash_keys=hashed),
            _i64(on_tpu), _i64(on_tpu), one, one)
        _assert_route(c, "sort_kv", jnp.int64)

        def gather(l64, r64, lk, rs, rp, bad, nl, lp, rpay):
            return mj._gather_pairs(l64, r64, lk, rs, rp, bad, nl, (lp,),
                                    (rpay,), (), (), out_cap=N, block=1024,
                                    pallas=routing.use_pallas("probe",
                                                              jnp.int64),
                                    interpret=False, hash_keys=hashed)
        c = _compile(gather, _i64(on_tpu), _i64(on_tpu), _i64(on_tpu),
                     _i64(on_tpu), _spec(on_tpu, jnp.int32),
                     _spec(on_tpu, jnp.bool_, ()), one, _i64(on_tpu),
                     _i64(on_tpu))
        _assert_route(c, "probe", jnp.int64)


def test_merge_sorted_mirror_stages_compile(on_tpu):
    tb = sm.tag_bits_for(N)
    one = _i64(on_tpu, ())
    dcap = 4096
    c = _compile(lambda b, d, n, k: sm._tag_tail(b, d, n, k, dcap=dcap,
                                                 tag_bits=tb),
                 _i64(on_tpu), one, one, one)
    _assert_route(c, "sort", jnp.int64)
    c = _compile(lambda base, drun, nr, ds, nt, k, ko: sm._merge_tagged(
        base, drun, nr, ds, nt, k, ko, tag_bits=tb, block=1024,
        pallas=routing.use_pallas("merge_ranks", jnp.int64),
        interpret=False),
        _i64(on_tpu), _i64(on_tpu, (dcap,)), one, one, one, one, one)
    _assert_route(c, "merge_ranks", jnp.int64)


def test_semi_join_stages_compile(on_tpu):
    from repro.backend.jax_ops import _jitted
    jt = _jitted()
    one = _i64(on_tpu, ())
    _compile(jt["repad_max"], _i64(on_tpu), one)
    c = _compile(jt["member_sorted_n"], _i64(on_tpu), _i64(on_tpu), one)
    assert "tpu_custom_call" not in c.as_text()


def test_unique_mask_routes(on_tpu):
    """int64 (the engine's operands) takes the neighbor-compare XLA
    program; int32 takes the Pallas kernel."""
    from repro.backend.jax_ops import _jitted
    assert not routing.use_pallas("unique_mask", jnp.int64)
    c = _compile(_jitted()["neighbor_mask"], _i64(on_tpu))
    assert "tpu_custom_call" not in c.as_text()
    assert routing.use_pallas("unique_mask", jnp.int32)
    c = _compile(unique_mask_sorted, _spec(on_tpu, jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_batch_probe_compiles(on_tpu):
    from repro.backend.jax_ops import _jitted
    pallas = routing.use_pallas("probe", np.int64)
    fn = _jitted()["batch_probe_j"]
    c = _compile(lambda sk, n, pr: fn(sk, n, pr, block=1024,
                                      use_pallas=pallas, interpret=False),
                 _i64(on_tpu), _i64(on_tpu, ()), _i64(on_tpu, (4096,)))
    _assert_route(c, "probe", jnp.int64)
