"""Device bring-up: kernel routing, the compile cache, the frontier
exchange's device requirement and per-shard device placement.

The routing checks steer the platform inside the test (this process
runs on the CPU); nothing here compiles for a TPU (that lives in
``test_tpu_compile.py``).
"""

from __future__ import annotations

import collections
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.kernels import routing
from repro.kernels.routing import PallasUnsupported, use_pallas

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def tpu(monkeypatch):
    monkeypatch.setattr(routing, "platform", lambda: "tpu")


@pytest.mark.parametrize("kernel", routing.KERNELS)
def test_tpu_int64_routes_to_xla(tpu, kernel):
    assert not use_pallas(kernel, np.int64)


def test_tpu_compiled_32bit_kernel_routes_to_pallas(tpu):
    assert use_pallas("unique_mask", np.int32)
    # a kernel with no compiled dtype stays on XLA at int32 too
    assert not use_pallas("sort", np.int32)


@pytest.mark.parametrize("kernel", routing.KERNELS)
def test_forced_pallas_at_int64_on_tpu_raises(tpu, kernel):
    with pytest.raises(PallasUnsupported, match=kernel):
        use_pallas(kernel, np.int64, force_pallas=True)


def test_forced_pallas_at_compiled_dtype_on_tpu(tpu):
    assert use_pallas("unique_mask", np.int32, force_pallas=True)


def test_cpu_routes_to_xla_and_refuses_forced_pallas():
    assert routing.platform() == "cpu"
    for kernel in routing.KERNELS:
        assert not use_pallas(kernel, np.int64)
        assert not use_pallas(kernel, np.int32)
    with pytest.raises(PallasUnsupported, match="jax-interpret"):
        use_pallas("sort", np.int64, force_pallas=True)


def test_interpret_runs_pallas_anywhere(tpu):
    assert use_pallas("sort", np.int64, interpret=True)
    assert use_pallas("probe", np.int64, force_pallas=True, interpret=True)


def test_unknown_kernel_is_an_error():
    with pytest.raises(KeyError):
        use_pallas("bogus", np.int64)


def test_jax_pallas_backend_raises_on_tpu_int64(tpu):
    """``backend="jax-pallas"`` on a TPU at the engine's int64 operands
    fails with the routing error before any compile."""
    from repro.backend import fresh_backend
    ops = fresh_backend("jax-pallas")
    with pytest.raises(PallasUnsupported):
        ops.sort_perm(np.array([3, 1, 2], np.int64))


def test_jaxops_counts_routes_and_host_fallbacks():
    from repro.backend import fresh_backend
    ops = fresh_backend("jax")
    keys, perm = ops.sort_perm(np.array([5, 3, 9, 3], np.int64))
    assert list(keys) == [3, 3, 5, 9] and list(perm) == [1, 3, 0, 2]
    li, ri = ops.join_pairs(np.array([1, 2], np.int64),
                            np.array([2, 2, 7], np.int64))
    assert sorted(zip(li, ri)) == [(1, 0), (1, 1)]
    st = ops.route_stats()
    assert st == {"pallas": 0, "xla": 3, "host": {}}  # sort, sort_kv, probe
    # a left key equal to the right pad sentinel takes the exact host path
    imin = np.iinfo(np.int64).min
    ops.join_pairs(np.array([imin], np.int64), np.array([imin], np.int64))
    assert ops.route_stats()["host"] == {"join_pairs": 1}


def test_route_counts_are_the_kernel_decisions():
    """Every eager kernel call counts once, by the decision its wrapper
    made: a 3-column dedup runs one sort per column plus the final row
    sort; a kernel inside a jitted program is not a call of its own."""
    import jax
    from repro.backend import fresh_backend
    from repro.kernels.sortmerge.ops import device_sort
    ops = fresh_backend("jax")
    cols = [np.array([2, 1, 2, 1], np.int64), np.array([5, 5, 5, 5], np.int64),
            np.array([7, 8, 7, 8], np.int64)]
    assert sorted(ops.dedup_rows(cols)) == [0, 1]
    assert ops.route_stats() == {"pallas": 0, "xla": 4, "host": {}}
    counter = collections.Counter()
    with jax.enable_x64(True), routing.tally(counter):
        jax.jit(device_sort)(np.arange(8, dtype=np.int64))
        assert counter == {}
        device_sort(jax.numpy.arange(8, dtype=jax.numpy.int64))
    assert counter == {"xla": 1}


def test_unique_mask_kernel_matches_neighbor_compare():
    """The (rows, 128)-tiled mask kernel, through the interpreter, at
    sizes that cross tile and row boundaries."""
    from repro.kernels.uniquefilter.ref import unique_mask_ref
    from repro.kernels.uniquefilter.uniquefilter import unique_mask_sorted
    rng = np.random.default_rng(0)
    for n, dt in [(1, np.int32), (129, np.int32), (2048 + 5, np.int64),
                  (3000, np.int32)]:
        x = np.sort(rng.integers(0, max(2, n // 3), n)).astype(dt)
        got = np.asarray(unique_mask_sorted(x, block=1024, interpret=True))
        assert (got == np.asarray(unique_mask_ref(x))).all(), (n, dt)


# ---------------------------------------------------------------------------
# compile cache (subprocesses: the function sets process-global config)


def _cache_dir_in_subprocess(env_value):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    env["PYTHONPATH"] = SRC
    code = ("import jax, os\n"
            "from repro.compile_cache import configure_compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "path = configure_compile_cache()\n"
            "print(repr((before, path, jax.config.jax_compilation_cache_dir,"
            " jax.config.jax_persistent_cache_min_compile_time_secs,"
            " os.environ.get('JAX_COMPILATION_CACHE_DIR'))))\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return eval(out.stdout.strip().splitlines()[-1])


def test_compile_cache_honors_env_var(tmp_path):
    want = str(tmp_path / "cache")
    before, path, after, min_s, env = _cache_dir_in_subprocess(want)
    assert path == want and env == want
    assert before == after == want  # JAX read it; nothing else was set
    assert min_s == 0.0


def test_compile_cache_default_is_fixed_inside_checkout():
    from repro.compile_cache import DEFAULT_DIR
    repo = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    assert os.path.realpath(DEFAULT_DIR) == os.path.join(repo, ".jax_cache")
    _, path, after, min_s, env = _cache_dir_in_subprocess(None)
    assert env is None
    assert path == after == str(DEFAULT_DIR)
    assert min_s == 0.0


def test_importing_compile_cache_changes_nothing():
    import jax
    before = jax.config.jax_compilation_cache_dir
    import repro.compile_cache  # noqa: F401
    assert jax.config.jax_compilation_cache_dir == before


# ---------------------------------------------------------------------------
# sharded placement


def test_exchange_refuses_host_fallback_for_device_backends():
    """One device, two shards: a device backend raises; only the numpy
    twin runs the host permute."""
    from repro.core import EngineConfig, HiperfactEngine
    from repro.distributed.pipeline import FrontierExchange
    with pytest.raises(ValueError, match="needs 2 devices"):
        FrontierExchange(2, prefer_device=True)
    with pytest.raises(ValueError, match="needs 2 devices"):
        HiperfactEngine(EngineConfig(backend="jax", shards=2))
    assert not FrontierExchange(2, prefer_device=False).device
    assert not HiperfactEngine(EngineConfig(shards=2)).exchange.device


def test_shard_workers_place_buffers_on_distinct_devices(subproc):
    out = subproc("""
import dataclasses
import jax
from repro.core import EngineConfig, Fact, HiperfactEngine
from repro.core.rulesets import rdfs_plus_rules
from repro.core.sharded import decoded_fact_checksum
facts = [Fact("Schema", "A", "subClassOf", "B"),
         Fact("Schema", "B", "subClassOf", "C")]
facts += [Fact("Data", f"x{i}", "type", "A") for i in range(200)]
sums = {}
for shards in (4, 1):
    e = HiperfactEngine(dataclasses.replace(EngineConfig.infer1("jax"),
                                            shards=shards))
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(facts)
    e.infer()
    sums[shards] = decoded_fact_checksum(e)
    if shards == 4:
        assert e.exchange.device
        devs = [w.ops.resident_devices() for w in e.workers]
        assert [w.ops.device for w in e.workers] == jax.devices()[:4]
        assert all(d == {jax.devices()[i]} for i, d in enumerate(devs)), devs
    else:
        assert e.ops.device is None
assert sums[4] == sums[1], sums
print("placed", sums)
""", n_devices=4)
    assert "placed" in out

