"""The paper's engine at pod scale: distributed transitive closure.

Runs the semi-naive closure (core/distributed.py) over an 8-host-device
mesh — the same shard_map program the multi-pod dry-run lowers on 512
chips.  MUST set XLA_FLAGS before any jax import, which this script does
itself::

    PYTHONPATH=src python examples/distributed_closure.py
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

# ruff: noqa: E402
import time

import numpy as np


def main() -> None:
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    from jax.sharding import Mesh
    from repro.core.distributed import ClosureConfig, DistributedClosure

    devices = jax.devices()
    if len(devices) < 8:
        # a silent [:N] slice would build a degenerate mesh and skew
        # every number printed below — fail with the fix instead
        raise SystemExit(
            f"need 8 devices for the 2x4 mesh, found {len(devices)}.\n"
            f"XLA_FLAGS was already set in the environment, so this "
            f"script did not force host devices; either unset it or "
            f"add: --xla_force_host_platform_device_count=8")
    mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    # random DAG-ish edge set
    rng = np.random.RandomState(7)
    n_nodes, n_edges = 250, 700
    src = rng.randint(0, n_nodes, n_edges)
    dst = np.minimum(src + rng.randint(1, 12, n_edges), n_nodes - 1)

    dc = DistributedClosure(mesh, ClosureConfig(
        edge_cap=1 << 15, delta_cap=1 << 13, slot_cap=1 << 11,
        join_cap=1 << 15))
    t0 = time.perf_counter()
    pairs, iters = dc.run(src, dst, max_iters=64)
    dt = time.perf_counter() - t0
    print(f"closure: {len(pairs)} pairs from {n_edges} edges "
          f"in {iters} semi-naive iterations ({dt:.2f}s)")

    # verify against a host oracle (semi-naive in numpy)
    want = set(zip(src.tolist(), dst.tolist()))
    frontier = set(want)
    by_src: dict[int, list[int]] = {}
    for a, b in zip(src.tolist(), dst.tolist()):
        by_src.setdefault(a, []).append(b)
    while frontier:
        new = {(a, c) for (a, b) in frontier for c in by_src.get(b, ())}
        frontier = new - want
        want |= frontier
    want_packed = sorted((a << 32) | b for a, b in want)
    ok = sorted(int(p) for p in pairs) == want_packed
    print(f"host-oracle check: {'OK' if ok else 'MISMATCH'} "
          f"({len(want)} pairs)")
    assert ok


if __name__ == "__main__":
    main()
