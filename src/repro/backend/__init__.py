"""Execution backends for the inference hot path (see backend/README.md).

``get_backend(name)`` resolves an ``EngineConfig.backend`` value to a
shared ``Ops`` instance:

* ``numpy``         — host twins (default; always available).
* ``jax``           — device path through ``kernels/``: Pallas on TPU,
                      portable jitted XLA lowering elsewhere.
* ``jax-pallas``    — force the compiled Pallas kernels (TPU).
* ``jax-interpret`` — force the Pallas kernels through the interpreter
                      (runs the real kernel code on CPU; tests/parity).

Instances are cached: the jit caches and sentinel-guard state they carry
are per-process resources, not per-engine ones.
"""

from __future__ import annotations

from repro.backend.base import Ops, splitmix64
from repro.backend.device_cache import DeviceArrayCache, TransferCounter
from repro.backend.handles import DeviceCol, is_handle
from repro.backend.numpy_ops import NumpyOps

BACKENDS = ("numpy", "jax", "jax-pallas", "jax-interpret")

_CACHE: dict[str, Ops] = {}


def fresh_backend(name: str = "numpy",
                  compress: bool | None = None, device=None) -> Ops:
    """A new, uncached ``Ops`` instance.

    Shard workers (``EngineConfig(shards=N)``) each get their own
    instance so transfer/sort-work counters and the device-array cache
    stay attributable per shard; the module-level jit caches are shared
    regardless, so extra instances do not recompile kernels.

    ``compress`` controls the device backends' compressed resident
    column tier (``None`` defers to ``REPRO_COMPRESS``, default on);
    the numpy twin is always raw.  ``device`` places a device backend's
    buffers on that jax device (shard workers, one device each); the
    numpy twin ignores it.
    """
    if name == "numpy":
        return NumpyOps()
    if name in ("jax", "jax-pallas", "jax-interpret"):
        from repro.backend.jax_ops import JaxOps
        mode = {"jax": "auto", "jax-pallas": "pallas",
                "jax-interpret": "interpret"}[name]
        # interpret mode uses small blocks: it exists to exercise the
        # kernel code path on CPU, not to win benchmarks
        kw = {"block": 256} if mode == "interpret" else {}
        return JaxOps(mode=mode, compress=compress, device=device, **kw)
    raise ValueError(
        f"unknown backend {name!r}; expected one of {BACKENDS}")


def get_backend(name: str = "numpy",
                compress: bool | None = None) -> Ops:
    key = name if compress is None else f"{name}+c{int(compress)}"
    ops = _CACHE.get(key)
    if ops is None:
        ops = _CACHE[key] = fresh_backend(name, compress=compress)
    return ops


__all__ = ["BACKENDS", "DeviceArrayCache", "DeviceCol", "NumpyOps", "Ops",
           "TransferCounter", "fresh_backend", "get_backend", "is_handle",
           "splitmix64"]
