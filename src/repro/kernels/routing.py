"""Where each kernel call runs: its Pallas kernel or its XLA lowering.

Every kernel family here has two lowerings of the same function: the
Pallas kernel and a plain XLA form (``jnp.sort``, stable ``argsort``,
``searchsorted``, a neighbor compare).  Both run on the device.  This
module is the one place that chooses between them, per call, from the
platform and the operand dtype:

* off TPU the XLA form runs, except under the Pallas interpreter
  (``interpret=True``), which runs any dtype on any platform;
* on TPU a Pallas kernel runs only at the operand dtypes listed in
  ``PALLAS_TPU_DTYPES`` — the dtypes at which it compiles for v5e at
  real widths (``tests/test_tpu_compile.py`` holds that claim);
* everything else on TPU takes the XLA form, unless the caller forced
  Pallas (``backend="jax-pallas"``), which raises ``PallasUnsupported``
  instead of crashing inside Mosaic or silently re-routing.

Why most kernels are absent from the table: the engine's operands are
int64 (tagged sort keys, packed ``(id, attr)`` join keys), and Mosaic
lowers no 64-bit vector code.  At int32 the bitonic network and the
binary-search kernels still need 1-D gathers, which Mosaic refuses; see
each kernel family's README.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np

# kernel -> operand dtypes at which its Pallas form compiles for TPU v5e
PALLAS_TPU_DTYPES: dict[str, frozenset] = {
    "sort": frozenset(),          # bitonic_sort: 1-D gathers
    "sort_kv": frozenset(),       # bitonic_sort_kv: 1-D gathers
    "merge_ranks": frozenset(),   # whole other run in VMEM + gathers
    "probe": frozenset(),         # probe_sorted: same binary search
    # no engine path passes int32 yet (its operands widen to int64); the
    # 32-bit fact lanes of ROADMAP speed item 3 would make this route live
    "unique_mask": frozenset({np.dtype(np.int32)}),
}
KERNELS = tuple(PALLAS_TPU_DTYPES)


_tally = threading.local()


class PallasUnsupported(ValueError):
    """A forced Pallas call at a platform/dtype where it cannot compile."""


def platform() -> str:
    """The platform of the default device (``tpu``, ``cpu``, ...)."""
    return jax.default_backend()


def use_pallas(kernel: str, dtype, *, force_pallas: bool = False,
               interpret: bool = False) -> bool:
    """True iff this call of ``kernel`` on ``dtype`` operands runs the
    Pallas kernel; False means its XLA lowering."""
    if kernel not in PALLAS_TPU_DTYPES:
        raise KeyError(f"unknown kernel {kernel!r}; expected one of "
                       f"{KERNELS}")
    if interpret:
        return True
    plat = platform()
    dt = np.dtype(dtype)
    if plat == "tpu" and dt in PALLAS_TPU_DTYPES[kernel]:
        return True
    if force_pallas:
        if plat != "tpu":
            raise PallasUnsupported(
                f"Pallas kernels compile only for TPU, not {plat!r}; "
                f"use backend='jax-interpret' to run them here")
        ok = sorted(str(d) for d in PALLAS_TPU_DTYPES[kernel]) or "none"
        raise PallasUnsupported(
            f"the Pallas {kernel!r} kernel does not compile for TPU at "
            f"{dt}; compiled dtypes: {ok}. Use backend='jax', which "
            f"routes this call to its XLA lowering")
    return False


@contextlib.contextmanager
def tally(counter):
    """Count, into ``counter`` (keys ``"pallas"`` / ``"xla"``), every
    ``route`` decision this thread makes for an eager kernel call."""
    prev = getattr(_tally, "counter", None)
    _tally.counter = counter
    try:
        yield counter
    finally:
        _tally.counter = prev


def route(kernel: str, x, *, dtype=None, force_pallas: bool = False,
          interpret: bool = False) -> bool:
    """``use_pallas`` for one call of ``kernel`` on operand ``x`` (at
    ``dtype``, where the kernel widens ``x`` first).  An eager call is
    counted in the thread's ``tally``; a traced one is part of an
    enclosing program and is not a call of its own."""
    pallas = use_pallas(kernel, x.dtype if dtype is None else dtype,
                        force_pallas=force_pallas, interpret=interpret)
    counter = getattr(_tally, "counter", None)
    if counter is not None and not isinstance(x, jax.core.Tracer):
        counter["pallas" if pallas else "xla"] += 1
    return pallas
