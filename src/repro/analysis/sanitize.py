"""Runtime sanitizers: prove the warm device path never syncs implicitly.

Two mechanisms compose, because each has a blind spot:

* ``jax.transfer_guard("disallow")`` — XLA's own guard.  It has teeth on
  TPU/GPU, where host and device memory are distinct; on the CPU backend
  a jax array and its numpy view share memory, no copy happens, and the
  guard observes *no transfer event at all* (verified empirically: even
  ``disallow`` blocks nothing on CPU).  CI runs on CPU, so alone it
  would be a green light that tests nothing.

* a Python-level sentinel that patches ``np.asarray`` / ``np.array`` to
  reject ``jax.Array`` inputs, and ``jnp.asarray`` / ``jnp.array`` to
  reject concrete ``np.ndarray`` inputs outside a trace.  These are the
  two implicit directions (D2H and H2D).  The explicit transfer API —
  ``jax.device_get`` / ``jax.device_put`` — is wrapped to open an
  allowance window, because *explicit* transfers (the per-batch plan
  upload, the final counts download) are part of the engine's contract;
  only *implicit* ones are bugs.  Patching must happen at the numpy
  module attributes: ``ArrayImpl.__array__`` is a C++ slot that
  monkeypatching cannot reach.

``no_implicit_transfers()`` is the pytest sanitize mode's wrapper: warm
the fused fold once, then run the same-shaped batch inside the guard —
any ``.item()``, ``np.asarray(device_value)`` or stray upload that
sneaks into the hot path raises :class:`ImplicitTransferError` on CPU
and trips the XLA guard on real accelerators.

``jit_cache_size`` reads a jitted callable's executable count — the
compile-counter half of the sanitize mode, asserting the ~1/8 shape
quantization grid bounds compiles across mixed-size batches.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ImplicitTransferError",
    "no_implicit_transfers",
    "jit_cache_size",
]


class ImplicitTransferError(RuntimeError):
    """An implicit host<->device transfer inside a sanitized region."""


_state = threading.local()


def _explicit_depth() -> int:
    return getattr(_state, "explicit", 0)


@contextlib.contextmanager
def _explicitly():
    _state.explicit = _explicit_depth() + 1
    try:
        yield
    finally:
        _state.explicit -= 1


def _is_concrete_device(x) -> bool:
    """A committed device value (not a tracer — inside jit everything is
    symbolic and no transfer can occur)."""
    return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)


@contextlib.contextmanager
def no_implicit_transfers():
    """Forbid implicit host<->device transfers inside the block.

    Composes ``jax.transfer_guard("disallow")`` (effective on TPU/GPU)
    with the numpy/jnp sentinel patch (effective everywhere, including
    the CPU backend CI runs on).  ``jax.device_get`` / ``device_put``
    remain allowed — they are the explicit API the engine's per-batch
    upload/download contract is written against.
    """
    orig_np_asarray = np.asarray
    orig_np_array = np.array
    orig_jnp_asarray = jnp.asarray
    orig_jnp_array = jnp.array
    orig_device_get = jax.device_get
    orig_device_put = jax.device_put

    def guard_np(orig, name):
        def wrapper(obj, *args, **kwargs):
            if _explicit_depth() == 0 and _is_concrete_device(obj):
                raise ImplicitTransferError(
                    f"implicit device->host transfer: np.{name}() on a "
                    "jax.Array inside a sanitized region — use "
                    "jax.device_get for the explicit download"
                )
            return orig(obj, *args, **kwargs)

        return wrapper

    def guard_jnp(orig, name):
        def wrapper(obj, *args, **kwargs):
            if _explicit_depth() == 0 and isinstance(obj, np.ndarray):
                raise ImplicitTransferError(
                    f"implicit host->device transfer: jnp.{name}() on a "
                    "np.ndarray inside a sanitized region — use "
                    "jax.device_put for the explicit upload"
                )
            return orig(obj, *args, **kwargs)

        return wrapper

    def explicit_get(x):
        with _explicitly():
            return orig_device_get(x)

    def explicit_put(x, *args, **kwargs):
        with _explicitly():
            return orig_device_put(x, *args, **kwargs)

    np.asarray = guard_np(orig_np_asarray, "asarray")
    np.array = guard_np(orig_np_array, "array")
    jnp.asarray = guard_jnp(orig_jnp_asarray, "asarray")
    jnp.array = guard_jnp(orig_jnp_array, "array")
    jax.device_get = explicit_get
    jax.device_put = explicit_put
    try:
        with jax.transfer_guard("disallow"):
            yield
    finally:
        np.asarray = orig_np_asarray
        np.array = orig_np_array
        jnp.asarray = orig_jnp_asarray
        jnp.array = orig_jnp_array
        jax.device_get = orig_device_get
        jax.device_put = orig_device_put


def jit_cache_size(fn) -> int:
    """Number of traced entries in a jitted callable's cache — the
    compile counter the quantization-grid bound is asserted against."""
    probe = getattr(fn, "_cache_size", None)
    if callable(probe):
        return int(probe())
    raise AttributeError(f"{fn!r} is not a jitted callable (no cache probe)")
