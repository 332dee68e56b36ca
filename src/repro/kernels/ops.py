"""Backend-aware public wrappers around the Pallas kernels.

Dispatch policy (per process, decided from the actual JAX backend):
  - tpu : compiled Pallas kernels.
  - cpu : interpret mode — executes the kernel body with bit-identical
          semantics, so the ref-vs-kernel tests are meaningful on CPU.
Any other backend is refused with a named error: the kernels are written
against ``pallas.tpu``, and no blocked-jnp stand-in may hide which device
ran them.
"""
from __future__ import annotations

import jax

from repro.kernels import distance_argmin as _da
from repro.kernels import flash_attention as _fa


class UnsupportedBackendError(RuntimeError):
    """A Pallas kernel was called on a backend that is neither TPU nor CPU."""


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise UnsupportedBackendError(
        f"repro.kernels: backend {backend!r} is not supported — the "
        "kernels compile for TPU and run in interpret mode on CPU only")


def distance_argmin_l2(x, centers, center_valid, **kw):
    kw.setdefault("interpret", _interpret())
    return _da.distance_argmin_l2(x, centers, center_valid, **kw)


def distance_argmin_hamming(codes, centers, center_valid, **kw):
    kw.setdefault("interpret", _interpret())
    return _da.distance_argmin_hamming(codes, centers, center_valid, **kw)


def distance_argmin_hamming_packed(packed, packed_centers, center_valid,
                                   *, bits, **kw):
    kw.setdefault("interpret", _interpret())
    return _da.distance_argmin_hamming_packed(packed, packed_centers,
                                              center_valid, bits=bits, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _interpret())
    return _fa.flash_attention(q, k, v, **kw)


def flash_centroid_attention(q, centers, v_cent, log_mass, **kw):
    kw.setdefault("interpret", _interpret())
    return _fa.flash_centroid_attention(q, centers, v_cent, log_mass, **kw)
