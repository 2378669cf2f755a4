"""Bridges to the native fused binning kernel (native/binning_ffi.cc).

Two entry points over ONE shared library:

  * `bin_columns_native` — the ctypes fast path used by
    dataset/binning.py:transform. Pure numpy in/out, no jax dispatch,
    writes straight into a caller-provided [n, num_scalar] uint8 matrix
    (strided, so categorical columns can live alongside) — the fused
    ingest+bin pipeline's hot call.
  * `binning_native` — the XLA FFI custom call ("ydf_binning",
    registered through the same ops/native_ffi.py path as
    "ydf_histogram"), for jitted pipelines that bin on-device arrays
    without leaving the trace.

Both compute, per numerical column f:
    bin(v) = #{ b : boundary[f, b] <= v, b < nbounds[f] }   (uint8)
with NaN -> impute[f] handled in-kernel — bit-identical to the NumPy
`searchsorted(side="right")` path (asserted by tests/test_binning_native
.py). CPU only; on TPU binning is the Pallas kernel / jnp.searchsorted
path in ops/binning_pallas.py.
"""

from __future__ import annotations

import ctypes
from typing import Optional

import numpy as np

# One shared library with the histogram kernels (ops/native_ffi.py):
# both ride the persistent worker pool in native/thread_pool.h.
from ydf_tpu.ops.native_ffi import KERNELS_LIB as _LIB

_PROTO_READY = False


def _lib_with_prototypes():
    global _PROTO_READY
    lib = _LIB.load()
    if lib is not None and not _PROTO_READY:
        lib.ydf_bin_columns.restype = None
        lib.ydf_bin_columns.argtypes = [
            ctypes.POINTER(ctypes.c_float),    # values [F, n]
            ctypes.POINTER(ctypes.c_float),    # boundaries [F, max_b]
            ctypes.POINTER(ctypes.c_int32),    # nbounds [F]
            ctypes.POINTER(ctypes.c_float),    # impute [F]
            ctypes.POINTER(ctypes.c_uint8),    # out [n, out_stride]
            ctypes.c_int64,                    # n
            ctypes.c_int64,                    # F
            ctypes.c_int64,                    # max_b
            ctypes.c_int64,                    # out_stride
            ctypes.c_int32,                    # num_threads (0 = auto)
        ]
        _PROTO_READY = True
    return lib


def available() -> bool:
    """ctypes fast-path availability (does not touch jax)."""
    return _lib_with_prototypes() is not None


def ffi_available() -> bool:
    """XLA FFI custom-call availability (registers on first call)."""
    return _LIB.ensure_ffi_registered()


def bin_columns_native(
    values: np.ndarray,      # f32 [F, n], C-contiguous (column-major stack)
    boundaries: np.ndarray,  # f32 [F, max_b] ascending, +inf padded
    nbounds: np.ndarray,     # i32 [F] real boundary counts
    impute: np.ndarray,      # f32 [F] NaN replacement per column
    out: Optional[np.ndarray] = None,  # uint8 [n, out_stride>=F]
    num_threads: int = 0,
) -> np.ndarray:
    """Bins all columns in one native call; returns `out` (allocated
    [n, F] when not given). When `out` is wider than F, only the first
    F columns of each row are written (the numerical block of a
    [n, num_scalar] bin matrix). Caller must have checked available()."""
    lib = _lib_with_prototypes()
    values = np.ascontiguousarray(values, dtype=np.float32)
    boundaries = np.ascontiguousarray(boundaries, dtype=np.float32)
    nbounds = np.ascontiguousarray(nbounds, dtype=np.int32)
    impute = np.ascontiguousarray(impute, dtype=np.float32)
    F, n = values.shape
    if out is None:
        out = np.empty((n, F), dtype=np.uint8)
    if not (
        out.dtype == np.uint8
        and out.ndim == 2
        and out.flags.c_contiguous
        and out.shape[0] == n
        and out.shape[1] >= F
    ):
        raise ValueError(
            f"out must be C-contiguous uint8 [n={n}, >=F={F}], got "
            f"{out.dtype} {out.shape}"
        )
    lib.ydf_bin_columns(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        boundaries.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        nbounds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        impute.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n, F, boundaries.shape[1], out.shape[1], num_threads,
    )
    return out


def binning_native(values, boundaries, nbounds, impute):
    """XLA FFI path: uint8 bins [n, F] from f32 values [F, n] inside a
    jitted computation. Caller must have checked ffi_available()."""
    import jax
    import jax.numpy as jnp

    F, n = values.shape
    return jax.ffi.ffi_call(
        "ydf_binning",
        jax.ShapeDtypeStruct((n, F), jnp.uint8),
    )(
        values.astype(jnp.float32),
        boundaries.astype(jnp.float32),
        nbounds.astype(jnp.int32),
        impute.astype(jnp.float32),
    )
