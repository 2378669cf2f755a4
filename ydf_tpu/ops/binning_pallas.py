"""On-device quantile binning — the TPU-side of the fused ingestion
pipeline (jnp path + Pallas/Mosaic kernel).

The CPU fast path is the native kernel (ops/binning_native.py); these
are its device-resident counterparts so binning compiles for platform
"tpu" alongside the rest of the training loop (the lowering pack under
artifacts/tpu_lowering/ carries the Mosaic artifact):

  * `bin_columns_jit` — a vmapped `jnp.searchsorted` formulation; runs
    on any backend, used as the jit-composable reference.
  * `binning_pallas` — a Mosaic kernel: for each (feature, example
    chunk) grid step the chunk's values are NaN->impute fixed and
    compared against the feature's boundary column held VMEM-resident
    as a [Bp, 1] sublane vector; bin = popcount of (boundary <= value)
    via an integer sum over sublanes. O(B) compares per value instead
    of O(log B), but on the VPU the op is memory-bound on the value
    stream either way (256 8x128 vector compares per 1024-value chunk),
    and the compare-reduce needs no data-dependent control flow, which
    is exactly what Mosaic wants.

Semantics match the native kernel / NumPy oracle bit-for-bit:
bin(v) = #{ b < nb : boundary_b <= v }, NaN -> impute first, a
still-NaN value (NaN impute) bins to nb, results clamped to nb <= 255.

Layouts are example-minor like ops/histogram_pallas.py: values arrive
[F, n] (each feature's column contiguous along lanes); boundaries are
pre-transposed to [Bp, F] so the kernel's [Bp, 1] block broadcasts
against the [1, C] value row with no in-kernel relayout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@jax.jit
def bin_columns_jit(values, boundaries, nbounds, impute):
    """Vmapped searchsorted binning: values f32 [F, n], boundaries
    f32 [F, max_b] ascending (+inf padded), nbounds i32 [F], impute
    f32 [F] -> uint8 bins [n, F]. Any backend."""

    def one(col, bd, nb, imp):
        v = jnp.where(jnp.isnan(col), imp, col)
        idx = jnp.searchsorted(bd, v, side="right")
        idx = jnp.minimum(idx, nb)
        return jnp.where(jnp.isnan(v), nb, idx)

    idx = jax.vmap(one)(values, boundaries, nbounds, impute)  # [F, n]
    return idx.T.astype(jnp.uint8)


def _bin_kernel(vals_ref, bdT_ref, nb_ref, imp_ref, out_ref, *, F):
    """One example-chunk grid step; the feature loop is unrolled
    in-kernel (F is static) so every block keeps its full first
    dimension — Mosaic wants the last two block dims (8, 128)-divisible
    or full.

    vals_ref [F, C]  f32   feature values for this chunk
    bdT_ref  [Bp, F] f32   boundary columns (+inf padded)
    nb_ref   [1, F]  i32   real boundary counts
    imp_ref  [1, F]  f32   NaN replacements
    out_ref  [F, C]  i32   bin indices (clamped to nb)
    """
    for f in range(F):
        v = vals_ref[f : f + 1, :]                     # [1, C]
        v = jnp.where(jnp.isnan(v), imp_ref[0, f], v)
        # f32 compare-sum (Mosaic has no integer reductions here);
        # counts <= 255 are exact in f32.
        le = (bdT_ref[:, f : f + 1] <= v).astype(jnp.float32)  # [Bp, C]
        cnt = jnp.sum(le, axis=0, keepdims=True).astype(jnp.int32)
        nb = nb_ref[0, f]
        cnt = jnp.minimum(cnt, nb)
        out_ref[f : f + 1, :] = jnp.where(jnp.isnan(v), nb, cnt)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def binning_pallas(
    values,      # f32 [F, n]
    boundaries,  # f32 [F, max_b] ascending, +inf padded
    nbounds,     # i32 [F]
    impute,      # f32 [F]
    chunk: int = 1024,
    interpret: bool = False,
):
    """Mosaic binning kernel; returns uint8 bins [n, F] with the same
    contract as bin_columns_jit / the native kernel."""
    F, n = values.shape
    Bp = _round_up(max(boundaries.shape[1], 1), 8)
    n_pad = _round_up(max(n, 1), chunk)

    vals = values.astype(jnp.float32)
    if n_pad != n:
        # Padded examples bin to garbage and are sliced off below.
        vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
    bd = boundaries.astype(jnp.float32)
    if Bp != boundaries.shape[1]:
        bd = jnp.pad(bd, ((0, 0), (0, Bp - boundaries.shape[1])),
                     constant_values=jnp.inf)
    bdT = bd.T  # [Bp, F]

    grid = (n_pad // chunk,)
    out = pl.pallas_call(
        functools.partial(_bin_kernel, F=F),
        name="ydf_bin",
        grid=grid,
        in_specs=[
            pl.BlockSpec((F, chunk), lambda c: (0, c)),
            pl.BlockSpec((Bp, F), lambda c: (0, 0)),
            pl.BlockSpec((1, F), lambda c: (0, 0)),
            pl.BlockSpec((1, F), lambda c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((F, chunk), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((F, n_pad), jnp.int32),
        interpret=interpret,
    )(
        vals,
        bdT,
        nbounds.astype(jnp.int32)[None, :],
        impute.astype(jnp.float32)[None, :],
    )
    return out[:, :n].T.astype(jnp.uint8)
