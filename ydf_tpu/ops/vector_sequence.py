"""NUMERICAL_VECTOR_SEQUENCE projection scores.

The reference offloads this exact computation — per (example, anchor):
max-over-sequence dot product and (negated) min-over-sequence squared
euclidean distance — to its only CUDA kernel
(`ydf/learner/decision_tree/gpu.cu.cc:139-180` ComputeMaxDotProduct /
ComputeNegMinSquareDistance, CPU fallback in `gpu.cc`). The TPU analogue
is below: one Pallas kernel that flattens the (example, vector) axes into
a single [BN*L, D] x [D, A] MXU contraction per block and reduces
max/min over the sequence axis with a length mask, plus a pure-XLA
formulation used off-TPU and as the correctness oracle.

Score conventions (both "higher is more"):
  * projected_more_than: score = max_{v in seq} <v, anchor>
  * closer_than:         score = -min_{v in seq} |v - anchor|^2
Empty sequences score -FLT_MAX (the CUDA kernel's behaviour: the running
min stays FLT_MAX and is negated), so they always fall on the negative
side of any learned threshold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF_SCORE = -3.4028235e38  # -FLT_MAX, matching gpu.cu.cc


def _scores_xla(values, lengths, anchors, is_closer):
    """Reference formulation: plain XLA ops (fused mask + reduce).

    values  f32 [n, L, D] (zero-padded), lengths i32 [n],
    anchors f32 [A, D], is_closer bool [A]  →  scores f32 [n, A].
    """
    values = jnp.asarray(values, jnp.float32)
    anchors = jnp.asarray(anchors, jnp.float32)
    L = values.shape[1]
    # HIGHEST: full-f32 MXU passes — the d2 expansion below cancels
    # catastrophically under the default bf16 matmul precision.
    dots = jnp.einsum(
        "nld,ad->nla", values, anchors, precision=jax.lax.Precision.HIGHEST
    )
    v_sq = jnp.sum(jnp.square(values), axis=2)  # [n, L]
    a_sq = jnp.sum(jnp.square(anchors), axis=1)  # [A]
    d2 = v_sq[:, :, None] - 2.0 * dots + a_sq[None, None, :]
    valid = (jnp.arange(L)[None, :] < lengths[:, None])[:, :, None]
    max_dot = jnp.max(jnp.where(valid, dots, NEG_INF_SCORE), axis=1)
    neg_min_d2 = -jnp.min(jnp.where(valid, d2, -NEG_INF_SCORE), axis=1)
    return jnp.where(is_closer[None, :], neg_min_d2, max_dot)


_MASK_BIG = 1.0e30


def _vs_kernel(values_ref, mask_ref, anchors_ref, is_closer_ref, out_ref):
    """One example-block: scores[BN, A] from values [BN, L, D].

    mask_ref f32 [BN, L]: 0 where the vector exists, -1e30 past the
    sequence end — an ADDITIVE mask, precomputed outside the kernel
    because Mosaic only supports minor-dim broadcast of 32-bit vectors
    (a bool [BN, L] → [BN, L, 1] unsqueeze fails to lower)."""
    BN, L, D = values_ref.shape
    A = anchors_ref.shape[0]
    vals = values_ref[:]  # [BN, L, D]
    flat = vals.reshape(BN * L, D)
    dots = jnp.dot(
        flat, anchors_ref[:].T, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(BN, L, A)
    v_sq = jnp.sum(jnp.square(flat), axis=1).reshape(BN, L)
    a_sq = jnp.sum(jnp.square(anchors_ref[:]), axis=1)  # [A]
    d2 = v_sq[:, :, None] - 2.0 * dots + a_sq[None, None, :]
    m = mask_ref[:][:, :, None]  # [BN, L, 1] f32
    max_dot = jnp.max(dots + m, axis=1)
    neg_min_d2 = -jnp.min(d2 - m, axis=1)
    out = jnp.where(is_closer_ref[:][None, :] != 0, neg_min_d2, max_dot)
    # Empty sequences: every slot masked → ±1e30-ish; pin to the CUDA
    # kernel's -FLT_MAX sentinel.
    out_ref[:] = jnp.where(out <= -_MASK_BIG / 2, NEG_INF_SCORE, out)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _scores_pallas(values, lengths, anchors, is_closer, block=128,
                   interpret=False):
    n, L, D = values.shape
    A = anchors.shape[0]
    # Keep the block's values tile within a VMEM budget (~4 MiB).
    BN = block
    while BN > 8 and BN * L * D * 4 > 4 * 1024 * 1024:
        BN //= 2
    pad = (-n) % BN
    values = jnp.pad(
        jnp.asarray(values, jnp.float32), ((0, pad), (0, 0), (0, 0))
    )
    lengths = jnp.pad(jnp.asarray(lengths, jnp.int32), (0, pad))
    mask_add = jnp.where(
        jnp.arange(L)[None, :] < lengths[:, None], 0.0, -_MASK_BIG
    ).astype(jnp.float32)
    out = pl.pallas_call(
        _vs_kernel,
        name="ydf_vs_scores",
        grid=((n + pad) // BN,),
        in_specs=[
            pl.BlockSpec((BN, L, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((BN, L), lambda i: (i, 0)),
            pl.BlockSpec((A, D), lambda i: (0, 0)),
            pl.BlockSpec((A,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BN, A), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, A), jnp.float32),
        interpret=interpret,
    )(
        values,
        mask_add,
        jnp.asarray(anchors, jnp.float32),
        jnp.asarray(is_closer, jnp.int32),
    )
    return out[:n]


def vs_scores(values, lengths, anchors, is_closer, impl: str = "auto"):
    """Projection scores [n, A]; anchor a is closer_than iff is_closer[a].

    impl: "xla" (pure XLA, any backend), "pallas" (compiled TPU kernel),
    "pallas_interpret" (kernel in interpret mode — CPU tests), "auto"
    (pallas on TPU, xla elsewhere)."""
    if impl == "auto":
        from ydf_tpu.config import is_tpu_backend

        impl = "pallas" if is_tpu_backend() else "xla"
    if impl == "xla":
        return _scores_xla(values, lengths, anchors, is_closer)
    if impl == "pallas":
        return _scores_pallas(values, lengths, anchors, is_closer)
    if impl == "pallas_interpret":
        return _scores_pallas(
            values, lengths, anchors, is_closer, interpret=True
        )
    raise ValueError(f"Unknown impl {impl!r}")


def vs_scores_oracle(values, lengths, anchors, is_closer):
    """NumPy oracle (mirrors the reference CPU fallback, gpu.cc)."""
    values = np.asarray(values, np.float64)
    anchors = np.asarray(anchors, np.float64)
    n, _, _ = values.shape
    A = anchors.shape[0]
    out = np.full((n, A), NEG_INF_SCORE, np.float64)
    for e in range(n):
        seq = values[e, : int(lengths[e])]
        if seq.shape[0] == 0:
            continue
        for a in range(A):
            if is_closer[a]:
                d2 = np.sum(np.square(seq - anchors[a][None, :]), axis=1)
                out[e, a] = -d2.min()
            else:
                out[e, a] = (seq @ anchors[a]).max()
    return out.astype(np.float32)
