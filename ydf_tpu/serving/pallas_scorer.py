"""Pallas/Mosaic batched data-bank scorer — TPU serving of forests
beyond the QuickScorer envelope.

QuickScorer (serving/quickscorer.py) is the fastest TPU engine but
caps trees at 64 leaves; production GBTs grown best-first routinely
exceed that. This kernel serves ANY tree shape by walking the stacked
node tables (the forest's [T, N] struct-of-arrays — the data bank in
stacked form) directly on the TPU:

  * node-table gathers are ONE-HOT masked reductions over the padded
    node axis (`sum(onehot(node) * table_row)`): gather-free VPU work,
    the same trick the histogram kernel uses to build one-hot tiles in
    VMEM, because Mosaic has no vector gather;
  * the per-example feature read is the same one-hot reduction over
    the feature axis of the example block;
  * categorical masks ride as u16 half-words in f32 lanes (exact —
    values < 2^16), statically unrolled over mask words like the
    QuickScorer bitmap unroll;
  * trees accumulate sequentially (fori_loop), one f32 add per tree —
    exactly the XLA oracle's lax.scan order, so interpret-mode output
    is BIT-IDENTICAL to ops/routing.py:forest_predict_values for the
    engine envelope (tests/test_serving_engine.py).

Envelope: single-accumulator forests (V == 1), no categorical-set /
vector-sequence / oblique conditions, encode-time imputation. Work per
example block is O(T · depth · Np) VPU lanes — linear in model size,
independent of leaf counts.

The Mosaic lowering artifact rides in artifacts/tpu_lowering/
(serve_bank_pallas_kernel.*, exported by utils/tpu_lowering.py) next
to the histogram/binning kernel artifacts.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

i32 = jnp.int32
f32 = jnp.float32


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class BankTables(NamedTuple):
    """Host-prepped padded node tables, all f32 (payloads are exact in
    f32: node/column ids < 2^24, mask halves < 2^16)."""

    feat_col: np.ndarray   # [T, Np] x-column of the node's feature
    thresh: np.ndarray     # [T, Np]
    is_cat: np.ndarray     # [T, Np] 0/1
    is_leaf: np.ndarray    # [T, Np] 0/1
    left: np.ndarray       # [T, Np]
    right: np.ndarray      # [T, Np]
    leaf_val: np.ndarray   # [T, Np] leaf value at leaf nodes, 0 else
    mask_lo: np.ndarray    # [T, W, Np] u16 low half-words of cat_mask
    mask_hi: np.ndarray    # [T, W, Np] u16 high half-words
    num_features: int      # F_all = Fn + Fc (unpadded)


def build_tables(forest) -> Optional[BankTables]:
    """Stacked forest arrays → padded kernel tables, or None outside
    the envelope."""
    f = {k: np.asarray(v) for k, v in forest.to_numpy().items()}
    if f["oblique_weights"].size > 0 or f["leaf_value"].shape[-1] != 1:
        return None
    if f.get("vs_anchor") is not None and f["vs_anchor"].size > 0:
        return None
    if f["is_set"][~f["is_leaf"]].any():
        return None
    T, N = f["feature"].shape
    W = int(f["cat_mask"].shape[-1])
    Np = _round_up(max(N, 1), 128)

    def pad(a, dtype=np.float32):
        out = np.zeros((T, Np), dtype)
        out[:, :N] = a
        return out

    # The x-column a node reads: numerical ids index x_num, categorical
    # ids already point at their x_all column (global feature id =
    # Fn + cat column). Clip once on the host like the oracle's gather.
    feat = np.maximum(f["feature"], 0)
    mask = np.asarray(f["cat_mask"], np.uint32)  # [T, N, W]
    mask_lo = (mask & 0xFFFF).astype(np.float32)
    mask_hi = (mask >> 16).astype(np.float32)
    mlo = np.zeros((T, W, Np), np.float32)
    mhi = np.zeros((T, W, Np), np.float32)
    mlo[:, :, :N] = np.transpose(mask_lo, (0, 2, 1))
    mhi[:, :, :N] = np.transpose(mask_hi, (0, 2, 1))
    return BankTables(
        feat_col=pad(feat.astype(np.float32)),
        thresh=pad(np.asarray(f["threshold"], np.float32)),
        is_cat=pad(f["is_cat"].astype(np.float32)),
        is_leaf=pad(f["is_leaf"].astype(np.float32)),
        left=pad(f["left"].astype(np.float32)),
        right=pad(f["right"].astype(np.float32)),
        leaf_val=pad(
            np.where(
                f["is_leaf"], f["leaf_value"][..., 0], 0.0
            ).astype(np.float32)
        ),
        mask_lo=mlo,
        mask_hi=mhi,
        num_features=0,  # filled by the engine (needs the binner)
    )


def _bank_kernel(
    x_ref,       # [BN, Fp] f32 example block (numericals + cat codes)
    featc_ref,   # [T, Np]
    thresh_ref,  # [T, Np]
    iscat_ref,   # [T, Np]
    isleaf_ref,  # [T, Np]
    left_ref,    # [T, Np]
    right_ref,   # [T, Np]
    leafv_ref,   # [T, Np]
    mlo_ref,     # [T, W, Np]
    mhi_ref,     # [T, W, Np]
    out_ref,     # [1, BN]
    *, T: int, Np: int, W: int, max_depth: int,
):
    BN = x_ref.shape[0]
    iota_np = jax.lax.broadcasted_iota(i32, (BN, Np), 1)
    iota_f = jax.lax.broadcasted_iota(i32, x_ref.shape, 1)
    x = x_ref[...]

    def tree_body(t, acc):
        def gather(row, sel):
            # One-hot masked reduction: exactly one lane contributes
            # (v * 1), the rest multiply to exact zeros — bit-exact for
            # any f32 payload, any reduction order.
            return jnp.sum(sel * row[None, :], axis=1)

        def depth_body(_, node):
            sel = (node[:, None] == iota_np).astype(f32)  # [BN, Np]
            feat = gather(featc_ref[t, :], sel).astype(i32)
            thr = gather(thresh_ref[t, :], sel)
            is_cat = gather(iscat_ref[t, :], sel) > 0.5
            is_leaf = gather(isleaf_ref[t, :], sel) > 0.5
            left = gather(left_ref[t, :], sel).astype(i32)
            right = gather(right_ref[t, :], sel).astype(i32)
            selF = feat[:, None] == iota_f
            v = jnp.sum(jnp.where(selF, x, 0.0), axis=1)  # [BN]
            # Categorical contains: the mask word clamps like the
            # oracle's take_along_axis (unpack_mask_bit), the bit index
            # uses the raw low 5 bits.
            c = jnp.maximum(v.astype(i32), 0)
            weff = jnp.minimum(c >> 5, W - 1)
            idx = c & 31
            word16 = jnp.zeros((BN,), i32)
            for w in range(W):  # static unroll (W is small)
                lo_w = gather(mlo_ref[t, w, :], sel)
                hi_w = gather(mhi_ref[t, w, :], sel)
                half = jnp.where(idx < 16, lo_w, hi_w).astype(i32)
                word16 = jnp.where(weff == w, half, word16)
            shift = jnp.where(idx < 16, idx, idx - 16)
            bit = (word16 >> shift) & 1
            # Not a select between two bool vectors: Mosaic has no
            # i8 -> i1 truncation for its result.
            go_left = (is_cat & (bit == 1)) | (~is_cat & (v < thr))
            nxt = jnp.where(go_left, left, right)
            return jnp.where(is_leaf, node, nxt)

        node = jax.lax.fori_loop(
            0, max_depth, depth_body, jnp.zeros((BN,), i32)
        )
        sel = (node[:, None] == iota_np).astype(f32)
        return acc + gather(leafv_ref[t, :], sel)

    out_ref[...] = jax.lax.fori_loop(
        0, T, tree_body, jnp.zeros((BN,), f32)
    )[None, :]


class PallasBankEngine:
    """Callable engine: x_num f32 [n, Fn] (+ x_cat i32 [n, Fc]) → raw
    scores [n] — the QuickScorerEngine calling contract over the
    data-bank walk. Categorical codes ride the float example block
    (vocab indices < 2^24 are exact in f32)."""

    def __init__(self, tables: BankTables, num_numerical: int,
                 max_depth: int, block_examples: int = 256,
                 interpret: bool = False):
        self.tables = tables
        self.num_numerical = num_numerical
        self.max_depth = max_depth
        self.block = block_examples
        self.interpret = interpret

    def __call__(self, x_num, x_cat=None) -> jnp.ndarray:
        from ydf_tpu.utils import telemetry

        if telemetry.ENABLED:
            import time

            t0 = time.perf_counter_ns()
            out = self._score(x_num, x_cat)
            out.block_until_ready()
            telemetry.histogram(
                "ydf_serve_kernel_latency_ns", engine="PallasBank",
                batch_pow2=telemetry.pow2_bucket(int(out.shape[0])),
            ).observe_ns(time.perf_counter_ns() - t0)
            return out
        return self._score(x_num, x_cat)

    def _score(self, x_num, x_cat=None) -> jnp.ndarray:
        tb = self.tables
        x_all = jnp.asarray(x_num, f32)
        if x_cat is not None and np.shape(x_cat)[1] > 0:
            x_all = jnp.concatenate(
                [x_all, jnp.asarray(x_cat, f32)], axis=1
            )
        if int(x_all.shape[1]) < tb.num_features:
            raise ValueError(
                f"model reads {tb.num_features} feature columns but only "
                f"{int(x_all.shape[1])} were provided — pass x_cat when "
                "the model contains categorical conditions"
            )
        n = x_all.shape[0]
        BN = self.block
        T, Np = tb.feat_col.shape
        W = tb.mask_lo.shape[1]
        Fp = _round_up(max(int(x_all.shape[1]), 1), 128)
        x_pad = jnp.pad(
            x_all,
            ((0, (-n) % BN), (0, Fp - int(x_all.shape[1]))),
        )
        n_pad = x_pad.shape[0]

        kernel = functools.partial(
            _bank_kernel, T=T, Np=Np, W=W, max_depth=self.max_depth
        )
        full = lambda i: (0, 0)
        full3 = lambda i: (0, 0, 0)
        out = pl.pallas_call(
            kernel,
            grid=(n_pad // BN,),
            in_specs=[
                pl.BlockSpec((BN, Fp), lambda i: (i, 0)),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, Np), full),
                pl.BlockSpec((T, W, Np), full3),
                pl.BlockSpec((T, W, Np), full3),
            ],
            # A 2-D row block: Mosaic tiles a rank-1 (BN,) block as
            # T(BN), XLA lays the rank-1 array out as T(1024), and the
            # compiler refuses the mismatch.
            out_specs=pl.BlockSpec((1, BN), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, n_pad), f32),
            interpret=self.interpret,
        )(
            x_pad,
            jnp.asarray(tb.feat_col),
            jnp.asarray(tb.thresh),
            jnp.asarray(tb.is_cat),
            jnp.asarray(tb.is_leaf),
            jnp.asarray(tb.left),
            jnp.asarray(tb.right),
            jnp.asarray(tb.leaf_val),
            jnp.asarray(tb.mask_lo),
            jnp.asarray(tb.mask_hi),
        )
        return out[0, :n]


def in_envelope(model) -> bool:
    """PallasBank envelope: the native engine's gate minus oblique
    support (projections need the dense weight matrix, not the bank)."""
    from ydf_tpu.serving.native_serve import in_envelope as native_env

    return (
        native_env(model)
        and np.size(np.asarray(model.forest.oblique_weights)) == 0
    )


def build_pallas_scorer(model, interpret: Optional[bool] = None):
    """PallasBankEngine for a trained/imported model, or None outside
    the envelope — the registry's IsCompatible/build flow."""
    if not in_envelope(model):
        return None
    tables = build_tables(model.forest)
    if tables is None:
        return None
    tables = tables._replace(num_features=model.binner.num_scalar)
    if interpret is None:
        from ydf_tpu.config import is_tpu_backend

        interpret = not is_tpu_backend()
    return PallasBankEngine(
        tables, model.binner.num_numerical, model.max_depth,
        interpret=interpret,
    )
