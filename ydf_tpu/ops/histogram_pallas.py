"""Pallas/Mosaic histogram kernel — the TPU-native form of the training
hot loop.

The XLA `_histogram_matmul` impl (ops/histogram.py) expresses the
histogram as one-hot matmuls, but XLA materializes every one-hot operand
in HBM: ~[chunk, B] f32 per feature per layer, ≈17 TB of traffic per
tree at the bench shape — two orders of magnitude over the input
re-read floor, flipping the op from compute-bound to hopelessly
memory-bound. This kernel is the fix: one-hot tiles are BUILT IN VMEM
(a broadcasted-iota compare), fed straight to the MXU, and never touch
HBM. Traffic drops to the floor (bins + stats re-read per layer); the
roofline projection in BASELINE.md assumes exactly this kernel.

Layout: grid (feature_blocks, example_chunks), sequential on TPU, so
the output block for one feature slice stays resident in VMEM while the
example chunks sweep (accumulation across grid steps along the last
grid axis). Per step, for each (feature f, stat s) the kernel computes

    out[f, s] += onehot(bins[:, f])[C, B]^T  @  (slot_onehot * stats_s)[C, Lp]

an MXU dot with the example chunk C as the contraction dimension —
deep in the systolic array's efficient regime (C = 1024 by default).
The slot one-hot zero-fills trash rows (slot == L: inactive or padded
examples — and, under the grower's sibling-subtraction mode, every
larger-child row), which either land in a padded column (sliced off by
the wrapper) or outside the iota range entirely.

Sub-128-lane slot packing (ROADMAP item, PR 4): the dot's lane
dimension is the slot axis, and the MXU issues full 128-lane passes no
matter how few are live — so a sibling-subtraction layer with L = 32
live slots used to waste 3/4 of every pass ([B, C] @ [C, 128] with 96
dead lanes, once per stat column). When L <= 64 the kernel now packs
G = 128 // L STAT columns into one lane dimension (lane j = k·L + l
holds stat column g·G + k, slot l) and issues ceil(S/G) dots per
feature instead of S — at the bench shape (L = 32, S = 3, G >= 3) the
subtraction layers collapse to ONE full-width dot per feature, a 3x
MXU-issue reduction that finally realizes the slot-halving win on this
backend (the halved [L, F, B, S] output block and psum payload were
already real). Lane packing permutes lanes only — each output element
is the same [B, C] x [C, 128] contraction — so results stay
bit-identical to the unpacked path. Layers with L > 64 keep the
original per-stat dots.

Operand precision follows stats.dtype (the quantized-gradient pipeline
in ops/histogram.py hands this kernel the already-split/quantized
operand):

  * f32 — exact, bit-faithful parity with the segment oracle. Mosaic
    decomposes each f32 MXU dot into 3 bf16 passes (hi·hi + hi·lo +
    lo·hi), so this is the SLOW reference precision.
  * bf16 (the "bf16x2" mode's hi/residual halves, S doubled by the
    wrapper) — one-hot and slot one-hot are EXACT in bf16 (0/1), so
    every dot runs as a single native-bf16 MXU pass with f32
    accumulation: 2 passes per original stat column vs f32's 3.
  * int8 (the "int8" mode's quantized stats) — both operands are int8
    tiles (2× the bf16 issue rate on v5+ MXUs) contracting into an
    int32 accumulator. EXACT: products ≤ 127, per-chunk sums ≤
    C·127 ≪ 2^31, cross-chunk accumulation in int32. The wrapper
    dequantizes once after the reduction.

Reference counterpart: the per-(node, feature) bucket-fill scan loops
`ydf/learner/decision_tree/splitter_scanner.h:860,933` — one linear
pass per open node per feature on CPU; here the whole layer's
(nodes x features x bins) histogram is a batch of dense contractions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _hist_kernel(
    bins_ref, slot_ref, stats_ref, out_ref, *, Fb, S, B, Lp, op_dtype,
    acc_dtype,
):
    """One (feature-block, example-chunk) grid step.

    Everything rides an example-minor [*, C] layout so the chunk C is the
    (128-divisible) lane dimension of every block and the contraction
    dimension of every dot — Mosaic's block rules want the last two dims
    (8, 128)-divisible or full.

    bins_ref  [Fb, C] int32         feature bin ids for this chunk/block
    slot_ref  [1, C]  int32         frontier slot; >= L = inactive/pad
    stats_ref [S, C]  op_dtype      per-example statistics (f32 exact,
                                    bf16 halves, or int8 quantized)
    out_ref   [Fb, S, B, Lp] acc_dtype  accumulated across the chunk axis
    """
    c_step = pl.program_id(1)

    @pl.when(c_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    C = bins_ref.shape[1]
    slot_ohT = (
        slot_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (Lp, C), 0)
    ).astype(op_dtype)  # [Lp, C]; trash rows all-zero or padded-row
    biotaT = jax.lax.broadcasted_iota(jnp.int32, (B, C), 0)
    for f in range(Fb):
        ohT = (bins_ref[f : f + 1, :] == biotaT).astype(op_dtype)  # [B,C]
        for s in range(S):
            # one-hot × stat product is exact in every op_dtype (the
            # one-hot factor is 0/1); int8 keeps |values| ≤ 127.
            aT = slot_ohT * stats_ref[s : s + 1, :]  # [Lp, C]
            h = jax.lax.dot_general(
                ohT, aT, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype,
            )  # [B, Lp]
            out_ref[f, s, :, :] += h


def _hist_kernel_packed(
    bins_ref, slot_ref, stats_ref, out_ref, *, Fb, S, B, L, G, Sg,
    op_dtype, acc_dtype,
):
    """Slot-packed variant for L <= 64 live slots: lane j = k·L + l of
    group g carries (stat column g·G + k, slot l), so one [B, C] @
    [C, 128] dot covers G stat columns at full lane utilization instead
    of G dots with 128 − L dead lanes each (module docstring).

    out_ref [Fb, Sg, B, 128]; the wrapper unpacks lanes back to
    [L, F, B, S]. Trash rows (slot == L) match no packed lane — block
    k's lanes only accept slot values in [0, L).
    """
    c_step = pl.program_id(1)

    @pl.when(c_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    C = bins_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (128, C), 0)
    slot_b = slot_ref[...]  # [1, C] broadcasts against [128, C]
    zero = jnp.zeros((), op_dtype)
    biotaT = jax.lax.broadcasted_iota(jnp.int32, (B, C), 0)
    for f in range(Fb):
        ohT = (bins_ref[f : f + 1, :] == biotaT).astype(op_dtype)  # [B,C]
        for g in range(Sg):
            # aT[k·L + l, c] = stats[g·G + k, c] when slot[c] == l (and
            # the column exists), else 0 — the select keeps the product
            # exact in every op_dtype, including int8.
            aT = None
            for k in range(G):
                s = g * G + k
                if s >= S:
                    break
                # Upper bound is load-bearing: without it lane (k+1)·L
                # would satisfy lane − k·L == L and absorb block k's
                # TRASH rows into the next block's slot-0 lane. The
                # lower bound is implicit (slot >= 0 never equals a
                # negative lane − k·L).
                m = (slot_b == (lane - k * L)) & (lane < (k + 1) * L)
                part = jnp.where(m, stats_ref[s : s + 1, :], zero)
                aT = part if aT is None else aT + part
            h = jax.lax.dot_general(
                ohT, aT, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype,
            )  # [B, 128]
            out_ref[f, g, :, :] += h


def _hist_routed_kernel(
    binsb_ref, binsf_ref, slot_ref, leaf_ref, setgl_ref, tabs_ref,
    glbT_ref, stats_ref, out_ref, nslot_ref, nleaf_ref, *,
    Fb, Fp, S, B, Lhp, L1p, L, op_dtype, acc_dtype,
):
    """Fused previous-layer routing + this-layer histogram — the Pallas
    mirror of the native `SlotFn` fusion seam (routing_native
    histogram_routed / docs/row_routing.md): each example's histogram
    slot is computed IN-REGISTER from the previous layer's decision
    tables and consumed by the accumulation dots in the same grid step,
    so the per-layer hist_slot array never touches HBM and the bin
    matrix — loaded once for the contraction — is the only per-example
    traffic. Everything a row gather would need becomes a one-hot MXU
    contraction (gathers don't vectorize on the VPU; one-hot dots are
    what the MXU is for):

      slot_oh [L1p, C]   one-hot of the PREVIOUS frontier slot
      T = tabs @ slot_oh  [Kp, C]  every per-slot table row gathered at
                          once (do_split, route_f, left/right ids,
                          split_rank, is_set, and the PRE-COMPOSED next
                          hist slots hmap[2r] / hmap[2r+1] / hmap[L] —
                          composing hmap into the table is what removes
                          any gather by NEW slot)
      b_sel  [1, C]      the routed feature's bin via a feature one-hot
                         row-select over the full bin block
      M = glbT @ slot_oh [B, C]    each example's slot's go-left row;
                          the bin one-hot then selects M[bin_e]

    All table values (ids <= N, bins < B, slots <= L) are exact in f32
    and every contraction has exactly one non-zero term per output
    (one-hot factor), so the routing is EXACT — bit-identical to the
    XLA gather chain in ops/grower.py — independent of op_dtype; only
    the histogram dots follow stats.dtype (module docstring).

    binsb_ref [Fb, C]  this feature block's bins (histogram operand)
    binsf_ref [Fp, C]  ALL features' bins (routing needs any column)
    slot_ref  [1, C]   previous-layer slot; L = trash
    leaf_ref  [1, C]   current leaf ids
    setgl_ref [1, C]   per-example set-split go-left (u8-as-i32)
    tabs_ref  [Kp, L1p] packed f32 decision tables (rows above)
    glbT_ref  [B, L1p] go_left_bins transposed
    stats_ref [S, C]
    out_ref   [Fb, S, B, Lhp]; nslot/nleaf [1, C] i32 — written
    identically at every feature-block step (the grid revisits these
    blocks once per block; full idempotent rewrites keep every visit's
    store correct).
    """
    c_step = pl.program_id(1)

    @pl.when(c_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    f32 = jnp.float32
    C = binsb_ref.shape[1]
    slot_oh = (
        slot_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (L1p, C), 0)
    ).astype(f32)  # [L1p, C]
    T = jax.lax.dot_general(
        tabs_ref[...], slot_oh, (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
    )  # [Kp, C]: every table row gathered by previous slot at once
    split_e = T[0:1, :] > 0.0
    rf_e = T[1:2, :]
    left_e, right_e = T[2:3, :], T[3:4, :]
    sr_e = T[4:5, :]
    isset_e = T[5:6, :] > 0.0
    hl_e, hr_e, trash_e = T[6:7, :], T[7:8, :], T[8:9, :]

    # The routed feature's bin: one-hot row select over the FULL block
    # (route_f may name any feature, not just this histogram block's).
    fio = jax.lax.broadcasted_iota(jnp.int32, (Fp, C), 0).astype(f32)
    feat_oh = (rf_e == fio).astype(f32)  # [Fp, C]
    b_sel = jnp.sum(
        feat_oh * binsf_ref[...].astype(f32), axis=0, keepdims=True
    )  # [1, C] — exact: one non-zero term, bins < B <= 256

    # Go-left: gather each slot's per-bin row, then select the bin.
    M = jax.lax.dot_general(
        glbT_ref[...], slot_oh, (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
    )  # [B, C]
    bio_f = jax.lax.broadcasted_iota(jnp.int32, (B, C), 0).astype(f32)
    b_oh = (b_sel == bio_f).astype(f32)
    gl = jnp.sum(b_oh * M, axis=0, keepdims=True) > 0.0  # [1, C]
    gl = jnp.where(isset_e, setgl_ref[...] > 0, gl)

    new_slot = jnp.where(
        split_e, 2.0 * sr_e + jnp.where(gl, 0.0, 1.0), float(L)
    )
    new_leaf = jnp.where(
        split_e, jnp.where(gl, left_e, right_e),
        leaf_ref[...].astype(f32),
    )
    hist_slot = jnp.where(split_e, jnp.where(gl, hl_e, hr_e), trash_e)
    nslot_ref[...] = new_slot.astype(jnp.int32)
    nleaf_ref[...] = new_leaf.astype(jnp.int32)

    # This layer's histogram from the in-register hist slot — identical
    # accumulation to _hist_kernel.
    hs = hist_slot.astype(jnp.int32)  # [1, C]
    hslot_ohT = (
        hs == jax.lax.broadcasted_iota(jnp.int32, (Lhp, C), 0)
    ).astype(op_dtype)  # [Lhp, C]; trash lanes sliced off by the wrapper
    biotaT = jax.lax.broadcasted_iota(jnp.int32, (B, C), 0)
    for f in range(Fb):
        ohT = (binsb_ref[f : f + 1, :] == biotaT).astype(op_dtype)
        for s in range(S):
            aT = hslot_ohT * stats_ref[s : s + 1, :]
            h = jax.lax.dot_general(
                ohT, aT, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype,
            )  # [B, Lhp]
            out_ref[f, s, :, :] += h


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_slots", "num_bins", "chunk", "feature_block", "interpret"
    ),
)
def histogram_routed_pallas(
    bins: jax.Array,         # int-like [n, F]
    slot: jax.Array,         # int32 [n], previous-layer slot; L = trash
    leaf_id: jax.Array,      # int32 [n]
    do_split: jax.Array,     # bool/u8 [L+1]
    route_f: jax.Array,      # int32 [L+1]
    go_left: jax.Array,      # bool/u8 [L+1, B]
    left_id: jax.Array,      # int32 [L+1]
    right_id: jax.Array,     # int32 [L+1]
    split_rank: jax.Array,   # int32 [L+1]
    hmap: jax.Array,         # int32 [L+1] (identity when subtraction off)
    is_set: jax.Array,       # bool/u8 [L+1]
    set_go_left: jax.Array,  # u8 [n] (or [1] when no set features)
    stats: jax.Array,        # f32 [n, S] / bf16 [n, 2S] / int8 [n, S]
    *,
    num_slots: int,
    num_bins: int = 256,
    quant_scale: jax.Array | None = None,
    chunk: int = 1024,
    feature_block: int | None = None,
    interpret: bool = False,
):
    """Fused route+histogram, Pallas/Mosaic backend — same contract as
    routing_native.histogram_routed: applies the PREVIOUS layer's splits
    per example and accumulates THIS layer's [num_slots, F, num_bins, S]
    histogram from the resulting hist slot in one pass. Returns
    (hist f32 — dequantized/refolded like ops/histogram.py —, new_slot
    [n] i32, new_leaf [n] i32). Table arrays follow route_update's
    padded [L+1] contract. stats.dtype selects the histogram precision
    (f32 exact / bf16x2 halves / int8+quant_scale); routing is exact in
    every mode."""
    n, F = bins.shape
    Sq = stats.shape[1]
    L1 = do_split.shape[0]
    L = L1 - 1
    Lh, B = num_slots, num_bins
    f32, i32 = jnp.float32, jnp.int32
    Lhp = _round_up(max(Lh, 1), 128)
    L1p = _round_up(L1, 128)

    if stats.dtype == jnp.bfloat16:
        op_dtype, acc_dtype = jnp.bfloat16, jnp.float32
    elif jnp.issubdtype(stats.dtype, jnp.integer):
        if quant_scale is None:
            raise ValueError("int8 fused histogram requires quant_scale")
        op_dtype, acc_dtype = jnp.int8, jnp.int32
    else:
        op_dtype, acc_dtype = jnp.float32, jnp.float32

    # Packed decision tables, one f32 row per table (kernel docstring).
    # hmap is composed HERE — rows 6..8 carry the next hist slot for
    # go-left / go-right / no-split, so the kernel never gathers by new
    # slot. Every value (ids <= N <= 2^24, slots, bins) is f32-exact.
    sr_i = split_rank.astype(i32)
    hl = hmap[jnp.clip(2 * sr_i, 0, L)]
    hr = hmap[jnp.clip(2 * sr_i + 1, 0, L)]
    tabs = jnp.stack(
        [
            do_split.astype(f32),
            route_f.astype(f32),
            left_id.astype(f32),
            right_id.astype(f32),
            split_rank.astype(f32),
            is_set.astype(f32),
            hl.astype(f32),
            hr.astype(f32),
            jnp.broadcast_to(hmap[L].astype(f32), (L1,)),
        ]
    )  # [9, L1]
    Kp = 16  # sublane-pad the 9 table rows (f32 tiles want 8k rows)
    tabs = jnp.pad(tabs, ((0, Kp - tabs.shape[0]), (0, L1p - L1)))
    glbT = jnp.pad(
        go_left.astype(f32).T, ((0, 0), (0, L1p - L1))
    )  # [B, L1p]

    set_gl = (
        set_go_left.astype(i32)
        if set_go_left.shape[0] == n
        else jnp.zeros((n,), i32)
    )

    if feature_block is None:
        # Keep the resident output block around ~6 MB of VMEM.
        per_f = Sq * B * Lhp * 4
        feature_block = max(1, min(F, (6 << 20) // max(per_f, 1)))
    Fb = feature_block
    Fp = _round_up(F, Fb)
    n_pad = _round_up(max(n, 1), chunk)

    bins_i = bins.astype(i32)
    leaf_i = leaf_id.astype(i32)
    slot_i = slot.astype(i32)
    if Fp != F:
        bins_i = jnp.pad(bins_i, ((0, 0), (0, Fp - F)))
    if n_pad != n:
        bins_i = jnp.pad(bins_i, ((0, n_pad - n), (0, 0)))
        # Padded examples ride the trash path: slot L never splits
        # (do_split pads False), their hist slot is hmap[L] (>= Lh, in
        # the sliced lanes), and their zero stats contribute nothing.
        slot_i = jnp.pad(slot_i, (0, n_pad - n), constant_values=L)
        leaf_i = jnp.pad(leaf_i, (0, n_pad - n))
        set_gl = jnp.pad(set_gl, (0, n_pad - n))
        stats = jnp.pad(stats, ((0, n_pad - n), (0, 0)))

    kernel = functools.partial(
        _hist_routed_kernel, Fb=Fb, Fp=Fp, S=Sq, B=B, Lhp=Lhp, L1p=L1p,
        L=L, op_dtype=op_dtype, acc_dtype=acc_dtype,
    )
    grid = (Fp // Fb, n_pad // chunk)
    hist, new_slot, new_leaf = pl.pallas_call(
        kernel,
        name="ydf_hist_routed",
        grid=grid,
        in_specs=[
            pl.BlockSpec((Fb, chunk), lambda fb, c: (fb, c)),
            pl.BlockSpec((Fp, chunk), lambda fb, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda fb, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda fb, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda fb, c: (0, c)),
            pl.BlockSpec((Kp, L1p), lambda fb, c: (0, 0)),
            pl.BlockSpec((B, L1p), lambda fb, c: (0, 0)),
            pl.BlockSpec((Sq, chunk), lambda fb, c: (0, c)),
        ],
        out_specs=[
            pl.BlockSpec((Fb, Sq, B, Lhp), lambda fb, c: (fb, 0, 0, 0)),
            pl.BlockSpec((1, chunk), lambda fb, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda fb, c: (0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Fp, Sq, B, Lhp), acc_dtype),
            jax.ShapeDtypeStruct((1, n_pad), i32),
            jax.ShapeDtypeStruct((1, n_pad), i32),
        ],
        interpret=interpret,
    )(
        bins_i.T,
        bins_i.T,
        slot_i[None, :],
        leaf_i[None, :],
        set_gl[None, :],
        tabs,
        glbT,
        stats.astype(op_dtype).T,
    )

    # [Fp, S, B, Lhp] -> [Lh, F, B, S], then the same dequantize/refold
    # as ops/histogram.py so every backend returns f32 histograms.
    out = jnp.transpose(hist[:F, :, :, :Lh], (3, 0, 2, 1))
    if stats.dtype == jnp.bfloat16:
        S = Sq // 2
        out = out.astype(f32)
        out = out[..., :S] + out[..., S:]
    elif jnp.issubdtype(stats.dtype, jnp.integer):
        out = out.astype(f32) * quant_scale[None, None, None, :]
    else:
        out = out.astype(f32)
    return out, new_slot[0, :n], new_leaf[0, :n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_slots", "num_bins", "chunk", "feature_block", "interpret"
    ),
)
def histogram_pallas(
    bins: jax.Array,   # int-like [n, F]
    slot: jax.Array,   # int32 [n], L = trash
    stats: jax.Array,  # f32 [n, S]
    num_slots: int,
    num_bins: int = 256,
    chunk: int = 1024,
    feature_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns hist[num_slots, F, num_bins, S], same contract as
    ops/histogram.py:histogram."""
    n, F = bins.shape
    S = stats.shape[1]
    L, B = num_slots, num_bins
    Lp = _round_up(max(L, 1), 128)
    # Sub-128-lane slot packing (module docstring): when the live slot
    # count fits 2+ times into the 128-lane dim, pack G stat columns per
    # dot and issue Sg = ceil(S/G) dots per feature instead of S.
    G = min(S, 128 // max(L, 1)) if L >= 1 else 1
    packed = G >= 2
    Sg = -(-S // G) if packed else S

    # Operand/accumulator precision follows stats.dtype (see module
    # docstring): bf16 halves accumulate f32; int8 contracts into int32.
    if stats.dtype == jnp.bfloat16:
        op_dtype, acc_dtype = jnp.bfloat16, jnp.float32
    elif jnp.issubdtype(stats.dtype, jnp.integer):
        op_dtype, acc_dtype = jnp.int8, jnp.int32
    else:
        op_dtype, acc_dtype = jnp.float32, jnp.float32

    out_L = 128 if packed else Lp
    if feature_block is None:
        # Keep the resident output block around ~6 MB of VMEM.
        per_f = Sg * B * out_L * 4
        feature_block = max(1, min(F, (6 << 20) // max(per_f, 1)))
    Fb = feature_block
    Fp = _round_up(F, Fb)

    n_pad = _round_up(max(n, 1), chunk)
    bins_i = bins.astype(jnp.int32)
    if Fp != F:
        # Padded feature columns histogram garbage; sliced off below.
        bins_i = jnp.pad(bins_i, ((0, 0), (0, Fp - F)))
    if n_pad != n:
        bins_i = jnp.pad(bins_i, ((0, n_pad - n), (0, 0)))
        # Padded examples fall in the trash slot -> all-zero one-hot row
        # (or the sliced padded row when L < Lp; packed lanes never
        # match slot == L at all).
        slot = jnp.pad(slot, (0, n_pad - n), constant_values=L)
        stats = jnp.pad(stats, ((0, n_pad - n), (0, 0)))

    if packed:
        kernel = functools.partial(
            _hist_kernel_packed, Fb=Fb, S=S, B=B, L=L, G=G, Sg=Sg,
            op_dtype=op_dtype, acc_dtype=acc_dtype,
        )
    else:
        kernel = functools.partial(
            _hist_kernel, Fb=Fb, S=S, B=B, Lp=Lp, op_dtype=op_dtype,
            acc_dtype=acc_dtype,
        )
    grid = (Fp // Fb, n_pad // chunk)
    out = pl.pallas_call(
        kernel,
        name="ydf_hist",
        grid=grid,
        in_specs=[
            pl.BlockSpec((Fb, chunk), lambda fb, c: (fb, c)),
            pl.BlockSpec((1, chunk), lambda fb, c: (0, c)),
            pl.BlockSpec((S, chunk), lambda fb, c: (0, c)),
        ],
        out_specs=pl.BlockSpec(
            (Fb, Sg, B, out_L), lambda fb, c: (fb, 0, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((Fp, Sg, B, out_L), acc_dtype),
        interpret=interpret,
    )(
        bins_i.T,
        slot.astype(jnp.int32)[None, :],
        stats.astype(op_dtype).T,
    )

    if packed:
        # Unpack lanes: stat column s lives in group s // G at lane
        # offset (s % G)·L. [Fp, Sg, B, 128] -> [L, F, B, S].
        cols = []
        for s in range(S):
            g, k = divmod(s, G)
            cols.append(out[:F, g, :, k * L : k * L + L])  # [F, B, L]
        return jnp.transpose(jnp.stack(cols, axis=0), (3, 1, 2, 0))

    # [Fp, S, B, Lp] -> [L, F, B, S]
    return jnp.transpose(out[:F, :, :, :L], (3, 0, 2, 1))
