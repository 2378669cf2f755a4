"""Layer-synchronous, fully-batched decision-tree grower.

This replaces the reference's depth-first recursive trainer
(`ydf/learner/decision_tree/training.cc:4739` DecisionTreeTrain →
GrowTreeLocal `:5132`, with its per-(node,feature) CPU work queue
`:1483`) with the breadth-first formulation the reference itself uses for
distributed training (`ydf/learner/distributed_decision_tree/training.h:
104-143`) — the formulation that maps onto XLA:

  per layer:  histogram  →  prefix-scan gains  →  per-node argmax
              →  allocate children  →  re-route examples

Everything is static-shaped: the frontier (nodes that may still split) is a
fixed array of `L` slots; node storage has fixed capacity `N`; examples carry
an int32 frontier-slot (L = retired). The whole tree build is one jittable
function — no host round-trips, no dynamic shapes, scan/fori friendly, and
identical code runs single-chip or under shard_map (the histogram then gets a
psum over the data axis; see ydf_tpu/parallel/).

Tree node layout (struct-of-arrays, capacity N, BFS allocation order):
  feature[N]        split feature, -1 for leaves
  threshold_bin[N]  numerical split: bin <= t goes left
                    categorical split: cut rank in the sorted-bin order
  is_cat[N]         categorical split?
  cat_mask[N, W]    uint32 bitmask over bins; bit set → bin goes left
  left/right[N]     child node ids
  is_leaf[N]
  leaf_stats[N, S]  split-rule statistics of the node's examples
  num_nodes         scalar
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.ops.histogram import histogram
from ydf_tpu.ops import lookup
from ydf_tpu.ops.lookup import lookup_mask_bit, lookup_small, pick_column


# float32 holds every whole number below this, and no count past it: a
# node of this many rows or more has sums that round (layer_decide), and
# a categorical cell of that size does too (grow_tree's direct layers).
EXACT_ROWS = 1 << 24


class TreeArrays(NamedTuple):
    feature: jax.Array
    threshold_bin: jax.Array
    is_cat: jax.Array
    # Categorical-set split (reference Contains conditions,
    # decision_tree.proto:98-108): cat_mask bit v set → item v is in the
    # selected subset; an example whose set INTERSECTS the subset goes
    # RIGHT (the reference's positive branch). is_cat and is_set are
    # mutually exclusive.
    is_set: jax.Array
    cat_mask: jax.Array
    left: jax.Array
    right: jax.Array
    is_leaf: jax.Array
    leaf_stats: jax.Array
    num_nodes: jax.Array


class GrowResult(NamedTuple):
    tree: TreeArrays
    leaf_id: jax.Array  # int32 [n]: leaf node id of every example


def _pack_mask(mask: jax.Array) -> jax.Array:
    """bool [..., B] → uint32 [..., B//32] bitmask."""
    b = mask.shape[-1]
    w = (b + 31) // 32
    m = mask.reshape(*mask.shape[:-1], w, 32).astype(jnp.uint32)
    shifts = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(m * shifts, axis=-1, dtype=jnp.uint32)


def unpack_mask_bit(packed: jax.Array, bit: jax.Array) -> jax.Array:
    """packed [..., W] uint32, bit [...] int → bool []."""
    word = jnp.take_along_axis(
        packed, (bit >> 5)[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return ((word >> (bit.astype(jnp.uint32) & 31)) & 1).astype(jnp.bool_)


# --------------------------------------------------------------------- #
# Split-search seam
#
# The per-layer split search is factored into standalone functions so the
# single-machine grower below and the feature-parallel distributed
# manager (ydf_tpu/parallel/dist_gbt.py) run the SAME gain/argmax/
# child-allocation code: the distributed manager assembles the layer
# histogram from per-worker feature slices and then calls exactly these
# functions, so a distributed train chooses bit-identical splits to the
# single-machine build by construction. _grow_tree_jit calls them inline
# (traced into its one jitted program, unchanged ops); dist_gbt jits
# them per layer.
# --------------------------------------------------------------------- #


def prepare_stats_for_hist(stats, hist_quant: str):
    """Per-tree stats preparation shared by the grower and the
    distributed manager: returns (hist_stats, qscale, total) — the
    (possibly quantized/split) histogram operand, the int8 per-tree
    scale (None otherwise), and the root stat totals [S] on the SAME
    grid every layer's histograms will sum (see the per-tree-scale
    design note at the call site in _grow_tree_jit)."""
    f32 = jnp.float32
    if hist_quant == "int8":
        qscale = jnp.max(jnp.abs(stats), axis=0) / 127.0
        qscale = jnp.maximum(
            qscale.astype(f32), jnp.finfo(jnp.float32).tiny
        )
        qscale = jnp.exp2(jnp.ceil(jnp.log2(qscale)))
        # Multiply by the exact pow2 reciprocal (≡ divide, bit for bit).
        stats_q = jnp.clip(
            jnp.round(stats * (1.0 / qscale)[None, :]), -127.0, 127.0
        )
        total = jnp.sum(stats_q, axis=0) * qscale  # [S] dequantized
        hist_stats = stats_q.astype(jnp.int8)
    elif hist_quant == "bf16x2":
        qscale = None
        total = jnp.sum(stats, axis=0)  # [S]
        s_hi = stats.astype(jnp.bfloat16)
        s_lo = (stats - s_hi.astype(f32)).astype(jnp.bfloat16)
        hist_stats = jnp.concatenate([s_hi, s_lo], axis=1)  # [n, 2S]
    else:
        qscale = None
        total = jnp.sum(stats, axis=0)  # [S]
        hist_stats = stats
    return hist_stats, qscale, total


@jax.named_scope("ydf.sibling")
def sibling_reconstruct(hist_small, parent_hist, small_is_left, Ld: int):
    """Sibling-subtraction reconstruction: the [Lh, F, B, S] histograms
    of the SMALLER children plus the carried parent histograms →
    the full [Ld, F, B, S] layer histogram (larger sibling = parent −
    child). Shared seam: the distributed manager reduces only the
    smaller-child slices from its workers and reconstructs here."""
    Lh = hist_small.shape[0]
    hist_big = parent_hist - hist_small
    sil = small_is_left[:, None, None, None, None]
    # Split s's children live at slots (2s, 2s+1) = (left, right).
    hist = jnp.where(
        sil,
        jnp.stack([hist_small, hist_big], axis=1),
        jnp.stack([hist_big, hist_small], axis=1),
    ).reshape(2 * Lh, *hist_small.shape[1:])
    if 2 * Lh < Ld:  # odd frontier cap: top slots never occupied
        hist = jnp.pad(
            hist, ((0, Ld - 2 * Lh),) + ((0, 0),) * (hist.ndim - 1)
        )
    return hist


@jax.named_scope("ydf.gain")
def scalar_candidates(hist, *, Fn: int, O: int, rule, rule_ctx):
    """Candidate left-stats for every cut of the scalar features:
    numerical prefix cumsums plus the sorted-order categorical prefixes
    (O orderings per categorical feature). Returns (left_all
    [Ld, Fn + Fc·O, B, S], ranks [Ld, Fc, O, B] or None, right_all):
    right_all[..., t, :] sums the cells past cut t themselves, for the
    nodes whose `parent - left` cannot be trusted (layer_decide)."""
    Ld, F, B, S = hist.shape
    Fc = F - Fn
    def sums_past(cells, axis):
        # [.., t, ..] = sum of cells t+1.. along `axis`: a cumsum from
        # the far end, moved down by one cut.
        back = jnp.flip(jnp.cumsum(jnp.flip(cells, axis), axis), axis)
        zero = jnp.zeros_like(jax.lax.slice_in_dim(back, 0, 1, axis=axis))
        return jnp.concatenate(
            [jax.lax.slice_in_dim(back, 1, None, axis=axis), zero], axis
        )

    csum_num = jnp.cumsum(hist[:, :Fn], axis=2)  # [Ld, Fn, B, S]
    past_num = sums_past(hist[:, :Fn], 2)
    if Fc == 0:
        return csum_num, None, past_num
    hist_cat = hist[:, Fn:]  # [Ld, Fc, B, S]
    with jax.named_scope("ydf.catsplit"):
        # O orderings per categorical feature (reference
        # FindSplitLabelClassificationFeatureCategorical,
        # training.cc:3933-3975: multiclass scans one sorted order PER
        # label class — "one label value vs others"); binary and
        # non-classification rules keep the single exact order. Each
        # ordering becomes its own candidate column.
        if O > 1:
            cat_key = rule.cat_sort_keys(hist_cat, rule_ctx)
        else:
            cat_key = rule.cat_sort_key(hist_cat, rule_ctx)[:, :, None]
        # [Ld, Fc, O, B]. Empty bins sort last → they land on the
        # right side, so unseen categories at serving time route right.
        cat_key = jnp.where(
            (hist_cat[..., -1] > 0)[:, :, None, :], cat_key, jnp.inf
        )
        order = jnp.argsort(cat_key, axis=-1)  # [Ld, Fc, O, B]
        ranks = jnp.argsort(order, axis=-1)    # rank of each bin
        sorted_hist = jnp.take_along_axis(
            hist_cat[:, :, None], order[..., None], axis=3
        )  # [Ld, Fc, O, B, S]
        csum_cat = jnp.cumsum(sorted_hist, axis=3).reshape(
            Ld, Fc * O, B, S
        )
        past_cat = sums_past(sorted_hist, 3).reshape(Ld, Fc * O, B, S)
    return (
        jnp.concatenate([csum_num, csum_cat], axis=1),
        ranks,
        jnp.concatenate([past_num, past_cat], axis=1),
    )


class LayerDecision(NamedTuple):
    """Output of layer_decide — everything a layer's split search
    determines: which frontier slots split, where the children live,
    the per-slot routing tables, and the node-array write payloads."""

    do_split: jax.Array      # bool [Ld]
    split_rank: jax.Array    # int32 [Ld] rank among this layer's splits
    wid: jax.Array           # int32 [Ld] node write index (N = trash)
    left_id: jax.Array       # int32 [Ld] child node ids (N = none)
    right_id: jax.Array
    best_t: jax.Array        # int32 [Ld] chosen cut
    best_f: jax.Array        # int32 [Ld] raw candidate-column index
    best_f_scalar: jax.Array  # collapsed onto the real scalar features
    best_f_store: jax.Array  # stored feature id (set ids offset by nvf)
    is_cat_split: jax.Array
    is_set_split: jax.Array
    fset: jax.Array          # real set-feature index (set splits)
    set_dir: jax.Array       # False = ascending order column
    route_f: jax.Array       # int32 [Ld] bins column the routing gathers
    go_left_bins: jax.Array  # bool [Ld, B] per-bin left decision
    store_mask: jax.Array    # bool [Ld, 32·W] stored cat/set mask bits
    left_stats: jax.Array    # f32 [Ld, S] chosen-cut child stats
    right_stats: jax.Array
    num_nodes: jax.Array     # updated node count


@jax.named_scope("ydf.gain")
def layer_decide(
    left_all, ranks, sranks_dirs, parent, active, nid, num_nodes,
    k_gain, k_feat, dirs, rule_ctx=None, *,
    rule, L: int, B: int, N: int, Fn: int, Fc: int, O: int, Fs: int,
    W: int, min_examples: int, min_split_gain: float,
    candidate_features: int, num_valid_features, children_in_frontier,
    right_scalar,
):
    """One layer's split search: gain → validity/sampling masks →
    per-slot argmax → frontier-overflow cap → child allocation → chosen
    stats + routing tables. Pure function of its inputs; shared by the
    single-machine grower (traced into its program) and the distributed
    manager's reduction (jitted per layer over the histogram assembled
    from worker feature slices). `right_scalar` is scalar_candidates'
    third value, None where the layer has no scalar feature."""
    i32 = jnp.int32
    Ld = left_all.shape[0]
    F = Fn + Fc
    Fcand = Fn + Fc * O
    cut_ids = jnp.arange(B, dtype=i32)

    Fa = Fcand + 2 * Fs  # total candidate columns
    right_all = parent[:, None, None, :] - left_all  # [Ld, Fa, B, S]
    if right_scalar is not None:
        # A node of 2**24 rows or more: f32 no longer holds every whole
        # number, the prefix sums round by more than a row, and
        # `parent - left` carries the rounding of both. Where the right
        # side of a cut is empty that difference can read 6 rows of
        # nothing, pass min_examples, and win on a gain of g^2 / h over
        # two residues (a leaf of 2.6e11 on the chip, PERF.md section
        # 6); where it is small, its sums are mostly the parent's error,
        # and every `parent - left` below hands that on. The cells past
        # the cut, summed themselves (scalar_candidates), are exact for
        # counts below 2**24 and as good as the side is small. Below
        # 2**24 rows the old form stays, bit for bit; so do set splits,
        # whose sides are no union of cells.
        large = (parent[:, -1] >= float(EXACT_ROWS))[:, None, None, None]
        Fr = right_scalar.shape[1]
        right_all = jnp.concatenate(
            [
                jnp.where(large, right_scalar, right_all[:, :Fr]),
                right_all[:, Fr:],
            ],
            axis=1,
        )

    gain = rule.gain(left_all, right_all, parent[:, None, None, :],
                     k_gain, rule_ctx)  # [Ld, F, B]

    valid = (
        (left_all[..., -1] >= min_examples)
        & (right_all[..., -1] >= min_examples)
        & active[:, None, None]
    )
    if hasattr(rule, "split_valid"):
        # Rule-specific validity (e.g. uplift's per-treatment-arm
        # minimum example counts).
        valid &= rule.split_valid(left_all, right_all)
    if candidate_features > 0 and candidate_features < F + Fs:
        # Exact per-node sampling of `candidate_features` features
        # without replacement (reference: per-node attribute sampling,
        # ydf/learner/decision_tree/training.cc FindBestCondition).
        # Each set feature is ONE candidate — its two direction
        # columns share a score.
        base = jax.random.uniform(k_feat, (Ld, F + Fs))
        if num_valid_features is not None and num_valid_features < F:
            # Constant-zero pad columns (feature-parallel padding) must
            # not consume sample slots — they'd dilute the real
            # candidate set relative to the unpadded configuration.
            # Set features (always real) keep their scores.
            col_real = jnp.concatenate(
                [
                    jnp.arange(F) < num_valid_features,
                    jnp.ones((Fs,), jnp.bool_),
                ]
            )
            base = jnp.where(col_real, base, -1.0)
        kth = jax.lax.top_k(base, candidate_features)[0][:, -1]
        # Expand per-FEATURE scores onto candidate columns: the O
        # orderings of one categorical (and a set feature's two
        # direction columns) share a single sampling score.
        scores = jnp.concatenate(
            [
                base[:, :Fn],
                jnp.repeat(base[:, Fn:F], O, axis=1),
                base[:, F:],
                base[:, F:],
            ],
            axis=1,
        ) if (Fs or O > 1) else base
        valid &= (scores >= kth[:, None])[:, :, None]
    if dirs is not None:
        leaf_l = rule.leaf_value(left_all, rule_ctx)[..., 0]
        leaf_r = rule.leaf_value(right_all, rule_ctx)[..., 0]
        mono_ok = (dirs[None, :, None] == 0) | (
            dirs[None, :, None] * (leaf_r - leaf_l) >= 0
        )
        valid &= mono_ok
    gain = jnp.where(valid, gain, -jnp.inf)

    # ---- best cut per frontier slot --------------------------------- #
    flat = gain.reshape(Ld, Fa * B)
    best_idx = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best_idx[:, None], 1)[:, 0]
    best_f = (best_idx // B).astype(i32)
    best_t = (best_idx % B).astype(i32)

    do_split = active & jnp.isfinite(best_gain) & (best_gain > min_split_gain)
    if children_in_frontier and 2 * Ld > L:
        # Frontier overflow: keep the top-L/2 splits by gain, the rest
        # become leaves (breadth-first analogue of the reference's
        # best-first growth cap, training.cc:4580).
        order_by_gain = jnp.argsort(
            jnp.where(do_split, -best_gain, jnp.inf)
        )
        rank_by_gain = jnp.argsort(order_by_gain)
        do_split &= rank_by_gain < (L // 2)

    # ---- allocate children ------------------------------------------ #
    # Node-capacity guard: children that would not fit in N become
    # leaves. The masked-out slots form a suffix in cumsum order, so
    # ranks of surviving slots are unchanged.
    rank0 = jnp.cumsum(do_split.astype(i32)) - 1
    do_split &= num_nodes + 2 * (rank0 + 1) <= N
    split_rank = jnp.cumsum(do_split.astype(i32)) - 1  # [Ld]
    wid = jnp.where(do_split, nid, N)  # write index (trash when no split)
    left_id = jnp.where(do_split, num_nodes + 2 * split_rank, N)
    right_id = jnp.where(do_split, left_id + 1, N)

    # Left-stats of the chosen cut (gather from the candidate cumsums).
    chosen = jnp.take_along_axis(
        left_all, best_f[:, None, None, None], axis=1
    )[:, 0]  # [Ld, B, S]
    left_stats = jnp.take_along_axis(
        chosen, best_t[:, None, None], axis=1
    )[:, 0]  # [Ld, S]
    right_stats = jnp.take_along_axis(
        jnp.take_along_axis(
            right_all, best_f[:, None, None, None], axis=1
        )[:, 0],
        best_t[:, None, None], axis=1,
    )[:, 0]  # [Ld, S]: parent - left_stats, or the cells past the cut

    is_set_split = best_f >= Fcand
    # Direction column → (direction, real set-feature index).
    set_dir = (best_f - Fcand) >= Fs      # False = asc, True = desc
    fset = jnp.where(set_dir, best_f - Fcand - Fs, best_f - Fcand)
    is_cat_split = (best_f >= Fn) & ~is_set_split
    # Per-slot routing mask over bins: numerical → prefix of bin ids,
    # categorical → prefix of the sorted order (rank <= cut) in the
    # CHOSEN ordering's column.
    if Fc > 0:
        ranks_flat = ranks.reshape(Ld, Fc * O, B)
        chosen_rank = jnp.take_along_axis(
            ranks_flat,
            jnp.clip(best_f - Fn, 0, Fc * O - 1)[:, None, None],
            axis=1,
        )[:, 0]  # [Ld, B]
        go_left_bins = jnp.where(
            is_cat_split[:, None],
            chosen_rank <= best_t[:, None],
            cut_ids[None, :] <= best_t[:, None],
        )  # [Ld, B]
    else:
        go_left_bins = cut_ids[None, :] <= best_t[:, None]
    if Fs > 0:
        # Stored set mask: bit = item in the selected subset
        # (rank <= cut in the chosen direction); intersecting
        # examples go RIGHT.
        Vs = sranks_dirs[0].shape[-1]
        fclip = jnp.clip(fset, 0, Fs - 1)[:, None, None]
        cs0 = jnp.take_along_axis(sranks_dirs[0], fclip, axis=1)[:, 0]
        cs1 = jnp.take_along_axis(sranks_dirs[1], fclip, axis=1)[:, 0]
        chosen_srank = jnp.where(set_dir[:, None], cs1, cs0)  # [Ld, Vs]
        sel = chosen_srank <= best_t[:, None]
        Wb = 32 * W
        if Vs < Wb:
            sel = jnp.pad(sel, ((0, 0), (0, Wb - Vs)))
        glb = go_left_bins
        if B < Wb:
            glb = jnp.pad(glb, ((0, 0), (0, Wb - B)))
        store_mask = jnp.where(is_set_split[:, None], sel, glb)
    else:
        store_mask = go_left_bins

    # The stored feature id collapses the two direction columns back
    # onto the real feature block — offset by the UNPADDED scalar
    # count (feature-parallel padding appends zero columns to `bins`;
    # serving decodes set ids against the unpadded layout).
    nvf = F if num_valid_features is None else num_valid_features
    # Collapse ordering columns back onto the real categorical id and
    # the set direction columns onto the real set id.
    best_f_scalar = jnp.where(
        is_cat_split, Fn + (best_f - Fn) // O, best_f
    )
    best_f_store = jnp.where(is_set_split, nvf + fset, best_f_scalar)
    num_nodes_new = num_nodes + 2 * jnp.sum(do_split.astype(i32))
    route_f = jnp.clip(best_f_scalar, 0, max(F - 1, 0))
    return LayerDecision(
        do_split=do_split, split_rank=split_rank, wid=wid,
        left_id=left_id, right_id=right_id, best_t=best_t,
        best_f=best_f, best_f_scalar=best_f_scalar,
        best_f_store=best_f_store, is_cat_split=is_cat_split,
        is_set_split=is_set_split, fset=fset, set_dir=set_dir,
        route_f=route_f, go_left_bins=go_left_bins,
        store_mask=store_mask, left_stats=left_stats,
        right_stats=right_stats, num_nodes=num_nodes_new,
    )


@jax.named_scope("ydf.route")
def sibling_next_state(
    hist, do_split, split_rank, left_stats, right_stats, *,
    Ld: int, L: int,
):
    """Sibling-subtraction bookkeeping for the NEXT layer (shared
    seam): scatters this layer's histograms by split rank into the
    parent-histogram carry, flags each split's smaller child, and builds
    the slot→hist-slot map. Returns (parent_next, small_is_left_next,
    Lh_next, hmap). The caller guards on hist_subtract / F > 0 /
    children_in_frontier."""
    i32 = jnp.int32
    Lh_next = min(Ld, L // 2)  # static bound on this layer's splits
    # Index each split's data by its rank (children of rank s sit at
    # slots 2s / 2s+1 next layer); rank Lh_next is the scatter trash
    # row, sliced off.
    ridx = jnp.where(do_split, split_rank, Lh_next)
    parent_next = (
        jnp.zeros((Lh_next + 1,) + hist.shape[1:], hist.dtype)
        .at[ridx].set(hist)[:Lh_next]
    )
    # Smaller child by the count-like last stat column (the same column
    # the min_examples validity check uses). The choice only steers
    # WORK, not results: parent − child is exact for any additive
    # stats, so a skewed weighting costs speed, never correctness.
    small_left = left_stats[:, -1] <= right_stats[:, -1]  # [Ld]
    small_is_left_next = (
        jnp.zeros((Lh_next + 1,), jnp.bool_)
        .at[ridx].set(small_left)[:Lh_next]
    )
    tgt_l_pre = jnp.where(do_split, 2 * split_rank, L)
    tgt_r_pre = jnp.where(do_split, 2 * split_rank + 1, L)
    hmap = jnp.full((L + 1,), Lh_next, i32)
    hmap = hmap.at[tgt_l_pre].set(
        jnp.where(do_split & small_left, split_rank, Lh_next)
    )
    hmap = hmap.at[tgt_r_pre].set(
        jnp.where(do_split & ~small_left, split_rank, Lh_next)
    )
    hmap = hmap.at[L].set(Lh_next)
    return parent_next, small_is_left_next, Lh_next, hmap


# grow_tree's signature -> the look-ups its trace counted (select, gather).
_TRACED_LOOKUPS: dict = {}


def grow_tree(
    bins, stats, key, *, hist_impl: str = "auto",
    hist_subtract: Optional[bool] = None,
    hist_quant: Optional[str] = None,
    route_impl: str = "auto", route_fuse: Optional[bool] = None,
    bins_t=None, dense_lookups="auto", **kw,
):
    """Thin wrapper resolving hist_impl="auto" (plus the
    sibling-subtraction, gradient-quantization and routing-impl
    defaults) to concrete values BEFORE the jit boundary — the jitted
    cache must be keyed on the concrete impl (see
    ops/histogram.py:resolve_hist_impl for why).

    `bins_t` (optional, native routing only): a pre-transposed
    FEATURE-major u8 [F, n] copy of `bins` for the fused route kernel's
    column-stream gather. Callers growing many trees over the SAME bins
    matrix should pass it (learners/gbt.py hoists the transpose out of
    the boosting scan); when absent the grower transposes in-trace.

    `dense_lookups`: how the XLA chain's per-row look-ups run
    (ops/lookup.py resolve_dense: "auto" is by size on a TPU and the
    gather elsewhere; None, True and False are a test's or an export's
    choice)."""
    from ydf_tpu.ops.histogram import (
        resolve_hist_impl,
        resolve_hist_quant,
        resolve_hist_subtract,
    )
    from ydf_tpu.ops.routing_native import (
        resolve_route_fuse,
        resolve_route_impl,
    )

    route = resolve_route_impl(route_impl)
    if route_fuse is None:
        route_fuse = resolve_route_fuse()
    if route == "native" and bins.shape[1] == 0:
        # Set-features-only datasets have no bins matrix for the fused
        # kernel to gather from; the XLA chain handles them.
        route = "xla"
    if route == "native":
        from ydf_tpu.config import is_tpu_backend

        if is_tpu_backend():
            # The fused kernel is a CPU custom call; on TPU the XLA
            # chain is the (fused-by-XLA) path.
            route = "xla"
    kw.update(
        hist_impl=resolve_hist_impl(hist_impl),
        hist_subtract=resolve_hist_subtract(hist_subtract),
        hist_quant=resolve_hist_quant(hist_quant),
        route_impl=route,
        route_fuse=route_fuse,
        bins_t=bins_t if route == "native" else None,
        dense_lookups=lookup.resolve_dense(dense_lookups),
    )
    # The look-ups are counted where they are traced (ops/lookup.py).
    # JAX keeps this function's trace: a program that reuses it runs no
    # line of the body, so the count is kept beside it, by the same key.
    signature = tuple(
        (x.shape, str(x.dtype)) if hasattr(x, "shape") else x
        for x in jax.tree.leaves((bins, stats, key, kw))
    ) + (jax.tree.structure(kw),)
    before = lookup.tally()
    out = _grow_tree_jit(bins, stats, key, **kw)
    traced = lookup.tally(since=before)
    if any(traced):
        _TRACED_LOOKUPS[signature] = traced
    else:
        lookup.count(*_TRACED_LOOKUPS.get(signature, (0, 0)))
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "rule", "max_depth", "frontier", "max_nodes", "num_bins",
        "num_numerical", "min_examples", "min_split_gain",
        "candidate_features", "num_valid_features", "hist_impl",
        "hist_subtract", "hist_quant", "stat_columns", "feature_num_bins",
        "route_impl", "route_fuse", "dense_lookups", "monotone",
    ),
)
def _grow_tree_jit(
    bins: jax.Array,        # uint8 [n, F] scalar features
    stats: jax.Array,       # f32 [n, S] weighted per-example statistics
    key: jax.Array,
    *,
    rule: Any,
    max_depth: int,
    frontier: int,
    max_nodes: int,
    num_bins: int = 256,
    num_numerical: Optional[int] = None,
    min_examples: int = 5,
    min_split_gain: float = 1e-9,
    candidate_features: int = -1,   # per-node feature sample; -1 = all
    num_valid_features: Optional[int] = None,  # real (unpadded) columns
    # Concrete impl only — "auto" must be resolved by the grow_tree
    # wrapper; a literal "auto" here would be baked into the jit cache
    # key and pin the first resolution forever (the body raises on it).
    hist_impl: str = "segment",
    # Sibling-subtraction histograms (LightGBM-lineage slot halving): at
    # every layer past the root, only the SMALLER child of each split
    # carries a live histogram slot; the larger sibling's histogram is
    # reconstructed as parent − child from the parent histograms carried
    # across layers. Halves the per-layer contraction width on every
    # dense backend and lets the native kernel early-continue larger
    # child rows. See ops/histogram.py's design note for the float
    # tolerance argument. Resolved by the grow_tree wrapper
    # (YDF_TPU_HIST_SUBTRACT=0 disables).
    hist_subtract: bool = True,
    # Gradient-quantization mode for the stats operand of the scalar
    # histogram ("f32" exact / "bf16x2" / "int8" — resolved by the
    # grow_tree wrapper from YDF_TPU_HIST_QUANT). In int8 mode a
    # dynamic scale is computed from the root frontier's stat ranges,
    # carried unchanged through the layer-loop scan state (see the
    # per-tree-scale note at the quantization block below), and
    # histogram() dequantizes before anything reaches the gain search,
    # so split gains are scale-invariant up to the documented error
    # bound (docs/histogram_quantization.md). Set-feature candidates
    # run EXACT f32 sums of the same dequantized g̃ grid (their
    # contraction is not histogram-dominated; staying on one grid keeps
    # parent − prefix consistent).
    hist_quant: str = "f32",
    # What the caller knows of the columns of `stats`, one
    # ops/histogram.py StatColumn each, or None: which bf16 pieces the
    # matmul histogram of the scalar features may leave off the MXU.
    # The histograms are the same bit for bit with it and without; it
    # is derived where the stats are made (learners/gbt.py), never
    # taken from a user.
    stat_columns: Optional[tuple] = None,
    # The bin count of each of the first columns of `bins` (the binner's
    # feature_num_bins: every bin of column f is below it), or None:
    # which columns the matmul histogram may give one-hot tiles they
    # share (ops/histogram.py onehot_plan). The histograms are the same
    # bit for bit with it and without; it is derived where the bins are
    # made (learners/gbt.py), never taken from a user.
    feature_num_bins: Optional[tuple] = None,
    # Example-routing impl for the per-layer slot/leaf update: "xla"
    # (default — the exact oracle chain of gathers/selects) or "native"
    # (the fused ydf_route_update CPU kernel, one multithreaded pass per
    # layer that also emits the next layer's histogram slots;
    # bit-identical by construction — docs/row_routing.md). Resolved by
    # the grow_tree wrapper from YDF_TPU_ROUTE_IMPL.
    route_impl: str = "xla",
    # Whether native routing may fuse into the native histogram kernel
    # (YDF_TPU_ROUTE_FUSE, default on; resolved by the wrapper). The
    # unfused native path keeps one standalone route_update pass per
    # layer — same bits either way, measurably different wall on hosts
    # whose LLC hides XLA's inter-pass traffic (docs/row_routing.md).
    route_fuse: bool = True,
    # How the XLA chain's per-row look-ups run (ops/lookup.py): None
    # chooses by each table's static size, True and False force the
    # compare-and-select form and the gather. Resolved by the grow_tree
    # wrapper; the forests are the same bit for bit either way.
    dense_lookups: Optional[bool] = None,
    # Pre-transposed feature-major u8 [F, n] copy of `bins` for the
    # native route kernel (see the grow_tree wrapper docstring);
    # ignored unless route_impl == "native".
    bins_t: Optional[jax.Array] = None,
    rule_ctx: Any = None,
    # Per-feature monotone directions (+1 / -1 / 0), static tuple of
    # length F or None. A cut on a +1 feature is only valid when the
    # right (greater-value) child's leaf estimate is >= the left's
    # (reference: monotonic constraints, training.h:160-168; bound
    # clamping happens post-training on the finished trees).
    monotone: Optional[tuple] = None,
    # Traced alternative to `monotone` for candidate layouts whose
    # monotone status is data-dependent (per-tree oblique projections):
    # f32 [K] with K <= number of candidate columns; trailing columns are
    # unconstrained. Mutually exclusive with `monotone`.
    monotone_dirs: Optional[jax.Array] = None,
    # CATEGORICAL_SET features: packed multi-hot uint32 [n, Fs, Ws]
    # (bit v of word block = example's set contains item v). Candidate
    # splits are prefixes of the per-node sorted item order (the same
    # one-pass reduction as categorical bins, made exact over overlapping
    # memberships by the per-example min-rank histogram); the reference's
    # greedy forward selection (training.cc categorical-set splits)
    # explores the same sorted-order family sequentially.
    set_bits: Optional[jax.Array] = None,
) -> GrowResult:
    if hist_impl == "auto":
        raise ValueError(
            "grow_tree's jitted core requires a concrete hist_impl — "
            "call grow_tree() (the wrapper resolves 'auto' before the "
            "jit cache key; a literal 'auto' would pin the first "
            "resolution forever)"
        )
    n, F = bins.shape
    S = stats.shape[1]
    # Feature-major bins copy for the STANDALONE native route kernel —
    # one traced value shared by the layers that still need it (per-TREE
    # transpose when no hoisted copy arrives; learners/gbt.py hoists it
    # out of the whole boosting scan).
    binsT = None
    if route_impl == "native" and F > 0:
        binsT = bins_t if bins_t is not None else bins.T
    # Fully-fused mode (docs/row_routing.md): when BOTH the histogram
    # and the routing run native, each layer's histogram kernel applies
    # the previous layer's splits per row on the fly (the route step
    # rides the bins row already streaming for the contraction) — the
    # standalone per-layer routing pass exists only for the LAST layer,
    # where no histogram follows. bf16x2 stats keep the unfused
    # native-route path (no fused bf16 kernel).
    fuse_route = (
        route_fuse
        and route_impl == "native"
        and hist_impl == "native"
        and hist_quant in ("f32", "int8")
        and F > 0
    )
    L, B, N = frontier, num_bins, max_nodes
    Fn = F if num_numerical is None else num_numerical
    Fc = F - Fn
    # Sorted-order count per categorical feature (multiclass: one per
    # label class; see the Fc block below).
    O = int(getattr(rule, "num_cat_orderings", 1)) if Fc > 0 else 1
    Fcand = Fn + Fc * O  # scalar candidate columns after expansion
    # Set features occupy the feature index block [F, F + Fs). Their item
    # vocabulary Vs may exceed num_bins — the node mask then widens to
    # cover it, while candidate CUT positions stay capped at B (only the
    # top-B items of either direction's order can enter a selection; the
    # tail of a 2k-item text vocabulary never carries a whole split).
    Fs = 0 if set_bits is None else set_bits.shape[1]
    Ws = 0 if set_bits is None else set_bits.shape[2]
    Vs = 32 * Ws
    Tc = min(Vs, B)  # set-prefix cut positions
    W = (max(B, Vs) + 31) // 32

    f32 = jnp.float32
    i32 = jnp.int32

    # Node storage, padded with one trash row at index N.
    tree = dict(
        feature=jnp.full((N + 1,), -1, i32),
        threshold_bin=jnp.zeros((N + 1,), i32),
        is_cat=jnp.zeros((N + 1,), jnp.bool_),
        is_set=jnp.zeros((N + 1,), jnp.bool_),
        cat_mask=jnp.zeros((N + 1, W), jnp.uint32),
        left=jnp.zeros((N + 1,), i32),
        right=jnp.zeros((N + 1,), i32),
        is_leaf=jnp.ones((N + 1,), jnp.bool_),
        leaf_stats=jnp.zeros((N + 1, S), f32),
    )

    # int8 gradient quantization: ONE per-tree scale, computed from the
    # root frontier's stat ranges and carried unchanged through the
    # layer-loop scan state. The semantics are then EXACTLY "grow the
    # tree on the dequantized stats g̃ = round(g/scale)·scale": every
    # histogram, parent total, and sibling subtraction sees the same
    # per-row values, so parent − child cancels EXACTLY and the root
    # total must be the quantized total too. (Re-quantizing per layer
    # looks tighter but breaks that cancellation: a per-row rounding
    # bias of ~scale/2 times a 100k-row layer, set against an
    # exact parent, materializes phantom gradient mass in near-empty
    # sibling cells and produces unbounded phantom gains — measured as
    # a 2.5x-too-large bogus root gain on the bench-like shape.) The
    # scale is snapped to a power of two inside histogram(); mirror
    # that here so the root total uses the identical grid.
    # Quantize/split ONCE per tree (prepare_stats_for_hist, the shared
    # seam); every layer's histogram takes the transformed operand
    # directly (histogram() detects the dtype) instead of re-paying the
    # O(n·S) transform per layer.
    with jax.named_scope("ydf.hist"):
        hist_stats, qscale, total = prepare_stats_for_hist(stats, hist_quant)
    tree["leaf_stats"] = tree["leaf_stats"].at[0].set(total)

    # Frontier state, padded with one trash slot at index L.
    frontier_id = jnp.full((L + 1,), N, i32).at[0].set(0)
    node_stats = jnp.zeros((L + 1, S), f32).at[0].set(total)
    slot = jnp.zeros((n,), i32)  # every example starts at the root slot 0
    leaf_id = jnp.zeros((n,), i32)
    num_nodes = jnp.asarray(1, i32)

    if Fs > 0:
        # Unpacked multi-hot membership, bool [n, Fs, Vs] — input-derived,
        # computed once for the whole build.
        shifts = jnp.arange(32, dtype=jnp.uint32)
        multi = (
            ((set_bits[..., None] >> shifts) & jnp.uint32(1)) > 0
        ).reshape(n, Fs, Vs)
        # Under quantization the set-feature candidates must see the
        # SAME dequantized stats g̃ the scalar histograms sum — mixing
        # exact per-item stats against the quantized parent chain would
        # re-open the phantom-mass hazard the per-tree scale closes
        # (left_set = parent − prefix with operands on different grids).
        # hist_stats holds the int8 grid points / bf16 halves; the
        # casts below are exact, so these equal the pre-seam
        # stats_q·scale and s_hi+s_lo expressions bit for bit.
        if hist_quant == "int8":
            stats_set = hist_stats.astype(f32) * qscale
        elif hist_quant == "bf16x2":
            stats_set = (
                hist_stats[:, :S].astype(f32)
                + hist_stats[:, S:].astype(f32)
            )
        else:
            stats_set = stats

    # Sibling-subtraction scan state, carried across the (unrolled) layer
    # loop: (parent_hist [Lh, F, B, S], hist_slot [n], small_is_left
    # [Lh], Lh). hist_slot is each example's histogram slot for the
    # layer: split-rank s when the example sits in split s's SMALLER
    # child, the trash slot Lh otherwise — so the layer's histogram is
    # built over ≤ ceil(Ld/2) live slots and larger-child rows are
    # skippable by every backend. The XLA route computes it as
    # hmap[new_slot]; the native route kernel emits it from the same
    # fused pass over rows.
    sub_state = None
    # Fully-fused routing: the previous layer's decision tables, applied
    # per row by this layer's fused histogram kernel (None at the root).
    route_ctx = None

    # Trash-row compaction capacity for the XLA-CPU segment impl: under
    # sibling subtraction the live (smaller-child) rows are at most
    # ceil(r/2) per split for count-like weights, so n//2 plus one slot
    # per possible split (+ margin) holds; histogram() falls back to the
    # full-row path at runtime when non-uniform example weights break
    # the bound. Other impls ignore the hint (the native kernel already
    # early-continues trash rows).
    def _compact_cap(Lh):
        return (n // 2 + Lh + 8) if hist_impl == "segment" else 0

    # A categorical cell can hold more rows than float32 counts exactly
    # (one carrier of a 115M-row airline table: 28M): its sum rounds, and
    # `parent - sibling` of two such sums leaves, in a child that holds
    # none of the category, a residue of a few rows and of their g and h.
    # Such a phantom category sorts among the real ones; on the right of
    # a cut past a tiny real category it made a leaf of 1.6e11 on the
    # chip (PERF.md section 6). So a layer whose parents include a
    # node of EXACT_ROWS rows or more is histogrammed directly, all its
    # slots, and a category a child lacks reads exactly zero; below that
    # every cell is exact and subtraction is too. Tables with no
    # categorical column, or too few rows for any node to get there,
    # trace what they always did.
    direct_when_large = (
        Fc > 0 and n >= EXACT_ROWS and hist_subtract and not fuse_route
    )

    for depth in range(max_depth):
        key, k_gain, k_feat = jax.random.split(jax.random.fold_in(key, depth), 3)
        children_in_frontier = depth + 1 < max_depth
        # Layer d has at most min(2^d, L) candidate nodes — size the
        # histogram and split search to that, not to the full frontier
        # capacity (a large constant-factor win at shallow depths).
        Ld = min(2**depth, L)

        parent = node_stats[:Ld]  # [Ld, S]
        active = frontier_id[:Ld] < N

        # ---- candidate left-stats for every cut ------------------------- #
        # Numerical features: cut t ⇒ left = bins <= t (prefix over bin id).
        # Categorical: cut t ⇒ left = t+1 smallest bins in cat_sort_key
        # order (prefix over the sorted order).
        if F == 0:
            # Set-features-only dataset (e.g. a single tokenized text
            # column): the candidate tensor is built from the set blocks
            # alone below.
            left_all = jnp.zeros((Ld, 0, B, S), f32)
            hist = None
            ranks = None
        elif sub_state is not None:
            # Sibling subtraction: histogram ONLY the smaller child of
            # every previous-layer split (Lh ≤ ceil(Ld/2) live slots; all
            # other rows carry the trash slot Lh), then reconstruct the
            # larger sibling as parent − child (sibling_reconstruct, the
            # shared seam). The matmul/segment/pallas contraction width
            # halves; the native kernel early-continues the trash rows.
            parent_hist, hslot_e, small_is_left, Lh = sub_state

            def by_subtraction():
                if fuse_route:
                    # Fully-fused: the kernel routes each row through the
                    # PREVIOUS layer's splits (route_ctx) and accumulates
                    # its histogram slot in the same pass — hslot_e was
                    # never materialized (docs/row_routing.md).
                    from ydf_tpu.ops import routing_native

                    hist_small, *routed = routing_native.histogram_routed(
                        bins, slot, leaf_id, *route_ctx,
                        stats=hist_stats, num_slots=Lh, num_bins=B,
                        quant_scale=qscale,
                    )
                else:
                    routed = (slot, leaf_id)
                    hist_small = histogram(
                        bins, hslot_e, hist_stats, num_slots=Lh,
                        num_bins=B, impl=hist_impl, quant=hist_quant,
                        quant_scale=qscale, compact=_compact_cap(Lh),
                        stat_columns=stat_columns,
                        feature_num_bins=feature_num_bins,
                    )  # [Lh, F, B, S] (dequantized f32 under quantization)
                hist = sibling_reconstruct(
                    hist_small, parent_hist, small_is_left, Ld
                )
                return hist, *routed

            if direct_when_large:
                # The slots' parents: split s's children sit at 2s, 2s+1.
                counts = jnp.pad(parent[:, -1], (0, Ld % 2))
                parents = counts.reshape(-1, 2).sum(axis=1)
                hist, slot, leaf_id = jax.lax.cond(
                    jnp.any(parents >= EXACT_ROWS),
                    lambda: (histogram(
                        bins, slot, hist_stats, num_slots=Ld, num_bins=B,
                        impl=hist_impl, quant=hist_quant,
                        quant_scale=qscale, stat_columns=stat_columns,
                        feature_num_bins=feature_num_bins,
                    ), slot, leaf_id),
                    by_subtraction,
                )
            else:
                hist, slot, leaf_id = by_subtraction()
        elif fuse_route and depth > 0:
            # Subtraction off, fused: route the previous layer's splits
            # and histogram the resulting frontier slots in one pass
            # (identity hmap — hist slot == frontier slot).
            from ydf_tpu.ops import routing_native

            hist, slot, leaf_id = routing_native.histogram_routed(
                bins, slot, leaf_id, *route_ctx,
                stats=hist_stats, num_slots=Ld, num_bins=B,
                quant_scale=qscale,
            )
        else:
            hist = histogram(
                bins, slot, hist_stats, num_slots=Ld, num_bins=B,
                impl=hist_impl, quant=hist_quant, quant_scale=qscale,
                stat_columns=stat_columns, feature_num_bins=feature_num_bins,
            )  # [Ld, F, B, S]
        right_scalar = None
        if F > 0:
            left_all, ranks, right_scalar = scalar_candidates(
                hist, Fn=Fn, O=O, rule=rule, rule_ctx=rule_ctx
            )

        if Fs > 0:
            # ---- categorical-set candidates ------------------------- #
            # Per-(slot, feature, item) stats in one contraction. Unlike
            # categorical bins, memberships overlap, so prefix stats of a
            # sorted item order come from the per-example MIN-RANK
            # histogram (exact): example ∈ prefix-t ⇔ min over its items
            # of rank(item) <= t. Contains ⇒ RIGHT (positive), so the
            # left-side stats are parent − prefix. BOTH sort directions
            # are explored (the informative items may sit at either end
            # of the rule's item score; the reference's greedy forward
            # selection effectively walks the descending end) — candidate
            # columns [F, F+Fs) ascending, [F+Fs, F+2Fs) descending.
            oh = (slot[:, None] == jnp.arange(Ld)).astype(f32)  # [n, Ld]
            per_item = jnp.einsum(
                "nfv,nl,ns->lfvs", multi.astype(f32), oh, stats_set
            )  # [Ld, Fs, Vs, S]
            skey = rule.cat_sort_key(per_item, rule_ctx)  # [Ld, Fs, Vs]
            # Items absent from the node sort last IN BOTH DIRECTIONS →
            # never selected (unseen items route to the negative branch).
            present = per_item[..., -1] > 0
            sranks_dirs, rank_min_dirs, left_set_blocks = [], [], []
            for dkey in (
                jnp.where(present, skey, jnp.inf),
                jnp.where(present, -skey, jnp.inf),
            ):
                sorder = jnp.argsort(dkey, axis=-1)
                sranks = jnp.argsort(sorder, axis=-1).astype(i32)
                ranks_pad = jnp.concatenate(
                    [sranks, jnp.full((L + 1 - Ld, Fs, Vs), Vs, i32)], 0
                )
                rank_min_cols, pos_hists = [], []
                for f in range(Fs):
                    rs = ranks_pad[:, f][slot]  # [n, Vs]
                    rm = jnp.min(jnp.where(multi[:, f], rs, Vs), axis=1)
                    rank_min_cols.append(rm)
                    # Examples whose best item rank lies beyond the cut
                    # budget Tc can never enter a selection → excluded.
                    in_cut = (rm < Tc).astype(f32)
                    h = histogram(
                        jnp.minimum(rm, Tc - 1)[:, None], slot,
                        stats_set * in_cut[:, None],
                        num_slots=Ld, num_bins=Tc, impl=hist_impl,
                        quant="f32",  # exact sums of the SAME g̃ grid
                    )  # [Ld, 1, Tc, S]
                    pos_hists.append(h[:, 0])
                sranks_dirs.append(sranks)
                rank_min_dirs.append(jnp.stack(rank_min_cols, 1))
                pos_prefix = jnp.cumsum(jnp.stack(pos_hists, 1), axis=2)
                left_set = parent[:, None, None, :] - pos_prefix
                if Tc < B:
                    # Pad count = -1 ⇒ fails the min_examples check,
                    # never chosen.
                    left_set = jnp.pad(
                        left_set, ((0, 0), (0, 0), (0, B - Tc), (0, 0)),
                        constant_values=-1.0,
                    )
                left_set_blocks.append(left_set)
            left_all = jnp.concatenate([left_all] + left_set_blocks, axis=1)

        # ---- split search (shared seam: ops/grower.py layer_decide) ----- #
        Fa = Fcand + 2 * Fs  # total candidate columns
        dirs = None
        if monotone_dirs is not None:
            dirs = jnp.zeros((Fa,), f32).at[
                : monotone_dirs.shape[0]
            ].set(monotone_dirs.astype(f32))
        elif monotone is not None and any(monotone):
            dirs_np = np.zeros((Fa,), np.float32)
            dirs_np[: len(monotone)] = np.array(monotone, np.float32)
            dirs = jnp.asarray(dirs_np)  # [Fa]; set features always 0
        dec = layer_decide(
            left_all, ranks, sranks_dirs if Fs > 0 else None,
            parent, active, frontier_id[:Ld], num_nodes,
            k_gain, k_feat, dirs, rule_ctx,
            rule=rule, L=L, B=B, N=N, Fn=Fn, Fc=Fc, O=O, Fs=Fs, W=W,
            min_examples=min_examples, min_split_gain=min_split_gain,
            candidate_features=candidate_features,
            num_valid_features=num_valid_features,
            children_in_frontier=children_in_frontier,
            right_scalar=right_scalar,
        )
        do_split, split_rank = dec.do_split, dec.split_rank
        wid, left_id, right_id = dec.wid, dec.left_id, dec.right_id
        best_t = dec.best_t
        is_set_split, fset, set_dir = (
            dec.is_set_split, dec.fset, dec.set_dir
        )
        go_left_bins = dec.go_left_bins
        left_stats, right_stats = dec.left_stats, dec.right_stats

        tree["feature"] = tree["feature"].at[wid].set(dec.best_f_store)
        tree["threshold_bin"] = tree["threshold_bin"].at[wid].set(best_t)
        tree["is_cat"] = tree["is_cat"].at[wid].set(dec.is_cat_split)
        tree["is_set"] = tree["is_set"].at[wid].set(is_set_split)
        tree["cat_mask"] = tree["cat_mask"].at[wid].set(
            _pack_mask(dec.store_mask)
        )
        tree["left"] = tree["left"].at[wid].set(left_id)
        tree["right"] = tree["right"].at[wid].set(right_id)
        tree["is_leaf"] = tree["is_leaf"].at[wid].set(False)
        tree["leaf_stats"] = tree["leaf_stats"].at[left_id].set(left_stats)
        tree["leaf_stats"] = tree["leaf_stats"].at[right_id].set(right_stats)
        num_nodes = dec.num_nodes

        # ---- sibling-subtraction bookkeeping for the NEXT layer --------- #
        # Computed BEFORE routing so the fused native kernel can emit
        # the next layer's histogram slots in the same pass over rows
        # (the smaller-child flags and the slot→hist-slot map must come
        # from the same decisions the routing applies).
        next_sub = None
        hmap = None
        if children_in_frontier:
            Lh_next = min(Ld, L // 2)  # static bound on this layer's splits
            if hist_subtract and F > 0 and Lh_next >= 1:
                parent_next, small_is_left_next, Lh_next, hmap = (
                    sibling_next_state(
                        hist, do_split, split_rank, left_stats,
                        right_stats, Ld=Ld, L=L,
                    )
                )
                next_sub = (parent_next, small_is_left_next, Lh_next)

        # ---- route examples --------------------------------------------- #
        # Pad per-slot decision arrays from Ld up to L+1 so they can be
        # indexed by `slot` (values in [0, Ld) ∪ {L}; L = inactive).
        pad = lambda a, fill: jnp.concatenate(
            [a, jnp.full((L + 1 - Ld,) + a.shape[1:], fill, a.dtype)], 0
        )
        # The bins column of the chosen split: the raw best_f indexes the
        # EXPANDED candidate columns (O orderings per categorical, two
        # direction columns per set feature), so routing must gather the
        # collapsed best_f_scalar column. (With O > 1 the raw index used
        # to be clipped into a NEIGHBORING feature's column — a
        # train-time mis-route for multiclass forests with 2+ categorical
        # features; tests/test_routing_native.py has the regression.)
        route_f = dec.route_f
        if Fs > 0:
            # Per-example set-split decision (shared by both routing
            # impls): not-contains (min rank beyond the cut) → LEFT.
            with jax.named_scope("ydf.route"):
                is_set_e = lookup_small(
                    is_set_split, slot, Ld, False, dense_lookups
                )
                fset_e = jnp.clip(
                    lookup_small(fset, slot, Ld, 0, dense_lookups),
                    0, Fs - 1,
                )
                dir_e = lookup_small(set_dir, slot, Ld, False, dense_lookups)
                rm0 = pick_column(rank_min_dirs[0], fset_e, dense_lookups)
                rm1 = pick_column(rank_min_dirs[1], fset_e, dense_lookups)
                rm_e = jnp.where(dir_e, rm1, rm0)
                t_e = lookup_small(best_t, slot, Ld, 0, dense_lookups)
                set_go_left_e = rm_e > t_e

        if route_impl == "native" and F > 0:
            # Native routing. The per-slot decision tables follow one
            # padded [L+1] contract shared by the standalone
            # ydf_route_update kernel and the fused histogram+routing
            # kernels (docs/row_routing.md).
            from ydf_tpu.ops import routing_native

            hmap_k = (
                hmap if hmap is not None
                else jnp.arange(L + 1, dtype=i32)  # identity: no remap
            )
            set_gl_k = (
                set_go_left_e.astype(jnp.uint8) if Fs > 0
                else jnp.zeros((1,), jnp.uint8)
            )
            tables = (
                pad(do_split, False), pad(route_f, 0),
                pad(go_left_bins, False),
                pad(left_id, N), pad(right_id, N),
                pad(split_rank, 0), hmap_k,
                pad(is_set_split, False), set_gl_k,
            )
            if fuse_route and children_in_frontier:
                # Fully-fused mode: this layer's routing is applied by
                # the NEXT layer's histogram kernel in its own row walk
                # — just carry the decision tables.
                route_ctx = tables
            else:
                # Last layer (or unfused native): one standalone
                # multithreaded pass over rows — slot lookup, bin
                # gather, left/right decision, child slot + node id,
                # next layer's hist slot (hmap composed in-kernel) —
                # bit-identical to the XLA chain below.
                new_slot, new_leaf, hist_slot_e, _counts = (
                    routing_native.route_update(binsT, slot, leaf_id,
                                                *tables)
                )
                leaf_id = new_leaf
        else:
            with jax.named_scope("ydf.route"):
                # Every per-row look-up is a compare-and-select pass
                # (ops/lookup.py), a gather only past its size limit.
                # Rows hold a slot in [0, Ld) or the retired slot L,
                # which reads each table's pad value.
                at_slot = lambda table, fill: lookup_small(
                    table, slot, Ld, fill, dense_lookups
                )
                split_e = at_slot(do_split, False)
                if F > 0:
                    bin_e = pick_column(
                        bins, at_slot(route_f, 0), dense_lookups
                    ).astype(i32)
                    if Fc == 0:
                        # go_left_bins is `bin <= best_t` and is never
                        # indexed. (A retired row reads a cut of 0; its
                        # side is dropped by split_e below.)
                        go_left_e = bin_e <= at_slot(best_t, 0)
                    else:
                        # The slot's mask as packed words: never an
                        # [n, B] intermediate.
                        words = _pack_mask(
                            jnp.pad(go_left_bins, ((0, 0), (0, -B % 32)))
                        )
                        go_left_e = lookup_mask_bit(
                            words, slot, Ld, bin_e, dense_lookups
                        )
                else:
                    go_left_e = jnp.zeros((n,), jnp.bool_)
                if Fs > 0:
                    go_left_e = jnp.where(is_set_e, set_go_left_e, go_left_e)
                child_id_e = jnp.where(
                    go_left_e, at_slot(left_id, N), at_slot(right_id, N)
                )
                leaf_id = jnp.where(split_e, child_id_e, leaf_id)
                if children_in_frontier:
                    rank_e = at_slot(split_rank, 0)
                    child_slot_e = jnp.where(
                        go_left_e, 2 * rank_e, 2 * rank_e + 1
                    )
                    new_slot = jnp.where(split_e, child_slot_e, L)
                    # Children sit below 2 * Lh_next; every other entry
                    # of hmap, L's too, is the trash slot Lh_next.
                    hist_slot_e = (
                        lookup_small(
                            hmap, new_slot, 2 * Lh_next, Lh_next,
                            dense_lookups,
                        )
                        if hmap is not None else new_slot
                    )

        if children_in_frontier:
            if fuse_route:
                # slot/leaf_id update deferred into the next layer's
                # fused histogram call; sub_state carries no per-example
                # hist slot (the kernel computes it in-register).
                if next_sub is not None:
                    parent_next, small_is_left_next, Lh_next = next_sub
                    sub_state = (
                        parent_next, None, small_is_left_next, Lh_next
                    )
                else:
                    sub_state = None
            else:
                slot = new_slot
                # sub_state carries the PER-EXAMPLE histogram slot of
                # the next layer (both impls compute hmap[new_slot]; the
                # native kernel emits it from the same fused pass).
                if next_sub is not None:
                    parent_next, small_is_left_next, Lh_next = next_sub
                    sub_state = (
                        parent_next, hist_slot_e, small_is_left_next,
                        Lh_next
                    )
                else:
                    sub_state = None
            # New frontier: children packed at slots [0, 2·#splits).
            tgt_l = jnp.where(do_split, 2 * split_rank, L)
            tgt_r = jnp.where(do_split, 2 * split_rank + 1, L)
            frontier_id = jnp.full((L + 1,), N, i32)
            frontier_id = frontier_id.at[tgt_l].set(left_id)
            frontier_id = frontier_id.at[tgt_r].set(right_id)
            frontier_id = frontier_id.at[L].set(N)
            node_stats = jnp.zeros((L + 1, S), f32)
            node_stats = node_stats.at[tgt_l].set(left_stats)
            node_stats = node_stats.at[tgt_r].set(right_stats)
            node_stats = node_stats.at[L].set(0.0)
        else:
            slot = jnp.full((n,), L, i32)

    trimmed = TreeArrays(
        feature=tree["feature"][:N],
        threshold_bin=tree["threshold_bin"][:N],
        is_cat=tree["is_cat"][:N],
        is_set=tree["is_set"][:N],
        cat_mask=tree["cat_mask"][:N],
        left=tree["left"][:N],
        right=tree["right"][:N],
        is_leaf=tree["is_leaf"][:N],
        leaf_stats=tree["leaf_stats"][:N],
        num_nodes=num_nodes,
    )
    return GrowResult(tree=trimmed, leaf_id=leaf_id)
