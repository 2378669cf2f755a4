"""Tree routing: example → leaf, vectorized over examples (and trees).

The semantic reference is the reference's own JAX export routing
(`ydf/port/python/ydf/model/export_jax.py:970-1150` _predict_fn /
_route_example): iterate `max_depth` times, each step gathering the current
node's condition and stepping to a child; leaves self-loop.

Two input modes:
  * binned mode — uint8 bin matrix (training / fast serving): numerical
    condition `bin <= threshold_bin`, categorical `mask bit set`.
  * value mode — raw float numericals + int categorical indices (serving on
    un-binned data): numerical condition `v < threshold`, same mask for
    categoricals. The two are exactly equivalent by construction of the
    binner (threshold = boundaries[threshold_bin]).

Forests are scanned tree-by-tree with an accumulating [n, V] output (a vmap
over trees would materialize [T, n] node arrays — too much HBM at scale).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ydf_tpu.ops import lookup
from ydf_tpu.ops.grower import TreeArrays, unpack_mask_bit
from ydf_tpu.ops.lookup import lookup_mask_bit, lookup_small, pick_column

i32 = jnp.int32


def _set_intersects(tree, node, x_set: jax.Array, f: jax.Array) -> jax.Array:
    """bool [n]: does each example's packed set (feature f - offset, offset
    = number of scalar features) intersect the node's selected subset?
    Contains ⇒ the reference's positive branch ⇒ RIGHT."""
    Fs = x_set.shape[1]
    Wm = min(x_set.shape[2], tree.cat_mask.shape[-1])
    fs = jnp.clip(f, 0, Fs - 1)
    words = jnp.take_along_axis(
        x_set, fs[:, None, None].astype(i32), axis=1
    )[:, 0, :Wm]
    mask = tree.cat_mask[node][:, :Wm]
    return jnp.any((words & mask) != 0, axis=1)


def route_tree_bins(
    tree, bins: jax.Array, max_depth: int,
    x_set: Optional[jax.Array] = None,
    num_scalar: Optional[int] = None,
    impl: str = "xla",
    num_numerical: Optional[int] = None,
    dense_lookups="auto",
) -> jax.Array:
    """Leaf node id per example. tree: TreeArrays-like (single tree).
    `x_set`: packed multi-hot set features uint32 [n, Fs, W]. Set features
    sit after the scalar features in the node feature-id space, and the
    grower stores their ids offset by the UNPADDED scalar-column count
    (grow_tree `best_f_store`). `num_scalar` gives that offset; the
    default bins.shape[1] is only correct when the bins matrix carries
    no trailing pad columns — under feature-parallel padding (mesh
    feature axis > 1) the matrix is wider than the stored offset, so
    callers MUST pass the unpadded count explicitly (learners/gbt.py
    passes `grow_num_valid`; tests/test_routing_native.py has the
    trailing-pad-columns regression).

    `num_numerical` is the caller's count of numerical columns among
    the scalar ones: where it says that no column is categorical, no
    node's mask is fetched (None: one may be). `dense_lookups` is
    ops/lookup.py's seam, as grow_tree takes it ("auto": each look-up by
    its table's size on a TPU, a gather elsewhere).

    `impl` selects the formulation: "xla" (default — the fori_loop of
    whole-array look-ups below, ops/lookup.py) or "native" (the fused
    one-pass tree-walk kernel native/routing_ffi.cc:ydf_route_tree,
    bit-identical; CPU
    only, resolved by the caller via
    ops/routing_native.py:resolve_route_impl).

    Does NOT support oblique nodes (projections are not part of the input
    bin matrix) — oblique forests must route in value mode."""
    ow = getattr(tree, "oblique_weights", None)
    if ow is not None and ow.size > 0:
        raise NotImplementedError(
            "binned routing over oblique forests is not supported; use "
            "value-mode routing (forest_predict_values)"
        )
    va = getattr(tree, "vs_anchor", None)
    if va is not None and va.size > 0:
        raise NotImplementedError(
            "binned routing over vector-sequence forests is not supported; "
            "use value-mode routing (forest_predict_values)"
        )
    n, Fb = bins.shape
    if impl == "native":
        from ydf_tpu.ops import routing_native

        is_set = getattr(tree, "is_set", None)
        if is_set is None:
            is_set = jnp.zeros_like(tree.is_cat)
        return routing_native.route_tree(
            bins, tree.feature, tree.threshold_bin, tree.is_cat, is_set,
            tree.cat_mask, tree.left, tree.right, tree.is_leaf,
            max_depth, x_set=x_set, num_scalar=num_scalar,
        )

    dense_lookups = lookup.resolve_dense(dense_lookups)
    N = tree.feature.shape[0]
    is_set = getattr(tree, "is_set", None)
    has_set = is_set is not None and x_set is not None and x_set.size > 0
    Fscalar = Fb if num_scalar is None else num_scalar
    has_cat = num_numerical is None or num_numerical < Fscalar

    def body(_, node):
        at_node = lambda table, fill: lookup_small(
            table, node, N, fill, dense_lookups
        )
        f = jnp.maximum(at_node(tree.feature, 0), 0)
        b = pick_column(
            bins, jnp.clip(f, 0, Fb - 1), dense_lookups
        ).astype(i32)
        go_left = b <= at_node(tree.threshold_bin, 0)
        if has_cat:
            go_left = jnp.where(
                at_node(tree.is_cat, False),
                lookup_mask_bit(tree.cat_mask, node, N, b, dense_lookups),
                go_left,
            )
        if has_set:
            go_left = jnp.where(
                at_node(is_set, False),
                ~_set_intersects(tree, node, x_set, f - Fscalar),
                go_left,
            )
        nxt = jnp.where(
            go_left, at_node(tree.left, 0), at_node(tree.right, 0)
        )
        return jnp.where(at_node(tree.is_leaf, True), node, nxt)

    # fori_loop (not a Python loop): the body is traced once, keeping the
    # graph size independent of depth — best-first-grown trees can be
    # 50+ deep, which would explode an unrolled trace. Its look-ups run
    # max_depth times and are counted so.
    before = lookup.counts()
    leaves = jax.lax.fori_loop(0, max_depth, body, jnp.zeros((n,), i32))
    select, gather = lookup.counts(since=before)
    lookup.count(select * (max_depth - 1), gather * (max_depth - 1))
    return leaves


def apply_leaf_values(
    leaf_id: jax.Array,         # int32 [n]
    leaf_value_raw: jax.Array,  # f32 [N] UNSCALED value per node
    preds: jax.Array,           # f32 [n]
    scale: float = 1.0,
    impl: str = "xla",
) -> jax.Array:
    """preds + (leaf_value_raw·scale)[leaf_id] — the boosting loop's
    per-tree prediction update, shared by the training-set and
    validation-set paths (learners/gbt.py). The leaf values arrive
    UNSCALED with the shrinkage factor separate because XLA CPU
    contracts the scale-multiply into the add as a hardware FMA (one
    rounding, straight through the gather — docs/row_routing.md);
    impl="native" runs the fused ydf_leaf_update kernel, which
    replicates whichever contraction behavior the host's XLA exhibits
    (routing_native.update_uses_fma probe) so both impls stay
    bit-identical."""
    if impl == "native":
        from ydf_tpu.ops import routing_native

        return routing_native.leaf_update(
            leaf_id, leaf_value_raw, scale, preds
        )
    return preds + (leaf_value_raw * jnp.float32(scale))[leaf_id]


def _vs_tree_projections(tree, x_vs_vals, x_vs_len):
    """Per-example projection values of one tree's VS anchors: [n, Pv].

    Anchors live per tree (vs_anchor [Pv, D], vs_feat [Pv], vs_is_closer
    [Pv]); scores are computed per VS feature against ALL anchors, then
    each anchor selects its own feature's column (Fv is small, the
    redundant factor is cheap and keeps the kernel batched)."""
    from ydf_tpu.ops.vector_sequence import vs_scores

    Fv = x_vs_vals.shape[1]
    per_feat = [
        vs_scores(
            x_vs_vals[:, fv], x_vs_len[:, fv], tree.vs_anchor,
            tree.vs_is_closer,
        )
        for fv in range(Fv)
    ]
    stacked = jnp.stack(per_feat, axis=1)  # [n, Fv, Pv]
    fsel = jnp.clip(tree.vs_feat, 0, Fv - 1)  # [Pv]
    n = stacked.shape[0]
    return jnp.take_along_axis(
        stacked, jnp.broadcast_to(fsel[None, None, :], (n, 1, fsel.shape[0])),
        axis=1,
    )[:, 0, :]


def route_tree_values(
    tree,
    x_num: jax.Array,  # f32 [n, Fn] (missing already imputed)
    x_cat: jax.Array,  # i32 [n, Fc] vocabulary indices (OOV/overflow → 0)
    num_numerical: int,
    max_depth: int,
    x_set: Optional[jax.Array] = None,       # u32 [n, Fs, W] packed sets
    set_missing: Optional[jax.Array] = None,  # bool [n, Fs] missing cells
    x_vs_vals: Optional[jax.Array] = None,   # f32 [n, Fv, L, D] sequences
    x_vs_len: Optional[jax.Array] = None,    # i32 [n, Fv]
    vs_missing: Optional[jax.Array] = None,  # bool [n, Fv] missing cells
) -> jax.Array:
    """Leaf node id per example, value mode. tree.threshold is float.
    Feature index space: [0, Fn) numerical, [Fn, Fn+Fc) categorical,
    [Fn+Fc, Fn+Fc+Fs) categorical-set, [F_total, F_total+P) oblique,
    [F_total+P, F_total+P+Pv) vector-sequence anchors."""
    n = x_num.shape[0] if x_num.size else x_cat.shape[0]
    ow = getattr(tree, "oblique_weights", None)
    onr = getattr(tree, "oblique_na_repl", None)
    P = 0 if ow is None else ow.shape[0]
    Fs = 0 if x_set is None else x_set.shape[1]
    F_total = x_num.shape[1] + x_cat.shape[1] + Fs
    num_scalar = F_total - Fs
    va = getattr(tree, "vs_anchor", None)
    Pv = 0 if va is None else va.shape[0]
    if Pv > 0 and x_vs_vals is not None:
        # One batched kernel pass per tree, outside the depth loop.
        vs_proj = _vs_tree_projections(tree, x_vs_vals, x_vs_len)
    else:
        vs_proj = None

    def body(_, node):
        f = jnp.maximum(tree.feature[node], 0)
        is_cat = tree.is_cat[node]
        fn = jnp.clip(f, 0, max(x_num.shape[1] - 1, 0))
        fc = jnp.clip(f - num_numerical, 0, max(x_cat.shape[1] - 1, 0))
        if x_num.shape[1] > 0:
            v = jnp.take_along_axis(x_num, fn[:, None], axis=1)[:, 0]
        else:
            v = jnp.zeros((n,), jnp.float32)
        if x_cat.shape[1] > 0:
            c = jnp.take_along_axis(x_cat, fc[:, None], axis=1)[:, 0]
        else:
            c = jnp.zeros((n,), i32)
        if P > 0:
            # Oblique node: feature index in [F, F+P) selects a projection;
            # compare dot(x_num, w_p) to the threshold. Features with zero
            # projection weight must not poison the dot with their NaNs;
            # missing features INSIDE the projection use their stored
            # na_replacement when present (decision_tree.proto Oblique
            # field 4), else the NaN propagates → na_left.
            p_id = jnp.clip(f - F_total, 0, P - 1)
            w_vec = ow[p_id]  # [n, Fn]
            repl = onr[p_id]  # [n, Fn], NaN = no replacement
            x_eff = jnp.where(
                jnp.isnan(x_num) & ~jnp.isnan(repl), repl, x_num
            )
            x_eff = jnp.where(w_vec != 0, x_eff, 0.0)
            v = jnp.where(
                (f >= F_total) & (f < F_total + P),
                jnp.sum(x_eff * w_vec, axis=1),
                v,
            )
        if vs_proj is not None:
            q_id = jnp.clip(f - F_total - P, 0, vs_proj.shape[1] - 1)
            v = jnp.where(
                f >= F_total + P,
                jnp.take_along_axis(vs_proj, q_id[:, None], axis=1)[:, 0],
                v,
            )
        go_left = jnp.where(
            is_cat,
            unpack_mask_bit(tree.cat_mask[node], jnp.maximum(c, 0)),
            v < tree.threshold[node],
        )
        # Missing values (NaN numerical / negative categorical code) take
        # the node's stored direction — the reference's NodeCondition
        # na_value (decision_tree.proto:182), inverted to "goes left".
        missing = jnp.where(is_cat, c < 0, jnp.isnan(v))
        is_set = getattr(tree, "is_set", None)
        if is_set is not None and Fs > 0:
            fs = f - num_scalar
            go_left = jnp.where(
                is_set[node],
                ~_set_intersects(tree, node, x_set, fs),
                go_left,
            )
            if set_missing is not None:
                sm = jnp.take_along_axis(
                    set_missing, jnp.clip(fs, 0, Fs - 1)[:, None], axis=1
                )[:, 0]
                missing = jnp.where(is_set[node], sm, missing)
            else:
                missing = jnp.where(is_set[node], False, missing)
        if vs_proj is not None:
            # A VS projection value is never NaN (empty → -FLT_MAX), so
            # missing-ness comes from the per-cell mask when provided.
            is_vs_node = f >= F_total + P
            if vs_missing is not None:
                q_id = jnp.clip(f - F_total - P, 0, vs_proj.shape[1] - 1)
                fv = jnp.clip(
                    tree.vs_feat[q_id], 0, vs_missing.shape[1] - 1
                )
                vm = jnp.take_along_axis(
                    vs_missing, fv[:, None], axis=1
                )[:, 0]
                missing = jnp.where(is_vs_node, vm, missing)
            else:
                missing = jnp.where(is_vs_node, False, missing)
        go_left = jnp.where(missing, tree.na_left[node], go_left)
        nxt = jnp.where(go_left, tree.left[node], tree.right[node])
        return jnp.where(tree.is_leaf[node], node, nxt)

    # See route_tree_bins: fori_loop keeps trace size depth-independent.
    return jax.lax.fori_loop(0, max_depth, body, jnp.zeros((n,), i32))


@functools.partial(jax.jit, static_argnames=("max_depth", "combine"))
def forest_predict_bins(
    forest,  # pytree with per-tree arrays stacked on axis 0, incl. leaf_value [T, N, V]
    bins: jax.Array,
    max_depth: int,
    combine: str = "sum",
    x_set: Optional[jax.Array] = None,
) -> jax.Array:
    """Σ (or mean) over trees of routed leaf values. Returns [n, V]."""
    T = forest.leaf_value.shape[0]
    n = bins.shape[0]

    def body(acc, tree):
        leaves = route_tree_bins(tree, bins, max_depth, x_set=x_set)
        return acc + tree.leaf_value[leaves], None

    init = jnp.zeros((n, forest.leaf_value.shape[-1]), jnp.float32)
    acc, _ = jax.lax.scan(body, init, forest)
    return acc / T if combine == "mean" else acc


@functools.partial(
    jax.jit, static_argnames=("num_numerical", "max_depth", "combine")
)
def forest_predict_values(
    forest,
    x_num: jax.Array,
    x_cat: jax.Array,
    num_numerical: int,
    max_depth: int,
    combine: str = "sum",
    x_set: Optional[jax.Array] = None,
    set_missing: Optional[jax.Array] = None,
    x_vs_vals: Optional[jax.Array] = None,
    x_vs_len: Optional[jax.Array] = None,
    vs_missing: Optional[jax.Array] = None,
) -> jax.Array:
    T = forest.leaf_value.shape[0]
    n = x_num.shape[0] if x_num.size else x_cat.shape[0]

    def body(acc, tree):
        leaves = route_tree_values(
            tree, x_num, x_cat, num_numerical, max_depth,
            x_set=x_set, set_missing=set_missing,
            x_vs_vals=x_vs_vals, x_vs_len=x_vs_len, vs_missing=vs_missing,
        )
        return acc + tree.leaf_value[leaves], None

    init = jnp.zeros((n, forest.leaf_value.shape[-1]), jnp.float32)
    acc, _ = jax.lax.scan(body, init, forest)
    return acc / T if combine == "mean" else acc


@functools.partial(
    jax.jit, static_argnames=("num_numerical", "max_depth")
)
def forest_leaves(
    forest,
    x_num: jax.Array,
    x_cat: jax.Array,
    num_numerical: int,
    max_depth: int,
    x_set: Optional[jax.Array] = None,
    set_missing: Optional[jax.Array] = None,
    x_vs_vals: Optional[jax.Array] = None,
    x_vs_len: Optional[jax.Array] = None,
    vs_missing: Optional[jax.Array] = None,
) -> jax.Array:
    """Leaf node id of every example in every tree: int32 [n, T]
    (reference PredictLeaves, decision_forest_model.py:189)."""

    def body(c, tree):
        return c, route_tree_values(
            tree, x_num, x_cat, num_numerical, max_depth,
            x_set=x_set, set_missing=set_missing,
            x_vs_vals=x_vs_vals, x_vs_len=x_vs_len, vs_missing=vs_missing,
        )

    _, leaves = jax.lax.scan(body, 0, forest)  # [T, n]
    return leaves.T


def leaf_proximity(
    leaves1: jax.Array, leaves2: jax.Array, chunk: int = 1024
) -> jax.Array:
    """Breiman proximity: fraction of trees routing a pair to the SAME
    leaf — f32 [n1, n2] (reference Proximity,
    random_forest/random_forest.h:211-217). The leaves1 chunk size is
    capped by n2*T so the [chunk, n2, T] comparison tensor stays bounded
    (~256 MB) regardless of the data2/tree sizes — a fixed chunk would
    allocate multi-GB blocks at e.g. 20k rows x 300 trees."""
    n2, T = leaves2.shape
    cap = max(1, (1 << 26) // max(n2 * T, 1))
    return _leaf_proximity_jit(leaves1, leaves2, min(chunk, cap))


@functools.partial(jax.jit, static_argnames=("chunk",))
def _leaf_proximity_jit(
    leaves1: jax.Array, leaves2: jax.Array, chunk: int
) -> jax.Array:
    n1, T = leaves1.shape
    n1p = ((n1 + chunk - 1) // chunk) * chunk
    l1 = jnp.pad(leaves1, ((0, n1p - n1), (0, 0)))
    l1c = l1.reshape(n1p // chunk, chunk, T)

    def one(l1_blk):
        # [chunk, n2, T] equality, averaged over trees.
        return jnp.mean(
            (l1_blk[:, None, :] == leaves2[None, :, :]).astype(jnp.float32),
            axis=2,
        )

    _, prox = jax.lax.scan(lambda c, b: (c, one(b)), 0, l1c)
    return prox.reshape(n1p, -1)[:n1]


def route_histogram_fused(
    bins, slot, leaf_id, do_split, route_f, go_left, left_id, right_id,
    split_rank, hmap, is_set, set_go_left, stats, *, num_slots, num_bins,
    quant_scale=None, impl: str = "native",
):
    """The fused previous-layer-routing + this-layer-histogram seam
    (docs/row_routing.md): ONE pass over rows applies the previous
    layer's decision tables per example and accumulates this layer's
    [num_slots, F, num_bins, S] histogram from the in-register hist
    slot. Two backends, one contract — returns (hist f32, new_slot [n]
    i32, new_leaf [n] i32), bit-identical to each other and to the
    unfused route-then-histogram chain:

      * "native" — the multithreaded CPU SlotFn kernel
        (ops/routing_native.py:histogram_routed; f32/int8 stats).
      * "pallas" / "pallas_interpret" — the Mosaic kernel
        (ops/histogram_pallas.py:histogram_routed_pallas; f32/bf16x2/
        int8 stats), the TPU-native form: routing gathers become
        one-hot MXU contractions and the bin matrix is the only
        per-example traffic.

    Table arrays follow the padded [L+1] contract of
    routing_native.route_update; `hmap` must be the identity when
    sibling subtraction is off."""
    if impl == "native":
        from ydf_tpu.ops import routing_native

        return routing_native.histogram_routed(
            bins, slot, leaf_id, do_split, route_f, go_left, left_id,
            right_id, split_rank, hmap, is_set, set_go_left, stats,
            num_slots=num_slots, num_bins=num_bins,
            quant_scale=quant_scale,
        )
    if impl in ("pallas", "pallas_interpret"):
        from ydf_tpu.ops.histogram_pallas import histogram_routed_pallas

        return histogram_routed_pallas(
            bins, slot, leaf_id, do_split, route_f, go_left, left_id,
            right_id, split_rank, hmap, is_set, set_go_left, stats,
            num_slots=num_slots, num_bins=num_bins,
            quant_scale=quant_scale,
            interpret=(impl == "pallas_interpret"),
        )
    raise ValueError(
        f"route_histogram_fused impl {impl!r} must be 'native', "
        "'pallas' or 'pallas_interpret'"
    )
