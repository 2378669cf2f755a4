"""Isolation Forest learner.

Re-design of `ydf/learner/isolation_forest/isolation_forest.cc:907`
(TrainWithStatusImpl): per tree, subsample examples without replacement
(default 256), grow with uniformly random (feature, threshold) splits to
depth ceil(log2(subsample)) (`:670-672`), score by mean isolation depth.

The random split is realized through the generic grower with
`RandomSplitRule`: Gumbel-max over (feature, bin-cut) with per-cut weights
proportional to the value-space width of the bin gap — which marginalizes
the reference's "uniform threshold in [min, max)" (`:395`) onto bin cuts.
Because each tree sees only `subsample_count` examples, the grower runs on
the gathered subsample (tiny histograms), not the full dataset.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.config import Task, TreeConfig
from ydf_tpu.dataset.dataset import InputData, release_device_inputs
from ydf_tpu.learners.generic import GenericLearner
from ydf_tpu.models.forest import forest_from_stacked_trees
from ydf_tpu.models.if_model import IsolationForestModel, average_path_length
from ydf_tpu.ops import grower
from ydf_tpu.ops.split_rules import RandomSplitRule


class IsolationForestLearner(GenericLearner):
    """API shape of the reference PYDF IsolationForestLearner
    (`specialized_learners_pre_generated.py:892`)."""

    # The reference IF trains on numerical/categorical splits only — no
    # categorical-set conditions (isolation_forest.cc).
    _supports_set_features = False

    def __init__(
        self,
        label: Optional[str] = None,  # unsupervised: label optional
        task: Task = Task.ANOMALY_DETECTION,
        num_trees: int = 300,
        subsample_count: int = 256,
        subsample_ratio: float = -1.0,
        max_depth: int = -2,  # -2 → ceil(log2(subsample)) like the reference
        split_axis: str = "AXIS_ALIGNED",
        sparse_oblique_projection_density_factor: float = 2.0,
        sparse_oblique_weights: str = "BINARY",
        sparse_oblique_num_projections_exponent: float = 1.0,
        sparse_oblique_max_num_projections: int = 64,
        features: Optional[Sequence[str]] = None,
        random_seed: int = 123456,
        **kwargs,
    ):
        super().__init__(
            label=label, task=task, features=features,
            random_seed=random_seed, **kwargs,
        )
        self.num_trees = num_trees
        self.subsample_count = subsample_count
        self.subsample_ratio = subsample_ratio
        self.max_depth = max_depth
        # Sparse-oblique random splits (reference isolation_forest.cc:311
        # SetRandomSplitNumericalSparseOblique): numerical splits become
        # random sparse projections with a uniform random threshold. TPU
        # recast like the GBT oblique path: P projections sampled per
        # TREE, binned with UNIFORM (linspace) boundaries over the
        # subsample's projected range — the RandomSplitRule's gap-weighted
        # cut then realizes the reference's uniform-threshold draw, with
        # per-node adaptivity coming from the valid-cut mask.
        if split_axis not in ("AXIS_ALIGNED", "SPARSE_OBLIQUE"):
            raise ValueError(f"Unknown split_axis {split_axis!r}")
        from ydf_tpu.ops.oblique import WEIGHT_TYPES

        if sparse_oblique_weights not in WEIGHT_TYPES:
            raise ValueError(
                f"Unknown sparse_oblique_weights {sparse_oblique_weights!r}"
            )
        self.split_axis = split_axis
        self.sparse_oblique_projection_density_factor = (
            sparse_oblique_projection_density_factor
        )
        self.sparse_oblique_weights = sparse_oblique_weights
        self.sparse_oblique_num_projections_exponent = (
            sparse_oblique_num_projections_exponent
        )
        self.sparse_oblique_max_num_projections = (
            sparse_oblique_max_num_projections
        )

    def train(self, data: InputData, valid=None) -> IsolationForestModel:
        prep = self._prepare(data)
        binner = prep["binner"]
        release_device_inputs()  # this job's table goes up: no second one
        bins = jnp.asarray(prep["bins"])
        n, F = bins.shape

        if self.subsample_ratio > 0:
            sub = max(int(self.subsample_ratio * n), 2)
        else:
            sub = self.subsample_count
        sub = min(sub, n)
        depth = (
            int(np.ceil(np.log2(max(sub, 2))))
            if self.max_depth == -2
            else self.max_depth
        )

        # log gap widths per (feature, cut): weight of picking cut t is the
        # value-space distance between consecutive boundaries.
        B = binner.num_bins  # "auto" already resolved at binning time
        log_gap = np.full((F, B), -np.inf, np.float32)
        for f in range(binner.num_numerical):
            nb = int(binner.feature_num_bins[f]) - 1  # number of boundaries
            if nb <= 0:
                continue
            b = binner.boundaries[f, :nb].astype(np.float64)
            gaps = np.diff(b, prepend=b[0] - (b[-1] - b[0] + 1e-6) / max(nb, 1))
            gaps = np.maximum(gaps, 1e-12)
            log_gap[f, :nb] = np.log(gaps)
        # Categorical features: uniform over observed cut points.
        for f in range(binner.num_numerical, F):
            nb = int(binner.feature_num_bins[f])
            log_gap[f, : max(nb - 1, 1)] = 0.0

        tree_cfg = TreeConfig(
            max_depth=depth,
            max_frontier=max(2 ** max(depth - 1, 0), 1),
            num_bins=B,
            min_examples=1,
        )
        max_nodes = min(tree_cfg.max_nodes, 4 * sub + 3)

        Fn = binner.num_numerical
        obl_P = 0
        x_raw = None
        if self.split_axis == "SPARSE_OBLIQUE" and Fn > 0:
            obl_P = int(
                np.ceil(Fn ** self.sparse_oblique_num_projections_exponent)
            )
            obl_P = min(
                max(obl_P, 2), self.sparse_oblique_max_num_projections
            )
            ds = prep["dataset"]
            x_raw = np.zeros((n, Fn), np.float32)
            for i, name in enumerate(binner.feature_names[:Fn]):
                if ds.dataspec.has_column(name) and name in ds.data:
                    x_raw[:, i] = ds.encoded_numerical(name)
                else:
                    x_raw[:, i] = binner.impute_values[i]
            # Oblique replaces axis-aligned numerical splits entirely
            # (the reference routes every NUMERICAL pick through the
            # oblique sampler when sparse_oblique is configured).
            log_gap[:Fn] = -np.inf
            x_raw = jnp.asarray(x_raw)

        stacked, leaf_values, obl = _train_if(
            bins, num_trees=self.num_trees, sub=sub, depth=depth,
            tree_cfg=tree_cfg, max_nodes=max_nodes,
            num_numerical=binner.num_numerical,
            log_gap=jnp.asarray(log_gap), seed=self.random_seed,
            x_raw=x_raw, obl_P=obl_P,
            obl_density=self.sparse_oblique_projection_density_factor,
            obl_weight_type=self.sparse_oblique_weights,
        )

        if obl_P > 0:
            # Remap grow-time feature ids [Fn, Fn+P) (projection block)
            # onto the Forest convention: projections live after ALL real
            # features; categoricals shift back by P.
            Freal = binner.num_features
            feat = np.asarray(stacked.feature)
            in_block = (feat >= Fn) & (feat < Fn + obl_P)
            remapped = np.where(
                in_block,
                Freal + (feat - Fn),
                np.where(feat >= Fn + obl_P, feat - obl_P, feat),
            )
            stacked = stacked._replace(feature=remapped.astype(np.int32))
            forest = forest_from_stacked_trees(
                stacked, leaf_values, binner.boundaries,
                oblique_weights=np.asarray(obl[0]),
                oblique_boundaries=np.asarray(obl[1]),
            )
        else:
            forest = forest_from_stacked_trees(
                stacked, leaf_values, binner.boundaries
            )
        return IsolationForestModel(
            task=self.task,
            label=self.label,
            classes=None,
            dataspec=prep["dataset"].dataspec,
            binner=binner,
            forest=forest,
            max_depth=depth,
            num_examples_per_tree=sub,
        )


def _train_if(
    bins, *, num_trees, sub, depth, tree_cfg: TreeConfig, max_nodes,
    num_numerical, log_gap, seed, x_raw=None, obl_P=0, obl_density=2.0,
    obl_weight_type="BINARY",
):
    return _if_run(
        bins, log_gap, x_raw, jnp.asarray(seed, jnp.uint32),
        num_trees=num_trees, sub=sub, depth=depth,
        frontier=tree_cfg.frontier, num_bins=tree_cfg.num_bins,
        max_nodes=max_nodes, num_numerical=num_numerical,
        obl_P=obl_P, obl_density=obl_density,
        obl_weight_type=obl_weight_type,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_trees", "sub", "depth", "frontier", "num_bins", "max_nodes",
        "num_numerical", "obl_P", "obl_density", "obl_weight_type",
    ),
)
def _if_run(
    bins, log_gap, x_raw, seed, *, num_trees, sub, depth, frontier,
    num_bins, max_nodes, num_numerical, obl_P, obl_density,
    obl_weight_type,
):
    """Module-level jit so the compiled executable is cached across
    train() calls (a per-call closure can never hit the jit cache —
    profiling on the RF path measured ~30 s of recompilation per call)."""
    n = bins.shape[0]
    rule = RandomSplitRule()
    B = num_bins
    P = obl_P
    Fn = num_numerical

    def one_tree(carry, t):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
        k_samp, k_grow, k_obl = jax.random.split(key, 3)
        # subsample WITHOUT replacement: Gumbel top-k over examples.
        scores = jax.random.uniform(k_samp, (n,))
        _, idx = jax.lax.top_k(scores, sub)
        sub_bins = bins[idx]
        if P > 0:
            # Per-tree sparse projections on the subsample (reference
            # isolation_forest.cc:311 samples per node; the per-tree
            # pool + per-node uniform pick is the batched recast).
            # Shared sampler: ops/oblique.py.
            from ydf_tpu.ops.oblique import (
                sample_projection_coefficients,
            )

            W = sample_projection_coefficients(
                k_obl, P, Fn,
                density=obl_density,
                weight_type=obl_weight_type,
            )
            z = x_raw[idx] @ W.T  # [sub, P]
            zmin = jnp.min(z, axis=0)  # [P]
            zmax = jnp.max(z, axis=0)
            # Uniform (linspace) boundaries over the projected range:
            # equal bin gaps ⇒ the gap-weighted random cut draws the
            # reference's uniform threshold in (min, max].
            qs = jnp.arange(1, B, dtype=jnp.float32) / B  # [B-1]
            bnd = zmin[:, None] + (
                jnp.maximum(zmax - zmin, 1e-12)[:, None] * qs[None, :]
            )  # [P, B-1]
            zb = jax.vmap(
                lambda b, zz: jnp.searchsorted(b, zz, side="right")
            )(bnd, z.T).astype(jnp.uint8).T  # [sub, P]
            grow_bins = jnp.concatenate(
                [sub_bins[:, :Fn], zb, sub_bins[:, Fn:]], axis=1
            )
            grow_log_gap = jnp.concatenate(
                [
                    log_gap[:Fn],  # -inf: axis numericals disabled
                    jnp.zeros((P, B), jnp.float32),
                    log_gap[Fn:],
                ],
                axis=0,
            )
            grow_Fn = Fn + P
        else:
            W = jnp.zeros((0, 0), jnp.float32)
            bnd = jnp.zeros((0, B - 1), jnp.float32)
            grow_bins = sub_bins
            grow_log_gap = log_gap
            grow_Fn = num_numerical
        stats = jnp.ones((sub, 1), jnp.float32)
        res = grower.grow_tree(
            grow_bins, stats, k_grow,
            rule=rule,
            max_depth=depth,
            frontier=frontier,
            max_nodes=max_nodes,
            num_bins=num_bins,
            num_numerical=grow_Fn,
            min_examples=1,
            min_split_gain=float("-inf"),
            candidate_features=-1,
            rule_ctx=grow_log_gap,
        )
        tree = res.tree
        # Node depths: parents precede children in BFS id order, so
        # `depth` sweeps converge after max_depth scatter passes.
        nd = jnp.zeros((max_nodes + 1,), jnp.int32)
        for _ in range(depth):
            internal = ~tree.is_leaf
            tl = jnp.where(internal, tree.left, max_nodes)
            tr = jnp.where(internal, tree.right, max_nodes)
            d1 = nd[:max_nodes] + 1
            nd = nd.at[tl].set(d1)
            nd = nd.at[tr].set(d1)
        node_depth = nd[:max_nodes].astype(jnp.float32)
        counts = tree.leaf_stats[:, 0]
        lv = (node_depth + _avg_path_length_jnp(counts))[:, None]
        return carry, (tree, lv, W, bnd)

    _, (trees, lvs, Ws, bnds) = jax.lax.scan(
        one_tree, 0, jnp.arange(num_trees)
    )
    return trees, lvs, (Ws, bnds)


def _avg_path_length_jnp(n):
    euler = 0.5772156649015329
    nf = jnp.maximum(n, 1.0)
    h = jnp.log(jnp.maximum(nf - 1.0, 1.0)) + euler
    c = 2.0 * h - 2.0 * (nf - 1.0) / nf
    return jnp.where(n > 2, c, jnp.where(n == 2, 1.0, 0.0))
