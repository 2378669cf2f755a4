"""Random Forest learner.

Re-design of `ydf/learner/random_forest/random_forest.cc:411`
(TrainWithStatusImpl): bagging + per-node attribute sampling. Where the
reference exploits tree-parallelism over CPU threads, the TPU build scans
trees sequentially on device — each tree build is itself fully batched over
(examples × features × bins), which is where the parallelism budget goes.

Bootstrap sampling uses Poisson(1) example weights — the standard
large-n approximation of with-replacement bagging (the reference draws
exact multinomial counts, `random_forest.cc:350`).
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.config import Task, TreeConfig
from ydf_tpu.dataset.dataset import InputData, release_device_inputs
from ydf_tpu.learners.generic import GenericLearner
from ydf_tpu.models.forest import forest_from_stacked_trees
from ydf_tpu.models.rf_model import RandomForestModel
from ydf_tpu.ops import grower, routing
from ydf_tpu.ops.split_rules import (
    ClassificationRule,
    RegressionRule,
    UpliftEuclideanRule,
)


class RandomForestLearner(GenericLearner):
    """API shape of the reference PYDF RandomForestLearner
    (`specialized_learners_pre_generated.py:53`)."""

    def __init__(
        self,
        label: str,
        task: Task = Task.CLASSIFICATION,
        num_trees: int = 300,
        max_depth: int = 16,
        min_examples: int = 5,
        bootstrap_training_dataset: bool = True,
        bootstrap_size_ratio: float = 1.0,
        num_candidate_attributes: int = 0,
        num_candidate_attributes_ratio: float = -1.0,
        split_axis: str = "AXIS_ALIGNED",
        sparse_oblique_num_projections_exponent: float = 1.0,
        sparse_oblique_projection_density_factor: float = 2.0,
        sparse_oblique_weights: str = "BINARY",
        sparse_oblique_max_num_projections: int = 64,
        winner_take_all: bool = True,
        compute_oob_performances: bool = True,
        compute_oob_variable_importances: bool = False,
        max_frontier="auto",
        uplift_treatment: Optional[str] = None,
        honest: bool = False,
        honest_ratio_leaf_examples: float = 0.5,
        maximum_training_duration: float = -1.0,
        mesh=None,
        features: Optional[Sequence[str]] = None,
        weights: Optional[str] = None,
        random_seed: int = 123456,
        **kwargs,
    ):
        super().__init__(
            label=label, task=task, features=features, weights=weights,
            random_seed=random_seed, **kwargs,
        )
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.min_examples = min_examples
        self.bootstrap_training_dataset = bootstrap_training_dataset
        self.bootstrap_size_ratio = bootstrap_size_ratio
        self.num_candidate_attributes = num_candidate_attributes
        self.num_candidate_attributes_ratio = num_candidate_attributes_ratio
        # Sparse-oblique splits (reference oblique.cc; RF is the paper's
        # original home — Tomita et al. JMLR'20): same per-tree batched
        # recast as the GBT learner — P projections per tree as one MXU
        # matmul, quantile-binned, competing as extra candidate columns.
        if split_axis not in ("AXIS_ALIGNED", "SPARSE_OBLIQUE"):
            raise ValueError(f"Unknown split_axis {split_axis!r}")
        from ydf_tpu.ops.oblique import WEIGHT_TYPES

        if sparse_oblique_weights not in WEIGHT_TYPES:
            raise ValueError(
                f"Unknown sparse_oblique_weights {sparse_oblique_weights!r}"
            )
        self.split_axis = split_axis
        self.sparse_oblique_num_projections_exponent = (
            sparse_oblique_num_projections_exponent
        )
        self.sparse_oblique_projection_density_factor = (
            sparse_oblique_projection_density_factor
        )
        self.sparse_oblique_weights = sparse_oblique_weights
        self.sparse_oblique_max_num_projections = (
            sparse_oblique_max_num_projections
        )
        self.winner_take_all = winner_take_all
        # OOB evaluation / permutation importances (reference
        # random_forest.proto compute_oob_performances — default true — and
        # compute_oob_variable_importances; both require bootstrapping,
        # random_forest.cc:566-571).
        self.compute_oob_performances = compute_oob_performances
        self.compute_oob_variable_importances = compute_oob_variable_importances
        self.max_frontier = max_frontier
        self.uplift_treatment = uplift_treatment
        # Honest trees (reference honest-split partitioning,
        # training.cc:4836-4860): per tree, a random half of the examples
        # grows the STRUCTURE and the other half estimates the LEAF
        # values — decoupling selection from estimation (Wager & Athey).
        self.honest = honest
        self.honest_ratio_leaf_examples = honest_ratio_leaf_examples
        # Deadline in seconds for the whole train() call; the chunked
        # tree loop stops within one chunk and keeps the trees finished
        # so far (reference abstract_learner.proto:52-64).
        self.maximum_training_duration = maximum_training_duration
        # jax.sharding.Mesh: data-parallel (rows over the data axis) and/or
        # feature-parallel (columns over the feature axis) training — the
        # per-layer histogram contraction all-reduces over the data axis
        # via GSPMD (see ydf_tpu/parallel/mesh.py).
        self.mesh = mesh

    # ------------------------------------------------------------------ #

    def _candidate_features(self, F: int) -> int:
        """Per-node attribute sample size; 0 selects the reference defaults:
        sqrt(F) for classification, F/3 for regression
        (`random_forest.cc` num_candidate_attributes semantics)."""
        if self.num_candidate_attributes_ratio > 0:
            return max(int(np.ceil(self.num_candidate_attributes_ratio * F)), 1)
        if self.num_candidate_attributes > 0:
            return min(self.num_candidate_attributes, F)
        if self.num_candidate_attributes == 0:
            if self.task == Task.CLASSIFICATION:
                return max(int(np.ceil(np.sqrt(F))), 1)
            return max(int(np.ceil(F / 3)), 1)
        return -1

    def train(self, data: InputData, valid: Optional[InputData] = None):
        from ydf_tpu.utils.profiling import StageTimer, maybe_trace

        # maximum_training_duration clock starts at train() entry.
        self._train_start = time.monotonic()
        timer = StageTimer()
        with timer.stage("ingest_bin"):
            prep = self._prepare(data, timer=timer)
        binner = prep["binner"]
        release_device_inputs()  # this job's table goes up: no second one
        bins = jnp.asarray(prep["bins"])
        set_bits = prep.get("set_bits")
        if set_bits is not None:
            set_bits = jnp.asarray(set_bits)
        w_base = jnp.asarray(prep["sample_weights"])
        n, F = bins.shape

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
            if prep.get("raw_numerical") is not None:
                x_raw = np.asarray(prep["raw_numerical"], np.float32)
            else:
                ds_r = prep["dataset"]
                x_raw = np.zeros((n, Fn), np.float32)
                for i, name in enumerate(binner.feature_names[:Fn]):
                    if ds_r.dataspec.has_column(name) and name in ds_r.data:
                        x_raw[:, i] = ds_r.encoded_numerical(name)
                    else:
                        x_raw[:, i] = binner.impute_values[i]

        tcodes = None
        if self.task in (Task.CATEGORICAL_UPLIFT, Task.NUMERICAL_UPLIFT):
            if not self.uplift_treatment:
                raise ValueError("Uplift tasks require uplift_treatment=")
            ds = prep["dataset"]
            tcol = ds.dataspec.column_by_name(self.uplift_treatment)
            if tcol.vocab_size > 3:
                raise NotImplementedError(
                    "Only binary treatments are supported"
                )
            tcodes = ds.encoded_categorical(self.uplift_treatment)

        if self.mesh is not None:
            from ydf_tpu.parallel import mesh as pmesh

            dp = self.mesh.shape[pmesh.DATA_AXIS]
            fp = self.mesh.shape.get(pmesh.FEATURE_AXIS, 1)
            # Same pattern as the GBT mesh path (gbt.py): pad rows (zero
            # weight → no effect on statistics), then shard everything.
            arrays = [
                np.asarray(bins),
                np.asarray(w_base),
                np.asarray(prep["labels"]),
            ]
            if set_bits is not None:
                arrays.append(np.asarray(set_bits))
            if tcodes is not None:
                # Pad rows get treatment code 0 (= missing/OOV) → excluded
                # from every per-arm statistic via t_known below.
                arrays.append(np.asarray(tcodes))
            arrays, _ = pmesh.pad_rows_to_multiple(arrays, dp)
            bins_np, w_np, labels_np = arrays[:3]
            if fp > 1:
                # Feature-parallel: pad the feature axis with constant-zero
                # columns (never a valid split — their right-side count is
                # 0) and shard [n, F] over (data, feature). Per-node
                # candidate sampling skips the pad columns via
                # num_valid_features below.
                fpad = (-bins_np.shape[1]) % fp
                if fpad:
                    bins_np = np.pad(bins_np, ((0, 0), (0, fpad)))
                bins = pmesh.shard_batch_and_features(self.mesh, bins_np)
            else:
                bins = pmesh.shard_batch(self.mesh, bins_np)
            w_base = pmesh.shard_batch(self.mesh, w_np)
            prep["labels"] = pmesh.shard_batch(self.mesh, labels_np)
            if set_bits is not None:
                set_bits = pmesh.shard_batch(self.mesh, arrays[3])
            if tcodes is not None:
                tcodes = pmesh.shard_batch(
                    self.mesh, arrays[3 + (set_bits is not None)]
                )
            if x_raw is not None:
                # Pad rows (zero weight) contribute only to the unweighted
                # per-tree projection quantiles — a <dp/n perturbation of
                # candidate bin boundaries (same note as the GBT path).
                x_raw = np.pad(
                    x_raw, ((0, bins.shape[0] - x_raw.shape[0]), (0, 0))
                )
                x_raw = pmesh.shard_batch(self.mesh, x_raw)
            # OOB bookkeeping indexes labels and weights together — keep
            # the padded row count consistent (pad rows carry zero weight,
            # so they never enter the OOB accumulators).
            prep["sample_weights"] = w_np
            n = bins.shape[0]

        if self.task in (Task.CATEGORICAL_UPLIFT, Task.NUMERICAL_UPLIFT):
            # Treatment-effect trees (reference uplift.h; RF uplift as in
            # sim_pte_categorical_uplift_rf): binary treatment, binary or
            # numerical outcome, Euclidean-divergence splits. tcodes was
            # encoded (and under a mesh, padded + sharded) above.
            rule = UpliftEuclideanRule()
            tcodes = jnp.asarray(tcodes)
            t01 = (tcodes == 2).astype(jnp.float32)
            # OOV/missing treatment (code <= 0) is excluded entirely —
            # the reference ignores the treatment OOV item
            # (decision_tree.proto:66-69).
            t_known = jnp.asarray((tcodes >= 1).astype(np.float32))
            if self.task == Task.CATEGORICAL_UPLIFT:
                classes = prep["classes"]
                if len(classes) != 2:
                    raise NotImplementedError(
                        "Only binary outcomes are supported"
                    )
                # Positive outcome = second dictionary item (reference:
                # outcome categorical value 2).
                y = jnp.asarray(
                    (prep["labels"] == 1).astype(np.float32)
                )
            else:
                classes = None
                y = jnp.asarray(prep["labels"].astype(np.float32))

            # Statistics are linear in the bootstrap weight:
            # stats(w) = stat_basis * w[:, None] — the factored form the
            # shared compiled chunk executable consumes (see _train_rf).
            stat_basis = jnp.stack(
                [
                    t_known * (1.0 - t01),
                    t_known * (1.0 - t01) * y,
                    t_known * t01,
                    t_known * t01 * y,
                    t_known,
                ],
                axis=1,
            )
        elif self.task == Task.CLASSIFICATION:
            classes = prep["classes"]
            C = len(classes)
            rule = ClassificationRule(num_classes=C)
            y = jnp.asarray(prep["labels"])
            y_onehot = jax.nn.one_hot(y, C, dtype=jnp.float32)
            stat_basis = jnp.concatenate(
                [y_onehot, jnp.ones((n, 1), jnp.float32)], 1
            )
        else:
            classes = None
            rule = RegressionRule()
            y = jnp.asarray(prep["labels"].astype(np.float32))
            stat_basis = jnp.stack(
                [y, jnp.square(y), jnp.ones((n,), jnp.float32)], axis=1
            )

        from ydf_tpu.config import resolve_max_frontier

        tree_cfg = TreeConfig(
            max_depth=self.max_depth,
            # "auto" shrinks the frontier/bin axes of the dense layer
            # buffers to the dataset (config.py resolvers).
            max_frontier=resolve_max_frontier(
                self.max_frontier, n, self.min_examples
            ),
            num_bins=binner.num_bins,
            min_examples=self.min_examples,
        )
        # Cap node capacity by what the dataset can actually produce: every
        # leaf holds ≥1 example (min_examples is a *weighted* count, so
        # n//min_examples would under-size with weights), hence ≤ 2n-1
        # nodes; the grower additionally guards allocation overflow.
        max_nodes = min(tree_cfg.max_nodes, 2 * n + 3)
        cand = self._candidate_features(binner.num_features)

        oob_enabled = (
            self.compute_oob_performances
            and self.bootstrap_training_dataset
            and self.task in (Task.CLASSIFICATION, Task.REGRESSION)
        )
        deadline = (
            self._train_start + self.maximum_training_duration
            if self.maximum_training_duration
            and self.maximum_training_duration > 0
            else None
        )
        with timer.stage("device_loop"), maybe_trace("rf_train"):
            stacked, leaf_values, oob, trained = _train_rf(
            bins, w_base,
            set_bits=set_bits,
            stat_basis=stat_basis, rule=rule, tree_cfg=tree_cfg,
            max_nodes=max_nodes, num_trees=self.num_trees,
            bootstrap=self.bootstrap_training_dataset,
            candidate_features=cand,
            num_numerical=binner.num_numerical,
            x_raw=None if x_raw is None else jnp.asarray(x_raw),
            obl_P=obl_P,
            obl_density=self.sparse_oblique_projection_density_factor,
            obl_weight_type=self.sparse_oblique_weights,
            obl_weight_range=None,
            num_valid_features=(
                binner.num_scalar
                if bins.shape[1] > binner.num_scalar
                else None
            ),
            seed=self.random_seed,
            honest_ratio=(
                self.honest_ratio_leaf_examples if self.honest else 0.0
            ),
            winner_take_all=(
                self.winner_take_all and self.task == Task.CLASSIFICATION
            ),
            compute_oob=oob_enabled,
            oob_importances=(
                oob_enabled and self.compute_oob_variable_importances
            ),
            deadline=deadline,
        )
        self._trained_trees = trained  # may be < num_trees on deadline

        if obl_P > 0:
            # Remap grow-time feature ids [Fn, Fn+P) (projection block)
            # onto the Forest convention (projections after ALL real
            # features; categoricals shift back by P) and attach per-tree
            # projection vectors + bin cutpoints — same as the GBT path.
            stacked_tuple, obl_w, obl_b = stacked
            Freal = binner.num_features
            feat = np.asarray(stacked_tuple.feature)
            in_block = (feat >= Fn) & (feat < Fn + obl_P)
            remapped = np.where(
                in_block,
                Freal + (feat - Fn),
                np.where(feat >= Fn + obl_P, feat - obl_P, feat),
            )
            stacked_tuple = stacked_tuple._replace(
                feature=remapped.astype(np.int32)
            )
            forest = forest_from_stacked_trees(
                stacked_tuple, leaf_values, binner.boundaries,
                oblique_weights=np.asarray(obl_w),
                oblique_boundaries=np.asarray(obl_b),
            )
        else:
            forest = forest_from_stacked_trees(
                stacked, leaf_values, binner.boundaries
            )
        model = RandomForestModel(
            task=self.task,
            label=self.label,
            classes=classes,
            dataspec=prep["dataset"].dataspec,
            binner=binner,
            forest=forest,
            max_depth=self.max_depth,
            winner_take_all=self.winner_take_all,
            extra_metadata=(
                {"uplift_treatment": self.uplift_treatment}
                if self.uplift_treatment
                else None
            ),
        )
        if oob is not None:
            with timer.stage("oob_finalize"):
                self._attach_oob(model, oob, prep, binner)
        model.training_profile = timer.finish()
        return model

    def _attach_oob(self, model, oob, prep, binner):
        """OOB evaluation + optional permutation importances from the
        accumulated per-example OOB votes (reference
        EvaluateOOBPredictions / ComputeVariableImportancesFrom-
        AccumulatedPredictions, random_forest.cc:1147-1283)."""
        from ydf_tpu.metrics import evaluate_predictions

        labels = np.asarray(prep["labels"])
        w_all = np.asarray(prep["sample_weights"])
        cnt = np.asarray(oob["count"])
        # Rows the mesh path padded in carry zero weight and zero count.
        idx = cnt > 0

        def finalize(sums):
            sums = np.asarray(sums, np.float64)
            if self.task == Task.CLASSIFICATION:
                proba = sums[idx] / np.maximum(
                    sums[idx].sum(axis=1, keepdims=True), 1e-12
                )
                return proba
            return sums[idx, 0] / cnt[idx]

        def oob_eval(sums):
            return evaluate_predictions(
                self.task,
                labels[idx],
                finalize(sums),
                classes=prep.get("classes"),
                weights=w_all[idx],
            )

        base = oob_eval(oob["sum"])
        model.oob_evaluation = {
            "source": "oob",
            "num_examples": int(idx.sum()),
            "num_trees": getattr(self, "_trained_trees", self.num_trees),
            "metrics": {k: float(v) for k, v in base.metrics.items()},
        }
        if "sum_shuffled" not in oob:
            return
        # MEAN_DECREASE_IN_* / MEAN_INCREASE_IN_RMSE — the reference's
        # ComputePermutationFeatureImportance naming (variable_importance.h).
        decrease_acc, increase_rmse = [], []
        for f, name in enumerate(binner.feature_names):
            ev = oob_eval(oob["sum_shuffled"][f])
            if self.task == Task.CLASSIFICATION:
                decrease_acc.append(
                    {
                        "feature": name,
                        "importance": float(base.accuracy - ev.accuracy),
                    }
                )
            else:
                increase_rmse.append(
                    {
                        "feature": name,
                        "importance": float(ev.rmse - base.rmse),
                    }
                )
        vi = {}
        if decrease_acc:
            decrease_acc.sort(key=lambda d: -d["importance"])
            vi["MEAN_DECREASE_IN_ACCURACY"] = decrease_acc
        if increase_rmse:
            increase_rmse.sort(key=lambda d: -d["importance"])
            vi["MEAN_INCREASE_IN_RMSE"] = increase_rmse
        model.oob_variable_importances = vi


def _train_rf(
    bins, w_base, *, stat_basis, rule, tree_cfg: TreeConfig, max_nodes,
    num_trees, bootstrap, candidate_features, num_numerical, seed,
    honest_ratio=0.0, winner_take_all=False, compute_oob=False,
    oob_importances=False, set_bits=None, num_valid_features=None,
    x_raw=None, obl_P=0, obl_density=2.0, obl_weight_type="BINARY",
    obl_weight_range=None, deadline=None, chunk_trees=25,
):
    """Chunked driver over the module-level jitted chunk executable.

    `stat_basis` is U [n, S] with per-example statistics linear in the
    bootstrap weight: stats(w) = U * w[:, None] — the factored form that
    lets ONE compiled executable serve every task (the per-task stats_fn
    closures of the old design forced a recompile on every train() call;
    profiling showed ~30 s of the measured 252 s abalone row was exactly
    that recompilation).

    Trees are trained in chunks of `chunk_trees` by one reusable
    executable; the tail chunk overshoots and is sliced off (overshoot
    trees are masked out of the OOB accumulators). Chunking also gives
    `deadline` (maximum_training_duration) a stopping point within one
    chunk, mirroring the reference's deadline check
    (abstract_learner.proto:52-64). Per-tree RNG is fold_in(seed, t), so
    chunking never changes the produced model."""
    n, F = bins.shape
    P = obl_P
    if P > 0 and oob_importances:
        raise NotImplementedError(
            "compute_oob_variable_importances with SPARSE_OBLIQUE "
            "(shuffled-attribute routing through projections is not "
            "implemented; OOB evaluation itself works)"
        )
    # Real (unpadded) scalar columns — under feature-parallel padding the
    # bins matrix carries trailing constant-zero columns that are neither
    # split candidates nor permutation-importance targets.
    Fr = F if num_valid_features is None else num_valid_features
    Fs = 0 if set_bits is None else set_bits.shape[1]
    V = rule.num_outputs

    C = max(1, min(int(chunk_trees), num_trees))
    if compute_oob:
        carry = (
            jnp.zeros((n, V), jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros(
                (Fr + Fs if oob_importances else 0, n, V), jnp.float32
            ),
        )
    else:
        carry = (
            jnp.zeros((0, V), jnp.float32),
            jnp.zeros((0,), jnp.float32),
            jnp.zeros((0, 0, V), jnp.float32),
        )

    static = dict(
        chunk=C, rule=rule, max_depth=tree_cfg.max_depth,
        frontier=tree_cfg.frontier, num_bins=tree_cfg.num_bins,
        min_examples=tree_cfg.min_examples, max_nodes=max_nodes,
        bootstrap=bootstrap, candidate_features=candidate_features,
        num_numerical=num_numerical,
        num_valid_features=num_valid_features,
        honest_ratio=honest_ratio, winner_take_all=winner_take_all,
        compute_oob=compute_oob, oob_importances=oob_importances,
        obl_P=obl_P, obl_density=obl_density,
        obl_weight_type=obl_weight_type,
        obl_weight_range=obl_weight_range,
    )
    parts = []
    start = 0
    trained = 0
    while start < num_trees:
        carry, out = _rf_run_chunk(
            bins, w_base, stat_basis, set_bits, x_raw,
            jnp.asarray(start, jnp.int32),
            jnp.asarray(num_trees, jnp.int32),
            jnp.asarray(seed, jnp.uint32), carry, **static,
        )
        # Force to host per chunk: bounds device memory at C trees and
        # gives the deadline check real (not async-queued) timing.
        parts.append(jax.tree.map(np.asarray, out))
        start += C
        trained = min(start, num_trees)
        if (
            deadline is not None
            and start < num_trees
            and time.monotonic() >= deadline
        ):
            break

    def cat(field):
        return np.concatenate([p[field] for p in parts], 0)[:trained]

    trees = grower.TreeArrays(
        *[cat(f) for f in grower.TreeArrays._fields[:-1]],
        num_nodes=cat("num_nodes"),
    )
    lvs = cat("lv")
    oob_out = None
    if compute_oob:
        oob_out = {"sum": carry[0], "count": carry[1]}
        if oob_importances:
            oob_out["sum_shuffled"] = carry[2]
    if P > 0:
        return (trees, cat("obl_w"), cat("obl_b")), lvs, oob_out, trained
    return trees, lvs, oob_out, trained


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk", "rule", "max_depth", "frontier", "num_bins",
        "min_examples", "max_nodes", "bootstrap", "candidate_features",
        "num_numerical", "num_valid_features", "honest_ratio",
        "winner_take_all", "compute_oob", "oob_importances", "obl_P",
        "obl_density", "obl_weight_type", "obl_weight_range",
    ),
)
def _rf_run_chunk(
    bins, w_base, stat_basis, set_bits, x_raw, t_start, n_valid, seed,
    carry,
    *, chunk, rule, max_depth, frontier, num_bins, min_examples,
    max_nodes, bootstrap, candidate_features, num_numerical,
    num_valid_features, honest_ratio, winner_take_all, compute_oob,
    oob_importances, obl_P, obl_density, obl_weight_type,
    obl_weight_range,
):
    """One compiled executable training `chunk` trees [t_start,
    t_start+chunk); cached across train() calls (module-level jit — the
    per-call closure of the old design could never hit the cache).
    Trees with index >= n_valid are tail overshoot: still computed (the
    executable's shape is fixed) but masked out of the OOB carry and
    sliced off by the driver."""
    n, F = bins.shape
    P = obl_P
    Fn = num_numerical
    B = num_bins
    Fr = F if num_valid_features is None else num_valid_features
    Fs = 0 if set_bits is None else set_bits.shape[1]
    V = rule.num_outputs
    tree_cfg = TreeConfig(
        max_depth=max_depth, max_frontier=frontier, num_bins=num_bins,
        min_examples=min_examples,
    )

    def stats_fn(w):
        return stat_basis * w[:, None]

    def tree_vote(lv, leaves):
        """Per-example vote of one tree (reference
        AddClassificationLeafToAccumulator: winner-take-all → one-hot of
        the top class, else the leaf distribution)."""
        v = lv[leaves]  # [n, V]
        if winner_take_all:
            v = jax.nn.one_hot(jnp.argmax(v, axis=1), V, dtype=jnp.float32)
        return v

    def one_tree(carry, t):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
        k_boot, k_grow, k_honest, k_obl = jax.random.split(key, 4)
        if bootstrap:
            draws = jax.random.poisson(k_boot, 1.0, (n,)).astype(
                jnp.float32
            )
            w = w_base * draws
        else:
            w = w_base
        if honest_ratio > 0.0:
            # Honest split: structure half vs leaf-estimation half.
            est = jax.random.bernoulli(k_honest, honest_ratio, (n,))
            w_grow = w * (1.0 - est)
            w_leaf = w * est
        else:
            w_grow = w
        if P > 0:
            # Per-tree sparse projections (shared sampler,
            # ops/oblique.py): one MXU matmul + quantile binning; the
            # projection columns splice in after the numericals and
            # compete as ordinary candidates.
            from ydf_tpu.ops.oblique import (
                sample_projection_coefficients,
            )

            W = sample_projection_coefficients(
                k_obl, P, Fn,
                density=obl_density,
                weight_type=obl_weight_type,
                weight_range=obl_weight_range,
            )
            z = x_raw @ W.T  # [n, P]
            qs = jnp.linspace(1.0 / B, 1.0 - 1.0 / B, B - 1)
            bnd = jnp.quantile(z, qs, axis=0).T  # [P, B-1]
            zb = jax.vmap(
                lambda b, zz: jnp.searchsorted(b, zz, side="right")
            )(bnd, z.T).astype(jnp.uint8).T
            grow_bins = jnp.concatenate(
                [bins[:, :Fn], zb, bins[:, Fn:]], axis=1
            )
            grow_Fn = Fn + P
            grow_valid = (
                None
                if num_valid_features is None
                else num_valid_features + P
            )
        else:
            W = jnp.zeros((0, 0), jnp.float32)
            bnd = jnp.zeros((0, B - 1), jnp.float32)
            grow_bins = bins
            grow_Fn = num_numerical
            grow_valid = num_valid_features
        res = grower.grow_tree(
            grow_bins, stats_fn(w_grow), k_grow,
            rule=rule,
            max_depth=tree_cfg.max_depth,
            frontier=tree_cfg.frontier,
            max_nodes=max_nodes,
            num_bins=tree_cfg.num_bins,
            num_numerical=grow_Fn,
            min_examples=tree_cfg.min_examples,
            candidate_features=candidate_features,
            num_valid_features=grow_valid,
            set_bits=set_bits,
        )
        if honest_ratio > 0.0:
            # Re-estimate every LEAF's statistics from the held-out
            # half, routed through the grown structure. Internal nodes
            # keep their grow-half stats (they feed cover/SHAP), and a
            # leaf that drew no estimation examples falls back to its
            # grow-half stats instead of an all-zero value.
            est_stats = stats_fn(w_leaf)
            seg = jax.ops.segment_sum(
                est_stats, res.leaf_id,
                num_segments=res.tree.leaf_stats.shape[0],
            )
            use_est = (
                res.tree.is_leaf & (seg[..., -1] > 0)
            )[:, None]
            leaf_stats = jnp.where(use_est, seg, res.tree.leaf_stats)
            tree = res.tree._replace(leaf_stats=leaf_stats)
            lv = rule.leaf_value(leaf_stats, None)
        else:
            tree = res.tree
            lv = rule.leaf_value(res.tree.leaf_stats, None)

        if compute_oob:
            # Out-of-bag accumulation (reference
            # UpdateOOBPredictionsWithNewTree, random_forest.cc:1082):
            # examples the bootstrap did NOT draw vote on this tree.
            # Tail-overshoot trees (t >= n_valid) are masked out —
            # they are computed to keep the executable's shape fixed
            # but must not vote.
            oob = (draws == 0.0) & (w_base > 0.0)
            oob_f = oob.astype(jnp.float32) * (
                t < n_valid
            ).astype(jnp.float32)
            oob_sum, oob_cnt, oob_shuf = carry
            oob_sum = oob_sum + tree_vote(lv, res.leaf_id) * oob_f[:, None]
            oob_cnt = oob_cnt + oob_f
            if oob_importances:
                # Per-feature shuffled accumulators: the value of
                # feature f is taken from a random other row before
                # routing (reference GetLeafWithSwappedAttribute via a
                # per-tree permutation). One routed pass per feature,
                # vmapped.
                def shuffled_vote(f, k_f):
                    perm = jax.random.permutation(k_f, n)
                    col = bins[perm, jnp.minimum(f, F - 1)]
                    b2 = jnp.where(
                        jnp.arange(F)[None, :] == f, col[:, None], bins
                    )
                    if Fs > 0:
                        # Set features (index block [Fr, Fr+Fs)):
                        # shuffle the whole packed row of the feature.
                        s2 = jnp.where(
                            (jnp.arange(Fs)[None, :, None] + Fr) == f,
                            set_bits[perm], set_bits,
                        )
                    else:
                        s2 = None
                    leaves = routing.route_tree_bins(
                        tree, b2, tree_cfg.max_depth, x_set=s2,
                        num_scalar=num_valid_features,
                    )
                    return tree_vote(lv, leaves)

                k_shuf = jax.random.split(
                    jax.random.fold_in(key, 3), Fr + Fs
                )
                votes = jax.vmap(shuffled_vote)(
                    jnp.arange(Fr + Fs), k_shuf
                )  # [Fr+Fs, n, V]
                oob_shuf = oob_shuf + votes * oob_f[None, :, None]
            carry = (oob_sum, oob_cnt, oob_shuf)
        return carry, (tree, lv, W, bnd)

    carry, (trees, lvs, Ws, bnds) = jax.lax.scan(
        one_tree, carry, t_start + jnp.arange(chunk)
    )
    out = {f: getattr(trees, f) for f in trees._fields}
    out["lv"] = lvs
    out["obl_w"] = Ws
    out["obl_b"] = bnds
    return carry, out
