"""Gradient Boosted Trees learner — the flagship trainer.

Re-design of the reference GBT learner
(`ydf/learner/gradient_boosted_trees/gradient_boosted_trees.cc:1187`
TrainWithStatusImpl) as ONE jitted `lax.scan` over boosting iterations:

  reference boosting loop (:1460)            this file
  ──────────────────────────────             ─────────────────────────────
  loss->UpdateGradients        (:1477)   →   loss.grad_hess      (in scan)
  SampleTrainingExamples       (:1488)   →   bernoulli weight mask
  per-dim decision_tree::Train (:1539)   →   ops.grower.grow_tree (fully
                                             batched layer-synchronous)
  UpdatePredictions            (:1576)   →   leaf_value[leaf_id] add
  validation loss + early stop (:404)    →   per-iter losses recorded;
                                             model truncated at the argmin
                                             validation loss (same final
                                             model as the reference's
                                             early-stopping truncation)

The entire training loop — gradients, histograms, split search, routing —
runs on device with static shapes; the host only orchestrates setup and the
final truncation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import signal
import threading
import time
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.config import Task, TreeConfig
from ydf_tpu.utils import failpoints, log, telemetry
from ydf_tpu.dataset.dataset import (
    Dataset,
    InputData,
    release_device_inputs,
)
from ydf_tpu.learners.generic import GenericLearner
from ydf_tpu.learners.losses import make_loss
from ydf_tpu.models.forest import forest_from_stacked_trees
from ydf_tpu.models.gbt_model import GradientBoostedTreesModel
from ydf_tpu.ops import device_loop, grower, lookup
from ydf_tpu.ops.histogram import (
    StatColumn,
    narrow_columns_per_slot,
    resolve_hist_impl,
    resolve_hist_quant,
)
from ydf_tpu.ops.routing import apply_leaf_values, route_tree_bins
from ydf_tpu.ops.split_rules import HessianGainRule


def _bool_column(values: np.ndarray) -> np.ndarray:
    """Boolean event indicator from a raw column (bool/int/float/strings).
    Missing values (NaN) are an error — silently treating them as observed
    events would corrupt Cox gradients and the C-index."""
    v = np.asarray(values)
    if v.dtype.kind in ("O", "U", "S"):
        low = np.char.lower(v.astype(str))
        truthy = np.isin(low, ("1", "true", "t", "yes", "y"))
        falsy = np.isin(low, ("0", "false", "f", "no", "n"))
        if not (truthy | falsy).all():
            bad = v[~(truthy | falsy)][:3]
            raise ValueError(
                "event-observed column contains missing or unrecognized "
                f"values (e.g. {bad.tolist()!r}); expected true/false "
                "indicators"
            )
        return truthy
    if v.dtype.kind == "f" and np.isnan(v).any():
        raise ValueError(
            "event-observed column contains missing values (NaN)"
        )
    return v.astype(bool)


class GradientBoostedTreesLearner(GenericLearner):
    """API-compatible with the reference PYDF learner
    (`specialized_learners_pre_generated.py:1290`); hyperparameter names and
    defaults follow the reference generic hyperparameters."""

    def __init__(
        self,
        label: str,
        task: Task = Task.CLASSIFICATION,
        num_trees: int = 300,
        shrinkage: float = 0.1,
        max_depth: int = 6,
        min_examples: int = 5,
        subsample: float = 1.0,
        validation_ratio: float = 0.1,
        early_stopping: str = "LOSS_INCREASE",
        early_stopping_num_trees_look_ahead: int = 30,
        l2_regularization: float = 0.0,
        num_candidate_attributes: int = -1,
        num_candidate_attributes_ratio: float = -1.0,
        loss: str = "DEFAULT",
        ranking_group: Optional[str] = None,
        ndcg_truncation: int = 5,
        ranking_max_group_size: Optional[int] = None,
        label_event_observed: Optional[str] = None,
        label_entry_age: Optional[str] = None,
        max_frontier="auto",
        sampling_method: str = "RANDOM",
        goss_alpha: float = 0.2,
        goss_beta: float = 0.1,
        selective_gradient_boosting_ratio: float = 0.01,
        apply_link_function: bool = True,
        dart_dropout: float = 0.0,
        split_axis: str = "AXIS_ALIGNED",
        sparse_oblique_num_projections_exponent: float = 1.0,
        sparse_oblique_projection_density_factor: float = 2.0,
        sparse_oblique_weights: str = "BINARY",
        sparse_oblique_weights_power_of_two_min_exponent: int = -3,
        sparse_oblique_weights_power_of_two_max_exponent: int = 3,
        sparse_oblique_weights_integer_minimum: int = -5,
        sparse_oblique_weights_integer_maximum: int = 5,
        sparse_oblique_max_num_projections: int = 64,
        mhld_oblique_max_num_attributes: int = 4,
        numerical_vector_sequence_num_anchors: int = 16,
        numerical_vector_sequence_enable_closer_than: bool = True,
        numerical_vector_sequence_enable_projected_more_than: bool = True,
        monotonic_constraints: Optional[dict] = None,
        working_dir: Optional[str] = None,
        resume_training: bool = False,
        resume_training_snapshot_interval_trees: int = 50,
        maximum_training_duration: float = -1.0,
        use_hessian_gain: bool = True,
        features: Optional[Sequence[str]] = None,
        weights: Optional[str] = None,
        random_seed: int = 123456,
        mesh=None,
        distributed_workers: Optional[Sequence[str]] = None,
        distributed_membership=None,
        **kwargs,
    ):
        super().__init__(
            label=label, task=task, features=features, weights=weights,
            random_seed=random_seed, **kwargs,
        )
        self.num_trees = num_trees
        self.shrinkage = shrinkage
        self.max_depth = max_depth
        self.min_examples = min_examples
        self.subsample = subsample
        self.validation_ratio = validation_ratio
        self.early_stopping = early_stopping
        self.early_stopping_num_trees_look_ahead = early_stopping_num_trees_look_ahead
        self.l2_regularization = l2_regularization
        # Split gain from the loss's hessians (XGBoost's criterion,
        # ops/split_rules.HessianGainRule): the only gain this grower
        # has. Upstream YDF defaults to false (gain from the variance
        # of the gradients); COVERAGE.md records the departure.
        if not use_hessian_gain:
            raise NotImplementedError(
                "use_hessian_gain=False (upstream YDF's default: split "
                "gain from the variance of the gradients) is not "
                "implemented; GradientBoostedTreesLearner always grows "
                "by the hessian gain"
            )
        self.use_hessian_gain = use_hessian_gain
        self.num_candidate_attributes = num_candidate_attributes
        self.num_candidate_attributes_ratio = num_candidate_attributes_ratio
        self.loss = loss
        self.ranking_group = ranking_group
        self.ndcg_truncation = ndcg_truncation
        # Cap on the documents of a query that train (None: every one
        # does); a longer query keeps its first ones, with a warning
        # (ranking_loss.build_rank_groups).
        self.ranking_max_group_size = ranking_max_group_size
        # Survival analysis (reference train config label_event_observed /
        # label_entry_age, Cox loss loss_imp_cox.cc): the label column is
        # the departure age.
        self.label_event_observed = label_event_observed
        self.label_entry_age = label_entry_age
        self.max_frontier = max_frontier
        # Sampling per iteration (reference :1488-1522): RANDOM (stochastic
        # GBM via `subsample`), GOSS, or SELGB (ranking only).
        if sampling_method not in ("RANDOM", "GOSS", "SELGB"):
            raise ValueError(
                f"Unknown sampling_method {sampling_method!r}; expected "
                "RANDOM, GOSS or SELGB"
            )
        if sampling_method == "SELGB" and task != Task.RANKING:
            # Reference: "Selective Gradient Boosting is only applicable to
            # ranking" (gradient_boosted_trees.cc:3053-3056).
            raise ValueError("sampling_method=SELGB requires task=RANKING")
        self.sampling_method = sampling_method
        self.goss_alpha = goss_alpha
        self.goss_beta = goss_beta
        self.selective_gradient_boosting_ratio = selective_gradient_boosting_ratio
        self.apply_link_function = apply_link_function
        # DART dropout rate over past iterations (reference :1468-1474).
        self.dart_dropout = dart_dropout
        # Sparse-oblique splits (Tomita et al. JMLR'20; reference
        # ydf/learner/decision_tree/oblique.cc). TPU-first formulation:
        # per TREE (not per node-candidate), sample P random sparse
        # projections, compute them as ONE [n, Fn] x [Fn, P] matmul on the
        # MXU, quantile-bin the projected values, and let the histogram
        # split search treat them as P extra numerical columns.
        # MHLD_OBLIQUE (reference oblique.h Canete-Sifuentes et al.;
        # oblique.cc FindBestConditionMHLDObliqueTemplate): projections
        # from Linear Discriminant Analysis instead of random sampling.
        # TPU recast: per-tree batched LDA — scatter matrices via MXU
        # matmuls, masked feature subsets, Cholesky + eigh (the
        # TPU-supported symmetric form of the reference's
        # SW⁻¹·SB eigenproblem, oblique.cc SolveLDA).
        if split_axis not in (
            "AXIS_ALIGNED", "SPARSE_OBLIQUE", "MHLD_OBLIQUE"
        ):
            raise ValueError(f"Unknown split_axis {split_axis!r}")
        self.mhld_oblique_max_num_attributes = mhld_oblique_max_num_attributes
        if sparse_oblique_weights not in (
            "BINARY", "CONTINUOUS", "POWER_OF_TWO", "INTEGER"
        ):
            raise ValueError(
                f"Unknown sparse_oblique_weights {sparse_oblique_weights!r}"
            )
        self.split_axis = split_axis
        # POWER_OF_TWO / INTEGER coefficient ranges (reference
        # decision_tree.proto PowerOfTwoWeights/IntegerWeights defaults).
        self.sparse_oblique_weights_power_of_two_min_exponent = (
            sparse_oblique_weights_power_of_two_min_exponent
        )
        self.sparse_oblique_weights_power_of_two_max_exponent = (
            sparse_oblique_weights_power_of_two_max_exponent
        )
        self.sparse_oblique_weights_integer_minimum = (
            sparse_oblique_weights_integer_minimum
        )
        self.sparse_oblique_weights_integer_maximum = (
            sparse_oblique_weights_integer_maximum
        )
        self.sparse_oblique_num_projections_exponent = (
            sparse_oblique_num_projections_exponent
        )
        self.sparse_oblique_projection_density_factor = (
            sparse_oblique_projection_density_factor
        )
        self.sparse_oblique_weights = sparse_oblique_weights
        self.sparse_oblique_max_num_projections = sparse_oblique_max_num_projections
        # NUMERICAL_VECTOR_SEQUENCE anchor splits (reference
        # vector_sequence.cc; decision_tree.proto numerical_vector_sequence
        # config, defaults :433-442). The reference samples
        # num_random_selected_anchors per (node, feature); the TPU
        # formulation samples `num_anchors` per kind per (tree, feature)
        # and evaluates them as extra binned candidate columns — the same
        # per-tree recast as the sparse-oblique projections.
        self.numerical_vector_sequence_num_anchors = (
            numerical_vector_sequence_num_anchors
        )
        self.numerical_vector_sequence_enable_closer_than = (
            numerical_vector_sequence_enable_closer_than
        )
        self.numerical_vector_sequence_enable_projected_more_than = (
            numerical_vector_sequence_enable_projected_more_than
        )
        self._supports_vs_features = True
        # Monotonic constraints: {feature_name: +1|-1} (reference
        # training.h:160-168 ApplyConstraintOnNode). Split search rejects
        # order-violating cuts; a post-training pass clamps leaf values to
        # propagated bounds, guaranteeing global monotonicity. For
        # multiclass the guarantee is per-CLASS RAW SCORE monotonicity
        # (each of the K trees per iteration is constrained — the
        # reference's semantics); softmax probabilities are ratios of
        # monotone quantities and are NOT individually monotone.
        self.monotonic_constraints = dict(monotonic_constraints or {})
        # Checkpoint/resume (reference DeploymentConfig.cache_path +
        # resume_training, abstract_learner.proto:52-64): with a
        # working_dir, the boosting loop snapshots its full state every
        # `resume_training_snapshot_interval_trees` iterations and
        # `resume_training=True` continues from the latest snapshot.
        self.working_dir = working_dir
        self.resume_training = resume_training
        self.resume_training_snapshot_interval_trees = (
            resume_training_snapshot_interval_trees
        )
        # Deadline for the whole train() call in seconds; the boosting
        # loop runs chunked and stops within one chunk of the deadline,
        # keeping the trees finished so far (reference
        # abstract_learner.proto:52-64 maximum_training_duration and the
        # GBT deadline check, gradient_boosted_trees.cc:1314-1325).
        self.maximum_training_duration = maximum_training_duration
        # Test-only fault injection (reference MaybeSimulateFailure,
        # worker.cc:415-452): abort after N snapshots. The generalized
        # version is the failpoint registry (utils/failpoints.py, site
        # "gbt.chunk"); this hook predates it and stays for the old
        # tests. _preempt_after_chunks simulates a SIGTERM delivered
        # during chunk N (same code path as a real signal, minus the OS
        # delivery — tests/test_chaos.py covers the real one too).
        self._abort_after_chunks = None
        self._preempt_after_chunks = None
        # jax.sharding.Mesh with axes (data, feature): distributes training
        # via GSPMD sharding annotations (see ydf_tpu/parallel/mesh.py — the
        # TPU-native replacement of the reference's gRPC worker protocol).
        self.mesh = mesh
        # Distributed training over the RPC worker substrate
        # (reference distribute/ manager–worker protocol): "host:port"
        # addresses of running `ydf_tpu.cli worker` processes.
        # Requires training from a sharded DatasetCache; the cache's
        # layout selects the mode — feature_shards=N trains
        # feature-parallel (parallel/dist_gbt.py), row_shards=N
        # row-parallel with streamed shard loads, sum-merged
        # histograms, and row-sharded validation / distributed early
        # stopping (parallel/dist_row.py; both together = hybrid).
        # Either way the model is bit-identical to the single-machine
        # build (docs/distributed_training.md). Combined with
        # working_dir/resume_training, the manager snapshots at tree
        # boundaries and survives its own preemption/death — a new
        # manager resumes bit-identically via the epoch-fenced worker
        # reattach (docs/distributed_training.md "Resume").
        self.distributed_workers = (
            list(distributed_workers) if distributed_workers else None
        )
        # Elastic membership: a parallel.dist_gbt.MembershipChannel the
        # manager polls at every tree boundary — workers join/leave a
        # RUNNING distributed train without changing a bit of the model
        # (docs/distributed_training.md "Elastic membership").
        self.distributed_membership = distributed_membership

    # ------------------------------------------------------------------ #

    @classmethod
    def hyperparameter_templates(cls) -> dict:
        """Predefined hyperparameter sets (reference
        gradient_boosted_trees_hparams_templates.cc:31,46). The reference's
        BEST_FIRST_GLOBAL growing strategy maps to our frontier-capped
        breadth-first growth (top-gain splits survive frontier overflow),
        so the templates translate to the knobs that exist here."""
        return {
            "better_defaultv1": {"max_depth": 8, "max_frontier": 32},
            "benchmark_rank1v1": {
                "max_depth": 8,
                "max_frontier": 32,
                "split_axis": "SPARSE_OBLIQUE",
            },
        }

    def train(
        self, data: InputData, valid: Optional[InputData] = None
    ) -> GradientBoostedTreesModel:
        from ydf_tpu.utils.profiling import StageTimer, maybe_trace

        # An operator's trace (YDF_TPU_PROFILE_DIR) holds all of
        # train(): the host spans before the device loop too.
        with maybe_trace("gbt_train"):
            return self._train(data, valid, StageTimer())

    def _train(self, data, valid, timer) -> GradientBoostedTreesModel:
        # Root of the telemetry trace (its children: the timer's
        # `ydf.*` spans and `train.chunk`); recorded via emit_span at
        # the end.
        _t_train0_ns = time.perf_counter_ns()
        # Deadline clock starts at train() entry — ingestion and binning
        # count against maximum_training_duration like the reference's.
        deadline = (
            time.monotonic() + self.maximum_training_duration
            if self.maximum_training_duration
            and self.maximum_training_duration > 0
            else None
        )
        with timer.stage("ingest_bin"):
            prep = self._prepare(
                data, valid=valid, targets=False, timer=timer
            )
            # The six arrays this job would hand the boosting loop may
            # be on the device already, kept with the Dataset by an
            # earlier job that made the same ones: then nothing that
            # goes by the row runs on the host, here or in `split`.
            inputs_key = self._device_inputs_key(data, valid, prep)
            kept = (
                prep["dataset"].device_inputs(prep["bins"], inputs_key)
                if inputs_key is not None
                else None
            )
            timer.counts["device_loop.inputs_cached"] = float(
                kept is not None
            )
            if kept is None:
                # Before this job's table goes up, never after: the
                # chip holds one table.
                release_device_inputs()
                if "sample_weights" not in prep:
                    with timer.stage("ingest_bin.targets"):
                        self._encode_targets(prep)
        binner = prep["binner"]
        bins_all = prep["bins"]
        set_all = prep.get("set_bits")
        labels_all = prep.get("labels")  # None, like w_all, where `kept`
        w_all = prep.get("sample_weights")
        n = bins_all.shape[0]
        num_classes = len(prep.get("classes", [])) or 1

        from ydf_tpu.learners.losses import CustomLoss
        from ydf_tpu.learners.ranking_loss import (
            LambdaMartNdcg,
            build_rank_groups,
        )

        if isinstance(self.loss, CustomLoss):
            loss_obj = self.loss
        else:
            loss_obj = make_loss(self.loss, self.task, num_classes)
        grouped = isinstance(loss_obj, LambdaMartNdcg)
        if grouped:
            # Non-NDCG losses (e.g. SQUARED_ERROR on a ranking task) need no
            # group structure and skip all of it.
            if self.task != Task.RANKING:
                raise ValueError(
                    f"{loss_obj.name} requires task=Task.RANKING"
                )
            loss_obj = dataclasses.replace(
                loss_obj, ndcg_truncation=self.ndcg_truncation
            )
        if self.task == Task.RANKING and self.ranking_group is None:
            raise ValueError("Task.RANKING requires ranking_group=")

        # The query structure of a group-structured loss, as the compiled
        # program takes it: (training RankGroups, validation RankGroups or
        # None, the counters' facts). It is kept with the Dataset beside
        # the six arrays, so only a job that makes them makes it: the
        # rows ordered by query here, bucketed and sent below.
        rank = None
        codes_all = order_all = None
        with timer.stage("rank_groups"):
            if kept is not None:
                rank = kept[6] if len(kept) > 6 else None
            elif self.task == Task.RANKING:
                codes_all, order_all = _rows_by_query(
                    prep["dataset"].data[self.ranking_group], ordered=grouped
                )

        ev_all = en_all = None
        if self.task == Task.SURVIVAL_ANALYSIS:
            if self.label_event_observed is None:
                raise ValueError(
                    "Task.SURVIVAL_ANALYSIS requires label_event_observed="
                )
            ev_all = _bool_column(
                prep["dataset"].data[self.label_event_observed]
            )
            if self.label_entry_age is not None:
                en_all = np.asarray(
                    prep["dataset"].data[self.label_entry_age], np.float64
                )

        # --- validation extraction (reference :1243): deterministic split
        # of the training set, unless an explicit valid dataset is given.
        # Ranking splits whole query groups, like the reference; under a
        # group-structured loss both parts' rows go by query (tr_codes,
        # va_codes: each row's query, non-decreasing).
        tr_codes = va_codes = None
        tr_order = va_order = None  # of a part whose rows no split gathers
        set_tr = set_va = None
        vs_all = prep.get("vs")  # (values, lengths, missing) or None
        vs_tr = vs_va = None  # (values, lengths) pairs
        if vs_all is not None:
            vs_all = (vs_all[0], vs_all[1])
        with timer.stage("split"):
            if kept is not None:
                bins_tr, y_tr, w_tr, bins_va, y_va, w_va = kept[:6]
            elif "valid_bins" in prep:
                bins_tr, y_tr, w_tr = bins_all, labels_all, w_all
                bins_va = prep["valid_bins"]
                y_va = prep["valid_labels"]
                w_va = prep.get(
                    "valid_weights", np.ones((bins_va.shape[0],), np.float32)
                )
                set_tr, set_va = set_all, prep.get("valid_set_bits")
                if vs_all is not None:
                    vs_tr = vs_all
                    vv = prep.get("valid_vs")
                    vs_va = (vv[0], vv[1]) if vv is not None else None
                if grouped:
                    tr_order = order_all
                    va_codes, va_order = _rows_by_query(
                        prep["valid_dataset"].data[self.ranking_group],
                        ordered=True,
                    )
                    va_codes = va_codes[va_order]
            elif (
                self.validation_ratio > 0
                and self.early_stopping != "NONE"
                and not (self.distributed_workers and prep.get("cache"))
            ):
                # Distributed training from a cache skips this branch: the
                # slice bins_all[tr_idx] would materialize the FULL bin
                # matrix on the manager, defeating row-parallel memory
                # scaling. The row-parallel entry point recomputes the
                # identical deterministic split (same rng expressions) and
                # ships index sets; feature-parallel still rejects
                # validation with its targeted error.
                rng = np.random.RandomState(self.random_seed)
                if codes_all is not None:
                    # The queries are numbered by their sorted distinct
                    # ids; the first `nvg` of a seeded permutation of
                    # those numbers validate. Never every query (nor
                    # none): a single-query dataset trains without
                    # validation rather than on nothing.
                    nq = int(codes_all.max()) + 1
                    nvg = min(
                        max(int(nq * self.validation_ratio), 1), nq - 1
                    )
                    va_query = np.zeros((nq,), bool)
                    va_query[rng.permutation(nq)[:nvg]] = True
                    if grouped:
                        in_va = va_query[codes_all[order_all]]
                        tr_idx, va_idx = order_all[~in_va], order_all[in_va]
                        tr_codes, va_codes = codes_all[tr_idx], codes_all[va_idx]
                    else:
                        in_va = va_query[codes_all]
                        va_idx = np.flatnonzero(in_va)
                        tr_idx = np.flatnonzero(~in_va)
                    rows_tr, rows_va = len(tr_idx), len(va_idx)
                    if (
                        grouped
                        and self.split_axis == "AXIS_ALIGNED"
                        and set_all is None
                        and vs_all is None
                    ):
                        # Whole queries split, so how many rows train
                        # goes by the table; the program's shapes should
                        # not: both parts get a capacity (zero-weight
                        # rows of no query at their ends).
                        rows_tr, rows_va = _split_capacities(
                            n, rows_tr, rows_va, self.validation_ratio
                        )
                    bins_tr = _take_rows(bins_all, tr_idx, rows_tr)
                    bins_va = _take_rows(bins_all, va_idx, rows_va)
                else:
                    tr_idx, va_idx, bins_tr, bins_va = _split_rows(
                        prep.get("dataset"), bins_all, rng,
                        self.random_seed, self.validation_ratio,
                    )
                y_tr, w_tr = (
                    _take_rows(a, tr_idx, bins_tr.shape[0])
                    for a in (labels_all, w_all)
                )
                y_va, w_va = (
                    _take_rows(a, va_idx, bins_va.shape[0])
                    for a in (labels_all, w_all)
                )
                if set_all is not None:
                    set_tr, set_va = set_all[tr_idx], set_all[va_idx]
                if vs_all is not None:
                    vs_tr = (vs_all[0][tr_idx], vs_all[1][tr_idx])
                    vs_va = (vs_all[0][va_idx], vs_all[1][va_idx])
            else:
                bins_tr, y_tr, w_tr = bins_all, labels_all, w_all
                bins_va = np.zeros((0, bins_all.shape[1]), np.uint8)
                y_va = np.zeros((0,), labels_all.dtype)
                w_va = np.zeros((0,), np.float32)
                if set_all is not None:
                    set_tr = set_all
                    set_va = np.zeros(
                        (0,) + set_all.shape[1:], set_all.dtype
                    )
                if vs_all is not None:
                    vs_tr = vs_all
                    vs_va = (
                        np.zeros((0,) + vs_all[0].shape[1:], np.float32),
                        np.zeros((0,) + vs_all[1].shape[1:], np.int32),
                    )
                if grouped:
                    tr_order = order_all
            if tr_order is not None:
                # All of a part trains (or validates): its rows by query.
                def by_query(a, order):
                    if a is None or order is None:
                        return a
                    if isinstance(a, tuple):
                        return tuple(x[order] for x in a)
                    return a[order]

                tr_codes = codes_all[tr_order]
                bins_tr, y_tr, w_tr, set_tr, vs_tr = (
                    by_query(a, tr_order)
                    for a in (bins_tr, y_tr, w_tr, set_tr, vs_tr)
                )
                bins_va, y_va, w_va, set_va, vs_va = (
                    by_query(a, va_order)
                    for a in (bins_va, y_va, w_va, set_va, vs_va)
                )

        if self.mesh is not None:
            from ydf_tpu.parallel import mesh as pmesh

            dp = self.mesh.shape[pmesh.DATA_AXIS]
            fp = self.mesh.shape[pmesh.FEATURE_AXIS]
            # Padding rows carry zero weight → no effect on stats/losses.
            # Done BEFORE ranking-group registration so group row indices
            # and registered sizes refer to the final (padded) arrays.
            tr_arrays = [bins_tr, y_tr, w_tr] + (
                [set_tr] if set_tr is not None else []
            )
            tr_arrays, _ = pmesh.pad_rows_to_multiple(tr_arrays, dp)
            bins_tr, y_tr, w_tr = tr_arrays[:3]
            if set_tr is not None:
                set_tr = tr_arrays[3]
            if bins_va.shape[0] > 0:
                va_arrays = [bins_va, y_va, w_va] + (
                    [set_va] if set_va is not None else []
                )
                va_arrays, _ = pmesh.pad_rows_to_multiple(va_arrays, dp)
                bins_va, y_va, w_va = va_arrays[:3]
                if set_va is not None:
                    set_va = va_arrays[3]
            if fp > 1:
                # Pad the feature axis too: constant-zero columns can never
                # yield a valid split (their right-side count is 0).
                fpad = (-bins_tr.shape[1]) % fp
                if fpad:
                    bins_tr = np.pad(bins_tr, ((0, 0), (0, fpad)))
                    bins_va = np.pad(bins_va, ((0, 0), (0, fpad)))
                shard_bins = pmesh.shard_batch_and_features
            else:
                shard_bins = pmesh.shard_batch
            bins_tr = shard_bins(self.mesh, bins_tr)
            y_tr = pmesh.shard_batch(self.mesh, y_tr)
            w_tr = pmesh.shard_batch(self.mesh, w_tr)
            bins_va = shard_bins(self.mesh, bins_va)
            y_va = pmesh.shard_batch(self.mesh, y_va)
            w_va = pmesh.shard_batch(self.mesh, w_va)
            # Which devices each sharded training input landed on
            # (training_logs["mesh"]; chip_smoke.py --mesh checks it).
            mesh_input_devices = {
                name: sorted(d.id for d in a.sharding.device_set)
                for name, a in (
                    ("bins", bins_tr), ("labels", y_tr), ("weights", w_tr)
                )
            }
            if set_tr is not None:
                # Set features ride the data axis only (replicated over the
                # feature axis — their per-item stats all-reduce via the
                # same GSPMD contraction as the scalar histogram).
                set_tr = pmesh.shard_batch(self.mesh, set_tr)
                if set_va is not None and set_va.shape[0] > 0:
                    set_va = pmesh.shard_batch(self.mesh, set_va)
            if vs_tr is not None:
                # Vector sequences ride the data axis; per-tree anchor
                # sampling gathers across shards, the projection kernel is
                # row-local.
                def _pad_shard_vs(pair, target_rows):
                    v, l = np.asarray(pair[0]), np.asarray(pair[1])
                    v = np.pad(
                        v,
                        [(0, target_rows - v.shape[0])]
                        + [(0, 0)] * (v.ndim - 1),
                    )
                    l = np.pad(l, [(0, target_rows - l.shape[0]), (0, 0)])
                    return (
                        pmesh.shard_batch(self.mesh, v),
                        pmesh.shard_batch(self.mesh, l),
                    )

                vs_tr = _pad_shard_vs(vs_tr, bins_tr.shape[0])
                if vs_va is not None and vs_va[0].shape[0] > 0:
                    vs_va = _pad_shard_vs(vs_va, bins_va.shape[0])

        from ydf_tpu.learners.survival_loss import CoxProportionalHazardLoss

        if isinstance(loss_obj, CoxProportionalHazardLoss):
            if self.task != Task.SURVIVAL_ANALYSIS:
                raise ValueError(
                    "COX_PROPORTIONAL_HAZARD requires "
                    "task=Task.SURVIVAL_ANALYSIS"
                )
            if "valid_bins" in prep:
                ev_tr, en_tr = ev_all, en_all
                vds = prep["valid_dataset"]
                ev_va = _bool_column(vds.data[self.label_event_observed])
                en_va = (
                    np.asarray(vds.data[self.label_entry_age], np.float64)
                    if self.label_entry_age
                    else None
                )
            elif bins_va.shape[0] > 0:
                ev_tr = ev_all[tr_idx]
                ev_va = ev_all[va_idx]
                en_tr = None if en_all is None else en_all[tr_idx]
                en_va = None if en_all is None else en_all[va_idx]
            else:
                ev_tr, en_tr, ev_va, en_va = ev_all, en_all, None, None

            def _pad_survival(y_arr, ev, en):
                """Mesh row padding: pad rows become censored examples whose
                entry AND departure precede every real update time, so they
                leave every risk set before any event — their gradients and
                loss terms are exactly zero (their zero training weight
                already keeps them out of the tree statistics)."""
                y_np = np.asarray(y_arr, np.float64).copy()
                nr = len(ev)
                p = len(y_np) - nr
                en_full = (
                    np.zeros((nr,), np.float64)
                    if en is None
                    else np.asarray(en, np.float64)
                )
                if p == 0:
                    return y_np, ev, en_full, nr
                tpad = min(
                    float(y_np[:nr].min()), float(en_full.min())
                ) - 1.0
                y_np[nr:] = tpad
                ev = np.concatenate([np.asarray(ev, bool), np.zeros(p, bool)])
                en_full = np.concatenate([en_full, np.full((p,), tpad)])
                return y_np, ev, en_full, nr

            y_reg, ev_reg, en_reg, n_real = _pad_survival(y_tr, ev_tr, en_tr)
            loss_obj.register_survival(
                "train", y_reg, ev_reg, en_reg, num_real=n_real,
                weights=(
                    np.asarray(w_tr) if self.weights is not None else None
                ),
            )
            if bins_va.shape[0] > 0:
                yv_reg, evv_reg, env_reg, nv_real = _pad_survival(
                    y_va, ev_va, en_va
                )
                loss_obj.register_survival(
                    "valid", yv_reg, evv_reg, env_reg, num_real=nv_real,
                    weights=(
                        np.asarray(w_va)
                        if self.weights is not None
                        else None
                    ),
                )
        K = loss_obj.num_dims
        F = binner.num_features
        if self.num_candidate_attributes_ratio > 0:
            cand = max(int(np.ceil(self.num_candidate_attributes_ratio * F)), 1)
        elif self.num_candidate_attributes > 0:
            cand = min(self.num_candidate_attributes, F)
        else:
            cand = -1

        from ydf_tpu.config import resolve_max_frontier

        tree_cfg = TreeConfig(
            max_depth=self.max_depth,
            # "auto" shrinks the frontier/bin axes of the dense layer
            # buffers to the dataset (config.py resolvers); the binner
            # already resolved num_bins against the training rows.
            max_frontier=resolve_max_frontier(
                self.max_frontier, bins_tr.shape[0], self.min_examples
            ),
            num_bins=binner.num_bins,
            min_examples=self.min_examples,
        )
        rule = HessianGainRule(l2=self.l2_regularization)

        # Example-routing impl for the whole boosting loop, resolved ONCE
        # at the env boundary (YDF_TPU_ROUTE_IMPL, validated eagerly) and
        # passed explicitly down the stack — unlike the histogram env
        # vars, the closure cache IS keyed on it (the fused-gradient path
        # changes the scan carry structure). The fused kernels are CPU
        # custom calls: the TPU backend and the GSPMD mesh path keep the
        # XLA chain, which is bit-identical anyway (docs/row_routing.md).
        from ydf_tpu.config import is_tpu_backend
        from ydf_tpu.ops.routing_native import (
            resolve_route_fuse,
            resolve_route_impl,
        )

        route_impl = resolve_route_impl(None)
        route_fuse = resolve_route_fuse()
        if route_impl == "native" and (
            self.mesh is not None
            or is_tpu_backend()
            or self.dart_dropout > 0.0
            or K > 1
        ):
            # TPU/mesh: the fused kernels are CPU custom calls. DART and
            # multi-output (K > 1) losses: their preds updates live in
            # XLA expressions whose FMA-contraction choices are compiler
            # whim — measured on the multiclass path, the ORACLE program
            # itself contracts some class columns and not others, so no
            # kernel can replicate it and a native-routed program would
            # drift a ulp from the second iteration on
            # (docs/row_routing.md). These configs keep the XLA routing
            # wholesale; the bench family (binomial/MSE, K = 1) gets the
            # fused path.
            route_impl = "xla"

        monotone = None
        if self.monotonic_constraints:
            # Multi-dim losses (multiclass) work unchanged: each of the K
            # trees per iteration is single-output, so per-tree split
            # rejection and leaf clamping make every class score monotone
            # (the reference restricts monotonic GBT only to
            # use_hessian_gain=true, gradient_boosted_trees.cc:478-483 —
            # which is this grower's gain).
            dirs = [0] * binner.num_features
            for name, d in self.monotonic_constraints.items():
                if name not in binner.feature_names:
                    raise ValueError(f"Unknown monotonic feature {name!r}")
                idx = binner.feature_names.index(name)
                if idx >= binner.num_numerical:
                    raise ValueError(
                        f"Monotonic constraint on non-numerical {name!r}"
                    )
                dirs[idx] = int(np.sign(d))
            # Feature-parallel padding appends zero columns; extend.
            dirs += [0] * (bins_tr.shape[1] - len(dirs))
            monotone = tuple(dirs)

        # --- sparse-oblique projections: encode raw numerical features
        # (imputed) split the same way as the bins; the boosting loop
        # projects them per tree with one MXU matmul.
        obl_P = 0
        x_tr_raw = x_va_raw = None
        if self.split_axis == "MHLD_OBLIQUE":
            if self.task != Task.CLASSIFICATION:
                # The reference restriction (oblique.cc:689-692): LDA
                # needs class labels.
                raise ValueError(
                    "MHLD_OBLIQUE is only available for classification; "
                    "use SPARSE_OBLIQUE for other tasks"
                )
            if self.monotonic_constraints:
                raise ValueError(
                    "monotonic constraints are not supported with "
                    "MHLD_OBLIQUE (LDA coefficients cannot be sign-forced)"
                )
        if (
            self.split_axis in ("SPARSE_OBLIQUE", "MHLD_OBLIQUE")
            and binner.num_numerical > 0
        ):
            obl_P = int(
                np.ceil(
                    binner.num_numerical
                    ** self.sparse_oblique_num_projections_exponent
                )
            )
            obl_P = min(max(obl_P, 2), self.sparse_oblique_max_num_projections)

            def enc_raw(ds):
                m = np.zeros((ds.num_rows, binner.num_numerical), np.float32)
                for i, name in enumerate(
                    binner.feature_names[: binner.num_numerical]
                ):
                    if ds.dataspec.has_column(name) and name in ds.data:
                        m[:, i] = ds.encoded_numerical(name)
                    else:
                        m[:, i] = binner.impute_values[i]
                return m

            if prep.get("raw_numerical") is not None:
                # Out-of-core path: the cache stored the imputed float32
                # matrix; the cache dataset carries no feature columns.
                x_all = np.asarray(prep["raw_numerical"], np.float32)
            else:
                x_all = enc_raw(prep["dataset"])
            if "valid_bins" in prep:
                x_tr_raw = x_all
                x_va_raw = enc_raw(prep["valid_dataset"])
            elif bins_va.shape[0] > 0:
                x_tr_raw, x_va_raw = x_all[tr_idx], x_all[va_idx]
            else:
                x_tr_raw = x_all
                x_va_raw = np.zeros((0, binner.num_numerical), np.float32)
            if tr_order is not None:
                x_tr_raw = x_tr_raw[tr_order]
                if va_order is not None:
                    x_va_raw = x_va_raw[va_order]
            if self.mesh is not None:
                # Match the row padding applied to bins_tr/bins_va above
                # (pad rows carry zero weight; their raw values only enter
                # the unweighted projection quantiles, a <dp/n perturbation
                # of the bin boundaries), then ride the data axis. The
                # per-tree projection matmul and quantile reduce over the
                # sharded example axis — GSPMD inserts the collectives.
                x_tr_raw = np.pad(
                    x_tr_raw,
                    ((0, bins_tr.shape[0] - x_tr_raw.shape[0]), (0, 0)),
                )
                x_tr_raw = pmesh.shard_batch(self.mesh, x_tr_raw)
                if x_va_raw.shape[0] > 0:
                    x_va_raw = np.pad(
                        x_va_raw,
                        ((0, bins_va.shape[0] - x_va_raw.shape[0]), (0, 0)),
                    )
                    x_va_raw = pmesh.shard_batch(self.mesh, x_va_raw)

        # --- vector-sequence anchor candidates per tree (reference
        # vector_sequence.cc; see ops/vector_sequence.py).
        vs_Ac = vs_Ap = 0
        if vs_tr is not None and binner.num_vs > 0:
            if self.numerical_vector_sequence_enable_closer_than:
                vs_Ac = self.numerical_vector_sequence_num_anchors
            if self.numerical_vector_sequence_enable_projected_more_than:
                vs_Ap = self.numerical_vector_sequence_num_anchors
            if vs_Ac + vs_Ap == 0:
                vs_tr = vs_va = None
        else:
            vs_tr = vs_va = None
        vs_Pv = (vs_Ac + vs_Ap) * binner.num_vs if vs_tr is not None else 0

        if grouped and rank is None:
            with timer.stage("rank_groups"):
                # Bucketed and sent here, after the mesh's padding rows
                # are there (they belong to no query), counted with the
                # bytes this job sends.
                groups_tr, facts = build_rank_groups(
                    tr_codes, bins_tr.shape[0],
                    self.ranking_max_group_size, self.ndcg_truncation,
                )
                groups_va = None
                if bins_va.shape[0] > 0:
                    groups_va, _ = build_rank_groups(
                        va_codes, bins_va.shape[0],
                        self.ranking_max_group_size, self.ndcg_truncation,
                    )
                device_loop.count_h2d(sum(
                    a.nbytes for a in jax.tree.leaves((groups_tr, groups_va))
                ))
                rank = jax.tree.map(jnp.asarray, (groups_tr, groups_va)) + (
                    facts,
                )
        if rank is not None:
            for name, value in rank[2].items():
                timer.counts["device_loop." + name] = value

        with timer.stage("device_loop"), _flight_guard():
            if self.distributed_workers:
                # Feature-parallel manager–worker training: the bins
                # never materialize on this host (workers hold the
                # cache's feature shards); returns the same
                # (stacked trees, leaf values, logs) layout as
                # _train_gbt, so everything below is shared.
                forest_stacked, leaf_values, logs = _train_gbt_distributed(
                    self, prep, nv_rows=bins_va.shape[0],
                    loss_obj=loss_obj, rule=rule, tree_cfg=tree_cfg,
                    candidate_features=cand, obl_P=obl_P,
                    vs_Pv=vs_Pv, set_tr=set_tr,
                )
            else:
                with timer.stage("device_loop.h2d"):
                    # Every array this train() sends to the device, in
                    # one place: the enqueue is the span, the bytes the
                    # counter. (Under a mesh they were placed when they
                    # were sharded, above; the bytes are the same.) A
                    # job whose inputs were kept sends nothing.
                    inputs = dict(
                        bins_tr=bins_tr, y_tr=y_tr, w_tr=w_tr,
                        bins_va=bins_va, y_va=y_va, w_va=w_va,
                        x_tr_raw=x_tr_raw, x_va_raw=x_va_raw,
                        set_tr=set_tr, set_va=set_va,
                        vs_tr=vs_tr, vs_va=vs_va,
                    )
                    if kept is None:
                        device_loop.count_h2d(
                            sum(a.nbytes for a in jax.tree.leaves(inputs))
                        )
                        inputs = jax.tree.map(jnp.asarray, inputs)
                        if inputs_key is not None:
                            prep["dataset"].keep_device_inputs(
                                bins_all, inputs_key,
                                [inputs[k] for k in _KEPT_INPUTS]
                                + ([rank] if rank is not None else []),
                            )
                stat_columns = _hist_stat_columns(
                    self.weights, self.sampling_method, loss_obj
                )
                timer.counts["device_loop.hist_columns_per_slot"] = float(
                    narrow_columns_per_slot(
                        stat_columns, resolve_hist_quant(None)
                    )
                )
                forest_stacked, leaf_values, logs = _train_gbt(
            **inputs,
            stat_columns=stat_columns,
            groups_tr=rank[0] if rank is not None else None,
            groups_va=rank[1] if rank is not None else None,
            timer=timer,
            loss_obj=loss_obj,
            rule=rule,
            tree_cfg=tree_cfg,
            num_trees=self.num_trees,
            shrinkage=self.shrinkage,
            subsample=self.subsample,
            candidate_features=cand,
            num_numerical=binner.num_numerical,
            # Under feature parallelism the bin matrix gains constant-zero
            # pad columns; per-node feature sampling must ignore them.
            num_valid_features=(
                binner.num_scalar
                if bins_tr.shape[1] > binner.num_scalar
                else None
            ),
            seed=self.random_seed,
            sampling=self.sampling_method,
            goss_alpha=self.goss_alpha,
            goss_beta=self.goss_beta,
            selgb_ratio=self.selective_gradient_boosting_ratio,
            dart_dropout=self.dart_dropout,
            oblique_P=obl_P,
            oblique_density=self.sparse_oblique_projection_density_factor,
            oblique_weight_type=self.sparse_oblique_weights,
            oblique_mode=(
                "MHLD" if self.split_axis == "MHLD_OBLIQUE" else "SPARSE"
            ),
            mhld_max_attributes=self.mhld_oblique_max_num_attributes,
            num_label_classes=num_classes,
            oblique_weight_range=(
                (
                    self.sparse_oblique_weights_power_of_two_min_exponent,
                    self.sparse_oblique_weights_power_of_two_max_exponent,
                )
                if self.sparse_oblique_weights == "POWER_OF_TWO"
                else (
                    self.sparse_oblique_weights_integer_minimum,
                    self.sparse_oblique_weights_integer_maximum,
                )
                if self.sparse_oblique_weights == "INTEGER"
                else None
            ),
            monotone=monotone,
            vs_Ac=vs_Ac,
            vs_Ap=vs_Ap,
            route_impl=route_impl,
            route_fuse=route_fuse,
            cache_dir=self.working_dir,
            resume=self.resume_training,
            snapshot_interval=self.resume_training_snapshot_interval_trees,
            abort_after_chunks=self._abort_after_chunks,
            preempt_after_chunks=self._preempt_after_chunks,
            early_stop_lookahead=(
                self.early_stopping_num_trees_look_ahead
                if self.early_stopping == "LOSS_INCREASE"
                else 0
            ),
            deadline=deadline,
        )

        with timer.stage("finalize"):
            train_losses = np.asarray(logs["train_loss"])
            valid_losses = np.asarray(logs["valid_loss"])
            has_valid = bins_va.shape[0] > 0 or bool(
                # Row-parallel distributed training row-shards the
                # validation split onto the workers (bins_va never
                # materializes here); its real per-iteration valid losses
                # ride logs["valid_loss"] and drive the same argmin trim.
                logs.get("distributed", {}).get("has_valid")
            )
            if has_valid and self.early_stopping != "NONE":
                best_iter = int(np.argmin(valid_losses))
                num_iters = best_iter + 1
            else:
                # A deadline (maximum_training_duration) may have stopped the
                # chunked loop early: keep the iterations actually trained.
                num_iters = min(self.num_trees, len(train_losses))

            # [T, K, ...] → [T*K, ...] iteration-major (the reference's
            # num_trees_per_iter layout, gradient_boosted_trees.h:57-151).
            def flatten(a):
                a = np.asarray(a)
                return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])[
                    : num_iters * K
                ]

            stacked = grower.TreeArrays(
                feature=flatten(forest_stacked.feature),
                threshold_bin=flatten(forest_stacked.threshold_bin),
                is_cat=flatten(forest_stacked.is_cat),
                is_set=flatten(forest_stacked.is_set),
                cat_mask=flatten(forest_stacked.cat_mask),
                left=flatten(forest_stacked.left),
                right=flatten(forest_stacked.right),
                is_leaf=flatten(forest_stacked.is_leaf),
                leaf_stats=flatten(forest_stacked.leaf_stats),
                num_nodes=flatten(forest_stacked.num_nodes[..., None])[:, 0],
            )
            if obl_P > 0 or vs_Pv > 0:
                # Tree features: [0, Fn) numerical, [Fn, Fn+P) oblique
                # projections, [Fn+P, Fn+P+Pv) vector-sequence anchors,
                # [Fn+P+Pv, ...) categorical(+set). Remap to the Forest
                # convention (projection blocks after ALL real features, same
                # order) and attach each tree's per-projection data + bin
                # cutpoints. Both blocks shift by the same Freal - Fn.
                Fn = binner.num_numerical
                Freal = binner.num_features
                PB = obl_P + vs_Pv
                feat = np.asarray(stacked.feature)
                in_block = (feat >= Fn) & (feat < Fn + PB)
                remapped = np.where(
                    in_block,
                    Freal + (feat - Fn),
                    np.where(feat >= Fn + PB, feat - PB, feat),
                )
                stacked = stacked._replace(feature=remapped.astype(np.int32))

                def per_iter(key):
                    return np.repeat(np.asarray(logs[key]), K, axis=0)[
                        : num_iters * K
                    ]

                kwargs = {}
                if obl_P > 0:
                    kwargs["oblique_weights"] = per_iter("oblique_w")
                    kwargs["oblique_boundaries"] = per_iter("oblique_b")
                if vs_Pv > 0:
                    Tn = num_iters * K
                    per_kind = [True] * vs_Ac + [False] * vs_Ap
                    kwargs["vs_anchors"] = per_iter("vs_a")
                    kwargs["vs_boundaries"] = per_iter("vs_b")
                    kwargs["vs_feat"] = np.broadcast_to(
                        np.repeat(
                            np.arange(binner.num_vs, dtype=np.int32),
                            vs_Ac + vs_Ap,
                        ),
                        (Tn, vs_Pv),
                    )
                    kwargs["vs_is_closer"] = np.broadcast_to(
                        np.tile(np.array(per_kind, bool), binner.num_vs),
                        (Tn, vs_Pv),
                    )
                forest = forest_from_stacked_trees(
                    stacked, flatten(leaf_values), binner.boundaries, **kwargs
                )
            else:
                forest = forest_from_stacked_trees(
                    stacked, flatten(leaf_values), binner.boundaries
                )

            if self.monotonic_constraints:
                forest = _clamp_monotone_leaves(
                    forest, binner, self.monotonic_constraints
                )

            initial_predictions = np.asarray(logs["initial_predictions"])
            chunk_walls = logs.get("chunk_walls") or []
            model = GradientBoostedTreesModel(
                task=self.task,
                label=self.label,
                classes=prep.get("classes"),
                dataspec=prep["dataset"].dataspec,
                binner=binner,
                forest=forest,
                initial_predictions=initial_predictions,
                num_trees_per_iter=K,
                max_depth=self.max_depth,
                loss_name=loss_obj.name,
                apply_link_function=self.apply_link_function,
                training_logs={
                    "train_loss": train_losses[:num_iters].tolist(),
                    "valid_loss": valid_losses[:num_iters].tolist()
                    if has_valid
                    else None,
                    "num_trees": num_iters,
                    # Iterations the boosting loop actually ran — less than the
                    # requested num_trees when in-loop early stopping fired
                    # (reference early_stopping.h:29-66).
                    "num_trees_trained": int(train_losses.shape[0]),
                    # One YDF-style record per TRAINED boosting iteration
                    # (reference TrainingLogs; the tuner/early-stopping
                    # consumable). Seconds are per-chunk wall attributed
                    # uniformly within the chunk (docs/observability.md).
                    "iterations": _iteration_records(
                        train_losses, valid_losses, has_valid, chunk_walls
                    ),
                },
                extra_metadata=self._model_metadata(),
            )
            if "distributed" in logs:
                # Exchange accounting of the feature-parallel run (worker
                # count, reduce bytes, per-verb RPC p50s, recoveries) — the
                # bench family's source (bench.measure_distributed_family).
                model.training_logs["distributed"] = logs["distributed"]
            else:
                # What "auto" resolved to for this train on this backend
                # (chip_smoke.py asserts the TPU answers).
                model.training_logs["implementations"] = {
                    "hist_impl": resolve_hist_impl("auto"),
                    "hist_quant": resolve_hist_quant(None),
                    "route_impl": route_impl,
                }
            if self.mesh is not None:
                model.training_logs["mesh"] = {
                    "shape": dict(self.mesh.shape),
                    "input_devices": mesh_input_devices,
                }
        # What making this job's inputs cost is kept with the Dataset the
        # caller handed over (not a re-typed copy of it), by the job that
        # made them; every job on it reports it as `dataset.*`.
        owner = data if isinstance(data, Dataset) else prep["dataset"]
        timer.keep_build(owner.build_seconds, made=kept is None)
        # Per-stage wall breakdown (reference Monitoring per-stage logs);
        # `device_loop.compile` is the boosting program's build inside it.
        model.training_profile = timer.finish()
        if telemetry.ENABLED:
            _emit_chunk_spans(chunk_walls)
            telemetry.emit_span(
                "train",
                _t_train0_ns,
                time.perf_counter_ns() - _t_train0_ns,
                {
                    "rows": int(n),
                    "num_trees": int(train_losses.shape[0]),
                    "learner": "GRADIENT_BOOSTED_TREES",
                },
            )
            # End-of-train memory accounting: the MemoryLedger snapshot
            # (per-subsystem bytes + RSS figures) rides training_logs
            # beside the per-iteration records — the training half of
            # bench.py's train_peak_rss_bytes headline field.
            try:
                model.training_logs["memory"] = telemetry.ledger(
                ).snapshot()
            except Exception:
                pass
            telemetry.flush()
        return model

    def _device_inputs_key(self, data, valid, prep) -> Optional[tuple]:
        """Everything besides the bin matrix that this job's six device
        arrays (`_KEPT_INPUTS`) depend on, or None for a job whose
        inputs are not kept with the Dataset: one that is not given a
        Dataset (a dict or a frame makes a new one every call), brings
        its own validation rows, has survival columns, set or
        vector-sequence features or raw features for oblique splits, or
        places its arrays across workers or a mesh. A ranking job's
        arrays also depend on its group column, and the query structure
        kept beside them (`RankGroups`) on the cap and the truncation."""
        if (
            not isinstance(data, Dataset)
            or valid is not None
            or self.task
            not in (Task.CLASSIFICATION, Task.REGRESSION, Task.RANKING)
            or prep.get("set_bits") is not None
            or prep.get("vs") is not None
            or self.split_axis != "AXIS_ALIGNED"
            or self.distributed_workers
            or self.mesh is not None
        ):
            return None
        splits = self.validation_ratio > 0 and self.early_stopping != "NONE"
        return (
            (self.random_seed, self.validation_ratio) if splits else None,
            self.label, self.task, self.weights,
            (
                self.ranking_group, self.loss, self.ranking_max_group_size,
                self.ndcg_truncation,
            )
            if self.task == Task.RANKING
            else None,
            # the placement: the one device `jnp.asarray` sends to
            jax.default_backend(), jax.config.jax_default_device,
        )

    def _model_metadata(self) -> Optional[dict]:
        md = {}
        if self.ranking_group:
            md["ranking_group"] = self.ranking_group
            md["ndcg_truncation"] = self.ndcg_truncation
        if self.label_event_observed:
            md["label_event_observed"] = self.label_event_observed
            if self.label_entry_age:
                md["label_entry_age"] = self.label_entry_age
        return md or None


# The arrays of a job that a Dataset keeps on the device for the next
# (`Dataset.keep_device_inputs`), in `_train_gbt`'s order.
_KEPT_INPUTS = ("bins_tr", "y_tr", "w_tr", "bins_va", "y_va", "w_va")


def _rows_by_query(group_values, ordered: bool):
    """(codes, order) of a ranking job's group column: `codes` [n]
    numbers each row's query by the sorted distinct ids, so that the
    numbering does not depend on the order of the rows; `order` (None
    unless `ordered`) lists the rows by query, in dataset order within
    one, which is the order a group-structured loss keeps them in on the
    device: a query's documents are consecutive rows, and a table
    shuffled across queries gives the arrays of the ordered one."""
    _, codes = np.unique(np.asarray(group_values), return_inverse=True)
    codes = codes.reshape(-1)
    return codes, np.argsort(codes, kind="stable") if ordered else None


def _take_rows(a, idx, rows):
    """a[idx], with zero rows after it up to `rows` rows."""
    if rows == len(idx):
        return a[idx]
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    np.take(a, idx, axis=0, out=out[: len(idx)])
    return out


def _split_capacities(n, rows_tr, rows_va, ratio):
    """(training rows, validation rows) to pad the parts of a split by
    whole queries to. The parts' sizes go by which queries validate,
    and a compiled program goes by its shapes: a table of `n` rows
    always gets the sizes the ratio gives, plus 1/128 of `n`, rounded
    up to a round number (the power of two under n / 64), so that
    tables of one size share their shapes, and with them their
    program's speed (on the chip, programs built for 12,584,074 to
    12,614,780 training rows ran 13.89 to 14.10 s a job: PERF.md section
    6, PR 36). A part larger than that takes the next round number
    that holds it."""
    step = 1 << max((n >> 6).bit_length() - 1, 3)
    expected_va = int(n * ratio)

    def capacity(expected, rows):
        least = max(expected + max(n >> 7, 1), rows)
        return -(-least // step) * step

    return capacity(n - expected_va, rows_tr), capacity(expected_va, rows_va)


def _split_rows(dataset, bins_all, rng, seed, ratio):
    """(tr_idx, va_idx, bins_all[tr_idx], bins_all[va_idx]) of the
    seeded row split. The permutation and the two gathers are nearly all
    of `split` (9 of 10 s a job at 52M x 28, and what made a job's wall
    swing by 1.3 % from run to run), so they are kept with the Dataset
    beside its cached bin matrix: the same matrix, seed and ratio give
    the same rows every job of a sweep."""
    key = ("split", seed, ratio)
    hit = dataset.cached_bin_aux(key) if dataset is not None else None
    if hit is not None and hit[0] is bins_all:
        return hit[1:]
    n = bins_all.shape[0]
    perm = rng.permutation(n)
    nv = min(max(int(n * ratio), 1), n - 1)
    va_idx, tr_idx = perm[:nv], perm[nv:]
    rows = (tr_idx, va_idx, bins_all[tr_idx], bins_all[va_idx])
    if dataset is not None:
        for a in rows:  # shared by every later job, as the cached bins are
            a.setflags(write=False)
        dataset.store_bin_aux(key, (bins_all,) + rows)
    return rows


def _hist_stat_columns(weights, sampling: str, loss_obj):
    """What the histogram may assume of the stats rows `[g w, h w, w]`
    the boosting loop grows every tree from (ops/histogram.py
    StatColumn), or None for nothing. The one place that says so, from
    what the learner can see before it builds the program: `weights`
    (the learner's weights column, or None), the sampling method and
    the loss's class. With no weights column the rows' weights are ones,
    and zeros on the rows the learner pads (the split's capacities, the
    mesh's pad); a `RANDOM` or `SELGB` sample multiplies them by a mask
    of zeros and ones (`GOSS` re-weights the rows it keeps by
    (1 - alpha) / beta), so `w` is 0 or 1: its own first bf16 piece. A
    loss whose class declares `unit_hessian` returns a hessian of ones,
    so `h w` is `w` bit for bit."""
    if weights is not None or sampling not in ("RANDOM", "SELGB"):
        return None
    hessian = (
        StatColumn(same_as=2) if getattr(loss_obj, "unit_hessian", False)
        else StatColumn()
    )
    return (StatColumn(), hessian, StatColumn(pieces=1))


class _BoostFns(NamedTuple):
    """The jitted programs of one boosting configuration
    (`_make_boost_fn`): `init_state(y_tr, w_tr)` gives the first carry
    and the initial predictions, `run_chunk(carry, start, chunk_len,
    *data)` grows trees [start, start + chunk_len)."""

    init_state: Callable
    run_chunk: Callable
    use_dart: bool


@functools.lru_cache(maxsize=16)
def _make_boost_fn(
    loss_obj, rule, tree_cfg: TreeConfig, num_trees, shrinkage, subsample,
    candidate_features, num_numerical, num_valid_features, seed, n, nv,
    sampling="RANDOM", goss_alpha=0.2, goss_beta=0.1, selgb_ratio=0.01,
    dart_dropout=0.0, oblique_P=0, oblique_density=2.0,
    oblique_weight_type="BINARY", oblique_weight_range=None,
    oblique_mode="SPARSE", mhld_max_attributes=4, num_label_classes=1,
    monotone=None, vs_Ac=0, vs_Ap=0, route_impl="xla", route_fuse=True,
    stat_columns=None,
):
    """Builds (and caches) the jitted boosting loop for one static config,
    as a `_BoostFns`. `stat_columns` is `_hist_stat_columns`' word on
    the stats rows every tree is grown from.

    Caching the closure is what makes jax.jit's own cache effective across
    `train()` calls: a fresh closure per call would retrace + recompile the
    whole lax.scan every time. Keyed on hashable frozen-dataclass configs
    (a ranking loss is one: its query structure is an argument of
    `run_chunk`, `groups_tr` and `groups_va`)."""
    K = loss_obj.num_dims
    N = tree_cfg.max_nodes
    B = tree_cfg.num_bins

    use_dart = dart_dropout > 0.0
    P = oblique_P

    # Native fused end-of-tree update (docs/row_routing.md): with the
    # native routing path on, the per-tree (per-class column)
    # `preds += leaf_value[leaf_id]` runs as one kernel pass
    # (fuse_update); for squared error under unit sampling the same pass
    # also recomputes the next iteration's [g·w, h·w, w] stats rows
    # (fuse_grad — the carry then threads the stats to the next scan
    # step, so gradients never make a second trip through memory).
    # fuse_update is NOT optional when routing natively: leaving the
    # update to XLA would let the native program's different fusion
    # clustering make different FMA-contraction choices than the oracle
    # program compiles (measured on the multiclass path — ulp drift
    # from the second iteration on), while the kernel pins the probed
    # contraction behavior for every column. Only losses whose gradient
    # is plain arithmetic fuse_grad: squared error's g = p − y is
    # bit-identical between XLA and the kernel, while sigmoid/softmax
    # losses keep the XLA recompute (elementwise, deterministic across
    # both compiled programs).
    fuse_update = route_impl == "native" and not use_dart
    from ydf_tpu.learners.losses import MeanSquaredError

    fuse_grad = (
        fuse_update
        and K == 1
        and isinstance(loss_obj, MeanSquaredError)
        and sampling == "RANDOM"
        and subsample >= 1.0
        and oblique_mode != "MHLD"  # LDA consumes w_eff pre-update
    )

    def _init(y_tr, w_tr):
        y_f = y_tr.astype(jnp.float32)
        init_pred = loss_obj.initial_predictions(y_f, w_tr)  # [K]
        preds0 = jnp.broadcast_to(init_pred[None, :], (n, K)).astype(jnp.float32)
        vpreds0 = jnp.broadcast_to(init_pred[None, :], (nv, K)).astype(jnp.float32)
        key0 = jax.random.PRNGKey(seed)
        if use_dart:
            carry0 = (
                preds0, vpreds0, key0,
                jnp.zeros((num_trees, n, K), jnp.float32),
                jnp.zeros((num_trees, nv, K), jnp.float32),
                jnp.zeros((num_trees,), jnp.float32),
            )
        elif fuse_grad:
            # Iteration 0's stats rows, with EXACTLY the ops the unfused
            # path would run (g·(w·1), h·(w·1), w·1) so the fused loop is
            # bit-identical from the first tree.
            g0, h0 = loss_obj.grad_hess(y_tr, preds0)  # squared error only
            w_eff0 = w_tr * jnp.ones((n,), jnp.float32)
            stats0 = jnp.stack(
                [g0[:, 0] * w_eff0, h0[:, 0] * w_eff0, w_eff0], axis=1
            )
            carry0 = (preds0, vpreds0, key0, stats0)
        else:
            carry0 = (preds0, vpreds0, key0)
        return carry0, init_pred

    def _make_step(bins_tr, y_tr, w_tr, bins_va, y_va, w_va,
                   x_tr_raw=None, x_va_raw=None, set_tr=None, set_va=None,
                   vs_tr=None, vs_va=None, groups_tr=None, groups_va=None):
        y_f = y_tr.astype(jnp.float32)

        # A group-structured loss (ranking) reads its rows through the
        # query structure; what every tree shares of it (relevances and
        # ideal DCG in the buckets' layout) is made here, once a chunk.
        rank_tr = rank_va = {}
        if groups_tr is not None:
            rank_tr = {"groups": loss_obj.group_context(y_tr, groups_tr)}
        if groups_va is not None:
            rank_va = {"groups": loss_obj.group_context(y_va, groups_va)}
        # Outside DART the scores a tree's lambdas come from are the
        # forest before it, so their one view of the training scores
        # also gives that forest's loss (`grad_hess_loss`): the step
        # reports the loss of the forest before its tree, and `run_chunk`
        # hands each loss to the tree that made it.
        loss_before = groups_tr is not None and not use_dart

        def train_loss(preds):
            with jax.named_scope("ydf.loss"):
                return loss_obj.loss(y_tr, preds, w_tr, tag="train", **rank_tr)

        # Feature-major bins copy for the fused native route kernel,
        # computed HERE — outside the boosting scan — so the one
        # materialized transpose (14 MB at the bench shape) is shared by
        # every tree and layer. Per-tree candidate blocks (oblique/VS
        # projections) rebuild grow_bins per iteration; those configs
        # let the grower transpose in-trace instead.
        bins_tr_T = bins_tr.T if route_impl == "native" else None

        def sample_mask(k_sub, g, preds):
            """Per-example training-weight multiplier for this iteration —
            the reference's SampleTrainingExamples / GOSS / SelGB switch
            (gradient_boosted_trees.cc:1488-1522)."""
            if sampling == "GOSS":
                # Gradient one-side sampling (Ke et al. 2017): keep the
                # goss_alpha fraction with the largest |g|, sample
                # goss_beta of the rest, re-weighted by (1-alpha)/beta.
                gmag = jnp.sum(jnp.abs(g), axis=1)
                k_top = max(int(goss_alpha * n), 1)
                thr = jax.lax.top_k(gmag, k_top)[0][-1]
                top = gmag >= thr
                rest_p = min(goss_beta / max(1.0 - goss_alpha, 1e-6), 1.0)
                keep = jax.random.bernoulli(k_sub, rest_p, (n,))
                upw = (1.0 - goss_alpha) / max(goss_beta, 1e-9)
                return jnp.where(top, 1.0, jnp.where(keep, upw, 0.0))
            if sampling == "SELGB":
                # Selective Gradient Boosting (Lucchese et al. 2018,
                # ranking; reference SampleTrainingExamplesWithSelGB,
                # gradient_boosted_trees.cc:3067-3092): PER QUERY GROUP,
                # keep every positive example and the selgb_ratio fraction
                # of that group's negatives scored highest by the current
                # model (the "hard" negatives).
                from ydf_tpu.learners import ranking_loss

                groups, ctx = rank_tr["groups"]
                keep = []
                for s_g, (y_g, valid, _, _) in zip(
                    ranking_loss.to_groups(groups, preds[:, 0]), ctx
                ):
                    pos_g = (y_g > 0) & valid
                    neg_g = ~pos_g & valid
                    neg_score = jnp.where(neg_g, s_g, -jnp.inf)
                    # Rank of each negative inside its group, by
                    # descending score: rank r kept iff
                    # r < ceil(ratio * #negatives).
                    order = jnp.argsort(-neg_score, axis=1)
                    rank = jnp.argsort(order, axis=1)
                    n_neg = jnp.sum(neg_g, axis=1, keepdims=True)
                    keep_neg = neg_g & (rank < jnp.ceil(selgb_ratio * n_neg))
                    keep.append((pos_g | keep_neg).astype(jnp.float32))
                return ranking_loss.from_groups(groups, keep)[0]
            if subsample < 1.0:
                return jax.random.bernoulli(
                    k_sub, subsample, (n,)
                ).astype(jnp.float32)
            return jnp.ones((n,), jnp.float32)

        def make_mhld_W(k_proj, w_eff):
            """MHLD projections (reference oblique.cc SolveLDA /
            FindBestConditionMHLDObliqueTemplate, recast per-tree and
            batched): weighted scatter matrices SW/SB over the numerical
            features via MXU matmuls, then per random feature subset
            (size cycling 2..max_num_attributes — the batched analogue of
            the reference's greedy attribute growth) the top generalized
            eigenvector of SW⁻¹·SB through the TPU-supported symmetric
            form: SW = L·Lᵀ, eigh(L⁻¹·SB·L⁻ᵀ), w = L⁻ᵀ·v."""
            Fn = x_tr_raw.shape[1]
            C = max(num_label_classes, 2)
            oh = jax.nn.one_hot(
                y_tr.astype(jnp.int32), C, dtype=jnp.float32
            )
            cw = oh * w_eff[:, None]
            n_c = cw.sum(0)  # [C]
            tot = jnp.maximum(w_eff.sum(), 1e-12)
            mu_c = (cw.T @ x_tr_raw) / jnp.maximum(n_c, 1e-12)[:, None]
            mu = (w_eff @ x_tr_raw) / tot
            Sxx = (x_tr_raw * w_eff[:, None]).T @ x_tr_raw
            SW = Sxx - (mu_c.T * n_c[None, :]) @ mu_c
            d = mu_c - mu[None, :]
            SB = (d.T * n_c[None, :]) @ d
            smax = min(max(mhld_max_attributes, 2), Fn)
            sizes = 2 + (jnp.arange(P) % max(smax - 1, 1))
            k_sub = jax.random.split(k_proj, P)

            def subset_mask(kk, size):
                scores = jax.random.uniform(kk, (Fn,))
                kth = jnp.sort(scores)[Fn - size]
                return scores >= kth

            masks = jax.vmap(subset_mask)(k_sub, sizes)  # [P, Fn]
            reg = 1e-3 * jnp.trace(SW) / Fn + 1e-6

            def solve_one(m):
                mf = m.astype(jnp.float32)
                MM = mf[:, None] * mf[None, :]
                # Excluded features: identity block in SW (invertible),
                # zero block in SB → their coefficients come out zero.
                SWp = SW * MM + jnp.diag(1.0 - mf) + reg * jnp.eye(Fn)
                SBp = SB * MM
                L = jnp.linalg.cholesky(SWp)
                A = jax.scipy.linalg.solve_triangular(L, SBp, lower=True)
                M2 = jax.scipy.linalg.solve_triangular(
                    L, A.T, lower=True
                ).T
                M2 = 0.5 * (M2 + M2.T)
                _, evecs = jnp.linalg.eigh(M2)
                v = evecs[:, -1]
                wp = jax.scipy.linalg.solve_triangular(
                    L.T, v, lower=False
                ) * mf
                return (
                    wp / jnp.maximum(jnp.linalg.norm(wp), 1e-12)
                ).astype(jnp.float32)

            return jax.vmap(solve_one)(masks)

        def make_projections(k_proj, w_eff=None):
            """P oblique projections as one MXU matmul + quantile
            binning (reference oblique.cc SampleProjection, recast per-tree
            and batched); MHLD mode swaps the random coefficient sampling
            for batched LDA. Returns (W [P, Fn], boundaries [P, B-1],
            aug_tr [n, F+P], aug_va [nv, F+P])."""
            Fn = x_tr_raw.shape[1]
            if oblique_mode == "MHLD":
                W = make_mhld_W(k_proj, w_eff)
                return (W,) + _bin_projections(W)
            from ydf_tpu.ops.oblique import sample_projection_coefficients

            mono_vec = None
            if monotone is not None and any(monotone[:num_numerical]):
                # Sign-forced coefficients on constrained features
                # (reference oblique.cc:1113-1126).
                mono_vec = jnp.asarray(
                    np.array(monotone[:num_numerical], np.float32)
                )
            W = sample_projection_coefficients(
                k_proj, P, Fn,
                density=oblique_density,
                weight_type=oblique_weight_type,
                weight_range=oblique_weight_range,
                monotone_vec=mono_vec,
            )
            return (W,) + _bin_projections(W)

        def _bin_projections(W):
            """Shared tail: project, quantile-bin, splice the projection
            columns after the numerical block of the bin matrices."""
            z_tr = x_tr_raw @ W.T  # [n, P] — the MXU hot op
            qs = jnp.linspace(1.0 / B, 1.0 - 1.0 / B, B - 1)
            bnd = jnp.quantile(z_tr, qs, axis=0).T  # [P, B-1]
            binize = jax.vmap(
                lambda b, zz: jnp.searchsorted(b, zz, side="right")
            )
            zb_tr = binize(bnd, z_tr.T).astype(jnp.uint8).T  # [n, P]
            aug_tr = jnp.concatenate(
                [bins_tr[:, :num_numerical], zb_tr, bins_tr[:, num_numerical:]],
                axis=1,
            )
            if nv > 0:
                z_va = x_va_raw @ W.T
                zb_va = binize(bnd, z_va.T).astype(jnp.uint8).T
                aug_va = jnp.concatenate(
                    [
                        bins_va[:, :num_numerical],
                        zb_va,
                        bins_va[:, num_numerical:],
                    ],
                    axis=1,
                )
            else:
                aug_va = bins_va
            return bnd, aug_tr, aug_va

        def make_vs_projections(k_vs):
            """Per-tree NUMERICAL_VECTOR_SEQUENCE anchor candidates
            (reference vector_sequence.cc:265-326 recast per-tree): for
            each VS feature, closer_than anchors are random vectors drawn
            from the data, projected_more_than anchors are differences of
            two random vectors; each anchor's per-example score (kernel in
            ops/vector_sequence.py) becomes one quantile-binned candidate
            column. Returns (anchors [Pv, D], boundaries [Pv, B-1],
            cols_tr u8 [n, Pv], cols_va u8 [nv, Pv])."""
            from ydf_tpu.ops.vector_sequence import vs_scores

            vals_all, len_all = vs_tr
            Fv = vals_all.shape[1]
            closer_mask = jnp.asarray([True] * vs_Ac + [False] * vs_Ap)
            qs = jnp.linspace(1.0 / B, 1.0 - 1.0 / B, B - 1)
            binize = jax.vmap(
                lambda b, zz: jnp.searchsorted(b, zz, side="right")
            )
            anchors_list, bnd_list, cols_tr, cols_va = [], [], [], []
            for fv in range(Fv):
                vals_f = vals_all[:, fv]  # [n, L, D]
                len_f = len_all[:, fv]
                ne = (len_f > 0).astype(jnp.float32)
                tot = jnp.sum(ne)
                # Uniform over non-empty examples (the reference's
                # rejection loop, vector_sequence.cc:255-276); degenerate
                # all-empty columns fall back to uniform (their scores are
                # all -FLT_MAX — no split will validate anyway).
                p = jnp.where(tot > 0, ne / jnp.maximum(tot, 1.0), 1.0 / n)

                def samp(kk):
                    k1, k2 = jax.random.split(kk)
                    idx = jax.random.choice(k1, n, p=p)
                    li = jax.random.randint(
                        k2, (), 0, jnp.maximum(len_f[idx], 1)
                    )
                    return vals_f[idx, li]

                ks = jax.random.split(
                    jax.random.fold_in(k_vs, fv), vs_Ac + 2 * vs_Ap
                )
                parts = []
                if vs_Ac:
                    parts.append(jax.vmap(samp)(ks[:vs_Ac]))
                if vs_Ap:
                    v1 = jax.vmap(samp)(ks[vs_Ac: vs_Ac + vs_Ap])
                    v2 = jax.vmap(samp)(ks[vs_Ac + vs_Ap:])
                    parts.append(v1 - v2)
                anchors_f = jnp.concatenate(parts, axis=0)  # [A, D]
                scores = vs_scores(vals_f, len_f, anchors_f, closer_mask)
                bnd = jnp.quantile(scores, qs, axis=0).T  # [A, B-1]
                # Keep empty-sequence scores (-FLT_MAX) strictly below
                # every learnable threshold: an "exists vector" condition
                # can never hold on an empty sequence.
                bnd = jnp.maximum(bnd, -1e29)
                cols_tr.append(binize(bnd, scores.T).astype(jnp.uint8).T)
                if nv > 0:
                    sva = vs_scores(
                        vs_va[0][:, fv], vs_va[1][:, fv], anchors_f,
                        closer_mask,
                    )
                    cols_va.append(
                        binize(bnd, sva.T).astype(jnp.uint8).T
                    )
                anchors_list.append(anchors_f)
                bnd_list.append(bnd)
            return (
                jnp.concatenate(anchors_list, axis=0),
                jnp.concatenate(bnd_list, axis=0),
                jnp.concatenate(cols_tr, axis=1),
                jnp.concatenate(cols_va, axis=1) if nv > 0 else bins_va,
            )

        def boost_step(carry, it):
            if use_dart:
                preds, vpreds, key, contrib, vcontrib, tree_scale = carry
                key, k_sub, k_drop = jax.random.split(
                    jax.random.fold_in(key, it), 3
                )
                # Drop a random subset of past iterations (DART, Vinayak &
                # Gilad-Bachrach 2015; reference :1468-1474): gradients are
                # computed on the ensemble without the dropped trees.
                drop = jax.random.bernoulli(
                    k_drop, dart_dropout, (num_trees,)
                ) & (jnp.arange(num_trees) < it)
                nd = jnp.sum(drop.astype(jnp.float32))
                dropped_sum = jnp.einsum(
                    "t,tnk->nk", drop * tree_scale, contrib
                )
                preds_used = preds - dropped_sum
            elif fuse_grad:
                # Stats rows arrive pre-computed from the previous
                # iteration's fused update kernel; the key evolution is
                # kept IDENTICAL to the unfused path (k_sub is simply
                # unused — RANDOM sampling at subsample 1.0 draws
                # nothing from it).
                preds, vpreds, key, stats_carry = carry
                key, k_sub = jax.random.split(jax.random.fold_in(key, it))
                preds_used = preds
            else:
                preds, vpreds, key = carry
                key, k_sub = jax.random.split(jax.random.fold_in(key, it))
                preds_used = preds

            if fuse_grad:
                # w_eff only feeds the per-tree projection machinery
                # here; w_tr·1 ≡ w_tr bit for bit.
                w_eff = w_tr
            else:
                with jax.named_scope("ydf.grad"):
                    if loss_before:
                        g, h, tl = loss_obj.grad_hess_loss(
                            y_tr, preds_used, **rank_tr
                        )
                    else:
                        g, h = loss_obj.grad_hess(
                            y_tr, preds_used, **rank_tr
                        )  # [n, K]
                    m = sample_mask(k_sub, g, preds_used)
                    w_eff = w_tr * m

            if P > 0:
                key, k_proj = jax.random.split(key)
                obl_w, obl_b, grow_bins, grow_bins_va = make_projections(
                    k_proj, w_eff
                )
                grow_num_numerical = num_numerical + P
                grow_num_valid = (
                    None
                    if num_valid_features is None
                    else num_valid_features + P
                )
            else:
                obl_w = jnp.zeros((0, 0), jnp.float32)
                obl_b = jnp.zeros((0, B - 1), jnp.float32)
                grow_bins, grow_bins_va = bins_tr, bins_va
                grow_num_numerical = num_numerical
                grow_num_valid = num_valid_features

            if vs_tr is not None and vs_Ac + vs_Ap > 0:
                key, k_vs = jax.random.split(key)
                vs_a, vs_b, vs_cols, vs_cols_va = make_vs_projections(k_vs)
                Pv = vs_a.shape[0]
                # Insert after the oblique block: [num, obl, vs, cat].
                grow_bins = jnp.concatenate(
                    [
                        grow_bins[:, :grow_num_numerical],
                        vs_cols,
                        grow_bins[:, grow_num_numerical:],
                    ],
                    axis=1,
                )
                if nv > 0:
                    grow_bins_va = jnp.concatenate(
                        [
                            grow_bins_va[:, :grow_num_numerical],
                            vs_cols_va,
                            grow_bins_va[:, grow_num_numerical:],
                        ],
                        axis=1,
                    )
                grow_num_numerical += Pv
                if grow_num_valid is not None:
                    grow_num_valid += Pv
            else:
                vs_a = jnp.zeros((0, 0), jnp.float32)
                vs_b = jnp.zeros((0, B - 1), jnp.float32)

            # Monotone direction vector over the per-tree candidate layout
            # [numerical, oblique, vs]: projection columns inherit +1 when
            # they touch any constrained feature (their coefficients were
            # sign-forced in make_projections); vs columns are never
            # constrained. Without extra blocks, the static tuple path in
            # the grower is used unchanged.
            grow_monotone = monotone
            grow_mono_dirs = None
            if (
                monotone is not None
                and any(monotone)
                and grow_num_numerical != num_numerical
            ):
                mono_vec = jnp.asarray(
                    np.array(monotone[:num_numerical], np.float32)
                )
                parts = [mono_vec]
                if P > 0:
                    parts.append(
                        (jnp.abs(obl_w) @ jnp.abs(mono_vec) > 0).astype(
                            jnp.float32
                        )
                    )
                pad = grow_num_numerical - sum(p.shape[0] for p in parts)
                if pad > 0:
                    parts.append(jnp.zeros((pad,), jnp.float32))
                grow_mono_dirs = jnp.concatenate(parts)
                grow_monotone = None

            def leaf_value_of(lv, leaf):
                """lv[leaf, 0] by the rule of the routing's look-ups
                (ops/lookup.py): a select copies the value's bits."""
                dense = lookup.resolve_dense()
                if dense is False:
                    # As it always was: the native kernels replicate
                    # what XLA:CPU makes of this very expression
                    # (docs/row_routing.md, the FMA contraction).
                    lookup.count(0, 1)
                    return lv[leaf, 0]
                # The barrier keeps the selects out of the fusion that
                # adds the [n, 1] predictions, whose tiles hold an
                # eighth of a register (the TPU compiler's estimate:
                # 748M cycles a tree at 50.4M rows fused, 92M apart).
                return jax.lax.optimization_barrier(
                    lookup.lookup_small(lv[:, 0], leaf, N, 0.0, dense)
                )

            trees_k, leaves_k = [], []
            fused = fuse_update or fuse_grad  # K == 1, non-DART
            stats_next = None
            new_contrib = jnp.zeros((n, K), jnp.float32)
            new_vcontrib = jnp.zeros((nv, K), jnp.float32)
            for k in range(K):
                kk = jax.random.fold_in(key, k)
                if fuse_grad:
                    stats = stats_carry
                else:
                    with jax.named_scope("ydf.grad"):
                        stats = jnp.stack(
                            [g[:, k] * w_eff, h[:, k] * w_eff, w_eff],
                            axis=1,
                        )
                res = grower.grow_tree(
                    grow_bins, stats, kk,
                    bins_t=bins_tr_T if grow_bins is bins_tr else None,
                    rule=rule,
                    max_depth=tree_cfg.max_depth,
                    frontier=tree_cfg.frontier,
                    max_nodes=N,
                    num_bins=tree_cfg.num_bins,
                    num_numerical=grow_num_numerical,
                    min_examples=tree_cfg.min_examples,
                    candidate_features=candidate_features,
                    num_valid_features=grow_num_valid,
                    monotone=grow_monotone,
                    monotone_dirs=grow_mono_dirs,
                    set_bits=set_tr,
                    route_impl=route_impl,
                    route_fuse=route_fuse,
                    stat_columns=stat_columns,
                )
                # Leaf values scaled by shrinkage at storage time, like the
                # reference (set_leaf applies shrinkage). The raw
                # (unscaled) values are kept separate for the fused
                # update kernels: XLA CPU contracts the η-multiply into
                # the preds add as a hardware FMA (one rounding, through
                # the gather AND through an optimization_barrier —
                # measured; docs/row_routing.md), so train preds in the
                # oracle are fma(raw, η, preds) while the model stores
                # round(raw·η). The kernels take (raw, η) and replicate
                # the host's observed contraction to stay bit-identical.
                with jax.named_scope("ydf.leaf"):
                    lv_raw = rule.leaf_value(res.tree.leaf_stats, None)
                    lv = lv_raw * shrinkage
                    if fused:
                        # End-of-tree update as ONE fused kernel pass per
                        # class column: preds[:, k] += lv[leaf_id], and
                        # (squared error) the next iteration's stats rows
                        # from the same pass — bit-identical to the
                        # gather+mul+add(+grad) chain below. Safe inside
                        # the k loop: g for every class was computed from
                        # preds_used at the top of the iteration.
                        from ydf_tpu.ops import routing_native

                        if fuse_grad:
                            p_col, stats_next = (
                                routing_native.leaf_update_grad(
                                    res.leaf_id, lv_raw[:, 0], shrinkage,
                                    preds[:, 0], y_f, w_tr
                                )
                            )
                        else:
                            p_col = routing_native.leaf_update(
                                res.leaf_id, lv_raw[:, 0], shrinkage,
                                preds[:, k]
                            )
                        preds = (
                            p_col[:, None] if K == 1
                            else preds.at[:, k].set(p_col)
                        )
                    else:
                        new_contrib = new_contrib.at[:, k].set(
                            leaf_value_of(lv, res.leaf_id)
                        )
                if nv > 0:
                    with jax.named_scope("ydf.valid"):
                        vleaves = route_tree_bins(
                            res.tree, grow_bins_va, tree_cfg.max_depth,
                            x_set=set_va,
                            # Stored set-feature ids are offset by the
                            # UNPADDED scalar count (see grow_tree
                            # best_f_store).
                            num_scalar=grow_num_valid,
                            impl=route_impl,
                            num_numerical=grow_num_numerical,
                        )
                        if fused:
                            vp_col = apply_leaf_values(
                                vleaves, lv_raw[:, 0], vpreds[:, k],
                                scale=shrinkage, impl=route_impl
                            )
                            vpreds = (
                                vp_col[:, None] if K == 1
                                else vpreds.at[:, k].set(vp_col)
                            )
                        else:
                            new_vcontrib = new_vcontrib.at[:, k].set(
                                leaf_value_of(lv, vleaves)
                            )
                trees_k.append(res.tree)
                leaves_k.append(lv)

            if use_dart:
                # New tree enters at weight 1/(nd+1); dropped trees shrink
                # by nd/(nd+1) (reference :1558-1573).
                with jax.named_scope("ydf.leaf"):
                    factor = 1.0 / (nd + 1.0)
                    tree_scale_old = tree_scale
                    tree_scale = jnp.where(
                        drop, tree_scale * nd * factor, tree_scale
                    )
                    tree_scale = tree_scale.at[it].set(factor)
                    contrib = jax.lax.dynamic_update_index_in_dim(
                        contrib, new_contrib, it, 0
                    )
                    preds = (
                        preds_used
                        + dropped_sum * nd * factor
                        + new_contrib * factor
                    )
                if nv > 0:
                    # Same incremental form as the train preds: only the
                    # dropped-trees contraction is O(T); recomputing the
                    # full ensemble each step would be O(T^2) overall.
                    with jax.named_scope("ydf.valid"):
                        vdropped = jnp.einsum(
                            "t,tnk->nk", drop * tree_scale_old, vcontrib
                        )
                        vcontrib = jax.lax.dynamic_update_index_in_dim(
                            vcontrib, new_vcontrib, it, 0
                        )
                        vpreds = (
                            vpreds
                            - vdropped
                            + vdropped * nd * factor
                            + new_vcontrib * factor
                        )
            elif not fused:
                with jax.named_scope("ydf.leaf"):
                    preds = preds + new_contrib
                if nv > 0:
                    with jax.named_scope("ydf.valid"):
                        vpreds = vpreds + new_vcontrib

            trees = jax.tree.map(lambda *xs: jnp.stack(xs), *trees_k)
            lvs = jnp.stack(leaves_k)  # [K, N, 1]
            if not loss_before:
                tl = train_loss(preds)
            with jax.named_scope("ydf.loss"):
                vl = (
                    loss_obj.loss(y_va, vpreds, w_va, tag="valid", **rank_va)
                    if nv > 0
                    else jnp.float32(0)
                )
            if use_dart:
                new_carry = (preds, vpreds, key, contrib, vcontrib, tree_scale)
            elif fuse_grad:
                new_carry = (preds, vpreds, key, stats_next)
            else:
                new_carry = (preds, vpreds, key)
            return new_carry, (trees, lvs, tl, vl, obl_w, obl_b, vs_a, vs_b)

        return boost_step, (train_loss if loss_before else None)

    @jax.jit
    def init_state(y_tr, w_tr):
        return _init(y_tr, w_tr)

    @functools.partial(jax.jit, static_argnames=("chunk_len",))
    def run_chunk(carry, start, chunk_len, bins_tr, y_tr, w_tr,
                  bins_va, y_va, w_va, x_tr_raw=None, x_va_raw=None,
                  set_tr=None, set_va=None, vs_tr=None, vs_va=None,
                  groups_tr=None, groups_va=None):
        """One checkpointable slice of the boosting loop: iterations
        [start, start + chunk_len). Chunking is invisible to the result —
        the per-iteration RNG folds the iteration index into the carried
        key, so every chunk boundary gives the same forest."""
        step, loss_after = _make_step(
            bins_tr, y_tr, w_tr, bins_va, y_va, w_va, x_tr_raw, x_va_raw,
            set_tr, set_va, vs_tr, vs_va, groups_tr, groups_va,
        )
        carry, ys = jax.lax.scan(step, carry, start + jnp.arange(chunk_len))
        if loss_after is not None:
            # Each step read the loss of the forest before its tree: the
            # chunk's last forest is viewed once more, for its loss
            # alone, and every loss moves to the tree that made it.
            tls = jnp.concatenate([ys[2][1:], loss_after(carry[0])[None]])
            ys = ys[:2] + (tls,) + ys[3:]
        return carry, ys

    return _BoostFns(init_state, run_chunk, use_dart)


def _note_chunk(
    chunk_walls, start, clen, num_trees, t0_ns, chunk_arrays, nv_rows
):
    """Per-chunk bookkeeping of the boosting loop: records the chunk's
    host wall (the attribution source for the per-iteration training
    logs and the `train.chunk` telemetry span),
    feeds the training metrics, and emits the per-chunk progress line
    at debug level (the reference manager's per-stage Monitoring log,
    distributed_gradient_boosted_trees.cc:832-836)."""
    dur_ns = time.perf_counter_ns() - t0_ns
    chunk_walls.append((start, clen, t0_ns, dur_ns))
    tl = float(np.asarray(chunk_arrays["tls"])[-1])
    vl = float(np.asarray(chunk_arrays["vls"])[-1]) if nv_rows > 0 else None
    if telemetry.ENABLED:
        telemetry.counter("ydf_train_iterations_total").inc(clen)
        telemetry.histogram("ydf_train_chunk_latency_ns").observe_ns(
            dur_ns
        )
        telemetry.gauge("ydf_train_last_train_loss").set(tl)
        if vl is not None:
            telemetry.gauge("ydf_train_last_valid_loss").set(vl)
    if log.is_debug():
        done = min(start + clen, num_trees)
        msg = (
            f"gbt: iter {done}/{num_trees} train_loss={tl:.6g}"
            + (f" valid_loss={vl:.6g}" if vl is not None else "")
            + f" chunk_s={dur_ns / 1e9:.3f}"
        )
        log.debug(msg)


def _iteration_records(train_losses, valid_losses, has_valid, chunk_walls):
    """training_logs["iterations"]: one YDF-style record per TRAINED
    boosting iteration — iteration (1-based), losses, and wall seconds.
    Seconds are the measured per-chunk host wall attributed uniformly
    across the chunk's iterations (the device loop is one fused scan;
    finer host timing does not exist — see docs/observability.md)."""
    trained = int(np.asarray(train_losses).shape[0])
    secs = np.zeros((trained,), np.float64)
    for s, c, _t0, dur in chunk_walls or []:
        hi = min(s + c, trained)
        if hi > s and c > 0:
            secs[s:hi] = dur / 1e9 / c
    out = []
    for i in range(trained):
        rec = {
            "iteration": i + 1,
            "train_loss": float(train_losses[i]),
            "valid_loss": float(valid_losses[i]) if has_valid else None,
            "seconds": float(secs[i]),
        }
        out.append(rec)
    return out


def _emit_chunk_spans(chunk_walls):
    """One measured `train.chunk` telemetry span per dispatched chunk.
    The scan is one fused device program: the host cannot time a tree
    or a layer inside it (the device's own trace can:
    profiling.device_seconds_by_scope)."""
    for s, c, t0, dur in chunk_walls or []:
        telemetry.emit_span(
            "train.chunk", t0, dur, {"start_iter": s, "iterations": c}
        )


def _chunk_len(clen: int, start: int, num_trees: int, use_dart: bool) -> int:
    """Fixed chunk length so ONE compiled executable serves every chunk;
    the tail overshoots and is sliced off at merge. DART is the exception —
    extra iterations would rescale kept trees — and pays one extra compile
    for an exact tail."""
    return min(clen, num_trees - start) if use_dart else clen


def _chunk_arrays_from_ys(ys, timer) -> dict:
    """run_chunk outputs → the flat dict of host arrays that the loop
    keeps, in memory or as a chunk's payload file."""
    with timer.stage("device_loop.wait"):
        # The fetch below would block on the chunk anyway: waiting here
        # tells the host's wait for the device from the copies.
        jax.block_until_ready(ys)
    trees_c, lvs_c, tls_c, vls_c, ow_c, ob_c, va_c, vb_c = ys
    with timer.stage("device_loop.fetch"):
        d = {f"trees_{j}": np.asarray(a) for j, a in enumerate(trees_c)}
        d["lvs"] = np.asarray(lvs_c)
        d["tls"] = np.asarray(tls_c)
        d["vls"] = np.asarray(vls_c)
        d["ow"] = np.asarray(ow_c)
        d["ob"] = np.asarray(ob_c)
        d["vsa"] = np.asarray(va_c)
        d["vsb"] = np.asarray(vb_c)
    # This materialization is THE host-sync point of the boosting loop:
    # everything else (carry, bin matrix, labels) stays device-resident.
    device_loop.count_host_sync(sum(a.nbytes for a in d.values()))
    return d


def _early_stop_hit(vls_seen, done: int, lookahead: int) -> bool:
    """Look-ahead early stopping (reference early_stopping.h:29-66): stop
    once the validation loss has not improved for `lookahead` trees.
    `vls_seen` covers iterations [0, done) so argmin is an absolute index."""
    if lookahead <= 0:
        return False
    vall = np.concatenate(vls_seen)[:done]
    return done - (int(np.argmin(vall)) + 1) >= lookahead


def _merge_chunk_parts(parts, num_trees, use_dart, carry):
    """Concatenates per-chunk payload dicts and slices off the tail
    overshoot, on the host. Bakes each iteration's final DART weight into
    its stored leaf values, so serving needs no extra state."""
    from ydf_tpu.ops.grower import TreeArrays

    n_tree_fields = sum(1 for k in parts[0] if k.startswith("trees_"))
    trees_np = [
        np.concatenate([p[f"trees_{j}"] for p in parts], axis=0)[:num_trees]
        for j in range(n_tree_fields)
    ]
    lvs = np.concatenate([p["lvs"] for p in parts], axis=0)[:num_trees]
    tls = np.concatenate([p["tls"] for p in parts], axis=0)[:num_trees]
    vls = np.concatenate([p["vls"] for p in parts], axis=0)[:num_trees]
    obl_w = np.concatenate([p["ow"] for p in parts], axis=0)[:num_trees]
    obl_b = np.concatenate([p["ob"] for p in parts], axis=0)[:num_trees]
    def _vs_part(p, key):
        # Chunk payloads written before the vector-sequence fields.
        return p.get(key, np.zeros((p["lvs"].shape[0], 0, 0), np.float32))

    vs_a = np.concatenate([_vs_part(p, "vsa") for p in parts], axis=0)[
        :num_trees
    ]
    vs_b = np.concatenate([_vs_part(p, "vsb") for p in parts], axis=0)[
        :num_trees
    ]
    if use_dart:
        tree_scale = np.asarray(jax.tree.leaves(carry)[5])
        lvs = lvs * tree_scale[: lvs.shape[0], None, None, None]
    return TreeArrays(*trees_np), lvs, tls, vls, obl_w, obl_b, vs_a, vs_b


def _trees_per_chunk(
    num_trees, *, snapshot_interval, early_stop_lookahead, deadline
) -> int:
    """How many trees one dispatch of the boosting loop grows: the
    snapshot interval under a working_dir (None without one); the
    early-stop look-ahead window (0: no early stopping), at most 25
    trees, when the loop can stop between chunks; else all of them, one
    dispatch. Never more than `num_trees`."""
    if snapshot_interval is not None:
        clen = snapshot_interval
    elif deadline is not None or 0 < early_stop_lookahead < num_trees:
        # (Early stopping can only ever fire when the loop outlives the
        # look-ahead window.)
        clen = min(early_stop_lookahead or 25, 25)
    else:
        clen = num_trees
    return max(1, min(clen, num_trees))


class _MemoryParts:
    """Where a train() without a working_dir keeps its chunks' arrays:
    a list. Nothing to restore, nothing to preempt."""

    def __init__(self):
        self._parts = []

    def restore(self):
        return None

    def guard(self):
        return contextlib.nullcontext()

    def add(self, start, clen, part, carry, init_pred):
        self._parts.append(part)

    def parts(self):
        return self._parts


class _SnapshotParts:
    """Where a train() under a working_dir keeps them: each chunk's
    arrays in a payload file of its own (kept until training finishes,
    so I/O stays linear in the tree count), and a snapshot that records
    the carry and which chunks are done. The snapshot carries the
    fingerprint of the config and data, so a resume against a different
    dataset or hyperparameters fails fast instead of silently mixing
    trees. (Reference CreateSnapshot / TryLoadSnapshotFromDisk,
    gradient_boosted_trees.cc:345-427; index protocol utils/snapshot.h.)"""

    def __init__(self, cache_dir, fingerprint, resume, timer):
        from ydf_tpu.utils.snapshot import Snapshots

        self._dir = cache_dir
        self._fingerprint = fingerprint
        self._resume = resume
        self._timer = timer
        self._snaps = Snapshots(cache_dir, max_kept=2)
        self._starts = []  # carried across interrupted runs via the snapshot

    def _chunk_path(self, start_it: int) -> str:
        return os.path.join(self._dir, f"chunk_{start_it}.npz")

    def restore(self):
        """(iterations done, carry, initial predictions, their validation
        losses) of the latest snapshot, or None to start afresh."""
        state = self._snaps.latest() if self._resume else None
        if state is None:
            return None
        _, arrays, meta = state
        if meta.get("fingerprint") != self._fingerprint:
            raise ValueError(
                f"Snapshot in {self._dir!r} was created with different "
                "data or hyperparameters; refusing to resume. Delete the "
                "directory or disable resume_training."
            )
        carry = tuple(
            jnp.asarray(arrays[f"carry_{i}"])
            for i in range(meta["num_carry"])
        )
        self._starts = list(meta.get("chunk_starts", []))
        # The validation-loss history of the completed chunks, so early
        # stopping after a resume sees the true global minimum.
        vls_seen = []
        for st in self._starts:
            try:
                with np.load(self._chunk_path(st)) as z:
                    vls_seen.append(np.asarray(z["vls"]))
            except Exception:
                pass
        return (
            meta["completed_iters"], carry, jnp.asarray(arrays["init_pred"]),
            vls_seen,
        )

    def guard(self):
        return _PreemptionGuard()

    def add(self, start, clen, part, carry, init_pred):
        from ydf_tpu.utils.snapshot import _durable_replace

        tmp = self._chunk_path(start) + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **part)
        # Durable before the snapshot that references it: the final
        # merge reads chunk payloads back after a crash, so a torn
        # chunk behind a durable snapshot would be unrecoverable.
        _durable_replace(tmp, self._chunk_path(start))
        with self._timer.stage("device_loop.fetch"):
            arrays = {"init_pred": np.asarray(init_pred)}
            for i, leaf in enumerate(jax.tree.leaves(carry)):
                arrays[f"carry_{i}"] = np.asarray(leaf)
        # The snapshot's copy of the carry is this store's host-sync
        # point on top of the chunk payload fetch.
        device_loop.count_host_sync(sum(a.nbytes for a in arrays.values()))
        self._starts.append(start)
        self._snaps.save(
            start + clen,
            arrays,
            meta={
                "completed_iters": start + clen,
                "num_carry": len(jax.tree.leaves(carry)),
                "fingerprint": self._fingerprint,
                "chunk_starts": self._starts,
            },
        )

    def parts(self):
        out = []
        for st in self._starts:
            with np.load(self._chunk_path(st)) as z:
                out.append({k: z[k] for k in z.files})
        return out


def _train_gbt(
    bins_tr, y_tr, w_tr, bins_va, y_va, w_va, *,
    loss_obj, rule, tree_cfg: TreeConfig, num_trees, shrinkage, subsample,
    candidate_features, num_numerical, num_valid_features, seed,
    sampling="RANDOM", goss_alpha=0.2, goss_beta=0.1, selgb_ratio=0.01,
    dart_dropout=0.0, oblique_P=0, oblique_density=2.0,
    oblique_weight_type="BINARY", oblique_weight_range=None,
    oblique_mode="SPARSE", mhld_max_attributes=4, num_label_classes=1,
    monotone=None,
    x_tr_raw=None, x_va_raw=None, set_tr=None, set_va=None,
    vs_tr=None, vs_va=None, groups_tr=None, groups_va=None,
    vs_Ac=0, vs_Ap=0, route_impl="xla", route_fuse=True, stat_columns=None,
    cache_dir=None, resume=False, snapshot_interval=50,
    abort_after_chunks=None, preempt_after_chunks=None,
    early_stop_lookahead=0, deadline=None, timer,
):
    """The boosting loop: chunks of trees, each one dispatch of the
    jitted scan with the carry donated. Returns stacked trees
    [T, K, ...], leaf values [T, K, N, 1] and per-iteration logs, all on
    the host; `timer` is the calling train()'s StageTimer (the
    `device_loop.*` spans). The loop stops between chunks once the
    validation loss has not improved for `early_stop_lookahead` trees
    (reference early_stopping.h:29-66), or within one chunk of
    `deadline`, an absolute time.monotonic() value, and returns the
    iterations finished so far (reference GBT deadline check,
    gradient_boosted_trees.cc:1314-1325). Under `cache_dir` every chunk
    ends in a durable snapshot, and SIGTERM/SIGINT ends the train
    resumable at the next one."""
    # Identity-hashed losses can never hit the cache — bypass it so dead
    # entries don't pin device memory or evict the reusable
    # frozen-dataclass ones.
    from ydf_tpu.learners.losses import CustomLoss

    builder = (
        _make_boost_fn
        if type(loss_obj).__hash__ is not object.__hash__
        and not isinstance(loss_obj, CustomLoss)  # identity-hashed fields
        else _make_boost_fn.__wrapped__
    )
    with timer.stage("device_loop.init"):
        boost = builder(
            loss_obj, rule, tree_cfg, num_trees, shrinkage, subsample,
            candidate_features, num_numerical, num_valid_features, seed,
            bins_tr.shape[0], bins_va.shape[0],
            sampling, goss_alpha, goss_beta, selgb_ratio, dart_dropout,
            oblique_P, oblique_density, oblique_weight_type,
            oblique_weight_range, oblique_mode, mhld_max_attributes,
            num_label_classes, monotone,
            vs_Ac if vs_tr is not None else 0,
            vs_Ap if vs_tr is not None else 0,
            route_impl=route_impl,
            route_fuse=route_fuse,
            stat_columns=stat_columns,
        )
    nv_rows = bins_va.shape[0]
    data_args = (bins_tr, y_tr, w_tr, bins_va, y_va, w_va) + (
        (x_tr_raw, x_va_raw) if oblique_P > 0 else ()
    )
    data_kwargs = {}
    if set_tr is not None:
        data_kwargs = {"set_tr": set_tr, "set_va": set_va}
    if vs_tr is not None:
        data_kwargs["vs_tr"] = vs_tr
        data_kwargs["vs_va"] = vs_va
    if groups_tr is not None:
        data_kwargs["groups_tr"] = groups_tr
        data_kwargs["groups_va"] = groups_va

    can_early_stop = early_stop_lookahead > 0 and nv_rows > 0
    if cache_dir is None:
        store = _MemoryParts()
    else:
        import hashlib

        fp = hashlib.sha1()
        if hasattr(loss_obj, "fingerprint"):
            fp.update(loss_obj.fingerprint())
        fp.update(
            repr(
                (
                    type(loss_obj).__name__, rule, tree_cfg, num_trees,
                    shrinkage, subsample, candidate_features, num_numerical,
                    num_valid_features, seed, sampling, goss_alpha,
                    goss_beta, selgb_ratio, dart_dropout, oblique_P,
                    oblique_density, oblique_weight_type, vs_Ac, vs_Ap,
                    # The fused-gradient path changes the carry structure,
                    # so a snapshot must never resume across routing impls.
                    route_impl,
                    route_fuse,
                )
            ).encode()
        )
        fp.update(np.asarray(bins_tr.shape, np.int64).tobytes())
        fp.update(np.asarray(bins_va.shape, np.int64).tobytes())
        if set_tr is not None:
            fp.update(np.asarray(set_tr.shape, np.int64).tobytes())
            fp.update(
                np.asarray(set_tr[: min(1000, set_tr.shape[0])]).tobytes()
            )
        fp.update(np.asarray(bins_tr[: min(1000, bins_tr.shape[0])]).tobytes())
        fp.update(np.asarray(y_tr[: min(1000, y_tr.shape[0])]).tobytes())
        store = _SnapshotParts(cache_dir, fp.hexdigest(), resume, timer)
    clen = _trees_per_chunk(
        num_trees,
        snapshot_interval=None if cache_dir is None else snapshot_interval,
        early_stop_lookahead=early_stop_lookahead if can_early_stop else 0,
        deadline=deadline,
    )

    restored = store.restore()
    if restored is None:
        with timer.stage("device_loop.init"):
            carry, init_pred = boost.init_state(y_tr, w_tr)
        start, vls_seen = 0, []
    else:
        start, carry, init_pred, vls_seen = restored

    chunks_done = 0
    chunk_walls = []
    # A ranking loss's views of the training scores that this call's
    # chunks made, and the trees they grew: one a tree and one a chunk
    # for its last forest, two a tree under DART (`_make_step`'s
    # `loss_before`).
    rank_views = grown = 0
    with store.guard() as guard:
        while start < num_trees:
            c = _chunk_len(clen, start, num_trees, boost.use_dart)
            t0_ns = time.perf_counter_ns()
            # The carry is donated: the old one dies here, and everything
            # below reads the new one or the fetched part.
            carry, ys = device_loop.run_chunk(
                boost, carry, start, c, *data_args, timer=timer,
                **data_kwargs
            )
            if groups_tr is not None:
                rank_views += 2 * c if boost.use_dart else c + 1
                grown += min(c, num_trees - start)
            part = _chunk_arrays_from_ys(ys, timer)
            _note_chunk(
                chunk_walls, start, c, num_trees, t0_ns, part, nv_rows
            )
            store.add(start, c, part, carry, init_pred)
            start += c
            chunks_done += 1
            failpoints.hit("gbt.chunk")
            _oom_failpoint()
            if guard is not None:
                if (
                    preempt_after_chunks is not None
                    and chunks_done >= preempt_after_chunks
                ):
                    guard.trigger(signal.SIGTERM)
                if guard.triggered:
                    # The snapshot just saved IS the forced final
                    # snapshot; exit resumable with a distinct
                    # (schedulable) outcome. Telemetry buffered since the
                    # last flush would die with this process: export it
                    # and write the flight-recorder black box BEFORE
                    # raising. Both are no-ops when telemetry is off /
                    # has no export dir.
                    if telemetry.ENABLED:
                        _emit_chunk_spans(chunk_walls)
                        telemetry.flight_record(
                            "preempt", signal=guard.signal_name,
                            completed_iters=start, num_trees=num_trees,
                        )
                        telemetry.flush()
                        telemetry.flight_dump("preempt")
                    raise TrainingPreempted(
                        f"training preempted by {guard.signal_name}: "
                        f"snapshot at {start}/{num_trees} iterations in "
                        f"{cache_dir!r} is resumable (resume_training=True)"
                    )
            if can_early_stop:
                # vls_seen covers iterations [0, start), those of
                # before a resume too, so argmin is an absolute index.
                vls_seen.append(part["vls"])
                if _early_stop_hit(
                    vls_seen, min(start, num_trees), early_stop_lookahead
                ):
                    break
            if (
                abort_after_chunks is not None
                and chunks_done >= abort_after_chunks
            ):
                raise _TrainingAborted(
                    f"aborted after {chunks_done} chunks "
                    f"({start} iterations)"
                )
            if deadline is not None and time.monotonic() >= deadline:
                break

    if grown:
        timer.counts["device_loop.rank_score_views"] = rank_views / grown
    with timer.stage("device_loop.merge"):
        trees, lvs, tls, vls, obl_w, obl_b, vs_a, vs_b = _merge_chunk_parts(
            store.parts(), num_trees, boost.use_dart, carry
        )
    logs = {
        "train_loss": tls,
        "valid_loss": vls,
        "initial_predictions": init_pred,
        "oblique_w": obl_w,
        "oblique_b": obl_b,
        "vs_a": vs_a,
        "vs_b": vs_b,
        # Chunks of before a resume carry no wall (they ran in another
        # process); their iteration records report 0 seconds.
        "chunk_walls": chunk_walls,
    }
    return trees, lvs, logs


def _train_gbt_distributed(
    learner, prep, *, nv_rows, loss_obj, rule, tree_cfg, candidate_features,
    obl_P, vs_Pv, set_tr,
):
    """Distributed training entry point. The mode comes from the
    cache's shard layout: `row_shards=N` selects ROW-parallel training
    (parallel/dist_row.py — additive histogram sum-merge, streamed
    shard loads, row-sharded validation with distributed early
    stopping; `feature_shards=C > 1` on the same cache makes it hybrid
    row×feature), a plain `feature_shards=N` cache keeps the
    feature-parallel manager (parallel/dist_gbt.py). Validates the
    configuration down to the supported core (K = 1 loss, RANDOM
    sampling, axis-aligned splits — everything else raises with the
    knob to flip; feature-parallel additionally rejects a validation
    split), then hands off. Returns the exact (stacked trees, leaf
    values, logs) layout _train_gbt produces, so the model-assembly
    tail in train() is shared."""
    from ydf_tpu.dataset.cache import DatasetCache  # noqa: F401
    from ydf_tpu.ops.histogram import (
        resolve_hist_impl,
        resolve_hist_quant,
        resolve_hist_subtract,
    )
    from ydf_tpu.parallel.dist_gbt import DistGBTManager
    from ydf_tpu.parallel.dist_row import RowDistGBTManager
    from ydf_tpu.parallel.worker_service import WorkerPool

    cache = prep.get("cache")
    if cache is None:
        raise ValueError(
            "distributed_workers= requires training from a sharded "
            "DatasetCache: create_dataset_cache(..., feature_shards=N) "
            "or create_dataset_cache(..., row_shards=N), then "
            "train(cache)"
        )
    row_mode = getattr(cache, "row_shards", 0) > 0
    if not row_mode and cache.feature_shards < 1:
        raise ValueError(
            f"dataset cache {cache.path!r} has no shards; recreate it "
            "with create_dataset_cache(..., "
            f"feature_shards={len(learner.distributed_workers)}) or "
            f"row_shards={len(learner.distributed_workers)}"
        )
    wants_valid = (
        learner.validation_ratio > 0 and learner.early_stopping != "NONE"
    )
    unsupported = []
    if (nv_rows > 0 or wants_valid) and not row_mode:
        unsupported.append(
            "a validation split (set early_stopping='NONE' or "
            "validation_ratio=0.0 — feature-parallel training has no "
            "validation routing; a row-sharded cache "
            "(create_dataset_cache(..., row_shards=N)) supports "
            "distributed early stopping)"
        )
    if loss_obj.num_dims != 1:
        unsupported.append(
            f"multi-output losses (loss {loss_obj.name} has "
            f"{loss_obj.num_dims} dims)"
        )
    if learner.sampling_method != "RANDOM":
        unsupported.append(
            f"sampling_method={learner.sampling_method!r}"
        )
    if learner.dart_dropout > 0.0:
        unsupported.append("dart_dropout > 0")
    if learner.split_axis != "AXIS_ALIGNED" or obl_P > 0:
        unsupported.append(f"split_axis={learner.split_axis!r}")
    if vs_Pv > 0 or set_tr is not None:
        unsupported.append("set / vector-sequence features")
    if learner.monotonic_constraints:
        unsupported.append("monotonic constraints")
    if learner.mesh is not None:
        unsupported.append("mesh= (GSPMD) combined with RPC workers")
    if (
        learner.maximum_training_duration
        and learner.maximum_training_duration > 0
    ):
        unsupported.append("maximum_training_duration")
    if unsupported:
        raise ValueError(
            "distributed_workers= does not support: "
            + "; ".join(unsupported)
        )
    binner = prep["binner"]
    pool = WorkerPool(list(learner.distributed_workers))
    common = dict(
        loss_obj=loss_obj, rule=rule, tree_cfg=tree_cfg,
        num_trees=learner.num_trees, shrinkage=learner.shrinkage,
        subsample=learner.subsample,
        candidate_features=candidate_features,
        num_numerical=binner.num_numerical,
        seed=learner.random_seed,
        hist_impl=resolve_hist_impl("auto"),
        hist_subtract=resolve_hist_subtract(None),
        hist_quant=resolve_hist_quant(None),
        # Preemption-safe distributed training: with a working_dir the
        # manager snapshots at tree boundaries through the round-10
        # Snapshots contract, installs the SIGTERM/SIGINT guard
        # (forced final snapshot → TrainingPreempted → exit 75), and
        # resume_training reattaches a NEW manager bit-identically
        # (docs/distributed_training.md "Resume").
        working_dir=learner.working_dir,
        resume=learner.resume_training,
        snapshot_interval=(
            learner.resume_training_snapshot_interval_trees
        ),
        preempt_after_snapshots=learner._preempt_after_chunks,
        membership=learner.distributed_membership,
    )
    if row_mode:
        # Deterministic train/validation split — the EXACT expressions
        # of the single-machine branch in train() (which distributed
        # cache training skips so the bin matrix never materializes on
        # the manager): same seed, same permutation, same index sets.
        tr_idx = va_idx = None
        if wants_valid:
            n = cache.num_rows
            rng = np.random.RandomState(learner.random_seed)
            perm = rng.permutation(n)
            nv = min(max(int(n * learner.validation_ratio), 1), n - 1)
            va_idx, tr_idx = perm[:nv], perm[nv:]
        mgr = RowDistGBTManager(
            pool, cache, tr_idx=tr_idx, va_idx=va_idx,
            early_stop_lookahead=(
                learner.early_stopping_num_trees_look_ahead
                if learner.early_stopping == "LOSS_INCREASE"
                and va_idx is not None
                else 0
            ),
            **common,
        )
    else:
        mgr = DistGBTManager(pool, cache, **common)
    with _flight_guard():
        try:
            return mgr.train()
        finally:
            # The pool (and its persistent pipelined connections) is
            # per-train: release the sockets so the workers' idle reap
            # never has to.
            pool.close()


def _oom_failpoint():
    """The `telemetry.oom` chaos hook: converts an injected fault at
    the chunk boundary into a REAL MemoryError, so the chaos suite can
    prove an OOM mid-train leaves a usable flight-recorder post-mortem
    (reason "oom", MemoryLedger snapshot in the dump header) — the
    guard used to be exercised only by ordinary exceptions. Free
    module-constant check when failpoints are unarmed."""
    try:
        failpoints.hit("telemetry.oom")
    except failpoints.FailpointError as e:
        raise MemoryError(f"injected OOM: {e}") from None


@contextlib.contextmanager
def _flight_guard():
    """Flight-recorder guard around a boosting loop: an exception that
    escapes it (failpoint crash, worker-fleet loss, a real bug, an
    OOM) flushes buffered telemetry and writes the crash black box
    (`flight_<pid>.jsonl`) before propagating — the run stays
    diagnosable even though it died mid-chunk. MemoryError dumps with
    reason "oom" and, like every dump, the header carries the
    MemoryLedger snapshot — the post-mortem that says WHO held the
    bytes. TrainingPreempted is excluded: the preemption path writes
    its own dump with the signal name. Free no-op when telemetry is
    off; the dump itself never raises."""
    try:
        yield
    except TrainingPreempted:
        raise
    except BaseException as e:
        if telemetry.ENABLED:
            kind = "oom" if isinstance(e, MemoryError) else "exception"
            telemetry.flight_record(
                kind, error=f"{type(e).__name__}: {e}"
            )
            telemetry.flush()
            telemetry.flight_dump(
                "oom" if kind == "oom" else "train_exception"
            )
        raise


class _TrainingAborted(RuntimeError):
    """Raised by the test-only abort hook (the reference injects failures
    the same way: MaybeSimulateFailure, worker.cc:415-452)."""


class TrainingPreempted(RuntimeError):
    """SIGTERM/SIGINT arrived during checkpointed training. The boosting
    loop finished the in-flight chunk, saved its snapshot durably, and
    exited RESUMABLE: rerun with resume_training=True to continue from
    exactly where it stopped (bit-identical to an uninterrupted run).
    Schedulers distinguish this from a crash by `exit_code` (wired up by
    `python -m ydf_tpu.cli train`)."""

    #: EX_TEMPFAIL: transient condition — reschedule the job.
    exit_code = 75


class _PreemptionGuard:
    """Installs SIGTERM/SIGINT handlers around the checkpointed boosting
    loop (main thread only — Python delivers signals there; tuner trials
    on worker threads skip installation and keep the process handlers).
    The handler only sets a flag: the loop checks it at each chunk
    boundary, right after the snapshot save, so the forced "final
    snapshot" of a preemption is always the one just made durable. A
    second signal restores the previous handlers and re-delivers itself
    — a wedged chunk can still be killed the default way."""

    _SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self):
        self.triggered = False
        self.signal_name: Optional[str] = None
        self._old = {}

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            for sig in self._SIGNALS:
                try:
                    self._old[sig] = signal.signal(sig, self._handle)
                except (ValueError, OSError):
                    pass  # exotic embedding: keep existing handlers
        return self

    def __exit__(self, *exc):
        for sig, old in self._old.items():
            try:
                signal.signal(
                    sig, old if old is not None else signal.SIG_DFL
                )
            except (ValueError, OSError, TypeError):
                pass
        self._old.clear()
        return False

    def trigger(self, signum: int) -> None:
        """Flag a preemption (real handler and the _preempt_after_chunks
        test hook share this path)."""
        self.signal_name = signal.Signals(signum).name
        self.triggered = True

    def _handle(self, signum, frame):
        if self.triggered:
            # Second signal: restore the previous disposition and
            # re-deliver — the user wants out NOW.
            old = self._old.pop(signum, signal.SIG_DFL)
            try:
                signal.signal(
                    signum, old if old is not None else signal.SIG_DFL
                )
            except (ValueError, OSError, TypeError):
                signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        self.trigger(signum)




def _clamp_monotone_leaves(forest, binner, constraints):
    """Propagates [lower, upper] bounds down each tree and clamps leaf
    values — the reference's ApplyConstraintOnNode (training.h:160-168):
    at a monotone split, the midpoint of the two children's value
    estimates bounds the opposite sides, which guarantees monotonicity
    of the final piecewise-constant function."""
    from ydf_tpu.models.forest import Forest

    f = forest.to_numpy()
    nfeat = binner.num_features
    dirs = np.zeros((nfeat,), np.int8)
    for name, d in constraints.items():
        dirs[binner.feature_names.index(name)] = np.sign(d)
    ow = f.get("oblique_weights")
    P = 0 if ow is None else ow.shape[1]
    lv = f["leaf_value"].copy()  # [T, N, 1]
    T = lv.shape[0]
    for t in range(T):
        if P > 0:
            # A projection touching any constrained feature is monotone
            # INCREASING by construction (coefficients were sign-forced at
            # sampling time, cf. reference oblique.cc:1113-1126).
            touch = np.abs(ow[t][:, : len(dirs)]) @ np.abs(
                dirs[: ow.shape[2]].astype(np.float32)
            )
            proj_dirs = (touch > 0).astype(np.int8)
        stack = [(0, -np.inf, np.inf)]
        while stack:
            nid, lo, hi = stack.pop()
            if f["is_leaf"][t, nid]:
                lv[t, nid, 0] = np.clip(lv[t, nid, 0], lo, hi)
                continue
            left, right = int(f["left"][t, nid]), int(f["right"][t, nid])
            feat = int(f["feature"][t, nid])
            if 0 <= feat < nfeat:
                d = dirs[feat]
            elif P > 0 and nfeat <= feat < nfeat + P:
                d = proj_dirs[feat - nfeat]
            else:
                d = 0
            if d == 0:
                stack.append((left, lo, hi))
                stack.append((right, lo, hi))
            else:
                mid = 0.5 * (lv[t, left, 0] + lv[t, right, 0])
                mid = float(np.clip(mid, lo, hi))
                if d > 0:
                    stack.append((left, lo, mid))
                    stack.append((right, mid, hi))
                else:
                    stack.append((left, mid, hi))
                    stack.append((right, lo, mid))
    return Forest.from_numpy({**f, "leaf_value": lv})
