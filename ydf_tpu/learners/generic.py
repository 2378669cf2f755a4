"""GenericLearner: shared train() plumbing for all learners.

Mirrors the role of the reference's AbstractLearner
(`ydf/learner/abstract_learner.h:42` TrainWithStatus) + the PYDF
GenericLearner (`ydf/port/python/ydf/learner/generic_learner.py:255`):
dataset ingestion → dataspec → feature selection → label encoding →
learner-specific training, returning a model.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ydf_tpu.config import Task
from ydf_tpu.dataset.binning import BinnedDataset, Binner
from ydf_tpu.dataset.dataset import Dataset, InputData
from ydf_tpu.dataset.dataspec import ColumnType
from ydf_tpu.hyperparameters import HyperparameterValidationMixin


class GenericLearner(HyperparameterValidationMixin):
    # Every learner constructor validates its kwargs against the
    # machine-readable hyperparameter spec (ydf_tpu/hyperparameters.py —
    # counterpart of the reference's SetHyperParameters validation,
    # abstract_learner.h): unknown names are rejected at construction
    # time with a suggestion instead of being silently absorbed.

    def __init__(
        self,
        label: Optional[str],
        task: Task,
        features: Optional[Sequence[str]] = None,
        weights: Optional[str] = None,
        max_vocab_count: int = 2000,
        min_vocab_frequency: int = 5,
        num_bins="auto",
        random_seed: int = 123456,
        column_types: Optional[Dict[str, ColumnType]] = None,
        discretize_numerical_columns: bool = False,
        num_discretized_numerical_bins: int = 255,
    ):
        self.label = label
        self.task = task
        self.features = list(features) if features is not None else None
        self.weights = weights
        self.max_vocab_count = max_vocab_count
        self.min_vocab_frequency = min_vocab_frequency
        self.num_bins = num_bins
        self.random_seed = random_seed
        # User-forced column types (reference: DataSpecificationGuide) and
        # the PYDF discretize_numerical_columns / num_discretized_numerical_
        # bins pair (data_spec.proto:361 detect_numerical_as_discretized_
        # numerical).
        self.column_types = dict(column_types) if column_types else {}
        self.discretize_numerical_columns = discretize_numerical_columns
        self.num_discretized_numerical_bins = num_discretized_numerical_bins

    # ---- reference PYDF learner-surface parity ----------------------- #
    # (ref port/python/ydf/learner/generic_learner.py)

    def learner_name(self) -> str:
        """e.g. "GradientBoostedTreesLearner" (ref learner_name)."""
        return type(self).__name__

    def hyperparameters(self) -> Dict[str, object]:
        """Current hyperparameter values keyed by spec name (ref
        learner.hyperparameters)."""
        return {
            name: getattr(self, name)
            for name in type(self).hyperparameter_spec()
            if hasattr(self, name)
        }

    def validate_hyperparameters(self) -> None:
        """Re-checks the CURRENT attribute values against the spec —
        catches invalid values assigned after construction (ref
        learner.validate_hyperparameters)."""
        from ydf_tpu.hyperparameters import validate_call_kwargs

        validate_call_kwargs(type(self), self.hyperparameters())

    def extract_input_feature_names(self, data: InputData) -> list:
        """The feature columns this learner would train on for `data`
        (ref extract_input_feature_names): dataspec inference + the
        label/weights/group/treatment exclusions — a metadata query, no
        binning or encoding pass."""
        return self._select_feature_names(self._infer_dataset(data))

    def cross_validation(
        self,
        data: InputData,
        folds: int = 10,
        confidence_intervals: bool = True,
    ):
        """k-fold out-of-fold pooled evaluation (ref
        learner.cross_validation; metrics/cross_validation.py)."""
        from ydf_tpu.metrics.cross_validation import cross_validation

        return cross_validation(
            self, data, num_folds=folds,
            seed=self.random_seed,
            confidence_intervals=confidence_intervals,
        )

    # ------------------------------------------------------------------ #

    def _infer_dataset(self, data: InputData) -> Dataset:
        """Dataset ingestion with this learner's type policy: forced label /
        group / treatment column types + user column_types + discretization
        flags. Shared by _prepare and learners that need the dataspec of
        the FULL dataset before an internal split (CART's pruning holdout).
        """
        column_types = dict(self.column_types)
        group_col = getattr(self, "ranking_group", None)
        if group_col:
            # Ranking query-group keys default to HASH columns (the
            # reference's convention, data_spec.proto:85): no dictionary,
            # never a split candidate; learners group on the raw values.
            # An explicit user-supplied type wins.
            column_types.setdefault(group_col, ColumnType.HASH)
        treat_col = getattr(self, "uplift_treatment", None)
        if treat_col:
            # Treatment groups are dictionary-encoded: index 1 = control
            # (most frequent), index 2 = treated — the reference's
            # convention (decision_tree.proto:66-69).
            column_types[treat_col] = ColumnType.CATEGORICAL
        if self.label is not None and self.task in (
            Task.CLASSIFICATION, Task.CATEGORICAL_UPLIFT,
        ):
            # Classification labels are always dictionary-encoded, whatever
            # their raw dtype (reference: label goes through a categorical
            # guide) — the shared dictionary makes label encoding consistent
            # across train/valid/test datasets.
            column_types[self.label] = ColumnType.CATEGORICAL
        return Dataset.from_data(
            data,
            label=self.label,
            # A learner that pre-splits its input pins the FULL dataset's
            # dataspec here so the label dictionary covers classes that
            # only occur in held-out rows.
            dataspec=getattr(self, "_forced_dataspec", None),
            max_vocab_count=self.max_vocab_count,
            min_vocab_frequency=self.min_vocab_frequency,
            column_types=column_types,
            detect_numerical_as_discretized=self.discretize_numerical_columns,
            discretized_max_bins=self.num_discretized_numerical_bins,
        )

    def _prepare_from_cache(self, cache, valid=None) -> Dict:
        """Ingestion from an on-disk binned DatasetCache (out-of-core
        path, dataset/cache.py): the bins stay memmapped until the single
        device transfer. Task plumbing columns (ranking groups, uplift
        treatment, survival event/entry) and the raw numerical matrix
        (SPARSE_OBLIQUE) are available when the cache stored them
        (create_dataset_cache kwargs)."""
        from ydf_tpu.config import Task as _Task

        if self.label != cache.label:
            raise ValueError(
                f"Cache was built for label {cache.label!r}, learner wants "
                f"{self.label!r}"
            )
        if cache.weights != self.weights:
            # Both directions matter: a learner expecting weights the cache
            # lacks would silently train unweighted, and a weightless
            # learner on a weighted cache would silently apply the cached
            # weights while an explicit valid= dataset gets uniform ones —
            # either way, inconsistently weighted early stopping.
            raise ValueError(
                f"Learner weights column {self.weights!r} does not match "
                f"the cache's stored weights ({cache.weights!r}); recreate "
                f"the cache with weights={self.weights!r} or construct the "
                f"learner with weights={cache.weights!r}"
            )
        # Column requirements per task — a helpful error instead of a
        # KeyError deep in the loss.
        def _need(col_attr: str) -> None:
            col = getattr(self, col_attr, None)
            if col and col not in cache.extra_columns:
                raise ValueError(
                    f"task {self.task} needs column {col!r} stored in the "
                    f"cache; recreate it with create_dataset_cache(..., "
                    f"{col_attr}={col!r})"
                )

        if self.task == _Task.RANKING:
            _need("ranking_group")
        elif self.task == _Task.SURVIVAL_ANALYSIS:
            _need("label_event_observed")
            _need("label_entry_age")
        elif self.task in (_Task.CATEGORICAL_UPLIFT, _Task.NUMERICAL_UPLIFT):
            _need("uplift_treatment")
        raw = None
        if getattr(self, "split_axis", "AXIS_ALIGNED") != "AXIS_ALIGNED":
            raw = cache.raw_numerical
            if raw is None and cache.binner.num_numerical > 0:
                raise ValueError(
                    "SPARSE_OBLIQUE needs raw feature values; recreate the "
                    "cache with store_raw_numerical=True"
                )
        classes = cache.label_classes()
        labels = np.asarray(cache.labels)
        w = cache.sample_weights
        data = {cache.label: labels}
        for col in cache.extra_columns:
            data[col] = cache.extra_column(col)
        out = {
            "dataset": Dataset(data, cache.dataspec),
            "binned": None,
            "binner": cache.binner,
            "cache": cache,  # handle (distributed training shards off it)
            "bins": cache.bins,  # uint8 memmap [n, F]
            "set_bits": None,
            "vs": None,
            "raw_numerical": raw,
            "labels": labels,
            "sample_weights": (
                np.asarray(w, np.float32)
                if w is not None
                else np.ones((cache.num_rows,), np.float32)
            ),
        }
        if self.task in (_Task.CLASSIFICATION, _Task.CATEGORICAL_UPLIFT):
            if classes is None:
                raise ValueError(
                    "Cache label is numerical; train with a regression task"
                )
            out["classes"] = classes
        if valid is not None:
            vds = Dataset.from_data(
                valid, label=self.label, dataspec=cache.dataspec
            )
            out["valid_dataset"] = vds
            out["valid_bins"] = cache.binner.transform(vds)
            out["valid_set_bits"] = None
            out["valid_vs"] = None
            if self.label is not None:
                out["valid_labels"] = vds.encoded_label(
                    self.label, self.task
                )
            if self.weights is not None:
                out["valid_weights"] = vds.data[self.weights].astype(
                    np.float32
                )
        return out

    def _select_feature_names(self, ds: Dataset) -> list:
        """Training feature columns for an inferred dataset: explicit
        `features=` wins; otherwise every supported column minus the
        label/weights/group/treatment/survival plumbing columns."""
        if self.features is not None:
            return list(self.features)
        exclude = {
            self.label,
            self.weights,
            getattr(self, "ranking_group", None),
            getattr(self, "uplift_treatment", None),
            getattr(self, "label_event_observed", None),
            getattr(self, "label_entry_age", None),
        } - {None}
        supported = {
            ColumnType.NUMERICAL,
            ColumnType.CATEGORICAL,
            ColumnType.BOOLEAN,
            ColumnType.DISCRETIZED_NUMERICAL,
        }
        if getattr(self, "_supports_set_features", True):
            # Isolation forests opt out (the reference trains IF on
            # numerical splits only, isolation_forest.cc).
            supported.add(ColumnType.CATEGORICAL_SET)
        if getattr(self, "_supports_vs_features", False):
            # Anchor-projection splits (reference vector_sequence.cc);
            # GBT-only for now.
            supported.add(ColumnType.NUMERICAL_VECTOR_SEQUENCE)
        return [
            c.name
            for c in ds.dataspec.columns
            if c.name not in exclude and c.type in supported
        ]

    def _prepare(
        self,
        data: InputData,
        valid: Optional[InputData] = None,
        targets: bool = True,
        timer=None,
    ) -> Dict:
        """Common ingestion: dataset, binning, encoded label/weights.
        `targets=False` leaves "labels" and "sample_weights" of an
        in-memory dataset to a later `_encode_targets(out)`: a caller
        that may hold them already (GBT's device inputs kept with the
        Dataset) decides once it has seen the bins.

        Its steps are spans of `timer` (the calling train()'s
        StageTimer; None: a throw-away one): `ingest_bin.dataspec`
        (the learner's column types, and a re-inference where they
        differ from the Dataset's), `ingest_bin.binner_fit`,
        `ingest_bin.transform` and `ingest_bin.targets`."""
        from ydf_tpu.config import resolve_num_bins
        from ydf_tpu.dataset.cache import DatasetCache
        from ydf_tpu.utils.profiling import StageTimer

        if isinstance(data, DatasetCache):
            return self._prepare_from_cache(data, valid=valid)
        timer = timer or StageTimer()
        with timer.stage("ingest_bin.dataspec"):
            ds = self._infer_dataset(data)
        feature_names = self._select_feature_names(ds)
        # Auto-shrunk bins must still hold every categorical dictionary
        # (indices >= num_bins collapse to OOV).
        max_vocab = max(
            (
                ds.dataspec.column_by_name(f).vocab_size
                for f in feature_names
                if ds.dataspec.column_by_name(f).type
                == ColumnType.CATEGORICAL
            ),
            default=0,
        )
        with timer.stage("ingest_bin.binner_fit"):
            binner = BinnedDataset.fit_binner(
                ds, feature_names,
                num_bins=resolve_num_bins(
                    self.num_bins, ds.num_rows, min_cat_vocab=max_vocab
                ),
            )
        if binner.num_vs > 0 and not getattr(
            self, "_supports_vs_features", False
        ):
            # An explicitly requested VS feature must not silently train
            # as a no-op column.
            raise NotImplementedError(
                f"{type(self).__name__} does not support "
                f"NUMERICAL_VECTOR_SEQUENCE features "
                f"{binner.vs_names}"
            )
        with timer.stage("ingest_bin.transform"):
            binned = BinnedDataset.of_binner(ds, binner)
            out = {
                "dataset": ds,
                "binned": binned,
                "binner": binner,
                "bins": binned.bins,
                "set_bits": binned.set_bits,  # None without set columns
                "vs": binned.vs,  # None without vector-sequence columns
            }
            if valid is not None:
                vds = Dataset.from_data(
                    valid, label=self.label, dataspec=ds.dataspec
                )
                out["valid_dataset"] = vds
                out["valid_bins"] = binner.transform(vds)
                out["valid_set_bits"] = binner.transform_sets(vds)
                out["valid_vs"] = binner.transform_vs(vds)
                if self.label is not None:
                    out["valid_labels"] = vds.encoded_label(
                        self.label, self.task
                    )
                if self.weights is not None:
                    out["valid_weights"] = vds.data[self.weights].astype(
                        np.float32
                    )
        if (
            self.label is not None
            and self._label_task() == Task.CLASSIFICATION
        ):
            out["classes"] = ds.label_classes(self.label)
        if targets:
            with timer.stage("ingest_bin.targets"):
                self._encode_targets(out)
        return out

    def _label_task(self) -> Task:
        """The task the label column is encoded under."""
        if self.task == Task.CATEGORICAL_UPLIFT:
            # Outcomes are dictionary-encoded like classification labels.
            return Task.CLASSIFICATION
        if self.task in (Task.NUMERICAL_UPLIFT, Task.SURVIVAL_ANALYSIS):
            # Survival labels are departure ages — plain numericals.
            return Task.REGRESSION
        return self.task

    def _encode_targets(self, out: Dict) -> None:
        """Adds the training rows' encoded label ("labels") and weights
        ("sample_weights") to `out`, a `_prepare` result: the part of
        ingestion that passes over every row on every call (at 56M rows
        a class label's encoding and `np.ones` are 2.2 s): the span
        `ingest_bin.targets` of whoever calls it."""
        ds = out["dataset"]
        if self.label is not None:
            out["labels"] = ds.encoded_label(self.label, self._label_task())
        if self.weights is not None:
            out["sample_weights"] = ds.data[self.weights].astype(np.float32)
        else:
            out["sample_weights"] = np.ones((ds.num_rows,), np.float32)

    def train(self, data: InputData, valid: Optional[InputData] = None):
        raise NotImplementedError
