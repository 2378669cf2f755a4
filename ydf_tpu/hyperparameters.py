"""Generic hyperparameter spec + validation layer.

TPU-native counterpart of the reference's generic-hyperparameter system
(`ydf/learner/decision_tree/generic_parameters.cc` — the string-dict spec,
`ydf/learner/abstract_learner.h` SetHyperParameters — the validation, and
`ydf/learner/wrapper_generator.cc` — the generated typed wrappers). Here
the flow is inverted, which is the natural Python formulation: the typed
constructor signature IS the source of truth, and the machine-readable
spec is derived from it by introspection, enriched with the curated
constraint/doc table below.

What this provides:

* ``hyperparameter_spec(LearnerCls)`` → ``{name: HyperParameter}`` with
  type, default, bounds, choices and doc — the analogue of the reference's
  ``GenericHyperParameterSpecification`` proto.
* Constructor-time validation on every learner (hooked via
  ``GenericLearner.__init_subclass__``): unknown kwargs are rejected with
  a did-you-mean suggestion instead of crashing late or being silently
  absorbed; known kwargs are checked against type/range/choice
  constraints.
* ``format_documentation()`` → the generated hyperparameter doc page
  (reference `learner/export_doc.cc`), exposed as the
  ``hyperparameters`` CLI subcommand.

The tuner's ``validate_space`` and the CLI consume the same spec.
"""

from __future__ import annotations

import dataclasses
import difflib
import inspect
from typing import Any, Dict, List, Optional, Tuple, Type

import numpy as np

#: Parameters that identify dataset columns or non-tunable plumbing —
#: real constructor arguments, but not "hyperparameters" in the
#: reference's sense (they appear in the spec with kind="config").
_CONFIG_PARAMS = {
    "label", "task", "features", "weights", "ranking_group",
    "uplift_treatment", "label_event_observed", "label_entry_age",
    "column_types", "working_dir", "resume_training",
    "resume_training_snapshot_interval_trees", "mesh", "random_seed",
    "base_learner", "search_space", "tuner", "monotonic_constraints",
    "workers", "worker_timeout_s",
}


@dataclasses.dataclass(frozen=True)
class HyperParameter:
    """One entry of a learner's hyperparameter specification."""

    name: str
    type: str  # "int" | "float" | "bool" | "str" | "enum" | "object"
    default: Any
    doc: str = ""
    min_value: Optional[float] = None
    max_value: Optional[float] = None
    choices: Optional[Tuple[str, ...]] = None
    kind: str = "hyperparameter"  # or "config"
    allow_auto: bool = False  # int parameter also accepting "auto"

    def to_json(self) -> Dict[str, Any]:
        default = self.default
        if not isinstance(default, (bool, int, float, str, type(None))):
            # Task enums and other objects: serialize by name/repr.
            default = getattr(default, "name", None) or repr(default)
        out: Dict[str, Any] = {
            "name": self.name,
            "type": self.type,
            "default": default,
            "doc": self.doc,
            "kind": self.kind,
        }
        if self.min_value is not None:
            out["min_value"] = self.min_value
        if self.max_value is not None:
            out["max_value"] = self.max_value
        if self.choices is not None:
            out["choices"] = list(self.choices)
        if self.allow_auto:
            # Explains the int-typed parameter's "auto" default to
            # spec-driven consumers (cli.py prints this JSON).
            out["allow_auto"] = True
        return out


@dataclasses.dataclass(frozen=True)
class _Info:
    doc: str
    min_value: Optional[float] = None
    max_value: Optional[float] = None
    choices: Optional[Tuple[str, ...]] = None
    # int-typed parameter that also accepts the literal "auto" (resolved
    # against the dataset at train time, e.g. num_bins/max_frontier
    # shrinking to small data).
    allow_auto: bool = False


# Curated constraint/doc table, shared across learners (the reference
# shares its generic parameters the same way: one kColumnNameX entry is
# reused by every learner that accepts it, generic_parameters.cc).
_PARAM_INFO: Dict[str, _Info] = {
    # ---- shared dataset/ingestion knobs (GenericLearner) ----
    "max_vocab_count": _Info(
        "Maximum categorical dictionary size per column; less frequent "
        "values collapse into the out-of-vocabulary item. -1 disables the "
        "cap.", min_value=-1),
    "min_vocab_frequency": _Info(
        "Minimum number of occurrences for a categorical value to enter "
        "the dictionary.", min_value=1),
    "num_bins": _Info(
        "Number of histogram bins per numerical feature (including the "
        "missing-value bin). The uint8 bin matrix caps this at 256. "
        "\"auto\" (default) shrinks to the dataset — pow2ceil(n/180) "
        "clipped to [64, 256] — so small-data training does not stream "
        "256-bin layer buffers for a 4k-row dataset.",
        min_value=2, max_value=256, allow_auto=True),
    "discretize_numerical_columns": _Info(
        "Pre-discretize all numerical columns in the dataspec "
        "(DISCRETIZED_NUMERICAL in the reference): cheaper training, "
        "coarser thresholds."),
    "num_discretized_numerical_bins": _Info(
        "Bins used when discretize_numerical_columns=True.",
        min_value=2, max_value=65536),
    # ---- tree growth ----
    "num_trees": _Info("Number of trees.", min_value=1),
    "max_depth": _Info(
        "Maximum tree depth. -1 means unlimited in the reference; here "
        "growth is layer-synchronous so a finite cap is required (-2 for "
        "the isolation-forest automatic depth ceil(log2(examples))).",
        min_value=-2),
    "min_examples": _Info(
        "Minimum number of examples in a node for it to be split.",
        min_value=1),
    "max_frontier": _Info(
        "Maximum open nodes per layer (static-shape analogue of the "
        "reference's best-first growth cap: when a layer would exceed it, "
        "only the highest-gain splits survive). \"auto\" (default) caps "
        "at pow2ceil(n / (2*min_examples)), bounded by 1024 — a layer "
        "can never usefully hold more open nodes than that.",
        min_value=1, allow_auto=True),
    "num_candidate_attributes": _Info(
        "Number of features sampled per node as split candidates. 0 uses "
        "the task default (sqrt(F) classification, F/3 regression); -1 "
        "uses all features.", min_value=-1),
    "num_candidate_attributes_ratio": _Info(
        "Fraction of features sampled per node; takes precedence over "
        "num_candidate_attributes when > 0. -1 disables.",
        min_value=-1.0, max_value=1.0),
    # ---- GBT ----
    "shrinkage": _Info(
        "Learning rate applied to each tree's output.",
        min_value=0.0, max_value=1.0),
    "subsample": _Info(
        "Fraction of examples sampled per iteration (stochastic gradient "
        "boosting).", min_value=0.0, max_value=1.0),
    "validation_ratio": _Info(
        "Fraction of training examples held out for validation loss and "
        "early stopping. 0 disables.", min_value=0.0, max_value=1.0),
    "early_stopping": _Info(
        "Early-stopping policy over the validation loss.",
        choices=("NONE", "LOSS_INCREASE", "MIN_LOSS_FINAL")),
    "early_stopping_num_trees_look_ahead": _Info(
        "Look-ahead window (trees) for the early-stopping minimum.",
        min_value=1),
    "l2_regularization": _Info(
        "L2 penalty on leaf values in the gain and leaf output.",
        min_value=0.0),
    "use_hessian_gain": _Info(
        "Split gain from the loss's hessians (XGBoost's criterion) and "
        "not from the variance of the gradients. True is the only gain "
        "implemented and the default here; upstream YDF defaults to "
        "false. False raises NotImplementedError."),
    "loss": _Info(
        "Loss function. DEFAULT selects by task (binomial log-likelihood "
        "for binary classification, multinomial for multiclass, MSE for "
        "regression, lambdarank NDCG for ranking, Cox for survival).",
        choices=(
            "DEFAULT", "BINOMIAL_LOG_LIKELIHOOD", "MULTINOMIAL_LOG_LIKELIHOOD",
            "SQUARED_ERROR", "MEAN_AVERAGE_ERROR", "POISSON",
            "BINARY_FOCAL_LOSS", "LAMBDA_MART_NDCG", "XE_NDCG_MART",
            "COX_PROPORTIONAL_HAZARD",
        )),
    "ndcg_truncation": _Info(
        "NDCG@k truncation for the lambdarank loss.", min_value=1),
    "ranking_max_group_size": _Info(
        "Cap on the documents of a query group that train; a longer "
        "group keeps its first ones, with a warning. None (the default): "
        "every document of every group trains.",
        min_value=1),
    "sampling_method": _Info(
        "Per-iteration example sampling: RANDOM (uses `subsample`), GOSS "
        "(gradient-based one-side sampling) or SELGB (selective gradient "
        "boosting, ranking only).",
        choices=("RANDOM", "GOSS", "SELGB")),
    "goss_alpha": _Info("GOSS: fraction of top-gradient examples kept.",
                        min_value=0.0, max_value=1.0),
    "goss_beta": _Info("GOSS: sampling rate of the remaining examples.",
                       min_value=0.0, max_value=1.0),
    "selective_gradient_boosting_ratio": _Info(
        "SelGB: ratio of negative examples kept.",
        min_value=0.0, max_value=1.0),
    "apply_link_function": _Info(
        "Apply the loss's link function (sigmoid/softmax/exp) in "
        "predict(); False returns raw margins."),
    "dart_dropout": _Info(
        "DART: probability of dropping each past tree when computing the "
        "gradients of a new iteration. 0 disables DART.",
        min_value=0.0, max_value=1.0),
    "early_stopping_initial_iteration": _Info(
        "First iteration at which early stopping may trigger.",
        min_value=0),
    # ---- oblique ----
    "split_axis": _Info(
        "Split structure: AXIS_ALIGNED or SPARSE_OBLIQUE random "
        "projections (computed as one MXU matmul per tree).",
        choices=("AXIS_ALIGNED", "SPARSE_OBLIQUE", "MHLD_OBLIQUE")),
    "sparse_oblique_num_projections_exponent": _Info(
        "Projections per tree = ceil(num_features ** exponent).",
        min_value=0.0, max_value=2.0),
    "sparse_oblique_projection_density_factor": _Info(
        "Expected nonzero coefficients per projection = factor.",
        min_value=0.0),
    "sparse_oblique_weights": _Info(
        "Projection coefficient distribution (reference oblique.h:15-38).",
        choices=("BINARY", "CONTINUOUS", "POWER_OF_TWO", "INTEGER")),
    "sparse_oblique_max_num_projections": _Info(
        "Upper bound on projections per tree.", min_value=1),
    "sparse_oblique_weights_power_of_two_min_exponent": _Info(
        "POWER_OF_TWO weights: minimum exponent (weight = ±2^e)."),
    "sparse_oblique_weights_power_of_two_max_exponent": _Info(
        "POWER_OF_TWO weights: maximum exponent (weight = ±2^e)."),
    "sparse_oblique_weights_integer_minimum": _Info(
        "INTEGER weights: minimum coefficient value."),
    "sparse_oblique_weights_integer_maximum": _Info(
        "INTEGER weights: maximum coefficient value."),
    "mhld_oblique_max_num_attributes": _Info(
        "MHLD oblique: max attributes entering the LDA projection.",
        min_value=1),
    # ---- vector sequence ----
    "numerical_vector_sequence_num_anchors": _Info(
        "Anchors sampled per (tree, VS feature) per condition kind.",
        min_value=1),
    "numerical_vector_sequence_enable_closer_than": _Info(
        "Enable anchor closer-than conditions."),
    "numerical_vector_sequence_enable_projected_more_than": _Info(
        "Enable anchor projected-more-than conditions."),
    # ---- RF ----
    "bootstrap_training_dataset": _Info(
        "Bootstrap-sample examples per tree (bagging); required for OOB "
        "evaluation."),
    "bootstrap_size_ratio": _Info(
        "Bootstrap sample size as a fraction of the training set.",
        min_value=0.0),
    "winner_take_all": _Info(
        "Classification voting: each tree votes its majority class "
        "instead of averaging probabilities."),
    "compute_oob_performances": _Info(
        "Compute out-of-bag evaluation during training."),
    "compute_oob_variable_importances": _Info(
        "Compute out-of-bag permutation variable importances (slower)."),
    "honest": _Info(
        "Honest trees: half the examples grow the structure, the other "
        "half estimates leaf values (Wager & Athey)."),
    "honest_ratio_leaf_examples": _Info(
        "Fraction of examples reserved for leaf-value estimation in "
        "honest trees.", min_value=0.0, max_value=1.0),
    "maximum_training_duration": _Info(
        "Deadline in seconds for the whole train() call; the tree loop "
        "stops within one chunk of it and returns the trees finished so "
        "far. Negative = no limit (reference "
        "abstract_learner.proto maximum_training_duration)."),
    # ---- Isolation forest ----
    "subsample_count": _Info(
        "Examples sampled per isolation tree.", min_value=2),
    "subsample_ratio": _Info(
        "Examples per isolation tree as a fraction; overrides "
        "subsample_count when > 0.", min_value=-1.0, max_value=1.0),
    # ---- HP optimizer / tuner ----
    "num_trials": _Info("Number of search trials.", min_value=1),
    "holdout_ratio": _Info(
        "Fraction of training rows held out for trial scoring.",
        min_value=0.0, max_value=1.0),
    "parallel_trials": _Info(
        "Concurrent trials (0 = one per visible device).", min_value=0),
    "cross_validation_folds": _Info(
        "When >= 2, score each trial by k-fold cross-validation instead "
        "of a single holdout (reference evaluation via cross-validation, "
        "hyperparameters_optimizer.cc).", min_value=0),
    # ---- deep learners ----
    "num_layers": _Info("Number of hidden / transformer layers.",
                        min_value=1),
    "layer_size": _Info("Width of each MLP hidden layer.", min_value=1),
    "drop_out": _Info("Dropout rate.", min_value=0.0, max_value=1.0),
    "cat_embedding_dim": _Info(
        "Embedding dimension for categorical features.", min_value=1),
    "token_dim": _Info("Transformer token dimension.", min_value=1),
    "num_heads": _Info("Transformer attention heads.", min_value=1),
    "num_epochs": _Info("Training epochs.", min_value=1),
    "batch_size": _Info("Training batch size.", min_value=1),
    "learning_rate": _Info("Optimizer learning rate.", min_value=0.0),
    # ---- CART ----
    # validation_ratio doc shared with GBT above.
}

_CONFIG_DOC: Dict[str, str] = {
    "label": "Name of the label column.",
    "task": "Learning task (ydf_tpu.Task).",
    "features": "Explicit input feature list; None selects all "
                "supported columns.",
    "weights": "Name of the example-weight column.",
    "ranking_group": "Query-group column for ranking tasks.",
    "uplift_treatment": "Treatment-assignment column for uplift tasks.",
    "label_event_observed": "Event-observed indicator column (survival).",
    "label_entry_age": "Entry-age column (left-truncated survival).",
    "column_types": "Forced column types, {name: ColumnType}.",
    "working_dir": "Directory for training snapshots.",
    "resume_training": "Resume from the latest snapshot in working_dir.",
    "resume_training_snapshot_interval_trees":
        "Trees between training snapshots.",
    "mesh": "jax.sharding.Mesh for distributed training.",
    "random_seed": "Seed for all stochastic choices.",
    "monotonic_constraints": "{feature_name: +1|-1} monotonicity.",
    "base_learner": "Learner whose hyperparameters are optimized.",
    "search_space": "{name: [candidate values]} search space.",
    "tuner": "Configured RandomSearchTuner.",
}


def _type_of(default: Any, annotation: Any) -> str:
    if isinstance(default, bool):
        return "bool"
    if isinstance(default, int):
        return "int"
    if isinstance(default, float):
        return "float"
    if isinstance(default, str):
        return "str"
    if default is None and str(annotation) == "Optional[int]":
        return "int"  # None: no limit (ranking_max_group_size)
    return "object"


def _iter_init_params(cls: Type) -> Dict[str, inspect.Parameter]:
    """Named __init__ parameters across the MRO (child wins), skipping
    self / *args / **kwargs."""
    out: Dict[str, inspect.Parameter] = {}
    for klass in reversed(cls.__mro__):
        init = klass.__dict__.get("__init__")
        if init is None:
            continue
        fn = inspect.unwrap(getattr(init, "__wrapped__", init))
        try:
            sig = inspect.signature(fn)
        except (TypeError, ValueError):
            continue
        for name, p in sig.parameters.items():
            if name == "self" or p.kind in (
                inspect.Parameter.VAR_POSITIONAL,
                inspect.Parameter.VAR_KEYWORD,
            ):
                continue
            out[name] = p
    return out


def hyperparameter_spec(cls: Type) -> Dict[str, HyperParameter]:
    """Machine-readable hyperparameter spec of a learner class."""
    spec: Dict[str, HyperParameter] = {}
    for name, p in _iter_init_params(cls).items():
        default = None if p.default is inspect.Parameter.empty else p.default
        info = _PARAM_INFO.get(name)
        kind = "config" if name in _CONFIG_PARAMS else "hyperparameter"
        doc = (info.doc if info else _CONFIG_DOC.get(name, ""))
        ptype = _type_of(default, p.annotation)
        if info and info.choices is not None:
            ptype = "enum"
        if info and info.allow_auto:
            # "auto" defaults would infer as str; the parameter is an int
            # with a dataset-resolved sentinel.
            ptype = "int"
        spec[name] = HyperParameter(
            name=name,
            type=ptype,
            default=default,
            doc=doc,
            min_value=info.min_value if info else None,
            max_value=info.max_value if info else None,
            choices=info.choices if info else None,
            kind=kind,
            allow_auto=bool(info and info.allow_auto),
        )
    return spec


def _check_value(hp: HyperParameter, value: Any, cls_name: str) -> None:
    if value is None:
        return
    if hp.choices is not None:
        if not isinstance(value, str):
            if hp.name == "loss" and hasattr(value, "grad_hess"):
                # CustomLoss objects are a documented alternative to the
                # enum names (reference custom-loss bridges,
                # learner/custom_loss.cc) — the duck-type check mirrors
                # what the boosting loop requires of them.
                return
            raise TypeError(
                f"{cls_name}: hyperparameter {hp.name!r} expects one of "
                f"{list(hp.choices)}, got {type(value).__name__} {value!r}"
            )
        if value not in hp.choices:
            raise ValueError(
                f"{cls_name}: invalid value {value!r} for "
                f"hyperparameter {hp.name!r}; expected one of "
                f"{list(hp.choices)}"
            )
        return
    if hp.type == "bool":
        if not isinstance(value, bool):
            raise TypeError(
                f"{cls_name}: hyperparameter {hp.name!r} expects a bool, "
                f"got {type(value).__name__}"
            )
        return
    if hp.type in ("int", "float"):
        if hp.allow_auto and value == "auto":
            return
        # numpy scalars are everyday inputs (np.int64 from np.arange,
        # np.float32 from a search grid) — accept them alongside the
        # Python types; np.bool_ is rejected like bool.
        if isinstance(value, (bool, np.bool_)) or not isinstance(
            value, (int, float, np.integer, np.floating)
        ):
            raise TypeError(
                f"{cls_name}: hyperparameter {hp.name!r} expects "
                f"{'an int' if hp.type == 'int' else 'a number'}, got "
                f"{type(value).__name__}"
            )
        if hp.type == "int" and not isinstance(value, (int, np.integer)):
            raise TypeError(
                f"{cls_name}: hyperparameter {hp.name!r} expects an int, "
                f"got {type(value).__name__}"
            )
        if hp.min_value is not None and value < hp.min_value:
            raise ValueError(
                f"{cls_name}: hyperparameter {hp.name!r}={value!r} is below "
                f"the minimum {hp.min_value}"
            )
        if hp.max_value is not None and value > hp.max_value:
            raise ValueError(
                f"{cls_name}: hyperparameter {hp.name!r}={value!r} is above "
                f"the maximum {hp.max_value}"
            )
        return
    if hp.type == "str" and not isinstance(value, str):
        raise TypeError(
            f"{cls_name}: hyperparameter {hp.name!r} expects a str, got "
            f"{type(value).__name__}"
        )


def validate_call_kwargs(cls: Type, kwargs: Dict[str, Any]) -> None:
    """Rejects unknown constructor kwargs (did-you-mean suggestion) and
    checks known ones against the spec. Called automatically from every
    learner constructor via the __init_subclass__ hook."""
    spec = hyperparameter_spec(cls)
    for name, value in kwargs.items():
        hp = spec.get(name)
        if hp is None:
            close = difflib.get_close_matches(name, spec.keys(), n=1)
            hint = f"; did you mean {close[0]!r}?" if close else ""
            raise TypeError(
                f"{cls.__name__} got an unknown hyperparameter "
                f"{name!r}{hint} (see {cls.__name__}."
                "hyperparameter_spec() for the full list)"
            )
        _check_value(hp, value, cls.__name__)


class HyperparameterValidationMixin:
    """Inherit to get (a) constructor-kwarg validation on every subclass
    and (b) the ``hyperparameter_spec()`` classmethod. Shared by
    GenericLearner, GenericDeepLearner and the HP-optimizer learner."""

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        install_validation(cls)

    @classmethod
    def hyperparameter_spec(cls) -> Dict[str, HyperParameter]:
        """{name: HyperParameter} — machine-readable spec of every
        constructor parameter (type, default, bounds, choices, doc)."""
        return hyperparameter_spec(cls)


def install_validation(cls: Type) -> None:
    """Wraps cls.__init__ (only when defined by cls itself) so that every
    construction validates its kwargs against the spec."""
    init = cls.__dict__.get("__init__")
    if init is None or getattr(init, "_hp_validated", False):
        return
    import functools

    @functools.wraps(init)
    def wrapped(self, *args, **kwargs):
        # Bind positionals to names so they're validated too.
        try:
            bound = inspect.signature(init).bind(self, *args, **kwargs)
            named = {
                k: v for k, v in bound.arguments.items()
                if k not in ("self", "args", "kwargs")
            }
            named.update(bound.arguments.get("kwargs", {}))
        except TypeError:
            named = dict(kwargs)
        validate_call_kwargs(type(self), named)
        init(self, *args, **kwargs)
        # Coerce numpy scalars to Python scalars post-init so they never
        # leak into JSON metadata (model save, tuner logs, snapshots).
        for name in hyperparameter_spec(type(self)):
            v = getattr(self, name, None)
            if isinstance(v, np.generic):
                setattr(self, name, v.item())

    wrapped._hp_validated = True
    cls.__init__ = wrapped


# ---------------------------------------------------------------------- #
# Documentation generation (reference learner/export_doc.cc).
# ---------------------------------------------------------------------- #

def format_documentation(classes: Optional[List[Type]] = None) -> str:
    """Markdown hyperparameter documentation for the given learner
    classes (default: all registered learners)."""
    if classes is None:
        classes = default_learner_classes()
    lines = ["# Hyperparameters", ""]
    for cls in classes:
        spec = hyperparameter_spec(cls)
        lines.append(f"## {cls.__name__}")
        lines.append("")
        for kind, title in (("hyperparameter", "Hyperparameters"),
                            ("config", "Configuration")):
            rows = [h for h in spec.values() if h.kind == kind]
            if not rows:
                continue
            lines.append(f"### {title}")
            lines.append("")
            lines.append("| name | type | default | constraints | doc |")
            lines.append("|---|---|---|---|---|")
            for h in rows:
                cons = []
                if h.min_value is not None:
                    cons.append(f"min {h.min_value}")
                if h.max_value is not None:
                    cons.append(f"max {h.max_value}")
                if h.choices is not None:
                    cons.append(" / ".join(h.choices))
                lines.append(
                    f"| `{h.name}` | {h.type} | `{h.default!r}` | "
                    f"{'; '.join(cons)} | {h.doc} |"
                )
            lines.append("")
    return "\n".join(lines)


def default_learner_classes() -> List[Type]:
    from ydf_tpu.learners.cart import CartLearner
    from ydf_tpu.learners.gbt import GradientBoostedTreesLearner
    from ydf_tpu.learners.hyperparameter_optimizer import (
        HyperParameterOptimizerLearner,
    )
    from ydf_tpu.learners.isolation_forest import IsolationForestLearner
    from ydf_tpu.learners.random_forest import RandomForestLearner

    return [
        GradientBoostedTreesLearner,
        RandomForestLearner,
        CartLearner,
        IsolationForestLearner,
        HyperParameterOptimizerLearner,
    ]
