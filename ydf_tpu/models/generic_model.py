"""GenericModel: base class of all trained models.

Role of the reference's AbstractModel (`ydf/model/abstract_model.h:63`:
Predict/Evaluate/Save + describe) and PYDF GenericModel
(`ydf/port/python/ydf/model/generic_model.py:277`). Serving here routes raw
(un-binned) features through the Forest arrays — the vectorized XLA
equivalent of the reference's fast engines (`ydf/serving/fast_engine.h:41`);
binned-input serving is also available and bit-identical.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.config import Task
from ydf_tpu.utils import telemetry
from ydf_tpu.dataset.binning import Binner
from ydf_tpu.dataset.dataset import Dataset, InputData
from ydf_tpu.dataset.dataspec import DataSpecification
from ydf_tpu.metrics import Evaluation, evaluate_predictions
from ydf_tpu.models.forest import Forest
from ydf_tpu.ops.routing import forest_predict_bins, forest_predict_values


class GenericModel:
    model_type = "GENERIC"

    def __init__(
        self,
        task: Task,
        label: Optional[str],
        classes: Optional[List[str]],
        dataspec: DataSpecification,
        binner: Binner,
        forest: Forest,
        max_depth: int,
        extra_metadata: Optional[Dict[str, Any]] = None,
        native_missing: bool = False,
    ):
        self.task = task
        self.label = label
        self.classes = classes
        self.dataspec = dataspec
        self.binner = binner
        self.forest = forest
        self.max_depth = max_depth
        self.extra_metadata = extra_metadata or {}
        # True: missing values reach routing as NaN / -1 and follow the
        # forest's per-node na_left direction (the reference's NodeCondition
        # na_value semantics) — used by models imported from YDF format.
        # False: global imputation at encode time (our learners' training
        # semantics, reference training.cc LocalImputation*).
        self.native_missing = native_missing
        # Per-stage train() wall breakdown (utils/profiling.py), set by
        # the learners; None for imported/loaded models.
        self.training_profile: Optional[Dict[str, float]] = None

    # ------------------------------------------------------------------ #
    # Introspection
    # ------------------------------------------------------------------ #

    def input_feature_names(self) -> List[str]:
        return list(self.binner.feature_names)

    def num_trees(self) -> int:
        return int(self.forest.num_trees)

    def num_nodes(self) -> int:
        return int(np.asarray(self.forest.num_nodes).sum())

    def describe(self, output_format: str = "text") -> str:
        """Model card (reference describe.cc / pydf model.describe()):
        structure stats, input features with types, structure variable
        importances, training logs and self-evaluation when present.
        output_format: "text" or "html"."""
        if output_format == "html":
            return self._describe_html()
        f = self.forest.to_numpy()
        nn = np.asarray(f["num_nodes"])
        is_leaf = np.asarray(f["is_leaf"])
        # Per-tree leaf counts over the real node range.
        leaf_counts = [
            int(is_leaf[t, : nn[t]].sum()) for t in range(len(nn))
        ]
        feats = self.input_feature_names()
        lines = [
            f'Type: "{self.model_type}"',
            f"Task: {self.task.value}",
            f'Label: "{self.label}"',
        ]
        if self.classes:
            lines.append(f"Classes: {self.classes}")
        lines += [
            "",
            f"Input features ({len(feats)}):",
        ]
        for name in feats:
            col = self.dataspec.column_by_name(name)
            extra = (
                f" vocab={col.vocab_size}"
                if col.vocabulary is not None
                else f" mean={col.mean:.4g}"
            )
            lines.append(f"  {name}: {col.type.value}{extra}")
        for name in getattr(self.binner, "vs_names", []):
            col = self.dataspec.column_by_name(name)
            lines.append(
                f"  {name}: {col.type.value} dim={col.vector_length}"
            )
        lines += [
            "",
            f"Number of trees: {self.num_trees()}",
            f"Total number of nodes: {self.num_nodes()}",
            f"Number of leaves: {sum(leaf_counts)}",
            (
                f"Nodes per tree: min {int(nn.min())} / mean "
                f"{float(nn.mean()):.1f} / max {int(nn.max())}"
            )
            if len(nn)
            else "",
            f"Maximum depth: {self.max_depth}",
        ]
        # Structure variable importances (reference describe.cc section).
        try:
            from ydf_tpu.analysis.importance import structure_importances

            si = structure_importances(self)
            top = si.get("NUM_NODES") or next(iter(si.values()), [])
            if top:
                lines += ["", "Variable importances (NUM_NODES):"]
                for d in top[:10]:
                    lines.append(
                        f"  {d['feature']:>25}: {d['importance']:.5g}"
                    )
        except Exception:
            pass
        logs = getattr(self, "training_logs", None)
        if logs and logs.get("train_loss"):
            tl = logs["train_loss"]
            lines += [
                "",
                f"Training: {len(tl)} iterations, final train loss "
                f"{tl[-1]:.5f}"
                + (
                    f", final valid loss {logs['valid_loss'][-1]:.5f}"
                    if logs.get("valid_loss")
                    else ""
                ),
            ]
        oob = getattr(self, "oob_evaluation", None)
        if oob:
            m = ", ".join(
                f"{k}={v:.4f}" for k, v in list(oob["metrics"].items())[:4]
            )
            lines += ["", f"Self-evaluation (OOB): {m}"]
        lines += ["", "Dataspec:", str(self.dataspec)]
        return "\n".join(l for l in lines if l is not None)

    def _describe_html(self) -> str:
        """Sectioned, self-contained HTML model card (reference
        describe.cc:742 tabbed output: model / dataspec / training /
        variable importances / structure)."""
        from ydf_tpu.utils import html_report as H

        f = self.forest.to_numpy()
        nn = np.asarray(f["num_nodes"])
        is_leaf = np.asarray(f["is_leaf"])
        leaf_counts = [
            int(is_leaf[t, : nn[t]].sum()) for t in range(len(nn))
        ]
        summary = [
            ("Type", self.model_type),
            ("Task", self.task.value),
            ("Label", self.label),
        ]
        if self.classes:
            summary.append(("Classes", ", ".join(map(str, self.classes))))
        summary += [
            ("Trees", self.num_trees()),
            ("Nodes", self.num_nodes()),
            ("Leaves", sum(leaf_counts)),
            ("Max depth", self.max_depth),
        ]
        if getattr(self, "loss_name", ""):
            summary.append(("Loss", self.loss_name))
        model_pane = f"<div class='card'>{H.kv_table(summary)}</div>"

        feat_rows = []
        for name in self.input_feature_names():
            col = self.dataspec.column_by_name(name)
            extra = (
                f"vocab={col.vocab_size}"
                if col.vocabulary is not None
                else f"mean={col.mean:.4g}"
            )
            feat_rows.append((name, col.type.value, extra,
                              col.num_missing or 0))
        for name in getattr(self.binner, "vs_names", []):
            col = self.dataspec.column_by_name(name)
            feat_rows.append(
                (name, col.type.value, f"dim={col.vector_length}", 0)
            )
        dataspec_pane = H.data_table(
            ("feature", "type", "stats", "missing"), feat_rows
        )

        train_pane = "<div class='sub'>(no training logs)</div>"
        logs = getattr(self, "training_logs", None)
        if logs and logs.get("train_loss"):
            tl = [float(v) for v in logs["train_loss"]]
            series = [("train loss", list(range(1, len(tl) + 1)), tl)]
            if logs.get("valid_loss"):
                vl = [float(v) for v in logs["valid_loss"]]
                series.append(
                    ("valid loss", list(range(1, len(vl) + 1)), vl)
                )
            train_pane = (
                H.line_chart(series, title="Training loss",
                             x_label="iteration (trees)", y_label="loss")
                + H.kv_table([
                    ("Iterations", len(tl)),
                    ("Final train loss", f"{tl[-1]:.5f}"),
                ] + ([
                    ("Final valid loss", f"{logs['valid_loss'][-1]:.5f}")
                ] if logs.get("valid_loss") else []))
            )
        oob = getattr(self, "oob_evaluation", None)
        if oob:
            train_pane += "<h3>Self-evaluation (OOB)</h3>" + H.kv_table(
                [(k, f"{v:.5f}") for k, v in oob["metrics"].items()]
            )

        vi_pane = "<div class='sub'>(unavailable)</div>"
        try:
            from ydf_tpu.analysis.importance import structure_importances

            si = structure_importances(self)
            panes = []
            for kind, vals in si.items():
                if vals:
                    panes.append((kind, H.bar_chart_h(
                        [(d["feature"], d["importance"]) for d in vals],
                        title=kind,
                    )))
            if panes:
                vi_pane = H.tabs(panes, group="vi")
        except Exception:
            pass

        body = (
            f"<h1>{H.esc(self.model_type)} — {H.esc(str(self.label))}</h1>"
            "<div class='sub'>ydf_tpu model card</div>"
            + H.tabs(
                [
                    ("Model", model_pane),
                    ("Dataspec", dataspec_pane),
                    ("Training", train_pane),
                    ("Variable importances", vi_pane),
                ],
                group="desc",
            )
        )
        return H.document(f"{self.model_type} {self.label}", body)

    # ------------------------------------------------------------------ #
    # Analysis (reference: model.analyze / model.predict_shap /
    # model.analyze_prediction, generic_model.py:674-1271)
    # ------------------------------------------------------------------ #

    def analyze(self, data: InputData, **kwargs):
        from ydf_tpu.analysis import analyze as _analyze

        return _analyze(self, data, **kwargs)

    def predict_shap(self, data: InputData, max_rows: int = 200):
        """(phi [n, F, V], bias [V], rows [n]) SHAP values of the raw
        score; `rows` are the input row indices scored (subsampled and
        sorted when the input exceeds max_rows)."""
        from ydf_tpu.analysis import tree_shap

        return tree_shap(self, data, max_rows=max_rows)

    def analyze_prediction(self, single_example: InputData) -> str:
        """Per-example SHAP breakdown (reference analyze_prediction)."""
        from ydf_tpu.analysis import tree_shap

        phi, bias, _ = tree_shap(self, single_example, max_rows=1)
        names = self.input_feature_names()
        contrib = phi[0, :, 0]
        order = np.argsort(-np.abs(contrib))
        lines = [f"bias: {float(np.atleast_1d(bias)[0]):+.5f}"]
        for i in order:
            if abs(contrib[i]) > 1e-9:
                lines.append(f"{names[i]:>30}: {contrib[i]:+.5f}")
        return "\n".join(lines)

    # ------------------------------------------------------------------ #
    # JAX export / fine-tuning (reference: model.to_jax_function and
    # update_with_jax_params, pydf export_jax.py:488-1150,
    # generic_model.py:1271 — trivially native here: the forest already
    # lives in JAX arrays)
    # ------------------------------------------------------------------ #

    # --- tree inspection / editing (reference port/python/ydf/model/tree/)
    def get_tree(self, tree_idx: int):
        """Tree `tree_idx` as editable Python node objects
        (models/tree_api.py; reference model/tree/tree.py)."""
        from ydf_tpu.models.tree_api import forest_tree_to_python

        if not 0 <= tree_idx < self.num_trees():
            raise ValueError(
                f"tree_idx {tree_idx} out of range [0, {self.num_trees()})"
            )
        return forest_tree_to_python(self, tree_idx)

    def get_all_trees(self):
        return [self.get_tree(i) for i in range(self.num_trees())]

    def iter_trees(self):
        for i in range(self.num_trees()):
            yield self.get_tree(i)

    def set_tree(self, tree_idx: int, tree) -> None:
        """Replaces tree `tree_idx` with an edited Python tree."""
        from ydf_tpu.models.tree_api import set_forest_tree

        if not 0 <= tree_idx < self.num_trees():
            raise ValueError(
                f"tree_idx {tree_idx} out of range [0, {self.num_trees()})"
            )
        set_forest_tree(self, tree_idx, tree)

    def print_tree(self, tree_idx: int = 0) -> None:
        print(self.get_tree(tree_idx).pretty())

    def to_standalone_cc(
        self, name: str = "ydf_model", algorithm: str = "IF_ELSE"
    ) -> dict:
        """Dependency-free C++ header reproducing this model's predictions
        bit-for-bit (reference embed subsystem, serving/embed/embed.h:
        27-30). algorithm: "IF_ELSE" (per-tree branch chains) or
        "ROUTING" (data-bank node tables). Returns {filename: source}."""
        from ydf_tpu.serving.embed import to_standalone_cc

        return to_standalone_cc(self, name=name, algorithm=algorithm)

    def to_standalone_java(
        self, name: str = "YdfModel", package: str = None,
        algorithm: str = "IF_ELSE",
    ) -> dict:
        """Dependency-free standalone Java class (reference Java embed
        target, serving/embed/java/java_embed.cc). Same IR and modes as
        to_standalone_cc. Returns {filename: source}."""
        from ydf_tpu.serving.embed_java import to_standalone_java

        return to_standalone_java(
            self, name=name, package=package, algorithm=algorithm
        )

    def to_jax_function(self, apply_link_function: bool = True):
        """Returns (fn, params, encoder):

        * fn(x_num, x_cat, params) — jittable, differentiable in
          params["leaf_values"] (fine-tune leaves with optax, like the
          reference's leaves_as_params mode);
        * params — {"leaf_values": [T, N, V] f32};
        * encoder(data) -> (x_num, x_cat) host-side feature encoding.
        """
        from ydf_tpu.ops.routing import forest_predict_values

        if self.binner.num_set > 0:
            raise NotImplementedError(
                "to_jax_function over CATEGORICAL_SET features is not "
                "supported yet (the exported fn signature carries only "
                "x_num/x_cat)"
            )
        if getattr(self.forest, "vs_anchor", np.zeros(0)).size > 0:
            raise NotImplementedError(
                "to_jax_function over NUMERICAL_VECTOR_SEQUENCE conditions "
                "is not supported yet (the exported fn signature carries "
                "only x_num/x_cat, so VS nodes would silently misroute)"
            )

        forest = self.forest
        num_numerical = self.binner.num_numerical
        max_depth = self.max_depth
        combine = "mean" if self.model_type == "RANDOM_FOREST" else "sum"
        init = np.asarray(
            getattr(self, "initial_predictions", np.zeros(1)), np.float32
        )
        task = self.task
        K = int(getattr(self, "num_trees_per_iter", 1) or 1)
        link = apply_link_function

        is_rf = self.model_type == "RANDOM_FOREST"
        wta = bool(getattr(self, "winner_take_all", False))
        loss_name = getattr(self, "loss_name", "")
        multi_gbt = K > 1 and forest.leaf_value.shape[-1] == 1

        def fn(x_num, x_cat, params):
            f = forest._replace(
                leaf_value=jnp.asarray(params["leaf_values"])
            )
            if is_rf and task == Task.CLASSIFICATION and wta:
                # Winner-take-all voting: leaves become one-hot votes
                # (matches RandomForestModel.predict; the argmax makes
                # this branch non-differentiable in the leaf values, as
                # in the reference's voting engines).
                lv = f.leaf_value
                votes = jax.nn.one_hot(
                    jnp.argmax(lv, axis=-1), lv.shape[-1], dtype=lv.dtype
                )
                f = f._replace(leaf_value=votes)
            if multi_gbt:
                # Multiclass GBT: tree t contributes to dim t % K.
                outs = []
                for k in range(K):
                    sub = jax.tree.map(lambda a: a[k::K], f)
                    outs.append(
                        forest_predict_values(
                            sub, x_num, x_cat,
                            num_numerical=num_numerical,
                            max_depth=max_depth, combine=combine,
                        )[:, 0]
                    )
                raw = jnp.stack(outs, axis=1)
            else:
                raw = forest_predict_values(
                    f, x_num, x_cat, num_numerical=num_numerical,
                    max_depth=max_depth, combine=combine,
                )
            scores = raw + jnp.asarray(init)[None, :raw.shape[-1]]
            if is_rf:
                # RF outputs are already probabilities / means — no link.
                if task == Task.CLASSIFICATION:
                    if scores.shape[-1] == 2:
                        return scores[:, 1]
                    return scores
                return scores[:, 0] if scores.shape[-1] == 1 else scores
            if not link:
                return scores
            if task == Task.CLASSIFICATION:
                if scores.shape[-1] == 1:
                    return jax.nn.sigmoid(scores[:, 0])
                return jax.nn.softmax(scores, axis=-1)
            if loss_name == "POISSON":
                return jnp.exp(scores[:, 0])  # log link
            return scores[:, 0] if scores.shape[-1] == 1 else scores

        params = {"leaf_values": jnp.asarray(forest.leaf_value)}

        def encoder(data):
            ds = Dataset.from_data(data, dataspec=self.dataspec)
            x_num, x_cat, _ = self._encode_inputs(ds)
            return jnp.asarray(x_num), jnp.asarray(x_cat)

        return fn, params, encoder

    def to_tensorflow_saved_model(
        self, path: str, servo_api: bool = False,
        feature_dtypes: Optional[dict] = None,
    ) -> None:
        """Exports a standalone TF SavedModel reproducing predict()
        (reference port/python/ydf/model/export_tf.py): raw named feature
        tensors in, predictions out; the forest runs through the jax2tf
        bridge and the feature encoding is mirrored in the TF graph."""
        from ydf_tpu.models.export_tf import to_tensorflow_saved_model

        to_tensorflow_saved_model(
            self, path, servo_api=servo_api, feature_dtypes=feature_dtypes
        )

    def update_with_jax_params(self, params) -> None:
        """Writes fine-tuned leaf values back into the model (reference
        update_with_jax_params)."""
        lv = jnp.asarray(params["leaf_values"], jnp.float32)
        if lv.shape != self.forest.leaf_value.shape:
            raise ValueError(
                f"leaf_values shape {lv.shape} != "
                f"{self.forest.leaf_value.shape}"
            )
        self.forest = self.forest._replace(leaf_value=lv)
        # Invalidate serving caches derived from the old arrays.
        self._qs_cache = {}
        if hasattr(self, "_dim_forests"):
            del self._dim_forests

    # ------------------------------------------------------------------ #
    # Serving
    # ------------------------------------------------------------------ #

    def _encode_inputs(self, ds: Dataset):
        """Raw features → (x_num f32 [n, Fn] imputed, x_cat i32 [n, Fc],
        x_set u32 [n, Fs, W] packed sets or None)."""
        b = self.binner
        n = ds.num_rows
        x_num = np.zeros((n, b.num_numerical), np.float32)
        x_cat = np.zeros((n, b.num_categorical), np.int32)
        for i, name in enumerate(b.feature_names[: b.num_scalar]):
            if i < b.num_numerical:
                if ds.dataspec.has_column(name) and name in ds.data:
                    x_num[:, i] = ds.encoded_numerical(
                        name, impute=not self.native_missing
                    )
                else:
                    # Whole column absent = every value missing.
                    x_num[:, i] = (
                        np.nan if self.native_missing else b.impute_values[i]
                    )
            else:
                j = i - b.num_numerical
                if ds.dataspec.has_column(name) and name in ds.data:
                    idx = ds.encoded_categorical(
                        name, missing_code=-1 if self.native_missing else 0
                    )
                    x_cat[:, j] = np.where(idx >= b.num_bins, 0, idx)
                elif self.native_missing:
                    x_cat[:, j] = -1
        x_set = None
        if b.num_set > 0:
            # Mask width follows the trained forest (imported models keep
            # the full reference vocabulary; native ones the binner cap).
            W = int(np.shape(self.forest.cat_mask)[-1])
            x_set = np.zeros((n, b.num_set, W), np.uint32)
            for j, name in enumerate(b.feature_names[b.num_scalar:]):
                if ds.dataspec.has_column(name) and name in ds.data:
                    x_set[:, j, :] = ds.encoded_categorical_set(name, W)
        return x_num, x_cat, x_set

    def _encode_vs(self, ds: Dataset):
        """(values [n, Fv, L, D], lengths [n, Fv], missing [n, Fv]) padded
        vector-sequence inputs, or None when the model has none."""
        b = self.binner
        if getattr(b, "num_vs", 0) == 0:
            return None
        return b.transform_vs(ds)

    def _encode_set_missing(self, ds: Dataset):
        """bool [n, Fs] per-cell missing mask for set features (drives
        na_value routing of imported models); None when no set features."""
        b = self.binner
        if b.num_set == 0:
            return None
        out = np.zeros((ds.num_rows, b.num_set), bool)
        for j, name in enumerate(b.feature_names[b.num_scalar:]):
            if ds.dataspec.has_column(name) and name in ds.data:
                out[:, j] = ds.categorical_set_missing_mask(name)
            else:
                out[:, j] = True
        return out

    def list_compatible_engines(self) -> List[str]:
        """Names of serving engines compatible with this model, fastest
        first (reference PYDF model.list_compatible_engines /
        register_engines.cc IsCompatible ranking)."""
        from ydf_tpu.serving.registry import compatible_engines

        return [f.name for f in compatible_engines(self)]

    def force_engine(self, name: Optional[str]) -> None:
        """Pins predict() to one engine by name (reference PYDF
        model.force_engine); None restores automatic (fastest-compatible)
        selection. Raises for unknown or incompatible names."""
        from ydf_tpu.serving.registry import best_engine

        if name is not None:
            best_engine(self, forced=name)  # validates
        self._forced_engine = name

    def _fast_engine(self):
        """Fastest compatible non-generic engine for the CURRENT forest,
        or None when the registry ranks the generic routed engine first
        (serving/registry.py — the reference's BuildFastEngine flow).
        Cached per forest object — multiclass predict temporarily swaps
        self.forest per output dim."""
        from ydf_tpu.serving.registry import best_engine

        import os

        cache = getattr(self, "_qs_cache", None)
        if cache is None:
            cache = self._qs_cache = {}
        forced = getattr(self, "_forced_engine", None)
        # The env force-flag and the serving-impl switch participate in
        # compatibility gating (registry._qs_allowed /
        # registry._native_compatible) and tests toggle them
        # mid-process — they must be part of the key or a stale
        # selection would be served.
        key = (
            forced,
            os.environ.get("YDF_TPU_FORCE_QUICKSCORER"),
            os.environ.get("YDF_TPU_SERVE_IMPL"),
            id(self.forest.feature),
        )
        hit = cache.get(key)
        # Entries pin the keyed array (id() is only unique among live
        # objects) and are verified by identity before use. Caching the
        # whole selection (not just the build) keeps the per-predict cost
        # at a dict lookup — the compatibility probes compile the forest.
        if hit is None or hit[0] is not self.forest.feature:
            if len(cache) > 8:
                cache.clear()
            factory = best_engine(self, forced=forced)
            eng = None if factory.name == "Routed" else factory.build(self)
            cache[key] = (self.forest.feature, eng)
        return cache[key][1]

    def _note_serve(self, engine: str, batch: int, t0_ns: int, sp) -> None:
        """Per-call serving telemetry: latency histogram keyed by
        engine + power-of-two batch bucket (bounded label cardinality),
        request counter, span labels. Sites call under an ENABLED
        guard — the disabled predict path pays one bool check."""
        dur = time.perf_counter_ns() - t0_ns
        b = telemetry.pow2_bucket(max(batch, 1))
        telemetry.histogram(
            "ydf_serve_latency_ns", engine=engine, batch_pow2=b
        ).observe_ns(dur)
        telemetry.counter(
            "ydf_serve_requests_total", engine=engine
        ).inc()
        sp.set(engine=engine, batch=batch)

    def _raw_scores(self, data: InputData, combine: str) -> np.ndarray:
        # serve → batch(predict) → encode/kernel span hierarchy; the
        # latency histogram covers the WHOLE call (encode included —
        # the user-visible per-request latency).
        with telemetry.span("serve.predict") as sp:
            t0_ns = time.perf_counter_ns() if telemetry.ENABLED else 0
            ds = Dataset.from_data(data, dataspec=self.dataspec)
            with telemetry.span("serve.encode"):
                x_num, x_cat, x_set = self._encode_inputs(ds)
                vs = self._encode_vs(ds)
            if (
                combine == "sum"
                and not self.native_missing
                and x_set is None
                and vs is None
            ):
                eng = self._fast_engine()
                if eng is not None:
                    with telemetry.span("serve.kernel"):
                        out = np.asarray(
                            eng(jnp.asarray(x_num), jnp.asarray(x_cat))
                        )[:, None]
                    if telemetry.ENABLED:
                        self._note_serve(
                            type(eng).__name__, ds.num_rows, t0_ns, sp
                        )
                    return out
            set_missing = (
                self._encode_set_missing(ds) if self.native_missing else None
            )
            with telemetry.span("serve.kernel"):
                out = forest_predict_values(
                    self.forest,
                    jnp.asarray(x_num),
                    jnp.asarray(x_cat),
                    num_numerical=self.binner.num_numerical,
                    max_depth=self.max_depth,
                    combine=combine,
                    x_set=None if x_set is None else jnp.asarray(x_set),
                    set_missing=(
                        None if set_missing is None
                        else jnp.asarray(set_missing)
                    ),
                    x_vs_vals=None if vs is None else jnp.asarray(vs[0]),
                    x_vs_len=None if vs is None else jnp.asarray(vs[1]),
                    vs_missing=(
                        jnp.asarray(vs[2])
                        if vs is not None and self.native_missing
                        else None
                    ),
                )
                out = np.asarray(out)
            if telemetry.ENABLED:
                self._note_serve("Routed", ds.num_rows, t0_ns, sp)
            return out

    # ---- reference PYDF surface-parity accessors ---------------------- #
    # (ref port/python/ydf/model/generic_model.py; attribute-style state
    # like .label/.task/.dataspec also remains directly accessible.)

    def name(self) -> str:
        """Model type name, e.g. "RANDOM_FOREST" (ref model.name())."""
        return self.model_type

    def data_spec(self):
        """The model's dataspec (ref model.data_spec())."""
        return self.dataspec

    def label_classes(self) -> List[str]:
        """Classification label dictionary (ref model.label_classes())."""
        if not self.classes:
            raise ValueError(
                "label_classes is only defined for classification models"
            )
        return list(self.classes)

    def _column_indices(self) -> Dict[str, int]:
        return {c.name: i for i, c in enumerate(self.dataspec.columns)}

    def label_col_idx(self) -> int:
        return self._column_indices().get(self.label, -1)

    def input_features_col_idxs(self) -> List[int]:
        return [f[2] for f in self.input_features()]

    def input_features(self) -> List[tuple]:
        """[(name, column_type, column_index)] of the training features
        (ref model.input_features() InputFeature tuples)."""
        by_name = self._column_indices()
        cols = self.dataspec.columns
        return [
            (n, cols[by_name[n]].type.value, by_name[n])
            for n in self.input_feature_names()
        ]

    def predict_class(self, data: InputData) -> np.ndarray:
        """Most likely class name per example (classification only; ref
        model.predict_class)."""
        if not self.classes:
            raise ValueError(
                "predict_class is only defined for classification models"
            )
        p = np.asarray(self.predict(data))
        classes = np.asarray(self.classes)
        if p.ndim == 1:  # binary: probability of classes[1]
            return classes[(p >= 0.5).astype(np.int64)]
        return classes[np.argmax(p, axis=1)]

    def self_evaluation(self):
        """The model's own training-time evaluation: OOB metrics for RF,
        the held-out validation metrics for GBT, the pruning-validation
        metrics for CART (ref model.self_evaluation). None when the
        model has no self evaluation."""
        oob = getattr(self, "oob_evaluation", None)
        if oob is not None:
            return oob
        logs = getattr(self, "training_logs", None)
        if logs and logs.get("valid_loss") is not None:
            vl = np.asarray(logs["valid_loss"])
            if vl.size:
                # Logs are truncated to the KEPT iterations (gbt.py), so
                # the last entry is the saved model's validation loss —
                # with early stopping that is also the minimum; without
                # it, min() would report a loss the model never keeps.
                return {
                    "source": "gbt_validation",
                    "metrics": {"loss": float(vl[-1])},
                }
        return None

    def variable_importances(self) -> Dict[str, list]:
        """Model-stored variable importances as
        {importance_name: [(value, feature_name), ...]} sorted best
        first (ref model.variable_importances). Structure importances
        are always available; OOB permutation importances appear when
        they were computed at training time."""
        from ydf_tpu.analysis.importance import structure_importances

        out = {}
        for key, rows in structure_importances(self).items():
            out[key] = [
                (float(r["importance"]), r["feature"]) for r in rows
            ]
        oob_vi = getattr(self, "oob_variable_importances", None)
        if oob_vi:
            for key, rows in oob_vi.items():
                out[key] = [
                    (float(r["importance"]), r["feature"]) for r in rows
                ]
        return out

    def serialize(self) -> bytes:
        """The model as bytes (a tar of the saved directory); restore
        with ydf_tpu.deserialize_model (ref model.serialize)."""
        import io
        import tarfile
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            self.save(tmp)
            buf = io.BytesIO()
            with tarfile.open(fileobj=buf, mode="w") as tar:
                tar.add(tmp, arcname="model")
            return buf.getvalue()

    def to_cpp(self, name: str = "ydf_model") -> Dict[str, str]:
        """Standalone C++ serving sources (ref model.to_cpp; here the
        embed codegen is the C++ serving artifact — see
        to_standalone_cc for the algorithm choice)."""
        return self.to_standalone_cc(name=name)

    def to_tensorflow_function(self, feature_dtypes: Optional[dict] = None):
        """A callable tf.Module reproducing predict() without writing a
        SavedModel (ref model.to_tensorflow_function)."""
        from ydf_tpu.models.export_tf import to_tensorflow_function

        return to_tensorflow_function(self, feature_dtypes=feature_dtypes)

    def to_docker(self, path: str, exist_ok: bool = False) -> None:
        """Self-contained Docker serving endpoint directory (ref
        model.to_docker): Dockerfile + HTTP server + the saved model +
        this package, ready for `docker build`."""
        from ydf_tpu.models.export_docker import to_docker

        to_docker(self, path, exist_ok=exist_ok)

    def predict_leaves(self, data: InputData) -> np.ndarray:
        """Leaf node id of every example in every tree: int32 [n, T]
        (reference PredictLeaves,
        decision_forest_model.py:189 / decision_forest.cc leaves)."""
        from ydf_tpu.ops.routing import forest_leaves

        ds = Dataset.from_data(data, dataspec=self.dataspec)
        x_num, x_cat, x_set = self._encode_inputs(ds)
        vs = self._encode_vs(ds)
        set_missing = (
            self._encode_set_missing(ds) if self.native_missing else None
        )
        return np.asarray(
            forest_leaves(
                self.forest,
                jnp.asarray(x_num),
                jnp.asarray(x_cat),
                num_numerical=self.binner.num_numerical,
                max_depth=self.max_depth,
                x_set=None if x_set is None else jnp.asarray(x_set),
                set_missing=(
                    None if set_missing is None
                    else jnp.asarray(set_missing)
                ),
                x_vs_vals=None if vs is None else jnp.asarray(vs[0]),
                x_vs_len=None if vs is None else jnp.asarray(vs[1]),
                vs_missing=(
                    jnp.asarray(vs[2])
                    if vs is not None and self.native_missing
                    else None
                ),
            )
        )

    def distance(
        self, data1: InputData, data2: Optional[InputData] = None
    ) -> np.ndarray:
        """Pairwise distance [n1, n2] = 1 − Breiman proximity (the
        fraction of trees routing the pair to the same leaf) — the
        reference's model.distance
        (decision_forest_model.py:196; proximity definition
        random_forest.h:211-217). data2=None compares data1 with
        itself."""
        from ydf_tpu.ops.routing import leaf_proximity

        l1 = jnp.asarray(self.predict_leaves(data1))
        l2 = l1 if data2 is None else jnp.asarray(
            self.predict_leaves(data2)
        )
        return 1.0 - np.asarray(leaf_proximity(l1, l2))

    def predict(self, data: InputData) -> np.ndarray:
        raise NotImplementedError

    def predict_tf_examples(self, serialized) -> np.ndarray:
        """Scores a sequence of serialized tf.Example protos — the
        reference's tf.Example serving adapter (serving/tf_example.h:
        feed tf.Examples straight to the engines) over the in-repo wire
        codec, no TensorFlow dependency."""
        from ydf_tpu.dataset.tfrecord import tf_examples_to_columns

        cols = tf_examples_to_columns(serialized)
        return self.predict(Dataset.from_data(cols, dataspec=self.dataspec))

    def predict_example(self, example: dict):
        """Scores ONE {column: value} row — the reference's
        single-example Predict overload (abstract_model.h:500-516) over
        the row-wise example path (dataset/example.py). Missing columns
        follow the model's missing-value semantics."""
        ds = Dataset.from_examples([example], dataspec=self.dataspec)
        out = self.predict(ds)
        return out[0]

    def benchmark(
        self, data: InputData, num_runs: int = 10, engines: bool = False
    ) -> dict:
        """Inference speed on `data` (reference model.benchmark /
        cli/benchmark_inference.cc): best wall time over `num_runs`
        batched predicts, compile excluded.

        engines=True additionally times each applicable serving engine on
        the pre-encoded inputs (reference benchmark_inference.cc runs
        every compatible engine): `routed` (flat-node traversal,
        ops/routing.py), `native_batch` / `native_binned` (the batched
        data-bank kernel, serving/native_serve.py), `quickscorer`
        (leaf-mask Pallas kernel) and `binned_quickscorer`
        (uint8-bin-matrix variant, the 8-bit-engine analogue). Engine
        rows exclude host-side encoding, which the `predict` row
        includes."""
        import time

        if num_runs < 1:
            raise ValueError("num_runs must be >= 1")
        ds = Dataset.from_data(data, dataspec=self.dataspec)
        self.predict(ds)  # warmup + compile
        # Peak-RSS bracketing AFTER warmup (compile allocations are
        # excluded, like the timing): a serving path that grows the
        # process peak during steady-state predicts is a memory
        # regression, caught by the same floor-guard machinery as
        # latency (bench.py infer_peak_rss_delta_bytes).
        rss0 = telemetry.peak_rss_bytes()
        times = []
        # Per-run latencies feed the serving latency histogram class
        # (utils/telemetry.py), which derives the p50/p99 per-example
        # figures the bench's serving-regression guard reads.
        hist = telemetry.LatencyHistogram()
        for _ in range(num_runs):
            t0 = time.perf_counter()
            self.predict(ds)
            dt = time.perf_counter() - t0
            times.append(dt)
            hist.observe_s(dt)
        best = min(times)
        n = max(ds.num_rows, 1)
        out = {
            "num_examples": ds.num_rows,
            "num_runs": num_runs,
            "best_wall_s": best,
            "ns_per_example": 1e9 * best / n,
            # Percentiles over the per-call wall times, normalized per
            # example (log2-bucket resolution, ~12.5 % — see
            # LatencyHistogram). p50 tracks the typical call; p99 the
            # tail the QPS story cares about.
            "p50_ns_per_example": hist.percentile_ns(50) / n,
            "p99_ns_per_example": hist.percentile_ns(99) / n,
            # How much the process-lifetime RSS peak grew across the
            # measured runs; 0 = steady-state serving allocated nothing
            # the process had not already peaked at.
            "peak_rss_delta_bytes": max(
                telemetry.peak_rss_bytes() - rss0, 0
            ),
        }
        if not engines:
            return out

        def _time_engine(fn):
            np.asarray(fn())  # warmup + compile
            ts = []
            for _ in range(num_runs):
                t0 = time.perf_counter()
                np.asarray(fn())
                ts.append(time.perf_counter() - t0)
            return 1e9 * min(ts) / n

        eng = {}
        x_num, x_cat, x_set = self._encode_inputs(ds)
        vs = self._encode_vs(ds)
        jx_num, jx_cat = jnp.asarray(x_num), jnp.asarray(x_cat)
        eng["routed"] = _time_engine(
            lambda: forest_predict_values(
                self.forest, jx_num, jx_cat,
                num_numerical=self.binner.num_numerical,
                max_depth=self.max_depth,
                combine="sum",
                x_set=None if x_set is None else jnp.asarray(x_set),
                x_vs_vals=None if vs is None else jnp.asarray(vs[0]),
                x_vs_len=None if vs is None else jnp.asarray(vs[1]),
            )
        )
        if (
            x_set is None
            and vs is None
            and not self.native_missing
            # QuickScorer sums one scalar per tree — multiclass forests
            # (K trees/iter) go through the routed engine per class.
            and getattr(self, "num_trees_per_iter", 1) == 1
        ):
            # A builder returns None for a forest outside its envelope;
            # a compile or run failure of an applicable engine raises.
            from ydf_tpu.serving import (
                build_binned_quickscorer,
                build_quickscorer,
            )
            from ydf_tpu.serving.native_serve import (
                build_native_binned_engine,
                build_native_engine,
            )

            qs = build_quickscorer(self)
            if qs is not None:
                eng["quickscorer"] = _time_engine(
                    lambda: qs(jx_num, jx_cat)
                )
            bq = build_binned_quickscorer(self)
            if bq is not None:
                bins_u8 = jnp.asarray(
                    self.binner.transform(ds)[
                        :, : self.binner.num_scalar
                    ]
                )
                eng["binned_quickscorer"] = _time_engine(
                    lambda: bq(bins_u8, jx_cat)
                )
            nb = build_native_engine(self)
            if nb is not None:
                eng["native_batch"] = _time_engine(
                    lambda: nb(x_num, x_cat)
                )
            nbb = build_native_binned_engine(self)
            if nbb is not None:
                bins_nb = np.ascontiguousarray(
                    self.binner.transform(ds)[
                        :, : self.binner.num_scalar
                    ]
                )
                eng["native_binned"] = _time_engine(
                    lambda: nbb(bins_nb)
                )
        out["engines_ns_per_example"] = eng
        return out

    # ------------------------------------------------------------------ #
    # Evaluation
    # ------------------------------------------------------------------ #

    def evaluate(
        self,
        data: InputData,
        weights: Optional[str] = None,
        confidence_intervals: bool = False,
        num_bootstrap: int = 2000,
    ) -> Evaluation:
        ds = Dataset.from_data(data, dataspec=self.dataspec)
        preds = self.predict(ds)
        w = ds.data[weights].astype(np.float32) if weights else None
        if self.task in (Task.CATEGORICAL_UPLIFT, Task.NUMERICAL_UPLIFT):
            tcol = self.extra_metadata.get("uplift_treatment")
            if not tcol:
                raise ValueError("Uplift model lacks uplift_treatment metadata")
            tcodes = ds.encoded_categorical(tcol)
            keep = tcodes >= 1  # drop OOV/missing treatments, like training
            treatments = (tcodes[keep] == 2).astype(np.int64)
            if self.task == Task.CATEGORICAL_UPLIFT:
                labels = (
                    ds.encoded_categorical(self.label)[keep] == 2
                ).astype(np.int64)
            else:
                labels = np.asarray(ds.data[self.label], np.float64)[keep]
            return evaluate_predictions(
                self.task,
                labels,
                np.asarray(preds)[keep],
                weights=None if w is None else w[keep],
                treatments=treatments,
            )
        if self.task == Task.SURVIVAL_ANALYSIS:
            from ydf_tpu.learners.gbt import _bool_column

            ecol = self.extra_metadata.get("label_event_observed")
            if not ecol:
                raise ValueError(
                    "Survival model lacks label_event_observed metadata"
                )
            return evaluate_predictions(
                self.task,
                np.asarray(ds.data[self.label], np.float64),
                preds,
                weights=w,
                events=_bool_column(np.asarray(ds.data[ecol])),
            )
        labels = ds.encoded_label(self.label, self.task)
        groups = None
        ndcg_truncation = 5
        if self.task == Task.RANKING:
            gcol = self.extra_metadata.get("ranking_group")
            groups = ds.data[gcol] if gcol else None
            ndcg_truncation = int(self.extra_metadata.get("ndcg_truncation", 5))
        return evaluate_predictions(
            self.task, labels, preds, classes=self.classes, weights=w,
            groups=groups, ndcg_truncation=ndcg_truncation,
            confidence_intervals=confidence_intervals,
            num_bootstrap=num_bootstrap,
        )

    # ------------------------------------------------------------------ #
    # Persistence (see models/io.py)
    # ------------------------------------------------------------------ #

    def save_ydf(self, path: str) -> None:
        """Exports in the reference implementation's model-directory
        format (readable by the reference's LoadModel / pip ydf)."""
        from ydf_tpu.models.ydf_format import export_ydf_model

        export_ydf_model(self, path)

    def save(self, path: str) -> None:
        from ydf_tpu.models import io

        io.save_model(self, path)

    def _metadata(self) -> Dict[str, Any]:
        """Subclass-specific JSON metadata."""
        return {}
