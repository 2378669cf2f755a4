"""Feature bucketization: dataset → dense uint8 bin matrix.

This is the TPU build's equivalent of the reference's DISCRETIZED_NUMERICAL
transform (`ydf/dataset/data_spec.proto:267`) and of the distributed dataset
cache's discretization (`ydf/learner/distributed_decision_tree/dataset_cache/
dataset_cache.proto:42-58`) — except it is applied to *every* feature up
front, because the TPU trainer is histogram-only: training operates on a
dense `uint8[num_examples, num_features]` matrix, the layout that makes the
per-layer split search one big XLA reduction.

Semantics:
  * NUMERICAL / BOOLEAN / DISCRETIZED_NUMERICAL columns: missing values are
    globally mean-imputed (reference GLOBAL_IMPUTATION,
    `training.cc:160`), then digitized against per-column ascending
    boundaries: `bin(v) = #{b : boundary_b <= v}` so the split
    "bin <= t" ⇔ "v < boundary_t" ⇔ the reference's HigherCondition
    "v >= threshold goes right" with threshold = boundary_t.
  * If a column has ≤ num_bins-1 distinct values, boundaries are the
    midpoints between consecutive distinct values — making binned training
    *exactly* equivalent to exhaustive split search (the reference's
    splitter_scanner.h numerical bucket semantics). Otherwise boundaries
    are (deduplicated) quantiles.
  * CATEGORICAL columns: bin = dictionary index (0 = OOV). Vocabulary
    indices ≥ num_bins collapse to OOV; the dictionary is frequency-sorted,
    so only the rarest categories collapse.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ydf_tpu.dataset.dataspec import ColumnType, DataSpecification
from ydf_tpu.dataset.dataset import Dataset

_NUMERICAL_LIKE = (
    ColumnType.NUMERICAL,
    ColumnType.BOOLEAN,
    ColumnType.DISCRETIZED_NUMERICAL,
)

_BIN_IMPLS = ("native", "numpy")

#: np.repeat-expansion ceiling of boundaries_from_sketch: a weighted
#: item set whose total weight fits under this is quantiled through
#: np.quantile on the expanded multiset (bit-identical to the legacy
#: sample path, which never exceeds the 200k row sample); above it the
#: weighted replica of the same "linear" method runs in O(items).
_QUANTILE_EXPAND_CAP = 1 << 21


def boundaries_from_sketch(
    values: np.ndarray,
    weights: np.ndarray,
    num_bins: int,
    distinct_is_exact: bool,
) -> np.ndarray:
    """Bin boundaries from a weighted item set (ascending unique
    `values`, positive integer `weights`) — the shared boundary → bin
    seam of `Binner.fit` and the sketch-fed distributed cache build
    (dataset/sketch.py): both paths call THIS function, so single-
    machine and distributed builds agree on boundary semantics by
    construction.

      * `distinct_is_exact` and ≤ num_bins-1 items: midpoints between
        consecutive distinct values, computed in `values`' own dtype —
        binned training is exactly equivalent to exhaustive split
        search, and the legacy fit path (f32 unique values) keeps its
        bit-identical boundaries.
      * otherwise: deduplicated weighted quantiles of the multiset,
        replicating numpy's "linear" method (virtual index q·(n-1),
        same-lerp `a+(b-a)·t` / `b-(b-a)·(1-t)` branch at t ≥ 0.5) so a
        weight-1 item set reproduces np.quantile of the raw sample
        bit-for-bit.
    """
    max_boundaries = num_bins - 1
    values = np.asarray(values)
    weights = np.asarray(weights, np.int64)
    if values.size == 0:
        return np.zeros((0,), np.float32)
    if distinct_is_exact and len(values) <= max_boundaries:
        return ((values[:-1] + values[1:]) / 2).astype(np.float32)
    total = int(weights.sum())
    qs_pos = np.linspace(0, 1, num_bins + 1)[1:-1]
    v64 = values.astype(np.float64)
    if total <= _QUANTILE_EXPAND_CAP:
        qs = np.quantile(
            np.repeat(v64, weights), qs_pos, method="linear"
        )
    else:
        cw = np.cumsum(weights)
        h = qs_pos * (total - 1)
        lo = np.floor(h).astype(np.int64)
        g = h - lo
        hi = np.minimum(lo + 1, total - 1)
        a = v64[np.searchsorted(cw, lo, side="right")]
        b = v64[np.searchsorted(cw, hi, side="right")]
        qs = np.where(g < 0.5, a + (b - a) * g, b - (b - a) * (1 - g))
    return np.unique(qs).astype(np.float32)


def resolve_bin_impl(impl: str = "auto") -> str:
    """Resolves the scalar-binning implementation for Binner.transform.

    "auto" prefers the fused native kernel (native/binning_ffi.cc via
    ops/binning_native.py, ~10x the per-column NumPy `searchsorted`
    loop at the bench shape) and degrades to "numpy" without a
    toolchain. YDF_TPU_BIN_IMPL forces a choice; like the histogram's
    YDF_TPU_HIST_IMPL, a bad value must fail HERE with a clear message,
    not later inside the transform."""
    if impl == "auto":
        forced = os.environ.get("YDF_TPU_BIN_IMPL")
        if forced:
            impl = forced
    if impl != "auto":
        if impl not in _BIN_IMPLS:
            raise ValueError(
                f"Unknown binning impl {impl!r} (YDF_TPU_BIN_IMPL?); "
                f"expected one of {_BIN_IMPLS}"
            )
        if impl == "native":
            from ydf_tpu.ops import binning_native

            if not binning_native.available():
                raise RuntimeError(
                    "binning impl forced to 'native' but the native "
                    "kernel is unavailable (no C++ toolchain?) — unset "
                    "YDF_TPU_BIN_IMPL or use 'numpy'"
                )
        return impl
    from ydf_tpu.ops import binning_native

    return "native" if binning_native.available() else "numpy"


@dataclasses.dataclass
class Binner:
    """Per-feature binning rules, fit once on the training dataset.

    Feature order is [numericals..., categoricals...] — a static partition so
    the split-search kernels can slice the bin matrix into a numerical block
    (scanned with prefix sums over bins) and a categorical block (scanned in
    gradient-ratio order) without per-feature branching.
    """

    feature_names: List[str]
    num_numerical: int  # features [0, num_numerical) are numerical-like
    num_bins: int
    # [F, num_bins-1] ascending; padded with +inf. Categorical rows unused.
    boundaries: np.ndarray
    # [F] imputation value for missing numericals (column mean).
    impute_values: np.ndarray
    # [F] number of "real" bins per feature (numerical: #boundaries+1,
    # categorical: min(vocab_size, num_bins), set: capped vocab).
    feature_num_bins: np.ndarray
    # Number of trailing CATEGORICAL_SET features. Layout is
    # [numericals..., categoricals..., sets...]; set features are not part
    # of the uint8 bin matrix — they encode as packed multi-hot uint32
    # words (transform_sets), one fixed width for all set features.
    num_set: int = 0
    # NUMERICAL_VECTOR_SEQUENCE features (data_spec.proto:73-84). Not part
    # of the bin matrix or of `feature_names`: their candidate splits are
    # per-tree sampled anchor projections (ops/vector_sequence.py), binned
    # on the fly. All VS features share one dense padded encoding
    # [n, Fv, vs_max_len, vs_dim] (transform_vs).
    vs_names: List[str] = dataclasses.field(default_factory=list)
    vs_dims: List[int] = dataclasses.field(default_factory=list)
    vs_max_len: int = 0

    @property
    def num_vs(self) -> int:
        return len(self.vs_names)

    @property
    def vs_dim(self) -> int:
        """Common (max) vector dimensionality of the padded encoding."""
        return max(self.vs_dims, default=0)

    @property
    def num_features(self) -> int:
        return len(self.feature_names)

    @property
    def num_scalar(self) -> int:
        """Features carried by the uint8 bin matrix (all but sets)."""
        return self.num_features - self.num_set

    @property
    def num_categorical(self) -> int:
        return self.num_features - self.num_numerical - self.num_set

    @property
    def set_width_words(self) -> int:
        """uint32 words per set feature in the packed multi-hot encoding."""
        if self.num_set == 0:
            return 0
        vmax = int(self.feature_num_bins[self.num_scalar:].max())
        return (vmax + 31) // 32

    # ------------------------------------------------------------------ #

    @staticmethod
    def fit(
        dataset: Dataset,
        features: Sequence[str],
        num_bins: int = 256,
        max_unique_for_exact: Optional[int] = None,
    ) -> "Binner":
        spec = dataset.dataspec
        max_boundaries = num_bins - 1

        # One shared fixed-seed row sample for every dense column: each
        # column used to draw its own sample with the SAME seed, so the
        # indices were identical anyway — hoisting the choice() out of
        # the loop is bit-identical and saves its O(n) cost per column.
        state: Dict[str, Optional[np.ndarray]] = {"sample_idx": None}

        def column_boundaries(name: str) -> np.ndarray:
            vals = dataset.encoded_numerical(name)
            # Boundary fitting is O(n log n) (unique/quantile sorts);
            # past ~200k rows a fixed-seed row sample estimates the
            # 255 quantiles with negligible split-quality impact —
            # the reference's distributed dataset cache discretizes
            # from samples the same way (dataset_cache.proto:42-58),
            # and sklearn's histogram GBT subsamples binning at the
            # same scale. A small pre-sample screens cardinality so
            # the full-column unique sort only runs when the column
            # really is low-cardinality.
            if len(vals) > 200_000:
                if state["sample_idx"] is None:
                    state["sample_idx"] = np.random.default_rng(
                        0xB1A5
                    ).choice(len(vals), 200_000, replace=False)
                sample = vals[state["sample_idx"]]
            else:
                sample = vals
            presample = sample[: 4 * max_boundaries + 4]
            if len(np.unique(presample)) <= max_boundaries:
                # Possibly low cardinality — confirm exactly (the
                # midpoint boundaries need the true unique set).
                uniq = np.unique(vals)
            else:
                uniq = None  # dense column: quantile path
            if uniq is not None and len(uniq) <= max_boundaries:
                return boundaries_from_sketch(
                    uniq, np.ones(len(uniq), np.int64), num_bins,
                    distinct_is_exact=True,
                )
            su, sc = np.unique(sample, return_counts=True)
            return boundaries_from_sketch(
                su, sc, num_bins, distinct_is_exact=False
            )

        return Binner._fit_common(
            spec, features, num_bins, column_boundaries
        )

    @staticmethod
    def fit_from_summaries(
        spec: DataSpecification,
        features: Sequence[str],
        num_bins: int,
        summaries: Dict,
    ) -> "Binner":
        """Binner.fit fed by mergeable pass-1 summaries instead of raw
        columns: `summaries` maps each numerical feature name to a
        dataset.sketch.NumericSummary. This is the boundary source of
        BOTH the single-machine streaming cache build and the
        distributed one (the former is the 1-partial instance of the
        latter), so caches agree byte-for-byte whenever the merged
        summaries do — exactly in exact mode, per the documented rank
        error in sketch mode."""

        def column_boundaries(name: str) -> np.ndarray:
            s = summaries[name]
            v, w = s.weighted_items()
            return boundaries_from_sketch(
                v, w, num_bins, distinct_is_exact=s.distinct_exact()
            )

        return Binner._fit_common(
            spec, features, num_bins, column_boundaries
        )

    @staticmethod
    def _fit_common(
        spec: DataSpecification,
        features: Sequence[str],
        num_bins: int,
        column_boundaries: Callable[[str], np.ndarray],
    ) -> "Binner":
        """Shared fit body: feature partition/ordering, the
        DISCRETIZED_NUMERICAL stored-boundary branch, imputation and
        per-feature bin counts — with the numerical boundary source
        abstracted as `column_boundaries(name)`."""
        if not (2 <= num_bins <= 256):
            raise ValueError(
                f"num_bins must be in [2, 256] (uint8 bin matrix), got {num_bins}"
            )
        if num_bins % 32 != 0:
            raise ValueError(
                f"num_bins must be a multiple of 32 (packed category masks), "
                f"got {num_bins}"
            )
        numericals = [
            f for f in features
            if spec.column_by_name(f).type in _NUMERICAL_LIKE
        ]
        categoricals = [
            f for f in features
            if spec.column_by_name(f).type == ColumnType.CATEGORICAL
        ]
        sets = [
            f for f in features
            if spec.column_by_name(f).type == ColumnType.CATEGORICAL_SET
        ]
        vs = [
            f for f in features
            if spec.column_by_name(f).type
            == ColumnType.NUMERICAL_VECTOR_SEQUENCE
        ]
        unsupported = (
            set(features) - set(numericals) - set(categoricals) - set(sets)
            - set(vs)
        )
        if unsupported:
            raise NotImplementedError(
                f"Unsupported feature columns for binning: {sorted(unsupported)}"
            )
        ordered = numericals + categoricals + sets
        F = len(ordered)
        max_boundaries = num_bins - 1
        boundaries = np.full((F, max_boundaries), np.inf, dtype=np.float32)
        impute = np.zeros((F,), dtype=np.float32)
        fnb = np.ones((F,), dtype=np.int32)

        for i, name in enumerate(numericals):
            col = spec.column_by_name(name)
            if (
                col.type == ColumnType.DISCRETIZED_NUMERICAL
                and col.discretized_boundaries is not None
            ):
                # First-class DISCRETIZED_NUMERICAL: the dataspec's stored
                # boundaries ARE the training bins (data_spec.proto:267),
                # so trained cuts map 1:1 onto DiscretizedHigher conditions
                # at export. Dataspec boundaries beyond the bin budget are
                # subsampled evenly (keeps coverage of the value range).
                b = np.asarray(col.discretized_boundaries, np.float32)
                if len(b) > max_boundaries:
                    idx = np.linspace(0, len(b) - 1, max_boundaries)
                    b = b[np.round(idx).astype(int)]
            else:
                b = column_boundaries(name)
            boundaries[i, : len(b)] = b
            impute[i] = np.float32(col.mean)
            fnb[i] = len(b) + 1

        for j, name in enumerate(categoricals):
            col = spec.column_by_name(name)
            fnb[len(numericals) + j] = min(col.vocab_size, num_bins)

        for j, name in enumerate(sets):
            # Set vocabularies are NOT capped at num_bins (text columns
            # routinely carry 2k items; the dictionary is already pruned
            # by max_vocab_count). The node mask widens to cover them;
            # only candidate cut positions are bounded by num_bins.
            col = spec.column_by_name(name)
            fnb[len(numericals) + len(categoricals) + j] = max(
                col.vocab_size, 1
            )

        return Binner(
            feature_names=ordered,
            num_numerical=len(numericals),
            num_bins=num_bins,
            boundaries=boundaries,
            impute_values=impute,
            feature_num_bins=fnb,
            num_set=len(sets),
            vs_names=vs,
            vs_dims=[spec.column_by_name(f).vector_length for f in vs],
            vs_max_len=max(
                (max(spec.column_by_name(f).max_num_vectors, 1) for f in vs),
                default=0,
            ),
        )

    # ------------------------------------------------------------------ #

    def fingerprint(self) -> str:
        """Content hash of the binning rules — the key under which a
        Dataset caches the bin matrix this Binner produces. Binners are
        treated as immutable once fit (the hash is memoized)."""
        fp = getattr(self, "_fingerprint", None)
        if fp is None:
            h = hashlib.sha1()
            h.update(
                repr((
                    self.feature_names, self.num_numerical, self.num_bins,
                    self.num_set, self.vs_names, self.vs_dims,
                    self.vs_max_len,
                )).encode()
            )
            for a in (self.boundaries, self.impute_values,
                      self.feature_num_bins):
                h.update(np.ascontiguousarray(a).tobytes())
            fp = h.hexdigest()
            self._fingerprint = fp
        return fp

    def transform(
        self,
        dataset: Dataset,
        out: Optional[np.ndarray] = None,
        impl: str = "auto",
        chunk_rows: int = 1 << 18,
    ) -> np.ndarray:
        """Returns the uint8 bin matrix [num_rows, num_scalar] (set
        features are packed separately by transform_sets).

        The numerical block goes through the fused native kernel when
        available (one call for all columns: NaN->impute + branchless
        searchsorted + uint8 store), chunked over rows so no full-f32
        copy of the dataset is ever materialized; the per-column NumPy
        path is the fallback and the parity oracle (bit-identical,
        tests/test_binning_native.py). Missing numericals impute with
        the BINNER's stored per-column value (identical to the dataspec
        column mean for every in-repo flow) on both paths.

        `out`: optional preallocated uint8 [num_rows, num_scalar]
        buffer (e.g. a slice of the dataset cache's memmap — the fused
        ingest path streams chunks straight into the bin matrix).
        Results for internally-allocated calls are cached on `dataset`
        keyed by this Binner's fingerprint, so repeated fits (tuner,
        CV, bench steady-state) skip re-binning entirely; the cached
        matrix is marked read-only."""
        n = dataset.num_rows
        caching = out is None
        if caching:
            cached = dataset.cached_bins(self.fingerprint())
            if cached is not None:
                return cached
            out = np.zeros((n, self.num_scalar), dtype=np.uint8)
        elif out.shape != (n, self.num_scalar) or out.dtype != np.uint8:
            raise ValueError(
                f"out must be uint8 {(n, self.num_scalar)}, got "
                f"{out.dtype} {out.shape}"
            )
        Fn = self.num_numerical
        impl = resolve_bin_impl(impl)
        if Fn and impl == "native":
            self._transform_numerical_native(dataset, out, chunk_rows)
        elif Fn:
            for i, name in enumerate(self.feature_names[:Fn]):
                vals = dataset.encoded_numerical(name, impute=False)
                nan = np.isnan(vals)
                if nan.any():
                    vals = np.where(nan, self.impute_values[i], vals)
                nb = int(self.feature_num_bins[i]) - 1
                out[:, i] = np.searchsorted(
                    self.boundaries[i, :nb], vals, side="right"
                ).astype(np.uint8)
        for i in range(Fn, self.num_scalar):
            name = self.feature_names[i]
            idx = dataset.encoded_categorical(name)
            idx = np.where(idx >= self.num_bins, 0, idx)
            out[:, i] = idx.astype(np.uint8)
        if caching:
            out.setflags(write=False)
            dataset.store_bins(self.fingerprint(), out)
        return out

    def _transform_numerical_native(
        self, dataset: Dataset, out: np.ndarray, chunk_rows: int
    ) -> None:
        """Fused native binning of the numerical block, chunked over
        rows: each chunk's columns are sliced/cast f32 into one [Fn, m]
        buffer (bounded transient, no full-f32 materialization of f64
        ingest columns) and binned by ONE kernel call writing the
        strided [m, num_scalar] output rows in place."""
        from ydf_tpu.ops import binning_native

        Fn = self.num_numerical
        n = dataset.num_rows
        nbounds = np.ascontiguousarray(
            self.feature_num_bins[:Fn] - 1, np.int32
        )
        bounds = np.ascontiguousarray(self.boundaries[:Fn], np.float32)
        impute = np.ascontiguousarray(self.impute_values[:Fn], np.float32)
        raw_cols = [
            dataset.data[name] for name in self.feature_names[:Fn]
        ]
        buf = np.empty((Fn, min(chunk_rows, max(n, 1))), np.float32)
        for a in range(0, n, chunk_rows):
            b = min(a + chunk_rows, n)
            vb = buf[:, : b - a]
            for f, raw in enumerate(raw_cols):
                vb[f, :] = raw[a:b]  # casts any numeric dtype to f32
            binning_native.bin_columns_native(
                vb, bounds, nbounds, impute, out=out[a:b]
            )

    def transform_sets(self, dataset: Dataset) -> Optional[np.ndarray]:
        """Packed multi-hot set features, uint32 [n, num_set, W]; None when
        the binner has no set features."""
        if self.num_set == 0:
            return None
        W = self.set_width_words
        out = np.zeros((dataset.num_rows, self.num_set, W), np.uint32)
        for j, name in enumerate(self.feature_names[self.num_scalar:]):
            if dataset.dataspec.has_column(name) and name in dataset.data:
                out[:, j, :] = dataset.encoded_categorical_set(name, W)
        return out

    def transform_vs(self, dataset: Dataset):
        """Dense padded vector-sequence encoding, or None without VS
        features: (values f32 [n, Fv, Lmax, Dmax], lengths i32 [n, Fv],
        missing bool [n, Fv]). Missing cells encode as empty sequences
        (missing-as-empty, the global-imputation analogue); the mask is
        kept for imported models' na_value routing."""
        if self.num_vs == 0:
            return None
        n = dataset.num_rows
        # Pad to the larger of the training-time max length and THIS batch's
        # max length: max_num_vectors in the reference dataspec is a
        # statistic, not a cap, and the engines score the full sequence —
        # truncating a serving batch to the training max would silently
        # drop vectors that could satisfy a closer_than condition.
        batch_max = 0
        for name in self.vs_names:
            if dataset.dataspec.has_column(name) and name in dataset.data:
                from ydf_tpu.dataset.dataspec import vector_sequence_cell

                for v in dataset.data[name].tolist():
                    c = vector_sequence_cell(v)
                    if c is not None:
                        batch_max = max(batch_max, c.shape[0])
        L, D = max(self.vs_max_len, batch_max), self.vs_dim
        values = np.zeros((n, self.num_vs, L, D), np.float32)
        lengths = np.zeros((n, self.num_vs), np.int32)
        missing = np.zeros((n, self.num_vs), bool)
        for j, name in enumerate(self.vs_names):
            if dataset.dataspec.has_column(name) and name in dataset.data:
                v, l, m = dataset.encoded_vector_sequence(
                    name, max_len=L, dim=D
                )
                values[:, j], lengths[:, j], missing[:, j] = v, l, m
            else:
                missing[:, j] = True
        return values, lengths, missing

    def threshold_value(self, feature_index: int, threshold_bin: int) -> float:
        """Float threshold of a numerical split "bin <= threshold_bin goes
        left" ⇔ "value >= boundaries[threshold_bin] goes right"."""
        return float(self.boundaries[feature_index, threshold_bin])

    def to_json(self) -> Dict:
        return {
            "feature_names": self.feature_names,
            "num_numerical": self.num_numerical,
            "num_bins": self.num_bins,
            "boundaries": self.boundaries.tolist(),
            "impute_values": self.impute_values.tolist(),
            "feature_num_bins": self.feature_num_bins.tolist(),
            "num_set": self.num_set,
            "vs_names": self.vs_names,
            "vs_dims": self.vs_dims,
            "vs_max_len": self.vs_max_len,
        }

    @staticmethod
    def from_json(d: Dict) -> "Binner":
        return Binner(
            feature_names=list(d["feature_names"]),
            num_numerical=int(d["num_numerical"]),
            num_bins=int(d["num_bins"]),
            boundaries=np.array(d["boundaries"], dtype=np.float32),
            impute_values=np.array(d["impute_values"], dtype=np.float32),
            feature_num_bins=np.array(d["feature_num_bins"], dtype=np.int32),
            num_set=int(d.get("num_set", 0)),
            vs_names=list(d.get("vs_names", [])),
            vs_dims=[int(x) for x in d.get("vs_dims", [])],
            vs_max_len=int(d.get("vs_max_len", 0)),
        )


@dataclasses.dataclass
class BinnedDataset:
    """A bin matrix (+ packed set features) + the Binner that produced it."""

    bins: np.ndarray  # uint8 [n, num_scalar]
    binner: Binner
    set_bits: Optional[np.ndarray] = None  # uint32 [n, num_set, W]
    # (values, lengths, missing) from Binner.transform_vs, or None.
    vs: Optional[tuple] = None

    @property
    def num_rows(self) -> int:
        return self.bins.shape[0]

    @staticmethod
    def create(
        dataset: Dataset, features: Sequence[str], num_bins: int = 256
    ) -> "BinnedDataset":
        """Fit + transform, memoized on the Dataset: a repeated fit at
        the same (features, num_bins) — tuner trials, CV folds sharing
        a fold dataset, bench steady-state — reuses the fitted Binner
        and the cached bin/set/vs encodings instead of re-binning."""
        return BinnedDataset.of_binner(
            dataset, BinnedDataset.fit_binner(dataset, features, num_bins)
        )

    @staticmethod
    def fit_binner(
        dataset: Dataset, features: Sequence[str], num_bins: int = 256
    ) -> Binner:
        """The first half of `create`: the Binner, fitted or memoized."""
        binner = dataset.cached_binner(features, num_bins)
        if binner is None:
            binner = Binner.fit(dataset, features, num_bins=num_bins)
            dataset.store_binner(features, num_bins, binner)
        return binner

    @staticmethod
    def of_binner(dataset: Dataset, binner: Binner) -> "BinnedDataset":
        """The second half of `create`: `dataset` under `binner`, its
        encodings made or memoized."""
        fp = binner.fingerprint()
        aux = dataset.cached_bin_aux(fp)
        if aux is None:
            aux = (
                binner.transform_sets(dataset),
                binner.transform_vs(dataset),
            )
            dataset.store_bin_aux(fp, aux)
        return BinnedDataset(
            bins=binner.transform(dataset),
            binner=binner,
            set_bits=aux[0],
            vs=aux[1],
        )
