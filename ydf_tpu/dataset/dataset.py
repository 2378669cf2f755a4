"""Columnar in-memory dataset — the TPU build's VerticalDataset.

Re-design of `ydf/dataset/vertical_dataset.h:51` (typed columns, NA handling)
on numpy: a Dataset is a dict of 1-D numpy arrays + a DataSpecification.
Ingestion accepts dicts of arrays/lists, pandas DataFrames, and typed paths
("csv:/path" — the reference's format-prefixed path convention,
`ydf/dataset/formats.cc:40-93`).

Encoding to model-internal integer/float arrays happens here; binning to
histogram bins happens in `binning.py`.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Union

import numpy as np

from ydf_tpu.dataset.dataspec import (
    Column,
    ColumnType,
    DataSpecification,
    _string_missing_mask,
    column_array as _column_array,
    infer_dataspec,
)

InputData = Union["Dataset", Dict[str, Any], str, "pandas.DataFrame"]  # noqa: F821


def _frame_io():
    """Lazy import of the optional-dependency frame adapters
    (polars / xarray, dataset/frame_io.py)."""
    from ydf_tpu.dataset import frame_io

    return frame_io


def _read_csv(path: str) -> Dict[str, np.ndarray]:
    """Reads a CSV into columns, with light type sniffing.

    IO is native first, like the reference
    (`ydf/dataset/csv_example_reader.cc`): the C++ loader in
    native/csv_loader.cc parses column-wise into numeric arrays + string
    dictionaries through ctypes; pandas is the fallback when the native
    library is unavailable (no toolchain) or the file defeats it.
    """
    from ydf_tpu.dataset import native_csv

    cols = native_csv.read_csv(path)
    if cols is not None:
        return cols
    import pandas as pd

    df = pd.read_csv(path)
    return {c: df[c].to_numpy() for c in df.columns}


_TFRECORD_PREFIXES = (
    # Reference format registry prefixes (formats.cc:56-81).
    "tfrecord",
    "tfrecordv2+gz+tfe",
    "tfrecord-nocompression",
    "tfrecordv2+tfe",
)


def _split_typed_path(path: str):
    """"prefix:path" → (format, path). Format defaults to csv."""
    if ":" in path and not os.path.exists(path):
        prefix, _, rest = path.partition(":")
        if prefix == "csv":
            return "csv", rest
        if prefix in _TFRECORD_PREFIXES:
            return "tfrecord", rest
        if prefix == "avro":
            return "avro", rest
        raise ValueError(f"Unsupported dataset format prefix {prefix!r}")
    return "csv", path


def _resolve_typed_path(path: str) -> List[str]:
    """Resolves "csv:/p/a*.csv" typed+sharded/glob paths to a file list."""
    _, path = _split_typed_path(path)
    files = sorted(glob.glob(path)) if any(c in path for c in "*?[") else [path]
    if not files:
        raise FileNotFoundError(path)
    return files


# Live Datasets for the memory ledger's "bin_matrix" pull source — the
# tuner/CV bin-matrix memo is the one in-memory structure that can
# silently hold hundreds of MB per Dataset (utils/telemetry.py:
# MemoryLedger; sampled only at ledger snapshots).
import threading as _threading  # noqa: E402
import weakref as _weakref  # noqa: E402

_LIVE_DATASETS: "_weakref.WeakSet" = _weakref.WeakSet()


def bin_matrix_bytes_total() -> int:
    return sum(d.bin_cache_bytes() for d in list(_LIVE_DATASETS))


# The one Dataset whose last train() left that job's inputs on the device
# (`Dataset.keep_device_inputs`). A weak reference: the arrays hang on
# their Dataset and die with it; nothing at module level owns device
# memory. The lock makes "let go of the holder's, then hold" one step,
# so that two threads training at once never leave two holders.
_DEVICE_RESIDENT: Optional["_weakref.ref"] = None
_DEVICE_LOCK = _threading.RLock()


def _device_resident() -> Optional["Dataset"]:
    return _DEVICE_RESIDENT() if _DEVICE_RESIDENT is not None else None


def release_device_inputs() -> None:
    """Lets go of the device arrays that the Dataset trained on last kept
    (`Dataset.keep_device_inputs`). Whoever is about to send a table to
    the device calls this FIRST (every learner's upload site does), so
    the chip never holds a table more than the job at hand needs. The
    references are dropped, nothing is deleted: a train() still running
    on those arrays in another thread keeps its own."""
    global _DEVICE_RESIDENT
    with _DEVICE_LOCK:
        held = _device_resident()
        if held is not None:
            held._device_inputs = None
        _DEVICE_RESIDENT = None


def device_inputs_bytes_total() -> int:
    held = _device_resident()
    return held.device_inputs_bytes() if held is not None else 0


def _register_mem_source() -> None:
    from ydf_tpu.utils import telemetry

    telemetry.register_mem_source("bin_matrix", bin_matrix_bytes_total)
    telemetry.register_mem_source("device_inputs", device_inputs_bytes_total)


_register_mem_source()


def _columns_of(data: InputData) -> Dict[str, np.ndarray]:
    """The columns of any input `Dataset.from_data` takes but a Dataset:
    a path (csv, tfrecord, avro), a frame, a dict, a grain source."""
    if isinstance(data, str):
        fmt, raw_path = _split_typed_path(data)
        if fmt == "tfrecord":
            from ydf_tpu.dataset.tfrecord import (
                read_tfrecord_columns,
                resolve_tfrecord_path,
            )

            return read_tfrecord_columns(resolve_tfrecord_path(raw_path))
        if fmt == "avro":
            from ydf_tpu.dataset.avro import read_avro_columns
            from ydf_tpu.dataset.tfrecord import resolve_tfrecord_path

            return read_avro_columns(resolve_tfrecord_path(raw_path))
        parts = [_read_csv(f) for f in _resolve_typed_path(data)]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
    if _frame_io().is_polars_frame(data):
        # polars (reference dataset/io/polars_io.py): checked before
        # the generic DataFrame branch — polars also has
        # .to_dict/.columns but its Series API differs in corners.
        return _frame_io().polars_to_columns(data)
    if hasattr(data, "to_dict") and hasattr(data, "columns"):  # DataFrame
        return {c: data[c].to_numpy() for c in data.columns}
    if isinstance(data, dict):
        return {k: _column_array(v) for k, v in data.items()}
    from ydf_tpu.dataset import grain_io

    if grain_io.is_grain(data):
        # PyGrain DataLoader / MapDataset / IterDataset of
        # per-example dicts (reference dataset/io/pygrain_io.py).
        return grain_io.to_columns(data)
    if _frame_io().is_xarray_dataset(data):
        # xarray (reference dataset/io/xarray_io.py).
        return _frame_io().xarray_to_columns(data)
    raise TypeError(f"Unsupported dataset type: {type(data)}")


class Dataset:
    """Columnar dataset: name → 1-D numpy array + dataspec.

    A Dataset also keeps what fitting on it made, so that the next fit
    on the SAME object does not make it again: on the host the fitted
    binners, the bin matrices and the seeded row split (`bin_cache_bytes`,
    the memory ledger's "bin_matrix" row); on the device the six arrays
    the last gradient-boosted train() on it handed its boosting loop
    (`device_inputs_bytes`, the ledger's "device_inputs" row: a table's
    worth of device memory). At most one Dataset in the process holds
    device arrays, the one trained on last, and it holds one set of
    them. To let go of them drop the Dataset (`del ds`; they go with
    it), or train on another one.

    What making all this cost is kept beside it, in `build_seconds`:
    the ingest that made the Dataset (`dataset.from_data`, and
    `dataset.from_data.infer` inside it), and the spans of the train()
    that made the inputs kept now (`profiling.BUILD_SPANS`, each as
    `dataset.<span>`), which every train() on it reports."""

    def __init__(self, data: Dict[str, np.ndarray], dataspec: DataSpecification):
        self.data = {k: np.asarray(v) for k, v in data.items()}
        self.dataspec = dataspec
        sizes = {len(v) for v in self.data.values()}
        if len(sizes) > 1:
            raise ValueError(f"Ragged columns: {sizes}")
        self.num_rows = sizes.pop() if sizes else 0
        # Binning memo (dataset/binning.py): fitted Binners keyed by
        # (features, num_bins), bin matrices / set+vs encodings keyed by
        # Binner fingerprint. Repeated fit calls on the SAME Dataset
        # object (tuner trials, CV folds, bench steady-state) skip
        # re-binning entirely. Valid only while columns are unmutated —
        # Datasets are treated as immutable throughout the package, and
        # cached bin matrices are marked read-only to enforce it on the
        # consumer side.
        self._binner_cache: Dict = {}
        self._bin_cache: Dict = {}
        # This Dataset under column types a learner forces (a
        # classification label is CATEGORICAL whatever its dtype), keyed
        # by what was forced: the same memo, one level up. Without it
        # every train() on a Dataset ingested with a numerical label
        # infers, bins and encodes all of it again (14.5 s a job at
        # 40M x 28, PERF.md section 6).
        self._retyped: Dict = {}
        # (bin matrix, key, device arrays) of the last train() on this
        # Dataset that kept its inputs on the device, or None.
        self._device_inputs: Optional[tuple] = None
        # Seconds, by profile key: see the class's docstring.
        self.build_seconds: Dict[str, float] = {}
        _LIVE_DATASETS.add(self)  # memory-ledger "bin_matrix" source

    def bin_cache_bytes(self) -> int:
        """Bytes held by this Dataset's cached bin matrices / encodings
        (the tuner/CV memo) — its share of the memory ledger's
        "bin_matrix" row."""
        held = {}  # by identity: an aux entry names the matrix it is of
        for v in self._bin_cache.values():
            for a in v if isinstance(v, tuple) else (v,):
                held[id(a)] = int(getattr(a, "nbytes", 0))
        return sum(held.values())

    # ---- a job's device inputs (see learners/gbt.py) ---------------- #

    def device_inputs(self, bins: np.ndarray, key) -> Optional[tuple]:
        """The device arrays kept by `keep_device_inputs(bins, key, ...)`,
        or None: `bins` is compared by identity (the cached bin matrix
        they were cut from), `key` by value."""
        entry = self._device_inputs
        if entry is not None and entry[0] is bins and entry[1] == key:
            return entry[2]
        return None

    def keep_device_inputs(self, bins: np.ndarray, key, arrays) -> None:
        """Keeps `arrays` (device arrays made from `bins` under `key`)
        with this Dataset and makes it the process's one holder of
        device inputs: what it or any other Dataset held goes first."""
        global _DEVICE_RESIDENT
        with _DEVICE_LOCK:
            release_device_inputs()
            self._device_inputs = (bins, key, tuple(arrays))
            _DEVICE_RESIDENT = _weakref.ref(self)

    def device_inputs_bytes(self) -> int:
        """Device bytes of the kept job inputs (the memory ledger's
        "device_inputs" row)."""
        entry = self._device_inputs
        if not entry:
            return 0
        import jax

        # (a ranking job keeps its query structure, a tree of arrays and
        # plain numbers, beside the six)
        return sum(
            int(getattr(a, "nbytes", 0)) for a in jax.tree.leaves(entry[2])
        )

    # ---- binning memo (see dataset/binning.py) ----------------------- #

    def cached_binner(self, features, num_bins: int):
        return self._binner_cache.get((tuple(features), int(num_bins)))

    def store_binner(self, features, num_bins: int, binner) -> None:
        self._binner_cache[(tuple(features), int(num_bins))] = binner

    def cached_bins(self, fingerprint: str):
        return self._bin_cache.get(("bins", fingerprint))

    def store_bins(self, fingerprint: str, bins: np.ndarray) -> None:
        self._bin_cache[("bins", fingerprint)] = bins

    def cached_bin_aux(self, fingerprint: str):
        return self._bin_cache.get(("aux", fingerprint))

    def store_bin_aux(self, fingerprint: str, aux) -> None:
        self._bin_cache[("aux", fingerprint)] = aux

    # ------------------------------------------------------------------ #
    # Construction
    # ------------------------------------------------------------------ #

    @staticmethod
    def from_examples(
        examples,
        dataspec: Optional[DataSpecification] = None,
        **kwargs,
    ) -> "Dataset":
        """Row-wise ingestion: a sequence of {column: value} dicts
        (reference dataset/example.proto path; see dataset/example.py).
        Missing columns in a row become missing cells."""
        from ydf_tpu.dataset.example import examples_to_columns

        return Dataset.from_data(
            examples_to_columns(examples), dataspec=dataspec, **kwargs
        )

    @staticmethod
    def from_data(
        data: InputData,
        label: Optional[str] = None,
        dataspec: Optional[DataSpecification] = None,
        max_vocab_count: int = 2000,
        min_vocab_frequency: int = 5,
        column_types: Optional[Dict[str, ColumnType]] = None,
        detect_numerical_as_discretized: bool = False,
        discretized_max_bins: int = 255,
    ) -> "Dataset":
        if isinstance(data, Dataset):
            if dataspec is not None:
                # Re-key the same columns under the caller's dataspec (e.g. a
                # model's / learner's dataspec for eval or validation data) so
                # dictionaries and imputation values are the shared ones.
                return Dataset(data.data, dataspec)
            if column_types:
                mismatched = [
                    name
                    for name, t in column_types.items()
                    if data.dataspec.has_column(name)
                    and data.dataspec.column_by_name(name).type != t
                ]
                if mismatched:
                    # Re-infer with the forced types (notably: classification
                    # labels must be CATEGORICAL whatever the raw dtype),
                    # once per Dataset and set of forced types.
                    key = (
                        label, max_vocab_count, min_vocab_frequency,
                        tuple(sorted(
                            (k, t.value) for k, t in column_types.items()
                        )),
                    )
                    if key not in data._retyped:
                        data._retyped[key] = Dataset.from_data(
                            dict(data.data),
                            label=label,
                            max_vocab_count=max_vocab_count,
                            min_vocab_frequency=min_vocab_frequency,
                            column_types=column_types,
                        )
                    return data._retyped[key]
            return data
        if dataspec is not None:
            # Re-keyed under a known dataspec (evaluation, prediction,
            # validation rows): nothing is inferred, nothing recorded.
            return Dataset(_columns_of(data), dataspec)
        # An ingest: timed, and its seconds kept on the Dataset it makes
        # (`build_seconds`), which every train() on it reports.
        from ydf_tpu.utils.profiling import StageTimer

        timer = StageTimer()
        with timer.stage("dataset.from_data"):
            cols = _columns_of(data)
            with timer.stage("dataset.from_data.infer"):
                dataspec = infer_dataspec(
                    cols,
                    label=label,
                    max_vocab_count=max_vocab_count,
                    min_vocab_frequency=min_vocab_frequency,
                    column_types=column_types,
                    detect_numerical_as_discretized=(
                        detect_numerical_as_discretized
                    ),
                    discretized_max_bins=discretized_max_bins,
                )
            ds = Dataset(cols, dataspec)
        ds.build_seconds.update(timer.seconds)
        return ds

    def sample(self, max_rows: int, seed: int = 1234):
        """(subset Dataset, sorted row indices). Row order is preserved so
        per-row outputs (e.g. SHAP values) map back to the input."""
        if self.num_rows <= max_rows:
            return self, np.arange(self.num_rows)
        rng = np.random.default_rng(seed)
        rows = np.sort(rng.choice(self.num_rows, size=max_rows, replace=False))
        return (
            Dataset({k: v[rows] for k, v in self.data.items()}, self.dataspec),
            rows,
        )

    # ------------------------------------------------------------------ #
    # Encoded views (model-internal representations)
    # ------------------------------------------------------------------ #

    def encoded_numerical(self, name: str, impute: bool = True) -> np.ndarray:
        """float32 values; missing → column-mean global imputation, or kept
        as NaN when impute=False (native na_value routing). Returns a VIEW
        of the stored column when no conversion is needed — callers never
        mutate encodings."""
        col = self.dataspec.column_by_name(name)
        raw = self.data[name]
        vals = raw if raw.dtype == np.float32 else raw.astype(np.float32)
        if impute and raw.dtype.kind not in "iub":  # ints/bools carry no NaN
            nan = np.isnan(vals)
            if nan.any():
                vals = np.where(nan, np.float32(col.mean), vals)
        return vals

    def encoded_categorical(
        self, name: str, missing_code: int = 0
    ) -> np.ndarray:
        """int32 dictionary indices; unknown → 0 (OOV), missing →
        `missing_code` (0 = OOV for our learners, -1 for native na_value
        routing of imported models)."""
        col = self.dataspec.column_by_name(name)
        raw = self.data[name]
        assert col.vocabulary is not None
        lookup = {item: i for i, item in enumerate(col.vocabulary)}
        if np.issubdtype(raw.dtype, np.integer) and raw.size:
            # Whole numbers in a narrow range (a class label): a table
            # over the range, one pass and no sort. np.unique's sort of
            # 40M labels was 8.9 s of every classification job.
            lo, hi = int(raw.min()), int(raw.max())
            if hi - lo < 1 << 16:
                table = np.array(
                    [lookup.get(str(v), 0) for v in range(lo, hi + 1)],
                    dtype=np.int32,
                )
                # int64: `raw - lo` wraps in a narrow dtype (int8 -100..100)
                return table[raw.astype(np.int64) - lo]
        if np.issubdtype(raw.dtype, np.number) and raw.dtype != np.bool_:
            # Vectorized via unique+inverse: the stringify/lookup loop
            # runs over the DISTINCT values (2 for a binary label)
            # instead of every row — was ~0.5 s of the 500k-row bench
            # ingest. np.unique collapses NaNs to one trailing entry
            # (equal_nan, numpy >= 1.24 semantics).
            fv = raw.astype(np.float64)
            uniq, inv = np.unique(fv, return_inverse=True)
            codes = np.array(
                [
                    missing_code
                    if np.isnan(v)
                    else lookup.get(
                        str(int(v)) if float(v).is_integer() else str(v), 0
                    )
                    for v in uniq.tolist()
                ],
                dtype=np.int32,
            )
            return codes[inv.reshape(fv.shape)]
        missing = _string_missing_mask(np.asarray(raw, dtype=object))
        keys = [
            "" if m else str(v) for v, m in zip(raw.tolist(), missing)
        ]
        return np.array(
            [missing_code if k == "" else lookup.get(k, 0) for k in keys],
            dtype=np.int32,
        )

    def encoded_hash(self, name: str) -> np.ndarray:
        """uint64 stable hashes (fingerprint64); missing → 0.

        HASH columns carry no dictionary (data_spec.proto:85) — they are
        grouping keys (ranking queries), never split candidates."""
        from ydf_tpu.dataset.dataspec import fingerprint64

        raw = self.data[name]
        if np.issubdtype(raw.dtype, np.number) and raw.dtype != np.bool_:
            fv = raw.astype(np.float64)
            keys = [
                None if np.isnan(v)
                else (str(int(v)) if float(v).is_integer() else str(v))
                for v in fv
            ]
        else:
            missing = _string_missing_mask(np.asarray(raw, dtype=object))
            keys = [None if m else str(v) for v, m in zip(raw.tolist(), missing)]
        return np.array(
            [0 if k is None else fingerprint64(k) for k in keys],
            dtype=np.uint64,
        )

    def encoded_categorical_set(
        self, name: str, width_words: int
    ) -> np.ndarray:
        """Packed multi-hot membership, uint32 [n, width_words].

        Bit v of row e is set iff example e's set contains vocabulary item v
        (OOV items collapse onto bit 0; items beyond 32*width_words drop to
        OOV). Missing rows are all-zero with bit pattern of an empty set —
        our learners treat missing-as-empty (global imputation analogue);
        imported models route missing by na_value using the separate
        missing mask from `categorical_set_missing_mask`."""
        from ydf_tpu.dataset.dataspec import tokenize_set_value

        col = self.dataspec.column_by_name(name)
        assert col.vocabulary is not None
        n = len(self.data[name])
        # Tokenize (Python, unavoidable over object cells), then vectorize
        # the vocabulary lookup + bit packing: sorted-vocab searchsorted and
        # one bitwise_or.at scatter instead of a per-token dict loop.
        rows: List[int] = []
        tokens: List[str] = []
        for e, v in enumerate(self.data[name].tolist()):
            items = tokenize_set_value(v)
            if items:
                rows.extend([e] * len(items))
                tokens.extend(items)
        out = np.zeros((n, width_words), np.uint32)
        if not tokens:
            return out
        vocab = np.asarray(col.vocabulary, dtype=object).astype(str)
        order = np.argsort(vocab)
        svocab = vocab[order]
        tok = np.asarray(tokens, dtype=object).astype(str)
        pos = np.searchsorted(svocab, tok)
        pos = np.minimum(pos, len(svocab) - 1)
        found = svocab[pos] == tok
        idx = np.where(found, order[pos], 0)
        idx = np.where(idx >= width_words * 32, 0, idx)
        rows_arr = np.asarray(rows, np.int64)
        flat = out.reshape(-1)
        np.bitwise_or.at(
            flat,
            rows_arr * width_words + (idx >> 5),
            (np.uint32(1) << (idx & 31).astype(np.uint32)),
        )
        return out

    def categorical_set_missing_mask(self, name: str) -> np.ndarray:
        """bool [n]: True where the set cell is missing (not merely empty)."""
        from ydf_tpu.dataset.dataspec import tokenize_set_value

        return np.array(
            [tokenize_set_value(v) is None for v in self.data[name].tolist()],
            dtype=bool,
        )

    def encoded_vector_sequence(
        self, name: str, max_len: int = 0, dim: int = 0
    ) -> tuple:
        """NUMERICAL_VECTOR_SEQUENCE cells → dense padded arrays.

        Returns (values f32 [n, Lmax, D] zero-padded, lengths i32 [n],
        missing bool [n]). Missing cells encode as empty (length 0) with
        the missing flag set — our learners treat missing-as-empty (the
        global-imputation analogue); imported reference models route
        missing by their stored na_value using the flag. Sequences longer
        than `max_len` (when given, e.g. serving with a model trained on
        shorter data) are truncated."""
        from ydf_tpu.dataset.dataspec import vector_sequence_cell

        col = self.dataspec.column_by_name(name)
        D = dim or col.vector_length
        cells = [vector_sequence_cell(v) for v in self.data[name].tolist()]
        n = len(cells)
        lengths = np.array(
            [0 if c is None else c.shape[0] for c in cells], np.int32
        )
        Lmax = max_len or max(int(lengths.max(initial=0)), 1)
        lengths = np.minimum(lengths, Lmax)
        values = np.zeros((n, Lmax, D), np.float32)
        for e, c in enumerate(cells):
            if c is not None and c.size:
                L = min(c.shape[0], Lmax)
                values[e, :L, : c.shape[1]] = c[:L, :D]
        missing = np.array([c is None for c in cells], bool)
        return values, lengths, missing

    def encoded_label(self, name: str, task) -> np.ndarray:
        """Label encoding: classification → int32 in [0, C) (dictionary order,
        i.e. class 0 is the most frequent — matching the reference where class
        indices are dictionary indices 1..C shifted down by one); regression /
        ranking → float32.

        Classification labels MUST be CATEGORICAL in the dataspec (learners
        force this at dataspec-inference time, like the reference routes the
        label through a guide) so that the class↔index mapping is the shared
        dictionary — never re-derived per dataset, which would silently
        mis-map classes on eval sets with a different class subset."""
        from ydf_tpu.config import Task

        col = self.dataspec.column_by_name(name)
        if task == Task.CLASSIFICATION:
            if col.type != ColumnType.CATEGORICAL:
                raise ValueError(
                    f"Classification label {name!r} must be CATEGORICAL in "
                    f"the dataspec (got {col.type.value}); train through a "
                    "learner so the label type is forced."
                )
            idx = self.encoded_categorical(name)
            if (idx == 0).any():
                raise ValueError(
                    f"Label column {name!r} has values outside the training "
                    "dictionary (missing or unseen classes)"
                )
            return (idx - 1).astype(np.int32)
        return self.data[name].astype(np.float32)

    def label_classes(self, name: str) -> List[str]:
        col = self.dataspec.column_by_name(name)
        if col.type == ColumnType.CATEGORICAL:
            assert col.vocabulary is not None
            return col.vocabulary[1:]
        return [str(v) for v in np.unique(self.data[name]).tolist()]

    def __len__(self) -> int:
        return self.num_rows
