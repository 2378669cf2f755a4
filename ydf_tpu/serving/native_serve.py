"""Native batched data-bank serving engine (native/serving_ffi.cc).

The production CPU serving path (ROADMAP item 1): the model is
flattened ONCE at load into the struct-of-arrays data bank of
serving/flatten.py — the same node encoding the portable blob and the
embed ROUTING lowering use — and cached on the model like the
QuickScorer compile cache; each predict call is then one multithreaded
native pass over rows (`ydf_serve_batch`), bit-identical to the XLA
oracle (ops/routing.py:forest_predict_values) for the engine envelope
and across thread counts (tests/test_serving_engine.py).

Two call surfaces over one kernel core:

  * the ctypes handle API — `ydf_serve_bank_create` copies the bank
    into native memory at model load and each predict is a two-pointer
    call with ZERO XLA dispatch (the serving hot path);
  * the XLA FFI custom call "ydf_serve_batch", registered with the
    merged kernel library (ops/native_ffi.py:KERNELS_LIB) so serving
    can run inside a jitted program and the registers-or-raises native
    smoke contract covers it (`serve_batch_ffi`).

Envelope (mirrors the QuickScorer gate minus its 64-leaf limit): no
categorical-set features, no vector-sequence conditions, encode-time
imputation (not native_missing), single-accumulator forests (V == 1;
multiclass GBT predict swaps per-class sub-forests through the fast
engine exactly as it does for QuickScorer). All four data-bank node
kinds are handled: numerical, leaf, categorical-mask, oblique. A
binned variant (`ydf_serve_batch_binned`, NativeBinnedEngine) consumes
the model's own uint8 bin matrix — the 8-bit fast path — for forests
without oblique nodes.

Engine selection rides serving/registry.py (rank 200, CPU-gated);
YDF_TPU_SERVE_IMPL={auto|xla|native} is resolved there.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Optional

import numpy as np

from ydf_tpu.ops.native_ffi import KERNELS_LIB as _LIB

_setup_lock = threading.Lock()
_setup_done = False


def _lib():
    """The merged kernel library with the serving symbols' argtypes
    declared (once per process); None when unavailable."""
    global _setup_done
    lib = _LIB.load()
    if lib is None:
        return None
    if _setup_done:
        return lib
    with _setup_lock:
        if _setup_done:
            return lib
        p = ctypes.c_void_p
        i64 = ctypes.c_int64
        i32 = ctypes.c_int32
        lib.ydf_serve_bank_create.restype = p
        lib.ydf_serve_bank_create.argtypes = [
            i64, i64, p, p, p, p, p, p, p, p, p,  # T..na_left
            i64, p, i32,                          # leaf_values, V
            i64, i32, p,                          # masks
            i64, p, i64, p, p,                    # proj CSR
            i32, i32,                             # Fn, Fc
        ]
        lib.ydf_serve_bank_free.argtypes = [p]
        lib.ydf_serve_batch.argtypes = [p, p, p, i64, p]
        lib.ydf_serve_batch_binned.argtypes = [p, p, i32, i64, p]
        lib.ydf_serve_ns_total.restype = i64
        lib.ydf_serve_calls_total.restype = i64
        _setup_done = True
    return lib


def available() -> bool:
    return _LIB.ensure_ffi_registered()


def _require_registered() -> None:
    """Explicit YDF_TPU_SERVE_IMPL=native must fail HERE, loudly — never
    silently fall back to the generic engine (the invisible-regression
    hazard the native smoke check exists for)."""
    if not _LIB.ensure_ffi_registered():
        raise RuntimeError(
            "native serving kernel requested (YDF_TPU_SERVE_IMPL=native) "
            "but native/serving_ffi.cc could not be built/registered — "
            "see the RuntimeWarning above for the toolchain error"
        )


# ---------------------------------------------------------------------- #
# Bank: flatten once at model load, cache on the model
# ---------------------------------------------------------------------- #

# Running total of live ServeBank table bytes — the "serve_bank" row of
# the memory ledger (pull source, sampled at snapshot only) and the
# bench headline's serve_bank_bytes. Plain int under a lock: bank
# create/close is model-load-rate, never the predict hot path. The
# per-bank identity registry beside it feeds the /statusz serving
# section's model-identity rows (registry.serving_status — which model
# is this process actually serving, the hot-swap verification signal).
_BANK_BYTES_LOCK = threading.Lock()
_BANK_BYTES_TOTAL = 0
_LIVE_BANKS: dict = {}


def _note_bank_bytes(delta: int) -> None:
    global _BANK_BYTES_TOTAL
    with _BANK_BYTES_LOCK:
        _BANK_BYTES_TOTAL = max(_BANK_BYTES_TOTAL + int(delta), 0)


def bank_bytes_total() -> int:
    """Bytes held by live serving data banks in this process (host-side
    tables; the native handle mirrors them once more)."""
    return _BANK_BYTES_TOTAL


def live_banks() -> list:
    """Identity of every live serving bank in this process:
    {fingerprint, num_trees, total_nodes, nbytes} per bank, in creation
    order — the model-identity half of `/statusz`'s serving section."""
    with _BANK_BYTES_LOCK:
        return [dict(v) for v in _LIVE_BANKS.values()]


from ydf_tpu.utils import telemetry as _telemetry  # noqa: E402

_telemetry.register_mem_source("serve_bank", bank_bytes_total)


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


class ServeBank:
    """One model's flat serving tables: the numpy-array form of
    flatten.py's DataBank plus the owned native handle."""

    def __init__(self, model):
        f = {k: np.asarray(v) for k, v in model.forest.to_numpy().items()}
        binner = model.binner
        nfeat = binner.num_scalar
        ow = f.get("oblique_weights")
        if ow is None or ow.size == 0:
            ow = None
        V = int(f["leaf_value"].shape[-1])
        leaf_values = np.asarray(f["leaf_value"], np.float32)

        from ydf_tpu.serving.flatten import (
            flatten_forest_data_bank,
            forest_fingerprint,
        )

        bank = flatten_forest_data_bank(f, leaf_values, nfeat, ow, V)
        W = int(np.shape(f["cat_mask"])[-1])
        # Model identity: stable across processes and wire round-trips
        # (same forest ⇒ same fingerprint), reported on /statusz and
        # verified by a fleet deploy against the router's own value.
        self.fingerprint = forest_fingerprint(f)

        self.num_numerical = int(binner.num_numerical)
        self.num_categorical = nfeat - self.num_numerical
        self.num_scalar = nfeat
        self.leaf_width = int(bank.leaf_width)
        self.mask_words = W
        self.total = int(bank.feature.shape[0])
        self.num_trees = len(bank.tree_offset)
        self.has_oblique = len(bank.proj_start) > 1
        # Binned serving needs real bin-space cuts: serving-only binners
        # (imported models) carry +inf boundary placeholders, and
        # oblique projections cannot run on bins at all.
        self.binnable = (
            not self.has_oblique
            and bool(np.isfinite(np.asarray(binner.boundaries)).any())
        )

        self.tree_offset = np.asarray(bank.tree_offset, np.uint32)
        self.feature = np.ascontiguousarray(bank.feature, np.int32)
        self.aux = np.ascontiguousarray(bank.aux, np.uint32)
        self.cat_feature = np.ascontiguousarray(bank.cat_feature, np.uint32)
        self.thresh = np.ascontiguousarray(bank.thresh, np.float32)
        self.thresh_bin = np.ascontiguousarray(bank.thresh_bin, np.int32)
        self.left = np.ascontiguousarray(bank.left, np.uint32)
        self.right = np.ascontiguousarray(bank.right, np.uint32)
        self.na_left = np.ascontiguousarray(bank.na_left, np.uint8)
        self.leaf_values = np.asarray(bank.leaf_values, np.float32)
        self.masks = (
            np.asarray(bank.masks, np.uint32).reshape(-1, W)
            if bank.masks
            else np.zeros((0, max(W, 1)), np.uint32)
        )
        self.proj_start = np.asarray(bank.proj_start, np.uint32)
        self.proj_feature = np.asarray(bank.proj_feature, np.uint32)
        self.proj_weight = np.asarray(bank.proj_weight, np.float32)

        # Host-side table bytes of this bank; the native handle copies
        # the same tables once more, so the process holds ~2x this while
        # the handle lives. Tracked in the module total the "serve_bank"
        # memory-ledger row reports (and bench.py's serve_bank_bytes).
        self.nbytes = int(
            self.tree_offset.nbytes + self.feature.nbytes
            + self.aux.nbytes + self.cat_feature.nbytes
            + self.thresh.nbytes + self.thresh_bin.nbytes
            + self.left.nbytes + self.right.nbytes + self.na_left.nbytes
            + self.leaf_values.nbytes + self.masks.nbytes
            + self.proj_start.nbytes + self.proj_feature.nbytes
            + self.proj_weight.nbytes
        )
        _note_bank_bytes(self.nbytes)
        self._counted = True
        with _BANK_BYTES_LOCK:
            _LIVE_BANKS[id(self)] = {
                "fingerprint": self.fingerprint,
                "num_trees": self.num_trees,
                "total_nodes": self.total,
                "nbytes": self.nbytes,
            }

        self._h = None
        lib = _lib()
        if lib is not None:
            self._h = lib.ydf_serve_bank_create(
                self.num_trees, self.total,
                _ptr(self.tree_offset), _ptr(self.feature), _ptr(self.aux),
                _ptr(self.cat_feature), _ptr(self.thresh),
                _ptr(self.thresh_bin), _ptr(self.left), _ptr(self.right),
                _ptr(self.na_left),
                len(self.leaf_values), _ptr(self.leaf_values),
                self.leaf_width,
                self.masks.shape[0], W, _ptr(self.masks),
                len(self.proj_start) - 1, _ptr(self.proj_start),
                len(self.proj_feature), _ptr(self.proj_feature),
                _ptr(self.proj_weight),
                self.num_numerical, self.num_categorical,
            )

    def close(self) -> None:
        if self._h:
            lib = _LIB._lib  # already loaded if a handle exists
            if lib is not None:
                lib.ydf_serve_bank_free(self._h)
            self._h = None
        if getattr(self, "_counted", False):
            _note_bank_bytes(-self.nbytes)
            self._counted = False
            with _BANK_BYTES_LOCK:
                _LIVE_BANKS.pop(id(self), None)

    def __del__(self):  # pragma: no cover - interpreter shutdown order
        try:
            self.close()
        except Exception:
            pass


def model_serve_bank(model) -> ServeBank:
    """The model's flat serving bank, built once per forest and cached
    on the model (the flatten-at-load contract — the analogue of the
    QuickScorer compile cache; multiclass predict swaps per-class
    sub-forests, so the cache is keyed per forest identity)."""
    cache = getattr(model, "_serve_bank_cache", None)
    if cache is None:
        cache = model._serve_bank_cache = {}
    key = id(model.forest.feature)
    hit = cache.get(key)
    if hit is not None and hit[0] is model.forest.feature:
        return hit[1]
    if len(cache) > 16:
        cache.clear()
    bank = ServeBank(model)
    cache[key] = (model.forest.feature, bank)
    return bank


# ---------------------------------------------------------------------- #
# Engines
# ---------------------------------------------------------------------- #


class NativeBatchEngine:
    """Callable engine: x_num f32 [n, Fn] (+ x_cat i32 [n, Fc]) → raw
    scores f32 [n] — the QuickScorerEngine calling contract, served by
    the native data-bank walk with zero XLA dispatch."""

    def __init__(self, bank: ServeBank):
        if bank._h is None:
            raise RuntimeError("native serving library unavailable")
        self.bank = bank

    def _run(self, x_num, x_cat) -> np.ndarray:
        b = self.bank
        x_num = np.ascontiguousarray(np.asarray(x_num), np.float32)
        if x_num.ndim != 2 or x_num.shape[1] != b.num_numerical:
            raise ValueError(
                f"x_num must be [n, {b.num_numerical}], got "
                f"{x_num.shape}"
            )
        n = x_num.shape[0]
        if x_cat is None:
            x_cat = np.zeros((n, b.num_categorical), np.int32)
        x_cat = np.ascontiguousarray(np.asarray(x_cat), np.int32)
        if x_cat.shape != (n, b.num_categorical):
            raise ValueError(
                f"x_cat must be [n, {b.num_categorical}], got "
                f"{x_cat.shape}"
            )
        out = np.empty((n, b.leaf_width), np.float32)
        _lib().ydf_serve_batch(
            b._h, _ptr(x_num), _ptr(x_cat), n, _ptr(out)
        )
        return out[:, 0] if b.leaf_width == 1 else out

    def __call__(self, x_num, x_cat=None) -> np.ndarray:
        from ydf_tpu.utils import telemetry

        if telemetry.ENABLED:
            import time

            t0 = time.perf_counter_ns()
            out = self._run(x_num, x_cat)
            telemetry.histogram(
                "ydf_serve_kernel_latency_ns", engine="NativeBatch",
                batch_pow2=telemetry.pow2_bucket(
                    max(int(np.shape(out)[0]), 1)
                ),
            ).observe_ns(time.perf_counter_ns() - t0)
            return out
        return self._run(x_num, x_cat)


class NativeBinnedEngine:
    """8-bit variant: the model's own uint8 bin matrix in (numerical
    bins + categorical codes over the scalar columns, i.e.
    binner.transform(ds)[:, :num_scalar]), raw scores out. The
    cheapest input path when examples are already bucketized — the
    reference's 8bits_numerical_features.h analogue on the data bank."""

    def __init__(self, bank: ServeBank):
        if bank._h is None:
            raise RuntimeError("native serving library unavailable")
        if not bank.binnable:
            raise ValueError(
                "model is outside the binned-serving envelope (oblique "
                "projections or serving-only binner)"
            )
        self.bank = bank

    def __call__(self, bins_u8) -> np.ndarray:
        from ydf_tpu.utils import telemetry

        b = self.bank
        bins = np.ascontiguousarray(np.asarray(bins_u8), np.uint8)
        if bins.ndim != 2 or bins.shape[1] < b.num_scalar:
            raise ValueError(
                f"bins must be [n, >={b.num_scalar}], got {bins.shape}"
            )
        if bins.shape[1] != b.num_scalar:
            bins = np.ascontiguousarray(bins[:, : b.num_scalar])
        n = bins.shape[0]
        out = np.empty((n, b.leaf_width), np.float32)
        if telemetry.ENABLED:
            import time

            t0 = time.perf_counter_ns()
            _lib().ydf_serve_batch_binned(
                b._h, _ptr(bins), b.num_scalar, n, _ptr(out)
            )
            telemetry.histogram(
                "ydf_serve_kernel_latency_ns", engine="NativeBinned",
                batch_pow2=telemetry.pow2_bucket(max(int(n), 1)),
            ).observe_ns(time.perf_counter_ns() - t0)
        else:
            _lib().ydf_serve_batch_binned(
                b._h, _ptr(bins), b.num_scalar, n, _ptr(out)
            )
        return out[:, 0] if b.leaf_width == 1 else out


def in_envelope(model) -> bool:
    """The native batched engine's compatibility envelope (the
    QuickScorer gate minus its leaf limit): single-accumulator forest,
    no set/VS conditions, encode-time imputation."""
    return (
        getattr(model.binner, "num_set", 0) == 0
        and np.size(getattr(model.forest, "vs_anchor", np.zeros(0))) == 0
        and not getattr(model, "native_missing", False)
        and int(model.forest.leaf_value.shape[-1]) == 1
    )


def build_native_engine(model) -> Optional[NativeBatchEngine]:
    """NativeBatchEngine for a trained/imported model, or None when the
    model is outside the envelope or the library is unavailable
    (registry auto mode degrades; YDF_TPU_SERVE_IMPL=native raises
    through _require_registered before reaching here)."""
    if not in_envelope(model):
        return None
    if not available():
        return None
    return NativeBatchEngine(model_serve_bank(model))


def build_native_binned_engine(model) -> Optional[NativeBinnedEngine]:
    """NativeBinnedEngine over the model's own binner, or None outside
    the (tighter) binned envelope: additionally no oblique projections
    and a real training binner (finite boundaries)."""
    if not in_envelope(model) or not available():
        return None
    bank = model_serve_bank(model)
    if not bank.binnable:
        return None
    return NativeBinnedEngine(bank)


# ---------------------------------------------------------------------- #
# XLA FFI surface (jit-embeddable; also the registers-or-raises proof)
# ---------------------------------------------------------------------- #


def serve_batch_ffi(bank: ServeBank, x_num, x_cat):
    """The same value-mode walk as a jitted XLA custom call
    ("ydf_serve_batch"): raw scores f32 [n, V]. Bank arrays ride as
    resident buffers — no per-call copy on CPU."""
    import jax
    import jax.numpy as jnp

    _require_registered()
    x_num = jnp.asarray(x_num, jnp.float32)
    x_cat = jnp.asarray(x_cat, jnp.int32)
    n = x_num.shape[0]
    return jax.ffi.ffi_call(
        "ydf_serve_batch",
        jax.ShapeDtypeStruct((n, bank.leaf_width), jnp.float32),
    )(
        x_num,
        x_cat,
        jnp.asarray(bank.tree_offset),
        jnp.asarray(bank.feature),
        jnp.asarray(bank.aux),
        jnp.asarray(bank.cat_feature),
        jnp.asarray(bank.thresh),
        jnp.asarray(bank.left),
        jnp.asarray(bank.right),
        jnp.asarray(bank.na_left),
        jnp.asarray(bank.leaf_values),
        jnp.asarray(bank.masks),
        jnp.asarray(bank.proj_start),
        jnp.asarray(bank.proj_feature),
        jnp.asarray(bank.proj_weight),
    )


# ---------------------------------------------------------------------- #
# In-kernel wall attribution (profiling.py / bench.py serve counters)
# ---------------------------------------------------------------------- #


def _counter(name: str) -> int:
    lib = _lib()
    if lib is None:
        return 0
    fn = getattr(lib, name, None)
    if fn is None:
        return 0
    fn.restype = ctypes.c_int64
    return int(fn())


def serve_kernel_seconds() -> float:
    """Cumulative wall seconds inside the native serving kernel (both
    input modes, both surfaces); 0.0 when unavailable."""
    return _counter("ydf_serve_ns_total") / 1e9


def serve_kernel_calls() -> int:
    return _counter("ydf_serve_calls_total")


def reset_serve_kernel_counters() -> None:
    lib = _lib()
    if lib is not None and hasattr(lib, "ydf_serve_counters_reset"):
        lib.ydf_serve_counters_reset()
