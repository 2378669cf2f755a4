"""XLA-FFI bridge to the native row-routing & prediction-update kernels
(native/routing_ffi.cc) plus the YDF_TPU_ROUTE_IMPL resolver.

The kernels close the NON-histogram half of the CPU training loop: one
fused pass per layer replaces the grower's ~10-op XLA routing chain
(ops/grower.py "route examples" block), one fused pass per tree replaces
the `preds += leaf_value[leaf_id]` gather+add (optionally together with
the squared-error gradient recompute), and one fused pass per tree
routes the validation batch through the finished tree
(ops/routing.py:route_tree_bins). All of them are bit-identical to the
XLA formulation by construction — per-row pure functions with the same
clamps and select order — so the XLA path stays the default/oracle and
YDF_TPU_ROUTE_IMPL=native is a pure speed switch (validated eagerly
here; see docs/row_routing.md).

Compiled into the shared kernel library (ops/native_ffi.py:KERNELS_LIB,
one .so with the histogram/binning kernels so all of them ride the
persistent thread pool); any build/load failure degrades the AUTO path
to XLA with a one-time RuntimeWarning, while an explicit impl="native"
registers-or-raises (the ~silent-fallback hazard, ADVICE r5).
"""

from __future__ import annotations

import os

from ydf_tpu.ops.native_ffi import KERNELS_LIB as _LIB

# Concrete routing impls the grower/learner dispatch on. "xla" is the
# default and the parity oracle; "native" is the fused kernel family.
_ROUTE_IMPLS = frozenset({"xla", "native"})


def available() -> bool:
    return _LIB.ensure_ffi_registered()


def build_is_stale() -> bool:
    return _LIB.is_stale()


def resolve_route_impl(value=None) -> str:
    """Resolves the routing impl BEFORE the jit boundary (same trace-time
    caveats as ops/histogram.py:resolve_hist_impl — the boosting loop's
    closure cache IS keyed on the resolved impl, so set the env before
    train()). An explicit value wins; YDF_TPU_ROUTE_IMPL selects
    globally; default/"auto" is "native" when the kernel library is
    buildable, else "xla". The default FLIPPED in the many-core round:
    with the AVX2 routing gather, the paired A/B at the bench shape
    measured native-fused 0.34 s FASTER than the XLA chain (it was
    +0.26 s slower before the SIMD path — docs/row_routing.md
    "Measured" records both sides of the decision). Both impls remain
    bit-identical, so the flip is pure speed; YDF_TPU_ROUTE_IMPL=xla
    restores the old pipeline wholesale. The learner still demotes
    native to xla for mesh/TPU backends, DART and K > 1 losses
    (learners/gbt.py — compiler-whim FMA contraction, same doc).
    Validation is EAGER: a typo fails here, at the env boundary."""
    if value is not None and value != "auto":
        if value not in _ROUTE_IMPLS:
            raise ValueError(
                f"route impl {value!r} is not a routing impl; expected "
                f"one of {sorted(_ROUTE_IMPLS)} (or 'auto')"
            )
        return value
    env = os.environ.get("YDF_TPU_ROUTE_IMPL")
    if env is not None:
        low = env.strip().lower()
        if low != "auto":
            if low not in _ROUTE_IMPLS:
                raise ValueError(
                    f"YDF_TPU_ROUTE_IMPL={env!r} is not a routing impl; "
                    f"expected one of {sorted(_ROUTE_IMPLS)} (or 'auto')"
                )
            return low
    return "native" if available() else "xla"


def resolve_route_fuse() -> bool:
    """Whether native routing may FUSE into the native histogram kernel
    (one row walk does both — docs/row_routing.md). Default on;
    YDF_TPU_ROUTE_FUSE=0 keeps the standalone per-layer route_update
    pass instead (bit-identical either way — this is a pure scheduling
    switch for hosts where one formulation measures faster). Validated
    eagerly at the env boundary like the impl resolvers."""
    env = os.environ.get("YDF_TPU_ROUTE_FUSE")
    if env is None:
        return True
    low = env.strip().lower()
    if low in ("1", "true", "on", ""):
        return True
    if low in ("0", "false", "off"):
        return False
    raise ValueError(
        f"YDF_TPU_ROUTE_FUSE={env!r} must be 0/1 (or unset)"
    )


def resolved_route_threads() -> int:
    """The thread cap the native routing kernels will resolve
    (YDF_TPU_ROUTE_THREADS, else hardware concurrency) — surfaced on
    bench records so a many-core host's pool compounding is visible."""
    try:
        n = int(os.environ.get("YDF_TPU_ROUTE_THREADS", "0"))
    except ValueError:
        n = 0
    return n if n > 0 else (os.cpu_count() or 1)


def _require_registered() -> None:
    """Explicit impl='native' must fail HERE, loudly — never silently
    fall back to the XLA chain (the invisible-regression hazard the
    native smoke check exists for)."""
    if not _LIB.ensure_ffi_registered():
        raise RuntimeError(
            "native routing kernel requested (impl='native') but "
            "native/routing_ffi.cc could not be built/registered — see "
            "the RuntimeWarning above for the toolchain error"
        )


def route_update(
    bins_t, slot, leaf_id, do_split, route_f, go_left, left_id, right_id,
    split_rank, hmap, is_set, set_go_left,
):
    """One fused per-layer routing pass. `bins_t` is the FEATURE-major
    u8 [F, n] transpose of the binned matrix — the kernel is
    bandwidth-bound, and feature-major turns each slot's chosen-feature
    gather into a sequential column stream (the transpose is computed
    once per training, hoisted out of the boosting scan by
    learners/gbt.py; ops/grower.py falls back to an in-trace `bins.T`
    when no hoisted copy is supplied). Per-slot arrays are padded to
    [L+1] (index L = trash); `go_left` is u8 [L+1, B]; `set_go_left` is
    u8 [n] when set features exist, else shape [1] (never read).
    Returns (new_slot, new_leaf, hist_slot, counts[L+1, 2]), where
    hist_slot = hmap[new_slot] — pass an identity hmap when sibling
    subtraction is off."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    n = bins_t.shape[1]
    L1 = do_split.shape[0]
    i32 = jnp.int32
    return jax.ffi.ffi_call(
        "ydf_route_update",
        (
            jax.ShapeDtypeStruct((n,), i32),        # new_slot
            jax.ShapeDtypeStruct((n,), i32),        # new_leaf
            jax.ShapeDtypeStruct((n,), i32),        # hist_slot
            jax.ShapeDtypeStruct((L1, 2), i32),     # counts
        ),
    )(
        bins_t.astype(jnp.uint8),
        slot.astype(i32),
        leaf_id.astype(i32),
        do_split.astype(jnp.uint8),
        route_f.astype(i32),
        go_left.astype(jnp.uint8),
        left_id.astype(i32),
        right_id.astype(i32),
        split_rank.astype(i32),
        hmap.astype(i32),
        is_set.astype(jnp.uint8),
        set_go_left.astype(jnp.uint8),
    )


def histogram_routed(
    bins, slot, leaf_id, do_split, route_f, go_left, left_id, right_id,
    split_rank, hmap, is_set, set_go_left, stats, *, num_slots, num_bins,
    quant_scale=None,
):
    """FUSED previous-layer routing + this-layer histogram: one native
    pass over rows applies the previous layer's chosen splits per
    example (exactly ydf_route_update's decision logic) and accumulates
    this layer's [L, F, B, S] histogram from the resulting hist slot —
    the per-layer hist_slot array never exists and the standalone
    routing sweep disappears (docs/row_routing.md).

    Returns (hist, new_slot, new_leaf). `stats` dtype selects the
    kernel: int8 (pre-quantized, requires `quant_scale` [S] — the
    dequantize happens in-kernel like histogram_native_q8) or f32.
    Table arrays follow route_update's padded [L1] contract; `hmap`
    must be the identity when sibling subtraction is off. `num_slots`
    is THIS layer's hist-slot count (the hmap range)."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    n, F = bins.shape
    S = stats.shape[1]
    i32 = jnp.int32
    f32 = jnp.float32
    out_types = (
        jax.ShapeDtypeStruct((num_slots, F, num_bins, S), f32),  # hist
        jax.ShapeDtypeStruct((n,), i32),  # new_slot
        jax.ShapeDtypeStruct((n,), i32),  # new_leaf
    )
    table_args = (
        slot.astype(i32),
        leaf_id.astype(i32),
        do_split.astype(jnp.uint8),
        route_f.astype(i32),
        go_left.astype(jnp.uint8),
        left_id.astype(i32),
        right_id.astype(i32),
        split_rank.astype(i32),
        hmap.astype(i32),
        is_set.astype(jnp.uint8),
        set_go_left.astype(jnp.uint8),
    )
    if stats.dtype == jnp.int8:
        if quant_scale is None:
            raise ValueError("int8 fused histogram requires quant_scale")
        return jax.ffi.ffi_call("ydf_histogram_q8_routed", out_types)(
            bins.astype(jnp.uint8), *table_args,
            stats, quant_scale.astype(f32),
        )
    return jax.ffi.ffi_call("ydf_histogram_routed", out_types)(
        bins.astype(jnp.uint8), *table_args, stats.astype(f32),
    )


# One-shot probe result: does THIS host's XLA CPU contract the
# shrinkage multiply into the prediction add as a hardware FMA?
_UPDATE_FMA = None


def update_uses_fma() -> bool:
    """Whether the XLA oracle's `preds + (raw_leaf·η)[leaf_id]` lowers
    to fma(raw, η, preds) — ONE rounding — instead of the plain
    two-rounding mul+add.

    Measured fact (x86-64 CPU with FMA units): XLA's fusion
    inlines the η-multiply producer through the leaf-value gather into
    the consumer loop, where LLVM contracts mul+add to vfmadd — and an
    hlo OptimizationBarrier around the scaled leaf values does NOT stop
    it (the contraction happens after fusion, at LLVM IR level). The
    stored model values stay round(raw·η), so train preds in the default
    pipeline genuinely differ 1 ulp from add-the-stored-value. The
    native update kernels replicate whichever behavior this probe
    observes (std::fmaf vs plain), keeping the native path bit-identical
    to the XLA oracle. YDF_TPU_UPDATE_FMA=0/1 overrides the probe (test
    hook; "auto"/unset probes).
    """
    global _UPDATE_FMA
    env = os.environ.get("YDF_TPU_UPDATE_FMA", "auto").strip().lower()
    if env not in ("", "auto"):
        if env in ("0", "1"):
            return env == "1"
        raise ValueError(
            f"YDF_TPU_UPDATE_FMA={env!r} must be 0, 1 or auto"
        )
    if _UPDATE_FMA is None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        rng = np.random.default_rng(0x9DF)
        N, n = 127, 4096
        raw = rng.standard_normal(N).astype(np.float32)
        eta = np.float32(0.1)
        leaf = rng.integers(0, N, n).astype(np.int32)
        p0 = rng.standard_normal(n).astype(np.float32)
        plain = (p0 + (raw * eta).astype(np.float32)[leaf]).astype(
            np.float32
        )
        # The probe may fire while an outer trace is active (a kernel
        # call inside the jitted boosting loop) — force eager
        # compile-time evaluation so the result is concrete.
        with jax.ensure_compile_time_eval():
            out = np.asarray(
                jax.jit(lambda r, l, p: p + (r * eta)[l])(
                    jnp.asarray(raw), jnp.asarray(leaf), jnp.asarray(p0)
                )
            )
        _UPDATE_FMA = not np.array_equal(out, plain)
    return _UPDATE_FMA


def leaf_update(leaf_id, leaf_value_raw, scale, preds, use_fma=None):
    """preds + (leaf_value_raw·scale)[leaf_id] in one pass (f32 [n]),
    replicating the XLA oracle's rounding: fma(raw, scale, preds) when
    the host's XLA contracts (see update_uses_fma), the plain
    two-rounding chain otherwise."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    if use_fma is None:
        use_fma = update_uses_fma()
    n = leaf_id.shape[0]
    f32 = jnp.float32
    return jax.ffi.ffi_call(
        "ydf_leaf_update", jax.ShapeDtypeStruct((n,), f32)
    )(
        leaf_id.astype(jnp.int32),
        leaf_value_raw.astype(f32),
        preds.astype(f32),
        jnp.asarray([scale], f32),
        jnp.asarray([1 if use_fma else 0], jnp.int32),
    )


def leaf_update_grad(leaf_id, leaf_value_raw, scale, preds, y, w,
                     use_fma=None):
    """Fused squared-error end-of-tree update: returns (preds_out [n],
    stats [n, 3]) with preds_out = update(preds, raw·scale) (same
    rounding contract as leaf_update) and stats = [(preds_out - y) * w,
    w, w] — exactly the grower's [g*w_eff, h*w_eff, w_eff] rows for
    MeanSquaredError under unit sampling, computed from the ROUNDED f32
    preds_out with the same elementwise ops as XLA (bit-identical)."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    if use_fma is None:
        use_fma = update_uses_fma()
    n = leaf_id.shape[0]
    f32 = jnp.float32
    return jax.ffi.ffi_call(
        "ydf_leaf_update_grad",
        (
            jax.ShapeDtypeStruct((n,), f32),
            jax.ShapeDtypeStruct((n, 3), f32),
        ),
    )(
        leaf_id.astype(jnp.int32),
        leaf_value_raw.astype(f32),
        preds.astype(f32),
        y.astype(f32),
        w.astype(f32),
        jnp.asarray([scale], f32),
        jnp.asarray([1 if use_fma else 0], jnp.int32),
    )


def route_tree(
    bins, feature, threshold_bin, is_cat, is_set, cat_mask, left, right,
    is_leaf, max_depth: int, x_set=None, num_scalar=None,
):
    """Full-tree batched routing (the validation set through one finished
    tree): leaf node id per example in ONE pass, replicating
    ops/routing.py:route_tree_bins bit-for-bit. `x_set` is the packed
    multi-hot u32 [n, Fs, Ws] (None when the tree has no set splits);
    `num_scalar` is the stored set-feature id offset (defaults to
    bins.shape[1], like the XLA path)."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    n, Fb = bins.shape
    i32 = jnp.int32
    if x_set is None or x_set.size == 0:
        x_set = jnp.zeros((1, 1, 1), jnp.uint32)
    offset = Fb if num_scalar is None else num_scalar
    params = jnp.asarray([max_depth, offset], i32)
    return jax.ffi.ffi_call(
        "ydf_route_tree", jax.ShapeDtypeStruct((n,), i32)
    )(
        bins.astype(jnp.uint8),
        feature.astype(i32),
        threshold_bin.astype(i32),
        is_cat.astype(jnp.uint8),
        is_set.astype(jnp.uint8),
        cat_mask.astype(jnp.uint32),
        left.astype(i32),
        right.astype(i32),
        is_leaf.astype(jnp.uint8),
        x_set.astype(jnp.uint32),
        params,
    )


# ---------------------------------------------------------------------- #
# In-loop wall-clock attribution (ydf_tpu/utils/profiling.py → bench.py
# route_s / update_s): same counter pattern as the histogram kernels —
# the boosting loop is one fused jit scan, so the only honest per-op
# timing on the CPU path is measured INSIDE the custom calls.


def _counter(name: str) -> int:
    lib = _LIB.load()
    if lib is None:
        return 0
    import ctypes

    fn = getattr(lib, name, None)
    if fn is None:
        return 0
    fn.restype = ctypes.c_int64
    return int(fn())


def route_kernel_seconds() -> float:
    """Cumulative wall seconds inside the routing kernels (per-layer
    route_update + full-tree route_tree); 0.0 when unavailable."""
    return _counter("ydf_route_ns_total") / 1e9


def update_kernel_seconds() -> float:
    """Cumulative wall seconds inside the prediction-update kernels
    (leaf_update + leaf_update_grad); 0.0 when unavailable."""
    return _counter("ydf_update_ns_total") / 1e9


def fused_kernel_seconds() -> float:
    """Cumulative wall seconds inside the FUSED histogram+routing
    kernels (ydf_histogram*_routed): the contraction and the routing
    share one row loop, so their time is inseparable by construction —
    bench.py reports it as `fused_s` next to hist_s/route_s. These
    counters reset with the histogram counters
    (histogram_native.reset_kernel_counters)."""
    return _counter("ydf_hist_fused_ns_total") / 1e9


def fused_kernel_calls() -> int:
    return _counter("ydf_hist_fused_calls_total")


def route_kernel_calls() -> int:
    return _counter("ydf_route_calls_total")


def update_kernel_calls() -> int:
    return _counter("ydf_update_calls_total")


def reset_kernel_counters() -> None:
    lib = _LIB.load()
    if lib is not None and hasattr(lib, "ydf_route_counters_reset"):
        lib.ydf_route_counters_reset()
