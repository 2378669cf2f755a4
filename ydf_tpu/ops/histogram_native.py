"""XLA-FFI bridge to the native CPU histogram kernels
(native/histogram_ffi.cc).

Compiled on first use (g++ -O3 -shared, against jax.ffi's bundled XLA
FFI headers) into native/build/ — together with the binning kernel into
ONE shared library (ops/native_ffi.py:KERNELS_LIB) so both ride the
persistent worker pool in native/thread_pool.h — and registered as the
CPU custom-call targets "ydf_histogram" / "ydf_histogram_q8"; any
build/load failure degrades to the pure-XLA segment impl with a
one-time RuntimeWarning (the ~5x fallback must never be invisible —
ADVICE r5), so the package still works without a toolchain. The tier-1
suite additionally runs a LOUD smoke check (tests/test_native_smoke.py)
so a stale build or missing registration fails CI instead of silently
benchmarking the fallback.

Why it exists: XLA-CPU lowers segment_sum to a generic scalar scatter
(~125-180M rows/s measured); these kernels stream the same rows at ~5x
that (scripts/exp_cpu_histogram.py has the full experiment matrix),
multithreaded over fixed 32k-row blocks with a fixed-order reduction —
bit-stable across thread counts (YDF_TPU_HIST_THREADS caps the per-call
task wave). Rows on the trash slot (slot == num_slots) are
early-continued before the per-row feature loop.

Two precisions (selected by ops/histogram.py's YDF_TPU_HIST_QUANT
pipeline): `histogram_native` is the exact f32-in/f64-accumulate path;
`histogram_native_q8` takes int8-quantized stats plus the per-column
scale and accumulates packed int16 lanes, dequantizing ONCE in the
fixed-block-order reduction (docs/histogram_quantization.md).

CPU-fallback only — on TPU the histogram is the Mosaic one-hot matmul
(ops/histogram_pallas.py). Counterpart of the reference's hand-tuned
bucket-fill loops (splitter_scanner.h:860,933).
"""

from __future__ import annotations

from ydf_tpu.ops.native_ffi import KERNELS_LIB as _LIB


def available() -> bool:
    return _LIB.ensure_ffi_registered()


def build_is_stale() -> bool:
    """True when native/build's kernel library is missing or older than
    its sources — surfaced by the tier-1 native smoke check."""
    return _LIB.is_stale()


def _require_registered() -> None:
    """Registration is a trace-time side effect; failing HERE (loudly,
    naming the kernel) beats XLA's runtime "No registered implementation
    for FFI custom call" — and beats a silent fallback even more."""
    if not _LIB.ensure_ffi_registered():
        raise RuntimeError(
            "native histogram kernel requested (impl='native') but "
            "native/histogram_ffi.cc could not be built/registered — "
            "see the RuntimeWarning above for the toolchain error"
        )


def histogram_native(bins, slot, stats, num_slots: int, num_bins: int):
    """hist[num_slots, F, num_bins, S]; same contract as
    ops/histogram.py:histogram. Registers the FFI targets on first use.
    Non-f32 stats (e.g. the bf16x2 halves) are cast to f32 — exact for
    bf16 — and accumulated in f64 like the plain path."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    n, F = bins.shape
    S = stats.shape[1]
    return jax.ffi.ffi_call(
        "ydf_histogram",
        jax.ShapeDtypeStruct((num_slots, F, num_bins, S), jnp.float32),
    )(
        bins.astype(jnp.uint8),
        slot.astype(jnp.int32),
        stats.astype(jnp.float32),
    )


def histogram_native_q8(
    bins, slot, stats_q8, scale, num_slots: int, num_bins: int
):
    """Quantized-gradient histogram: stats_q8 is int8 [n, S] (|q| <=
    127), scale f32 [S]; the kernel returns the DEQUANTIZED f32
    histogram (integer totals × scale, rounded once — bit-stable across
    thread counts by integer associativity). Registers the FFI targets
    on first use."""
    import jax
    import jax.numpy as jnp

    _require_registered()

    n, F = bins.shape
    S = stats_q8.shape[1]
    return jax.ffi.ffi_call(
        "ydf_histogram_q8",
        jax.ShapeDtypeStruct((num_slots, F, num_bins, S), jnp.float32),
    )(
        bins.astype(jnp.uint8),
        slot.astype(jnp.int32),
        stats_q8.astype(jnp.int8),
        scale.astype(jnp.float32),
    )


# ---------------------------------------------------------------------- #
# In-loop wall-clock attribution (ydf_tpu/utils/profiling.py): the
# boosting loop is one fused jit scan, so per-op histogram time on the
# CPU path is only honestly measurable INSIDE the custom call. The
# kernel accumulates a nanosecond counter; the bench resets it around
# the steady-state train() it attributes.


def kernel_seconds() -> float:
    """Cumulative wall seconds spent inside the native histogram
    kernels (both precisions) in this process; 0.0 when unavailable."""
    lib = _LIB.load()
    if lib is None:
        return 0.0
    import ctypes

    fn = lib.ydf_hist_ns_total
    fn.restype = ctypes.c_int64
    return fn() / 1e9


def kernel_calls() -> int:
    lib = _LIB.load()
    if lib is None:
        return 0
    import ctypes

    fn = lib.ydf_hist_calls_total
    fn.restype = ctypes.c_int64
    return int(fn())


def arena_bytes_peak() -> int:
    """Peak bytes of the kernels' per-thread partial/accumulator arenas
    (f32 f64 scratch AND the q8 int32 partials + packed-lane scratch the
    watermark spills land in) — the "hist_arena" row of the memory
    ledger (utils/telemetry.py:MemoryLedger). 0 when unavailable."""
    lib = _LIB.load()
    if lib is None:
        return 0
    import ctypes

    fn = lib.ydf_hist_arena_bytes_peak
    fn.restype = ctypes.c_int64
    return int(fn())


def reset_kernel_counters() -> None:
    lib = _LIB.load()
    if lib is not None:
        lib.ydf_hist_counters_reset()
