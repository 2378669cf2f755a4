"""Native-build smoke check: the tier-1 run must fail LOUDLY — not
silently benchmark the ~5x-slower XLA fallback — when the native kernel
library cannot be built, is stale against its sources, or its FFI
registration is missing (PR 3 satellite).

These tests assert which impl the suite ACTUALLY exercises. The only
sanctioned skip is a container with no C++ toolchain at all (not this
CI image): that is surfaced as a separate hard failure here rather than
a silent degrade.
"""

import shutil
import subprocess

import numpy as np
import pytest

import jax.numpy as jnp


def test_toolchain_present():
    assert shutil.which("g++") is not None, (
        "no g++ in the tier-1 image — every native-kernel test below "
        "would silently degrade to the XLA fallback"
    )


def test_native_kernels_build_and_register():
    """The shared kernel library (histogram f32/q8 + binning, one .so
    sharing the persistent thread pool) builds, loads, registers its
    FFI targets, and is NOT stale against its sources."""
    from ydf_tpu.ops import histogram_native
    from ydf_tpu.ops.native_ffi import KERNELS_LIB

    assert histogram_native.available(), (
        "native histogram kernel failed to build/register — the suite "
        "would otherwise silently exercise the segment fallback"
    )
    assert not KERNELS_LIB.is_stale(), (
        f"{KERNELS_LIB.lib_path} is older than its sources — rebuild "
        "did not trigger"
    )
    # Registration really happened (not just a loaded .so).
    assert KERNELS_LIB._ffi_registered


def test_auto_resolution_lands_on_native_on_cpu():
    """What the bench and the suite actually run: auto must resolve to
    the native impl on the CPU backend when the build succeeded."""
    from ydf_tpu.ops.histogram import resolve_hist_impl

    assert resolve_hist_impl("auto") == "native"


def test_native_impl_actually_executes():
    """End-to-end proof the custom call RUNS (not a fallback): the
    kernel's own call counter must advance across a histogram() call."""
    from ydf_tpu.ops import histogram_native
    from ydf_tpu.ops.histogram import histogram

    rng = np.random.RandomState(0)
    n, F, L, B = 5000, 3, 4, 16
    before = histogram_native.kernel_calls()
    out = histogram(
        jnp.asarray(rng.randint(0, B, size=(n, F)).astype(np.uint8)),
        jnp.asarray(rng.randint(0, L + 1, size=n).astype(np.int32)),
        jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32)),
        num_slots=L, num_bins=B, impl="native",
    )
    np.asarray(out)  # force execution
    assert histogram_native.kernel_calls() > before, (
        "impl='native' did not reach the native custom call"
    )


def test_explicit_native_request_fails_loudly_when_unavailable(
    monkeypatch,
):
    """When the library is marked failed, an explicit impl='native'
    must raise (never silently fall back)."""
    from ydf_tpu.ops import histogram_native

    monkeypatch.setattr(histogram_native._LIB, "_failed", True)
    monkeypatch.setattr(histogram_native._LIB, "_ffi_registered", False)
    with pytest.raises(RuntimeError, match="could not be built"):
        histogram_native._require_registered()


def test_stale_build_detection(tmp_path):
    """is_stale flags a library older than any source or the shared
    thread_pool.h header (extra_deps)."""
    from ydf_tpu.ops.native_ffi import NativeLibrary

    src = tmp_path / "k.cc"
    dep = tmp_path / "dep.h"
    src.write_text("// src")
    dep.write_text("// dep")
    lib = NativeLibrary(
        src_name="k.cc", lib_name="k.so", extra_deps=("dep.h",)
    )
    # Point it at the tmp sandbox.
    lib.srcs = (str(src),)
    lib.deps = (str(dep),)
    lib.lib_path = str(tmp_path / "k.so")
    assert lib.is_stale()  # missing .so
    (tmp_path / "k.so").write_text("so")
    import os
    import time

    old = time.time() - 100
    os.utime(tmp_path / "k.so", (old, old))
    assert lib.is_stale()  # older than src and header
    new = time.time() + 100
    os.utime(tmp_path / "k.so", (new, new))
    assert not lib.is_stale()


def test_q8_target_registered_alongside_f32():
    """Every training kernel — both histogram precisions, binning, and
    the PR-4 routing/prediction-update family — rides ONE library; a
    partial registration would mean a bench mode silently cannot run."""
    from ydf_tpu.ops.native_ffi import KERNELS_LIB

    assert set(KERNELS_LIB.ffi_targets) == {
        "ydf_histogram", "ydf_histogram_q8",
        "ydf_histogram_routed", "ydf_histogram_q8_routed",
        "ydf_binning",
        "ydf_route_update", "ydf_leaf_update", "ydf_leaf_update_grad",
        "ydf_route_tree",
        "ydf_serve_batch",
    }
    assert KERNELS_LIB.ensure_ffi_registered()


def test_route_kernels_build_and_register():
    """The fused row-routing family (native/routing_ffi.cc) registers
    with the rest of the shared library — registers-or-raises, never a
    silent XLA fallback under an explicit impl."""
    from ydf_tpu.ops import routing_native

    assert routing_native.available(), (
        "native routing kernels failed to build/register — "
        "YDF_TPU_ROUTE_IMPL=native would raise and the bench would lose "
        "the fused path"
    )
    assert not routing_native.build_is_stale()


def test_route_impl_native_actually_executes():
    """End-to-end proof the fused route_update custom call RUNS inside a
    grower build (not a fallback): its own call counter must advance."""
    import jax

    from ydf_tpu.ops import grower, routing_native
    from ydf_tpu.ops.split_rules import HessianGainRule

    rng = np.random.RandomState(0)
    n, F, B = 4000, 4, 32
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)).astype(np.uint8))
    stats = jnp.asarray(
        np.stack(
            [rng.normal(size=n), np.ones(n), np.ones(n)], axis=1
        ).astype(np.float32)
    )
    before = routing_native.route_kernel_calls()
    res = grower.grow_tree(
        bins, stats, jax.random.PRNGKey(0), rule=HessianGainRule(l2=1.0),
        max_depth=4, frontier=16, max_nodes=31, num_bins=B,
        min_examples=2, min_split_gain=0.0, route_impl="native",
    )
    np.asarray(res.leaf_id)  # force execution
    assert routing_native.route_kernel_calls() > before, (
        "route_impl='native' did not reach the ydf_route_update custom "
        "call"
    )


def test_explicit_native_route_fails_loudly_when_unavailable(monkeypatch):
    """Explicit YDF_TPU_ROUTE_IMPL=native with a failed build must raise
    (the same no-silent-fallback contract as the histogram kernels)."""
    from ydf_tpu.ops import routing_native

    monkeypatch.setattr(routing_native._LIB, "_failed", True)
    monkeypatch.setattr(routing_native._LIB, "_ffi_registered", False)
    with pytest.raises(RuntimeError, match="could not be built"):
        routing_native._require_registered()


def test_serving_kernel_registers_and_counter_advances():
    """The batched serving kernel (native/serving_ffi.cc) registers with
    the shared library and REALLY runs: its in-kernel wall/call counters
    must advance across an engine call — the bench's serve attribution
    and the QPS family would otherwise silently time a fallback."""
    import pandas as pd

    import ydf_tpu as ydf
    from ydf_tpu.config import Task
    from ydf_tpu.serving import native_serve

    assert native_serve.available(), (
        "native serving kernel failed to build/register — predict would "
        "silently fall back to the generic engine"
    )
    rng = np.random.RandomState(0)
    df = pd.DataFrame({f"f{i}": rng.normal(size=600) for i in range(4)})
    df["y"] = (df.f0 + df.f1).astype(np.float32)
    m = ydf.GradientBoostedTreesLearner(
        label="y", task=Task.REGRESSION, num_trees=3, max_depth=3,
        validation_ratio=0.0, early_stopping="NONE",
    ).train(df)
    eng = native_serve.build_native_engine(m)
    assert eng is not None
    from ydf_tpu.dataset.dataset import Dataset

    ds = Dataset.from_data(df, dataspec=m.dataspec)
    x_num, x_cat, _ = m._encode_inputs(ds)
    calls0 = native_serve.serve_kernel_calls()
    ns0 = native_serve.serve_kernel_seconds()
    out = eng(x_num, x_cat)
    assert np.isfinite(out).all()
    assert native_serve.serve_kernel_calls() > calls0, (
        "engine call did not reach the native serving kernel"
    )
    assert native_serve.serve_kernel_seconds() >= ns0


def test_explicit_native_serve_fails_loudly_when_unavailable(monkeypatch):
    """YDF_TPU_SERVE_IMPL=native with a failed build must raise (the
    serving side of the no-silent-fallback contract)."""
    from ydf_tpu.serving import native_serve

    monkeypatch.setattr(native_serve._LIB, "_failed", True)
    monkeypatch.setattr(native_serve._LIB, "_ffi_registered", False)
    with pytest.raises(RuntimeError, match="could not be built"):
        native_serve._require_registered()
