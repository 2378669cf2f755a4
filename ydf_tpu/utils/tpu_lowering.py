"""Device-less TPU lowering: a check that the training loop and the Pallas
kernels LOWER for TPU on a host with no TPU.

`jax.export` lowers a jitted function for an arbitrary target platform
on any host: the result is serialized StableHLO, with each Pallas kernel
lowered to Mosaic MLIR and embedded as a `tpu_custom_call`. That catches
ops Pallas cannot lower and shapes StableHLO rejects. It does NOT run
the Mosaic compiler or XLA:TPU, which only happens when a TPU client
compiles the module: both serving kernels exported cleanly for twenty
rounds and were refused by Mosaic at their first TPU compile (PR 21,
CHANGES.md). `chip_smoke.py` is the compile-and-run proof.

This module builds the flagship computations at their real
configurations, exports them for platform "tpu", and derives an
analytic roofline projection (FLOPs + bytes from XLA cost analysis vs
chip peak) published in BASELINE.md.

Reference counterparts being proven: the training hot loop
(`ydf/learner/decision_tree/splitter_scanner.h:860,933` — replaced by
the one-hot-matmul histogram contraction) and the production serving
engine (`ydf/serving/decision_forest/quick_scorer_extended.cc:1-985` —
replaced by the leaf-bitmask Pallas kernel).
"""

from __future__ import annotations

import contextlib
import gzip
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "build_train_step",
    "deviceless_tpu_sharding",
    "compile_histogram_matmul",
    "per_feature_body_ops",
    "export_train_step",
    "export_grow_tree",
    "export_binning_pallas",
    "export_histogram_routed_pallas",
    "export_quickscorer",
    "export_serve_bank",
    "export_vector_sequence",
    "grow_tree_cost",
    "tpu_projection",
    "kernel_source_digests",
    "write_artifacts",
    "CHIP_SPECS",
]


# Public chip specs (cloud.google.com/tpu/docs/system-architecture).
# peak_flops is bf16 with f32 accumulation — the precision the histogram
# contraction runs in (one-hot operand is exact in bf16).
CHIP_SPECS = {
    "v5e": {"peak_flops": 197e12, "hbm_gbps": 819e9, "hbm_gib": 16},
    "v4": {"peak_flops": 275e12, "hbm_gbps": 1228e9, "hbm_gib": 32},
    "v5p": {"peak_flops": 459e12, "hbm_gbps": 2765e9, "hbm_gib": 95},
}


# The stats operand each histogram quant mode hands a kernel: dtype and
# columns for S = 3 stats (bf16x2: high and residual halves side by side).
_QUANT_STATS = {
    "f32": (jnp.float32, 3),
    "bf16x2": (jnp.bfloat16, 6),
    "int8": (jnp.int8, 3),
}


def _register_serialization():
    """Registers the grower's output namedtuples with jax.export's pytree
    serializer (idempotent — repeat registration raises, so guard)."""
    from ydf_tpu.ops.grower import GrowResult, TreeArrays

    for cls, name in (
        (TreeArrays, "ydf_tpu.ops.grower.TreeArrays"),
        (GrowResult, "ydf_tpu.ops.grower.GrowResult"),
    ):
        try:
            jax.export.register_namedtuple_serialization(
                cls, serialized_name=name
            )
        except ValueError:
            pass  # already registered


@contextlib.contextmanager
def _hist_impl_env(impl: str):
    """Forces histogram auto-selection for the duration of a trace (see
    ops/histogram.py:resolve_hist_impl)."""
    old = os.environ.get("YDF_TPU_HIST_IMPL")
    os.environ["YDF_TPU_HIST_IMPL"] = impl
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("YDF_TPU_HIST_IMPL", None)
        else:
            os.environ["YDF_TPU_HIST_IMPL"] = old


@contextlib.contextmanager
def _tpu_lookups():
    """Traces the routing's look-ups as a TPU would for the duration of
    a trace on a host that has none: ops/lookup.py `resolve_dense`
    chooses by size there and keeps a CPU on the gather."""
    from ydf_tpu.ops import lookup

    old = lookup.is_tpu_backend
    lookup.is_tpu_backend = lambda: True
    try:
        yield
    finally:
        lookup.is_tpu_backend = old


def build_train_step(
    n: int = 500_000,
    F: int = 28,
    num_trees: int = 20,
    max_depth: int = 6,
    num_bins: int = 256,
    nv: int = 0,
    seed: int = 42,
    loss: str = "binomial",
):
    """The jitted GBT boosting program that train() dispatches (the
    donated chunk function of `learners/gbt.py:_make_boost_fn`: lax.scan
    of grow_tree over one chunk of all `num_trees` iterations) at an
    arbitrary static configuration, plus its example args (carry, first
    iteration, chunk length, then the data) as ShapeDtypeStructs —
    nothing is allocated, so bench-scale shapes trace in seconds.

    Defaults are the bench configuration (BASELINE.json config 1:
    500k x 28, 20 trees, depth 6)."""
    from ydf_tpu.config import TreeConfig
    from ydf_tpu.learners.gbt import _hist_stat_columns, _make_boost_fn
    from ydf_tpu.learners.losses import (
        BinomialLogLikelihood,
        MeanSquaredError,
    )
    from ydf_tpu.ops import device_loop
    from ydf_tpu.ops.split_rules import HessianGainRule

    loss_obj = (
        BinomialLogLikelihood() if loss == "binomial" else MeanSquaredError()
    )
    rule = HessianGainRule(l2=0.0)
    tree_cfg = TreeConfig(max_depth=max_depth, num_bins=num_bins)
    # Bypass the lru_cache: exports must trace fresh under the current
    # YDF_TPU_HIST_IMPL (the cache would hand back a closure whose jit
    # cache still holds the other impl's trace).
    boost = _make_boost_fn.__wrapped__(
        loss_obj, rule, tree_cfg, num_trees, 0.1, 1.0,
        -1, F, F, seed, n, nv,
        # As train() does for a job with no weights column.
        stat_columns=_hist_stat_columns(None, "RANDOM", loss_obj),
    )
    data = (
        jax.ShapeDtypeStruct((n, F), jnp.uint8),     # bins_tr
        jax.ShapeDtypeStruct((n,), jnp.float32),     # y_tr
        jax.ShapeDtypeStruct((n,), jnp.float32),     # w_tr
        jax.ShapeDtypeStruct((nv, F), jnp.uint8),    # bins_va
        jax.ShapeDtypeStruct((nv,), jnp.float32),    # y_va
        jax.ShapeDtypeStruct((nv,), jnp.float32),    # w_va
    )
    carry, _init_pred = jax.eval_shape(boost.init_state, data[1], data[2])
    start = jax.ShapeDtypeStruct((), jnp.int32)
    return device_loop.chunk_fn(boost), (carry, start, num_trees) + data


# What libtpu wants to know before it describes a chip that is not
# attached (.claude/skills/verify/SKILL.md): read when the library loads.
_DEVICELESS_ENV = {
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_WORKER_HOSTNAMES": "localhost",
    "TPU_SKIP_MDS_QUERY": "1",
}


def deviceless_tpu_sharding(topology_name: str = "v5e:2x2"):
    """A SingleDeviceSharding on one chip of a DESCRIBED topology: what
    XLA:TPU needs to compile with no device attached. Raises what
    libtpu raises where it can describe none. Call it from a test or a
    script, never while a module is imported: the process keeps
    libtpu, and its lock, from here on."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    for key, value in _DEVICELESS_ENV.items():
        os.environ.setdefault(key, value)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=topology_name
    )
    return SingleDeviceSharding(topo.devices[0])


def compile_histogram_matmul(
    sharding, L: int, F: int, quant: str = "f32", n: int = 3 << 18,
    chunk: int = 1 << 18, num_bins: int = 256, stat_columns=None,
) -> str:
    """XLA:TPU's compiled text of `ops/histogram.py:_histogram_matmul`
    itself for the chip `sharding` describes, at `L` slots, `F`
    features and the stats operand the `quant` mode hands it, described
    by `stat_columns` (one `ops/histogram.py` StatColumn a column, as
    `learners/gbt.py:_hist_stat_columns` gives them; None: any f32).
    The text carries the compiler's placement of every operation, its
    layouts and its `estimated_cycles`: no chip reading."""
    from ydf_tpu.ops.histogram import _histogram_matmul

    dtype, S = _QUANT_STATS[quant]
    args = (
        jax.ShapeDtypeStruct((n, F), jnp.uint8, sharding=sharding),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((n, S), dtype, sharding=sharding),
    )
    fn = jax.jit(
        lambda b, sl, st: _histogram_matmul(
            b, sl, st, L, num_bins, chunk, stat_columns
        )
    )
    return fn.lower(*args).compile().as_text()


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+?)(?:\{\S*)? ([a-z][a-z-]*)\("
)


def per_feature_body_ops(hlo_text: str, chunk: int) -> list[dict]:
    """What a compiled `_histogram_matmul` executes once a FEATURE with a
    result as long as the chunk: for every innermost `while` body of
    `hlo_text` (the loop over features; the loop over chunks holds it),
    each instruction whose result has a `chunk`-sized dimension, and
    each fusion around the MXU's convolution (`contracts`), as {"body",
    "name", "opcode", "shape", "contracts"}. Tuple plumbing, parameters
    and bitcasts run nothing and are left out."""
    computations, name = {}, None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            name = m.group(1)
            computations[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            computations[name].append(line)
    bodies = {
        m.group(1)
        for lines in computations.values() for line in lines
        for m in [re.search(r" while\(.*body=%([^\s,)]+)", line)] if m
    }
    innermost = [
        b for b in sorted(bodies)
        if not any(" while(" in line for line in computations[b])
    ]
    ops = []
    for body in innermost:
        for line in computations[body]:
            m = _HLO_INSTRUCTION.match(line)
            if not m or m.group(3) in (
                "parameter", "get-tuple-element", "tuple", "bitcast"
            ):
                continue
            shape = m.group(2)
            dims = [
                int(d) for dim in re.findall(r"\[([\d,]*)\]", shape)
                for d in dim.split(",") if d
            ]
            called = re.search(r"calls=%([^\s,)]+)", line)
            contracts = bool(called) and any(
                " convolution(" in l
                for l in computations.get(called.group(1), [])
            )
            if chunk in dims or contracts:
                ops.append({
                    "body": body, "name": m.group(1),
                    "opcode": m.group(3), "shape": shape,
                    "contracts": contracts,
                })
    return ops


def export_train_step(hist_impl: str = "matmul", platforms=("tpu",), **kw):
    """jax.export of the boosting program for `platforms`."""
    run, args = build_train_step(**kw)
    with _hist_impl_env(hist_impl), _tpu_lookups():
        return jax.export.export(run, platforms=tuple(platforms))(*args)


def export_grow_tree(
    n: int = 500_000,
    F: int = 28,
    max_depth: int = 6,
    num_bins: int = 256,
    hist_impl: str = "matmul",
    platforms=("tpu",),
):
    """jax.export of one tree build (the per-iteration hot path) — the
    unit the throughput projection is computed over."""
    from ydf_tpu.config import TreeConfig
    from ydf_tpu.ops.grower import grow_tree
    from ydf_tpu.ops.split_rules import HessianGainRule

    cfg = TreeConfig(max_depth=max_depth, num_bins=num_bins)
    rule = HessianGainRule(l2=0.0)

    def one_tree(bins, stats, key):
        # route_impl pinned to the XLA chain: the native fused route is a
        # CPU custom call (the ambient default since the many-core round),
        # which cannot serialize into a TPU export. Its look-ups go by
        # size, as on a TPU (dense_lookups=None; "auto" asks the host).
        return grow_tree(
            bins, stats, key,
            rule=rule, max_depth=max_depth, frontier=cfg.frontier,
            max_nodes=cfg.max_nodes, num_bins=num_bins, num_numerical=F,
            hist_impl=hist_impl, route_impl="xla", dense_lookups=None,
        )

    args = (
        jax.ShapeDtypeStruct((n, F), jnp.uint8),
        jax.ShapeDtypeStruct((n, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    return jax.export.export(jax.jit(one_tree), platforms=tuple(platforms))(
        *args
    )


def export_histogram_pallas(
    n: int = 262_144, F: int = 28, L: int = 32, B: int = 256,
    quant: str = "f32", platforms=("tpu",),
):
    """jax.export of the Mosaic histogram training kernel
    (ops/histogram_pallas.py) at a bench-layer shape. `quant` selects
    the stats operand the quantized-gradient pipeline would hand the
    kernel: "f32" exact, "bf16x2" (bf16 hi/lo halves, S doubled), or
    "int8" (quantized stats, int8 MXU tiles with int32 accumulation) —
    proving all three operand precisions Mosaic-lower for TPU."""
    from ydf_tpu.ops.histogram_pallas import histogram_pallas

    dtype, S = _QUANT_STATS[quant]
    args = (
        jax.ShapeDtypeStruct((n, F), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n, S), dtype),
    )
    return jax.export.export(
        jax.jit(
            lambda b, s, st: histogram_pallas(
                b, s, st, num_slots=L, num_bins=B
            )
        ),
        platforms=tuple(platforms),
    )(*args)


def export_histogram_routed_pallas(
    n: int = 262_144, F: int = 28, L: int = 32, Lh: int = 16,
    B: int = 256, quant: str = "f32", platforms=("tpu",),
):
    """jax.export of the FUSED route+histogram Mosaic kernel
    (ops/histogram_pallas.py:histogram_routed_pallas) at a bench-layer
    shape: the previous layer's decision tables applied in-register and
    this layer's histogram accumulated in the same grid step — the
    TPU-native mirror of the native SlotFn fusion seam that makes the
    device-resident boosting loop's per-layer routing free of HBM
    round trips (docs/device_loop.md). `quant` selects the stats
    operand like export_histogram_pallas; the routing contractions are
    f32 one-hot dots in every mode."""
    from ydf_tpu.ops.histogram_pallas import histogram_routed_pallas

    dtype, S = _QUANT_STATS[quant]
    L1 = L + 1
    args = (
        jax.ShapeDtypeStruct((n, F), jnp.uint8),    # bins
        jax.ShapeDtypeStruct((n,), jnp.int32),      # slot
        jax.ShapeDtypeStruct((n,), jnp.int32),      # leaf_id
        jax.ShapeDtypeStruct((L1,), jnp.uint8),     # do_split
        jax.ShapeDtypeStruct((L1,), jnp.int32),     # route_f
        jax.ShapeDtypeStruct((L1, B), jnp.uint8),   # go_left
        jax.ShapeDtypeStruct((L1,), jnp.int32),     # left_id
        jax.ShapeDtypeStruct((L1,), jnp.int32),     # right_id
        jax.ShapeDtypeStruct((L1,), jnp.int32),     # split_rank
        jax.ShapeDtypeStruct((L1,), jnp.int32),     # hmap
        jax.ShapeDtypeStruct((L1,), jnp.uint8),     # is_set
        jax.ShapeDtypeStruct((n,), jnp.uint8),      # set_go_left
        jax.ShapeDtypeStruct((n, S), dtype),        # stats
        jax.ShapeDtypeStruct((S if quant != "bf16x2" else S // 2,),
                             jnp.float32),          # quant_scale
    )

    def fused(bins, slot, leaf, ds, rf, gl, li, ri, sr, hm, iss, sgl,
              st, qs):
        return histogram_routed_pallas(
            bins, slot, leaf, ds, rf, gl, li, ri, sr, hm, iss, sgl, st,
            num_slots=Lh, num_bins=B,
            quant_scale=qs if quant == "int8" else None,
        )

    return jax.export.export(jax.jit(fused), platforms=tuple(platforms))(
        *args
    )


def export_binning_pallas(
    n: int = 262_144, F: int = 28, B: int = 256, platforms=("tpu",),
):
    """jax.export of the Mosaic quantile-binning kernel
    (ops/binning_pallas.py) — the ingestion side of the fused pipeline,
    proving feature binning compiles for TPU next to the training loop
    it feeds."""
    from ydf_tpu.ops.binning_pallas import binning_pallas

    args = (
        jax.ShapeDtypeStruct((F, n), jnp.float32),    # values
        jax.ShapeDtypeStruct((F, B - 1), jnp.float32),  # boundaries
        jax.ShapeDtypeStruct((F,), jnp.int32),        # nbounds
        jax.ShapeDtypeStruct((F,), jnp.float32),      # impute
    )
    return jax.export.export(
        jax.jit(
            lambda v, b, nb, imp: binning_pallas(v, b, nb, imp)
        ),
        platforms=tuple(platforms),
    )(*args)


def _tiny_quickscorer_engine():
    """A real QuickScorer engine compiled from a small trained model
    (interpret=False so lowering emits the Mosaic kernel)."""
    import pandas as pd

    import ydf_tpu as ydf
    from ydf_tpu.config import Task
    from ydf_tpu.serving.quickscorer import build_quickscorer

    rng = np.random.default_rng(0)
    df = pd.DataFrame({f"f{i}": rng.normal(size=600) for i in range(6)})
    df["y"] = (df["f0"] + df["f1"] * df["f2"] > 0).astype(np.float32)
    m = ydf.GradientBoostedTreesLearner(
        label="y", task=Task.REGRESSION, num_trees=8, max_depth=5,
        validation_ratio=0.0, early_stopping="NONE",
    ).train(df)
    eng = build_quickscorer(m, interpret=False)
    assert eng is not None, "tiny model fell outside the QuickScorer envelope"
    return eng


def export_quickscorer(n_examples: int = 4096, platforms=("tpu",)):
    """jax.export of the leaf-bitmask inference kernel
    (serving/quickscorer.py:_qs_kernel) for `platforms`. The engine is
    compiled from a real trained model so the export covers the full
    engine path, not a synthetic kernel shell."""
    eng = _tiny_quickscorer_engine()
    x = jax.ShapeDtypeStruct((n_examples, eng.num_numerical), jnp.float32)
    return jax.export.export(
        jax.jit(lambda xs: eng(xs)), platforms=tuple(platforms)
    )(x)


def export_serve_bank(n_examples: int = 4096, platforms=("tpu",)):
    """jax.export of the batched data-bank serving kernel
    (serving/pallas_scorer.py:_bank_kernel) — the TPU serving engine
    for forests beyond the QuickScorer 64-leaf envelope. Compiled from
    a real trained model (with categorical conditions, so the
    mask-half-word unroll is in the lowering), like export_quickscorer."""
    import pandas as pd

    import ydf_tpu as ydf
    from ydf_tpu.config import Task
    from ydf_tpu.serving.pallas_scorer import build_pallas_scorer

    rng = np.random.default_rng(0)
    df = pd.DataFrame({f"f{i}": rng.normal(size=600) for i in range(6)})
    df["c"] = np.asarray(rng.choice(list("abcd"), size=600))
    df["y"] = (
        df["f0"] + df["f1"] * df["f2"] + (df["c"] == "a")
    ).astype(np.float32)
    m = ydf.GradientBoostedTreesLearner(
        label="y", task=Task.REGRESSION, num_trees=8, max_depth=5,
        validation_ratio=0.0, early_stopping="NONE",
    ).train(df)
    eng = build_pallas_scorer(m, interpret=False)
    assert eng is not None, "tiny model fell outside the PallasBank envelope"
    x = jax.ShapeDtypeStruct(
        (n_examples, m.binner.num_numerical), jnp.float32
    )
    xc = jax.ShapeDtypeStruct(
        (n_examples, m.binner.num_categorical), jnp.int32
    )
    return jax.export.export(
        jax.jit(lambda a, b: eng._score(a, b)), platforms=tuple(platforms)
    )(x, xc)


def export_vector_sequence(
    n: int = 1024, m: int = 16, d: int = 8, A: int = 32, platforms=("tpu",)
):
    """jax.export of the vector-sequence anchor-distance Pallas kernel
    (ops/vector_sequence.py:_vs_kernel, the GPU-projector counterpart
    ref: vector_sequence.cc) for `platforms`."""
    from ydf_tpu.ops.vector_sequence import _scores_pallas

    args = (
        jax.ShapeDtypeStruct((n, m, d), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((A, d), jnp.float32),
        jax.ShapeDtypeStruct((A,), jnp.bool_),
    )
    return jax.export.export(
        jax.jit(
            lambda v, l, a, c: _scores_pallas(v, l, a, c, interpret=False)
        ),
        platforms=tuple(platforms),
    )(*args)


# --------------------------------------------------------------------------
# Cost analysis + roofline projection
# --------------------------------------------------------------------------


def grow_tree_cost(
    n: int = 500_000,
    F: int = 28,
    max_depth: int = 6,
    num_bins: int = 256,
    hist_impl: str = "matmul",
):
    """XLA cost analysis (FLOPs + HBM bytes) of ONE tree build, from the
    CPU lowering of the same HLO graph the TPU export contains. Costed
    per tree rather than per run because HloCostAnalysis counts a while
    (lax.scan) body once regardless of trip count."""
    from ydf_tpu.config import TreeConfig
    from ydf_tpu.ops.grower import grow_tree
    from ydf_tpu.ops.split_rules import HessianGainRule

    cfg = TreeConfig(max_depth=max_depth, num_bins=num_bins)
    rule = HessianGainRule(l2=0.0)

    def one_tree(bins, stats, key):
        # route_impl="xla" for the same reason as export_grow_tree: the
        # cost model must count the HLO the TPU runs, not host callbacks.
        return grow_tree(
            bins, stats, key,
            rule=rule, max_depth=max_depth, frontier=cfg.frontier,
            max_nodes=cfg.max_nodes, num_bins=num_bins, num_numerical=F,
            hist_impl=hist_impl, route_impl="xla", dense_lookups=None,
        )

    lowered = jax.jit(one_tree).lower(
        jax.ShapeDtypeStruct((n, F), jnp.uint8),
        jax.ShapeDtypeStruct((n, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    ca = lowered.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "n": n, "F": F, "max_depth": max_depth, "num_bins": num_bins,
        "hist_impl": hist_impl,
    }


def _analytic_hist_flops(n, F, max_depth, num_bins, S=3, L=1024,
                         subtract=True):
    """Closed-form FLOP count of the histogram contraction per tree:
    layer d contracts onehot[n,B]^T @ A[n, Ld*S] per feature
    (2*n*B*Ld*S flops), Ld = min(2^d, frontier). With the grower's
    sibling-subtraction mode (the default) layers past the root only
    histogram the SMALLER child of each previous split — the live slot
    count is Lh = min(2^(d-1), frontier // 2) and the sibling comes from
    a parent − child subtraction (O(Lh·F·B·S), negligible next to the
    n-row contraction) — halving the MXU work of every layer but the
    root's."""
    frontier = min(2 ** max(max_depth - 1, 0), L)
    total = 0.0
    for d in range(max_depth):
        if subtract and d > 0:
            Ld = max(1, min(2 ** (d - 1), frontier // 2))
        else:
            Ld = min(2**d, frontier)
        total += 2.0 * n * num_bins * Ld * S * F
    return total


def pallas_lane_packing_summary(
    n: int = 500_000, F: int = 28, max_depth: int = 6, num_bins: int = 256,
    S: int = 3, frontier_cap: int = 1024,
):
    """Per-layer MXU ISSUE accounting for the Pallas kernel's sub-128-lane
    slot packing (ops/histogram_pallas.py, ROADMAP item closed in PR 4).

    The MXU issues full 128-lane passes regardless of how few slot lanes
    are live, so the relevant cost is issued lane-FLOPs, not MACs:
    2·n·B·128 per (feature, dot). Unpacked, every layer issues S dots
    per feature; packed, a layer with L <= 64 live slots issues
    ceil(S / (128 // L)) — at the bench shape the sibling-subtraction
    layers (L = 1..16 live after halving) collapse to one dot per
    feature. The MAC-based roofline (tpu_projection) is unchanged by
    packing; this summary shows the issue-level win it unlocks."""
    frontier = min(2 ** max(max_depth - 1, 0), frontier_cap)
    per_layer = []
    issued_unpacked = issued_packed = 0.0
    for d in range(max_depth):
        if d > 0:
            L = max(1, min(2 ** (d - 1), frontier // 2))  # subtraction
        else:
            L = 1
        G = min(S, 128 // L) if L <= 64 else 1
        dots_unpacked = S
        dots_packed = -(-S // G)
        lane_flops = 2.0 * n * num_bins * 128 * F
        issued_unpacked += dots_unpacked * lane_flops
        issued_packed += dots_packed * lane_flops
        per_layer.append({
            "depth": d, "live_slots": L, "pack_G": G,
            "dots_per_feature_unpacked": dots_unpacked,
            "dots_per_feature_packed": dots_packed,
        })
    return {
        "config": {"n": n, "F": F, "max_depth": max_depth,
                   "num_bins": num_bins, "S": S},
        "per_layer": per_layer,
        "issued_lane_flops_per_tree_unpacked": issued_unpacked,
        "issued_lane_flops_per_tree_packed": issued_packed,
        "issue_reduction": round(issued_unpacked / issued_packed, 3),
    }


def _analytic_route_flops(n, max_depth, num_bins, L=1024, table_rows=16):
    """Closed-form FLOP count of the fused route+histogram kernel's
    ROUTING contractions per tree (ops/histogram_pallas.py
    _hist_routed_kernel). Every per-example table gather is a one-hot
    MXU dot against the previous frontier's padded slot axis
    (L1p = L+1 rounded up to 128 lanes):

      tabs gather   [Kp, L1p] @ [L1p, n]  — Kp = 16 packed table rows
      go-left       [B,  L1p] @ [L1p, n]  — each slot's per-bin row

    so layer d costs 2·n·(Kp + B)·L1p FLOPs, issued once per layer past
    the root (the root has no previous splits to route). These dots run
    f32 (exactness of the id arithmetic), i.e. 3 MXU passes per MAC,
    REGARDLESS of the histogram's quant mode. Earlier projections
    treated routing as free — defensible for the XLA gather chain
    (VPU-bound, hidden under the histogram), wrong for the fused kernel
    whose routing occupies the same MXU the histogram needs."""
    frontier = min(2 ** max(max_depth - 1, 0), L)
    L1p = -(-(frontier + 1) // 128) * 128
    per_layer = 2.0 * n * (table_rows + num_bins) * L1p
    return per_layer * max(max_depth - 1, 0)


# MXU issue cost per histogram MAC, in native-bf16-pass units, by stats
# operand precision (docs/histogram_quantization.md has the derivation):
#   f32     Mosaic decomposes an f32×f32 dot into bf16 passes (hi·hi +
#           hi·lo + lo·hi): 3 passes per MAC. (Earlier rooflines
#           projected f32 operands at the full bf16 peak — a ~3x
#           overcount the quantization work made explicit.)
#   bf16x2  the one-hot operand is EXACT in bf16, so only stats split:
#           2S single-pass bf16 columns = 2 passes per original MAC —
#           the "halved MXU-operand width" (32 -> 2x16 bit) win.
#   int8    int8 MXU tiles issue at 2x the bf16 rate on v5+: 0.5.
MXU_PASSES_PER_MAC = {"f32": 3.0, "bf16x2": 2.0, "int8": 0.5}


def tpu_projection(
    n: int = 500_000,
    F: int = 28,
    max_depth: int = 6,
    num_bins: int = 256,
    chips=("v5e", "v4", "v5p"),
    mfu: float = 0.4,
    cost: dict | None = None,
    hist_quant: str = "f32",
):
    """Analytic roofline projection of training throughput per chip.

    time/tree = max(compute at `mfu` of peak, HBM traffic at full
    bandwidth); rows·trees/s = n / time. `mfu` defaults to 0.4 — the
    histogram contraction is a [n,B]^T@[n,L*S] matmul with a 2^18-row
    contraction dimension, squarely in the MXU's efficient regime, but
    the small Ld*S output width at shallow depths costs tiling
    efficiency; 40% is the conservative end of large-contraction matmul
    MFU on TPU. Two FLOP numbers are reported: XLA-counted (from
    HloCostAnalysis of the real lowering — includes every elementwise op)
    and closed-form matmul-only (the floor). `hist_quant` scales the
    compute term by MXU_PASSES_PER_MAC — the gradient-quantization
    modes change the TILE precision of the dot, not its MAC count."""
    if cost is None:
        cost = grow_tree_cost(n, F, max_depth, num_bins, "matmul")
    analytic = _analytic_hist_flops(n, F, max_depth, num_bins)
    # HloCostAnalysis counts fori_loop/scan bodies ONCE regardless of trip
    # count, so the XLA number misses the x(F * chunks) factor on the
    # histogram dots; the closed-form matmul count is exact for the dots
    # and dominates everything else. Project on whichever is larger.
    flops = max(cost["flops"], analytic)
    passes = MXU_PASSES_PER_MAC[hist_quant]
    # Fused route+histogram kernel: the routing one-hot dots share the
    # MXU with the histogram and are NOT free (they used to be counted
    # as zero). f32 passes in every quant mode — id arithmetic must
    # stay exact.
    route_flops = _analytic_route_flops(n, max_depth, num_bins)
    route_passes = MXU_PASSES_PER_MAC["f32"]
    # HBM traffic floor per tree: re-read bins + stats once per layer
    # (the Pallas/fused formulation; XLA's unfused "bytes accessed"
    # wildly overcounts by materializing one-hots). The stats re-read
    # shrinks with the operand width (f32 12 B/row, bf16x2 hi+lo 12 B,
    # int8 3 B) — third-order next to the bins term.
    stats_bytes = {"f32": 12, "bf16x2": 12, "int8": 3}[hist_quant]
    bytes_floor = max_depth * (n * F * 1 + n * stats_bytes + n * 4 * 2)
    rows = []
    for chip in chips:
        spec = CHIP_SPECS[chip]
        t_compute = (flops * passes + route_flops * route_passes) / (
            spec["peak_flops"] * mfu
        )
        t_mem = bytes_floor / spec["hbm_gbps"]
        t_tree = max(t_compute, t_mem)
        rows.append({
            "chip": chip,
            "hist_quant": hist_quant,
            "mxu_passes_per_mac": passes,
            "flops_per_tree_projected": flops,
            "flops_per_tree_xla": cost["flops"],
            "flops_per_tree_matmul_floor": analytic,
            "route_flops_per_tree": route_flops,
            "route_mxu_passes_per_mac": route_passes,
            "hbm_bytes_floor_per_tree": bytes_floor,
            "assumed_mfu": mfu,
            "projected_s_per_tree": t_tree,
            "projected_rows_trees_per_sec": n / t_tree,
            "bound": "compute" if t_compute >= t_mem else "memory",
        })
    return {"config": {"n": n, "F": F, "max_depth": max_depth,
                       "num_bins": num_bins, "hist_quant": hist_quant},
            "basis": (
                "compute = hist MACs x quant-mode MXU passes + fused "
                "route+histogram routing dots (f32 passes, "
                "_analytic_route_flops) — routing is no longer "
                "projected as free"
            ),
            "rows": rows}


# --------------------------------------------------------------------------
# Artifact generation
# --------------------------------------------------------------------------


# The source files whose content determines the exported Mosaic
# artifacts. Paths are repo-relative; the digests ship in summary.json so
# CI can detect stale committed artifacts WITHOUT re-running the (slow)
# full export: if a kernel source changed and the artifacts were not
# regenerated, the recomputed digest diverges
# (tests/test_artifact_staleness.py).
KERNEL_SOURCES = (
    "ydf_tpu/ops/histogram_pallas.py",
    "ydf_tpu/ops/binning_pallas.py",
    "ydf_tpu/ops/vector_sequence.py",
    "ydf_tpu/serving/quickscorer.py",
    "ydf_tpu/serving/pallas_scorer.py",
    "ydf_tpu/utils/tpu_lowering.py",
)


def kernel_source_digests() -> dict:
    """sha256 of each Pallas-kernel source file (KERNEL_SOURCES),
    keyed by repo-relative path. Computed from the installed package
    location so the test and the export agree on the same bytes."""
    import hashlib

    root = Path(__file__).resolve().parent.parent.parent
    out = {}
    for rel in KERNEL_SOURCES:
        p = root / rel
        out[rel] = hashlib.sha256(p.read_bytes()).hexdigest()
    return out


def write_artifacts(outdir: str | Path, full_scale: bool = True) -> dict:
    """Exports every flagship computation for platform 'tpu' and writes:

      <name>.jax_export.bin.gz   -- jax.export serialized artifact
                                    (deserializable, versioned)
      <name>.stablehlo.mlir.gz   -- human-readable StableHLO (Pallas
                                    kernels appear as tpu_custom_call
                                    with the Mosaic module inline)
      summary.json               -- sizes + sanity flags + projection

    Returns the summary dict."""
    outdir = Path(outdir)
    outdir.mkdir(parents=True, exist_ok=True)
    _register_serialization()
    scale = (
        dict(n=500_000, F=28) if full_scale else dict(n=4096, F=8)
    )
    exports = {
        "train_step_matmul": lambda: export_train_step(
            hist_impl="matmul", **scale
        ),
        "train_step_segment": lambda: export_train_step(
            hist_impl="segment", **scale
        ),
        # The flagship: the boosting loop with the Mosaic histogram
        # kernel (ops/histogram_pallas.py) embedded as tpu_custom_call.
        "train_step_pallas": lambda: export_train_step(
            hist_impl="pallas", **scale
        ),
        "grow_tree_matmul": lambda: export_grow_tree(
            hist_impl="matmul", **scale
        ),
        "histogram_pallas_kernel": export_histogram_pallas,
        # The quantized-gradient operand precisions (YDF_TPU_HIST_QUANT)
        # Mosaic-lower next to the exact kernel: bf16 hi/lo halves and
        # int8 MXU tiles with int32 accumulation.
        "histogram_pallas_kernel_bf16x2": lambda: export_histogram_pallas(
            quant="bf16x2"
        ),
        "histogram_pallas_kernel_int8": lambda: export_histogram_pallas(
            quant="int8"
        ),
        # The device-resident loop's fused route+histogram kernel
        # (ops/histogram_pallas.py:histogram_routed_pallas): previous-
        # layer routing in-register + this-layer histogram in one
        # Mosaic pass, across the quantized-gradient operand modes.
        "histogram_routed_pallas_kernel": export_histogram_routed_pallas,
        "histogram_routed_pallas_kernel_bf16x2": (
            lambda: export_histogram_routed_pallas(quant="bf16x2")
        ),
        "histogram_routed_pallas_kernel_int8": (
            lambda: export_histogram_routed_pallas(quant="int8")
        ),
        # Ingestion: the fused binning pipeline's Mosaic kernel
        # (ops/binning_pallas.py) — bins compile on-device next to the
        # loop that consumes them.
        "binning_pallas_kernel": export_binning_pallas,
        "quickscorer_kernel": export_quickscorer,
        # Serving beyond the QuickScorer envelope: the batched
        # data-bank scorer (serving/pallas_scorer.py) — TPU serving of
        # any tree shape.
        "serve_bank_pallas_kernel": export_serve_bank,
        "vector_sequence_kernel": export_vector_sequence,
    }
    summary = {"platforms": ["tpu"], "artifacts": {}}
    for name, fn in exports.items():
        exp = fn()
        blob = exp.serialize()
        mlir = exp.mlir_module()
        (outdir / f"{name}.jax_export.bin.gz").write_bytes(
            gzip.compress(bytes(blob))
        )
        (outdir / f"{name}.stablehlo.mlir.gz").write_bytes(
            gzip.compress(mlir.encode())
        )
        summary["artifacts"][name] = {
            "platforms": list(exp.platforms),
            "serialized_bytes": len(blob),
            "mlir_chars": len(mlir),
            "mosaic_kernel": "tpu_custom_call" in mlir,
        }
    summary["projection"] = tpu_projection()
    # Per-quant-mode rooflines (one shared cost analysis — the MAC
    # count is precision-independent; only the tile rate changes).
    cost = grow_tree_cost()
    summary["projection_by_quant"] = {
        q: tpu_projection(cost=cost, hist_quant=q)
        for q in ("f32", "bf16x2", "int8")
    }
    # Sub-128-lane slot packing (PR 4): MXU issue accounting the
    # MAC-based projection can't see — the per-layer dot-count collapse
    # on sibling-subtraction layers.
    summary["pallas_slot_packing"] = pallas_lane_packing_summary()
    # Fused route+histogram transfer accounting: what fusion removes
    # from HBM per tree at the projection shape (the per-layer hist_slot
    # and new_slot/new_leaf intermediates the unfused chain writes and
    # re-reads), next to the routing MXU passes it adds (counted in
    # projection_by_quant's compute term — see its "basis").
    pn, pd = 500_000, 6
    summary["fused_route_accounting"] = {
        "config": {"n": pn, "max_depth": pd},
        "route_flops_per_tree": _analytic_route_flops(pn, pd, 256),
        "route_mxu_passes_per_mac": MXU_PASSES_PER_MAC["f32"],
        # hist_slot [n] i32 written+read per routed layer by the
        # unfused chain; fused, it lives in registers.
        "hist_slot_hbm_bytes_avoided_per_tree": 2 * (pd - 1) * pn * 4,
        "basis": (
            "fusion removes the per-layer hist_slot round trip "
            "(2 x (depth-1) x n x 4 B) and computes it in-register; "
            "the routing one-hot dots it adds are charged to the "
            "compute roofline via route_flops_per_tree"
        ),
    }
    summary["source_digests"] = kernel_source_digests()
    (outdir / "summary.json").write_text(json.dumps(summary, indent=2))
    return summary


if __name__ == "__main__":
    import sys

    jax.config.update("jax_platforms", "cpu")
    out = sys.argv[1] if len(sys.argv) > 1 else "artifacts/tpu_lowering"
    s = write_artifacts(out)
    print(json.dumps(s, indent=2))
