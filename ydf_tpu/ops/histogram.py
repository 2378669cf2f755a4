"""Per-(node, feature, bin) gradient-statistics histograms.

This op replaces the reference's entire split-search machinery:
`FillExampleBucketSet` (`ydf/learner/decision_tree/splitter_scanner.h:860`,
one linear pass per (open node, feature) dispatched on a CPU work queue
`training.cc:1483`) becomes ONE dense contraction producing
`hist[frontier_slot, feature, bin, stat]` for the whole layer at once.

Two implementations:

  * "matmul" (TPU): for each feature, contract a one-hot of the bin index
    against the (stats ⊗ slot-one-hot) matrix on the MXU:

        A[n, L*S]   = stats[n, S] scattered into the example's slot row
        hist[f]     = onehot(bins[:, f])^T  @  A        # [B, L*S]

    TPU has no fast scatter (HLO scatter lowers to a serial loop), so the
    one-hot matmul is the idiomatic way to histogram on the MXU. Work is
    chunked over examples to bound the materialized one-hot.

  * "segment" (CPU / small data): `jax.ops.segment_sum` over the fused
    (slot, bin) index — fast on CPU where scatter-add is native; used by the
    unit tests and as the correctness oracle.

Slot contract (shared by ALL backends — segment, matmul, native,
pallas): `slot` holds the histogram slot of every example, an int32 in
[0, num_slots]; the value num_slots is the TRASH slot — inactive,
padded, or deliberately-skipped rows — whose contribution is dropped.
Callers may pass ANY subset of rows as live; in particular the grower's
sibling-subtraction mode (ops/grower.py) passes at most ceil(frontier/2)
live slots per layer, with every larger-child row on the trash slot.

Design note — sibling-subtraction histograms (the slot-halving
contract). CPU histogram GBTs (sklearn/LightGBM, and the reference's
per-node splitters) halve their per-level work by building each level's
histograms only over the SMALLER child of every split and deriving the
sibling as parent − child. An earlier revision of this file argued the
trick cannot pay in a dense formulation because every row is touched
regardless — that was wrong for the contraction backends: the one-hot
matmul's FLOPs scale with n·B·L·S, so halving the LIVE SLOT COUNT L
halves the MXU contraction (and the psum payload under shard_map) even
though all n rows are still read. The grower therefore assigns
histogram slots only to the smaller child of each split and rebuilds
the sibling by subtraction before gain search:

  * matmul / segment: the [*, L*S] operand (resp. the [F*(L+1)*B, S]
    scatter target) halves — half the FLOPs / accumulator footprint.
  * native: the kernel early-continues rows on the trash slot, so the
    per-row F-loop runs only for smaller-child rows (~n/2 per layer
    past the root) and the f64 scratch halves.
  * pallas: the slot axis is padded to 128 lanes, so the dot shape only
    shrinks once L exceeds 128; correctness is unchanged (trash rows
    zero their one-hot column) and HBM traffic was already at the
    re-read floor.

Float tolerance of parent − child: both operands are f32 sums of the
same per-example stats, so the reconstruction error per cell is bounded
by a few ulps of the PARENT's magnitude, and it compounds only linearly
with depth (each layer's parent is itself at most one subtraction
deep per level). Count-like stats are small integers times weights —
cancellation can leave a derived count of 0 at ±~1e-4, far below the
min_examples >= 1 validity threshold, so no phantom split can validate.
Gain search already derived every right-hand candidate as parent −
left-prefix before this change; sibling subtraction adds one more
subtraction of the same character, not a new failure mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Concrete implementations _histogram_jit dispatches on; "auto" is
# resolved to one of these BEFORE the jit boundary (resolve_hist_impl).
_HIST_IMPLS = frozenset(
    {"segment", "matmul", "native", "pallas", "pallas_interpret"}
)

# Gradient-quantization modes for the stats operand (the one-hot operand
# is exact in bf16, so only `stats` needs a precision strategy):
#   f32     exact — bit-identical to the pre-quantization pipeline. The
#           matmul impl asks for Precision.HIGHEST: XLA:TPU's default
#           for an f32 dot is ONE bf16 pass, which rounds every gradient
#           to 8 mantissa bits. Measured on a v5e (PR 21, CHANGES.md):
#           at the default the sums sit 9e-4 of a cell's magnitude from
#           the segment impl and a 200k x 28 GBT diverges (train loss
#           rises from the second tree, leaf values reach -559); at
#           HIGHEST they sit 3e-7 away and losses and AUC equal
#           segment's.
#   bf16x2  split every f32 stat column into a bf16 high part plus a
#           bf16 residual; the contraction runs on native bf16 MXU tiles
#           (2 passes instead of the 6 XLA:TPU spends on an f32 operand
#           at HIGHEST) with f32 accumulation. Reconstruction error per
#           example is bounded by the bf16 rounding of the RESIDUAL,
#           ~2^-16 of the stat magnitude
#           (docs/histogram_quantization.md).
#   int8    LightGBM-GPU-style quantized gradients: stats are rounded to
#           int8 with a dynamic per-column scale (per-layer in the
#           grower), accumulated EXACTLY in integers, and dequantized
#           once after the reduction. Error per example <= scale/2.
_HIST_QUANTS = frozenset({"f32", "bf16x2", "int8"})


def _histogram_segment(
    bins, slot, stats, num_slots: int, num_bins: int, chunk: int = 1 << 18
):
    n, F = bins.shape
    S = stats.shape[1]
    L, B = num_slots, num_bins
    # Accumulation-safe dtype: int8 stats (quant mode) must scatter into
    # int32 lanes (an int8 accumulator would wrap after two rows), bf16
    # halves (bf16x2 mode) into f32 — both casts are exact per element.
    if jnp.issubdtype(stats.dtype, jnp.integer):
        stats = stats.astype(jnp.int32)
    elif stats.dtype == jnp.bfloat16:
        stats = stats.astype(jnp.float32)
    # ONE scatter over n*F rows with a fused (feature, slot, bin) segment
    # id — measured 1.46x over a vmap of per-feature scatters on XLA-CPU
    # (scripts/exp_cpu_histogram.py, round 5): one big scatter amortizes
    # per-op dispatch and keeps the [F*(L+1)*B, S] target resident.
    # The [rows, F, S] stats replication the fused id needs is bounded by
    # chunking over examples (~32M transient f32 elements), scanning
    # chunks into one accumulator — an unchunked 2M x 28 call would
    # materialize ~672 MB.
    fidx = jnp.arange(F, dtype=jnp.int32)[None, :]

    def fused_chunk(b_c, s_c, st_c):
        m = b_c.shape[0]
        idx = (
            fidx * (L + 1) + s_c[:, None].astype(jnp.int32)
        ) * B + b_c.astype(jnp.int32)  # [m, F]
        data = jnp.broadcast_to(st_c[:, None, :], (m, F, S))
        return jax.ops.segment_sum(
            data.reshape(m * F, S), idx.reshape(m * F),
            num_segments=F * (L + 1) * B, indices_are_sorted=False,
        )  # [F*(L+1)*B, S]

    rows = max(1, min(n, chunk, (1 << 25) // max(F * S, 1)))
    if n <= rows:
        hist = fused_chunk(bins, slot, stats)
    else:
        n_pad = ((n + rows - 1) // rows) * rows
        b_p = jnp.pad(bins, ((0, n_pad - n), (0, 0)))
        # Padded rows go to the trash slot L (dropped below).
        s_p = jnp.pad(slot, (0, n_pad - n), constant_values=L)
        st_p = jnp.pad(stats, ((0, n_pad - n), (0, 0)))

        def body(acc, xs):
            b_c, s_c, st_c = xs
            return acc + fused_chunk(b_c, s_c, st_c), None

        hist, _ = jax.lax.scan(
            body,
            jnp.zeros((F * (L + 1) * B, S), stats.dtype),
            (
                b_p.reshape(n_pad // rows, rows, F),
                s_p.reshape(n_pad // rows, rows),
                st_p.reshape(n_pad // rows, rows, S),
            ),
        )
    hist = hist.reshape(F, L + 1, B, S)[:, :L]
    return jnp.transpose(hist, (1, 0, 2, 3))  # [L, F, B, S]


def _histogram_matmul(
    bins, slot, stats, num_slots: int, num_bins: int, chunk: int = 1 << 18
):
    n, F = bins.shape
    S = stats.shape[1]
    L, B = num_slots, num_bins
    chunk = min(chunk, max(n, 1))

    n_pad = ((n + chunk - 1) // chunk) * chunk
    if n_pad != n:
        bins = jnp.pad(bins, ((0, n_pad - n), (0, 0)))
        # Padded examples land in the trash slot L and are dropped below.
        slot = jnp.pad(slot, (0, n_pad - n), constant_values=L)
        stats = jnp.pad(stats, ((0, n_pad - n), (0, 0)))
    bins_c = bins.reshape(n_pad // chunk, chunk, F)
    slot_c = slot.reshape(n_pad // chunk, chunk)
    stats_c = stats.reshape(n_pad // chunk, chunk, S)

    bvals = jnp.arange(B, dtype=jnp.int32)
    # int8 stats (quant mode) contract on integer operands with an int32
    # accumulator — exact, and the operands are MXU int8 tiles on TPU.
    # Everything else (f32, and the bf16x2 halves) accumulates in f32.
    acc_dtype = (
        jnp.int32 if jnp.issubdtype(stats.dtype, jnp.integer)
        else jnp.float32
    )
    # Only the f32 operand needs more than one pass (see _HIST_QUANTS);
    # the bf16x2 halves and int8 stats are exact in their tile type.
    precision = (
        jax.lax.Precision.HIGHEST if stats.dtype == jnp.float32 else None
    )

    def one_chunk(carry, xs):
        b_chunk, s_chunk, st_chunk = xs  # [chunk, F], [chunk], [chunk, S]
        # stats ⊗ onehot(slot), built per chunk to bound memory; the trash
        # slot L falls outside arange(L) and contributes zero rows.
        slot_oh = (
            s_chunk[:, None] == jnp.arange(L, dtype=s_chunk.dtype)[None, :]
        ).astype(st_chunk.dtype)  # [chunk, L]
        a_chunk = (slot_oh[:, :, None] * st_chunk[:, None, :]).reshape(
            chunk, L * S
        )

        def per_feature(f, acc):
            oh = (b_chunk[:, f, None].astype(jnp.int32) == bvals[None, :]).astype(
                a_chunk.dtype
            )  # [chunk, B]
            h = jax.lax.dot_general(
                oh,
                a_chunk,
                (((0,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=acc_dtype,
            )  # [B, L*S]
            return acc.at[f].add(h)

        carry = jax.lax.fori_loop(0, F, per_feature, carry)
        return carry, None

    init = jnp.zeros((F, B, L * S), dtype=acc_dtype)
    hist, _ = jax.lax.scan(one_chunk, init, (bins_c, slot_c, stats_c))
    hist = hist.reshape(F, B, L, S)
    # Returned in the ACCUMULATOR dtype (int32 for int8 stats — a cast
    # back to int8 would wrap); the _histogram_jit wrapper owns the final
    # output-dtype contract.
    return jnp.transpose(hist, (2, 0, 1, 3))  # [L, F, B, S]


def _compact_live_rows(bins, slot, stats, cap: int, num_slots: int):
    """Gathers the rows with a live slot (< num_slots) into the first
    positions of a `cap`-row buffer; padded positions carry the trash
    slot. Returns (bins_c, slot_c, stats_c, live_count). Rows beyond
    `cap` are DROPPED — the caller must fall back when live_count > cap
    (ROADMAP trash-row compaction: under the grower's sibling
    subtraction, live rows are the smaller children, ≤ ~n/2 + one per
    split, so a static n/2-ish capacity almost always holds)."""
    n = bins.shape[0]
    i32 = jnp.int32
    live = slot < num_slots
    live_count = jnp.sum(live.astype(i32))
    pos = jnp.cumsum(live.astype(i32)) - 1  # rank of each live row
    tgt = jnp.where(live & (pos < cap), pos, cap)  # overflow/trash -> cap
    # Scatter row ids into the compacted index map; untouched entries
    # stay n (no live row landed there) and gather as trash below.
    idx = jnp.full((cap + 1,), n, i32).at[tgt].set(jnp.arange(n, dtype=i32))
    idx = idx[:cap]
    safe = jnp.clip(idx, 0, n - 1)
    bins_c = jnp.take(bins, safe, axis=0)
    stats_c = jnp.take(stats, safe, axis=0)
    slot_c = jnp.where(idx < n, jnp.take(slot, safe), num_slots)
    return bins_c, slot_c, stats_c, live_count


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_slots", "num_bins", "impl", "chunk", "quant", "compact"
    ),
)
def _histogram_jit(
    bins, slot, stats, quant_scale, num_slots, num_bins, impl, chunk,
    quant, compact,
):
    if impl == "auto":
        # Refuse a literal "auto" INSIDE a jit boundary: callers that
        # bypassed resolve_hist_impl would cache the first resolution
        # under the key "auto" forever (the stale-cache hazard the
        # wrapper split exists to prevent).
        raise ValueError(
            "histogram impl 'auto' must be resolved before the jit "
            "boundary (use histogram()/grow_tree(), or resolve_hist_impl)"
        )
    if quant not in _HIST_QUANTS:
        raise ValueError(
            f"histogram quant {quant!r} must be resolved before the jit "
            f"boundary (expected one of {sorted(_HIST_QUANTS)}; use "
            "histogram()/grow_tree(), or resolve_hist_quant)"
        )
    f32 = jnp.float32
    # Callers on a hot loop (the grower) quantize/split ONCE per tree
    # and pass the transformed operand directly — int8 [n, S] stats for
    # "int8", bf16 [n, 2S] hi/lo halves for "bf16x2" — instead of
    # paying the O(n·S) transform on every layer. Detected by dtype.
    pre_quantized = quant == "int8" and jnp.issubdtype(
        stats.dtype, jnp.integer
    )
    pre_split = quant == "bf16x2" and stats.dtype == jnp.bfloat16
    S = stats.shape[1] // 2 if pre_split else stats.shape[1]

    if quant == "int8":
        # Dynamic symmetric scale per stat column: defaults to this
        # call's max-|stat| range when the caller did not carry one (the
        # grower computes one scale per TREE from the root frontier's
        # ranges and carries it through its scan state — see the
        # consistency argument at ops/grower.py). Guarded against
        # all-zero columns, then snapped UP to a
        # power of two: scaling by 2^k is a pure exponent shift, so
        # quantize rounds ONCE and dequantize (q × 2^k) is EXACT — in
        # particular unit example weights come back as exact integers,
        # keeping the `count >= min_examples` validity boundary
        # bit-faithful to the exact pipeline (a max/127 scale returns
        # k·0.99999999·… counts that fail `>= k`). Costs at most one
        # bit of the 7-bit resolution.
        if quant_scale is None:
            if pre_quantized:
                raise ValueError(
                    "pre-quantized int8 stats require quant_scale"
                )
            quant_scale = jnp.max(jnp.abs(stats), axis=0) / 127.0
        quant_scale = jnp.maximum(
            quant_scale.astype(f32), jnp.finfo(jnp.float32).tiny
        )
        quant_scale = jnp.exp2(jnp.ceil(jnp.log2(quant_scale)))

    def dispatch(bins_d, slot_d, stats_d):
        """Quantize -> impl -> dequantize for one (possibly compacted)
        row set. quant == "f32" is byte-for-byte the pre-quantization
        pipeline: the default mode stays bit-identical."""
        if quant == "bf16x2" and not pre_split:
            hi = stats_d.astype(jnp.bfloat16)
            lo = (stats_d - hi.astype(f32)).astype(jnp.bfloat16)
            stats_q = jnp.concatenate([hi, lo], axis=1)  # bf16 [n, 2S]
        elif quant == "int8" and not pre_quantized:
            # Multiply by the exact reciprocal: the scale is a power of
            # two, so 1/scale is exact and x*(1/scale) ≡ x/scale bit
            # for bit — and one multiply is cheaper than one divide on
            # every CPU this fallback runs on.
            q = jnp.round(stats_d * (1.0 / quant_scale)[None, :])
            stats_q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
        else:
            stats_q = stats_d

        if impl == "segment":
            out = _histogram_segment(
                bins_d, slot_d, stats_q, num_slots, num_bins, chunk
            )
        elif impl == "matmul":
            out = _histogram_matmul(
                bins_d, slot_d, stats_q, num_slots, num_bins, chunk
            )
        elif impl in ("pallas", "pallas_interpret"):
            from ydf_tpu.ops.histogram_pallas import histogram_pallas

            out = histogram_pallas(
                bins_d, slot_d, stats_q, num_slots, num_bins,
                interpret=(impl == "pallas_interpret"),
            )
        elif impl == "native":
            if quant == "int8":
                # The native int8 kernel dequantizes INSIDE its
                # fixed-block-order reduction (int64 totals × scale,
                # rounded once) — no Python-side dequantize.
                from ydf_tpu.ops.histogram_native import (
                    histogram_native_q8,
                )

                return histogram_native_q8(
                    bins_d, slot_d, stats_q, quant_scale, num_slots,
                    num_bins,
                )
            from ydf_tpu.ops.histogram_native import histogram_native

            out = histogram_native(
                bins_d, slot_d, stats_q, num_slots, num_bins
            )
        else:
            raise ValueError(f"Unknown histogram impl {impl!r}")

        if quant == "bf16x2":
            # Fold the high/residual halves back into S columns (f32
            # accumulators, so the fold is the only extra rounding).
            out = out.astype(f32)
            out = out[..., :S] + out[..., S:]
        elif quant == "int8":
            out = out.astype(f32) * quant_scale[None, None, None, :]
        return out

    if compact > 0 and impl == "segment" and compact < bins.shape[0]:
        # Trash-row compaction (XLA-CPU scatter path): gather the live
        # rows into a half-size buffer before the per-layer scatter, so
        # the fused segment_sum streams ~n/2 rows — the same row-work
        # reduction the native kernel's early-continue gives. Falls back
        # to the full-row path when the live count exceeds the static
        # capacity (possible under heavily non-uniform example weights,
        # where the "smaller" child by weight holds more ROWS).
        bins_c, slot_c, stats_c, live_count = _compact_live_rows(
            bins, slot, stats, compact, num_slots
        )
        out = jax.lax.cond(
            live_count <= compact,
            lambda: dispatch(bins_c, slot_c, stats_c),
            lambda: dispatch(bins, slot, stats),
        )
    else:
        out = dispatch(bins, slot, stats)
    # One output-dtype contract for every impl: "segment" follows
    # stats.dtype while "native"/"pallas" accumulate f32 — without this
    # cast, auto-selection could silently change the result dtype for
    # non-f32 stats (ADVICE r5). Pre-transformed operands (int8 / bf16
    # halves) stand in for f32 stats, so their output is f32.
    out_dtype = (
        jnp.float32 if (pre_quantized or pre_split) else stats.dtype
    )
    return out.astype(out_dtype)


def resolve_hist_impl(impl: str = "auto") -> str:
    """Resolves "auto" to a concrete impl BEFORE the jit boundary, so the
    jit cache is keyed on the concrete impl (resolving inside the traced
    body would cache the first resolution under the key "auto" and ignore
    later environment changes).

    YDF_TPU_HIST_IMPL overrides auto-selection — used by the device-less
    TPU export path (utils/tpu_lowering.py) to lower the matmul impl for
    platform 'tpu' on a box with no TPU devices, and by CPU perf
    experiments. Scope caveat: resolution happens at TRACE time, and the
    boosting loop's closure cache (learners/gbt.py:_make_boost_fn
    lru_cache) is keyed on neither this env var nor the impl — setting
    the variable between two same-config train() calls in one process
    does NOT retrace. It is reliable for export paths and fresh
    processes (tpu_lowering bypasses the closure cache via __wrapped__
    for exactly this reason)."""
    if impl != "auto":
        return impl
    import os

    from ydf_tpu.config import is_tpu_backend

    forced = os.environ.get("YDF_TPU_HIST_IMPL")
    if forced:
        # Fail HERE on a misconfigured override — "auto" or a typo
        # would otherwise surface later as a trace-time error pointing
        # back at this resolver (ADVICE r5).
        if forced not in _HIST_IMPLS:
            raise ValueError(
                f"YDF_TPU_HIST_IMPL={forced!r} is not a concrete "
                f"histogram impl; expected one of {sorted(_HIST_IMPLS)}"
            )
        return forced
    if is_tpu_backend():
        return "matmul"
    from ydf_tpu.ops.histogram_native import available

    return "native" if available() else "segment"


def resolve_hist_quant(value=None) -> str:
    """Resolves the gradient-quantization mode BEFORE the jit boundary
    (same trace-time caveats as resolve_hist_impl: the boosting loop's
    closure cache is keyed on neither the env var nor the mode). An
    explicit value wins; YDF_TPU_HIST_QUANT selects globally; default is
    "f32" (exact — bit-identical to the pre-quantization pipeline).
    Validation is EAGER: a typo fails here, at the env boundary, not as
    a trace-time error deep inside the grower."""
    if value is not None:
        if value not in _HIST_QUANTS:
            raise ValueError(
                f"histogram quant {value!r} is not a quantization mode; "
                f"expected one of {sorted(_HIST_QUANTS)}"
            )
        return value
    import os

    env = os.environ.get("YDF_TPU_HIST_QUANT")
    if env is None:
        return "f32"
    low = env.strip().lower()
    if low not in _HIST_QUANTS:
        raise ValueError(
            f"YDF_TPU_HIST_QUANT={env!r} is not a quantization mode; "
            f"expected one of {sorted(_HIST_QUANTS)}"
        )
    return low


def resolve_hist_subtract(value=None) -> bool:
    """Resolves the grower's sibling-subtraction default BEFORE the jit
    boundary (same trace-time caveats as resolve_hist_impl: the boosting
    loop's closure cache is keyed on neither this env var nor the flag).
    An explicit bool wins; YDF_TPU_HIST_SUBTRACT=0 disables the trick
    globally (parity debugging, perf A/B); default is ON."""
    if value is not None:
        return bool(value)
    import os

    env = os.environ.get("YDF_TPU_HIST_SUBTRACT")
    if env is None:
        return True
    low = env.strip().lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(
        f"YDF_TPU_HIST_SUBTRACT={env!r} is not a boolean; expected one of "
        "1/0/true/false/yes/no/on/off"
    )


@jax.named_scope("ydf.hist")
def histogram(
    bins: jax.Array,  # uint8/int32 [n, F] bin index per (example, feature)
    slot: jax.Array,  # int32 [n] frontier slot in [0, L]; L = inactive
    stats: jax.Array,  # float [n, S] weighted per-example statistics
    num_slots: int,
    num_bins: int = 256,
    impl: str = "auto",
    chunk: int = 1 << 18,
    quant: str | None = None,
    quant_scale: jax.Array | None = None,  # f32 [S] int8 scale (traced)
    compact: int = 0,
) -> jax.Array:
    """Returns hist[num_slots, F, num_bins, S] = Σ_examples stats.

    `quant` selects the stats-operand precision (None resolves
    YDF_TPU_HIST_QUANT; default "f32" is exact). In "int8" mode
    `quant_scale` carries the per-column dynamic scale — the grower
    computes it once per tree from the root frontier's stat ranges and
    threads it through its scan state; when omitted, the scale is
    computed from this call's stats. `compact`
    > 0 enables trash-row compaction on the segment impl: live rows are
    gathered into a `compact`-row buffer before the scatter (with a
    full-row fallback when they don't fit)."""
    return _histogram_jit(
        bins, slot, stats, quant_scale, num_slots, num_bins,
        resolve_hist_impl(impl), chunk, resolve_hist_quant(quant),
        compact,
    )
