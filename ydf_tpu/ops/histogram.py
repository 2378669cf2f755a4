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
    chunked over examples to bound the materialized one-hot. Both
    operands are bf16 and the dot is one MXU pass: the one-hot is exact
    in bf16, and an f32 stat rides as exact bf16 pieces laid side by
    side in A: three a column unless the caller says what the column
    holds (StatColumn below: a column of zeros and ones is its own
    first piece, a column that equals another is not contracted at
    all), so a slot of [g w, h w, w] is 9 columns of A, 7 where w is
    0 or 1, 4 where h w is w besides (see _HIST_QUANTS below). The
    one-hot is written [B, n], rows on the minor side, and A goes to
    the MXU in dots of at most 128 columns where whole pieces fit
    them: both by measurement on a v5e (PERF.md section 5).
    A does not depend on the feature and is built once a chunk, outside
    the loop over features, as [L*S, n] from its first operation on
    (`narrow` in _histogram_matmul): written as a [S, L, n] product and
    reshaped, it was rebuilt for every feature by the compiled program
    at L = 2 and 4 (PERF.md section 6, PR 34).
    The one-hot goes by each feature's bin count where the caller gives
    them (`feature_num_bins`, the binner's): features of at most 128
    bins share one-hot tiles of 128 rows, one MXU tile high, each in
    rows of its own (onehot_plan, _pack_onehot), where each took 256
    rows; a feature of more keeps its own 256. Where no feature has 128
    bins or fewer the program is the one it was.

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
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Concrete implementations _histogram_jit dispatches on; "auto" is
# resolved to one of these BEFORE the jit boundary (resolve_hist_impl).
_HIST_IMPLS = frozenset(
    {"segment", "matmul", "native", "pallas", "pallas_interpret"}
)

# Gradient-quantization modes for the stats operand (the one-hot operand
# is exact in bf16, so only `stats` needs a precision strategy):
#   f32     exact — every example's f32 stat reaches the sum with all
#           24 significand bits. XLA:TPU's default for an f32 dot is ONE
#           bf16 pass, which rounds every gradient to 8 bits: measured
#           on a v5e (PR 21, CHANGES.md), the sums then sit 9e-4 of a
#           cell's magnitude from the segment impl and a 200k x 28 GBT
#           diverges (train loss rises from the second tree, leaf values
#           reach -559). So the matmul impl splits each f32 stat into
#           THREE bf16 pieces (split_bf16: 8 + 8 + 8 bits, their sum is
#           the f32 value bit for bit), lays them side by side on the
#           narrow side of ONE single-pass bf16 dot with f32
#           accumulation, and adds the three slabs of the result. A 0/1
#           one-hot times a bf16 piece is exact, so this is the sum a
#           multi-pass f32 dot computes, with the one-hot streamed
#           through the MXU once and not once per pass. The operand
#           holds only the pieces that can be non-zero: a caller that
#           knows a column to be zeros and ones, or to equal another,
#           says so (StatColumn), and its second and third pieces, or
#           the whole column, stay off the MXU; the sums are the same
#           bit for bit.
#   bf16x2  the same with the last piece dropped: a bf16 high part plus
#           a bf16 residual, 16 bits of every stat. Reconstruction error
#           per example is bounded by the bf16 rounding of the RESIDUAL,
#           ~2^-16 of the stat magnitude
#           (docs/histogram_quantization.md). Its split stays the plain
#           cast pair it has always been and does NOT go through
#           split_bf16: the benchmark holds this mode as its
#           lower-precision control, and on a v5e the two differ (PR 27,
#           PERF.md section 7: through split_bf16's reduce_precision the
#           mode reads as sound, 1.2e-7 on the median leaf; the cast
#           pair reads 2.9e-6 to 3.6e-6).
#   int8    LightGBM-GPU-style quantized gradients: stats are rounded to
#           int8 with a dynamic per-column scale (per-layer in the
#           grower), accumulated EXACTLY in integers, and dequantized
#           once after the reduction. Error per example <= scale/2.
_HIST_QUANTS = frozenset({"f32", "bf16x2", "int8"})

# Columns of one MXU tile (128 x 128 on every TPU generation so far).
_MXU_COLUMNS = 128
# Rows of the same tile: a one-hot of at most this many rows is one tile
# high on the MXU, whatever its features' bin counts.
_MXU_ROWS = 128


class StatColumn(NamedTuple):
    """What a caller knows of one column of the f32 stats it hands the
    `matmul` histogram. The default is any f32. A wrong claim is not
    caught: a "0 or 1" column that holds a 0.5 is summed rounded to 8
    bits, so a description is derived from facts, where the stats are
    made (learners/gbt.py:_hist_stat_columns), and never passed through
    from a user."""

    # bf16 pieces that hold every value of the column exactly: 3 for
    # any f32, 1 where every value is 0 or 1 (it is its own first piece
    # and leaves a remainder of exactly 0).
    pieces: int = 3
    # Index of the column this one equals bit for bit, or None. Such a
    # column is not contracted: its sums are the other column's.
    same_as: Optional[int] = None


def _piece_counts(stat_columns, num_stats: int):
    """bf16 pieces of each column of a stats operand that go onto the
    MXU, 0 for a column that is another's copy; the description
    checked against the operand."""
    if len(stat_columns) != num_stats:
        raise ValueError(
            f"stat_columns describes {len(stat_columns)} columns of a "
            f"stats operand that has {num_stats}"
        )
    for col in stat_columns:
        if col.same_as is None:
            if col.pieces not in (1, 2, 3):
                raise ValueError(f"{col}: a column has 1 to 3 bf16 pieces")
        elif not (
            0 <= col.same_as < num_stats
            and stat_columns[col.same_as].same_as is None
        ):
            raise ValueError(f"{col}: same_as names no contracted column")
    return [
        col.pieces if col.same_as is None else 0 for col in stat_columns
    ]


def narrow_columns_per_slot(
    stat_columns=None, quant: str = "f32", num_stats: int = 3
) -> int:
    """Columns a slot on the narrow side of the `matmul` histogram's
    dots: every kept bf16 piece of an f32 operand (9 for three plain
    columns), the halves of `bf16x2`, the columns of `int8`."""
    if quant == "f32":
        stat_columns = stat_columns or (StatColumn(),) * num_stats
        return sum(_piece_counts(stat_columns, num_stats))
    return {"bf16x2": 2, "int8": 1}[quant] * num_stats


class OneHotPlan(NamedTuple):
    """How the `matmul` histogram's one-hot is laid out for one chunk:
    `wide` features take `num_bins` rows each, in a loop over them; each
    pack is one MXU tile high (`_MXU_ROWS`) and holds features whose
    bins fit it side by side, as (feature, first row, bins)."""

    wide: Tuple[int, ...]
    packs: Tuple[Tuple[Tuple[int, int, int], ...], ...]


def onehot_plan(feature_num_bins, num_features: int, num_bins: int):
    """The one-hot's layout (OneHotPlan) for `num_features` columns of
    bins, of which the first `len(feature_num_bins)` hold bins below
    their count there (the binner's `feature_num_bins`): those of at
    most `_MXU_ROWS` bins go first-fit by decreasing count into packs of
    one tile. A column with no count (none given, or a pad column past
    the description) keeps `num_bins` rows of its own. A wrong count is
    not caught: a bin at or past it is left out of the sums, so a count
    is derived where the bins are made, never passed through from a
    user."""
    described = tuple(feature_num_bins or ())[:num_features]
    counts = {
        f: max(1, min(int(c), num_bins)) for f, c in enumerate(described)
        if int(c) <= _MXU_ROWS
    }
    packs = []  # [rows used, [(feature, first row, bins), ...]]
    for f in sorted(counts, key=lambda f: -counts[f]):
        room = [p for p in packs if p[0] + counts[f] <= _MXU_ROWS]
        if not room:
            packs.append([0, []])
            room = packs[-1:]
        room[0][1].append((f, room[0][0], counts[f]))
        room[0][0] += counts[f]
    return OneHotPlan(
        wide=tuple(f for f in range(num_features) if f not in counts),
        packs=tuple(tuple(members) for _, members in packs),
    )


def onehot_rows(feature_num_bins, num_features: int, num_bins: int):
    """(one-hot rows the `matmul` histogram streams a chunk, features it
    packs): `num_bins` a wide feature, `_MXU_ROWS` a pack."""
    plan = onehot_plan(feature_num_bins, num_features, num_bins)
    return (
        num_bins * len(plan.wide) + _MXU_ROWS * len(plan.packs),
        sum(len(pack) for pack in plan.packs),
    )


def split_bf16(x, pieces):
    """f32 [n, S] -> bf16 [n, pieces * S]: column block p is the bf16
    rounding of what blocks 0..p-1 left of x, so the blocks add up to
    the leading 8 * pieces significand bits of x. Every subtraction is
    exact in f32, and three pieces hold all 24 bits of a normal f32.
    `pieces` may be one count a column: block p then holds the columns
    with more than p pieces, in their order (a count of 0: in none)."""
    counts = (
        (pieces,) * x.shape[1] if isinstance(pieces, int) else tuple(pieces)
    )
    parts, rest, live = [], x.astype(jnp.float32), list(range(x.shape[1]))
    for p in range(max(counts)):
        keep = [i for i, c in enumerate(live) if counts[c] > p]
        if len(keep) < len(live):
            rest = jnp.concatenate([rest[:, i:i + 1] for i in keep], axis=1)
            live = [live[i] for i in keep]
        # reduce_precision is the rounding to bf16 that no compiler
        # pass may fold away (a convert pair can be, under XLA's
        # excess-precision rule); the cast after it is then exact.
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        parts.append(part.astype(jnp.bfloat16))
        rest = rest - part
    return jnp.concatenate(parts, axis=1)


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


def _pack_onehot(pack, b_chunk):
    """One pack's one-hot, bool [_MXU_ROWS, chunk], from its members
    (feature, first row, bins) in the order of their first rows: each
    row takes the bin column of the last member whose first row is at
    or before it, by a chain of selects on the row, and holds a 1 where
    that bin is the row less the member's first row; rows past the
    members match no bin, and neither does a bin past its member's
    count. One compare an element, and the selects and the compare fuse
    into the contraction: on a v5e the fastest of three forms (PERF.md
    section 6), 0.130 s a level call where a concatenation of
    each member's own compares took 0.223 and a loop over packs with a
    compare a member 0.142."""
    rows = jnp.arange(_MXU_ROWS, dtype=jnp.int32)[:, None]
    local = np.full((_MXU_ROWS, 1), -1, np.int32)  # the bin a row counts
    code = None
    for f, first, count in pack:
        local[first:first + count, 0] = np.arange(count)
        column = b_chunk[:, f].astype(jnp.int32)[None, :]
        code = (
            column if code is None else jnp.where(rows >= first, column, code)
        )
    return code == jnp.asarray(local)


def _histogram_matmul(
    bins, slot, stats, num_slots: int, num_bins: int, chunk: int = 1 << 18,
    stat_columns=None, feature_num_bins=None,
):
    n, F = bins.shape
    S = stats.shape[1]
    L, B = num_slots, num_bins
    chunk = min(chunk, max(n, 1))
    # Features of at most 128 bins share one-hot tiles (onehot_plan); a
    # table where every feature has more keeps one loop over features.
    plan = onehot_plan(feature_num_bins, F, B)
    # Whole chunks are sliced out of the operands as they come, inside
    # the loop; the ragged tail is one more call of the same body on its
    # rows padded to a chunk, after the loop. No operand is reshaped to
    # [chunks, chunk, ...]: that reshape is a relayout of the whole
    # array whose compile time on a TPU depends on the chunk COUNT (62
    # chunks x 28 features: over 400 s where 64 take 2, PERF.md
    # section 6), and a pad of the whole array besides.
    n_full, tail = divmod(n, chunk)

    bvals = jnp.arange(B, dtype=jnp.int32)
    # int8 stats (quant mode) contract on integer operands with an int32
    # accumulator — exact, and the operands are MXU int8 tiles on TPU.
    # Everything else (f32, and the bf16x2 halves) accumulates in f32.
    acc_dtype = (
        jnp.int32 if jnp.issubdtype(stats.dtype, jnp.integer)
        else jnp.float32
    )
    # The bf16x2 halves and int8 stats are exact in their tile type and
    # go onto the MXU as they are; an f32 operand becomes bf16 pieces a
    # chunk (see _HIST_QUANTS), three a column or what `stat_columns`
    # says of it (StatColumn), added up after the scan.
    is_f32 = stats.dtype == jnp.float32
    columns = (
        stat_columns or (StatColumn(),) * S if is_f32
        else (StatColumn(pieces=1),) * S
    )
    counts = _piece_counts(columns, S)
    # The narrow side's blocks of L columns, in order (piece, column):
    # piece p of every column that has one.
    piece_blocks = [
        [(p, c) for c, count in enumerate(counts) if count > p]
        for p in range(max(counts))
    ]
    blocks = [block for piece in piece_blocks for block in piece]
    # Whole pieces a dot: as many as keep its narrow side within one
    # 128-column MXU tile, and one piece a dot past that (the width the
    # f32 operand had). Measured on a v5e (PERF.md section 5). Three
    # plain columns at 16 slots are dots of 96 and 48 columns; with a
    # weight of zeros and ones (7 columns a slot) one dot of 112.
    dots = [[0, 0]]  # [first block, blocks) of each dot
    for piece in piece_blocks:
        first, width = dots[-1]
        if width and (width + len(piece)) * L > _MXU_COLUMNS:
            dots.append([first + width, 0])
        dots[-1][1] += len(piece)

    def one_chunk(carry, b_chunk, s_chunk, st_chunk):
        # [F, B, blocks*L] += sums over [chunk, F], [chunk], [chunk, S]
        if is_f32:
            st_chunk = split_bf16(st_chunk, counts)  # bf16 [chunk, blocks]
        # Both operands are written with the rows on the minor,
        # contracted side, as the MXU takes them: a row's bin and slot
        # are then spread down the bins and slots, not across them, and
        # no operand is laid out anew per chunk. That is most of what a
        # level costs (PERF.md section 5).
        #
        # (st ⊗ onehot(slot))^T, [chunk, c] -> [c * L, chunk], rows
        # (column of st, slot), in two dimensions from its first
        # operation on: a 0/1 matrix on the MXU repeats each column of
        # st L times (one exact product a cell), a select keeps the
        # row's own slot; the trash slot L is no `column % L` and keeps
        # none. Written as a [c, L, chunk] product and reshaped, the
        # operand needed a relayout wherever L fills no whole sublane
        # tile (L = 2, 4), and XLA:TPU sank product and relayout into
        # the loop over features below, where they ran F times a chunk:
        # 1.83 s of a 15.0 s job in `synth100_gbt.sweep` (ledger, PR 31;
        # PERF.md section 6, PR 34). This form runs once a chunk in the
        # compiled program too, which tests/test_tpu_lowering.py checks.
        def narrow(st):
            c = st.shape[1]
            column = jnp.arange(c * L, dtype=jnp.int32)
            repeat = (
                column[:, None] // L == jnp.arange(c, dtype=jnp.int32)
            ).astype(st.dtype)  # [c * L, c]
            wide = jax.lax.dot_general(
                repeat, st, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype,
            ).astype(st.dtype)  # [c * L, chunk]
            slot_of = (column % L).astype(s_chunk.dtype)
            return jnp.where(slot_of[:, None] == s_chunk[None, :], wide, 0)

        # One operand a dot, columns (piece, stat, slot), each built
        # from its own pieces: slicing one [blocks * L, chunk] operand
        # into the dots' parts is a copy of it a chunk.
        a_dots = [
            narrow(st_chunk[:, first:first + width]) for first, width in dots
        ]

        def contract(oh):
            # [rows, chunk] one-hot -> [rows, blocks*L] sums
            return jnp.concatenate(
                [
                    jax.lax.dot_general(
                        oh,
                        a_dot,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=acc_dtype,
                    )
                    for a_dot in a_dots
                ],
                axis=1,
            )

        def onehot(f):
            return (
                bvals[:, None] == b_chunk[:, f].astype(jnp.int32)[None, :]
            ).astype(st_chunk.dtype)  # [B, chunk]

        def per_feature(f, acc):
            return acc.at[f].add(contract(onehot(f)))  # [B, blocks*L]

        if not plan.packs:
            return jax.lax.fori_loop(0, F, per_feature, carry)
        wide_acc, pack_acc = carry
        if plan.wide:
            wide_of = jnp.asarray(plan.wide, jnp.int32)
            wide_acc = jax.lax.fori_loop(
                0, len(plan.wide),
                lambda i, acc: acc.at[i].add(contract(onehot(wide_of[i]))),
                wide_acc,
            )
        return wide_acc, pack_acc + jnp.stack([
            contract(_pack_onehot(pack, b_chunk).astype(st_chunk.dtype))
            for pack in plan.packs
        ])  # [packs, _MXU_ROWS, blocks*L]

    def whole_chunk(i, carry):
        rows = [
            jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=0)
            for x in (bins, slot, stats)
        ]
        return one_chunk(carry, *rows)

    if plan.packs:
        hist = (
            jnp.zeros((len(plan.wide), B, len(blocks) * L), acc_dtype),
            jnp.zeros(
                (len(plan.packs), _MXU_ROWS, len(blocks) * L), acc_dtype
            ),
        )
    else:
        hist = jnp.zeros((F, B, len(blocks) * L), dtype=acc_dtype)
    hist = jax.lax.fori_loop(0, n_full, whole_chunk, hist)
    if tail:
        # Padded examples land in the trash slot L and add exact zeros,
        # so the partial sums are those of a table padded to whole
        # chunks, in the same order.
        pad = chunk - tail
        hist = one_chunk(
            hist,
            jnp.pad(bins[n - tail:], ((0, pad), (0, 0))),
            jnp.pad(slot[n - tail:], (0, pad), constant_values=L),
            jnp.pad(stats[n - tail:], ((0, pad), (0, 0))),
        )
    if plan.packs:
        # Each feature's sums from its own one-hot rows: a packed
        # feature's bins past its count are exact zeros, as a wide
        # feature's are.
        wide_acc, pack_acc = hist
        sums = {f: wide_acc[i] for i, f in enumerate(plan.wide)}
        for p, pack in enumerate(plan.packs):
            for f, first, count in pack:
                sums[f] = jnp.pad(
                    pack_acc[p, first:first + count],
                    ((0, B - count), (0, 0)),
                )
        hist = jnp.stack([sums[f] for f in range(F)])
    # A column's slabs, smallest piece first: the f32 sums of three
    # slabs lose no more in this fold than one accumulator of whole
    # values would; a one-piece column is its one slab. A column that
    # equals another takes that column's sums.
    hist = hist.reshape(F, B, len(blocks), L)
    folded = {
        c: functools.reduce(
            jnp.add,
            [hist[:, :, blocks.index((p, c))] for p in reversed(range(count))],
        )
        for c, count in enumerate(counts) if count
    }
    hist = jnp.stack(
        [
            folded[c if col.same_as is None else col.same_as]
            for c, col in enumerate(columns)
        ],
        axis=2,
    )  # [F, B, S, L]
    # Returned in the ACCUMULATOR dtype (int32 for int8 stats — a cast
    # back to int8 would wrap); the _histogram_jit wrapper owns the final
    # output-dtype contract.
    return jnp.transpose(hist, (3, 0, 1, 2))  # [L, F, B, S]


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
        "num_slots", "num_bins", "impl", "chunk", "quant", "compact",
        "stat_columns", "feature_num_bins",
    ),
)
def _histogram_jit(
    bins, slot, stats, quant_scale, num_slots, num_bins, impl, chunk,
    quant, compact, stat_columns=None, feature_num_bins=None,
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
            # Only an f32 operand has pieces to leave out: the other
            # modes' operands, and the other impls, take no description
            # of the stats; no other impl takes the bins' counts.
            out = _histogram_matmul(
                bins_d, slot_d, stats_q, num_slots, num_bins, chunk,
                stat_columns, feature_num_bins,
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
    stat_columns: Optional[Tuple[StatColumn, ...]] = None,
    feature_num_bins: Optional[Tuple[int, ...]] = None,
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
    full-row fallback when they don't fit). `stat_columns`, one
    StatColumn a column of f32 `stats`, lets the matmul impl leave the
    bf16 pieces that are zero by construction off the MXU; the sums are
    the same bit for bit, and every other impl and mode ignores it.
    `feature_num_bins`, the bin count of each of the first features
    (every bin of feature f is below its count), lets the matmul impl
    give the features of at most 128 bins one-hot tiles they share
    (onehot_plan); the sums are the same bit for bit, and every other
    impl ignores it."""
    return _histogram_jit(
        bins, slot, stats, quant_scale, num_slots, num_bins,
        resolve_hist_impl(impl), chunk, resolve_hist_quant(quant),
        compact, stat_columns, feature_num_bins,
    )
