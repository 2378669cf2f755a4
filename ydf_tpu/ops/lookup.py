"""Row-indexed look-ups in small tables, without a gather.

Routing asks, for every row, for one value out of a small table: the
row's bin in the column its node splits on (a row of `F` bins), or its
slot's or node's decision (a table of `Ld` or `N` entries). XLA:TPU runs
such a look-up as a gather, at 7 to 18 ns a row whatever the table's
size past a few dozen entries (PERF.md section 6, PR 29). The same
answer comes from compares and selects that stream:

* `pick_column(bins, f)`: a compare of `f` with every column's index,
  a select and a `max` over the columns: one fused pass over `bins`.
* `lookup_small(table, idx, size, fill)`: a tree of `size - 1` selects
  over the bits of `idx`, each leaf one scalar of the table:
  element-wise over the rows, so it fuses with whatever consumes it.
* `lookup_mask_bit(masks, idx, size, bit)`: one bit of a packed mask
  row, through `lookup_small` over the flat words.

A select copies bits, so each equals the gather it replaces, bit for
bit. The dense form costs rows x size, the gather rows: each helper
takes the dense form at or under a static size (`DENSE_COLUMNS_MAX`,
`DENSE_TABLE_MAX`) and the gather above it. The callers' `dense`
argument (None: by size) is the seam of the tests and of `grow_tree`,
which keeps a CPU on the gather.

`counts()` says how many look-ups went each way since the process
started. They are counted while tracing, so `ops/device_loop.dispatch`
reads them around a program's build and keeps them with the program
(`training_profile["device_loop.route_select"]`, `.route_gather`).
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ydf_tpu.config import is_tpu_backend

__all__ = [
    "DENSE_COLUMNS_MAX",
    "DENSE_TABLE_MAX",
    "pick_column",
    "lookup_small",
    "lookup_mask_bit",
    "resolve_dense",
    "counts",
    "count",
]

# The static sizes up to which the dense form is taken (TPU v5e, a
# level's look-up alone, PERF.md section 6, PR 29). Columns: the gather
# reads 12.6 to 13.1 ns a row at any width, the dense pick 0.21, 0.31,
# 1.11 and 4.27 ns at 28, 100, 512 and 2048 columns: the widest read is
# the limit. Entries: the gather reads 6.8 to 9.8 ns a row from 128
# entries up (0.05 at 32, where the compiler does as well itself), the
# selects 0.05, 0.07, 0.12, 0.40 and 0.81 ns at 32, 128, 256, 1024 and
# 2048, so they win at every size read; but a table's selects compile
# in 1.5, 2.7, 10.2 and 17.9 s at 128, 256, 1024 and 2048 entries
# against 0.3 s, once for each table and level of a program, and the
# limit sits where that stays seconds.
DENSE_COLUMNS_MAX = 2048
DENSE_TABLE_MAX = 256

_COUNTS = {"select": 0, "gather": 0}


def counts(since: Tuple[int, int] = (0, 0)) -> Tuple[int, int]:
    """(look-ups traced dense, look-ups traced as gathers) so far, or
    since an earlier reading."""
    return _COUNTS["select"] - since[0], _COUNTS["gather"] - since[1]


def count(select: int, gather: int) -> None:
    """Adds look-ups that were traced but not by this call: a loop body
    that runs `k` times counts `k - 1` times more, a jitted function
    whose trace JAX served from its cache counts what it counted when
    it was traced."""
    _COUNTS["select"] += select
    _COUNTS["gather"] += gather


def resolve_dense(value: Union[str, bool, None] = "auto") -> Optional[bool]:
    """The `dense` the helpers take, from what `grow_tree` and
    `route_tree_bins` are given: "auto" is by size (None) on a TPU and
    the gather (False) elsewhere: a CPU gathers as fast as it selects
    and compiles the select chains slower (PERF.md section 6, PR 29).
    Anything else is the caller's own choice."""
    if value == "auto":
        return None if is_tpu_backend() else False
    return value


def pick_column(
    bins: jax.Array, f: jax.Array, dense: Optional[bool] = None
) -> jax.Array:
    """`bins[row, f[row]]` for `bins` [n, F] and `f` [n] in [0, F):
    `take_along_axis(bins, f[:, None], 1)[:, 0]`, for integer `bins`."""
    F = bins.shape[1]
    if dense is None:
        dense = F <= DENSE_COLUMNS_MAX
    count(int(dense), int(not dense))
    if not dense:
        return jnp.take_along_axis(
            bins, f[:, None].astype(jnp.int32), axis=1
        )[:, 0]
    hit = f[:, None] == jnp.arange(F, dtype=f.dtype)[None, :]
    return jnp.max(
        jnp.where(hit, bins, jnp.iinfo(bins.dtype).min), axis=1
    )


def lookup_small(
    table: jax.Array, idx: jax.Array, size: int, fill,
    dense: Optional[bool] = None,
) -> jax.Array:
    """`table[idx]` where `idx` is in [0, size), else `fill`: what a
    gather from `table[:size]` padded with `fill` gives for every index
    the caller can produce (the grower's rows hold a slot below `Ld` or
    the retired slot `L`, whose answer is the pad's value: the caller
    names it). `table` is one-dimensional with at least `size` entries;
    `size` is static."""
    if dense is None:
        dense = size <= DENSE_TABLE_MAX
    count(int(dense), int(not dense))
    fill = jnp.asarray(fill, table.dtype)
    inside = (idx >= 0) & (idx < size)
    if not dense:
        return jnp.where(
            inside, table[jnp.clip(idx, 0, max(size - 1, 0))], fill
        )
    # A tree of selects over the index's bits, least first: size - 1
    # selects a row and as many levels as the index has bits.
    bits = max(size - 1, 0).bit_length()
    values = [table[s] if s < size else fill for s in range(1 << bits)]
    for b in range(bits):
        odd = ((idx >> b) & 1) == 1
        values = [
            jnp.where(odd, values[2 * j + 1], values[2 * j])
            for j in range(len(values) // 2)
        ]
    return jnp.where(inside, values[0], fill)


def lookup_mask_bit(
    masks: jax.Array, idx: jax.Array, size: int, bit: jax.Array,
    dense: Optional[bool] = None,
) -> jax.Array:
    """Bit `bit` of the packed mask `masks[idx]` (uint32 [>= size, W],
    32 bits a word), False where `idx` is outside [0, size): the one
    word that holds the bit comes out of the flat table by
    `lookup_small`, never a row of `W` words a row."""
    W = masks.shape[1]
    word = lookup_small(
        masks.reshape(-1), idx * W + (bit >> 5), size * W, 0, dense
    )
    return ((word >> (bit & 31).astype(jnp.uint32)) & 1).astype(jnp.bool_)
