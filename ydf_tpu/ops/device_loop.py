"""Device-resident boosting loop: multi-tree donated-carry dispatch.

learners/gbt.py runs the boosting loop as one host loop over chunks of
trees (`_train_gbt`), each chunk a `lax.scan` in one jitted program
(`_BoostFns.run_chunk`). This module owns HOW a chunk is dispatched and
what is counted at its boundary — the whole-loop-on-accelerator design
of XGBoost-GPU (PAPERS.md 1806.11248) and large-scale GPU tree boosting
(PAPERS.md 1706.08359), both of which attribute their headline wins to
eliminating per-iteration host round trips:

* **Donated carry** — one compiled chunk executable per boost
  function with `donate_argnums=(0,)`: the carry buffers (train/valid
  preds, PRNG key, DART state) are handed back to XLA at every
  dispatch, so they stay device-resident across the whole train with
  zero carry copies. Donation changes buffer aliasing only, never
  numerics, and the per-iteration RNG folds the absolute iteration
  index into the carried key — so a chunk boundary changes no bit of
  the forest (tests/test_device_loop.py proves it across quant modes).
* **The chunk length is the caller's** — `_train_gbt` works it out from
  what can end the loop (learners/gbt.py:_trees_per_chunk): the
  snapshot interval under a working_dir, the early-stop look-ahead
  window when stopping can fire or a deadline is set, else all the
  trees in one dispatch. Host sync happens exactly where early
  stopping, snapshots and telemetry live: at chunk boundaries.
* **One compile cache keyed on the static loop shape** — the chunk
  executable is ONE cached jit whose only static argument is
  `chunk_len`; a DART train's exact tail chunk, or a resume under
  another snapshot interval, compiles the new loop shape once and
  reuses every previously compiled one instead of rebuilding the jit
  wrapper and retracing `_grow_tree_jit` underneath it
  (tests/test_device_loop.py has the retrace regression).
* **Host-sync accounting** — every dispatch, every byte the loop
  materializes on host at a chunk boundary and every byte train() sends
  to the device is counted here (`stats_snapshot`: the benchmark's
  `dispatches_per_tree`, `host_sync_bytes_per_tree`,
  `h2d_bytes_per_tree`; docs/device_loop.md inventories the host-sync
  points). The same boundaries are spans (`ydf.device_loop.*`,
  utils/profiling.py): a span gives the time, a counter the count or
  the bytes.
* **The program's build** — the first dispatch of a loop shape traces,
  lowers and compiles it (or loads it from the persistent cache):
  `dispatch` makes that call the span `device_loop.compile` and
  remembers its seconds with the compiled function, so that every
  later job can say what the program it ran cost
  (`training_profile["device_loop.program_build_s"]`) and how its
  routing looks its tables up (`device_loop.route_select`,
  `device_loop.route_gather`).

The scan body itself (gradient recompute, per-tree quantization grid,
routing, histogram, gain/argmax via the shared grower seams
`prepare_stats_for_hist` / `layer_decide` / `sibling_reconstruct`, and
leaf updates) lives in learners/gbt.py:_make_boost_fn.
"""

from __future__ import annotations

import functools
import threading
import weakref
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ydf_tpu.ops import lookup
from ydf_tpu.utils import telemetry
from ydf_tpu.utils.profiling import StageTimer

__all__ = [
    "chunk_fn",
    "dispatch",
    "run_chunk",
    "count_dispatch",
    "count_host_sync",
    "count_h2d",
    "reset_stats",
    "stats_snapshot",
]


# --------------------------------------------------------------------------
# Compiled-chunk cache: one donated jit per boost function.
# --------------------------------------------------------------------------

# id(boost.run_chunk) -> (weakref to boost.run_chunk, donated jit). Keyed
# by identity because _make_boost_fn's lru_cache already dedupes equal
# configurations to one `_BoostFns`; the weakref guards against id reuse
# after a cache eviction. chunk_len stays a static argument INSIDE the
# one cached jit — that is the whole retrace fix: a new chunk length
# compiles its loop shape once and every previously seen shape stays
# hot.
_CHUNK_CACHE: Dict[int, Any] = {}
_CACHE_LOCK = threading.Lock()


def chunk_fn(boost):
    """The donated-carry compiled chunk executable for `boost` (a
    _make_boost_fn result). Builds `jax.jit(run_chunk_impl,
    static_argnames=("chunk_len",), donate_argnums=(0,))` once per
    `boost` and caches it — argnum 0 is the carry, so every dispatch
    hands the previous chunk's preds/key buffers back to XLA for
    in-place reuse."""
    inner = boost.run_chunk.__wrapped__
    key = id(boost.run_chunk)
    with _CACHE_LOCK:
        entry = _CHUNK_CACHE.get(key)
        if entry is not None:
            ref, fn = entry
            if ref() is boost.run_chunk:
                return fn
        fn = jax.jit(
            inner, static_argnames=("chunk_len",), donate_argnums=(0,)
        )
        _CHUNK_CACHE[key] = (weakref.ref(boost.run_chunk), fn)
        return fn


@functools.lru_cache(maxsize=None)
def _cache_hits() -> list:
    """[times JAX's persistent compile cache has answered since the
    first call], kept by one listener (jax.monitoring has no public way
    to take a listener off again)."""
    hits = [0]

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    return hits


def dispatch(fn, timer: StageTimer, *args, **kwargs):
    """Calls `fn`, a jitted boosting program (the donated chunk function
    of a `_BoostFns`), and returns what it returns. The first call
    with a signature builds the program: that call is the span
    `device_loop.compile`, and its seconds, whether the persistent
    cache answered, and how many of the routing's per-row look-ups were
    traced as compare-and-select passes and how many as gathers
    (ops/lookup.py counts them where they are traced) are remembered
    with `fn` for as long as it lives. Every call notes on `timer`
    which program the job ran."""
    signature = tuple(
        (x.shape, str(x.dtype)) if hasattr(x, "shape") else x
        for x in jax.tree.leaves((args, kwargs))
    )
    builds = fn.__dict__.setdefault("_program_builds", {})
    if signature in builds:
        out = fn(*args, **kwargs)
    else:
        hits = _cache_hits()
        hits0 = hits[0]
        spent0 = timer.seconds.get("device_loop.compile", 0.0)
        lookups0 = lookup.counts()
        with timer.stage("device_loop.compile"):
            out = fn(*args, **kwargs)
        builds[signature] = (
            timer.seconds["device_loop.compile"] - spent0, hits[0] > hits0,
            *lookup.counts(since=lookups0),
        )
    timer.programs[(id(fn), signature)] = builds[signature]
    return out


def run_chunk(boost, carry, start, chunk_len, *data_args,
              timer: Optional[StageTimer] = None, **data_kwargs):
    """One device dispatch growing `chunk_len` trees: iterations
    [start, start + chunk_len) of the boosting loop, with the carry
    donated. Drop-in for `boost.run_chunk` — bit-identical by
    construction: the per-iteration RNG folds the absolute iteration
    index into the carried key, so neither the chunk boundary nor the
    buffer donation can change a single bit of the result.

    The donated carry is dead after the call — callers must use the
    returned carry (learners/gbt.py:_train_gbt snapshots and fetches
    carry state only AFTER each chunk). `timer` is the calling
    train()'s: the host's time to enqueue the chunk is its
    `device_loop.dispatch`."""
    fn = chunk_fn(boost)
    timer = timer or StageTimer()
    with timer.stage("device_loop.dispatch"):
        new_carry, ys = dispatch(
            fn, timer, carry, jnp.asarray(start), chunk_len, *data_args,
            **data_kwargs
        )
    count_dispatch(chunk_len)
    return new_carry, ys


# --------------------------------------------------------------------------
# Host-sync accounting (the measurement side of the tentpole).
# --------------------------------------------------------------------------


class _Stats:
    """Process-wide dispatch/host-sync counters for the CURRENT
    measurement window (the benchmark resets at its start). Separate
    from the telemetry registry so a measurement reads exact numbers
    with telemetry off; the telemetry counters below feed the always-on
    dashboards."""

    __slots__ = (
        "dispatches", "trees", "host_sync_bytes", "h2d_bytes", "chunk_len"
    )

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.dispatches = 0
        self.trees = 0
        self.host_sync_bytes = 0
        self.h2d_bytes = 0
        self.chunk_len = 0


_STATS = _Stats()


def reset_stats() -> None:
    """Starts a fresh measurement window."""
    _STATS.reset()


def count_dispatch(trees: int) -> None:
    """Records one XLA dispatch of the boosting loop covering `trees`
    iterations."""
    _STATS.dispatches += 1
    _STATS.trees += int(trees)
    _STATS.chunk_len = max(_STATS.chunk_len, int(trees))
    if telemetry.ENABLED:
        telemetry.counter("ydf_train_dispatches_total").inc(1)


def count_host_sync(nbytes: int) -> None:
    """Records bytes materialized on host at a chunk boundary (the
    per-chunk tree/leaf/loss payload fetch in
    learners/gbt.py:_chunk_arrays_from_ys, snapshot carry fetches,
    ...). This is the host←device half of the sync; `count_h2d` has
    the other."""
    _STATS.host_sync_bytes += int(nbytes)
    if telemetry.ENABLED:
        telemetry.counter("ydf_train_host_sync_bytes_total").inc(
            int(nbytes)
        )


def count_h2d(nbytes: int) -> None:
    """Records bytes train() sends host→device: the bin matrices,
    labels and weights of the training and validation rows, once per
    train() (learners/gbt.py, the `device_loop.h2d` span). Nothing is
    sent after that: every input stays device-resident for the whole
    train."""
    _STATS.h2d_bytes += int(nbytes)
    if telemetry.ENABLED:
        telemetry.counter("ydf_train_h2d_bytes_total").inc(int(nbytes))


def stats_snapshot() -> Dict[str, float]:
    """The current window's counters plus the derived per-tree rates.
    `device_loop` is the largest trees-per-dispatch observed in the
    window (0 = no training ran)."""
    trees = max(_STATS.trees, 1)
    return {
        "dispatches": _STATS.dispatches,
        "trees": _STATS.trees,
        "host_sync_bytes": _STATS.host_sync_bytes,
        "h2d_bytes": _STATS.h2d_bytes,
        "device_loop": _STATS.chunk_len,
        "dispatches_per_tree": round(_STATS.dispatches / trees, 6),
        "host_sync_bytes_per_tree": round(
            _STATS.host_sync_bytes / trees, 1
        ),
    }
