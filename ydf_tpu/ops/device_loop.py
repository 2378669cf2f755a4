"""Device-resident boosting loop: multi-tree donated-carry dispatch.

The boosting drivers in learners/gbt.py already run the loop as a
`lax.scan` over chunks of trees (`run_chunk`), but every chunk used to
re-enter a plain jit: the carry (forest arrays, train/valid preds,
per-iteration losses, PRNG key) was COPIED on entry because XLA could
not alias the previous chunk's output buffers into the next chunk's
inputs. This module is the driver seam that closes ROADMAP item 3(b)'s
host-traffic half — the whole-loop-on-accelerator design of
XGBoost-GPU (PAPERS.md 1806.11248) and large-scale GPU tree boosting
(PAPERS.md 1706.08359), both of which attribute their headline wins to
eliminating per-iteration host round trips:

* **Donated carry** — one compiled chunk executable per boost
  function with `donate_argnums=(0,)`: the carry buffers are handed
  back to XLA at every dispatch, so forest arrays, preds, losses and
  the PRNG key stay device-resident across the whole train with zero
  carry copies. Donation changes buffer aliasing only, never numerics
  — the chunked drivers stay bit-identical to the single-scan run
  (tests/test_device_loop.py proves it across quant modes).
* **`YDF_TPU_TREES_PER_DISPATCH`** — how many trees one XLA dispatch
  grows. Default = the chunk size the calling driver already uses
  (the early-stop look-ahead window, or the snapshot interval), so
  host sync happens exactly where early stopping, snapshots, and
  telemetry already live: at chunk boundaries. Setting it to 1
  recovers a per-tree dispatch driver — the paired A/B baseline
  bench.py measures the win against.
* **One compile cache keyed on the static loop shape** — the chunk
  executable is ONE cached jit whose only static argument is
  `chunk_len`; resuming a checkpointed train with a different
  trees-per-dispatch (or alternating exact-tail DART chunks) reuses
  every previously compiled loop shape instead of rebuilding the jit
  wrapper and retracing `_grow_tree_jit` underneath it
  (tests/test_device_loop.py has the retrace regression).
* **Host-sync accounting** — every dispatch, every byte the drivers
  materialize on host at a chunk boundary and every byte train() sends
  to the device is counted here, so bench.py can emit
  `dispatches_per_tree` / `host_sync_bytes_per_tree` on headline
  records and docs/device_loop.md can inventory the remaining
  host-sync points instead of hand-waving them. The same boundaries
  are spans (`ydf.device_loop.*`, utils/profiling.py): a span gives
  the time, a counter the count or the bytes.
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
leaf updates) lives in learners/gbt.py:_make_boost_fn — this module
only owns HOW that body is dispatched.
"""

from __future__ import annotations

import functools
import os
import threading
import weakref
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ydf_tpu.ops import lookup
from ydf_tpu.utils import telemetry
from ydf_tpu.utils.profiling import StageTimer

__all__ = [
    "trees_per_dispatch",
    "chunk_fn",
    "dispatch",
    "run_chunk",
    "count_dispatch",
    "count_host_sync",
    "count_h2d",
    "reset_stats",
    "stats_snapshot",
]


def trees_per_dispatch(default: Optional[int] = None) -> Optional[int]:
    """Resolves YDF_TPU_TREES_PER_DISPATCH: how many trees one XLA
    dispatch grows. `default` is the calling driver's own chunk size
    (early-stop look-ahead window / snapshot interval) — returned
    unchanged when the knob is unset, so the env var only ever MOVES
    the host-sync boundary the driver already has. Validated eagerly
    like every YDF_TPU_* knob (config.resolved_env_config): a typo
    raises here, not as a silent perf cliff mid-train."""
    raw = os.environ.get("YDF_TPU_TREES_PER_DISPATCH")
    if raw is None or raw == "":
        return default
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"YDF_TPU_TREES_PER_DISPATCH={raw!r} is not an integer"
        ) from None
    if v < 1:
        raise ValueError(
            f"YDF_TPU_TREES_PER_DISPATCH must be >= 1, got {v}"
        )
    return v


# --------------------------------------------------------------------------
# Compiled-chunk cache: one donated jit per boost function.
# --------------------------------------------------------------------------

# id(run.run_chunk) -> (weakref to run.run_chunk, donated jit). Keyed by
# identity because _make_boost_fn's lru_cache already dedupes equal
# configurations to one `run`; the weakref guards against id reuse after
# a cache eviction. chunk_len stays a static argument INSIDE the one
# cached jit — that is the whole retrace fix: a resume that changes
# trees_per_dispatch mid-run compiles the new loop shape once and every
# previously seen shape (including the original) stays hot.
_CHUNK_CACHE: Dict[int, Any] = {}
_CACHE_LOCK = threading.Lock()


def chunk_fn(run):
    """The donated-carry compiled chunk executable for `run` (a
    _make_boost_fn result). Builds `jax.jit(run_chunk_impl,
    static_argnames=("chunk_len",), donate_argnums=(0,))` once per run
    and caches it — argnum 0 is the carry, so every dispatch hands the
    previous chunk's forest/preds/losses/key buffers back to XLA for
    in-place reuse."""
    inner = run.run_chunk.__wrapped__
    key = id(run.run_chunk)
    with _CACHE_LOCK:
        entry = _CHUNK_CACHE.get(key)
        if entry is not None:
            ref, fn = entry
            if ref() is run.run_chunk:
                return fn
        fn = jax.jit(
            inner, static_argnames=("chunk_len",), donate_argnums=(0,)
        )
        _CHUNK_CACHE[key] = (weakref.ref(run.run_chunk), fn)
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
    """Calls `fn`, a jitted boosting program (a `run`, or the donated
    chunk function of one), and returns what it returns. The first call
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


def run_chunk(run, carry, start, chunk_len, *data_args,
              timer: Optional[StageTimer] = None, **data_kwargs):
    """One device dispatch growing `chunk_len` trees: iterations
    [start, start + chunk_len) of the boosting loop, with the carry
    donated. Drop-in for `run.run_chunk` (learners/gbt.py routes its
    early-stop and checkpointed drivers through here) — bit-identical
    by construction: the per-iteration RNG folds the absolute iteration
    index into the carried key, so neither the chunk boundary nor the
    buffer donation can change a single bit of the result.

    The donated carry is dead after the call — callers must use the
    returned carry (the drivers already do; they snapshot/fetch carry
    state only AFTER each chunk). `timer` is the calling train()'s: the
    host's time to enqueue the chunk is its `device_loop.dispatch`."""
    fn = chunk_fn(run)
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
    measurement window (bench.py resets around each train). Separate
    from the telemetry registry so the bench can read exact per-train
    numbers with telemetry off; the telemetry counters below feed the
    always-on dashboards."""

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
    """Starts a fresh measurement window (bench.py, tests)."""
    _STATS.reset()


def count_dispatch(trees: int) -> None:
    """Records one XLA dispatch of the boosting loop covering `trees`
    iterations (the single-scan driver counts its one dispatch here
    too, so `dispatches_per_tree` is comparable across drivers)."""
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
    """The current window's counters plus the derived per-tree rates
    bench.py puts on headline records. `device_loop` is the largest
    trees-per-dispatch observed in the window (0 = no training ran)."""
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
