"""Speed-ranked serving-engine registry + the request-coalescing batcher.

Counterpart of the reference's FastEngineFactory registry
(`ydf/serving/decision_forest/register_engines.cc:172-875`: per model
type, every engine declares IsCompatible() and a speed rank; BuildFastEngine
picks the fastest compatible one). Here an engine factory is a small
dataclass; registration is module-level; `best_engine(model)` returns the
highest-ranked compatible factory and models expose
`list_compatible_engines()` / `force_engine(name)` like the reference's
PYDF API (`model/generic_model.py` same-named methods).

The generic routed engine (ops/routing.py value-mode scan) is rank 0 and
compatible with everything — it is the fallback the reference calls the
"generic engine". Above it: the native batched data-bank engine
(serving/native_serve.py, rank 200, the CPU production path), the
Pallas data-bank scorer (serving/pallas_scorer.py, rank 250, TPU) and
QuickScorer (rank 300, TPU / forced).

Serving env knobs are validated EAGERLY AT IMPORT (the
YDF_TPU_HIST_IMPL / failpoints contract — a typo must fail at the env
boundary, never silently fall back to the generic engine):

  * YDF_TPU_SERVE_IMPL={auto|xla|native} — engine-impl switch mirroring
    YDF_TPU_ROUTE_IMPL: "auto" prefers the native engine when built,
    "xla" pins the XLA paths (generic / QuickScorer), "native" demands
    the native kernel (registers-or-raises at engine build).
  * YDF_TPU_FORCE_QUICKSCORER={0|1} — CPU QuickScorer gate (tests).
  * YDF_TPU_SERVE_MAX_BATCH (int >= 1, default 256) and
    YDF_TPU_SERVE_BATCH_TIMEOUT_US (float > 0, default 2000) — the
    request-coalescing batcher's size/deadline bounds.
  * YDF_TPU_SERVE_MAX_QUEUE (int >= 0, default 0 = unbounded) — the
    batcher's pending-row bound: a submit beyond it is REJECTED with
    ServeOverloadError(reason="queue_full") instead of growing the
    queue without limit (overload degrades p99, never OOMs).
  * YDF_TPU_SERVE_MAX_QUEUE_BYTES (int >= 0, default 0 = off) — the
    admission signal: a submit whose row would push the MemoryLedger's
    `serve_batcher` gauge past this bound is rejected with
    reason="admission".
  * YDF_TPU_SERVE_DEADLINE_US (float >= 0, default 0 = off) — per-row
    deadline: rows older than this at flush time are shed with
    reason="deadline" instead of being served late.
  * YDF_TPU_TRACE_SAMPLE (float in [0, 1], default 0) — per-request
    journey-tracing sample rate. 0 keeps the exact zero-overhead
    singleton span path; a sampled request records the chain
    serve.request → batcher.enqueue (caller thread) and
    batcher.flush → serve.kernel → batcher.fanout (flusher thread),
    linked by a shared `req` id and carrying queue-age/batch labels.

Sheds are counted in ydf_serve_shed_total{reason} and mirrored into a
telemetry-independent module total for /statusz (docs/serving.md
"Serving under load").
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import random
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional


# --------------------------------------------------------------------- #
# Serving env knobs — eager validation at import
# --------------------------------------------------------------------- #

_SERVE_IMPLS = ("auto", "xla", "native")


def resolve_serve_impl(value: Optional[str] = None) -> str:
    """Resolves the serving-impl switch. An explicit value wins;
    YDF_TPU_SERVE_IMPL selects globally; default is "auto" (fastest
    compatible engine, native preferred when built). Invalid values
    raise — here AND at registry import."""
    if value is None:
        value = os.environ.get("YDF_TPU_SERVE_IMPL")
    if value is None:
        return "auto"
    low = value.strip().lower()
    if low not in _SERVE_IMPLS:
        raise ValueError(
            f"YDF_TPU_SERVE_IMPL={value!r} is not a serving impl; "
            f"expected one of {list(_SERVE_IMPLS)}"
        )
    return low


def _parse_serve_max_batch() -> int:
    env = os.environ.get("YDF_TPU_SERVE_MAX_BATCH")
    if env is None:
        return 256
    try:
        v = int(env)
    except ValueError:
        v = 0
    if v < 1:
        raise ValueError(
            f"YDF_TPU_SERVE_MAX_BATCH={env!r} must be an integer >= 1"
        )
    return v


def _parse_serve_batch_timeout_us() -> float:
    env = os.environ.get("YDF_TPU_SERVE_BATCH_TIMEOUT_US")
    if env is None:
        return 2000.0
    try:
        v = float(env)
    except ValueError:
        v = -1.0
    if v <= 0:
        raise ValueError(
            f"YDF_TPU_SERVE_BATCH_TIMEOUT_US={env!r} must be a number > 0"
        )
    return v


def _parse_force_quickscorer() -> None:
    env = os.environ.get("YDF_TPU_FORCE_QUICKSCORER")
    if env is not None and env not in ("", "0", "1"):
        raise ValueError(
            f"YDF_TPU_FORCE_QUICKSCORER={env!r} must be 0 or 1 (or unset)"
        )


def _parse_serve_max_queue() -> int:
    env = os.environ.get("YDF_TPU_SERVE_MAX_QUEUE")
    if env is None:
        return 0
    try:
        v = int(env)
    except ValueError:
        v = -1
    if v < 0:
        raise ValueError(
            f"YDF_TPU_SERVE_MAX_QUEUE={env!r} must be an integer >= 0 "
            "(0 = unbounded)"
        )
    return v


def _parse_serve_max_queue_bytes() -> int:
    env = os.environ.get("YDF_TPU_SERVE_MAX_QUEUE_BYTES")
    if env is None:
        return 0
    try:
        v = int(env)
    except ValueError:
        v = -1
    if v < 0:
        raise ValueError(
            f"YDF_TPU_SERVE_MAX_QUEUE_BYTES={env!r} must be an integer "
            ">= 0 (0 = no admission bound)"
        )
    return v


def _parse_serve_deadline_us() -> float:
    env = os.environ.get("YDF_TPU_SERVE_DEADLINE_US")
    if env is None:
        return 0.0
    try:
        v = float(env)
    except ValueError:
        v = -1.0
    if v < 0:
        raise ValueError(
            f"YDF_TPU_SERVE_DEADLINE_US={env!r} must be a number >= 0 "
            "(0 = no deadline)"
        )
    return v


def resolve_trace_sample(value: Optional[object] = None) -> float:
    """Resolves the journey-tracing sample rate: a float in [0, 1].
    An explicit value wins; YDF_TPU_TRACE_SAMPLE selects globally;
    default 0 (no sampling — the exact zero-overhead span path).
    Invalid values raise — here AND at registry import."""
    if value is None:
        value = os.environ.get("YDF_TPU_TRACE_SAMPLE")
    if value is None:
        return 0.0
    try:
        v = float(value)
    except (TypeError, ValueError):
        v = -1.0
    if not 0.0 <= v <= 1.0:
        raise ValueError(
            f"YDF_TPU_TRACE_SAMPLE={value!r} must be a sampling rate "
            "in [0, 1]"
        )
    return v


# Import-time eager parse: a malformed serving knob fails the first
# `import ydf_tpu.serving.registry` of the process, not a predict call
# hours into serving (the YDF_TPU_HIST_IMPL / failpoints contract).
SERVE_IMPL = resolve_serve_impl()
SERVE_MAX_BATCH = _parse_serve_max_batch()
SERVE_BATCH_TIMEOUT_US = _parse_serve_batch_timeout_us()
SERVE_MAX_QUEUE = _parse_serve_max_queue()
SERVE_MAX_QUEUE_BYTES = _parse_serve_max_queue_bytes()
SERVE_DEADLINE_US = _parse_serve_deadline_us()
TRACE_SAMPLE = resolve_trace_sample()
_parse_force_quickscorer()


class ServeOverloadError(RuntimeError):
    """A request shed by the serving overload policy. `reason` names
    the shed cause — "queue_full" (the bounded queue rejected the
    submit), "admission" (the MemoryLedger `serve_batcher` gauge is
    past YDF_TPU_SERVE_MAX_QUEUE_BYTES), or "deadline" (the row aged
    past YDF_TPU_SERVE_DEADLINE_US before its flush, or an injected
    `serve.flush` failpoint simulated exactly that). Callers fail FAST:
    a shed is the overload policy working, not a serving fault — retry
    against another replica or surface the rejection."""

    def __init__(self, message: str, reason: str):
        super().__init__(message)
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class EngineFactory:
    """One serving engine: higher rank = preferred when compatible
    (the reference factories are enumerated in speed order the same
    way)."""

    name: str
    rank: int
    is_compatible: Callable[[object], bool]
    build: Callable[[object], object]  # model -> engine or None


_REGISTRY: List[EngineFactory] = []


def register_engine(factory: EngineFactory) -> None:
    _REGISTRY.append(factory)
    _REGISTRY.sort(key=lambda f: -f.rank)


def list_engines() -> List[EngineFactory]:
    return list(_REGISTRY)


def compatible_engines(model) -> List[EngineFactory]:
    """Compatible factories, fastest first. `is_compatible` answers the
    envelope question with True/False; anything it raises (a compiler
    refusing a kernel, a bad override) propagates instead of quietly
    changing the engine."""
    return [f for f in _REGISTRY if f.is_compatible(model)]


# Last engine selection + live batchers — the /statusz "serving"
# section (utils/telemetry_http.py). Tracking is a dict store / weak
# add per selection or batcher construction, independent of telemetry.
_LAST_ENGINE = {"engine": None, "forced": False}
_BATCHERS: "weakref.WeakSet[CoalescingBatcher]" = weakref.WeakSet()
#: Guards _BATCHERS iteration vs concurrent construction/GC: a bare
#: WeakSet raises "Set changed size during iteration" when a batcher
#: is added (or a dead one collected) while the ledger pull source or
#: /statusz walks it.
_BATCHERS_LOCK = threading.Lock()


def _live_batchers() -> "List[CoalescingBatcher]":
    with _BATCHERS_LOCK:
        return list(_BATCHERS)

#: Shed accounting independent of telemetry (the /statusz serving
#: section must say how much was shed even on a telemetry-off host);
#: ydf_serve_shed_total{reason} mirrors it into the registry when
#: telemetry is on.
_SHED_TOTALS: Dict[str, int] = {}
_SHED_LOCK = threading.Lock()

#: The most recent load-run summary (serving/loadgen.py posts it) —
#: the /statusz serving section's "what did the last load test say".
_LAST_LOAD_RUN: Dict[str, Optional[dict]] = {"record": None}

#: Sampled-request id source for the journey-trace span chain (the
#: `req` label linking caller-thread and flusher-thread spans).
_REQ_IDS = itertools.count(1)
#: Sampling decisions need no statistical independence from anything —
#: a module PRNG keeps them cheap and reproducible enough.
_TRACE_RNG = random.Random(0x5EED)


def _note_shed(reason: str, n: int = 1) -> None:
    from ydf_tpu.utils import telemetry

    with _SHED_LOCK:
        _SHED_TOTALS[reason] = _SHED_TOTALS.get(reason, 0) + n
    if telemetry.ENABLED:
        telemetry.counter("ydf_serve_shed_total", reason=reason).inc(n)


def shed_totals() -> Dict[str, int]:
    """Process-lifetime shed counts by reason (telemetry-independent)."""
    with _SHED_LOCK:
        return dict(_SHED_TOTALS)


def note_load_run(record: dict) -> None:
    """Stores the latest load-run summary (serving/loadgen.py calls it
    at the end of every run) for the /statusz serving section."""
    _LAST_LOAD_RUN["record"] = dict(record)
    _register_serving_status()


def batcher_queue_bytes() -> int:
    """Bytes of rows currently queued in live CoalescingBatchers — the
    "serve_batcher" row of the memory ledger (pull source: sampled at
    snapshot time only, never on the predict_one hot path) and the
    admission signal YDF_TPU_SERVE_MAX_QUEUE_BYTES is checked against.
    Reads each batcher's byte counter — maintained under the batcher's
    lock at enqueue/dequeue — NEVER iterating `_queue` itself (a
    concurrent flush mutates the list mid-iteration). Scalars count
    their numpy itemsize, plain Python scalars a nominal 8."""
    total = 0
    for b in _live_batchers():
        total += b.queue_bytes()
    return total


def _register_mem_source() -> None:
    from ydf_tpu.utils import telemetry

    telemetry.register_mem_source("serve_batcher", batcher_queue_bytes)


_register_mem_source()


def serving_status() -> dict:
    """The serving process's /statusz section: selected engine, MODEL
    IDENTITY (the forest fingerprint + tree/node/byte counts of every
    live serving bank — which model this process is actually serving;
    the hot-swap verification signal a fleet deploy checks), per-
    batcher queue depth/bytes/bounds, shed totals by reason, and the
    last load-run summary (serving/loadgen.py). Row/flush counters
    (the QPS source) ride /metrics as ydf_serve_batcher_rows_total
    etc."""
    try:
        from ydf_tpu.serving.native_serve import live_banks

        banks = live_banks()
    except Exception:
        banks = []
    return {
        "engine": _LAST_ENGINE["engine"],
        "forced": _LAST_ENGINE["forced"],
        "banks": banks,
        "shed_total": shed_totals(),
        "last_load_run": _LAST_LOAD_RUN["record"],
        "batchers": [
            {
                "depth": len(b._queue),
                "queue_bytes": b.queue_bytes(),
                "max_batch": b.max_batch,
                "max_queue": b.max_queue,
                "max_queue_bytes": b.max_queue_bytes,
                "timeout_us": b.timeout_s * 1e6,
                "deadline_us": b.deadline_ns / 1e3,
                "closed": b._closed,
            }
            for b in _live_batchers()
        ],
    }


def _register_serving_status() -> None:
    from ydf_tpu.utils import telemetry_http

    telemetry_http.register_status("serving", serving_status)


def _note_selected(factory: EngineFactory, forced: bool) -> None:
    from ydf_tpu.utils import telemetry

    _LAST_ENGINE["engine"] = factory.name
    _LAST_ENGINE["forced"] = forced
    _register_serving_status()
    if telemetry.ENABLED:
        telemetry.counter(
            "ydf_serve_engine_selected_total",
            engine=factory.name, forced=str(forced).lower(),
        ).inc()


def best_engine(model, forced: Optional[str] = None) -> EngineFactory:
    if forced is not None:
        for f in _REGISTRY:
            if f.name == forced:
                if not f.is_compatible(model):
                    raise ValueError(
                        f"Engine {forced!r} is not compatible with this "
                        f"model (compatible: "
                        f"{[c.name for c in compatible_engines(model)]})"
                    )
                _note_selected(f, forced=True)
                return f
        raise ValueError(
            f"Unknown engine {forced!r}; registered: "
            f"{[f.name for f in _REGISTRY]}"
        )
    compat = compatible_engines(model)
    if not compat:
        raise RuntimeError("No compatible serving engine (missing routed?)")
    _note_selected(compat[0], forced=False)
    return compat[0]


# --------------------------------------------------------------------- #
# Built-in engines
# --------------------------------------------------------------------- #


def _scalar_sum_forest(model) -> bool:
    """Common QuickScorer envelope: single accumulator, no set/VS
    conditions, encode-time imputation."""
    import numpy as np

    # Geometry of the CURRENT forest, not the model class: multiclass GBT
    # predict temporarily swaps per-class single-output sub-forests in and
    # serves each through the fast engine.
    return (
        getattr(model.binner, "num_set", 0) == 0
        and np.size(getattr(model.forest, "vs_anchor", np.zeros(0))) == 0
        and not getattr(model, "native_missing", False)
        and int(model.forest.leaf_value.shape[-1]) == 1
    )


def _qs_allowed(model) -> bool:
    """QuickScorer engines pay off compiled on TPU; the CPU interpreter
    exists for tests (YDF_TPU_FORCE_QUICKSCORER=1) — same gating the
    pre-registry dispatch used."""
    from ydf_tpu.config import is_tpu_backend

    return (
        is_tpu_backend()
        or os.environ.get("YDF_TPU_FORCE_QUICKSCORER") == "1"
    )


def _qs_compatible(model) -> bool:
    if not (_scalar_sum_forest(model) and _qs_allowed(model)):
        return False
    from ydf_tpu.serving.quickscorer import compile_forest_cached

    # Memoized per forest: build() reuses this exact compile instead of
    # walking every tree a second time.
    return (
        compile_forest_cached(
            model.forest, model.binner.num_numerical,
            num_features=model.binner.num_scalar,
        )
        is not None
    )


def _build_qs(model):
    from ydf_tpu.serving.quickscorer import build_quickscorer

    return build_quickscorer(model)


def _build_routed(model):
    # Sentinel: the routed path lives in GenericModel._raw_scores (it
    # needs the full input tuple, not just x_num/x_cat).
    return None


def _native_compatible(model) -> bool:
    """Native batched data-bank engine (serving/native_serve.py): the
    CPU production path. YDF_TPU_SERVE_IMPL=xla disables it;
    =native claims compatibility for every in-envelope model and lets
    build() raise loudly when the kernel cannot register (the
    no-silent-fallback contract)."""
    from ydf_tpu.config import is_tpu_backend
    from ydf_tpu.serving import native_serve

    impl = resolve_serve_impl()
    if impl == "xla":
        return False
    if not native_serve.in_envelope(model):
        return False
    if impl == "native":
        return True  # build() registers-or-raises
    # auto: a CPU engine — on a TPU backend the compiled kernels win.
    if is_tpu_backend():
        return False
    return native_serve.available()


def _build_native(model):
    from ydf_tpu.serving import native_serve

    if resolve_serve_impl() == "native":
        native_serve._require_registered()
    eng = native_serve.build_native_engine(model)
    if eng is None:
        raise RuntimeError(
            "native serving engine selected but could not be built"
        )
    return eng


def _pallas_compatible(model) -> bool:
    """Pallas data-bank scorer (serving/pallas_scorer.py): TPU serving
    of forests beyond the QuickScorer envelope (any leaf count). CPU
    runs it only in interpret mode — tests build it directly."""
    from ydf_tpu.config import is_tpu_backend
    from ydf_tpu.serving import pallas_scorer

    return is_tpu_backend() and pallas_scorer.in_envelope(model)


def _build_pallas(model):
    from ydf_tpu.serving.pallas_scorer import build_pallas_scorer

    return build_pallas_scorer(model)


register_engine(EngineFactory(
    name="QuickScorer",  # leaf-mask Pallas kernel (quickscorer.py)
    rank=300,
    is_compatible=_qs_compatible,
    build=_build_qs,
))

register_engine(EngineFactory(
    name="PallasBank",  # data-bank Pallas scorer (pallas_scorer.py)
    rank=250,
    is_compatible=_pallas_compatible,
    build=_build_pallas,
))

register_engine(EngineFactory(
    name="NativeBatch",  # native data-bank walk (native_serve.py)
    rank=200,
    is_compatible=_native_compatible,
    build=_build_native,
))

register_engine(EngineFactory(
    name="Routed",  # generic value-mode tree scan (ops/routing.py)
    rank=0,
    is_compatible=lambda model: True,
    build=_build_routed,
))


# --------------------------------------------------------------------- #
# Request-coalescing batcher — the production-traffic front
# --------------------------------------------------------------------- #


class _Slot:
    """One pending single-row request."""

    __slots__ = ("row", "result", "error", "event", "t0_ns", "nbytes",
                 "sampled", "req")

    def __init__(self, row):
        self.row = row
        self.result = None
        self.error = None
        self.event = threading.Event()
        self.t0_ns = time.perf_counter_ns()
        self.nbytes = 0    # row bytes, charged to the queue counter
        self.sampled = False  # journey-trace sample (YDF_TPU_TRACE_SAMPLE)
        self.req = 0       # sampled-request id linking the span chain


class CoalescingBatcher:
    """Gathers concurrent single-row predict calls into kernel-sized
    batches (the reference's ExampleSet batch API turned into a serving
    front): callers block on `predict_one(*row)` while a background
    flusher coalesces up to `max_batch` rows or until the oldest row
    has waited `timeout_us`, runs ONE batched kernel call, and fans the
    results back out. Every row is answered exactly once, in
    submission order within its batch (tests/test_serving_engine.py).

    `batch_fn(*stacked)` receives each row position stacked on axis 0
    (np.stack) and returns an array whose leading axis matches the
    batch. Bounds default to YDF_TPU_SERVE_MAX_BATCH /
    YDF_TPU_SERVE_BATCH_TIMEOUT_US (validated at import).

    Overload policy (docs/serving.md "Serving under load"): the queue
    is bounded by `max_queue` rows (reject-on-full) and — through the
    MemoryLedger's `serve_batcher` gauge — by `max_queue_bytes`
    (admission); rows older than `deadline_us` at flush time are shed
    instead of served late. Every shed fails the caller FAST with a
    typed ServeOverloadError carrying the reason, is counted in
    ydf_serve_shed_total{reason}, and preserves the exact-once
    contract for survivors (each remaining row still gets its own
    result). The `serve.flush` failpoint injects a whole-flush
    deadline shed for the chaos tests.

    Instrumented with the per-engine serving telemetry: each answered
    row observes its whole queue+kernel latency into
    ydf_serve_latency_ns{engine="Batcher", batch_pow2} so p50/p99
    under concurrent load is measurable; the flusher keeps the
    ydf_serve_queue_depth / ydf_serve_queue_oldest_age_ns gauges
    current, and `trace_sample` (YDF_TPU_TRACE_SAMPLE) records the
    per-request journey span chain (docs/observability.md)."""

    def __init__(
        self,
        batch_fn: Callable,
        max_batch: Optional[int] = None,
        timeout_us: Optional[float] = None,
        max_queue: Optional[int] = None,
        max_queue_bytes: Optional[int] = None,
        deadline_us: Optional[float] = None,
        trace_sample: Optional[float] = None,
    ):
        self.batch_fn = batch_fn
        self.max_batch = int(max_batch or SERVE_MAX_BATCH)
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        timeout_us = (
            SERVE_BATCH_TIMEOUT_US if timeout_us is None else timeout_us
        )
        if timeout_us <= 0:
            raise ValueError("timeout_us must be > 0")
        self.timeout_s = float(timeout_us) / 1e6
        self.max_queue = int(
            SERVE_MAX_QUEUE if max_queue is None else max_queue
        )
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        self.max_queue_bytes = int(
            SERVE_MAX_QUEUE_BYTES if max_queue_bytes is None
            else max_queue_bytes
        )
        if self.max_queue_bytes < 0:
            raise ValueError("max_queue_bytes must be >= 0 (0 = off)")
        deadline_us = (
            SERVE_DEADLINE_US if deadline_us is None else deadline_us
        )
        if deadline_us < 0:
            raise ValueError("deadline_us must be >= 0 (0 = off)")
        self.deadline_ns = int(float(deadline_us) * 1e3)
        self.trace_sample = (
            TRACE_SAMPLE if trace_sample is None
            else resolve_trace_sample(trace_sample)
        )
        self._cv = threading.Condition()
        self._queue: List[_Slot] = []
        self._queue_bytes = 0  # maintained under _cv at enqueue/dequeue
        self._closed = False
        with _BATCHERS_LOCK:
            _BATCHERS.add(self)  # /statusz queue-depth visibility
        _register_serving_status()
        self._thread = threading.Thread(
            target=self._flusher_loop, daemon=True,
            name="ydf-serve-batcher",
        )
        self._thread.start()

    # -- caller side --------------------------------------------------- #

    def queue_bytes(self) -> int:
        """Bytes of rows currently pending, from the counter maintained
        under the lock (the race-free ledger/admission read)."""
        with self._cv:
            return self._queue_bytes

    def predict_one(self, *row):
        """Submits one row (its per-position arrays/scalars) and blocks
        until the coalesced batch containing it is served — or fails
        fast with ServeOverloadError when the overload policy sheds
        it (queue_full / admission here, deadline at flush)."""
        nb = 0
        for x in row:
            nb += int(getattr(x, "nbytes", 8))
        if self.max_queue_bytes:
            from ydf_tpu.utils import telemetry

            held = telemetry.ledger().get_bytes("serve_batcher")
            if held + nb > self.max_queue_bytes:
                _note_shed("admission")
                raise ServeOverloadError(
                    f"admission rejected: serve_batcher holds {held} "
                    f"bytes (+{nb} for this row) against "
                    f"max_queue_bytes={self.max_queue_bytes}",
                    reason="admission",
                )
        slot = _Slot(row)
        slot.nbytes = nb
        if self.trace_sample:
            from ydf_tpu.utils import telemetry

            if telemetry.ENABLED and (
                self.trace_sample >= 1.0
                or _TRACE_RNG.random() < self.trace_sample
            ):
                slot.sampled = True
                slot.req = next(_REQ_IDS)
                # Journey trace, caller half: serve.request covers the
                # whole queue+kernel residence; batcher.enqueue the
                # submit. The flusher half (batcher.flush →
                # serve.kernel → batcher.fanout) links back via `req`.
                with telemetry.span("serve.request") as sp:
                    sp.set(req=slot.req)
                    with telemetry.span("batcher.enqueue") as se:
                        se.set(req=slot.req)
                        self._enqueue(slot)
                    slot.event.wait()
                    if slot.error is not None:
                        sp.set(outcome=type(slot.error).__name__)
                if slot.error is not None:
                    raise slot.error
                return slot.result
        self._enqueue(slot)
        slot.event.wait()
        if slot.error is not None:
            raise slot.error
        return slot.result

    def _enqueue(self, slot: _Slot) -> None:
        with self._cv:
            if self._closed:
                raise RuntimeError("batcher is closed")
            depth = len(self._queue)
            if self.max_queue and depth >= self.max_queue:
                full = True
            else:
                full = False
                self._queue.append(slot)
                self._queue_bytes += slot.nbytes
                self._cv.notify_all()
        if full:
            _note_shed("queue_full")
            raise ServeOverloadError(
                f"queue full: {depth} pending rows at "
                f"max_queue={self.max_queue}",
                reason="queue_full",
            )

    # -- flusher side -------------------------------------------------- #

    def _flusher_loop(self):
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if self._closed and not self._queue:
                    return
                # Deadline is anchored on the OLDEST pending row.
                deadline = self._queue[0].t0_ns / 1e9 + self.timeout_s
                while (
                    len(self._queue) < self.max_batch and not self._closed
                ):
                    remaining = deadline - time.perf_counter_ns() / 1e9
                    if remaining <= 0:
                        break
                    self._cv.wait(remaining)
                batch = self._queue[: self.max_batch]
                del self._queue[: len(batch)]
                for s in batch:
                    self._queue_bytes -= s.nbytes
                depth_after = len(self._queue)
                oldest_age = (
                    time.perf_counter_ns() - self._queue[0].t0_ns
                    if self._queue else 0
                )
            if batch:
                self._flush(batch, depth_after, oldest_age)

    def _flush(self, batch: List[_Slot], queue_depth: int = 0,
               oldest_age_ns: int = 0):
        from ydf_tpu.utils import failpoints, telemetry

        if telemetry.ENABLED:
            telemetry.gauge("ydf_serve_queue_depth").set(queue_depth)
            telemetry.gauge("ydf_serve_queue_oldest_age_ns").set(
                oldest_age_ns
            )
        injected = False
        if failpoints.ENABLED:
            try:
                failpoints.hit("serve.flush")
            except (failpoints.FailpointError, ConnectionError):
                # Injected overload: this flush behaves as if every row
                # aged past its deadline — shed THE WHOLE BATCH, serve
                # the next (the chaos handle for the shed-fanout
                # exact-once contract).
                injected = True
        now = time.perf_counter_ns()
        if injected or self.deadline_ns:
            shed = []
            kept = []
            for s in batch:
                if injected or now - s.t0_ns > self.deadline_ns:
                    shed.append(s)
                else:
                    kept.append(s)
            if shed:
                _note_shed("deadline", len(shed))
                dl_us = self.deadline_ns / 1e3
                for s in shed:
                    s.error = ServeOverloadError(
                        f"shed at flush after "
                        f"{(now - s.t0_ns) / 1e3:.0f} us "
                        f"(deadline {dl_us:.0f} us"
                        f"{', injected' if injected else ''})",
                        reason="deadline",
                    )
                    s.event.set()
            batch = kept
        if not batch:
            return
        traced = False
        if self.trace_sample and telemetry.ENABLED:
            traced = any(s.sampled for s in batch)
        if traced:
            with telemetry.span("batcher.flush") as fs:
                fs.set(
                    batch=len(batch),
                    req=next(s.req for s in batch if s.sampled),
                    queue_age_ns=now - batch[0].t0_ns,
                )
                self._serve_batch(batch, traced=True)
        else:
            self._serve_batch(batch, traced=False)

    def _serve_batch(self, batch: List[_Slot], traced: bool):
        import numpy as np

        from ydf_tpu.utils import telemetry

        try:
            stacked = tuple(
                np.stack([s.row[k] for s in batch])
                for k in range(len(batch[0].row))
            )
            if traced:
                with telemetry.span("serve.kernel") as ks:
                    ks.set(batch=len(batch))
                    out = np.asarray(self.batch_fn(*stacked))
            else:
                out = np.asarray(self.batch_fn(*stacked))
            for j, s in enumerate(batch):
                s.result = out[j]
        except BaseException as e:  # noqa: BLE001 - fanned back to callers
            for s in batch:
                s.error = e
        finally:
            if telemetry.ENABLED:
                now = time.perf_counter_ns()
                b = telemetry.pow2_bucket(len(batch))
                hist = telemetry.histogram(
                    "ydf_serve_latency_ns", engine="Batcher", batch_pow2=b
                )
                for s in batch:
                    hist.observe_ns(now - s.t0_ns)
                telemetry.counter(
                    "ydf_serve_batcher_flushes_total"
                ).inc()
                telemetry.counter(
                    "ydf_serve_batcher_rows_total"
                ).inc(len(batch))
            if traced:
                with telemetry.span("batcher.fanout") as fo:
                    fo.set(batch=len(batch))
                    for s in batch:
                        s.event.set()
            else:
                for s in batch:
                    s.event.set()

    # -- lifecycle ----------------------------------------------------- #

    def close(self):
        """Serves the remaining queue, then stops the flusher."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def model_batcher(
    model,
    max_batch: Optional[int] = None,
    timeout_us: Optional[float] = None,
    max_queue: Optional[int] = None,
    max_queue_bytes: Optional[int] = None,
    deadline_us: Optional[float] = None,
    trace_sample: Optional[float] = None,
) -> CoalescingBatcher:
    """A CoalescingBatcher over the model's fastest compatible engine:
    rows are pre-encoded (x_num_row [Fn], x_cat_row [Fc]) vectors (the
    engine input contract); results are raw scores. Falls back to the
    generic routed scan when no fast engine is compatible. Overload
    bounds and the journey-trace sample rate pass through to the
    batcher (defaults: the YDF_TPU_SERVE_* env knobs)."""
    import jax.numpy as jnp
    import numpy as np

    eng = model._fast_engine()
    if eng is not None:
        fn = eng
    else:
        from ydf_tpu.ops.routing import forest_predict_values

        def fn(x_num, x_cat):
            return np.asarray(
                forest_predict_values(
                    model.forest,
                    jnp.asarray(x_num), jnp.asarray(x_cat),
                    num_numerical=model.binner.num_numerical,
                    max_depth=model.max_depth, combine="sum",
                )
            )[:, 0]

    return CoalescingBatcher(
        fn, max_batch=max_batch, timeout_us=timeout_us,
        max_queue=max_queue, max_queue_bytes=max_queue_bytes,
        deadline_us=deadline_us, trace_sample=trace_sample,
    )
