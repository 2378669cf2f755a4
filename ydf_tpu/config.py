"""Task types and shared configuration.

Mirrors the task enum of the reference (`ydf/model/abstract_model.proto` Task)
and the generic-hyperparameter surface of `ydf/learner/abstract_learner.proto`,
re-expressed as Python dataclasses (the TPU build has no protobuf dependency
on its hot path; configs are plain static Python used as jit-static args).
"""

from __future__ import annotations

import dataclasses
import enum
import os


def is_tpu_backend() -> bool:
    """True when JAX's default backend is a TPU. A backend that fails to
    initialise raises here; it must never silently select the CPU
    implementations."""
    import jax

    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Entry points call this before their first compile (chip_smoke.py,
    bench.py, cli.py, __graft_entry__.py, a blocking start_worker).
    Returns the persistent compilation cache's directory.

    JAX_COMPILATION_CACHE_DIR, when set, is read by JAX itself and
    nothing is set in code. Otherwise the cache sits at a fixed path
    under the checkout: the path is part of the cache key, so a
    directory that moves (tempfile, pid, time) never hits."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def resolve_num_bins(num_bins, n: int, min_cat_vocab: int = 0) -> int:
    """Resolves num_bins="auto" against the dataset size.

    The dense layer buffers are [Ld, F, B, S] — independent of n — so at
    small n the B axis dominates training cost (round-4 profile: abalone
    RF spent ~0.7 s/tree streaming 256-bin buffers over 4.2k rows).
    "auto" = pow2ceil(n / 180) clipped to [64, 256]; an explicit int is
    honored unchanged. Calibrated on measured quality (round 5): adult
    (22.8k rows) at B=128 keeps AUC bit-identical to 256 while halving
    the wall (3.7 -> 1.9 s); B=64 there costs 1pt AUC, hence the 180
    rows/bin knee and the 64 floor.

    `min_cat_vocab`: largest categorical dictionary among the training
    features. Dictionary indices >= num_bins collapse to OOV
    (dataset/binning.py), so the auto result is floored at that vocab —
    shrinking bins must never silently drop categories the old 256
    default kept."""
    if num_bins != "auto":
        return int(num_bins)
    floor = 64
    while floor < 256 and floor < min_cat_vocab:
        floor *= 2
    if n >= 180 * 256:
        return 256
    b = floor
    while b < 256 and b * 180 < n:
        b *= 2
    return b


def resolve_max_frontier(max_frontier, n: int, min_examples: int) -> int:
    """Resolves max_frontier="auto": a layer can never usefully hold more
    open nodes than n / (2*min_examples) (each split needs min_examples
    per child), so cap the frontier there — pow2-rounded up, bounded by
    the 1024 default. An explicit int is honored unchanged."""
    if max_frontier != "auto":
        return int(max_frontier)
    need = max(2, n // max(2 * min_examples, 1))
    p = 2
    while p < need and p < 1024:
        p *= 2
    return min(p, 1024)


class Task(enum.Enum):
    """Modeling task. Reference: ydf/model/abstract_model.proto:Task."""

    CLASSIFICATION = "CLASSIFICATION"
    REGRESSION = "REGRESSION"
    RANKING = "RANKING"
    CATEGORICAL_UPLIFT = "CATEGORICAL_UPLIFT"
    NUMERICAL_UPLIFT = "NUMERICAL_UPLIFT"
    ANOMALY_DETECTION = "ANOMALY_DETECTION"
    SURVIVAL_ANALYSIS = "SURVIVAL_ANALYSIS"


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    """Static shape/budget configuration of a single tree build.

    These are jit-static: one compilation per distinct TreeConfig.

    The grower is breadth-first / layer-synchronous (the design the reference
    uses for its *distributed* trainer, `ydf/learner/distributed_decision_tree/
    training.h:104-143`), because that is the XLA-friendly formulation: the
    per-layer work is one dense histogram reduction + one argmax, with static
    shapes everywhere.
    """

    max_depth: int = 6
    # Maximum number of nodes that can be split in one layer (frontier cap).
    # min(2**(max_depth-1), this). Nodes beyond the cap become leaves.
    max_frontier: int = 1024
    # Number of histogram bins (including the reserved missing/OOV bin 0 for
    # categorical columns).
    num_bins: int = 256
    min_examples: int = 5

    @property
    def frontier(self) -> int:
        if self.max_depth < 0:  # "unlimited" → practical cap
            return self.max_frontier
        return min(2 ** max(self.max_depth - 1, 0), self.max_frontier)

    @property
    def max_nodes(self) -> int:
        """Capacity of the node arrays of one tree."""
        if self.max_depth < 0:
            depth = 32
        else:
            depth = self.max_depth
        # Breadth-first growth: layer d has at most min(2**d, 2*frontier)
        # nodes. Sum over layers, +1 root slack.
        total = 0
        for d in range(depth + 1):
            total += min(2**d, 2 * self.frontier)
            if 2**d >= 2 * self.frontier and d > 20:
                total += (depth - d) * 2 * self.frontier
                break
        return int(total)


def resolved_env_config() -> dict:
    """Every YDF_TPU_* knob as the subsystems actually RESOLVED it —
    the eagerly-validated values, not raw os.environ (a typo'd env var
    raised at import; what shows here is what runs). The /statusz
    `config` section (utils/telemetry_http.py) and each distributed
    worker's status/shard-load response carry this dict, so config
    drift between manager and workers is visible instead of surfacing
    as a confusing perf or bit-identity report days later
    (docs/observability.md "Resource observability").

    Best-effort per knob: a subsystem that cannot import here (no
    toolchain, no jax) degrades that one entry to an `error: ...`
    string, never the whole page."""
    out = {}

    def put(key, fn):
        try:
            out[key] = fn()
        except Exception as e:  # noqa: BLE001 — page must render
            out[key] = f"error: {type(e).__name__}: {e}"

    def _telemetry():
        from ydf_tpu.utils import telemetry

        return telemetry

    put("YDF_TPU_TELEMETRY", lambda: _telemetry().ENABLED)
    put("YDF_TPU_TELEMETRY_DIR", lambda: _telemetry().EXPORT_DIR)
    put("YDF_TPU_MEM_SAMPLE", lambda: _telemetry().MEM_SAMPLE)
    put("YDF_TPU_LOG", lambda: __import__(
        "ydf_tpu.utils.log", fromlist=["LEVEL"]).LEVEL)
    put("YDF_TPU_METRICS_PORT", lambda: __import__(
        "ydf_tpu.utils.telemetry_http",
        fromlist=["METRICS_PORT"]).METRICS_PORT)

    def _failpoints():
        from ydf_tpu.utils import failpoints

        return sorted(failpoints._SPECS) if failpoints.ENABLED else []

    put("YDF_TPU_FAILPOINTS", _failpoints)

    def _hist():
        from ydf_tpu.ops import histogram

        return histogram

    put("YDF_TPU_HIST_IMPL", lambda: _hist().resolve_hist_impl("auto"))
    put("YDF_TPU_HIST_QUANT", lambda: _hist().resolve_hist_quant(None))
    put("YDF_TPU_HIST_SUBTRACT",
        lambda: _hist().resolve_hist_subtract(None))

    def _route():
        from ydf_tpu.ops import routing_native

        return routing_native

    put("YDF_TPU_ROUTE_IMPL", lambda: _route().resolve_route_impl(None))
    put("YDF_TPU_ROUTE_FUSE", lambda: _route().resolve_route_fuse())
    put("YDF_TPU_ROUTE_THREADS",
        lambda: _route().resolved_route_threads())
    put("YDF_TPU_POOL_STATS", lambda: __import__(
        "ydf_tpu.ops.pool_stats",
        fromlist=["POOL_STATS_ENABLED"]).POOL_STATS_ENABLED)

    def _serving():
        from ydf_tpu.serving import registry

        return registry

    put("YDF_TPU_SERVE_IMPL", lambda: _serving().resolve_serve_impl())
    put("YDF_TPU_SERVE_MAX_BATCH", lambda: _serving().SERVE_MAX_BATCH)
    put("YDF_TPU_SERVE_BATCH_TIMEOUT_US",
        lambda: _serving().SERVE_BATCH_TIMEOUT_US)
    put("YDF_TPU_SERVE_MAX_QUEUE", lambda: _serving().SERVE_MAX_QUEUE)
    put("YDF_TPU_SERVE_MAX_QUEUE_BYTES",
        lambda: _serving().SERVE_MAX_QUEUE_BYTES)
    put("YDF_TPU_SERVE_DEADLINE_US",
        lambda: _serving().SERVE_DEADLINE_US)
    put("YDF_TPU_TRACE_SAMPLE", lambda: _serving().TRACE_SAMPLE)

    def _cache_verify():
        from ydf_tpu.dataset import cache

        return cache._resolve_verify(None)

    put("YDF_TPU_CACHE_VERIFY", _cache_verify)

    def _worker():
        from ydf_tpu.parallel import worker_service

        return worker_service

    put("YDF_TPU_WORKER_MAX_FRAME", lambda: _worker()._max_frame())
    put("YDF_TPU_WORKER_SEND_TIMEOUT",
        lambda: _worker()._send_timeout())
    put("YDF_TPU_WORKER_SECRET",
        lambda: _worker()._env_secret() is not None)
    put("YDF_TPU_WORKER_STATE_TTL_S",
        lambda: _worker()._STATE_TTL_S)

    def _dist():
        from ydf_tpu.parallel import dist_gbt

        return dist_gbt

    put("YDF_TPU_DIST_RPC_TIMEOUT_S",
        lambda: _dist()._parse_rpc_timeout())
    put("YDF_TPU_DIST_VERIFY", lambda: _dist()._parse_verify())
    return out


#: Knobs that must agree between a distributed manager and its workers
#: for bit-identity / comparable perf — the subset the manager checks
#: against each worker's shard-load response (parallel/dist_gbt.py
#: logs a mismatch at load time; see resolved_env_config).
DIST_CONFIG_KEYS = (
    "YDF_TPU_HIST_IMPL",
    "YDF_TPU_HIST_QUANT",
    "YDF_TPU_HIST_SUBTRACT",
    "YDF_TPU_CACHE_VERIFY",
    "YDF_TPU_WORKER_MAX_FRAME",
)
