"""Feature-parallel distributed GBT training — the manager driver.

Reproduces the reference's L4 distributed trainer
(`ydf/learner/distributed_gradient_boosted_trees/`: a manager reduces
per-feature best splits from workers that each own a feature slice of
the dataset-cache, then broadcasts the chosen split for routing — the
TF Boosted Trees exchange, arxiv 1710.11555) on top of this repo's
hardened worker substrate (WorkerPool retry/backoff/quarantine,
checksummed dataset cache, failpoints).

Protocol per boosting tree (verbs in parallel/dist_worker.py):

  tree start   manager computes gradients/stats from its own preds
               (labels are replicated; the bins never leave the
               workers), quantizes them once per tree on the grower's
               exact per-tree int8/bf16x2 grid
               (ops/grower.py:prepare_stats_for_hist — the
               YDF_TPU_HIST_QUANT wire format: int8 ships 1 byte per
               stat), and broadcasts them with the first
               build_histograms of the tree.
  per layer    1. build_histograms fan-out: worker k returns the
                  [num_slots, F_k, B, S] histogram of its feature
                  slice (under sibling subtraction only the
                  smaller-child slots cross the wire — the halved
                  reduced tensor). The request piggy-backs the
                  PREVIOUS layer's routing broadcast.
               2. the manager concatenates slices in shard order —
                  bit-identical to the single-machine histogram,
                  because every impl accumulates per-feature
                  independently in fixed row order — and runs the
                  grower's OWN split search on it
                  (ops/grower.py:layer_decide, the shared seam).
               3. apply_split fan-out to the workers owning split
                  features: each returns the go-left bitmap of the
                  rows it routed — only ONE worker routes per split.
               4. the manager ORs the owner bitmaps, applies the
                  routing to its authoritative slot/leaf state
                  (dist_worker.apply_route_tables — exact integer
                  bookkeeping shared with the workers), and carries
                  the merged bitmap into the next layer's requests.
  tree end     the manager updates its predictions from its own leaf
               assignment; YDF_TPU_DIST_VERIFY=1 additionally asks one
               worker for leaf_stats and cross-checks counts/sums.

Fault tolerance: every RPC rides the pool's retry machinery, and shard
ownership is DYNAMIC — a worker that times out (straggler,
YDF_TPU_DIST_RPC_TIMEOUT_S), drops its connection, or restarts has its
shards reassigned to the next healthy worker, which receives the shard
plus the manager's authoritative mid-tree state (slot/leaf/stats/
position) and resumes exactly where the lost worker stood; a corrupt
cache shard is detected by the worker's crc check and re-sliced from
the verified bins.npy (byte-identical). Failpoint sites
dist.shard_load / dist.histogram_rpc / dist.split_broadcast inject
faults into each exchange; the chaos suite asserts every recovery
produces a bit-identical model (docs/distributed_training.md).

Because the float split search runs ONLY on the manager — through the
grower's own seam functions — and workers contribute exact per-feature
histogram slices plus integer routing, the distributed model equals
the single-machine model bit for bit (same chosen splits, same leaf
values); tests/test_worker_dist_gbt.py asserts it across quant modes.
"""

from __future__ import annotations

import contextlib
import functools
import os
import signal as _signal
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ydf_tpu.utils import failpoints, log, telemetry
from ydf_tpu.utils.telemetry import LatencyHistogram


class DistributedTrainingError(RuntimeError):
    """Distributed training could not complete: every worker is
    unreachable past the retry budget, or a worker reported a
    non-recoverable protocol error."""


def _parse_rpc_timeout() -> float:
    """YDF_TPU_DIST_RPC_TIMEOUT_S — per-RPC deadline (straggler bound),
    eagerly validated at import like YDF_TPU_HIST_IMPL. A worker that
    does not answer within it is treated exactly like a dropped
    connection: quarantined, and its shards reassigned."""
    raw = os.environ.get("YDF_TPU_DIST_RPC_TIMEOUT_S")
    if raw is None:
        return 600.0
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"YDF_TPU_DIST_RPC_TIMEOUT_S={raw!r} is not a number of "
            "seconds"
        ) from None
    if not v > 0:
        raise ValueError(
            f"YDF_TPU_DIST_RPC_TIMEOUT_S={raw} must be > 0"
        )
    return v


def _parse_verify() -> bool:
    """YDF_TPU_DIST_VERIFY — per-tree worker-state cross-check
    (leaf_stats verb), eagerly validated."""
    raw = os.environ.get("YDF_TPU_DIST_VERIFY")
    if raw is None:
        return False
    low = raw.strip().lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(
        f"YDF_TPU_DIST_VERIFY={raw!r} is not a boolean; expected one of "
        "1/0/true/false/yes/no/on/off"
    )


_RPC_TIMEOUT_S: float = _parse_rpc_timeout()
_VERIFY: bool = _parse_verify()


# ------------------------------------------------------------------ #
# Jitted manager-side pieces. Each mirrors the exact op sequence the
# single-machine boosting scan traces (learners/gbt.py boost_step and
# ops/grower.py), so the compiled arithmetic matches bit for bit.
# ------------------------------------------------------------------ #


@functools.partial(jax.jit, static_argnames=("loss_obj", "n"))
def _j_init(y_tr, w_tr, *, loss_obj, n):
    y_f = y_tr.astype(jnp.float32)
    init_pred = loss_obj.initial_predictions(y_f, w_tr)  # [K]
    preds0 = jnp.broadcast_to(init_pred[None, :], (n, 1)).astype(
        jnp.float32
    )
    return preds0, init_pred


@functools.partial(
    jax.jit, static_argnames=("loss_obj", "subsample", "hist_quant")
)
def _j_tree_prologue(y_tr, w_tr, preds, key, it, *, loss_obj, subsample,
                     hist_quant):
    """Gradients → sampled stats → per-tree quantized operand, with the
    SAME ops and key evolution as the single-machine boost_step."""
    from ydf_tpu.ops.grower import prepare_stats_for_hist

    key, k_sub = jax.random.split(jax.random.fold_in(key, it))
    g, h = loss_obj.grad_hess(y_tr, preds)  # [n, 1]
    if subsample < 1.0:
        m = jax.random.bernoulli(
            k_sub, subsample, (y_tr.shape[0],)
        ).astype(jnp.float32)
    else:
        m = jnp.ones((y_tr.shape[0],), jnp.float32)
    w_eff = w_tr * m
    stats = jnp.stack(
        [g[:, 0] * w_eff, h[:, 0] * w_eff, w_eff], axis=1
    )
    kk = jax.random.fold_in(key, 0)  # K == 1: class column 0
    hist_stats, qscale, total = prepare_stats_for_hist(stats, hist_quant)
    return key, kk, hist_stats, qscale, total


@functools.partial(
    jax.jit,
    static_argnames=(
        "rule", "L", "B", "N", "Fn", "Fc", "O", "min_examples",
        "min_split_gain", "candidate_features", "num_valid_features",
        "children", "subtract",
    ),
)
def _j_layer_step(
    hist, parent, active, nid, num_nodes, k_gain, k_feat, *,
    rule, L, B, N, Fn, Fc, O, min_examples, min_split_gain,
    candidate_features, num_valid_features, children, subtract,
):
    """One layer of the split search over the assembled [Ld, F, B, S]
    histogram — scalar_candidates + layer_decide + (optionally) the
    sibling bookkeeping, all straight from the grower's seam."""
    from ydf_tpu.ops import grower

    Ld = hist.shape[0]
    left_all, ranks, right_scalar = grower.scalar_candidates(
        hist, Fn=Fn, O=O, rule=rule, rule_ctx=None
    )
    dec = grower.layer_decide(
        left_all, ranks, None, parent, active, nid, num_nodes,
        k_gain, k_feat, None, None,
        rule=rule, L=L, B=B, N=N, Fn=Fn, Fc=Fc, O=O, Fs=0,
        W=(B + 31) // 32, min_examples=min_examples,
        min_split_gain=min_split_gain,
        candidate_features=candidate_features,
        num_valid_features=num_valid_features,
        children_in_frontier=children,
        right_scalar=right_scalar,
    )
    out = {"dec": dec, "mask": grower._pack_mask(dec.store_mask)}
    if children and subtract and min(Ld, L // 2) >= 1:
        parent_next, small_is_left, _Lh, hmap = grower.sibling_next_state(
            hist, dec.do_split, dec.split_rank, dec.left_stats,
            dec.right_stats, Ld=Ld, L=L,
        )
        out["sub"] = (parent_next, small_is_left)
        out["hmap"] = hmap
    return out


@functools.partial(jax.jit, static_argnames=("Ld",))
def _j_sibling_reconstruct(hist_small, parent_hist, small_is_left, *, Ld):
    from ydf_tpu.ops.grower import sibling_reconstruct

    return sibling_reconstruct(hist_small, parent_hist, small_is_left, Ld)


@functools.partial(
    jax.jit, static_argnames=("rule", "loss_obj", "shrinkage")
)
def _j_tree_epilogue(leaf_stats, leaf_id, preds, y_tr, w_tr, *, rule,
                     loss_obj, shrinkage):
    """End-of-tree update: leaf values, prediction update, training
    loss — the same gather/set/add chain as the single-machine
    boost_step's K == 1 unfused path."""
    lv_raw = rule.leaf_value(leaf_stats, None)  # [N, 1]
    lv = lv_raw * shrinkage
    n = leaf_id.shape[0]
    new_contrib = jnp.zeros((n, 1), jnp.float32)
    new_contrib = new_contrib.at[:, 0].set(lv[leaf_id, 0])
    preds = preds + new_contrib
    tl = loss_obj.loss(y_tr, preds, w_tr, tag="train")
    return preds, lv, tl


def _pad_to(a: np.ndarray, length: int, fill) -> np.ndarray:
    out = np.full((length,) + a.shape[1:], fill, a.dtype)
    out[: a.shape[0]] = a
    return out


def _transport_fields(pool) -> Dict[str, Any]:
    """The pool's always-on transport counters for the run's
    `training_logs["distributed"]` record (bench.py's dist_rpc_*
    headline fields): TCP connects, connection-reuse rate, and wire
    bytes split into pickled header vs zero-copy array payload. The
    pool is created per train, so the counts are per-run. Tolerates
    bare test doubles without the transport attribute."""
    snap = getattr(pool, "transport_snapshot", None)
    return snap() if callable(snap) else {}


class _DistStats:
    """Always-on manager-side exchange accounting (the bench family's
    source; mirrored into telemetry when it is armed)."""

    def __init__(self):
        self.rpc_ns: Dict[str, LatencyHistogram] = {}
        self.reduce_bytes = 0
        self.stats_bytes = 0
        self.recoveries = 0
        self.shard_rebuilds = 0
        # Manager-side histogram merge wall (row-parallel sum-merge /
        # feature-parallel concat), summed over all layers — the
        # dist_merge_s headline bench field.
        self.merge_ns = 0
        # Per-layer wall attribution (compute / network / straggler
        # wait, summed over all layers of the run): the "was that layer
        # slow because of compute, the network, or one straggler?"
        # breakdown the headline bench records carry.
        self.layer_wall_ns = 0
        self.compute_ns = 0
        self.net_ns = 0
        self.wait_ns = 0
        # Per-worker telemetry drained via get_telemetry (event counts
        # by address; the events themselves are merged into the
        # manager's trace buffer).
        self.drained_events: Dict[str, int] = {}
        # Resource accounting (this round): latest shard/state bytes
        # each worker reported at shard load (fleet total = the
        # dist_shard_bytes headline field), and per-worker RSS from the
        # get_telemetry drain.
        self.shard_bytes: Dict[str, int] = {}
        self.worker_rss_bytes: Dict[str, int] = {}
        self.config_mismatches = 0
        # Tree-boundary snapshot accounting (preemption-safe round):
        # count, summed write wall (bench.py's dist_snapshot_s) and
        # payload bytes.
        self.snapshots = 0
        self.snapshot_ns = 0
        self.snapshot_bytes = 0

    def observe_snapshot(self, dur_ns: int, nbytes: int) -> None:
        self.snapshots += 1
        self.snapshot_ns += int(dur_ns)
        self.snapshot_bytes += int(nbytes)
        if telemetry.ENABLED:
            telemetry.counter("ydf_dist_snapshots_total").inc()
            telemetry.counter("ydf_dist_snapshot_ns_total").inc(
                int(dur_ns)
            )
            telemetry.counter("ydf_dist_snapshot_bytes_total").inc(
                int(nbytes)
            )

    def observe_rpc(self, verb: str, dur_ns: int) -> None:
        self.rpc_ns.setdefault(verb, LatencyHistogram()).observe_ns(dur_ns)
        if telemetry.ENABLED:
            telemetry.histogram(
                "ydf_dist_rpc_latency_ns", verb=verb
            ).observe_ns(dur_ns)

    def observe_merge(self, dur_ns: int) -> None:
        self.merge_ns += int(dur_ns)
        if telemetry.ENABLED:
            telemetry.counter("ydf_dist_merge_ns_total").inc(int(dur_ns))

    def drop_worker_shards(self, addr: str) -> None:
        """Shard-fleet accounting on migration: a quarantined worker's
        resident-bytes report leaves the fleet total the moment its
        shards move (the replacement's load response re-adds them).
        Without this, `dist_shard_fleet` summed every load response
        ever seen — a run with one migration double-counted the moved
        shards, and a corrupt-shard rebuild's reload stacked a third
        copy."""
        if self.shard_bytes.pop(addr, None) is not None and (
            telemetry.ENABLED
        ):
            telemetry.mem_set(
                "dist_shard_fleet", sum(self.shard_bytes.values())
            )

    def observe_layer(
        self, wall_ns: int, hist_rpcs: Dict[int, Tuple[int, Optional[int]]]
    ) -> None:
        """Attributes one layer's wall into compute/net/wait from the
        per-worker histogram-RPC walls (manager-measured) and worker
        handle times (`_handle_ns` from the response):

          wait    = slowest − median histogram RPC (straggler wait —
                    the fan-out is a barrier, so everything past the
                    median worker's finish is waiting on stragglers);
          net     = median RPC wall − median worker handle time
                    (serialization + transport of the typical RPC);
          compute = the remainder (worker histogram kernels + the
                    manager's own split search / routing merge).

        The three sum to the layer wall by construction."""
        from statistics import median

        walls = sorted(w for w, _ in hist_rpcs.values())
        wait = net = 0
        if walls:
            med_w = median(walls)
            wait = int(max(walls[-1] - med_w, 0))
            handles = sorted(
                h for _, h in hist_rpcs.values() if h is not None
            )
            med_h = median(handles) if handles else med_w
            net = int(max(med_w - med_h, 0))
        wait = min(wait, wall_ns)
        net = min(net, wall_ns - wait)
        self.layer_wall_ns += wall_ns
        self.wait_ns += wait
        self.net_ns += net
        self.compute_ns += wall_ns - wait - net
        if telemetry.ENABLED:
            telemetry.counter("ydf_dist_layer_wait_ns_total").inc(wait)
            telemetry.counter("ydf_dist_layer_net_ns_total").inc(net)
            telemetry.counter("ydf_dist_layer_compute_ns_total").inc(
                wall_ns - wait - net
            )

    def summary(self) -> Dict[str, Any]:
        out = {
            "reduce_bytes": int(self.reduce_bytes),
            "stats_bytes": int(self.stats_bytes),
            "recoveries": int(self.recoveries),
            "shard_rebuilds": int(self.shard_rebuilds),
            "merge_s": round(self.merge_ns / 1e9, 6),
            "layer_wall_s": round(self.layer_wall_ns / 1e9, 6),
            "compute_s": round(self.compute_ns / 1e9, 6),
            "net_s": round(self.net_ns / 1e9, 6),
            "wait_s": round(self.wait_ns / 1e9, 6),
            "rpc_p50_ns": {
                v: round(h.percentile_ns(50), 1)
                for v, h in sorted(self.rpc_ns.items())
            },
            "rpc_count": {
                v: int(h.count) for v, h in sorted(self.rpc_ns.items())
            },
        }
        out["snapshots"] = int(self.snapshots)
        out["snapshot_s"] = round(self.snapshot_ns / 1e9, 6)
        out["snapshot_bytes"] = int(self.snapshot_bytes)
        out["shard_bytes"] = int(sum(self.shard_bytes.values()))
        if self.shard_bytes:
            out["worker_shard_bytes"] = dict(self.shard_bytes)
        if self.worker_rss_bytes:
            out["worker_rss_bytes"] = dict(self.worker_rss_bytes)
        if self.config_mismatches:
            out["config_mismatches"] = int(self.config_mismatches)
        if self.drained_events:
            out["telemetry_drained_events"] = dict(self.drained_events)
        return out


class MembershipChannel:
    """Elastic-membership mailbox for a RUNNING distributed train: an
    operator (or the churn tests) posts join/leave events, the manager
    claims whatever is due at each tree boundary (`_tree_boundary` →
    `_apply_membership`) and remaps shards onto the new worker set with
    the resume machinery — epoch bump fences the old view, joiners get
    verify-or-re-ship shard loads, leavers leave their state to the
    worker-side idle-TTL reaper. Applying membership ONLY at tree
    boundaries is what keeps the model bit-identical to a
    fixed-membership run: every merge inside a tree is order-fixed and
    worker-count invariant, and no tree ever spans two views.

    A join that fails (unreachable candidate, or the `dist.member_join`
    chaos site) is re-queued for a later boundary, bounded by
    MAX_JOIN_RETRIES — a flapping candidate cannot stall training."""

    #: Bounded re-queue budget for a failed join.
    MAX_JOIN_RETRIES = 2

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending: List[Dict[str, Any]] = []
        self._applied: List[Dict[str, Any]] = []

    def post(self, op: str, address: str, at_tree: int = 0) -> None:
        """Queues a membership event: `op` is "join" or "leave",
        `address` a "host:port" worker, `at_tree` the earliest tree
        boundary (completed-tree count) it may apply at."""
        if op not in ("join", "leave"):
            raise ValueError(
                f"membership op {op!r} must be 'join' or 'leave'"
            )
        with self._lock:
            self._pending.append({
                "op": op, "address": str(address),
                "at_tree": int(at_tree), "retries": 0,
            })

    def claim(self, done: int) -> List[Dict[str, Any]]:
        """Pops every event due at boundary `done` (at_tree <= done),
        in post order."""
        with self._lock:
            due = [e for e in self._pending if e["at_tree"] <= done]
            self._pending = [
                e for e in self._pending if e["at_tree"] > done
            ]
        return due

    def requeue(self, event: Dict[str, Any], at_tree: int) -> bool:
        """Puts a failed join back for a later boundary; False when its
        retry budget is spent (the event is dropped)."""
        event = dict(event)
        event["retries"] = int(event.get("retries", 0)) + 1
        if event["retries"] > self.MAX_JOIN_RETRIES:
            return False
        event["at_tree"] = int(at_tree)
        with self._lock:
            self._pending.append(event)
        return True

    def note_applied(self, event: Dict[str, Any], done: int) -> None:
        with self._lock:
            self._applied.append({**event, "applied_at_tree": int(done)})

    def applied(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._applied)

    def pending(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._pending)


class DistGBTManager:
    """Drives one distributed GBT train over a WorkerPool + feature-
    sharded DatasetCache. See the module docstring for the protocol."""

    def __init__(
        self, pool, cache, *, loss_obj, rule, tree_cfg, num_trees: int,
        shrinkage: float, subsample: float, candidate_features: int,
        num_numerical: int, seed: int, hist_impl: str,
        hist_subtract: bool, hist_quant: str,
        min_split_gain: float = 1e-9,
        rpc_timeout_s: Optional[float] = None,
        verify: Optional[bool] = None,
        working_dir: Optional[str] = None,
        resume: bool = False,
        snapshot_interval: int = 50,
        preempt_after_snapshots: Optional[int] = None,
        membership: Optional[MembershipChannel] = None,
    ):
        self.pool = pool
        self.membership = membership
        self.cache = cache
        self.loss_obj = loss_obj
        self.rule = rule
        self.cfg = tree_cfg
        self.num_trees = num_trees
        self.shrinkage = float(shrinkage)
        self.subsample = float(subsample)
        self.candidate_features = int(candidate_features)
        self.seed = seed
        self.hist_impl = hist_impl
        self.hist_subtract = bool(hist_subtract)
        self.hist_quant = hist_quant
        self.min_split_gain = float(min_split_gain)
        self.rpc_timeout_s = (
            _RPC_TIMEOUT_S if rpc_timeout_s is None else rpc_timeout_s
        )
        self.verify = _VERIFY if verify is None else verify

        self.num_shards = cache._require_shards()
        self.col_ranges = [
            cache.shard_col_range(k) for k in range(self.num_shards)
        ]
        self.F = cache.binner.num_scalar
        self.Fn = int(num_numerical)
        self.Fc = self.F - self.Fn
        self.n = cache.num_rows
        self.key_id = f"dist-{uuid.uuid4().hex[:12]}"
        # Dynamic shard ownership: shard k starts on worker k % W and
        # moves on failure (the recovery path re-ships shard + state).
        self.owner: List[int] = [
            k % len(pool.addresses) for k in range(self.num_shards)
        ]
        self.stats = _DistStats()
        # Manager-side authoritative per-example state (what makes a
        # lost worker recoverable mid-tree).
        self.slot = np.zeros(self.n, np.int32)
        self.hist_slot = np.zeros(self.n, np.int32)
        self.leaf_id = np.zeros(self.n, np.int32)
        self.pos = (-1, 0)
        self.cur_hist_stats: Optional[np.ndarray] = None
        self.cur_qscale: Optional[np.ndarray] = None
        self._init_ckpt(
            working_dir, resume, snapshot_interval,
            preempt_after_snapshots,
        )

    # ---- checkpoint / resume / epoch fencing ------------------------- #
    #
    # Preemption-safe distributed training (docs/distributed_training.md
    # "Resume"): with a working_dir, the manager writes a durable
    # snapshot through the round-10 Snapshots contract at tree
    # boundaries — forest-so-far, train (and row-mode validation)
    # predictions and losses, the carried PRNG key (the per-tree quant
    # grid is derived from it, so no mid-tree state is persisted) and
    # the shard ownership map — guards the loop with the SIGTERM/SIGINT
    # handler (forced final snapshot → TrainingPreempted → exit 75),
    # and on resume a NEW manager reattaches: same deterministic run
    # key (worker-state namespace), snapshot epoch + 1 as its fencing
    # token, shards verified-or-re-shipped idempotently, training
    # resumed bit-identical from the boundary.

    #: Per-tree array fields a snapshot stacks (tree dict layout of
    #: _train_tree's tree_np).
    _TREE_FIELDS = (
        "feature", "threshold_bin", "is_cat", "is_set", "cat_mask",
        "left", "right", "is_leaf", "leaf_stats", "num_nodes",
    )

    def _init_ckpt(self, working_dir, resume, snapshot_interval,
                   preempt_after_snapshots) -> None:
        """Shared by both managers (RowDistGBTManager skips
        super().__init__): arms the Snapshots handle, derives the
        deterministic run key, and loads the latest snapshot — epoch
        continuity is unconditional, training-state restore happens
        only under resume=True."""
        self.working_dir = working_dir
        self.resume = bool(resume)
        self.snapshot_interval = max(int(snapshot_interval or 50), 1)
        self.preempt_after_snapshots = preempt_after_snapshots
        self._snapshots_taken = 0
        #: The manager epoch token stamped on every RPC (_stamp) and
        #: persisted in each snapshot. Workers fence lower epochs with
        #: a typed rejection (dist_worker._check_epoch) — the
        #: split-brain close the per-instance namespacing of the
        #: feature-parallel round left open.
        self.epoch = 1
        self._snaps = None
        self._resume_state: Optional[Dict[str, Any]] = None
        if not working_dir:
            return
        from ydf_tpu.utils.snapshot import Snapshots

        self._snaps = Snapshots(working_dir, max_kept=2)
        # Deterministic run key: a resumed manager reattaches to the
        # SAME worker-state namespace its dead predecessor used — which
        # is exactly why the epoch fence (not namespacing) must protect
        # the workers from the predecessor's zombie frames.
        self.key_id = f"dist-{self._ckpt_fingerprint()[:16]}"
        self._prepare_resume()

    def _ckpt_mode_fields(self) -> tuple:
        """The shard-layout half of the snapshot fingerprint (the row
        manager overrides with its R×C grid and validation split).
        Worker COUNT is deliberately absent: resume is bit-identical
        across fleet sizes, so it must not invalidate a snapshot."""
        return ("feature", self.num_shards)

    def _ckpt_fingerprint(self) -> str:
        """sha1 identity of (dataset cache, shard layout, training
        config) — what a resume must match exactly. Mirrors the
        single-machine checkpointed driver's fingerprint discipline:
        resuming against different data or hyperparameters fails fast
        instead of silently mixing trees."""
        import hashlib

        fp = hashlib.sha1()
        fp.update(repr(self._ckpt_mode_fields()).encode())
        fp.update(
            repr(
                (
                    getattr(self.cache, "_meta", {}).get(
                        "request_fingerprint"
                    ),
                    self.n, self.F,
                    type(self.loss_obj).__name__, self.rule, self.cfg,
                    self.num_trees, self.shrinkage, self.subsample,
                    self.candidate_features, self.seed,
                    self.hist_impl, self.hist_subtract,
                    self.hist_quant, self.min_split_gain,
                )
            ).encode()
        )
        return fp.hexdigest()

    def _prepare_resume(self) -> None:
        state = self._snaps.latest()
        if state is None:
            if self.resume:
                log.info(
                    "dist: resume requested but no snapshot in "
                    f"{self.working_dir!r}; starting fresh"
                )
            return
        _idx, arrays, meta = state
        # Epoch continuity is UNCONDITIONAL: any new manager on this
        # working_dir attaches with a strictly higher epoch, so a
        # zombie predecessor's delayed frames are fenced even when the
        # operator starts fresh instead of resuming.
        self.epoch = int(meta.get("epoch", 0)) + 1
        if not self.resume:
            return
        if meta.get("fingerprint") != self._ckpt_fingerprint():
            raise ValueError(
                f"Distributed snapshot in {self.working_dir!r} was "
                "created with a different worker/shard configuration "
                "or dataset (cache layout, hyperparameters, "
                "YDF_TPU_HIST_* mode or seed differ from the current "
                "flags); refusing to resume. Delete the working "
                "directory or restore the original configuration."
            )
        self._resume_state = {"arrays": arrays, "meta": meta}

    def _restore_progress(self) -> Optional[Dict[str, Any]]:
        """Unpacks the resume snapshot into the training loop's
        accumulators (per-tree dicts, leaf values, losses, predictions,
        carried PRNG key). None when starting fresh."""
        if self._resume_state is None:
            return None
        arrays = self._resume_state["arrays"]
        meta = self._resume_state["meta"]
        done = int(meta["completed_trees"])
        trees_acc = [
            {
                f: np.asarray(arrays[f"tree_{f}"][t])
                for f in self._TREE_FIELDS
            }
            for t in range(done)
        ]
        return {
            "done": done,
            "trees_acc": trees_acc,
            "lvs_acc": [np.asarray(arrays["lvs"][t]) for t in range(done)],
            "tls": [float(v) for v in arrays["tls"]],
            "preds": jnp.asarray(arrays["preds"]),
            "key": jnp.asarray(arrays["key"]),
            "arrays": arrays,
        }

    def _restore_owner_map(self) -> None:
        """Re-applies the snapshot's shard→address ownership for
        addresses still in the (pruned) rotation, so a resumed manager
        reattaches each shard to the worker that most likely still
        holds it — the verify-or-re-ship load is idempotent either
        way."""
        if self._resume_state is None:
            return
        addrs = {
            self.pool.addr_str(i): i
            for i in range(len(self.pool.addresses))
        }
        saved = self._resume_state["meta"].get("owner_addrs") or []
        for sid, addr in enumerate(saved[: len(self.owner)]):
            if addr in addrs:
                self.owner[sid] = addrs[addr]

    def _attach_site(self) -> str:
        """Failpoint site of the initial shard placement: the resume
        reattach has its own (`dist.resume_attach`), so chaos schedules
        can target exactly the new-manager attach path."""
        return (
            "dist.resume_attach" if self._resume_state is not None
            else "dist.shard_load"
        )

    def _maybe_snapshot(self, done: int, trees_acc, lvs_acc, tls, preds,
                        key, extra_arrays: Optional[Dict[str, Any]] = None,
                        force: bool = False) -> bool:
        """Writes the tree-boundary snapshot when `done` sits on the
        snapshot cadence (or the final boundary, or forced by the
        preemption guard). Returns whether a snapshot was written."""
        if self._snaps is None or done == 0:
            return False
        if not (
            force
            or done % self.snapshot_interval == 0
            or done == self.num_trees
        ):
            return False
        failpoints.hit("dist.snapshot")
        t0 = time.perf_counter_ns()
        arrays: Dict[str, Any] = {
            f"tree_{f}": np.stack(
                [np.asarray(t[f]) for t in trees_acc]
            )
            for f in self._TREE_FIELDS
        }
        arrays["lvs"] = np.stack([np.asarray(v) for v in lvs_acc])
        # float(np.float32) losses are exact in f64 — the restored list
        # round-trips bit-identically.
        arrays["tls"] = np.asarray(tls, np.float64)
        arrays["preds"] = np.asarray(preds)
        arrays["key"] = np.asarray(key)
        if extra_arrays:
            arrays.update(extra_arrays)
        meta = {
            "completed_trees": int(done),
            "fingerprint": self._ckpt_fingerprint(),
            "epoch": int(self.epoch),
            "num_trees": int(self.num_trees),
            "mode": self._ckpt_mode_fields()[0],
            "owner_addrs": [
                self.pool.addr_str(w) for w in self.owner
            ],
        }
        self._snaps.save(done, arrays, meta)
        try:
            nbytes = os.path.getsize(self._snaps._payload_path(done))
        except OSError:
            nbytes = 0
        self.stats.observe_snapshot(time.perf_counter_ns() - t0, nbytes)
        return True

    def _guard_cm(self):
        """The SIGTERM/SIGINT preemption guard, armed only when
        snapshots exist to make the preemption resumable (without a
        working_dir a signal keeps its default disposition, as
        before)."""
        if self._snaps is None:
            return contextlib.nullcontext(None)
        from ydf_tpu.learners.gbt import _PreemptionGuard

        return _PreemptionGuard()

    def _tree_boundary(self, guard, done: int, trees_acc, lvs_acc, tls,
                       preds, key,
                       extra_arrays: Optional[Dict[str, Any]] = None
                       ) -> None:
        """Tree-boundary bookkeeping of a checkpointed run: the
        scheduled snapshot, the `_preempt_after_chunks` test hook
        (trigger after N snapshots — the same semantics as the
        single-machine checkpointed driver), and the forced-final-
        snapshot → TrainingPreempted exit when the guard tripped.

        Elastic membership applies HERE, before the snapshot check: the
        worker set may only change between trees (every merge inside a
        tree is pinned to one view) and it must work without a
        working_dir too."""
        self._apply_membership(done)
        if self._snaps is None:
            return
        saved = self._maybe_snapshot(
            done, trees_acc, lvs_acc, tls, preds, key, extra_arrays
        )
        if saved:
            self._snapshots_taken += 1
            if (
                self.preempt_after_snapshots is not None
                and self._snapshots_taken >= self.preempt_after_snapshots
                and guard is not None
                and not guard.triggered
            ):
                guard.trigger(_signal.SIGTERM)
        if guard is None or not guard.triggered:
            return
        if not saved:
            # Forced final snapshot: the preemption exit is only
            # resumable if the boundary just crossed is durable.
            self._maybe_snapshot(
                done, trees_acc, lvs_acc, tls, preds, key, extra_arrays,
                force=True,
            )
        from ydf_tpu.learners.gbt import TrainingPreempted

        if telemetry.ENABLED:
            telemetry.flight_record(
                "preempt", signal=guard.signal_name,
                completed_trees=done, num_trees=self.num_trees,
            )
            telemetry.flush()
            telemetry.flight_dump("preempt")
        raise TrainingPreempted(
            f"distributed training preempted by {guard.signal_name}: "
            f"snapshot at {done}/{self.num_trees} trees in "
            f"{self.working_dir!r} is resumable "
            "(resume_training=True / --resume)"
        )

    def _apply_membership(self, done: int) -> None:
        """Applies the membership channel's due join/leave events at
        tree boundary `done`, then remaps every shard onto the new
        worker set with the resume machinery:

          * epoch bump — fences the old view: a delayed frame from a
            worker that left (or a zombie manager's) is rejected by
            the worker-side `_check_epoch`, and load verbs ADOPT the
            higher epoch, which is exactly what re-admits a joiner.
          * owner recompute + `_load_shards(with_state=False)` per
            group — verify-or-re-ship: a worker that already holds a
            shard verifies it idempotently, a joiner receives it. No
            per-tree state ships because every tree's first layer
            request carries `reset=True`.
          * a failed JOIN (unreachable candidate, or the
            `dist.member_join` chaos site) quarantines the candidate
            out again and re-queues the event for a later boundary
            (bounded by MembershipChannel.MAX_JOIN_RETRIES); a LEAVE of
            a non-member is a no-op and the last worker is never
            removed. Leavers keep their resident state until the
            worker-side idle TTL reaps it.

        Bit-identity: all histogram/validation merges are order-fixed
        and worker-count invariant, so a remap between trees cannot
        change a single bit of the model."""
        ch = self.membership
        if ch is None:
            return
        events = ch.claim(done)
        if not events:
            return
        changed = False
        for ev in events:
            op, addr = ev["op"], ev["address"]
            if op == "join":
                try:
                    failpoints.hit("dist.member_join")
                    widx = self.pool.add_worker(addr)
                    resp = self.pool.request(
                        widx, {"verb": "ping"},
                        timeout_s=min(10.0, self.rpc_timeout_s),
                    )
                    if not resp.get("ok"):
                        raise ConnectionError(
                            f"join probe refused: {resp}"
                        )
                except (
                    failpoints.FailpointError, OSError, ConnectionError
                ) as e:
                    # Quarantine-and-retry: the candidate leaves the
                    # rotation again (it never owned a shard) and the
                    # event re-queues for a later boundary, bounded.
                    try:
                        self.pool.remove_worker(addr, drain_timeout_s=0.0)
                    except ValueError:
                        pass
                    requeued = ch.requeue(ev, done + 1)
                    log.info(
                        f"dist: worker join {addr} failed at tree "
                        f"{done} ({type(e).__name__}: {e}); "
                        + (
                            "re-queued" if requeued
                            else "dropped (retry budget spent)"
                        )
                    )
                    if telemetry.ENABLED:
                        telemetry.counter(
                            "ydf_dist_membership_total", op="join_failed"
                        ).inc()
                    continue
                changed = True
            else:
                try:
                    if not self.pool.remove_worker(
                        addr, drain_timeout_s=5.0
                    ):
                        continue  # not a member — idempotent
                except ValueError:
                    log.info(
                        f"dist: refusing leave of {addr} at tree "
                        f"{done} — it is the last worker"
                    )
                    continue
                self.stats.drop_worker_shards(addr)
                changed = True
            ch.note_applied(ev, done)
            if telemetry.ENABLED:
                telemetry.counter(
                    "ydf_dist_membership_total", op=op
                ).inc()
        if not changed:
            return
        self.epoch += 1
        W = len(self.pool.addresses)
        n_units = len(self.owner)
        self.owner = [k % W for k in range(n_units)]
        for widx, sids in sorted(self._groups(range(n_units)).items()):
            self._load_shards(widx, sids, with_state=False)
        log.info(
            f"dist: membership changed at tree boundary {done}: "
            f"{W} workers, epoch {self.epoch}"
        )

    # ---- RPC plumbing ------------------------------------------------ #

    def _stamp(self, req: Dict[str, Any], widx: int) -> Dict[str, Any]:
        """Stamps the manager's trace context into the request frame
        (`_trace` beside `verb` — just another dict key, so the
        pickle+HMAC framing is untouched at the byte level): the
        worker's per-request span records it, which is what makes the
        merged cross-process trace attributable. Must be called on the
        thread holding the open span (the training loop's), not the
        fan-out executor's.

        Every request additionally carries the manager's EPOCH token —
        the worker-side fence (dist_worker._check_epoch) rejects lower
        epochs with a typed response, so a zombie manager (or a delayed
        in-flight frame of a dead run) can never double-apply routing
        or histogram state."""
        req["epoch"] = self.epoch
        if telemetry.ENABLED:
            ctx = telemetry.current_context()
            if ctx is not None:
                req["_trace"] = {
                    **ctx, "worker_index": widx % len(self.pool.addresses)
                }
        return req

    def _request(self, widx: int, req: Dict[str, Any], site: str,
                 rpc_record: Optional[Dict[int, Tuple[int, Optional[int]]]]
                 = None):
        """One RPC with failpoint injection + latency accounting.
        Transport failures (including the straggler timeout) raise
        ConnectionError/OSError for the caller's recovery logic.
        `rpc_record[widx] = (wall_ns, handle_ns)` collects per-worker
        walls for the layer's compute/net/wait attribution."""
        failpoints.hit(site)
        t0 = time.perf_counter_ns()
        resp = self.pool.request(
            widx, req, timeout_s=self.rpc_timeout_s
        )
        wall_ns = time.perf_counter_ns() - t0
        self.stats.observe_rpc(req["verb"], wall_ns)
        if rpc_record is not None and isinstance(resp, dict):
            rpc_record[widx] = (wall_ns, resp.get("_handle_ns"))
        return resp

    def _state_payload(self) -> Dict[str, Any]:
        return {
            "slot": self.slot, "hist_slot": self.hist_slot,
            "leaf_id": self.leaf_id, "pos": self.pos,
            "hist_stats": self.cur_hist_stats,
            "qscale": self.cur_qscale,
        }

    def _pick_replacement(self, after: int) -> int:
        """Next healthy worker for a reassigned shard, waiting out
        quarantines with the pool's jittered backoff. Raises when the
        whole fleet stays unreachable past the retry budget."""
        for attempt in range(self.pool.retry_attempts):
            idx = self.pool.pick_worker(after)
            if idx is not None:
                return idx
            time.sleep(self.pool.backoff_delay(attempt))
        raise DistributedTrainingError(
            "no reachable worker to take over a feature shard "
            f"(all {len(self.pool.addresses)} quarantined)"
        )

    def _load_shards(self, widx: int, sids: List[int],
                     with_state: bool,
                     site: str = "dist.shard_load") -> int:
        """Delivers shards (plus, on recovery, the authoritative state)
        to a worker; on transport failure moves on to the next healthy
        worker; on a corruption report re-slices the shard from the
        verified bins.npy (byte-identical) and retries. Returns the
        worker index that ended up owning the shards. `site` is the
        failpoint of this exchange (`dist.resume_attach` during a
        resumed manager's initial reattach)."""
        rebuilt = False
        for attempt in range(self.pool.retry_attempts):
            req = {
                "verb": "load_cache_shard", "key": self.key_id,
                "shards": list(sids), "cache_dir": self.cache.path,
            }
            if with_state:
                req["state"] = self._state_payload()
            try:
                resp = self._request(
                    widx, self._stamp(req, widx), site
                )
            except (OSError, ConnectionError) as e:
                log.debug(
                    f"dist: shard load on {self.pool.addr_str(widx)} "
                    f"failed ({e}); reassigning"
                )
                self.pool.mark_failed(widx)
                self.stats.recoveries += 1
                self.stats.drop_worker_shards(self.pool.addr_str(widx))
                widx = self._pick_replacement(widx + 1)
                continue
            if resp.get("ok"):
                self.pool.mark_ok(widx)
                for sid in sids:
                    self.owner[sid] = widx
                self._note_shard_load(widx, resp)
                return widx
            if resp.get("stale_epoch"):
                raise DistributedTrainingError(
                    f"fenced out: worker {self.pool.addr_str(widx)} "
                    f"holds manager epoch {resp.get('have_epoch')} > "
                    f"ours ({self.epoch}) — a newer manager has "
                    "attached to this run; this manager must stop"
                )
            if resp.get("corrupt") and not rebuilt:
                # Worker-side crc caught a corrupt slice: re-slice it
                # from the (fully verified) bins.npy and try again —
                # the rebuilt bytes are identical, so training stays
                # bit-identical.
                log.info(
                    f"dist: cache shard(s) {sids} corrupt on load "
                    f"({resp.get('error')}); rebuilding from bins.npy"
                )
                if telemetry.ENABLED:
                    telemetry.counter(
                        "ydf_dist_shard_corruption_total"
                    ).inc()
                for sid in sids:
                    self.cache.rebuild_feature_shard(sid)
                self.stats.shard_rebuilds += len(sids)
                rebuilt = True
                continue
            raise DistributedTrainingError(
                f"worker {self.pool.addr_str(widx)} failed shard load: "
                f"{resp}"
            )
        raise DistributedTrainingError(
            f"could not place shards {sids} on any worker within "
            f"{self.pool.retry_attempts} attempts"
        )

    def _fan_out(self, groups: Dict[int, List[int]], make_req, site: str,
                 rpc_record=None):
        """Concurrent per-worker RPCs (the workers compute their
        histogram slices in parallel); results are handled in sorted
        worker order so recovery decisions stay deterministic. Returns
        [(widx, sids, resp_or_exception)]. Requests are built AND
        trace-stamped on this (the caller's) thread — the open
        dist.layer span is thread-local."""
        order = sorted(groups)
        with ThreadPoolExecutor(max_workers=max(len(order), 1)) as ex:
            futs = {
                w: ex.submit(
                    self._request, w,
                    self._stamp(make_req(groups[w]), w), site,
                    rpc_record,
                )
                for w in order
            }
            out = []
            for w in order:
                try:
                    out.append((w, groups[w], futs[w].result()))
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    out.append((w, groups[w], e))
        return out

    def _groups(self, sids) -> Dict[int, List[int]]:
        g: Dict[int, List[int]] = {}
        for sid in sids:
            g.setdefault(self.owner[sid], []).append(sid)
        return g

    def _handle_failure(self, widx: int, sids: List[int]) -> None:
        """Transport failure / straggler timeout on `widx`: quarantine
        it and move its shards (with the authoritative state) to the
        next healthy worker — the reference's worker-reassignment
        semantics. Before moving on, a best-effort telemetry drain
        rescues the dying worker's last spans (a worker that dropped
        one connection may still answer a short get_telemetry; one that
        is really gone costs a bounded timeout)."""
        self.pool.mark_failed(widx)
        self.stats.recoveries += 1
        # The quarantined worker's resident-bytes report leaves the
        # shard-fleet ledger now — its shards are about to live on the
        # replacement, whose load response re-adds them.
        self.stats.drop_worker_shards(self.pool.addr_str(widx))
        if telemetry.ENABLED:
            telemetry.counter("ydf_dist_recoveries_total").inc()
            self._drain_worker_telemetry([widx], timeout_s=5.0)
        new_w = self._pick_replacement(widx + 1)
        self._load_shards(new_w, sids, with_state=True)

    def _note_shard_load(self, widx: int, resp: Dict[str, Any]) -> None:
        """Resource + config bookkeeping on a successful shard load:
        records the worker's reported resident shard/state bytes (the
        dist_shard_bytes accounting) and compares the worker's resolved
        bit-identity-relevant env knobs against the manager's — drift
        (e.g. a worker still running YDF_TPU_HIST_QUANT=f32 under an
        int8 manager) is logged HERE, at load_data time, instead of
        surfacing as a confusing report later."""
        addr = self.pool.addr_str(widx)
        sb = resp.get("shard_bytes")
        if isinstance(sb, int):
            self.stats.shard_bytes[addr] = sb
            if telemetry.ENABLED:
                telemetry.mem_set("dist_shard_fleet",
                                  sum(self.stats.shard_bytes.values()))
        wcfg = resp.get("config")
        if not isinstance(wcfg, dict) or not wcfg:
            return
        try:
            from ydf_tpu.config import DIST_CONFIG_KEYS, resolved_env_config

            mine = resolved_env_config()
        except Exception:
            return
        for key in DIST_CONFIG_KEYS:
            if key in wcfg and wcfg[key] != mine.get(key):
                self.stats.config_mismatches += 1
                log.info(
                    f"dist: config mismatch with worker {addr}: "
                    f"{key}={wcfg[key]!r} (manager: {mine.get(key)!r})"
                )
                if telemetry.ENABLED:
                    telemetry.counter(
                        "ydf_dist_config_mismatch_total", key=key
                    ).inc()

    # ---- cross-process telemetry drain / trace merge ----------------- #

    def _drain_worker_telemetry(
        self, indices: Optional[List[int]] = None,
        timeout_s: Optional[float] = None,
    ) -> None:
        """Drains each worker's span buffer + metrics snapshot via the
        `get_telemetry` verb and merges the spans into the manager's
        trace buffer, producing ONE chrome-tracing file at the next
        flush. Worker clocks are corrected onto the manager's
        perf_counter epoch by the PING RTT midpoint: ping handling is
        a dict literal, so its clock sample sits at the RPC midpoint
        within ~rtt/2, and taking the minimum-RTT of a few pings
        bounds the error tightly. With the best (t_send, sample,
        t_recv) triple,

            offset = worker_clock − (t_send + rtt/2)

        and every drained timestamp shifts by −offset — nesting under
        the manager's layer spans survives cross-host clock skew. Each
        worker gets its own pid row (real pid when the worker is a
        separate process, synthetic for in-process fleets) plus a
        process_name metadata event naming its address. Best-effort:
        an unreachable worker is skipped, never an error."""
        if not telemetry.ENABLED:
            return
        done = set()
        for widx in (
            indices if indices is not None
            else range(len(self.pool.addresses))
        ):
            addr = self.pool.addr_str(widx)
            if addr in done:
                continue
            done.add(addr)
            t_out = timeout_s or min(30.0, self.rpc_timeout_s)
            try:
                # Clock offset from the minimum-RTT ping of a few: ping
                # handling is trivial, so its sample sits at the RPC
                # midpoint within ~rtt/2 (get_telemetry's own handling
                # is drain + snapshot — tens of ms on first call, which
                # would bias a midpoint estimate; measured +31 ms).
                # One throwaway warm ping first: with pooled
                # connections the sampled pings must ride an ALREADY
                # ESTABLISHED socket, so the RTT midpoint reflects
                # network round-trip only — a ping that pays a TCP
                # connect (fresh pool, or a reconnect after a drop)
                # would bias the offset by ~connect/2.
                self.pool.request(
                    widx, {"verb": "ping"},
                    timeout_s=min(10.0, t_out),
                )
                offset_ns = None
                best_rtt = None
                for _ in range(3):
                    t_send = time.perf_counter_ns()
                    pong = self.pool.request(
                        widx, {"verb": "ping"},
                        timeout_s=min(10.0, t_out),
                    )
                    t_recv = time.perf_counter_ns()
                    if not pong.get("ok") or "clock_ns" not in pong:
                        break
                    rtt = t_recv - t_send
                    if best_rtt is None or rtt < best_rtt:
                        best_rtt = rtt
                        offset_ns = pong["clock_ns"] - (
                            t_send + rtt // 2
                        )
                resp = self.pool.request(
                    widx, {"verb": "get_telemetry"}, timeout_s=t_out
                )
            except (OSError, ConnectionError):
                continue
            if not isinstance(resp, dict) or not resp.get("ok"):
                continue
            if isinstance(resp.get("rss_bytes"), int):
                # Per-worker RSS rides the drain — the distributed half
                # of the memory ledger (training_logs["distributed"]
                # worker_rss_bytes).
                self.stats.worker_rss_bytes[addr] = resp["rss_bytes"]
            if offset_ns is None:
                # No clock-bearing ping answered (protocol anomaly):
                # merge uncorrected rather than apply a garbage offset.
                offset_ns = 0
            wpid = resp.get("pid")
            if wpid is None or wpid == os.getpid():
                # In-process fleet: synthesize a distinct pid row per
                # worker so the trace still shows per-worker lanes.
                wpid = 1_000_000 + (widx % len(self.pool.addresses))
            merged = [{
                "name": "process_name", "ph": "M", "pid": wpid,
                "cat": "ydf_tpu",
                "args": {"name": f"worker {addr}"},
            }]
            for ev in resp.get("events", []):
                ev = dict(ev)
                if "ts" in ev:
                    ev["ts"] = ev["ts"] - offset_ns / 1000.0
                ev["pid"] = wpid
                merged.append(ev)
            telemetry.ingest_events(merged)
            n = len(merged) - 1
            self.stats.drained_events[addr] = (
                self.stats.drained_events.get(addr, 0) + n
            )
            if telemetry.ENABLED:
                telemetry.counter(
                    "ydf_dist_telemetry_drained_events_total"
                ).inc(n)

    def _exchange(self, sids: List[int], make_req, site: str,
                  on_ok, rpc_record=None) -> None:
        """Generic resilient fan-out: retries each shard group through
        failures, reassignments, and worker-restart need_shard replies
        until every shard in `sids` has answered."""
        pending = set(sids)
        for _attempt in range(4 * self.pool.retry_attempts):
            if not pending:
                return
            for widx, group, resp in self._fan_out(
                self._groups(sorted(pending)), make_req, site,
                rpc_record,
            ):
                if isinstance(resp, failpoints.FailpointError):
                    raise resp
                if isinstance(resp, BaseException):
                    if not isinstance(resp, (OSError, ConnectionError)):
                        raise resp
                    self._handle_failure(widx, group)
                    continue
                if resp.get("stale_epoch"):
                    # The fencing contract's manager half: a rejection
                    # means a NEWER manager attached to this run's
                    # worker state — continuing would race two
                    # managers, so this one stops loudly.
                    raise DistributedTrainingError(
                        "fenced out: worker "
                        f"{self.pool.addr_str(widx)} holds manager "
                        f"epoch {resp.get('have_epoch')} > ours "
                        f"({self.epoch}) — a newer manager has "
                        "attached to this run; this manager must stop"
                    )
                if resp.get("need_shard"):
                    # Worker restarted in place: re-ship shard + state
                    # to the SAME address and retry.
                    self.stats.recoveries += 1
                    self._load_shards(widx, group, with_state=True)
                    continue
                if not resp.get("ok"):
                    raise DistributedTrainingError(
                        f"worker {self.pool.addr_str(widx)} failed "
                        f"{site}: {resp}"
                    )
                on_ok(widx, group, resp)
                pending -= set(group)
        raise DistributedTrainingError(
            f"shards {sorted(pending)} unanswered after retries ({site})"
        )

    # ---- the training loop ------------------------------------------ #

    def train(self):
        """Runs the boosting loop; returns (stacked TreeArrays
        [T, 1, ...], leaf_values [T, 1, N, 1], logs) in the exact
        layout learners/gbt.py:_train_gbt produces."""
        cfg = self.cfg
        L, B, N = cfg.frontier, cfg.num_bins, cfg.max_nodes
        D = cfg.max_depth
        S = self.rule.num_stats
        labels = np.asarray(self.cache.labels)
        w = self.cache.sample_weights
        w_tr = (
            np.asarray(w, np.float32) if w is not None
            else np.ones((self.n,), np.float32)
        )
        y_j = jnp.asarray(labels)
        w_j = jnp.asarray(w_tr)

        t0_ns = time.perf_counter_ns()
        # Keep going with the workers that answer (reference distribute
        # semantics); raises only when NONE does. Shard ownership is
        # (re)computed over the pruned rotation.
        self.pool.ping_all(drop_unreachable=True)
        self.owner = [
            k % len(self.pool.addresses) for k in range(self.num_shards)
        ]
        self._restore_owner_map()
        # Initial shard placement: shard k → worker k % W (snapshot
        # ownership preferred on resume). The load verb is the reattach
        # handshake too: crc-verified shard load + epoch adoption,
        # idempotent for a worker that already holds the shard.
        attach_site = self._attach_site()
        for widx, sids in self._groups(range(self.num_shards)).items():
            self._load_shards(widx, sids, with_state=False,
                              site=attach_site)

        preds, init_pred = _j_init(
            y_j, w_j, loss_obj=self.loss_obj, n=self.n
        )
        key = jax.random.PRNGKey(self.seed)
        trees_acc: List[Dict[str, np.ndarray]] = []
        lvs_acc: List[np.ndarray] = []
        tls: List[float] = []
        start_it = 0
        rs = self._restore_progress()
        if rs is not None:
            # Resume from the tree boundary: forest-so-far, losses,
            # predictions and the CARRIED key restore exactly; tree
            # start re-derives gradients/quant grid from them, so the
            # continuation is bit-identical to an uninterrupted run.
            start_it = rs["done"]
            trees_acc, lvs_acc, tls = (
                rs["trees_acc"], rs["lvs_acc"], rs["tls"]
            )
            preds, key = rs["preds"], rs["key"]
            log.info(
                f"dist: resuming at tree {start_it}/{self.num_trees} "
                f"from {self.working_dir!r} (manager epoch {self.epoch})"
            )

        with self._guard_cm() as guard:
            for it in range(start_it, self.num_trees):
                with telemetry.span("dist.tree") as sp:
                    if telemetry.ENABLED:
                        sp.set(iteration=it)
                    preds, key, tree_np, lv, tl = self._train_tree(
                        it, key, preds, y_j, w_j, L, B, N, D, S
                    )
                trees_acc.append(tree_np)
                lvs_acc.append(np.asarray(lv))
                tls.append(float(tl))
                if log.is_debug():
                    log.debug(
                        f"dist gbt: iter {it + 1}/{self.num_trees} "
                        f"train_loss={tls[-1]:.6g}"
                    )
                self._tree_boundary(
                    guard, it + 1, trees_acc, lvs_acc, tls, preds, key
                )

        # Cross-process observability: drain every worker's span buffer
        # and metrics snapshot, clock-correct onto this host's epoch,
        # and merge into the manager's buffer — the next flush writes
        # ONE chrome-tracing file with per-worker pid rows.
        self._drain_worker_telemetry()

        wall_ns = time.perf_counter_ns() - t0_ns
        from ydf_tpu.ops.grower import TreeArrays

        def stack(field):
            return jnp.asarray(
                np.stack([t[field] for t in trees_acc])[:, None]
            )  # [T, K=1, ...]

        forest_stacked = TreeArrays(
            feature=stack("feature"),
            threshold_bin=stack("threshold_bin"),
            is_cat=stack("is_cat"),
            is_set=stack("is_set"),
            cat_mask=stack("cat_mask"),
            left=stack("left"),
            right=stack("right"),
            is_leaf=stack("is_leaf"),
            leaf_stats=stack("leaf_stats"),
            num_nodes=jnp.asarray(
                np.asarray([t["num_nodes"] for t in trees_acc])[:, None]
            ),
        )
        leaf_values = jnp.asarray(np.stack(lvs_acc)[:, None])  # [T,1,N,1]
        T = self.num_trees
        logs = {
            "train_loss": np.asarray(tls, np.float32),
            "valid_loss": np.zeros((T,), np.float32),
            "initial_predictions": np.asarray(init_pred),
            "oblique_w": np.zeros((T, 0, 0), np.float32),
            "oblique_b": np.zeros((T, 0, B - 1), np.float32),
            "vs_a": np.zeros((T, 0, 0), np.float32),
            "vs_b": np.zeros((T, 0, 0), np.float32),
            # Pre-resume trees carry no wall (they ran in a dead
            # manager); their iteration records report 0 seconds, like
            # the single-machine checkpointed driver's.
            "chunk_walls": [(start_it, T - start_it, t0_ns, wall_ns)],
            "distributed": {
                "workers": len(self.pool.addresses),
                "feature_shards": self.num_shards,
                "hist_quant": self.hist_quant,
                "epoch": int(self.epoch),
                "resumed_from": int(start_it),
                **self.stats.summary(),
                **_transport_fields(self.pool),
            },
        }
        return forest_stacked, leaf_values, logs

    def _train_tree(self, it, key, preds, y_j, w_j, L, B, N, D, S):
        key, kk, hist_stats, qscale, total = _j_tree_prologue(
            y_j, w_j, preds, key, it,
            loss_obj=self.loss_obj, subsample=self.subsample,
            hist_quant=self.hist_quant,
        )
        self.cur_hist_stats = np.asarray(hist_stats)
        self.cur_qscale = None if qscale is None else np.asarray(qscale)
        self.stats.stats_bytes += self.cur_hist_stats.nbytes
        if telemetry.ENABLED:
            telemetry.counter("ydf_dist_stats_bytes_total").inc(
                self.cur_hist_stats.nbytes
            )
        total_np = np.asarray(total)

        # Per-tree manager state (mirrors _grow_tree_jit's init).
        i32 = np.int32
        W_words = (B + 31) // 32
        tree = {
            "feature": np.full((N + 1,), -1, i32),
            "threshold_bin": np.zeros((N + 1,), i32),
            "is_cat": np.zeros((N + 1,), bool),
            "is_set": np.zeros((N + 1,), bool),
            "cat_mask": np.zeros((N + 1, W_words), np.uint32),
            "left": np.zeros((N + 1,), i32),
            "right": np.zeros((N + 1,), i32),
            "is_leaf": np.ones((N + 1,), bool),
            "leaf_stats": np.zeros((N + 1, S), np.float32),
        }
        tree["leaf_stats"][0] = total_np
        frontier_id = np.full((L + 1,), N, i32)
        frontier_id[0] = 0
        node_stats = np.zeros((L + 1, S), np.float32)
        node_stats[0] = total_np
        self.slot[:] = 0
        self.hist_slot[:] = 0
        self.leaf_id[:] = 0
        self.pos = (it, 0)
        num_nodes = jnp.asarray(1, jnp.int32)
        sub_state = None  # (parent_hist jnp, small_is_left jnp, Lh)
        pending_route = None
        key_t = kk

        from ydf_tpu.parallel.dist_worker import (
            apply_route_tables,
            pack_bits,
        )

        for depth in range(D):
            # One manager span per layer: worker histogram-RPC spans
            # nest under it in the merged trace, and the layer's wall
            # is attributed into compute/net/wait from the fan-out's
            # per-worker RPC walls (observe_layer).
            t_layer0 = time.perf_counter_ns()
            hist_rpcs: Dict[int, Tuple[int, Optional[int]]] = {}
            with telemetry.span("dist.layer") as lsp:
                if telemetry.ENABLED:
                    lsp.set(tree=it, layer=depth)
                key_t, k_gain, k_feat = jax.random.split(
                    jax.random.fold_in(key_t, depth), 3
                )
                children = depth + 1 < D
                Ld = min(2 ** depth, L)

                # ---- 1. histogram gather (workers, feature-sliced) - #
                if sub_state is not None:
                    _ph, _sil, Lh = sub_state
                    num_slots = Lh
                    compact = (
                        (self.n // 2 + Lh + 8)
                        if self.hist_impl == "segment" else 0
                    )
                else:
                    num_slots = Ld
                    compact = 0
                base_req = {
                    "verb": "build_histograms", "key": self.key_id,
                    "tree": it, "layer": depth, "reset": depth == 0,
                    "num_slots": num_slots, "num_bins": B,
                    "impl": self.hist_impl, "quant": self.hist_quant,
                    "compact": compact,
                }
                if depth == 0:
                    base_req["stats"] = {
                        "hist_stats": self.cur_hist_stats,
                        "qscale": self.cur_qscale,
                    }
                if pending_route is not None:
                    base_req["route"] = pending_route

                slices: Dict[int, np.ndarray] = {}

                def on_hist(widx, group, resp, _slices=slices):
                    for k, h in resp["hists"].items():
                        _slices[int(k)] = h
                        self.stats.reduce_bytes += h.nbytes
                    if telemetry.ENABLED:
                        telemetry.counter(
                            "ydf_dist_reduce_bytes_total"
                        ).inc(
                            sum(h.nbytes for h in resp["hists"].values())
                        )

                self._exchange(
                    list(range(self.num_shards)),
                    lambda sids, _r=base_req: {**_r, "shards": sids},
                    "dist.histogram_rpc",
                    on_hist,
                    rpc_record=hist_rpcs,
                )
                t_m0 = time.perf_counter_ns()
                hist_np = np.concatenate(
                    [slices[k] for k in range(self.num_shards)], axis=1
                )  # [num_slots, F, B, S] — shard order == feature order
                self.stats.observe_merge(time.perf_counter_ns() - t_m0)

                if sub_state is not None:
                    parent_hist, small_is_left, Lh = sub_state
                    hist = _j_sibling_reconstruct(
                        jnp.asarray(hist_np), parent_hist, small_is_left,
                        Ld=Ld,
                    )
                else:
                    hist = jnp.asarray(hist_np)

                # ---- 2. split search (the grower's shared seam) ---- #
                out = _j_layer_step(
                    hist, jnp.asarray(node_stats[:Ld]),
                    jnp.asarray(frontier_id[:Ld] < N),
                    jnp.asarray(frontier_id[:Ld]), num_nodes,
                    k_gain, k_feat,
                    rule=self.rule, L=L, B=B, N=N, Fn=self.Fn,
                    Fc=self.Fc,
                    O=1, min_examples=self.cfg.min_examples,
                    min_split_gain=self.min_split_gain,
                    candidate_features=self.candidate_features,
                    num_valid_features=None, children=children,
                    subtract=self.hist_subtract,
                )
                dec = out["dec"]
                num_nodes = dec.num_nodes
                do_split = np.asarray(dec.do_split)
                split_rank = np.asarray(dec.split_rank)
                wid = np.asarray(dec.wid)
                left_id = np.asarray(dec.left_id)
                right_id = np.asarray(dec.right_id)
                left_stats = np.asarray(dec.left_stats)
                right_stats = np.asarray(dec.right_stats)
                route_f = np.asarray(dec.route_f)
                go_left_bins = np.asarray(dec.go_left_bins)

                # ---- 3. node writes (manager-side tree arrays) ----- #
                tree["feature"][wid] = np.asarray(dec.best_f_store)
                tree["threshold_bin"][wid] = np.asarray(dec.best_t)
                tree["is_cat"][wid] = np.asarray(dec.is_cat_split)
                tree["is_set"][wid] = np.asarray(dec.is_set_split)
                tree["cat_mask"][wid] = np.asarray(out["mask"])
                tree["left"][wid] = left_id
                tree["right"][wid] = right_id
                tree["is_leaf"][wid] = False
                tree["leaf_stats"][left_id] = left_stats
                tree["leaf_stats"][right_id] = right_stats
                # Trash row N collects every masked write; re-pin it.
                tree["feature"][N] = -1
                tree["is_leaf"][N] = True

                # ---- 4. split broadcast / owner routing ------------ #
                hmap_np = (
                    np.asarray(out["hmap"]) if "hmap" in out
                    else np.arange(L + 1, dtype=i32)
                )
                tables = {
                    "L": L, "children": children,
                    "do_split": _pad_to(do_split, L + 1, False),
                    "route_f": _pad_to(route_f, L + 1, 0),
                    "go_left_bins": _pad_to(go_left_bins, L + 1, False),
                    "left_id": _pad_to(left_id, L + 1, N),
                    "right_id": _pad_to(right_id, L + 1, N),
                    "split_rank": _pad_to(split_rank, L + 1, 0),
                    "hmap": hmap_np,
                }
                merged = np.zeros(self.n, bool)
                # Only shards owning a split feature route ("only one
                # worker routes per split"); others receive the merged
                # bitmap with the next layer's histogram request.
                routing_sids = [
                    sid for sid, (lo, hi) in enumerate(self.col_ranges)
                    if np.any(do_split & (route_f >= lo) & (route_f < hi))
                ]
                split_req = {
                    "verb": "apply_split", "key": self.key_id,
                    "tree": it, "layer": depth,
                    "tables": {
                        "do_split": tables["do_split"],
                        "route_f": tables["route_f"],
                        "go_left_bins": tables["go_left_bins"],
                    },
                }

                def on_bits(widx, group, resp, _m=merged):
                    from ydf_tpu.parallel.dist_worker import unpack_bits

                    _m |= unpack_bits(resp["bits"], self.n)

                if routing_sids:
                    self._exchange(
                        routing_sids,
                        lambda sids, _r=split_req: {**_r, "shards": sids},
                        "dist.split_broadcast",
                        on_bits,
                    )
                self.slot, self.leaf_id, self.hist_slot = (
                    apply_route_tables(
                        self.slot, self.leaf_id, merged, tables
                    )
                )
                self.pos = (it, depth + 1)
                pending_route = {
                    "tables": tables, "go_left": pack_bits(merged)
                }

                # ---- 5. frontier + sibling carry for the next layer  #
                if children:
                    tgt_l = np.where(do_split, 2 * split_rank, L)
                    tgt_r = np.where(do_split, 2 * split_rank + 1, L)
                    frontier_id = np.full((L + 1,), N, i32)
                    frontier_id[tgt_l] = left_id
                    frontier_id[tgt_r] = right_id
                    frontier_id[L] = N
                    node_stats = np.zeros((L + 1, S), np.float32)
                    node_stats[tgt_l] = left_stats
                    node_stats[tgt_r] = right_stats
                    node_stats[L] = 0.0
                    if "sub" in out:
                        parent_next, small_next = out["sub"]
                        sub_state = (
                            parent_next, small_next, min(Ld, L // 2)
                        )
                    else:
                        sub_state = None
            self.stats.observe_layer(
                time.perf_counter_ns() - t_layer0, hist_rpcs
            )

        # ---- tree end: verify (optional) + prediction update -------- #
        if self.verify:
            self._verify_tree(it, D, N, pending_route, tree)
        nn = int(np.asarray(num_nodes))
        preds, lv, tl = _j_tree_epilogue(
            jnp.asarray(tree["leaf_stats"][:N]),
            jnp.asarray(self.leaf_id), preds, y_j, w_j,
            rule=self.rule, loss_obj=self.loss_obj,
            shrinkage=self.shrinkage,
        )
        tree_np = {k: v[:N] for k, v in tree.items()}
        tree_np["num_nodes"] = np.asarray(nn, i32)
        return preds, key, tree_np, np.asarray(lv), tl

    def _verify_tree(self, it, D, N, final_route, tree) -> None:
        """YDF_TPU_DIST_VERIFY: ask the worker owning shard 0 for its
        leaf assignment digest and per-leaf sums; a drifted worker is a
        protocol bug, surfaced loudly (never silently wrong trees)."""
        req = {
            "verb": "leaf_stats", "key": self.key_id,
            "tree": it, "layer": D, "route": final_route,
            "num_nodes_cap": N + 1,
        }
        resp = None

        def on_leaf(widx, group, r):
            nonlocal resp
            resp = r

        self._exchange([0], lambda sids: req, "dist.split_broadcast",
                       on_leaf)
        import zlib

        want_crc = zlib.crc32(np.ascontiguousarray(self.leaf_id).tobytes())
        if resp["leaf_crc"] != want_crc:
            raise DistributedTrainingError(
                f"worker leaf assignment diverged on tree {it}: "
                f"crc {resp['leaf_crc']:#x} != manager {want_crc:#x}"
            )
        counts = np.bincount(self.leaf_id, minlength=N + 1)
        if not np.array_equal(resp["leaf_counts"], counts):
            raise DistributedTrainingError(
                f"worker per-leaf counts diverged on tree {it}"
            )
        sums = resp.get("leaf_sums")
        if sums is not None:
            # Histogram-algebra leaf stats vs the worker's direct
            # per-row sums: same values up to float association (NOT
            # bit-compared), and only at populated LEAF nodes — the
            # manager array also carries internal-node stats.
            leafy = counts > 0
            mine = tree["leaf_stats"][: N + 1].astype(np.float64)[leafy]
            theirs = np.asarray(sums)[leafy]
            scale = np.maximum(np.abs(mine), 1.0)
            if not np.allclose(
                theirs / scale, mine / scale, atol=1e-3
            ):
                raise DistributedTrainingError(
                    f"worker per-leaf stat sums diverged on tree {it}"
                )

    def shutdown(self) -> None:
        pass  # workers are shared infrastructure; the manager owns no fleet
