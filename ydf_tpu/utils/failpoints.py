"""Process-wide fault-injection registry (failpoints).

Counterpart of the reference's fault-injection hook (MaybeSimulateFailure,
`ydf/utils/distribute/implementations/.../worker.cc:415-452` — a counter
that kills the worker on the N-th call), generalized into *named
injection sites* threaded through every recovery path this repo claims
to have: dataset-cache IO, snapshot save/load, worker RPC framing,
native kernel build/registration, and the boosting loop's chunk
boundary. The chaos suite (tests/test_chaos.py) drives randomized fault
schedules through these sites and asserts the recovered result is
bit-identical to the fault-free run.

Two ways to arm a failpoint, both speaking the same grammar:

  * Environment (whole-process, e.g. a training subprocess):

        YDF_TPU_FAILPOINTS="cache.write_chunk=error@2;worker.recv=drop_conn"

    Parsed and validated EAGERLY at import (same policy as
    YDF_TPU_HIST_IMPL): a typo'd site or action raises ValueError at the
    env boundary, never a silently-inert chaos run.

  * Programmatic (tests):

        with failpoints.active("snapshot.save=torn_write"):
            ...

Grammar: `site=action[@N]` entries joined by `;`. `@N` arms the spec on
the N-th hit of the site (1-based, default 1); every spec fires exactly
once, so a retried/resumed operation passes — which is precisely what
the recovery tests need to assert.

Actions:

  error       raise FailpointError at the armed hit.
  fail_once   alias of `error@1` (reads better for registration-style
              sites that are retried, e.g. native.register).
  drop_conn   raise ConnectionError — sites on the worker RPC path see a
              realistic transport failure instead of a foreign exception.
  torn_write  cooperative: hit() RETURNS "torn_write" and the site is
              responsible for simulating a crash mid-write (truncate the
              payload, then raise FailpointError). Only sites that
              document torn-write support accept it.
  stall       cooperative: hit() RETURNS "stall" and the site arms a
              per-block delay in the native work-stealing pool
              (pool_stats.block_stall() — adversarial steal schedules
              for the bit-stability suites). Only `pool.block_stall`
              accepts it.

Overhead contract: with YDF_TPU_FAILPOINTS unset, every instrumented
site costs one module-global boolean check (`ENABLED`, computed once at
import — never a per-call os.environ read) plus a function call at
chunk/RPC granularity; the headline bench is unaffected (acceptance
criterion of the robustness PR).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Dict, List, Optional

__all__ = [
    "FailpointError",
    "KNOWN_SITES",
    "ENABLED",
    "hit",
    "active",
    "parse",
    "fired_sites",
]


class FailpointError(RuntimeError):
    """An injected fault (actions `error` / `fail_once`, and the raise
    half of a cooperative `torn_write`). Deliberately NOT an OSError
    subclass: recovery paths that catch IO errors must be exercised via
    `drop_conn`, while FailpointError models an abrupt crash."""


#: Every instrumented site. parse() validates against this set so a
#: chaos schedule can never silently name a site that nothing hits.
KNOWN_SITES = frozenset(
    {
        # dataset/cache.py — per-chunk write of pass 2, and the final
        # (atomic) cache_meta.json publish.
        "cache.write_chunk",
        "cache.finalize",
        # utils/snapshot.py — payload write (torn_write-capable) and the
        # index update that follows it.
        "snapshot.save",
        "snapshot.index",
        # parallel/worker_service.py — worker-side request recv, the
        # window between recv and execution, and the response send.
        "worker.recv",
        "worker.handle",
        "worker.send",
        # ops/native_ffi.py — kernel compile and XLA FFI registration.
        "native.build",
        "native.register",
        # learners/gbt.py — boosting loop, after each chunk is stored
        # (under a working_dir: its snapshot durably saved).
        "gbt.chunk",
        # learners/gbt.py — OOM chaos hook at the boosting loop's
        # chunk boundaries: the injected fault is converted to a REAL
        # MemoryError so the flight-recorder's OOM path (reason "oom",
        # MemoryLedger snapshot in the dump header) is provable.
        "telemetry.oom",
        # parallel/dist_gbt.py — manager-side distributed-GBT RPCs:
        # shard load/re-ship, per-layer histogram gather, and the
        # split-broadcast/routing exchange. drop_conn surfaces as a
        # transport failure and drives the shard-reassignment recovery
        # path (chaos tests assert bit-identical models).
        "dist.shard_load",
        "dist.histogram_rpc",
        "dist.split_broadcast",
        # parallel/dist_row.py — the row-parallel tree-end
        # validation-routing/leaf-gather exchange (route_validation
        # verb); shares the shard_load/histogram_rpc sites above for
        # its other exchanges.
        "dist.validation_rpc",
        # parallel/dist_gbt.py — the manager's tree-boundary snapshot
        # write (preemption-safe distributed training): an injected
        # error crashes the manager between boundaries and the chaos
        # suite proves `--resume` from the previous snapshot is
        # bit-identical.
        "dist.snapshot",
        # parallel/dist_gbt.py — the resume-time worker reattach
        # (shard verify/re-ship by a NEW manager): drop_conn drives
        # the reattach's failover to the next healthy worker.
        "dist.resume_attach",
        # parallel/dist_cache.py — manager-side distributed cache-build
        # RPCs: the pass-1 ingest-stats exchange and the pass-2
        # bin-rows exchange. drop_conn surfaces as a transport failure
        # and drives the unit-reassignment recovery path (the chaos
        # tests assert the recovered cache is byte-identical); error
        # between the phases models a manager crash before the commit
        # record — reuse=True must rebuild.
        "dist.cache_ingest",
        "dist.cache_bin",
        # parallel/dist_worker.py — the worker-side manager-epoch
        # fence. An injected error makes the worker answer ONE request
        # with the typed stale-epoch rejection, as if a newer manager
        # had attached — the chaos handle for the zombie-manager
        # split-brain path (the worker's state is never mutated).
        "dist.epoch_fence",
        # utils/telemetry.py — span/metrics exporter. flush() swallows
        # the injected fault (export is observation): the chaos test
        # asserts a crashing exporter leaves training bit-identical.
        "telemetry.flush",
        # serving/registry.py — the request batcher's flush. The
        # injected fault is converted to a whole-batch deadline shed
        # (ServeOverloadError to exactly that flush's rows, survivors
        # of later flushes untouched) — the chaos handle for the
        # overload fan-out's exact-once contract.
        "serve.flush",
        # serving/fleet.py — router-side fleet sites (the manager-side
        # placement dist.* uses). fleet.replica_predict fires on the
        # predict RPC path: drop_conn surfaces as a dead replica and
        # drives the failover/quarantine rotation. fleet.swap fires
        # before each per-replica flip of a versioned hot-swap: an
        # injected error aborts the rollout mid-flip and drives the
        # rollback path (old version keeps serving everywhere).
        "fleet.replica_predict",
        "fleet.swap",
        # serving/fleet.py — elastic membership. fleet.join fires at
        # the start of add_replica's admission sequence, BEFORE any
        # cached deploy frame ships to the candidate: an injected fault
        # aborts the join and the candidate NEVER enters the rotation
        # (the serving fleet is untouched — the chaos suite proves a
        # replica killed mid-join is invisible to callers). fleet.drain
        # fires at the start of remove_replica, BEFORE any rotation
        # mutation: an injected fault leaves the fleet exactly as it
        # was, the departing replica still serving.
        "fleet.join",
        "fleet.drain",
        # parallel/dist_gbt.py — tree-boundary membership join of a
        # running distributed train (_apply_membership). An injected
        # fault quarantines the joiner (it never receives shards and
        # never enters the owner map), re-queues the join for a later
        # boundary (bounded retries), and the train continues on the
        # surviving set — chaos asserts the final model is
        # bit-identical to the fixed-membership run.
        "dist.member_join",
        # ops/pool_stats.py — adversarial-steal schedule for the native
        # work-stealing pool. The cooperative `stall` action makes
        # pool_stats.block_stall() arm a per-block busy-delay inside the
        # native workers (every stride-th block sleeps before running),
        # turning uniform block costs into a pathological straggler
        # pattern so idle lanes MUST steal. The bit-stability suites use
        # it to prove results are invariant under steal schedule, not
        # just thread count.
        "pool.block_stall",
    }
)

#: Sites that implement the cooperative torn_write action.
TORN_WRITE_SITES = frozenset({"snapshot.save"})

#: Sites that implement the cooperative stall action (native-pool
#: per-block delay; see pool_stats.block_stall()).
STALL_SITES = frozenset({"pool.block_stall"})

_ACTIONS = ("error", "fail_once", "drop_conn", "torn_write", "stall")


@dataclasses.dataclass
class _Spec:
    site: str
    action: str
    at: int  # 1-based hit index the spec arms on
    hits: int = 0
    fired: bool = False


def parse(spec: str) -> Dict[str, _Spec]:
    """Parses a failpoint schedule string into {site: _Spec}, validating
    sites, actions and counts eagerly. Empty/blank input → {}."""
    out: Dict[str, _Spec] = {}
    for entry in (spec or "").split(";"):
        entry = entry.strip()
        if not entry:
            continue
        site, sep, action = entry.partition("=")
        site = site.strip()
        action = action.strip()
        if not sep or not action:
            raise ValueError(
                f"YDF_TPU_FAILPOINTS entry {entry!r} is not of the form "
                "'site=action[@N]'"
            )
        if site not in KNOWN_SITES:
            raise ValueError(
                f"YDF_TPU_FAILPOINTS names unknown site {site!r}; "
                f"known sites: {sorted(KNOWN_SITES)}"
            )
        at = 1
        if "@" in action:
            action, _, n = action.partition("@")
            action = action.strip()
            n = n.strip()
            if not n.isdigit() or int(n) < 1:
                raise ValueError(
                    f"YDF_TPU_FAILPOINTS count {n!r} for site {site!r} "
                    "must be a positive integer"
                )
            at = int(n)
        if action not in _ACTIONS:
            raise ValueError(
                f"YDF_TPU_FAILPOINTS action {action!r} for site {site!r} "
                f"is not one of {list(_ACTIONS)}"
            )
        if action == "fail_once":
            action = "error"
            # fail_once always means "the first hit" regardless of @N.
            at = 1
        if action == "torn_write" and site not in TORN_WRITE_SITES:
            raise ValueError(
                f"site {site!r} does not support torn_write (supported: "
                f"{sorted(TORN_WRITE_SITES)}); use 'error' instead"
            )
        if action == "stall" and site not in STALL_SITES:
            raise ValueError(
                f"site {site!r} does not support stall (supported: "
                f"{sorted(STALL_SITES)}); use 'error' instead"
            )
        if site in out:
            raise ValueError(
                f"YDF_TPU_FAILPOINTS lists site {site!r} twice"
            )
        out[site] = _Spec(site=site, action=action, at=at)
    return out


_LOCK = threading.Lock()
# Eager env parse at import: a malformed schedule fails the first
# ydf_tpu import of the process, not the Nth training hour.
_SPECS: Dict[str, _Spec] = parse(os.environ.get("YDF_TPU_FAILPOINTS", ""))

#: Module-level constant when env-driven; flipped only by the
#: programmatic `active()` context manager. Sites read it through the
#: module (`failpoints.ENABLED`) so both stay O(attribute lookup).
ENABLED: bool = bool(_SPECS)


def hit(site: str) -> Optional[str]:
    """Called by an instrumented site. Free no-op unless a spec is armed
    for `site`. Raising actions raise here (FailpointError for error,
    ConnectionError for drop_conn); the cooperative torn_write action is
    RETURNED for the site to act on. Returns None when nothing fires."""
    if not ENABLED:
        return None
    with _LOCK:
        sp = _SPECS.get(site)
        if sp is None or sp.fired:
            return None
        sp.hits += 1
        if sp.hits != sp.at:
            return None
        sp.fired = True
        action, at = sp.action, sp.at
    try:
        # A firing failpoint is exactly the kind of event a post-mortem
        # wants in the flight recorder. Lazy import keeps this module
        # pure-stdlib at import time (the eager-env-validation subprocess
        # test relies on that), and flight_record is a free no-op when
        # telemetry is off.
        from ydf_tpu.utils import telemetry

        telemetry.flight_record(
            "failpoint", site=site, action=action, hit=at
        )
    except Exception:
        pass
    if action == "error":
        raise FailpointError(f"injected fault at {site!r} (hit {at})")
    if action == "drop_conn":
        raise ConnectionError(
            f"injected connection drop at {site!r} (hit {at})"
        )
    return action  # cooperative: "torn_write" / "stall"


def fired_sites() -> List[str]:
    """Sites of the CURRENTLY ARMED schedule whose spec has fired —
    chaos tests assert their schedule actually exercised the paths it
    named. Scoped with the schedule: `active()` arms fresh (unfired)
    specs and restores the previous set on exit."""
    with _LOCK:
        return [s.site for s in _SPECS.values() if s.fired]


@contextlib.contextmanager
def active(spec: str):
    """Arms `spec` (same grammar as the env var) for the duration of the
    with-block, on top of whatever is already armed; previous state is
    restored on exit. Thread-safe to *hit* concurrently, but nest/enter
    from one test thread at a time."""
    global _SPECS, ENABLED
    new = parse(spec)
    with _LOCK:
        old_specs, old_enabled = _SPECS, ENABLED
        merged = dict(old_specs)
        merged.update(new)
        _SPECS = merged
        ENABLED = True
    try:
        yield new
    finally:
        with _LOCK:
            _SPECS = old_specs
            ENABLED = old_enabled
