"""Router-driven fleet autoscaler: a control loop over signals the
system ALREADY exports.

The elastic-membership round's third tier (docs/serving.md "Elastic
fleet"): `FleetAutoscaler` watches the serving tier's exported
overload signals — the shed rate (`registry.shed_totals()`, which
backs `ydf_serve_shed_total{reason}`; the fleet admission cap's
"fleet_admission" sheds are the primary scale-up driver), and
optionally the loadgen-exported `queue_age_p99_ns` /
`pool_utilization{serve}` through a pluggable `signal_fn` — and calls
`FleetRouter.add_replica` / `remove_replica` against a pluggable
**replica provider**:

  * `InProcessReplicaProvider` — spawns `start_worker` threads on free
    localhost ports (tests, bench);
  * `SubprocessReplicaProvider` — spawns real worker processes (the
    CLI's deployment shape).

Control discipline, all knobs `YDF_TPU_AUTOSCALE_*` and eagerly
validated at construction:

  * **hysteresis bands** — scale UP when the per-tick shed delta
    crosses `shed_high`; scale DOWN only after `idle_ticks`
    consecutive zero-shed evaluations, so a noisy boundary never
    flaps;
  * **cooldown** — after any scale event, `cooldown_s` must elapse
    before the next one (a just-added replica gets time to absorb
    load before the loop judges again);
  * **bounds** — the fleet never leaves [min_replicas, max_replicas],
    and scale-down only ever removes replicas THIS autoscaler spawned
    (a fleet's founding members are the operator's).

Every decision — scale or hold — lands in a bounded decision log on
the router's `/statusz` neighbor section (`autoscaler:<id>`), and
scale events mirror into telemetry:
`ydf_fleet_scale_events_total{direction,reason}` plus the
`ydf_fleet_replicas` gauge refreshed every tick.

`tick()` is public and synchronous so tests (and the bench elastic
mode) drive the loop deterministically; `start()`/`stop()` run it on
a daemon thread at `interval_s` for real deployments.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ydf_tpu.utils import log, telemetry, telemetry_http

__all__ = [
    "FleetAutoscaler",
    "InProcessReplicaProvider",
    "SubprocessReplicaProvider",
]


def _env_number(name: str, value, default, cast, minimum):
    """Explicit arg wins, then the env knob, else the default — junk
    fails CONSTRUCTION (the eager-validation contract every YDF_TPU_*
    knob follows), not the first scale decision."""
    raw: Any = value
    if raw is None:
        raw = os.environ.get(name)
        if raw is None or raw == "":
            raw = default
    try:
        out = cast(raw)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name} must be a {cast.__name__} >= {minimum}, got {raw!r}"
        ) from None
    if out < minimum:
        raise ValueError(
            f"{name} must be >= {minimum}, got {out}"
        )
    return out


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _shutdown_worker(address: str, secret: Optional[bytes]) -> None:
    """Best-effort shutdown verb to one worker (provider teardown —
    the replica is already out of every rotation)."""
    from ydf_tpu.parallel.worker_service import WorkerPool

    pool = WorkerPool(
        [address], timeout_s=10.0, secret=secret, retry_attempts=1
    )
    try:
        pool.request(0, {"verb": "shutdown"})
    except (OSError, ConnectionError):
        pass
    finally:
        pool.close()


class InProcessReplicaProvider:
    """Spawns serving replicas as in-process `start_worker` daemon
    threads on free localhost ports — the tests/bench provider (same
    process, so chaos/telemetry state is shared and teardown is a
    shutdown verb away)."""

    def __init__(self, secret: Optional[bytes] = None):
        self.secret = secret
        self._threads: Dict[str, Any] = {}

    def spawn(self) -> str:
        from ydf_tpu.parallel.worker_service import start_worker

        port = _free_port()
        th = start_worker(
            port, host="127.0.0.1", blocking=False, secret=self.secret
        )
        addr = f"127.0.0.1:{port}"
        self._threads[addr] = th
        return addr

    def stop(self, address: str) -> None:
        _shutdown_worker(address, self.secret)
        th = self._threads.pop(address, None)
        if th is not None:
            th.join(timeout=10.0)

    def close(self) -> None:
        for addr in list(self._threads):
            self.stop(addr)


class SubprocessReplicaProvider:
    """Spawns serving replicas as real `start_worker` subprocesses —
    the CLI's deployment shape (a replica death is a process death,
    and its memory really is freed)."""

    #: Bounded wait for a spawned worker's port to accept.
    _SPAWN_TIMEOUT_S = 30.0

    def __init__(self, secret: Optional[bytes] = None):
        self.secret = secret
        self._procs: Dict[str, Any] = {}

    def spawn(self) -> str:
        import socket
        import subprocess

        port = _free_port()
        # A replica serves from a native CPU bank or the routed scan
        # (serving/replica.py) and never needs the accelerator; the
        # parent may hold it, and a chip belongs to one process.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if self.secret is not None:
            env["YDF_TPU_WORKER_SECRET"] = self.secret.decode()
        proc = subprocess.Popen(
            [
                sys.executable, "-c",
                "from ydf_tpu.parallel.worker_service import "
                f"start_worker; start_worker({port}, blocking=True)",
            ],
            env=env,
        )
        addr = f"127.0.0.1:{port}"
        deadline = time.monotonic() + self._SPAWN_TIMEOUT_S
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise ConnectionError(
                    f"spawned worker {addr} exited with "
                    f"{proc.returncode} before accepting"
                )
            try:
                with socket.create_connection(
                    ("127.0.0.1", port), timeout=1.0
                ):
                    self._procs[addr] = proc
                    return addr
            except OSError:
                time.sleep(0.05)
        proc.kill()
        raise ConnectionError(
            f"spawned worker {addr} did not accept within "
            f"{self._SPAWN_TIMEOUT_S}s"
        )

    def stop(self, address: str) -> None:
        _shutdown_worker(address, self.secret)
        proc = self._procs.pop(address, None)
        if proc is not None:
            try:
                proc.wait(timeout=10.0)
            except Exception:
                proc.kill()

    def close(self) -> None:
        for addr in list(self._procs):
            self.stop(addr)


class FleetAutoscaler:
    """The control loop. See the module docstring for the discipline;
    `tick()` is one synchronous evaluation (the deterministic test /
    bench drive), `start()` runs it on a daemon thread."""

    def __init__(
        self,
        router,
        provider,
        *,
        min_replicas: Optional[int] = None,
        max_replicas: Optional[int] = None,
        interval_s: Optional[float] = None,
        cooldown_s: Optional[float] = None,
        shed_high: Optional[int] = None,
        idle_ticks: Optional[int] = None,
        signal_fn: Optional[Callable[[], Dict[str, Any]]] = None,
        register_statusz: bool = True,
    ):
        self.router = router
        self.provider = provider
        self.min_replicas = _env_number(
            "YDF_TPU_AUTOSCALE_MIN", min_replicas, 1, int, 1
        )
        self.max_replicas = _env_number(
            "YDF_TPU_AUTOSCALE_MAX", max_replicas, 8, int, 1
        )
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                "YDF_TPU_AUTOSCALE_MAX "
                f"({self.max_replicas}) must be >= YDF_TPU_AUTOSCALE_MIN "
                f"({self.min_replicas})"
            )
        self.interval_s = _env_number(
            "YDF_TPU_AUTOSCALE_INTERVAL_S", interval_s, 1.0, float, 0.01
        )
        self.cooldown_s = _env_number(
            "YDF_TPU_AUTOSCALE_COOLDOWN_S", cooldown_s, 5.0, float, 0.0
        )
        #: Scale-up band: sheds observed since the previous tick at or
        #: past this trigger a grow.
        self.shed_high = _env_number(
            "YDF_TPU_AUTOSCALE_SHED_HIGH", shed_high, 1, int, 1
        )
        #: Scale-down band: this many CONSECUTIVE zero-shed ticks
        #: before a shrink — the hysteresis that stops flapping.
        self.idle_ticks = _env_number(
            "YDF_TPU_AUTOSCALE_IDLE_TICKS", idle_ticks, 3, int, 1
        )
        self.signal_fn = signal_fn
        self._lock = threading.Lock()
        self._last_shed_total: Optional[int] = None
        self._idle_streak = 0
        self._last_scale_monotonic: Optional[float] = None
        self._ticks = 0
        self._scale_ups = 0
        self._scale_downs = 0
        #: Replicas THIS autoscaler spawned, in spawn order — the only
        #: ones scale-down may remove (LIFO).
        self._spawned: List[str] = []
        #: Bounded decision log: every tick's decision, newest last.
        self._decisions: collections.deque = collections.deque(maxlen=64)
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._statusz_key: Optional[str] = None
        if register_statusz:
            self._statusz_key = f"autoscaler:{id(self):x}"
            telemetry_http.register_status(self._statusz_key, self.status)

    # ---- signals ----------------------------------------------------- #

    def read_signals(self) -> Dict[str, Any]:
        """One sample of the exported signals. The default reads the
        process-lifetime shed totals (telemetry-independent — the same
        numbers `ydf_serve_shed_total` mirrors) and differences them
        against the previous tick; `signal_fn` may override/extend
        with richer exported signals (queue_age_p99_ns,
        pool_utilization) — the loop only requires `shed_delta`."""
        total = sum(self._shed_totals().values())
        with self._lock:
            prev = self._last_shed_total
            self._last_shed_total = total
        sig = {
            "shed_total": total,
            "shed_delta": 0 if prev is None else max(total - prev, 0),
            "replicas": len(self.router.pool.addresses),
        }
        if self.signal_fn is not None:
            sig.update(self.signal_fn() or {})
        return sig

    @staticmethod
    def _shed_totals() -> Dict[str, int]:
        from ydf_tpu.serving.registry import shed_totals

        return shed_totals()

    # ---- the control loop -------------------------------------------- #

    def tick(self) -> Dict[str, Any]:
        """One evaluation: sample the signals, apply bands + cooldown +
        bounds, maybe scale, and return (and log) the decision."""
        now = time.monotonic()
        sig = self.read_signals()
        replicas = int(sig["replicas"])
        shed_delta = int(sig.get("shed_delta", 0))
        with self._lock:
            self._ticks += 1
            if shed_delta == 0:
                self._idle_streak += 1
            else:
                self._idle_streak = 0
            idle_streak = self._idle_streak
            last_scale = self._last_scale_monotonic
        in_cooldown = (
            last_scale is not None
            and now - last_scale < self.cooldown_s
        )
        direction, reason = "hold", "steady"
        if shed_delta >= self.shed_high:
            if replicas >= self.max_replicas:
                reason = "at_max"
            elif in_cooldown:
                reason = "cooldown"
            else:
                direction, reason = "up", "overload_shed"
        elif (
            idle_streak >= self.idle_ticks
            and replicas > self.min_replicas
        ):
            # Only replicas this autoscaler spawned are removable.
            if in_cooldown:
                reason = "cooldown"
            elif not self._spawned:
                reason = "nothing_to_remove"
            else:
                direction, reason = "down", "idle"
        decision: Dict[str, Any] = {
            "tick": self._ticks, "direction": direction,
            "reason": reason, "replicas": replicas,
            "shed_delta": shed_delta, "idle_streak": idle_streak,
        }
        if direction == "up":
            decision.update(self._scale_up())
        elif direction == "down":
            decision.update(self._scale_down())
        if decision.get("failed"):
            direction = decision["direction"] = "hold"
        with self._lock:
            self._decisions.append(decision)
            if direction in ("up", "down"):
                self._last_scale_monotonic = time.monotonic()
                self._idle_streak = 0
                if direction == "up":
                    self._scale_ups += 1
                else:
                    self._scale_downs += 1
        if telemetry.ENABLED:
            if direction in ("up", "down"):
                telemetry.counter(
                    "ydf_fleet_scale_events_total",
                    direction=direction, reason=decision["reason"],
                ).inc()
            telemetry.gauge("ydf_fleet_replicas").set(
                len(self.router.pool.addresses)
            )
        return decision

    def _scale_up(self) -> Dict[str, Any]:
        try:
            addr = self.provider.spawn()
        except Exception as e:
            log.info(f"autoscaler: spawn failed: {e}")
            return {"failed": True, "error": f"spawn: {e}"}
        try:
            res = self.router.add_replica(addr)
        except Exception as e:
            # The candidate never entered rotation (add_replica's
            # contract) — reclaim it and report the hold.
            log.info(f"autoscaler: join of {addr} failed: {e}")
            try:
                self.provider.stop(addr)
            except Exception:
                pass
            return {"failed": True, "error": f"join: {e}"}
        self._spawned.append(addr)
        return {"replica": addr, "join_ns": res.get("join_ns", 0),
                "replicas": res.get("replicas")}

    def _scale_down(self) -> Dict[str, Any]:
        addr = self._spawned[-1]
        try:
            res = self.router.remove_replica(addr)
        except Exception as e:
            log.info(f"autoscaler: drain of {addr} failed: {e}")
            return {"failed": True, "error": f"drain: {e}"}
        self._spawned.pop()
        try:
            self.provider.stop(addr)
        except Exception:
            pass
        return {"replica": addr, "drain_ns": res.get("drain_ns", 0),
                "replicas": res.get("replicas")}

    # ---- lifecycle --------------------------------------------------- #

    def start(self) -> None:
        """Runs tick() every interval_s on a daemon thread."""
        if self._thread is not None:
            return
        self._stop_evt.clear()

        def loop():
            while not self._stop_evt.wait(self.interval_s):
                try:
                    self.tick()
                except Exception as e:  # noqa: BLE001 — loop must live
                    log.info(f"autoscaler: tick failed: {e}")

        self._thread = threading.Thread(
            target=loop, name="ydf-autoscaler", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop_evt.set()
        self._thread.join(timeout=10.0)
        self._thread = None

    def status(self) -> Dict[str, Any]:
        """The /statusz section: config, live signal state and the
        bounded decision log (newest last)."""
        with self._lock:
            return {
                "config": {
                    "min_replicas": self.min_replicas,
                    "max_replicas": self.max_replicas,
                    "interval_s": self.interval_s,
                    "cooldown_s": self.cooldown_s,
                    "shed_high": self.shed_high,
                    "idle_ticks": self.idle_ticks,
                },
                "replicas": len(self.router.pool.addresses),
                "spawned": list(self._spawned),
                "ticks": self._ticks,
                "scale_ups": self._scale_ups,
                "scale_downs": self._scale_downs,
                "idle_streak": self._idle_streak,
                "last_shed_total": self._last_shed_total,
                "decisions": list(self._decisions),
            }

    def close(self) -> None:
        self.stop()
        if self._statusz_key is not None:
            telemetry_http.unregister_status(self._statusz_key)
            self._statusz_key = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
