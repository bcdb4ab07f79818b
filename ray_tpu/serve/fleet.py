"""Fleet actuation plane: the head-side controller that ACTS on what the
cluster senses (ROADMAP item 5 — the serving-side sense→act loop).

PRs 6-10 gave the head senses — traces, SLO digests, health rules,
goodput, object flows — and the serve stack reacts locally (quarantine,
fail-fast, prefix routing), but nothing converts those signals into
capacity or recovery decisions. `FleetController` closes the loop:

- **Autoscale policy** — every eval_period_s it folds the health plane's
  firing alerts (queue_depth carries an autoscaler demand hint,
  ttft_slo is armed by the slo_ttft_ms knob), the live
  serve_disagg_queue_depth gauge, and per-role load into target replica
  counts PER ROLE — so the prefill/decode ratio tracks the workload
  shape, not just its volume. Actuation is hysteretic: scale-ups
  respect the global autoscale_cooldown_s / autoscale_step_max knobs
  (core/config.py), scale-downs require idle_periods consecutive quiet
  evaluations — one alert burst cannot flap the fleet.
- **Actuation backends** — a serve-mode fleet scales through
  `ServeController.set_target` (the coordinator's `_sync` picks up the
  membership change); an in-process fleet (tier-1 tests) scales
  through injected `spawn_fn`/`retire_fn` callbacks plus the
  coordinator's add_worker/remove_worker graceful pick-set surgery.
- **Live request resume** rides in the coordinator (disagg.open_stream):
  a decode replica dying mid-stream re-runs the request's remaining
  tokens on a healthy peer — the fleet's chaos story is that a replica
  SIGKILLed every N seconds costs a latency blip, never a failed
  request (tests/test_fleet.py `TestKillResume`: no stream fails).
- **LoRA hot-swap** — `distribute_adapter` seals adapter weights into
  the object plane, pre-seeds every host over the `api.broadcast` relay
  tree, then pins them resident per replica; the coordinator's gossiped
  adapter-residency routing sends each request to a replica that
  already holds its adapter.
- **Auto-remediation** — the PR 9 alert→stack-dump loop gains teeth: a
  firing alert naming a replica drives quarantine → drain → restart →
  rejoin, each stage counted in serve_fleet_remediations{stage}.

Metrics: serve_fleet_target_replicas{role} vs serve_fleet_demand{role}
(the convergence evidence), serve_fleet_resumes /
serve_fleet_resume_seconds (in disagg.py), serve_fleet_adapter_residency
{adapter}, serve_fleet_remediations{stage}.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import api
from ..core.config import config
from ..core.health import get_health_plane
from ..core.logging import get_logger
from ..core.metrics import Counter, Gauge
from .disagg import _m_queue_depth

logger = get_logger("serve.fleet")

ROLES = ("prefill", "decode")

_m_target = Gauge(
    "serve_fleet_target_replicas",
    "fleet policy's target replica count, by role",
)
_m_demand = Gauge(
    "serve_fleet_demand",
    "observed demand signal (queue depth + firing alerts), by role",
)
_m_residency = Gauge(
    "serve_fleet_adapter_residency",
    "replicas holding a LoRA adapter resident, by adapter",
)
_m_remediations = Counter(
    "serve_fleet_remediations",
    "auto-remediation actions, by stage (quarantine/drain/restart/rejoin)",
)

# alerts whose firing means "this role needs capacity"
_SCALE_RULES = ("queue_depth", "ttft_slo")


@dataclasses.dataclass
class FleetConfig:
    """Fleet policy knobs (per role unless noted)."""

    min_replicas: int = 1
    max_replicas: int = 4
    eval_period_s: float = 2.0
    # a role is pressured when its queue depth exceeds this many waiting
    # requests per live replica (firing queue_depth/ttft_slo alerts
    # pressure it regardless)
    target_queue_depth: float = 2.0
    # consecutive quiet evaluations before a one-step scale-down — the
    # acceptance bar: no oscillation across 3 consecutive periods
    idle_periods: int = 3
    # hysteresis overrides; None = the global autoscale_cooldown_s /
    # autoscale_step_max knobs (core/config.py, raylint R6 keeps both
    # declared AND read)
    cooldown_s: Optional[float] = None
    step_max: Optional[int] = None
    # shift one replica of capacity between roles when one role is
    # pinned at max_replicas under pressure while the other sits idle
    # above min_replicas — the prefill/decode ratio follows the load mix
    rebalance_roles: bool = True

    def __post_init__(self) -> None:
        if not 0 <= int(self.min_replicas) <= int(self.max_replicas):
            raise ValueError(
                "need 0 <= min_replicas <= max_replicas, got "
                f"min={self.min_replicas} max={self.max_replicas}")
        if float(self.eval_period_s) <= 0:
            raise ValueError(
                f"eval_period_s must be > 0, got {self.eval_period_s}")
        if float(self.target_queue_depth) <= 0:
            raise ValueError(
                f"target_queue_depth must be > 0, "
                f"got {self.target_queue_depth}")
        if int(self.idle_periods) < 1:
            raise ValueError(
                f"idle_periods must be >= 1, got {self.idle_periods}")

    @classmethod
    def parse(cls, value) -> "FleetConfig":
        """Normalize a YAML/JSON dict (or an existing instance),
        rejecting unknown keys with a clear error instead of silently
        ignoring a typo'd knob."""
        if isinstance(value, cls):
            return value
        if not isinstance(value, dict):
            raise ValueError(
                f"fleet must be a mapping, got {type(value).__name__}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(value) - known
        if unknown:
            raise ValueError(
                f"unknown fleet option(s) {sorted(unknown)}; "
                f"valid: {sorted(known)}")
        return cls(**value)


class FleetController:
    """Sense→act policy engine over one DisaggCoordinator.

    Construction picks the actuation backend:
      - `deployments={"prefill": name, "decode": name}` (+ an optional
        `controller` handle) scales through ServeController.set_target;
      - `spawn_fn(role) -> worker` / `retire_fn(role, worker)` scale an
        in-process worker fleet through the coordinator's pick set.
    With neither, evaluate_once still computes targets and gauges (dry
    run) — useful for shadowing a policy before giving it hands.
    """

    def __init__(self, coordinator, config: Any = None, *,
                 controller: Any = None,
                 deployments: Optional[Dict[str, str]] = None,
                 spawn_fn: Optional[Callable[[str], Any]] = None,
                 retire_fn: Optional[Callable[[str, Any], None]] = None,
                 plane: Any = None):
        self.cfg = FleetConfig.parse(config or {})
        self.co = coordinator
        self._controller = controller
        self._deployments = dict(deployments) if deployments else None
        self._spawn = spawn_fn
        self._retire = retire_fn
        self._plane = plane if plane is not None \
            else get_health_plane(create=False)
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._targets: Dict[str, int] = {
            r: max(len(coordinator.workers(r)), self.cfg.min_replicas)
            for r in ROLES
        }
        self._last_scale_up = {r: float("-inf") for r in ROLES}
        self._idle = {r: 0 for r in ROLES}
        self._pressured = {r: False for r in ROLES}
        self._remediating: set = set()
        # audit trail of actuations (scale / rebalance / remediate):
        # the dashboard's "remediation actions" story and the tests'
        # convergence evidence
        self.actions: List[Dict[str, Any]] = []
        if self._plane is not None:
            self._plane.subscribe(self._on_alert)

    # ------------------------------------------------------------ knobs

    def _cooldown_s(self) -> float:
        if self.cfg.cooldown_s is not None:
            return float(self.cfg.cooldown_s)
        return float(config.get("autoscale_cooldown_s"))

    def _step_max(self) -> int:
        if self.cfg.step_max is not None:
            return max(1, int(self.cfg.step_max))
        return max(1, int(config.get("autoscale_step_max")))

    # ----------------------------------------------------------- sense

    def _pressure(self, role: str, alerts: List[Dict[str, Any]],
                  live: int) -> Tuple[bool, float]:
        """-> (pressured, demand_value) for one role: firing scale rules
        naming the role, or sustained queue depth past
        target_queue_depth per live replica."""
        queue = float(_m_queue_depth.get(tags={"role": role}))
        alert_hot = any(
            a.get("state") == "firing"
            and a.get("rule") in _SCALE_RULES
            and (a.get("labels") or {}).get("role", role) == role
            for a in alerts)
        demand = queue
        if alert_hot:
            demand = max(demand, self.cfg.target_queue_depth * max(live, 1)
                         + 1.0)
        pressured = alert_hot or (
            queue > self.cfg.target_queue_depth * max(live, 1))
        return pressured, demand

    # ------------------------------------------------------------- act

    def evaluate_once(self, now: Optional[float] = None) -> Dict[str, int]:
        """One sense→act pass. Returns the per-role targets after it."""
        if now is None:
            now = time.monotonic()
        alerts = self._plane.active() if self._plane is not None else []
        cooldown = self._cooldown_s()
        step_max = self._step_max()
        with self._lock:
            for role in ROLES:
                workers = self.co.workers(role)
                live = len(workers)
                target = self._targets.get(role, live)
                pressured, demand = self._pressure(role, alerts, live)
                self._pressured[role] = pressured
                _m_demand.set(demand, tags={"role": role})
                if pressured:
                    self._idle[role] = 0
                    if (target < self.cfg.max_replicas
                            and now - self._last_scale_up[role] >= cooldown):
                        # size the wave to the demand, bounded by
                        # step_max and the ceiling
                        want = int(demand
                                   // max(self.cfg.target_queue_depth, 1e-9))
                        step = max(1, min(step_max,
                                          want - target,
                                          self.cfg.max_replicas - target))
                        self._set_target(role, target + step, "scale-up",
                                         demand=demand)
                        self._last_scale_up[role] = now
                else:
                    inflight = 0
                    for w in workers:
                        try:
                            inflight += int(w.load())
                        except Exception:  # noqa: BLE001
                            pass
                    if inflight == 0 and demand <= 0:
                        self._idle[role] += 1
                        if (self._idle[role] >= self.cfg.idle_periods
                                and target > self.cfg.min_replicas):
                            self._set_target(role, target - 1, "scale-down")
                            # re-arm: one step per idle window, so the
                            # ramp-down is as hysteretic as the ramp-up
                            self._idle[role] = 0
                    else:
                        self._idle[role] = 0
                _m_target.set(float(self._targets[role]),
                              tags={"role": role})
            if self.cfg.rebalance_roles:
                self._maybe_rebalance(now)
            self._reconcile_inprocess()
            self._refresh_residency()
            return dict(self._targets)

    def _maybe_rebalance(self, now: float) -> None:
        """Role-ratio actuation: a role pinned at max_replicas under
        pressure borrows one replica of capacity from the other role
        when that one has been idle a full window above min_replicas."""
        for hot, cold in (("decode", "prefill"), ("prefill", "decode")):
            if (self._pressured[hot]
                    and self._targets[hot] >= self.cfg.max_replicas
                    and not self._pressured[cold]
                    and self._idle[cold] >= self.cfg.idle_periods
                    and self._targets[cold] > self.cfg.min_replicas):
                self._set_target(cold, self._targets[cold] - 1,
                                 "rebalance", peer=hot)
                self._idle[cold] = 0
                return

    def _set_target(self, role: str, target: int, kind: str,
                    **detail: Any) -> None:
        # caller holds self._lock
        target = min(max(int(target), self.cfg.min_replicas),
                     self.cfg.max_replicas)
        prev = self._targets.get(role)
        if target == prev:
            return
        self._targets[role] = target
        self.actions.append({"kind": kind, "role": role, "from": prev,
                             "to": target, "at": time.time(), **detail})
        logger.info("fleet %s %s: %d -> %d %s",
                    kind, role, prev if prev is not None else -1, target,
                    detail or "")
        if self._deployments is not None and role in self._deployments:
            ctrl = self._controller
            if ctrl is None:
                from .controller import get_or_create_controller

                ctrl = self._controller = get_or_create_controller()
            try:
                fn = getattr(ctrl.set_target, "remote", None)
                if fn is not None:  # actor handle
                    api.get(fn(self._deployments[role], target),
                            timeout=30.0)
                else:  # in-process double
                    ctrl.set_target(self._deployments[role], target)
            except Exception:  # noqa: BLE001 — retried next period
                logger.warning("set_target(%s, %d) failed",
                               self._deployments[role], target,
                               exc_info=True)

    def _reconcile_inprocess(self) -> None:
        """In-process actuation: converge the coordinator's pick sets to
        the targets through spawn_fn/retire_fn. Serve-mode fleets skip
        this — the serve controller owns replica lifecycles there."""
        if self._spawn is None:
            return
        for role in ROLES:
            target = self._targets[role]
            while len(self.co.workers(role)) < target:
                try:
                    self.co.add_worker(role, self._spawn(role))
                except Exception:  # noqa: BLE001 — retried next period
                    logger.warning("spawn_fn(%s) failed", role,
                                   exc_info=True)
                    break
            while len(self.co.workers(role)) > target:
                w = self.co.remove_worker(role)
                if w is None:
                    break
                if self._retire is not None:
                    try:
                        self._retire(role, w)
                    except Exception:  # noqa: BLE001 — best-effort
                        logger.warning("retire_fn(%s) failed", role,
                                       exc_info=True)

    # ----------------------------------------------------- remediation

    def _on_alert(self, alert: Dict[str, Any]) -> None:
        """The PR 9 alert loop extended into actuation: a firing alert
        naming a replica drives the quarantine→drain→restart→rejoin
        pipeline instead of only a stack dump."""
        if alert.get("state") != "firing":
            return
        rep = (alert.get("labels") or {}).get("replica")
        if not rep:
            return
        for role in ROLES:
            for w in self.co.workers(role):
                if str(w.key) == str(rep):
                    self.remediate(role, w.key,
                                   reason=alert.get("rule", "alert"))
                    return

    def remediate(self, role: str, key: Any, reason: str = "alert") -> bool:
        """quarantine → drain → restart → rejoin one replica, counting
        each stage in serve_fleet_remediations{stage}."""
        with self._lock:
            if key in self._remediating:
                return False
            self._remediating.add(key)
        try:
            self.co.health.quarantine(key, reason=reason)
            _m_remediations.inc(tags={"stage": "quarantine"})
            # drain: out of the pick set now; in-flight streams finish
            # under the coordinator's drain grace
            w = self.co.remove_worker(role, key)
            _m_remediations.inc(tags={"stage": "drain"})
            self.actions.append({"kind": "remediate", "role": role,
                                 "replica": str(key), "reason": reason,
                                 "at": time.time()})
            if self._spawn is not None:
                if w is not None and self._retire is not None:
                    try:
                        self._retire(role, w)
                    except Exception:  # noqa: BLE001 — it's being replaced
                        pass
                _m_remediations.inc(tags={"stage": "restart"})
                try:
                    self.co.add_worker(role, self._spawn(role))
                    _m_remediations.inc(tags={"stage": "rejoin"})
                except Exception:  # noqa: BLE001 — next eval retries
                    logger.warning("remediation respawn for %s failed",
                                   role, exc_info=True)
            elif w is not None and hasattr(w, "_replica"):
                # serve mode: kill the actor; the serve controller's
                # reconcile replaces it and the coordinator's _sync
                # rejoins the replacement
                try:
                    api.kill(w._replica)
                except Exception:  # noqa: BLE001 — already dead
                    pass
                _m_remediations.inc(tags={"stage": "restart"})
            logger.info("remediated %s replica %s (%s)", role, key, reason)
            return True
        finally:
            with self._lock:
                self._remediating.discard(key)

    # ------------------------------------------------------- LoRA swap

    def distribute_adapter(self, adapter_id: str, weights: Any = None,
                           ref: Any = None,
                           roles: Tuple[str, ...] = ("decode",),
                           timeout_s: float = 60.0) -> Dict[str, Any]:
        """Hot-swap distribution: seal the adapter into the object plane,
        pre-seed every host over the api.broadcast relay tree, then pin
        it resident on each replica of the given roles. Per-replica
        failures are reported, never raised — a replica that missed the
        load pulls lazily via adapter_ref on its first routed request."""
        if ref is None:
            ref = api.put(weights)
        try:
            # relay-tree pre-seed: replicas then resolve the ref from
            # their own host's store instead of all pulling the driver
            api.broadcast(ref, timeout=timeout_s)
        except Exception:  # noqa: BLE001 — pre-seeding is best-effort
            logger.debug("adapter broadcast pre-seed failed", exc_info=True)
        out: Dict[str, Any] = {"adapter_id": str(adapter_id), "ref": ref,
                               "loaded": [], "failed": []}
        for role in roles:
            for w in self.co.workers(role):
                try:
                    w.load_adapter({"adapter_id": str(adapter_id),
                                    "ref": ref, "timeout_s": timeout_s})
                    out["loaded"].append(str(w.key))
                except Exception as e:  # noqa: BLE001 — lazy pull later
                    out["failed"].append({"replica": str(w.key),
                                          "error": repr(e)})
        _m_residency.set(float(len(out["loaded"])),
                         tags={"adapter": str(adapter_id)})
        return out

    def sync_weights(self, weights: Any = None, ref: Any = None,
                     version: Optional[int] = None,
                     roles: Tuple[str, ...] = ROLES,
                     timeout_s: float = 60.0) -> Dict[str, Any]:
        """Live base-weight re-sync WITHOUT draining: seal the new tree
        into the object plane, pre-seed every host over the api.broadcast
        relay tree, then swap it in on each replica of the given roles
        (engine.update_params — in-flight requests keep the old weights,
        new dispatches serve the new generation). Per-replica failures
        are reported, never raised: a replica that missed the swap keeps
        serving the previous generation and its gossiped weights_version
        shows the skew. This is the online-RL trainer→fleet edge."""
        if ref is None:
            ref = api.put(weights)
        try:
            # relay-tree pre-seed: replicas then resolve the ref from
            # their own host's store instead of all pulling the driver
            api.broadcast(ref, timeout=timeout_s)
        except Exception:  # noqa: BLE001 — pre-seeding is best-effort
            logger.debug("weights broadcast pre-seed failed", exc_info=True)
        out: Dict[str, Any] = {"ref": ref, "version": version,
                               "synced": [], "failed": []}
        for role in roles:
            for w in self.co.workers(role):
                try:
                    res = w.update_weights({"ref": ref, "version": version,
                                            "timeout_s": timeout_s})
                    out["synced"].append(
                        {"replica": str(w.key),
                         "weights_version": res.get("weights_version")})
                except Exception as e:  # noqa: BLE001 — skew is visible
                    out["failed"].append({"replica": str(w.key),
                                          "error": repr(e)})
        return out

    def _refresh_residency(self) -> None:
        counts: Dict[str, int] = {}
        try:
            for _key, adapters in self.co.adapter_residency().items():
                for a in adapters:
                    counts[a] = counts.get(a, 0) + 1
        except Exception:  # noqa: BLE001 — gossip is advisory
            return
        for adapter, n in counts.items():
            _m_residency.set(float(n), tags={"adapter": adapter})

    # ------------------------------------------------------------ loop

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="fleet-controller")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.evaluate_once()
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.warning("fleet evaluation failed", exc_info=True)
            self._stop.wait(self.cfg.eval_period_s)

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=10.0)

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "targets": dict(self._targets),
                "live": {r: len(self.co.workers(r)) for r in ROLES},
                "idle_periods": dict(self._idle),
                "pressured": dict(self._pressured),
                "actions": list(self.actions[-50:]),
                "adapter_residency": self.co.adapter_residency(),
            }
