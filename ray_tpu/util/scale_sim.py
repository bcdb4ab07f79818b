"""128-node control-plane scale harness on one box (ISSUE 19).

Simulates N node agents grouped into pods against a REAL federated head:
an in-process ControlPlane wrapped by FederatedControlPlane over K
``ControlPlaneShard`` subprocesses, served over real sockets. Each pod
runs a real ``PodAggregator`` flushing heartbeat_bulk + merged telemetry
through a real ``ShardedControlPlane`` client; each simulated node is a
``ResourceTracker`` admitted through the same ``node_agent.admits`` rule
the live NodeAgent uses, with overflow delegated to the head's
``ClusterScheduler``. Only the worker *processes* are simulated — every
byte on the wire and every line of routing/merge/scheduling code is the
production path.

Measured as N grows (tests/test_scale_sim.py and `make scale` hold the
counts: zero failed requests, a promoted standby, no reconnect spike):

- ``head_cpu_cores``       CPU consumed by head-side work (RPC dispatch,
                           health evaluation, overflow scheduling) per
                           wall second — the O(pods) ingest claim.
- ``heartbeat_lag_ms_p95`` beat generated at a pod to head bulk-ack.
- ``actuation_latency_s``  HealthPlane.inject -> federated pubsub ->
                           remote subscriber callback (median).
- ``sched_tasks_per_s``    local admits + delegated placements.
- chaos (``kill_shard``):  SIGKILL one shard primary mid-run; the gate
                           is zero failed requests and bounded recovery.

Run directly: ``python -m ray_tpu.util.scale_sim --nodes 64 --kill-shard``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

from ..core import node_agent
from ..core.aggregator import PodAggregator
from ..core.config import config
from ..core.control_plane import ControlPlane, NodeInfo
from ..core.health import HealthPlane
from ..core.ids import NodeID
from ..core.logging import get_logger
from ..core.rpc import (ShardedControlPlane, _reconnects_total,
                        _redials_throttled, serve_control_plane,
                        shard_for_key)
from ..core.scheduler import ClusterScheduler
from ..core.shard import (SHARD_MAP_KEY, FederatedControlPlane,
                          ShardSupervisor)
from ..core.task_spec import TaskOptions
from . import slo

logger = get_logger("scale_sim")

_NODE_CPUS = 8.0
# alternating task lengths: even nodes run long tasks and saturate (their
# admission overflows to the head scheduler — the bottom-up path), odd
# nodes stay under the spread threshold and admit locally
_TASK_HOLD_ROUNDS = (5, 1)


def _p95(samples: List[float]) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[min(len(s) - 1, int(0.95 * len(s)))]


def _counter_total(counter) -> float:
    return sum(v for _, _, v in counter.samples())


class _TimedPlane:
    """CPU-accounting proxy around the head plane: every RPC-dispatched
    method is timed with ``time.thread_time`` (CPU, not wall — blocking on
    a shard socket is free), so the harness can report head cores consumed
    by ingest even though the sim fleet shares the process."""

    def __init__(self, inner):
        self._inner = inner
        self.pubsub = inner.pubsub  # served objects expose pubsub directly
        self._tl = threading.Lock()
        self.cpu_s = 0.0
        self.calls = 0

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def timed(*args, **kwargs):
            t0 = time.thread_time()
            try:
                return attr(*args, **kwargs)
            finally:
                dt = time.thread_time() - t0
                with self._tl:
                    self.cpu_s += dt
                    self.calls += 1

        return timed


class _SimNode:
    """One simulated node agent: identity + the real resource ledger and
    the real local-admission rule."""

    def __init__(self, index: int) -> None:
        self.node_id = NodeID.generate()
        self.hex = self.node_id.hex()
        self.tracker = node_agent.ResourceTracker({"CPU": _NODE_CPUS})
        self.hold_rounds = _TASK_HOLD_ROUNDS[index % len(_TASK_HOLD_ROUNDS)]
        self.running: List = []  # (release_round, demand)


class _Pod:
    """A pod thread: heartbeats + telemetry through a PodAggregator,
    KV/directory gossip and task admission for each member node."""

    def __init__(self, harness: "_Harness", pod_id: int,
                 members: List[_SimNode]) -> None:
        self.h = harness
        self.pod_id = pod_id
        self.members = members
        self.cp = ShardedControlPlane(
            harness.head_addr, harness.shard_addrs,
            role=f"simpod{pod_id}", route_directory=True)
        self.agg = PodAggregator(f"sim{pod_id}", self.cp,
                                 flush_period_s=harness.hb_period)
        self.failed = 0
        self.kv_ops = 0
        self.local_admits = 0
        self.delegated = 0
        self.hb_lags: List[float] = []
        self.rounds = 0
        self.thread = threading.Thread(
            target=self._run, daemon=True, name=f"sim-pod-{pod_id}")

    def _guard(self, fn) -> Any:
        """Every simulated request goes through here: an exception is a
        LOST request — the chaos gate requires this stays zero."""
        try:
            return fn()
        except Exception:
            logger.warning("pod %d request failed", self.pod_id,
                           exc_info=True)
            self.failed += 1
            return None

    def register(self) -> None:
        for node in self.members:
            self._guard(lambda n=node: self.cp.register_node(NodeInfo(
                node_id=n.node_id,
                address=f"sim://{n.hex[:8]}",
                resources_total={"CPU": _NODE_CPUS},
                labels={"pod": str(self.pod_id)})))

    def _run(self) -> None:
        h = self.h
        round_i = 0
        next_round = time.monotonic()
        while not h.stop.is_set():
            start = time.monotonic()
            overrun = max(0.0, start - next_round)
            for node in self.members:
                still_running = []
                for release_round, demand in node.running:
                    if release_round > round_i:
                        still_running.append((release_round, demand))
                    else:
                        node.tracker.release(demand)
                node.running = still_running
                self._guard(lambda n=node: self.agg.ingest_heartbeat(
                    n.node_id, n.tracker.available()))
                self._schedule(node, round_i)
                self._guard(lambda n=node: self.cp.kv_put(
                    f"object_transfer_load/{n.hex}", str(round_i)))
                self.kv_ops += 1
            self._telemetry(round_i)
            self._gossip(round_i)
            t0 = time.monotonic()
            if self._guard(self.agg.flush) is not None:
                # lag: beat generated at round start, head-acked at flush end
                self.hb_lags.append(overrun + (time.monotonic() - t0))
            round_i += 1
            self.rounds = round_i
            next_round += h.hb_period
            now = time.monotonic()
            if next_round < now:  # overloaded: don't spiral, re-anchor
                next_round = now
            else:
                h.stop.wait(next_round - now)

    def _schedule(self, node: _SimNode, round_i: int) -> None:
        h = self.h
        demand = {"CPU": 1.0}
        for _ in range(h.tasks_per_round):
            if (node_agent.admits(node.tracker.total,
                                  node.tracker.available(), demand,
                                  h.spread_threshold)
                    and node.tracker.try_acquire(demand)):
                self.local_admits += 1
                node.running.append((round_i + node.hold_rounds, demand))
            elif h.overflow(demand) is not None:
                self.delegated += 1

    def _telemetry(self, round_i: int) -> None:
        node = self.members[round_i % len(self.members)]
        metrics = [{"name": "sim_ops_total", "kind": "counter",
                    "description": "sim node op counter",
                    "samples": [("sim_ops_total", [["node", node.hex[:8]]],
                                 float(self.kv_ops))]}]
        digests = slo.snapshot() if round_i % 4 == 0 else None
        self._guard(lambda: self.agg.ingest_telemetry(
            node.hex, role="worker", metrics=metrics, digests=digests))

    def _gossip(self, round_i: int) -> None:
        """Directory churn against the shards (route_directory=True)."""
        node = self.members[round_i % len(self.members)]
        oid = f"simobj{self.pod_id:02x}{round_i:06x}"
        self._guard(lambda: self.cp.dir_add_location(oid, node.hex))
        self.kv_ops += 1
        if round_i >= 4:
            old = f"simobj{self.pod_id:02x}{round_i - 4:06x}"
            self._guard(lambda: self.cp.dir_remove_location(old, node.hex))
            self.kv_ops += 1

    def stop(self) -> None:
        self.agg.stop(final_flush=False)
        self.cp.close()


class _Harness:
    """Owns the head (inner plane + shards + federation + RPC server +
    health plane), the overflow scheduler, and the pod fleet."""

    def __init__(self, nodes: int, nshards: int, pod_size: int,
                 hb_period: float, tasks_per_round: int) -> None:
        self.stop = threading.Event()
        self.hb_period = hb_period
        self.tasks_per_round = tasks_per_round
        self.spread_threshold = float(config.scheduler_spread_threshold)

        self.inner = ControlPlane()
        self.sup = ShardSupervisor(nshards)
        self.sup.start()
        self.fed = FederatedControlPlane(self.inner, self.sup)
        self.fed.kv_put(SHARD_MAP_KEY, self.sup.shard_map())
        self.timed = _TimedPlane(self.fed)
        self.server = serve_control_plane(self.timed)
        self.head_addr = self.server.address
        self.shard_addrs = self.sup.addresses

        self.hp = HealthPlane(control_plane=self.fed)
        self._eval_cpu = 0.0
        self._eval_thread = threading.Thread(
            target=self._eval_loop, daemon=True, name="sim-health-eval")

        self._sched = ClusterScheduler(self.inner, self.spread_threshold)
        self._sched_lock = threading.Lock()
        self._sched_cpu = 0.0
        self._overflow_opts = TaskOptions(num_cpus=1.0)

        self.pods: List[_Pod] = []
        sim_nodes = [_SimNode(i) for i in range(nodes)]
        for p in range(0, nodes, pod_size):
            self.pods.append(_Pod(self, len(self.pods),
                                  sim_nodes[p:p + pod_size]))

    def overflow(self, demand: Dict[str, float]) -> Optional[NodeID]:
        """Bottom-up delegation target: the head's real ClusterScheduler
        over the heartbeat-fed cluster view. thread_time-accounted as
        head CPU — on a real deployment this pass runs on the head."""
        spec = SimpleNamespace(options=self._overflow_opts,
                               name="sim-overflow")
        with self._sched_lock:
            t0 = time.thread_time()
            try:
                return self._sched.select_node(spec)
            except ValueError:
                return None
            finally:
                self._sched_cpu += time.thread_time() - t0

    def _eval_loop(self) -> None:
        while not self.stop.wait(self.hb_period):
            t0 = time.thread_time()
            try:
                self.hp.evaluate()
            except Exception:
                logger.warning("health eval failed", exc_info=True)
            self._eval_cpu += time.thread_time() - t0

    def measure_actuation(self, samples: int = 5,
                          timeout_s: float = 10.0) -> float:
        """inject -> federated pubsub -> a pod's remote subscription."""
        seen: Dict[str, float] = {}
        evt = threading.Event()

        def on_alert(alert: Dict[str, Any]) -> None:
            rule = alert.get("rule", "")
            if rule.startswith("sim_actuate_"):
                seen[rule] = time.monotonic()
                evt.set()

        self.pods[0].cp.subscribe("alerts", on_alert)
        lats: List[float] = []
        for i in range(samples):
            evt.clear()
            rule = f"sim_actuate_{i}"
            t0 = time.monotonic()
            self.hp.inject(rule, labels={"target": "sim"}, value=1.0)
            if evt.wait(timeout_s) and rule in seen:
                lats.append(seen[rule] - t0)
            time.sleep(0.05)
        lats.sort()
        return lats[len(lats) // 2] if lats else float("inf")

    def kill_and_probe(self, probe_cp: ShardedControlPlane,
                       probe_key: str) -> Dict[str, Any]:
        """SIGKILL the primary owning probe_key; the very next write must
        ride through the failover (idempotent retry inside the client) —
        recovery is kill-to-first-success, not kill-to-promotion."""
        target = shard_for_key(probe_key, self.sup.nshards)
        t_kill = time.monotonic()
        self.sup.kill_primary(target)
        failed = 0
        recovery = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            try:
                probe_cp.kv_put(probe_key, "post-kill")
                if probe_cp.kv_get(probe_key) == "post-kill":
                    recovery = time.monotonic() - t_kill
                    break
            except Exception:
                logger.warning("probe request failed", exc_info=True)
                failed += 1
        healthy = self.sup.wait_healthy(30.0)
        promote_s = (self.sup.failovers[-1]["promote_s"]
                     if self.sup.failovers else None)
        return {"shard": target, "recovery_s": recovery,
                "promote_s": promote_s, "failed_requests": failed,
                "failovers": len(self.sup.failovers),
                "standby_respawned": healthy}

    def shutdown(self) -> None:
        self.stop.set()
        for pod in self.pods:
            pod.thread.join(timeout=30.0)
        self._eval_thread.join(timeout=10.0)
        for pod in self.pods:
            pod.stop()
        self.server.stop()
        self.fed.close()
        self.sup.stop()


def run_scale_sim(nodes: int = 32, nshards: int = 2, duration_s: float = 5.0,
                  pod_size: int = 8, hb_period_s: float = 0.5,
                  tasks_per_round: int = 2,
                  kill_shard: bool = False) -> Dict[str, Any]:
    """Run one harness pass; returns its measurement row."""
    reconnects0 = _counter_total(_reconnects_total)
    redials0 = _counter_total(_redials_throttled)
    h = _Harness(nodes, nshards, pod_size, hb_period_s, tasks_per_round)
    probe_cp = None
    chaos: Optional[Dict[str, Any]] = None
    try:
        for pod in h.pods:
            pod.register()
        t_start = time.monotonic()
        h._eval_thread.start()
        for pod in h.pods:
            pod.thread.start()
        # let the fleet reach steady state before measuring latency
        time.sleep(min(2.0, duration_s / 3.0))
        actuation = h.measure_actuation()
        if kill_shard:
            probe_cp = ShardedControlPlane(h.head_addr, h.shard_addrs,
                                           role="simprobe")
            time.sleep(duration_s / 4.0)
            chaos = h.kill_and_probe(probe_cp, "scale_sim/probe")
        remaining = duration_s - (time.monotonic() - t_start)
        if remaining > 0:
            time.sleep(remaining)
        wall = time.monotonic() - t_start
        h.stop.set()
    finally:
        h.shutdown()
        if probe_cp is not None:
            probe_cp.close()

    lags = [lag for pod in h.pods for lag in pod.hb_lags]
    local = sum(p.local_admits for p in h.pods)
    delegated = sum(p.delegated for p in h.pods)
    failed = sum(p.failed for p in h.pods)
    if chaos:
        failed += chaos["failed_requests"]
    head_cpu = h.timed.cpu_s + h._eval_cpu + h._sched_cpu
    result = {
        "nodes": nodes,
        "pods": len(h.pods),
        "nshards": nshards,
        "duration_s": round(wall, 3),
        "rounds": sum(p.rounds for p in h.pods),
        "head_cpu_cores": round(head_cpu / max(wall, 1e-9), 4),
        "head_rpc_calls": h.timed.calls,
        "head_rpc_cpu_s": round(h.timed.cpu_s, 4),
        "heartbeat_lag_ms_p95": round(_p95(lags) * 1e3, 2),
        "actuation_latency_s": round(actuation, 4),
        "sched_local_admits": local,
        "sched_delegated": delegated,
        "sched_tasks_per_s": round((local + delegated) / max(wall, 1e-9), 1),
        "kv_ops": sum(p.kv_ops for p in h.pods),
        "failed_requests": failed,
        "reconnects": _counter_total(_reconnects_total) - reconnects0,
        "redials_throttled": _counter_total(_redials_throttled) - redials0,
        "reconnect_spike": any(a["rule"] == "reconnect_spike"
                               for a in h.hp.active()),
        "chaos": chaos,
    }
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="ray_tpu federated control-plane scale harness")
    ap.add_argument("--nodes", type=int, default=64)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--duration", type=float, default=6.0)
    ap.add_argument("--pod-size", type=int, default=8)
    ap.add_argument("--hb-period", type=float, default=0.5)
    ap.add_argument("--kill-shard", action="store_true",
                    help="SIGKILL a shard primary mid-run (chaos gate)")
    args = ap.parse_args(argv)
    res = run_scale_sim(nodes=args.nodes, nshards=args.shards,
                        duration_s=args.duration, pod_size=args.pod_size,
                        hb_period_s=args.hb_period,
                        kill_shard=args.kill_shard)
    print(json.dumps(res, indent=2))
    if res["failed_requests"] > 0:
        print("FAIL: lost requests", file=sys.stderr)
        return 1
    if args.kill_shard and (not res["chaos"]
                            or res["chaos"]["recovery_s"] is None):
        print("FAIL: no recovery after shard kill", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
