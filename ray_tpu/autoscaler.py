"""Autoscaler: resource-demand-driven slice provisioning.

Reference: `python/ray/autoscaler/_private/autoscaler.py ::
StandardAutoscaler` + `resource_demand_scheduler.py` + `node_provider.py`,
rebuilt v2-shaped (SURVEY.md §7.5: build only the instance-manager style
surface). TPU delta: the provisioning unit is a SLICE (host group with ICI
topology), not a single VM — matching the slice-is-the-failure-domain
design (§7.1.3).

NodeProvider is the pluggable boundary (reference's AWS/GCP/KubeRay
providers); FakeNodeProvider backs tests exactly like the reference's
fake_multi_node provider.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .core.config import config
from .core.logging import get_logger

logger = get_logger("autoscaler")


@dataclasses.dataclass
class NodeType:
    """A provisionable shape, e.g. one v5p-16 slice = 4 hosts x 4 chips."""

    name: str
    resources: Dict[str, float]  # per-node resources
    num_hosts: int = 1  # hosts per provisioned unit (slice granularity)
    max_workers: int = 10  # max provisioned units
    topology: Optional[str] = None  # e.g. "2x2x4"


class NodeProvider:
    """Pluggable cloud boundary."""

    def create_nodes(self, node_type: NodeType, count: int) -> List[str]:
        raise NotImplementedError

    def terminate_node(self, node_id: str) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> Dict[str, str]:
        """-> {provider_node_id: node_type_name}"""
        raise NotImplementedError


class FakeNodeProvider(NodeProvider):
    """Adds virtual nodes to the local Runtime (the reference's
    RAY_FAKE_CLUSTER / FakeMultiNodeProvider pattern)."""

    def __init__(self, runtime=None):
        from . import api

        self.runtime = runtime or api._auto_init()
        self._nodes: Dict[str, Any] = {}
        self._types: Dict[str, str] = {}
        self._counter = 0

    def create_nodes(self, node_type: NodeType, count: int) -> List[str]:
        out = []
        for _ in range(count):
            for _h in range(node_type.num_hosts):
                self._counter += 1
                pid = f"fake-{node_type.name}-{self._counter}"
                info = self.runtime.add_node(resources=dict(node_type.resources))
                self._nodes[pid] = info.node_id
                self._types[pid] = node_type.name
                out.append(pid)
        return out

    def terminate_node(self, node_id: str) -> None:
        nid = self._nodes.pop(node_id, None)
        self._types.pop(node_id, None)
        if nid is not None:
            self.runtime.remove_node(nid)

    def non_terminated_nodes(self) -> Dict[str, str]:
        return dict(self._types)


class SubprocessNodeProvider(NodeProvider):
    """Provisions REAL worker runtimes: each node is an OS process that
    joins the head over the cross-host execution plane (core/cross_host.py,
    `init(address=...)`) and executes dispatched tasks/actors.

    This is the executable shape of the reference's provider matrix
    (`autoscaler/_private/node_provider.py` implementations): swap the
    subprocess spawn for a cloud API call and the rest of the loop is
    unchanged. Demand-driven scale-up launches a joiner; idle scale-down
    stops it through the head's dispatch channel (worker exits cleanly).
    """

    def __init__(self, runtime=None, extra_env: Optional[Dict[str, str]] = None):
        from . import api

        self.runtime = runtime or api._auto_init()
        cp_server = getattr(self.runtime, "_cp_server", None)
        if cp_server is None:
            raise RuntimeError(
                "SubprocessNodeProvider needs a joinable head: init with "
                "system_config={'control_plane_rpc_port': 0}"
            )
        self.head_address = cp_server.address
        self.extra_env = dict(extra_env or {})
        self._procs: Dict[str, Any] = {}  # provider id -> Popen
        self._types: Dict[str, str] = {}
        self._nodes: Dict[str, Any] = {}  # provider id -> NodeID (lazy)
        self._counter = 0

    def create_nodes(self, node_type: NodeType, count: int) -> List[str]:
        import os
        import subprocess
        import sys
        import textwrap

        out = []
        for _ in range(count):
            for _h in range(node_type.num_hosts):
                self._counter += 1
                pid = f"sub-{node_type.name}-{self._counter}"
                code = textwrap.dedent(f"""
                    from ray_tpu.core.cross_host import join_cluster
                    w = join_cluster(
                        {self.head_address!r},
                        num_cpus={node_type.resources.get("CPU", 1.0)},
                        num_tpus={node_type.resources.get("TPU", 0.0)},
                        resources={ {k: v for k, v in node_type.resources.items()
                                     if k not in ("CPU", "TPU")} !r},
                        labels={{"provider_node_id": {pid!r}}},
                    )
                    w.wait()
                """)
                env = dict(os.environ)
                env.setdefault("JAX_PLATFORMS", "cpu")
                env.update(self.extra_env)
                proc = subprocess.Popen([sys.executable, "-c", code], env=env)
                self._procs[pid] = proc
                self._types[pid] = node_type.name
                out.append(pid)
                logger.info("provisioned worker %s (pid %d) joining %s",
                            pid, proc.pid, self.head_address)
        return out

    def _resolve_node_id(self, pid: str):
        nid = self._nodes.get(pid)
        if nid is not None:
            return nid
        for node in self.runtime.control_plane.alive_nodes():
            if node.labels.get("provider_node_id") == pid:
                self._nodes[pid] = node.node_id
                return node.node_id
        return None

    def terminate_node(self, node_id: str) -> None:
        nid = self._nodes.get(node_id) or self._resolve_node_id(node_id)
        proc = self._procs.pop(node_id, None)
        self._types.pop(node_id, None)
        self._nodes.pop(node_id, None)
        graceful = nid is not None and nid in self.runtime.agents
        if graceful:
            # deliberate scale-down: notify so the worker exits instead of
            # treating the lost head connection as a restart and rejoining
            self.runtime.remove_node(nid, notify=True)
        if proc is not None:
            try:
                # short grace only when the worker was actually told to
                # exit; a not-yet-joined worker has nothing to hear
                proc.wait(timeout=5 if graceful else 0.1)
            except Exception:  # noqa: BLE001 — escalate
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except Exception:  # noqa: BLE001 — last resort, and reap
                    proc.kill()
                    proc.wait(timeout=5)

    def non_terminated_nodes(self) -> Dict[str, str]:
        # reap silently-died joiners so the scaler re-launches capacity
        for pid, proc in list(self._procs.items()):
            if proc.poll() is not None:
                logger.warning("provisioned worker %s exited rc=%s",
                               pid, proc.returncode)
                self._procs.pop(pid, None)
                self._types.pop(pid, None)
                self._nodes.pop(pid, None)
        # refresh the NodeID mapping (used by idle scale-down) from ONE
        # alive-nodes snapshot rather than one scan per unresolved pid
        unresolved = [p for p in self._types if p not in self._nodes]
        if unresolved:
            by_label = {
                n.labels.get("provider_node_id"): n.node_id
                for n in self.runtime.control_plane.alive_nodes()
            }
            for pid in unresolved:
                nid = by_label.get(pid)
                if nid is not None:
                    self._nodes[pid] = nid
        return dict(self._types)


class TPUVMNodeProvider(NodeProvider):
    """Provisions TPU-VM slices through the GCP TPU API (reference:
    `autoscaler/_private/gcp/node_provider.py` + its TPU-pod support;
    v2 instance-manager shape per SURVEY §7.5).

    The cloud boundary is an injectable `api_client` with the gcloud
    surface this provider drives:

        create_tpu_vm(name, accelerator_type, zone, startup_script) -> op
        delete_tpu_vm(name, zone) -> op
        list_tpu_vms(zone) -> [{"name", "state", "accelerator_type"}]

    A real deployment passes a thin wrapper over
    `google.cloud.tpu_v2.TpuClient` (or `gcloud compute tpus tpu-vm`);
    tests pass a mock that records the calls — and can "boot" the VM by
    executing the startup script locally, which is exactly what a fresh
    TPU-VM does: `ray-tpu start --address <head>` joins the cross-host
    plane and the rest of the autoscaler loop is provider-agnostic.

    NodeType.topology (e.g. "2x2x4") selects the accelerator_type; one
    create call provisions the whole slice (the TPU API's granularity is
    the slice, matching slice-is-the-failure-domain, SURVEY §7.1.3)."""

    STATE_PENDING = ("CREATING", "STARTING", "PROVISIONING")
    STATE_READY = ("READY", "ACTIVE")

    def __init__(self, head_address: str, api_client, zone: str,
                 name_prefix: str = "ray-tpu"):
        self.head_address = head_address
        self.api = api_client
        self.zone = zone
        self.name_prefix = name_prefix
        self._types: Dict[str, str] = {}  # vm name -> node_type.name
        self._counter = 0

    # -- the exact strings a real TPU-VM boots with -------------------------
    def _accelerator_type(self, node_type: NodeType) -> str:
        if node_type.topology:
            chips = 1
            for d in node_type.topology.split("x"):
                chips *= int(d)
            gen = node_type.resources.get("tpu_generation", "v5p")
            gen = gen if isinstance(gen, str) else "v5p"
            return f"{gen}-{chips}"
        return f"v5litepod-{int(node_type.resources.get('TPU', 1))}"

    def _startup_script(self, node_type: NodeType, vm_name: str) -> str:
        extra = {k: v for k, v in node_type.resources.items()
                 if k not in ("CPU", "TPU", "tpu_generation")}
        return (
            "#!/bin/bash\n"
            "# every host of the slice joins the head's cross-host plane\n"
            f"ray-tpu start --address {self.head_address} "
            f"--num-cpus {node_type.resources.get('CPU', 1)} "
            f"--resources '{extra!r}' "
            f"--labels provider_node_id={vm_name}\n"
        )

    # -- NodeProvider surface ----------------------------------------------
    def create_nodes(self, node_type: NodeType, count: int) -> List[str]:
        out = []
        for _ in range(count):
            self._counter += 1
            name = f"{self.name_prefix}-{node_type.name}-{self._counter}"
            self.api.create_tpu_vm(
                name=name,
                accelerator_type=self._accelerator_type(node_type),
                zone=self.zone,
                startup_script=self._startup_script(node_type, name),
            )
            self._types[name] = node_type.name
            out.append(name)
            logger.info("requested TPU-VM %s (%s) in %s", name,
                        self._accelerator_type(node_type), self.zone)
        return out

    def terminate_node(self, node_id: str) -> None:
        self._types.pop(node_id, None)
        self.api.delete_tpu_vm(name=node_id, zone=self.zone)

    def non_terminated_nodes(self) -> Dict[str, str]:
        live = {}
        for vm in self.api.list_tpu_vms(zone=self.zone):
            state = str(vm.get("state", "")).upper()
            if state in self.STATE_PENDING or state in self.STATE_READY:
                name = vm["name"]
                if name in self._types:
                    live[name] = self._types[name]
        # forget VMs the cloud no longer reports (preempted/deleted out
        # of band) so the scaler re-launches the capacity
        for name in list(self._types):
            if name not in live:
                self._types.pop(name, None)
        return live


class Autoscaler:
    """Reconciles pending resource demand against provisioned capacity.

    Demand sources: the scheduler's infeasible/pending queue (the
    reference reads the same from GCS resource load), plus — when a
    health plane is attached — the demand hints carried by firing alert
    rules (core/health.py `Rule.demand`): e.g. a sustained
    `serve_disagg_queue_depth{role=decode}` breach can ask for another
    decode-capable node before the pending queue ever backs up.
    """

    def __init__(
        self,
        node_types: List[NodeType],
        provider: NodeProvider,
        runtime=None,
        idle_timeout_s: float = 60.0,
        update_interval_s: float = 1.0,
        health_plane=None,
    ):
        from . import api

        self.runtime = runtime or api._auto_init()
        self.health_plane = health_plane
        self.runtime.autoscaling_enabled = True
        self.node_types = {t.name: t for t in node_types}
        self.provider = provider
        self.idle_timeout_s = idle_timeout_s
        self.update_interval_s = update_interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._idle_since: Dict[str, float] = {}
        # capacity launched but not yet joined: absorbs repeat demand so a
        # slow-joining node (SubprocessNodeProvider: seconds) isn't
        # re-launched every tick. Entries expire after launch_grace_s —
        # a joiner that never arrives is eventually retried.
        self.launch_grace_s = 30.0
        self._launching: List[tuple] = []  # (monotonic_ts, remaining_cap)
        # hysteresis: launches only happen outside the cooldown window
        # that the previous scale-up wave opened, and one pass may take
        # at most autoscale_step_max launch actions — so a burst of
        # alerts produces ONE bounded wave, not one node per alert
        self._last_wave_ts = float("-inf")

    # -- demand → decisions --------------------------------------------------

    def pending_demand(self) -> List[Dict[str, float]]:
        demands = list(self.runtime.pending_resource_demand())
        if self.health_plane is not None:
            try:
                demands.extend(self.health_plane.pending_demand())
            except Exception:  # noqa: BLE001 — health hints are advisory
                logger.warning("health-plane demand read failed",
                               exc_info=True)
        return demands

    def _fits(self, demand: Dict[str, float], resources: Dict[str, float]) -> bool:
        return all(resources.get(k, 0.0) >= v for k, v in demand.items())

    def _cluster_can_fit(self, demand: Dict[str, float]) -> bool:
        for node in self.runtime.control_plane.alive_nodes():
            if self._fits(demand, node.resources_available):
                return True
        return False

    def update(self) -> Dict[str, int]:
        """One reconcile pass. Returns {node_type: launched_count}."""
        launched: Dict[str, int] = {}
        demands = [d for d in self.pending_demand() if not self._cluster_can_fit(d)]
        by_type = self.provider.non_terminated_nodes()
        # In-flight launch capacity absorbs repeat demand (bin-packing-
        # lite, the reference's resource_demand_scheduler shape): a
        # 2-member gang provisions ONE fitting node, and a node still
        # JOINING (async providers) isn't re-launched every tick. A fresh
        # copy of each unexpired cap is spent per pass — the same pending
        # demand re-absorbs into it next tick instead of draining it.
        now = time.monotonic()
        alive_ids = {n.node_id for n in self.runtime.control_plane.alive_nodes()}
        # retire a launch entry as soon as SOME node that wasn't alive at
        # launch time joins (one join clears one entry, oldest first);
        # grace expiry covers joiners that die before registering
        assigned: set = set()
        kept = []
        for ts, cap, known in sorted(self._launching, key=lambda e: e[0]):
            new = alive_ids - known - assigned
            if new:
                assigned.add(next(iter(new)))
                continue
            if now - ts < self.launch_grace_s:
                kept.append((ts, cap, known))
        self._launching = kept
        pending_caps: List[Dict[str, float]] = [
            dict(cap) for _ts, cap, _known in self._launching
        ]
        cooldown_s = float(config.get("autoscale_cooldown_s"))
        step_max = max(1, int(config.get("autoscale_step_max")))
        in_cooldown = now - self._last_wave_ts < cooldown_s
        steps = deferred = 0
        for demand in demands:
            absorbed = False
            for cap in pending_caps:
                if self._fits(demand, cap):
                    for k, v in demand.items():
                        cap[k] = cap.get(k, 0.0) - v
                    absorbed = True
                    break
            if absorbed:
                continue
            if in_cooldown or steps >= step_max:
                deferred += 1
                continue
            for t in self.node_types.values():
                existing = sum(1 for v in by_type.values() if v == t.name)
                if existing >= t.max_workers:
                    continue
                if self._fits(demand, t.resources):
                    self.provider.create_nodes(t, 1)
                    launched[t.name] = launched.get(t.name, 0) + 1
                    steps += 1
                    by_type[f"_pending{len(by_type)}"] = t.name
                    cap = dict(t.resources)
                    for k, v in demand.items():
                        cap[k] = cap.get(k, 0.0) - v
                    pending_caps.append(cap)
                    self._launching.append((now, dict(t.resources), set(alive_ids)))
                    break
        if steps:
            self._last_wave_ts = now
        if deferred:
            logger.info(
                "deferred %d unabsorbed demand(s): %s", deferred,
                "inside autoscale_cooldown_s window" if in_cooldown
                else "autoscale_step_max reached this pass")
        self._scale_down()
        return launched

    def _scale_down(self) -> None:
        """Terminate provider nodes idle (all resources free) past timeout."""
        now = time.monotonic()
        nodes_by_provider = self.provider.non_terminated_nodes()
        alive = {n.node_id: n for n in self.runtime.control_plane.alive_nodes()}
        for pid in list(nodes_by_provider):
            nid = getattr(self.provider, "_nodes", {}).get(pid)
            node = alive.get(nid) if nid is not None else None
            idle = node is not None and node.resources_available == node.resources_total
            if idle and not self.pending_demand():
                since = self._idle_since.setdefault(pid, now)
                if now - since > self.idle_timeout_s:
                    logger.info("terminating idle node %s", pid)
                    self.provider.terminate_node(pid)
                    self._idle_since.pop(pid, None)
            else:
                self._idle_since.pop(pid, None)

    # -- loop ----------------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.update()
            except Exception:
                logger.warning("autoscaler update failed", exc_info=True)
            self._stop.wait(self.update_interval_s)

    def stop(self) -> None:
        self._stop.set()
