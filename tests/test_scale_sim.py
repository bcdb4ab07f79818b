"""Federated control plane (ISSUE 19): shard routing, pod aggregation,
bottom-up admission, gossip TTL sweep, heartbeat delta-encoding, and the
scale harness itself at smoke size.

The chaos-grade shard-kill coverage lives in test_shard_chaos.py; this
file is tier-1 — every test here is fast and in-process except the two
harness smokes, which spawn real shard subprocesses at N=8.
"""

import threading
import time

import pytest

from ray_tpu.core import node_agent
from ray_tpu.core.aggregator import (_AGG_ALLOWED_METHODS,
                                     _AGG_IDEMPOTENT_METHODS, PodAggregator,
                                     merge_metric_snapshots)
from ray_tpu.core.control_plane import (GOSSIP_RELAY_PREFIX, ControlPlane,
                                        NodeInfo, NodeState)
from ray_tpu.core.ids import NodeID
from ray_tpu.core.rpc import (ShardedControlPlane, serve_control_plane,
                              shard_for_key)
from ray_tpu.core.shard import (_SHARD_ALLOWED_METHODS,
                                _SHARD_IDEMPOTENT_METHODS,
                                _STANDBY_ALLOWED_METHODS,
                                _STANDBY_IDEMPOTENT_METHODS,
                                ControlPlaneShard, FederatedControlPlane,
                                ShardSupervisor)
from ray_tpu.util import slo


def _register(cp, n=2, cpus=8.0):
    nodes = []
    for i in range(n):
        nid = NodeID.generate()
        cp.register_node(NodeInfo(node_id=nid, address=f"sim://{i}",
                                  resources_total={"CPU": cpus}))
        nodes.append(nid)
    return nodes


# --------------------------------------------------------------------------
# bottom-up admission: the shared rule and the bulk-heartbeat head surface
# --------------------------------------------------------------------------


class TestAdmission:
    def test_admits_feasible_and_under_threshold(self):
        assert node_agent.admits({"CPU": 8.0}, {"CPU": 8.0},
                                 {"CPU": 1.0}, 0.5)

    def test_rejects_infeasible_demand(self):
        # a demand no amount of idleness can satisfy is never admitted
        assert not node_agent.admits({"CPU": 8.0}, {"CPU": 8.0},
                                     {"CPU": 9.0}, 0.5)

    def test_rejects_when_busy_or_over_threshold(self):
        assert not node_agent.admits({"CPU": 8.0}, {"CPU": 0.5},
                                     {"CPU": 1.0}, 0.5)
        # feasible and available, but utilization crossed the spread
        # threshold: delegate to the head for cluster-wide placement
        assert not node_agent.admits({"CPU": 8.0}, {"CPU": 4.0},
                                     {"CPU": 1.0}, 0.5)

    def test_node_agent_try_admit(self):
        agent = node_agent.NodeAgent.__new__(node_agent.NodeAgent)
        agent._stopped = threading.Event()
        agent.resources = node_agent.ResourceTracker({"CPU": 4.0})
        assert agent.try_admit({"CPU": 1.0}, spread_threshold=0.9)
        assert not agent.try_admit({"CPU": 16.0}, spread_threshold=0.9)
        agent._stopped.set()
        assert not agent.try_admit({"CPU": 1.0}, spread_threshold=0.9)

    def test_heartbeat_bulk_verdicts(self):
        cp = ControlPlane()
        known, _ = _register(cp)
        stranger = NodeID.generate()
        verdicts = cp.heartbeat_bulk([(known, {"CPU": 3.0}),
                                      (stranger, None)])
        assert verdicts[known.hex()] is True
        assert verdicts[stranger.hex()] is False
        assert cp.get_node(known).resources_available == {"CPU": 3.0}


# --------------------------------------------------------------------------
# gossip-key TTL sweep (satellite: KV hygiene at fleet scale)
# --------------------------------------------------------------------------


class TestGossipSweep:
    def test_sweeps_stale_keys_of_silent_dead_nodes(self):
        cp = ControlPlane()
        alive, ghost = _register(cp)
        cp.kv_put(f"object_transfer_load/{alive.hex()}", "0.5")
        cp.kv_put(f"object_transfer_load/{ghost.hex()}", "0.9")
        cp.kv_put(f"{GOSSIP_RELAY_PREFIX}deadbeef", f"slot|{ghost.hex()}")
        cp.kv_put("job/durable", "keep")  # not a gossip namespace
        # the ghost vanishes WITHOUT mark_node_dead (the case the sweep
        # exists for): reap via the health path, then sweep with ttl=0
        cp._nodes[ghost].state = NodeState.DEAD
        swept = cp.sweep_gossip(ttl_s=0.0)
        assert swept == 2
        assert cp.kv_get(f"object_transfer_load/{ghost.hex()}") is None
        assert cp.kv_get(f"{GOSSIP_RELAY_PREFIX}deadbeef") is None
        assert cp.kv_get(f"object_transfer_load/{alive.hex()}") == "0.5"
        assert cp.kv_get("job/durable") == "keep"

    def test_fresh_keys_survive_within_ttl(self):
        cp = ControlPlane()
        (ghost,) = _register(cp, n=1)
        cp.kv_put(f"object_transfer_host/{ghost.hex()}", "token")
        cp._nodes[ghost].state = NodeState.DEAD
        assert cp.sweep_gossip(ttl_s=3600.0) == 0
        assert cp.kv_get(f"object_transfer_host/{ghost.hex()}") == "token"


# --------------------------------------------------------------------------
# pod aggregation: merge semantics + one-flush-per-pod head traffic
# --------------------------------------------------------------------------


class TestAggregation:
    def test_merge_metric_snapshots_counters_sum_gauges_last(self):
        a = [{"name": "ops_total", "kind": "counter", "description": "",
              "samples": [("ops_total", [["node", "a"]], 3.0)]},
             {"name": "depth", "kind": "gauge", "description": "",
              "samples": [("depth", [], 5.0)]}]
        b = [{"name": "ops_total", "kind": "counter", "description": "",
              "samples": [("ops_total", [["node", "a"]], 4.0),
                          ("ops_total", [["node", "b"]], 1.0)]},
             {"name": "depth", "kind": "gauge", "description": "",
              "samples": [("depth", [], 7.0)]}]
        merged = {m["name"]: m for m in merge_metric_snapshots([a, b])}
        ops = dict(((tuple(map(tuple, tags))), v)
                   for _, tags, v in merged["ops_total"]["samples"])
        assert ops[(("node", "a"),)] == 7.0
        assert ops[(("node", "b"),)] == 1.0
        assert merged["depth"]["samples"][0][2] == 7.0

    def test_merged_to_snapshots_round_trip(self):
        d = slo.Digest("rt_lat", {"role": "t"})
        for v in (0.001, 0.01, 0.1, 0.1, 0.5):
            d.add(v)
        snap = d.to_snapshot()
        merged_once = slo.merge_snapshots([snap, snap])
        wire = slo.merged_to_snapshots(merged_once)
        # wire form survives a second merge: quantiles match exactly
        again = slo.merge_snapshots(wire)
        key = ("rt_lat", (("role", "t"),))
        assert slo.quantile_from_counts(merged_once[key]["counts"], 0.95) \
            == slo.quantile_from_counts(again[key]["counts"], 0.95)
        assert again[key]["count"] == 10  # two copies of five samples

    def test_pod_aggregator_flush_and_verdicts(self):
        cp = ControlPlane()
        member, _ = _register(cp)
        ghost = NodeID.generate()
        agg = PodAggregator("t0", cp, flush_period_s=3600.0)
        assert agg.ingest_heartbeat(member, {"CPU": 2.0})  # optimistic
        assert agg.ingest_heartbeat(ghost, None)           # not judged yet
        agg.ingest_telemetry(member.hex(), metrics=[
            {"name": "m", "kind": "counter", "description": "",
             "samples": [("m", [], 1.0)]}])
        agg.ingest_profile({"main;f": 3})
        agg.ingest_profile({"main;f": 2, "main;g": 1})
        assert agg.flush()
        # verdicts fanned back from the bulk reply
        assert agg.ingest_heartbeat(member, None) is True
        assert agg.ingest_heartbeat(ghost, None) is False
        # the head saw ONE pod-rolled report, not per-node reports
        snaps = cp.telemetry_snapshots()
        assert "pod:t0" in snaps
        assert snaps["pod:t0"]["role"] == "pod"
        assert agg.merged_profile() == {"main;f": 5, "main;g": 1}
        # beat landed: member's available resources reached the head
        assert cp.get_node(member).resources_available == {"CPU": 2.0}


# --------------------------------------------------------------------------
# shard routing + registries + K=1 equivalence
# --------------------------------------------------------------------------


class TestSharding:
    def test_shard_for_key_is_stable_and_spread(self):
        keys = [f"object_transfer_load/{i:032x}" for i in range(64)]
        owners = {k: shard_for_key(k, 4) for k in keys}
        assert owners == {k: shard_for_key(k, 4) for k in keys}
        assert len(set(owners.values())) > 1
        assert all(0 <= s < 4 for s in owners.values())
        assert all(shard_for_key(k, 1) == 0 for k in keys)

    def test_registries_idempotent_subset_of_allowed(self):
        # the invariant raylint R3 enforces statically, checked live
        assert _SHARD_IDEMPOTENT_METHODS <= _SHARD_ALLOWED_METHODS
        assert _STANDBY_IDEMPOTENT_METHODS <= _STANDBY_ALLOWED_METHODS
        assert _AGG_IDEMPOTENT_METHODS <= _AGG_ALLOWED_METHODS
        assert "promote" not in _STANDBY_IDEMPOTENT_METHODS

    def test_client_routes_kv_and_dir_to_owning_shard(self):
        head = ControlPlane()
        shards = [ControlPlaneShard(i, 2) for i in range(2)]
        from ray_tpu.core.rpc import ControlPlaneServer
        head_srv = serve_control_plane(head)
        shard_srvs = [ControlPlaneServer(s, port=0,
                                         allowed_methods=_SHARD_ALLOWED_METHODS)
                      for s in shards]
        client = ShardedControlPlane(
            head_srv.address, [s.address for s in shard_srvs],
            role="test", route_directory=True)
        try:
            keys = [f"k/{i}" for i in range(8)]
            for k in keys:
                client.kv_put(k, k.upper())
            for k in keys:
                owner = shard_for_key(k, 2)
                assert shards[owner].kv_get(k) == k.upper()
                assert shards[1 - owner].kv_get(k) is None
                assert client.kv_get(k) == k.upper()
            assert sorted(client.kv_keys("k/")) == sorted(keys)
            client.dir_add_location("obj1", "aa")
            owner = shards[shard_for_key("obj1", 2)]
            assert owner.dir_locations("obj1") == ["aa"]
            assert client.dir_locations("obj1") == ["aa"]
            # pubsub: channel owner's shard carries the subscription
            got = threading.Event()
            client.subscribe("chan-x", lambda m: got.set())
            time.sleep(0.1)
            shards[shard_for_key("chan-x", 2)].publish("chan-x", {"v": 1})
            assert got.wait(5.0)
        finally:
            client.close()
            head_srv.stop()
            for srv in shard_srvs:
                srv.stop()

    def test_k1_federation_is_behavior_identical(self):
        """The equivalence gate's unit form: K=1 federated kv/pubsub acts
        exactly like the plain head plane, plus membership forwarding."""
        inner = ControlPlane()
        sup = ShardSupervisor(1, spawn_standby=False)
        sup.start()
        fed = None
        try:
            fed = FederatedControlPlane(inner, sup)
            (node,) = _register(fed, n=1)  # __getattr__ -> inner
            assert inner.get_node(node) is not None
            fed.kv_put("a/b", "v1")
            assert fed.kv_get("a/b") == "v1"
            assert fed.kv_keys("a/") == ["a/b"]
            assert inner.kv_get("a/b") is None  # routed, not mirrored
            fed.kv_del("a/b")
            assert fed.kv_get("a/b") is None
            got = threading.Event()
            fed.pubsub.subscribe("alerts", lambda m: got.set())
            time.sleep(0.1)
            fed.pubsub.publish("alerts", {"rule": "x"})
            assert got.wait(5.0)
            # mark_node_dead purges the dead node's gossip keys shard-side
            fed.kv_put(f"object_transfer_load/{node.hex()}", "0.9")
            fed.mark_node_dead(node)
            assert fed.kv_get(f"object_transfer_load/{node.hex()}") is None
        finally:
            if fed is not None:
                fed.close()
            sup.stop()


# --------------------------------------------------------------------------
# heartbeat delta-encoding (satellite: telemetry_bytes_total)
# --------------------------------------------------------------------------


class TestDeltaEncoding:
    def _stub(self, recorder):
        class _CP:
            def report_telemetry(self, *a, **kw):
                recorder.append(kw)
                return True

        class _Stub:
            pass

        s = _Stub()
        s.node_id = NodeID.generate()
        s.agent = None
        s.control_plane = _CP()
        s._last_telemetry = -1e9
        s._telemetry_span_cursor = 0
        s._telemetry_event_cursor = 0
        s._telemetry_sent_hash = {}
        return s

    def test_unchanged_fields_ship_as_none(self, monkeypatch):
        from ray_tpu.core.cross_host import WorkerRuntime
        from ray_tpu.util import profiler

        # resource gauges mutate the metrics snapshot every refresh;
        # pin them so the steady-state comparison is deterministic
        monkeypatch.setattr(profiler, "update_resource_gauges", lambda: None)
        reports = []
        stub = self._stub(reports)
        WorkerRuntime._maybe_report_telemetry(stub)
        assert reports[0]["digests"] is not None
        stub._last_telemetry = -1e9
        WorkerRuntime._maybe_report_telemetry(stub)
        second = reports[1]
        # nothing changed between beats: the payload fields delta to None
        assert second["digests"] is None
        assert second["objects"] is None
        assert second["channels"] is None

    def test_changed_field_reships_and_counts_bytes(self, monkeypatch):
        from ray_tpu.core.cross_host import _m_tele_bytes, WorkerRuntime
        from ray_tpu.util import profiler

        monkeypatch.setattr(profiler, "update_resource_gauges", lambda: None)
        reports = []
        stub = self._stub(reports)
        WorkerRuntime._maybe_report_telemetry(stub)
        before = _m_tele_bytes.get({"field": "digests"})
        slo.observe("delta_probe_lat", 0.25, {"t": "x"})
        stub._last_telemetry = -1e9
        WorkerRuntime._maybe_report_telemetry(stub)
        assert reports[1]["digests"] is not None
        assert _m_tele_bytes.get({"field": "digests"}) > before

    def test_failed_flush_reships_next_beat(self):
        from ray_tpu.core.cross_host import WorkerRuntime

        reports = []
        stub = self._stub(reports)
        ok_cp = stub.control_plane

        class _DownCP:
            def report_telemetry(self, *a, **kw):
                raise OSError("head unreachable")

        stub.control_plane = _DownCP()
        WorkerRuntime._maybe_report_telemetry(stub)
        # hashes must NOT advance on a failed flush
        assert stub._telemetry_sent_hash == {}
        stub.control_plane = ok_cp
        stub._last_telemetry = -1e9
        WorkerRuntime._maybe_report_telemetry(stub)
        assert reports[0]["digests"] is not None


# --------------------------------------------------------------------------
# the harness itself, smoke-sized (`make scale` runs it at N=32)
# --------------------------------------------------------------------------


class TestScaleHarness:
    def test_smoke_n8(self):
        from ray_tpu.util.scale_sim import run_scale_sim

        res = run_scale_sim(nodes=8, nshards=2, duration_s=2.5)
        assert res["failed_requests"] == 0
        assert res["rounds"] > 0
        assert res["head_rpc_calls"] > 0
        assert res["head_cpu_cores"] < 1.0
        assert res["sched_local_admits"] > 0
        assert res["sched_delegated"] > 0
        assert res["kv_ops"] > 0

    def test_shard_kill_ride_through_n8(self):
        from ray_tpu.util.scale_sim import run_scale_sim

        res = run_scale_sim(nodes=8, nshards=2, duration_s=4.0,
                            kill_shard=True)
        assert res["failed_requests"] == 0, res
        chaos = res["chaos"]
        assert chaos is not None and chaos["recovery_s"] is not None
        assert chaos["recovery_s"] < 10.0
        assert chaos["failovers"] >= 1
        assert chaos["standby_respawned"]
        # the dial-jitter/rate-cap satellite: failover must not trip the
        # reconnect-storm alert
        assert not res["reconnect_spike"]
