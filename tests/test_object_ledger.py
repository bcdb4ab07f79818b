"""Object-plane observability: cluster-wide object ledger, per-edge
transfer-flow accounting, and leak/staleness detection (ISSUE 10).

Reference analogue: upstream ray's `ray memory` joins the reference table
(`src/ray/core_worker/reference_count.cc`) with the object directory so
one command answers "every live object, where it lives, who holds it,
why". These tests assert the same surface here: ledger rows carry pin
reason / creator / age and federate across hosts via heartbeat telemetry;
per-edge flow sums reconcile against object_pull_bytes; a deliberately
leaked object is flagged by the sweep AND fires an `object_leak` health
alert; and `locate` never hands out a holder the control plane already
marked DEAD (satellite regression, head and worker side).
"""

import os
import pickle
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import object_ledger
from ray_tpu.core.core_worker import ObjectRef
from ray_tpu.core.ids import NodeID, ObjectID, TaskID
from ray_tpu.core.node_agent import ObjectDirectory
from ray_tpu.core.object_store import ObjectLostError, SealedBytes, seal_value
from ray_tpu.core.object_transfer import (
    KV_PREFIX,
    ObjectTransferClient,
    ObjectTransferServer,
    _pulled_bytes,
)

pytestmark = pytest.mark.objects


@pytest.fixture
def socket_pull_path():
    """Force pulls over the socket: the same-host shm handoff is a
    ZERO-socket path that records no flow edges by contract (see
    test_broadcast.py::TestSameHostHandoff), and these tests assert on
    the socket path's flow accounting."""
    from ray_tpu.core.config import config

    was = bool(config.object_transfer_shm_handoff)
    config.apply_overrides({"object_transfer_shm_handoff": False})
    yield
    config.apply_overrides({"object_transfer_shm_handoff": was})

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _oid(i: int = 0) -> ObjectID:
    return ObjectID.for_task_return(TaskID.of(), i)


@pytest.fixture
def runtime():
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4, num_tpus=0)
    yield rt
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Fake cross-host holder (same ducks test_object_plane.py uses)
# ---------------------------------------------------------------------------


class _LatencyStore:
    def __init__(self, latency: float = 0.0):
        self.latency = latency
        self._values = {}
        self.fetches = 0
        self._lock = threading.Lock()

    def seed(self, oid, value):
        self._values[oid] = seal_value(value)

    def contains(self, oid):
        return oid in self._values

    def get_raw(self, oid, timeout=None):
        time.sleep(self.latency)
        with self._lock:
            self.fetches += 1
        try:
            return self._values[oid]
        except KeyError:
            raise ObjectLostError(oid)

    def get(self, oid, timeout=None):
        value = self.get_raw(oid, timeout)
        return value.load() if isinstance(value, SealedBytes) else value

    def delete(self, oid):
        self._values.pop(oid, None)


class _FakeRemoteAgent:
    is_remote = True

    def __init__(self, store):
        self.node_id = NodeID.generate()
        self.store = store
        self._stopped = threading.Event()


def _seed_remote(rt, value, latency: float = 0.0):
    """One fake remote holder with one object; returns (ref, store)."""
    store = _LatencyStore(latency)
    agent = _FakeRemoteAgent(store)
    rt.directory.register_agent(agent)
    oid = _oid(0)
    store.seed(oid, value)
    rt.directory.add_location(oid, agent.node_id)
    return ObjectRef(oid, rt), store


# ---------------------------------------------------------------------------
# Ledger metadata + federation joins (tentpole part 1)
# ---------------------------------------------------------------------------


class TestLedgerMetadata:
    def test_put_annotates_pin_reason_and_creator(self, runtime):
        ref = ray_tpu.put(np.arange(1024))
        rows = runtime.driver_agent.store.ledger_records()
        row = next(r for r in rows if r["object_id"] == ref.object_id.hex())
        assert row["pin_reason"] == object_ledger.PIN_USER_PUT
        assert row["creator_task"] == "driver"
        assert row["size_bytes"] > 0
        assert row["age_s"] >= 0.0 and row["idle_s"] >= 0.0
        assert row["creator_pid"] == os.getpid()

    def test_escape_stamps_sticky_pin_reason(self, runtime):
        ref = ray_tpu.put("escapee")
        pickle.dumps(ref)  # __reduce__ -> note_escaped
        rows = runtime.driver_agent.store.ledger_records()
        row = next(r for r in rows if r["object_id"] == ref.object_id.hex())
        assert row["pin_reason"] == object_ledger.PIN_ESCAPED
        # sticky: later cache stamping must not overwrite the escape
        runtime.driver_agent.store.annotate(
            ref.object_id, pin_reason=object_ledger.PIN_CACHE)
        rows = runtime.driver_agent.store.ledger_records()
        row = next(r for r in rows if r["object_id"] == ref.object_id.hex())
        assert row["pin_reason"] == object_ledger.PIN_ESCAPED

    def test_task_return_carries_creator_task(self, runtime):
        @ray_tpu.remote(num_cpus=0.1)
        def produce():
            return list(range(100))

        ref = produce.remote()
        assert ray_tpu.get(ref, timeout=30) == list(range(100))
        rows = [r for a in runtime.agents.values()
                if not getattr(a, "is_remote", False)
                for r in a.store.ledger_records()]
        row = next(r for r in rows if r["object_id"] == ref.object_id.hex())
        assert "produce" in row["creator_task"]

    def test_collect_objects_joins_refcount_and_locations(self, runtime):
        ref = ray_tpu.put(b"x" * 4096)
        body = object_ledger.collect_objects(runtime)
        row = next(r for r in body["objects"]
                   if r["object_id"] == ref.object_id.hex())
        assert row["refcount"] >= 1
        node_hex = runtime.driver_agent.node_id.hex()[:12]
        assert node_hex in row["locations"]
        assert row["store"] == "memory"
        assert body["total_objects"] >= 1
        assert body["total_bytes"] >= row["size_bytes"]
        # per-store node summaries carry the stats() extras
        key = f"{row['node_id']}/memory"
        assert key in body["nodes"]
        assert "num_evictions" in body["nodes"][key]

    def test_pull_through_replica_pinned_as_cache(self, runtime):
        ref, _ = _seed_remote(runtime, {"v": 1})
        assert ray_tpu.get(ref) == {"v": 1}
        rows = runtime.driver_agent.store.ledger_records()
        row = next(r for r in rows if r["object_id"] == ref.object_id.hex())
        assert row["pin_reason"] == object_ledger.PIN_CACHE

    def test_pull_cold_snapshot_without_runtime(self):
        # collect_flows must render even before any init (dashboard boot)
        body = object_ledger.collect_flows()
        assert "edges" in body and "total_bytes" in body


class TestShmStatsParity:
    """Satellite (d): shm_store stats()/ledger parity with the memory
    store, so the ledger reports both backends uniformly."""

    def _store(self):
        from ray_tpu.core import shm_store

        name = f"raytpu-test-ledger-{os.getpid()}"
        try:
            return shm_store.ShmObjectStore(name, capacity=1 << 20,
                                            max_objects=64, create=True)
        except Exception as e:  # noqa: BLE001 — no arena on this host
            pytest.skip(f"shm arena unavailable: {e}")

    def test_stats_keys_match_memory_store(self, runtime):
        store = self._store()
        try:
            mem_keys = set(runtime.driver_agent.store.stats())
            assert set(store.stats()) == mem_keys
        finally:
            store.close()
            store.unlink_name() if hasattr(store, "unlink_name") else None

    def test_eviction_and_ledger_records(self):
        store = self._store()
        try:
            oid = os.urandom(20)
            store.put(oid, b"p" * 512)
            store.annotate(oid, pin_reason=object_ledger.PIN_CACHE,
                           creator_task="t")
            rows = store.ledger_records()
            row = next(r for r in rows if r["object_id"] == oid.hex())
            assert row["pin_reason"] == object_ledger.PIN_CACHE
            assert row["creator_task"] == "t"
            assert row["size_bytes"] == 512
            ev0 = store.stats()["num_evictions"]
            assert store.delete(oid)
            assert store.stats()["num_evictions"] == ev0 + 1
            assert not any(r["object_id"] == oid.hex()
                           for r in store.ledger_records())
        finally:
            store.close()


# ---------------------------------------------------------------------------
# Leak & staleness detection (tentpole part 3)
# ---------------------------------------------------------------------------


class TestLeakDetection:
    def test_escaped_object_with_no_refs_flagged_and_alerts(
            self, runtime, monkeypatch):
        """Acceptance criterion: a deliberately leaked object (escaped
        ref, zero live references, older than the threshold) is flagged
        by the sweep and fires an object_leak health alert."""
        monkeypatch.setenv("RAY_TPU_OBJECT_LEAK_AGE_S", "0.01")
        from ray_tpu.core.health import get_health_plane

        plane = get_health_plane(create=True)
        ref = ray_tpu.put(b"L" * 8192)
        oid = ref.object_id
        pickle.dumps(ref)  # escape: exempt from refcount-zero auto-free
        del ref
        assert runtime.reference_counter.count(oid) == 0
        assert runtime.driver_agent.store.contains(oid)  # survived GC
        time.sleep(0.05)
        report = object_ledger.sweep(runtime, force=True)
        flagged = [l for l in report["leaks"]
                   if l["object_id"] == oid.hex()]
        assert flagged and flagged[0]["kind"] == "pinned_no_refs"
        assert report["counts"]["pinned_no_refs"] >= 1
        assert report["leaked_bytes"]["pinned_no_refs"] >= 8192
        rules = {a["rule"] for a in plane.active()}
        assert "object_leak" in rules
        # the flagged rows ride the objects API body too
        body = object_ledger.collect_objects(runtime)
        assert body["leak_counts"].get("pinned_no_refs", 0) >= 1

    def test_directory_entry_on_unknown_dead_node_flagged(self, runtime):
        ghost = NodeID.generate()
        oid = _oid(3)
        with runtime.directory._lock:
            runtime.directory._locations.setdefault(oid, []).append(ghost)
        report = object_ledger.sweep(runtime, force=True)
        flagged = [l for l in report["leaks"]
                   if l["kind"] == "dead_node_location"
                   and l["object_id"] == oid.hex()]
        assert flagged and flagged[0]["node_id"] == ghost.hex()[:12]

    def test_healthy_put_not_flagged(self, runtime):
        ref = ray_tpu.put("healthy")
        report = object_ledger.sweep(runtime, force=True)
        assert not any(l["object_id"] == ref.object_id.hex()
                       for l in report["leaks"])

    def test_cold_cache_flagged(self, runtime, monkeypatch):
        monkeypatch.setenv("RAY_TPU_OBJECT_LEAK_AGE_S", "0.05")
        ref, _ = _seed_remote(runtime, b"c" * 2048)
        assert ray_tpu.get(ref) == b"c" * 2048  # pulls through -> cache pin
        time.sleep(0.15)  # age past the threshold with no re-hit
        report = object_ledger.sweep(runtime, force=True)
        flagged = [l for l in report["leaks"]
                   if l["object_id"] == ref.object_id.hex()]
        assert flagged and flagged[0]["kind"] == "cold_cache"

    def test_sweep_disabled_ledger_is_noop(self, runtime):
        os.environ["RAY_TPU_OBJECT_LEDGER"] = "false"
        object_ledger.reload_enabled()
        try:
            report = object_ledger.sweep(runtime, force=True)
            assert isinstance(report, dict)
        finally:
            del os.environ["RAY_TPU_OBJECT_LEDGER"]
            object_ledger.reload_enabled()


# ---------------------------------------------------------------------------
# DEAD-node locate regression (satellite a)
# ---------------------------------------------------------------------------


class TestDeadNodeLocate:
    def test_directory_alive_check_filters_holders(self):
        directory = ObjectDirectory()
        store = _LatencyStore()
        agent = _FakeRemoteAgent(store)
        directory.register_agent(agent)
        oid = _oid(0)
        directory.add_location(oid, agent.node_id)
        assert directory.locate(oid) is agent
        directory.alive_check = lambda nid: False  # head marked it DEAD
        assert directory.locate(oid) is None
        directory.alive_check = lambda nid: True
        assert directory.locate(oid) is agent

    def test_runtime_wires_alive_check(self, runtime):
        assert runtime.directory.alive_check is not None
        # unknown-to-the-control-plane holders (directory-only ducks)
        # still resolve; only tracked-and-DEAD nodes are vetoed
        ref, _ = _seed_remote(runtime, "reachable")
        assert ray_tpu.get(ref) == "reachable"

    def test_runtime_locate_skips_dead_tracked_node(self, runtime):
        """The regression itself: a node the control plane marked DEAD
        must never be handed out as a pull holder, even while its
        directory entries linger."""
        store = _LatencyStore()
        agent = _FakeRemoteAgent(store)
        # make it a TRACKED node, then kill it
        from ray_tpu.core.control_plane import NodeInfo

        info = NodeInfo(node_id=agent.node_id, address="127.0.0.1",
                        resources_total={"CPU": 1.0})
        runtime.control_plane.register_node(info)
        runtime.directory.register_agent(agent)
        oid = _oid(1)
        store.seed(oid, "stale")
        runtime.directory.add_location(oid, agent.node_id)
        assert runtime.directory.locate(oid) is agent  # ALIVE: served
        runtime.control_plane.mark_node_dead(agent.node_id)
        assert runtime.directory.locate(oid) is None  # DEAD: filtered

    def test_worker_locate_skips_dead_nodes(self):
        """Worker-side half: RemoteDirectoryClient.locate filters
        directory entries against the (cached) ALIVE set before minting
        pull holders."""
        from types import SimpleNamespace

        from ray_tpu.core.cross_host import RemoteDirectoryClient

        dead = NodeID.generate()
        alive = NodeID.generate()
        oid = _oid(2)

        class _FakeCP:
            def __init__(self):
                self.kv = {
                    KV_PREFIX + dead.hex(): b"127.0.0.1:1",
                    KV_PREFIX + alive.hex(): b"127.0.0.1:2",
                }

            def dir_locations(self, oid_hex):
                return [dead.hex(), alive.hex()]

            def alive_nodes(self):
                return [SimpleNamespace(node_id=alive)]

            def kv_get(self, key):
                return self.kv.get(key)

            def subscribe(self, *a, **k):
                pass

        client = RemoteDirectoryClient(_FakeCP(), NodeID.generate())
        holder = client.locate(oid)
        assert holder is not None
        assert holder.node_id == alive  # dead-node entry skipped
        assert holder.store._addr == "127.0.0.1:2"

    def test_worker_locate_none_when_all_holders_dead(self):
        from ray_tpu.core.cross_host import RemoteDirectoryClient

        dead = NodeID.generate()
        oid = _oid(2)

        class _FakeCP:
            def dir_locations(self, oid_hex):
                return [dead.hex()]

            def alive_nodes(self):
                return []

            def kv_get(self, key):
                return b"127.0.0.1:1"

            def subscribe(self, *a, **k):
                pass

        client = RemoteDirectoryClient(_FakeCP(), NodeID.generate())
        assert client.locate(oid) is None


# ---------------------------------------------------------------------------
# Pull-through cache eviction accounting (satellite c)
# ---------------------------------------------------------------------------


class TestCacheEvictionAccounting:
    def test_eviction_counts_and_deregisters(self, runtime):
        ref, _ = _seed_remote(runtime, b"e" * 1024)
        oid = ref.object_id
        assert ray_tpu.get(ref) == b"e" * 1024
        store = runtime.driver_agent.store
        node = runtime.driver_agent.node_id
        assert store.contains(oid)
        assert node in runtime.directory.locations(oid)
        ev0 = store.stats()["num_evictions"]
        store.delete(oid)
        assert store.stats()["num_evictions"] == ev0 + 1
        assert node not in runtime.directory.locations(oid)

    def test_concurrent_pull_and_evict_stay_consistent(self, runtime):
        """Evicting the pull-through replica while other threads re-get
        the object must never corrupt the accounting: every get resolves
        (falling back to the origin holder), and at quiescence the
        directory agrees with the store."""
        ref, origin = _seed_remote(runtime, {"k": 7}, latency=0.005)
        oid = ref.object_id
        store = runtime.driver_agent.store
        node = runtime.driver_agent.node_id
        errors = []
        stop = threading.Event()

        def getter():
            while not stop.is_set():
                try:
                    if ray_tpu.get(ref, timeout=30) != {"k": 7}:
                        errors.append("wrong value")
                except ObjectLostError:
                    pass  # delete raced the resolution: a legal outcome
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))

        threads = [threading.Thread(target=getter) for _ in range(4)]
        for t in threads:
            t.start()
        for _ in range(20):
            store.delete(oid)
            time.sleep(0.005)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        # quiescent agreement: replica present <=> location registered
        if store.contains(oid):
            assert node in runtime.directory.locations(oid)
        else:
            assert node not in runtime.directory.locations(oid)
        assert store.stats()["num_evictions"] >= 1
        assert origin.fetches >= 1


# ---------------------------------------------------------------------------
# Flow accounting (tentpole part 2)
# ---------------------------------------------------------------------------


class TestFlowAccounting:
    def test_pull_flows_conserve_pull_bytes(self, runtime,
                                            socket_pull_path):
        """Acceptance criterion: per-edge flow sums reconcile with
        object_pull_bytes — record_flow sits at the same increment
        sites, so the deltas must match exactly for a quiet edge."""
        ref = ray_tpu.put(b"F" * (1 << 20))
        server = ObjectTransferServer(runtime.driver_agent.store)
        client = ObjectTransferClient()
        client.local_node = "pullerdst001"
        src_hex = "aabbccddeeff00112233"
        object_ledger.note_peer(server.address, src_hex)
        before = _pulled_bytes.get()
        try:
            out = client.pull(server.address, ref.object_id)
            assert out == b"F" * (1 << 20)
        finally:
            client.close()
            server.stop()
        delta = _pulled_bytes.get() - before
        assert delta >= 1 << 20
        body = object_ledger.collect_flows()
        mine = [e for e in body["edges"] if e["dst"] == "pullerdst001"]
        assert mine, "no flow edge recorded for the pull"
        assert sum(e["bytes"] for e in mine) == delta
        assert sum(e["transfers"] for e in mine) >= 1
        for e in mine:
            assert e["src"] == src_hex[:12]
            assert e["path"] in ("native", "chunked", "stripe")

    def test_window_bandwidth_gauge_populates(self, runtime,
                                              socket_pull_path):
        ref = ray_tpu.put(b"W" * (256 << 10))
        server = ObjectTransferServer(runtime.driver_agent.store)
        client = ObjectTransferClient()
        client.local_node = "windowdst002"
        try:
            client.pull(server.address, ref.object_id)
        finally:
            client.close()
            server.stop()
        body = object_ledger.collect_flows()
        mine = [e for e in body["edges"] if e["dst"] == "windowdst002"]
        assert mine and any(e["window_bps"] > 0 for e in mine)

    def test_channel_flow_edge_distinct_from_pull_paths(self):
        object_ledger.record_flow("chansrc00003", "chandst00003", "channel",
                                  4096, transfers=1)
        body = object_ledger.collect_flows()
        edge = next(e for e in body["edges"]
                    if e["src"] == "chansrc00003")
        assert edge["path"] == "channel"
        assert edge["bytes"] >= 4096

    def test_record_flow_disabled_is_noop(self):
        os.environ["RAY_TPU_OBJECT_LEDGER"] = "false"
        object_ledger.reload_enabled()
        try:
            object_ledger.record_flow("offsrc000004", "offdst000004",
                                      "chunked", 999)
        finally:
            del os.environ["RAY_TPU_OBJECT_LEDGER"]
            object_ledger.reload_enabled()
        body = object_ledger.collect_flows()
        assert not any(e["src"] == "offsrc000004" for e in body["edges"])

    def test_channel_stats_carries_depth_and_count(self):
        """Satellite (b): channel_stats() now reports open-channel count
        and aggregate queue depth — the fields the head federates."""
        from ray_tpu.core.channels import channel_stats

        stats = channel_stats()
        assert "channels" in stats and "depth" in stats
        assert stats["channels"] >= 0 and stats["depth"] >= 0


# ---------------------------------------------------------------------------
# Surfaces: status(), state API, dashboard payloads + board
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_status_renders_object_and_channel_sections(self, runtime):
        ref = ray_tpu.put(b"s" * 2048)  # held: GC would evict an unbound put
        payload = ray_tpu.status(as_dict=True)
        assert payload["objects"]["total_objects"] >= 1
        assert payload["objects"]["nodes"]
        assert "channels" in payload

    def test_state_list_objects_rows(self, runtime):
        ref = ray_tpu.put(b"q" * 1024)
        from ray_tpu.util import state

        rows = state.list_objects(limit=1000)
        row = next(r for r in rows
                   if r["object_id"] == ref.object_id.hex()[:16])
        assert row["pin_reason"] == object_ledger.PIN_USER_PUT
        assert row["refcount"] >= 1
        assert row["locations"]
        assert row["size_bytes"] >= 1024

    def test_dashboard_payloads_and_board(self, runtime):
        from ray_tpu import dashboard

        ref = ray_tpu.put(b"d" * 1024)  # held: GC would evict an unbound put
        body = dashboard._objects_payload()
        assert body["total_objects"] >= 1
        flows = dashboard._flows_payload()
        assert "edges" in flows
        boards = dashboard.build_dashboards()
        assert "objects" in boards
        titles = [p["title"] for p in boards["objects"]["panels"]]
        assert any("bandwidth" in t.lower() for t in titles)
        assert any("cache" in t.lower() for t in titles)
        assert any("leak" in t.lower() for t in titles)


# ---------------------------------------------------------------------------
# Cross-host federation (two OS processes, the acceptance scenario)
# ---------------------------------------------------------------------------


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_WORKER_PROCESSES"] = "0"
    env["RAY_TPU_TELEMETRY_REPORT_PERIOD_S"] = "0.3"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _spawn_worker(addr: str) -> subprocess.Popen:
    code = textwrap.dedent(f"""
        import ray_tpu
        w = ray_tpu.init(address={addr!r}, num_cpus=4, num_tpus=0,
                         resources={{"magic": 1.0}})
        w.wait(timeout=300)
    """)
    return subprocess.Popen(
        [sys.executable, "-c", code], env=_worker_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _wait_nodes(rt, n: int, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(rt.control_plane.alive_nodes()) >= n:
            return
        time.sleep(0.1)
    raise AssertionError("cluster never reached %d nodes" % n)


@pytest.fixture
def head_with_worker():
    rt = ray_tpu.init(
        num_cpus=2, num_tpus=0,
        system_config={"control_plane_rpc_port": 0, "worker_processes": 0},
    )
    proc = _spawn_worker(rt._cp_server.address)
    try:
        _wait_nodes(rt, 2)
        yield rt, proc
    finally:
        ray_tpu.shutdown()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()


class TestFederatedObjectPlane:
    def test_objects_listed_across_two_hosts(self, head_with_worker):
        """Acceptance criterion: `/api/v0/objects` (collect_objects) lists
        every live object across >= 2 hosts, each with size / location
        set / refcount / pin reason / age — the worker's rows arriving
        via heartbeat telemetry ledger snapshots."""
        rt, _proc = head_with_worker
        head_ref = ray_tpu.put(b"h" * 4096)

        @ray_tpu.remote(num_cpus=0, resources={"magic": 0.1})
        def produce():
            return b"w" * 8192

        wref = produce.remote()
        ready, _ = ray_tpu.wait([wref], num_returns=1, timeout=60)
        assert ready == [wref]

        deadline = time.monotonic() + 30
        body = {}
        while time.monotonic() < deadline:
            body = object_ledger.collect_objects(rt, limit=10_000)
            node_ids = {r["node_id"] for r in body["objects"]}
            if len(node_ids) >= 2 and any(
                    r["object_id"] == wref.object_id.hex()
                    for r in body["objects"]):
                break
            time.sleep(0.3)
        node_ids = {r["node_id"] for r in body["objects"]}
        assert len(node_ids) >= 2, f"only saw nodes {node_ids}"
        wrow = next(r for r in body["objects"]
                    if r["object_id"] == wref.object_id.hex())
        hrow = next(r for r in body["objects"]
                    if r["object_id"] == head_ref.object_id.hex())
        assert wrow["node_id"] != hrow["node_id"]
        for row in (wrow, hrow):
            assert row["size_bytes"] > 0
            assert row["age_s"] >= 0.0
            assert isinstance(row["refcount"], int)
            assert row["locations"]
            assert "pin_reason" in row
        assert hrow["pin_reason"] == object_ledger.PIN_USER_PUT
        # the head's per-node summaries span both hosts too
        assert len({k.split("/")[0] for k in body["nodes"]}) >= 2

        # satellite (b): the worker's channel_stats federated alongside
        telem = rt.control_plane.telemetry_snapshots()
        assert any("channels" in rec and "channels" in rec["channels"]
                   for rec in telem.values())

    def test_cross_host_pull_records_flow_edge(self, head_with_worker,
                                               socket_pull_path):
        """A real worker->head pull lands a labeled flow edge whose src
        is the worker node and whose dst is the head node."""
        rt, _proc = head_with_worker

        @ray_tpu.remote(num_cpus=0, resources={"magic": 0.1})
        def produce():
            return b"f" * (256 << 10)

        wref = produce.remote()
        assert ray_tpu.get(wref, timeout=60) == b"f" * (256 << 10)
        head_hex = rt.head_node_id.hex()[:12]
        local_hexes = {nid.hex()[:12] for nid, a in rt.agents.items()
                       if not getattr(a, "is_remote", False)}
        worker_hexes = {
            n.node_id.hex()[:12] for n in rt.control_plane.alive_nodes()
        } - local_hexes
        body = object_ledger.collect_flows(runtime=rt)
        mine = [e for e in body["edges"]
                if e["dst"] == head_hex and e["src"] in worker_hexes]
        assert mine, (
            f"no worker->head edge (head={head_hex}, "
            f"workers={worker_hexes}): {body['edges']}")
        assert sum(e["bytes"] for e in mine) >= 256 << 10
        for e in mine:
            assert e["path"] in ("native", "chunked", "stripe")
