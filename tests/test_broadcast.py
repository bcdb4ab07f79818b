"""Collective broadcast over the host object plane: pipelined relay
trees (pullers serve their committed prefix onward mid-transfer),
locality-ranked holders with the zero-copy same-host shm handoff, the
directory's partial-holder bookkeeping, and api.broadcast end to end.

Reference analogue: the reference's push-based broadcast is implicit in
its pull manager's chunk scheduling; here dissemination is explicit —
relay slots claimed in control-plane KV (`object_transfer_relay/*`),
slot k's parent at (k - fanout) // fanout — and PR 10's flow matrix is
the built-in verifier (per-edge byte sums reconcile exactly against the
pull counters)."""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import object_ledger
from ray_tpu.core.config import config
from ray_tpu.core.control_plane import ControlPlane, NodeInfo
from ray_tpu.core.ids import NodeID, ObjectID, TaskID
from ray_tpu.core.node_agent import ObjectDirectory
from ray_tpu.core.object_store import MemoryObjectStore
from ray_tpu.core.object_transfer import (
    HOST_PREFIX,
    KV_PREFIX,
    RELAY_PREFIX,
    ObjectTransferClient,
    ObjectTransferServer,
    _claim_relay_slot,
    _host_token,
    _pull_bytes,
    _pulled_bytes,
    _relay_parent,
    pull_from_any,
    purge_relay_claims,
)

pytestmark = pytest.mark.broadcast


def _oid(i: int = 0) -> ObjectID:
    return ObjectID.for_task_return(TaskID.of(), i)


def _flow_snapshot() -> dict:
    return {(e["src"], e["dst"], e["path"]): e["bytes"]
            for e in object_ledger.collect_flows()["edges"]}


def _flow_delta(before: dict) -> dict:
    return {k: v - before.get(k, 0)
            for k, v in _flow_snapshot().items() if v > before.get(k, 0)}


@pytest.fixture
def override_config():
    """Apply config overrides for one test, restoring prior values after
    (apply_overrides has no per-key removal, so restore = re-apply)."""
    saved = {}

    def apply(**overrides):
        for key in overrides:
            saved.setdefault(key, config.get(key))
        config.apply_overrides(overrides)

    yield apply
    config.apply_overrides(saved)


@pytest.fixture
def relay_plane(override_config):
    """One origin holder + 4 puller 'nodes' on a bare control plane —
    the bench's topology at test scale. Same-host shm handoff is off so
    the sockets (and therefore the flow matrix) see the relay tree the
    way cross-host pullers would."""
    override_config(
        object_transfer_shm_handoff=False,
        object_relay_min_bytes=1 << 18,
        object_broadcast_fanout=2,
        object_relay_timeout_s=10.0,
    )
    cp = ControlPlane()
    origin_store = MemoryObjectStore()
    origin = ObjectTransferServer(origin_store)
    cp.kv_put(KV_PREFIX + "origin", origin.address)
    pullers = []
    for i in range(4):
        store = MemoryObjectStore()
        server = ObjectTransferServer(store)
        client = ObjectTransferClient(chunk_bytes=128 * 1024)
        client.local_node = f"bp{i:03d}"
        pullers.append((store, server, client))
    yield cp, origin_store, origin, pullers
    for _store, server, client in pullers:
        client.close()
        server.stop()
    origin.stop()


class TestRelayTree:
    def test_concurrent_pulls_form_relay_tree(self, relay_plane):
        """4 concurrent pullers self-organize: exactly fanout slots pull
        from the origin, the rest stream from a parent's committed
        prefix — and every puller's inbound edges sum to exactly the
        wire-blob size (the flow matrix is conservative)."""
        cp, origin_store, origin, pullers = relay_plane
        arr = np.arange(262_144, dtype=np.float64)  # ~2MB
        oid = _oid()
        origin_store.put(oid, arr)
        # pre-stage the wire blob (the one-time encode is the putter's
        # cost, not part of the dissemination being verified)
        staged = pullers[0][2]._call(origin.address, "stage", oid.hex(),
                                     True)
        total = staged["size"]
        before = _flow_snapshot()
        results, errors = {}, []

        def pull(i, store, server, client):
            try:
                results[i] = pull_from_any(
                    cp, oid, client=client, cache_store=store,
                    relay_server=server, node_hex=client.local_node)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=pull, args=(i,) + p)
                   for i, p in enumerate(pullers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors
        for i in range(4):
            np.testing.assert_array_equal(results[i], arr)
            assert pullers[i][0].contains(oid)  # pull-through replica
        claims = cp.kv_keys(RELAY_PREFIX + oid.hex() + "/")
        assert len(claims) == 4  # every puller claimed a tree slot
        delta = _flow_delta(before)
        labels = {f"bp{i:03d}" for i in range(4)}
        # the origin fed at most `fanout` children — NOT all four
        origin_children = {dst for (src, dst, _p), b in delta.items()
                           if src == "origin" and dst in labels and b > 0}
        assert 1 <= len(origin_children) <= 2
        # relay edges exist: some puller sourced from another puller
        assert any(src in labels and dst in labels
                   for (src, dst, _p) in delta)
        # conservation, per puller: inbound edge bytes == blob size
        for label in labels:
            inbound = sum(b for (_s, dst, _p), b in delta.items()
                          if dst == label)
            assert inbound == total
        purge_relay_claims(oid.hex(), cp)
        assert cp.kv_keys(RELAY_PREFIX + oid.hex() + "/") == []

    def test_small_object_skips_relay(self, relay_plane):
        """Below object_relay_min_bytes the relay overhead (claims, a
        partial, KV round trips) is not worth it: the flat path serves
        the pull and no slot is ever claimed."""
        cp, origin_store, origin, pullers = relay_plane
        store, server, client = pullers[0]
        oid = _oid()
        origin_store.put(oid, list(range(1000)))  # tiny
        out = pull_from_any(cp, oid, client=client, cache_store=store,
                            relay_server=server,
                            node_hex=client.local_node)
        assert out == list(range(1000))
        assert cp.kv_keys(RELAY_PREFIX + oid.hex() + "/") == []

    def test_claim_slots_are_cas_and_parent_math(self):
        """Slot claims are first-writer-wins (kv_put overwrite=False);
        slot k's parent is (k - fanout) // fanout; root-tier slots have
        none. The claim value carries address|label|node for children,
        edge attribution, and dead-node purges respectively."""
        cp = ControlPlane()
        oid_hex = _oid().hex()
        assert _claim_relay_slot(cp, oid_hex, "h0:1", "l0", "n0") == 0
        assert _claim_relay_slot(cp, oid_hex, "h1:1", "l1", "n1") == 1
        assert _claim_relay_slot(cp, oid_hex, "h2:1", "l2", "n2") == 2
        assert _relay_parent(cp, oid_hex, 0, 2) is None
        assert _relay_parent(cp, oid_hex, 1, 2) is None
        assert _relay_parent(cp, oid_hex, 2, 2) == ("h0:1", "l0", "n0")
        assert _relay_parent(cp, oid_hex, 5, 2) == ("h1:1", "l1", "n1")
        purge_relay_claims(oid_hex, cp)
        assert cp.kv_keys(RELAY_PREFIX + oid_hex + "/") == []
        # a fresh broadcast of the same object starts from slot 0 again
        assert _claim_relay_slot(cp, oid_hex, "h9:1", "l9", "n9") == 0


class TestPartialHygiene:
    @pytest.mark.chaos
    def test_parent_death_falls_back_and_resumes(self, override_config):
        """A relay child parked on a dying parent's partial must fall
        back to a sealed holder and RESUME from its committed offset —
        and the flow matrix must show exactly one object's worth of
        bytes split across the two source edges (no re-pull from zero,
        no double count)."""
        override_config(
            object_transfer_shm_handoff=False,
            object_relay_min_bytes=1 << 18,
            object_broadcast_fanout=1,  # chain: slot 1's parent is slot 0
            object_relay_timeout_s=15.0,
        )
        cp = ControlPlane()
        origin_store = MemoryObjectStore()
        origin = ObjectTransferServer(origin_store)
        cp.kv_put(KV_PREFIX + "origin", origin.address)
        server_a = ObjectTransferServer(MemoryObjectStore())
        store_b = MemoryObjectStore()
        server_b = ObjectTransferServer(store_b)
        client_b = ObjectTransferClient(chunk_bytes=64 * 1024)
        client_b.local_node = "relayB"
        try:
            arr = np.arange(262_144, dtype=np.float64)  # ~2MB
            oid = _oid()
            origin_store.put(oid, arr)
            blob = origin._blob_for(oid.hex(), raw=True)
            total = len(blob)
            # node A: mid-relay parent — slot 0 claimed, partial with
            # the first 1MB committed, upstream about to die
            half = 16 * 64 * 1024
            assert _claim_relay_slot(cp, oid.hex(), server_a.address,
                                     "relayA", "aa") == 0
            pa = server_a.begin_partial(oid.hex(), True, total)
            memoryview(pa.buf)[:half] = blob[:half]
            pa.commit(half)
            before = _flow_snapshot()
            out, err = [], []

            def pull_b():
                try:
                    out.append(pull_from_any(
                        cp, oid, client=client_b, cache_store=store_b,
                        relay_server=server_b, node_hex="bb"))
                except BaseException as e:  # noqa: BLE001
                    err.append(e)

            t = threading.Thread(target=pull_b)
            t.start()
            # wait until B has streamed A's committed prefix and parked
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                pb = server_b._partials.get((oid.hex(), True))
                if pb is not None and pb.committed >= half:
                    break
                time.sleep(0.02)
            else:
                raise AssertionError("child never streamed the prefix")
            server_a.fail_partial(oid.hex(), True, "injected parent death")
            t.join(timeout=30)
            assert not t.is_alive()
            assert not err, err
            np.testing.assert_array_equal(out[0], arr)
            assert store_b.contains(oid)
            delta = _flow_delta(before)
            from_a = sum(b for (src, dst, _p), b in delta.items()
                         if src == "relayA" and dst == "relayB")
            from_origin = sum(b for (src, dst, _p), b in delta.items()
                              if src == "origin" and dst == "relayB")
            assert from_a >= half  # the prefix really rode the relay edge
            assert from_origin > 0  # the fallback resumed from the origin
            assert from_a + from_origin == total  # exact, no double-pull
            # hygiene: B promoted its partial, A's was popped by fail
            assert server_b._partials == {}
            assert server_a._partials == {}
            purge_relay_claims(oid.hex(), cp)
            assert cp.kv_keys(RELAY_PREFIX + oid.hex() + "/") == []
        finally:
            client_b.close()
            server_b.stop()
            server_a.stop()
            origin.stop()

    @pytest.mark.chaos
    def test_mark_node_dead_purges_relay_claims_and_host_token(self):
        """A dead node's relay-slot claims (matched by the node-hex
        suffix of the claim value) and its host token must leave the KV
        with it; other nodes' claims stay."""
        cp = ControlPlane()
        dead = NodeID(os.urandom(NodeID.SIZE))
        cp.register_node(NodeInfo(node_id=dead, address="h:1",
                                  resources_total={"CPU": 1.0}))
        oid_hex = _oid().hex()
        cp.kv_put(HOST_PREFIX + dead.hex(), "host-token")
        cp.kv_put(KV_PREFIX + dead.hex(), "h:1")
        assert _claim_relay_slot(cp, oid_hex, "h:1", "lab",
                                 dead.hex()) == 0
        assert _claim_relay_slot(cp, oid_hex, "h2:1", "lab2",
                                 "alivenode") == 1
        cp.mark_node_dead(dead, "chaos")
        assert cp.kv_get(HOST_PREFIX + dead.hex()) is None
        assert cp.kv_get(KV_PREFIX + dead.hex()) is None
        keys = cp.kv_keys(RELAY_PREFIX + oid_hex + "/")
        assert keys == [f"{RELAY_PREFIX}{oid_hex}/{1:06d}"]
        assert cp.kv_get(keys[0]).endswith("|alivenode")

    def test_partial_reader_parks_until_commit(self):
        """_read_range on a mid-relay partial parks until the range
        commits (the pipelining primitive), and finish_partial promotes
        the same bytearray into the blob cache byte-identically."""
        server = ObjectTransferServer(MemoryObjectStore())
        try:
            oid_hex = _oid().hex()
            payload = bytes(range(256)) * 1024  # 256KB
            p = server.begin_partial(oid_hex, True, len(payload))
            assert p is not None
            # duplicate registration refused: ONE pull per node feeds it
            assert server.begin_partial(oid_hex, True, len(payload)) is None
            memoryview(p.buf)[:4096] = payload[:4096]
            p.commit(4096)
            assert bytes(server._read_range(oid_hex, True, 0, 4096)) == \
                payload[:4096]
            got = []

            def read_tail():
                got.append(bytes(server._read_range(
                    oid_hex, True, 4096, len(payload) - 4096)))

            t = threading.Thread(target=read_tail)
            t.start()
            time.sleep(0.1)
            assert t.is_alive()  # parked: the tail is not committed yet
            memoryview(p.buf)[4096:] = payload[4096:]
            p.commit(len(payload))
            server.finish_partial(oid_hex, True)
            t.join(timeout=10)
            assert got == [payload[4096:]]
            # promoted: late reads hit the blob cache, same bytes
            assert bytes(server._read_range(
                oid_hex, True, 0, len(payload))) == payload
        finally:
            server.stop()


class TestSameHostHandoff:
    @staticmethod
    def _wait_native(obj, timeout=10.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if obj._plane.native is not None:
                return True
            time.sleep(0.02)
        return False

    def test_same_host_pull_is_zero_socket(self, override_config):
        """The locality contract: a puller on the holder's host maps the
        staged shm arena directly — zero bytes cross any socket, so the
        transfer counters and the flow matrix must not move at all."""
        override_config(object_transfer_shm_handoff=True)
        cp = ControlPlane()
        store = MemoryObjectStore()
        server = ObjectTransferServer(store)
        cp.kv_put(KV_PREFIX + "origin", server.address)
        client = ObjectTransferClient()
        client.local_node = "shmpull"
        local = MemoryObjectStore()
        try:
            arr = np.arange(262_144, dtype=np.float64)
            oid = _oid()
            store.put(oid, arr)
            assert self._wait_native(server)
            staged = client._call(server.address, "stage", oid.hex(), True)
            assert staged["shm"] is not None
            assert staged["shm"]["token"] == _host_token()
            pulled0, wire0 = _pulled_bytes.get(), _pull_bytes.get()
            before = _flow_snapshot()
            out = pull_from_any(cp, oid, client=client, cache_store=local,
                                node_hex="shmpull")
            np.testing.assert_array_equal(out, arr)
            assert local.contains(oid)  # the replica still lands locally
            assert _pulled_bytes.get() == pulled0
            assert _pull_bytes.get() == wire0
            assert not any(dst == "shmpull"
                           for (_s, dst, _p) in _flow_delta(before))
        finally:
            client.close()
            server.stop()


class _FakeStore:
    def __init__(self, kind):
        self.kind = kind


class _FakeAgent:
    def __init__(self, kind="memory", remote=False):
        self.node_id = NodeID(os.urandom(NodeID.SIZE))
        self.store = _FakeStore(kind)
        self.is_remote = remote
        self._stopped = threading.Event()


class TestDirectoryLocality:
    def test_locate_prefers_shm_then_memory_then_remote(self):
        """prefer_local ranks holders local-shm < local-memory < remote
        regardless of registration order; without it, registration order
        wins (the pre-existing contract)."""
        d = ObjectDirectory()
        remote = _FakeAgent(remote=True)
        mem = _FakeAgent(kind="memory")
        shm = _FakeAgent(kind="shm")
        oid = _oid()
        for a in (remote, mem, shm):
            d.register_agent(a)
            d.add_location(oid, a.node_id)
        assert d.locate(oid) is remote  # registration order
        assert d.locate(oid, prefer_local=True) is shm
        d.remove_location(oid, shm.node_id)
        assert d.locate(oid, prefer_local=True) is mem
        d.remove_location(oid, mem.node_id)
        assert d.locate(oid, prefer_local=True) is remote

    def test_partial_holders_invisible_until_promoted(self):
        """bytes_available adds record a PARTIAL holder: visible to
        partial_locations (broadcast planner / ledger), invisible to
        locate()/locations()/waiters; the full add promotes it."""
        d = ObjectDirectory()
        agent = _FakeAgent()
        d.register_agent(agent)
        oid = _oid()
        fired = []
        d.subscribe_once(oid, lambda: fired.append(1))
        d.add_location(oid, agent.node_id, bytes_available=4096)
        assert d.locate(oid) is None
        assert d.locations(oid) == []
        assert not fired  # a partial must not wake get() waiters
        assert d.partial_locations(oid) == {agent.node_id: 4096}
        d.add_location(oid, agent.node_id, bytes_available=8192)
        assert d.partial_locations(oid) == {agent.node_id: 8192}
        d.add_location(oid, agent.node_id)  # the full add promotes
        assert d.locate(oid) is agent
        assert fired == [1]
        assert d.partial_locations(oid) == {}

    def test_unregister_agent_drops_partials(self):
        d = ObjectDirectory()
        agent = _FakeAgent()
        d.register_agent(agent)
        oid = _oid()
        d.add_location(oid, agent.node_id, bytes_available=100)
        d.unregister_agent(agent.node_id)
        assert d.partial_locations(oid) == {}


class TestMaxStripes:
    def test_max_stripes_one_disables_striping(self, override_config,
                                               monkeypatch):
        """object_transfer_max_stripes=1 must keep a large chunked pull
        on a single holder: the peer is never probed or dialed."""
        import ray_tpu.core.object_transfer as ot

        override_config(object_transfer_max_stripes=1)
        monkeypatch.setattr(ot, "STAGING_BYTES", 1 << 20)
        monkeypatch.setenv("RAY_TPU_OBJECT_TRANSFER_STRIPE_MIN_BYTES",
                           str(1 << 20))
        store = MemoryObjectStore()
        server_a = ot.ObjectTransferServer(store)
        server_b = ot.ObjectTransferServer(store)
        client = ot.ObjectTransferClient(chunk_bytes=128 * 1024)
        try:
            arr = np.arange(500_000, dtype=np.float64)  # ~4MB
            oid = _oid()
            store.put(oid, arr)
            out = client.pull(server_a.address, oid,
                              peers=[server_b.address])
            np.testing.assert_array_equal(out, arr)
            assert server_b.address not in client._pools
        finally:
            client.close()
            server_a.stop()
            server_b.stop()


# -- api.broadcast end to end (head + a joined worker process) --------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_WORKER_PROCESSES"] = "0"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


class TestBroadcastAPI:
    def test_broadcast_warms_joined_worker(self):
        """ray_tpu.broadcast pushes a head-owned object to a joined
        worker runtime ahead of demand: the worker becomes a directory
        location without any consumer ever calling get(), and the relay
        claims are purged by the epilogue."""
        import subprocess
        import sys
        import textwrap

        rt = ray_tpu.init(
            num_cpus=2, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={rt._cp_server.address!r},
                             num_cpus=2, num_tpus=0)
            w.wait(timeout=300)
        """)
        proc = subprocess.Popen(
            [sys.executable, "-c", code], env=_worker_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        try:
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if len(rt.control_plane.alive_nodes()) >= 2:
                    break
                time.sleep(0.1)
            else:
                raise AssertionError("worker never joined")
            arr = np.arange(1 << 20, dtype=np.float64)  # 8MB > relay min
            ref = ray_tpu.put(arr)
            res = ray_tpu.broadcast(ref, timeout=60)
            assert res["failed"] == []
            assert len(res["warmed"]) >= 1
            locs = rt.directory.locations(ref.object_id)
            assert len(locs) >= 2  # head putter + the warmed worker
            oid_hex = ref.object_id.hex()
            assert rt.control_plane.kv_keys(RELAY_PREFIX + oid_hex) == []
        finally:
            ray_tpu.shutdown()
            try:
                proc.wait(timeout=20)
            except Exception:  # noqa: BLE001 — teardown is best-effort
                proc.kill()

    def test_broadcast_checkpoint_round_trip(self, ray_start_regular,
                                             tmp_path):
        """train.broadcast_checkpoint stages a checkpoint dir into the
        object plane; restore_checkpoint materializes an identical tree
        from the (possibly pre-seeded) local replica."""
        from ray_tpu import train

        src = tmp_path / "ckpt"
        src.mkdir()
        (src / "weights.bin").write_bytes(os.urandom(4096))
        (src / "meta.txt").write_text("step=7")
        ckpt = train.Checkpoint(str(src))
        ckpt.set_metadata({"step": 7})
        ref = train.broadcast_checkpoint(ckpt, timeout=30.0)
        out = train.restore_checkpoint(ref, str(tmp_path / "restored"))
        assert (tmp_path / "restored" / "weights.bin").read_bytes() == \
            (src / "weights.bin").read_bytes()
        assert (tmp_path / "restored" / "meta.txt").read_text() == "step=7"
        assert out.get_metadata() == {"step": 7}
