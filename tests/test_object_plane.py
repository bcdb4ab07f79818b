"""Host object plane: parallel batched GET, pull-through caching, and
location lifecycle (ISSUE 3 acceptance tests).

Reference analogue: `src/ray/object_manager/pull_manager.cc` fetches
concurrently from wherever replicas live, and every successful Plasma pull
creates a new replica. These tests assert the same properties here: a
batch of refs held by distinct runtimes resolves in ~max (not sum) of the
individual pull times, a remotely-pulled object becomes a local replica
that serves both repeat gets and third-party pulls, and evicted replicas
leave the directory.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.core_worker import ObjectRef, Runtime
from ray_tpu.core.ids import NodeID, ObjectID, TaskID
from ray_tpu.core.object_store import (
    MemoryObjectStore,
    ObjectLostError,
    SealedBytes,
    seal_value,
)
from ray_tpu.core.object_transfer import (
    ObjectTransferClient,
    ObjectTransferServer,
    _cache_hits,
    _cache_misses,
    _pulled_bytes,
)


def _oid(i: int = 0) -> ObjectID:
    return ObjectID.for_task_return(TaskID.of(), i)


class _LatencyStore:
    """Fake remote store: every fetch costs `latency` seconds of wall
    time, the instrumented stand-in for a cross-host transfer."""

    def __init__(self, latency: float):
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
    """Duck-typed cross-host holder (the shape RemoteNodeAgent presents to
    ObjectDirectory.locate): node_id + store + _stopped + is_remote."""

    is_remote = True

    def __init__(self, store):
        self.node_id = NodeID.generate()
        self.store = store
        self._stopped = threading.Event()


@pytest.fixture
def runtime():
    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4, num_tpus=0)
    yield rt
    ray_tpu.shutdown()


def _register_holders(rt, num_holders, refs_per_holder, latency):
    """num_holders fake remote runtimes, each seeded with refs_per_holder
    objects; returns (refs, stores) with locations registered."""
    refs, stores = [], []
    for h in range(num_holders):
        store = _LatencyStore(latency)
        agent = _FakeRemoteAgent(store)
        rt.directory.register_agent(agent)
        stores.append(store)
        for i in range(refs_per_holder):
            oid = _oid(i)
            store.seed(oid, {"holder": h, "i": i})
            rt.directory.add_location(oid, agent.node_id)
            refs.append(ObjectRef(oid, rt))
    return refs, stores


class TestParallelGet:
    def test_batch_completes_in_max_not_sum(self, runtime):
        """8 refs held by 4 distinct runtimes: the fan-out pool overlaps
        the pulls, so wall time tracks the slowest single pull, not the
        serial sum (ISSUE 3 acceptance criterion)."""
        latency = 0.3
        refs, _ = _register_holders(runtime, num_holders=4,
                                    refs_per_holder=2, latency=latency)
        assert len(refs) == 8
        t0 = time.monotonic()
        out = ray_tpu.get(refs)
        wall = time.monotonic() - t0
        assert [v["holder"] for v in out] == [0, 0, 1, 1, 2, 2, 3, 3]
        serial = latency * len(refs)  # 2.4s
        assert wall < serial / 2, (
            f"batched get took {wall:.2f}s — pulls did not overlap "
            f"(serial would be {serial:.1f}s)")

    def test_mixed_local_and_remote_refs(self, runtime):
        remote_refs, _ = _register_holders(runtime, num_holders=2,
                                           refs_per_holder=2, latency=0.05)
        local_refs = [ray_tpu.put(f"local-{i}") for i in range(3)]
        refs = [local_refs[0], remote_refs[0], local_refs[1],
                remote_refs[1], remote_refs[2], local_refs[2],
                remote_refs[3]]
        out = ray_tpu.get(refs)
        assert out[0] == "local-0" and out[2] == "local-1"
        assert out[5] == "local-2"
        assert out[1] == {"holder": 0, "i": 0}
        assert out[6] == {"holder": 1, "i": 1}

    def test_duplicate_refs_resolve_once(self, runtime):
        refs, stores = _register_holders(runtime, num_holders=1,
                                         refs_per_holder=1, latency=0.02)
        ref = refs[0]
        out = ray_tpu.get([ref, ref, ref, ref])
        assert all(v == {"holder": 0, "i": 0} for v in out)
        # the duplicate slots shared ONE resolution (and pull-through
        # caching means exactly one remote fetch ever happened)
        assert stores[0].fetches == 1

    def test_shared_deadline_across_parallel_waiters(self, runtime):
        """Unresolvable refs all share one deadline: the batch times out
        once, in ~timeout wall time, not once per ref."""
        never = [ObjectRef(_oid(i), runtime) for i in range(4)]
        from ray_tpu.core.core_worker import GetTimeoutError

        t0 = time.monotonic()
        with pytest.raises(GetTimeoutError):
            ray_tpu.get(never, timeout=0.4)
        assert time.monotonic() - t0 < 1.5

    def test_serial_path_when_concurrency_disabled(self, runtime,
                                                   monkeypatch):
        monkeypatch.setenv("RAY_TPU_GET_CONCURRENCY", "1")
        refs, _ = _register_holders(runtime, num_holders=2,
                                    refs_per_holder=1, latency=0.01)
        out = ray_tpu.get(refs)
        assert [v["holder"] for v in out] == [0, 1]

    def test_non_ref_in_batch_raises_type_error(self, runtime):
        ref = ray_tpu.put(1)
        with pytest.raises(TypeError):
            ray_tpu.get([ref, "not a ref"])


class TestPullThroughCache:
    @pytest.mark.parametrize("holders,per_holder,rounds",
                             [(1, 1, 1), (4, 2, 2)])
    def test_second_get_is_local_cache_hit(self, runtime, holders,
                                           per_holder, rounds):
        """Acceptance criterion: a repeat get of remotely-pulled objects
        increments object_cache_hits and moves no new bytes. One ref, and
        a batch spread over four holders asked for twice more: the hit
        rate of a disseminated object is rounds / (rounds + 1), never 0."""
        refs, stores = _register_holders(runtime, num_holders=holders,
                                         refs_per_holder=per_holder,
                                         latency=0.02)
        want = [{"holder": h, "i": i}
                for h in range(holders) for i in range(per_holder)]
        misses0 = _cache_misses.get()
        hits0 = _cache_hits.get()
        assert ray_tpu.get(refs) == want
        assert _cache_misses.get() == misses0 + len(refs)
        assert sum(s.fetches for s in stores) == len(refs)
        # pulled through: sealed into the local driver store + registered
        local_node = runtime.driver_agent.node_id
        for ref in refs:
            assert runtime.driver_agent.store.contains(ref.object_id)
            assert local_node in runtime.directory.locations(ref.object_id)
        pulled0 = _pulled_bytes.get()
        for _ in range(rounds):
            assert ray_tpu.get(refs) == want
        hits = _cache_hits.get() - hits0
        misses = _cache_misses.get() - misses0
        assert (hits, misses) == (rounds * len(refs), len(refs))
        assert sum(s.fetches for s in stores) == len(refs)  # no refetch
        assert _pulled_bytes.get() == pulled0  # no new bytes moved

    def test_cache_disabled_pulls_remote_every_time(self, runtime,
                                                    monkeypatch):
        monkeypatch.setenv("RAY_TPU_OBJECT_PULL_THROUGH_CACHE", "false")
        refs, stores = _register_holders(runtime, num_holders=1,
                                         refs_per_holder=1, latency=0.01)
        ref = refs[0]
        ray_tpu.get(ref)
        ray_tpu.get(ref)
        assert stores[0].fetches == 2
        assert not runtime.driver_agent.store.contains(ref.object_id)

    def test_new_location_serves_third_runtime_pull(self, runtime):
        """Acceptance criterion: the replica a pull-through created can
        itself serve another runtime over the real transfer plane."""
        refs, _ = _register_holders(runtime, num_holders=1,
                                    refs_per_holder=1, latency=0.01)
        ref = refs[0]
        value = ray_tpu.get(ref)  # pulls through into the driver store
        assert runtime.driver_agent.store.contains(ref.object_id)
        # third runtime = a fresh client pulling from a server that fronts
        # OUR store (the newly registered location)
        server = ObjectTransferServer(runtime.driver_agent.store)
        client = ObjectTransferClient()
        try:
            out = client.pull(server.address, ref.object_id)
            assert out == value
        finally:
            client.close()
            server.stop()

    def test_eviction_deregisters_location(self, runtime):
        ref = ray_tpu.put(np.arange(100))
        oid = ref.object_id
        node = runtime.driver_agent.node_id
        assert node in runtime.directory.locations(oid)
        runtime.driver_agent.store.delete(oid)
        assert node not in runtime.directory.locations(oid)

    def test_evicted_replica_falls_back_to_origin(self, runtime):
        refs, stores = _register_holders(runtime, num_holders=1,
                                         refs_per_holder=1, latency=0.01)
        ref = refs[0]
        ray_tpu.get(ref)
        assert stores[0].fetches == 1
        # evict the pulled-through replica; its location deregisters and
        # the next get goes back to the origin holder
        runtime.driver_agent.store.delete(ref.object_id)
        assert ray_tpu.get(ref) == {"holder": 0, "i": 0}
        assert stores[0].fetches == 2


class TestHolderDeathMidBatch:
    def test_reconstruction_fires_once_per_object_not_per_waiter(
            self, runtime, monkeypatch):
        """Concurrent waiters on one lost object coalesce on a single
        reconstruction attempt (satellite: holder dies mid-batch)."""
        ref = ray_tpu.put("victim")
        oid = ref.object_id
        # holder dies: bytes gone, location deregistered (via on_evict)
        runtime.driver_agent.store.delete(oid)
        assert not runtime.directory.locations(oid)
        calls = []

        def counting_reconstruct(object_id):
            calls.append(object_id)
            time.sleep(0.1)  # hold the window open so waiters pile up
            return False

        monkeypatch.setattr(runtime, "_try_reconstruct",
                            counting_reconstruct)
        errors = []

        def waiter():
            try:
                runtime._get_one(ref, time.monotonic() + 10.0)
            except ObjectLostError:
                errors.append(True)

        threads = [threading.Thread(target=waiter) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(errors) == 6  # every waiter saw the loss
        assert len(calls) == 1  # ...but reconstruction ran ONCE

    def test_holder_death_recovers_via_reconstruction(self, runtime,
                                                      monkeypatch):
        """A dying holder mid-get triggers reconstruction against the
        REMAINING deadline, and the repaired object resolves."""
        ref = ray_tpu.put("phoenix")
        oid = ref.object_id
        runtime.driver_agent.store.delete(oid)

        def repair(object_id):
            runtime.driver_agent.store.put(object_id, seal_value("phoenix"))
            runtime.directory.add_location(
                object_id, runtime.driver_agent.node_id)
            return True

        monkeypatch.setattr(runtime, "_try_reconstruct", repair)
        t0 = time.monotonic()
        assert ray_tpu.get(ref, timeout=5.0) == "phoenix"
        assert time.monotonic() - t0 < 5.0


class TestWaitConditionVariable:
    def test_wait_wakes_on_completion_not_poll(self, runtime):
        slow = ObjectRef(_oid(0), runtime)
        oid = slow.object_id

        def complete_later():
            time.sleep(0.2)
            runtime.driver_agent.store.put(oid, seal_value("done"))
            runtime.directory.add_location(
                oid, runtime.driver_agent.node_id)

        threading.Thread(target=complete_later, daemon=True).start()
        t0 = time.monotonic()
        ready, pending = ray_tpu.wait([slow], num_returns=1, timeout=5.0)
        wall = time.monotonic() - t0
        assert ready == [slow] and pending == []
        assert 0.1 < wall < 2.0

    def test_wait_num_returns_subset(self, runtime):
        fast = [ray_tpu.put(i) for i in range(3)]
        never = [ObjectRef(_oid(i), runtime) for i in range(2)]
        ready, pending = ray_tpu.wait(fast + never, num_returns=3,
                                      timeout=5.0)
        assert set(ready) == set(fast)
        assert set(pending) == set(never)

    def test_wait_timeout_returns_partial(self, runtime):
        done = ray_tpu.put("x")
        never = ObjectRef(_oid(), runtime)
        t0 = time.monotonic()
        ready, pending = ray_tpu.wait([done, never], num_returns=2,
                                      timeout=0.3)
        assert time.monotonic() - t0 < 2.0
        assert ready == [done] and pending == [never]

    def test_wait_deregisters_waiters(self, runtime):
        """Repeated waits on the same pending ref must not accumulate
        leaked callbacks on its future."""
        never = ObjectRef(_oid(), runtime)
        for _ in range(5):
            ray_tpu.wait([never], num_returns=1, timeout=0.05)
        fut = runtime._future_for(never.object_id)
        assert len(fut._waiters) == 0

    def test_wait_zero_returns(self, runtime):
        refs = [ray_tpu.put(1)]
        ready, pending = ray_tpu.wait(refs, num_returns=0, timeout=0.1)
        assert ready == [] and pending == refs
