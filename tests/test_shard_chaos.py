"""Shard-kill chaos (ISSUE 19): test_head_chaos.py generalized to the
federated control plane — SIGKILL individual KV shard primaries while
the fleet is mid-flight and assert ride-through, not recovery-with-loss.

Covers the two in-flight workloads the acceptance gate names:

- ``api.broadcast`` while shard primaries die one by one (the relay
  tree's CAS claims live in shard keyspace — each kill lands in the
  middle of claim/advertise traffic): zero failed broadcasts, relay
  claims purged, every shard back healthy behind a respawned standby.
- a disaggregated serve burst while a shard dies: serving is off the
  control-plane data path, so every request must complete token-exact
  with zero failures while the federated KV rides out the failover.

test_head_chaos.py itself stays untouched (the K=1 equivalence gate
requires it to pass unmodified)."""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import ray_tpu

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_WORKER_PROCESSES"] = "0"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture
def fed_runtime():
    """A head runtime with K=2 federated KV/pubsub shards."""
    ray_tpu.shutdown()
    rt = ray_tpu.init(
        num_cpus=8, num_tpus=0,
        system_config={"control_plane_rpc_port": 0,
                       "worker_processes": 0,
                       "control_plane_shards": 2})
    assert getattr(rt, "_federation", None) is not None
    yield rt
    ray_tpu.shutdown()


def test_shard_kill_during_broadcast(fed_runtime):
    """Per-shard generalization of the head-kill chaos: kill EVERY shard
    primary, one per broadcast round, while relay CAS claims for the
    in-flight object live in the killed shard's keyspace."""
    from ray_tpu.core.object_transfer import RELAY_PREFIX

    rt = fed_runtime
    sup, fed = rt._federation
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
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if len(rt.control_plane.alive_nodes()) >= 2:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("worker never joined")
        arr = np.arange(1 << 20, dtype=np.float64)  # 8MB > relay min
        refs = []
        for round_i in range(sup.nshards + 1):
            ref = ray_tpu.put(arr + round_i)
            if round_i < sup.nshards:
                # SIGKILL mid-flight: the broadcast below must claim its
                # relay slots through the shard failing over right now
                sup.kill_primary(round_i)
            res = ray_tpu.broadcast(ref, timeout=120)
            assert res["failed"] == [], f"round {round_i}: {res}"
            assert len(res["warmed"]) >= 1
            refs.append(ref)
        assert sup.wait_healthy(30.0), "a shard never came back"
        assert len(sup.failovers) >= sup.nshards
        # the relay tree re-formed and cleaned up each round: no claims
        # left behind in any shard's keyspace
        for ref in refs:
            oid_hex = ref.object_id.hex()
            assert rt.control_plane.kv_keys(RELAY_PREFIX + oid_hex) == []
        # federated KV is fully serving after the last failover
        rt.control_plane.kv_put("chaos/probe", "alive")
        assert rt.control_plane.kv_get("chaos/probe") == "alive"
    finally:
        ray_tpu.shutdown()
        try:
            proc.wait(timeout=20)
        except Exception:  # noqa: BLE001 — teardown is best-effort
            proc.kill()


@pytest.mark.disagg
def test_shard_kill_during_disagg_burst(fed_runtime):
    """Zero failed requests through a disagg prefill->decode burst while
    a KV shard dies: serving rides through token-exact (the control plane
    is off the serving data path, and the federated KV itself recovers
    behind the burst)."""
    import jax

    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.disagg import DisaggCoordinator, EngineWorker
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    rt = fed_runtime
    sup, fed = rt._federation
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def _engine(**kw):
        defaults = dict(max_batch_size=4, page_size=8, max_pages=64,
                        max_seq_len=96, prefill_buckets=(16, 32))
        defaults.update(kw)
        return InferenceEngine(params, cfg, EngineConfig(**defaults))

    pe, de, ref_engine = _engine(), _engine(page_size=4, max_pages=96), _engine()
    co = DisaggCoordinator([EngineWorker(pe, "p0")],
                           [EngineWorker(de, "d0")],
                           {"kv_transfer": "object", "small_blob_bytes": 0})
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (5, 11, 17, 23, 29, 8)]
    try:
        want = [ref_engine.generate(p, max_tokens=8)["token_ids"]
                for p in prompts]
        results = [None] * len(prompts)
        errors = []

        def run(i):
            try:
                results[i] = co.generate(prompts[i], max_tokens=8)
            except Exception as e:  # noqa: BLE001 — the gate counts these
                errors.append((i, e))

        killer = threading.Timer(0.4, sup.kill_primary, args=(0,))
        killer.start()
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        [t.start() for t in threads]
        [t.join(timeout=600) for t in threads]
        killer.join()
        assert errors == [], f"requests failed during shard kill: {errors}"
        for w, r in zip(want, results):
            assert r is not None
            assert r["token_ids"] == w
        assert sup.wait_healthy(30.0)
        assert len(sup.failovers) >= 1
        # the federated KV recovered behind the burst
        rt.control_plane.kv_put("chaos/disagg_probe", "alive")
        assert rt.control_plane.kv_get("chaos/disagg_probe") == "alive"
    finally:
        pe.stop(), de.stop(), ref_engine.stop()
