"""Serve across hosts (VERDICT r4 #3): replicas on joined runtimes,
traffic crossing the dispatch plane, replica-death failover mid-traffic.

Reference analogue: replicas placed cluster-wide by
`serve/_private/deployment_scheduler.py`, routed by the pow-2 scheduler,
replaced by the controller's health loop. The TPU serving shape: a
replica is a slice-owning runtime on another host; the head keeps the
controller + router (they drive the runtime API) and requests ride the
cross-host dispatch plane to wherever the replica lives.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_WORKER_PROCESSES"] = "0"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _spawn_worker(addr: str) -> subprocess.Popen:
    code = textwrap.dedent(f"""
        import ray_tpu
        w = ray_tpu.init(address={addr!r}, num_cpus=2, num_tpus=0,
                         resources={{"replica_pool": 1.0}})
        w.wait(timeout=600)
    """)
    return subprocess.Popen(
        [sys.executable, "-c", code], env=_worker_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


@pytest.fixture
def serve_cluster():
    """Head (no replica_pool resource) + 2 joined worker runtimes."""
    rt = ray_tpu.init(
        num_cpus=2, num_tpus=0,
        system_config={
            "control_plane_rpc_port": 0,
            "worker_processes": 0,
            "health_check_timeout_ms": 2500,
        },
    )
    procs = [_spawn_worker(rt._cp_server.address) for _ in range(2)]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        pool = sum(n.resources_total.get("replica_pool", 0)
                   for n in rt.control_plane.alive_nodes())
        if pool >= 2:
            break
        time.sleep(0.1)
    try:
        yield rt, procs
    finally:
        from ray_tpu import serve

        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
        for p in procs:
            if p.poll() is None:
                p.kill()


class TestServeCrossHost:
    def test_replicas_on_joined_hosts_and_failover(self, serve_cluster):
        rt, procs = serve_cluster
        from ray_tpu import serve

        @serve.deployment(
            num_replicas=2,
            ray_actor_options={
                "num_cpus": 0,
                "resources": {"replica_pool": 0.5},
                "scheduling_strategy": ray_tpu.SpreadSchedulingStrategy(),
            },
        )
        class Echo:
            def __call__(self, x):
                return {"x": x, "pid": os.getpid()}

        handle = serve.run(Echo.bind(), name="xh-echo")
        worker_pids = {p.pid for p in procs}

        # requests are served by REMOTE replicas (pid-asserted), spread
        # across both joined runtimes
        seen = set()
        for i in range(16):
            out = handle.remote(i).result(timeout=60)
            assert out["x"] == i
            assert out["pid"] in worker_pids, (out, worker_pids)
            seen.add(out["pid"])
        assert seen == worker_pids, "traffic never spread to both hosts"

        # kill one replica's HOST mid-traffic: the health plane reaps the
        # node, the controller replaces the replica onto surviving
        # capacity, and traffic keeps flowing
        victim = procs[0]
        os.kill(victim.pid, signal.SIGKILL)
        survivor_pid = procs[1].pid

        deadline = time.monotonic() + 90
        recovered = 0
        while time.monotonic() < deadline and recovered < 8:
            try:
                out = handle.remote("after").result(timeout=30)
            except Exception:
                time.sleep(0.3)  # router view mid-update; clients retry
                continue
            assert out["pid"] == survivor_pid, out
            recovered += 1
        assert recovered >= 8, "traffic never recovered after host death"

    def test_per_host_proxy_on_joined_runtime(self, serve_cluster):
        """Per-host ingress (reference: one ProxyActor per node): a proxy
        placed on a joined runtime serves HTTP THERE, picks up apps
        deployed both before and AFTER it started (route-table poll),
        and routes through back-channel handles."""
        import json
        import urllib.request

        rt, procs = serve_cluster
        from ray_tpu import serve

        @serve.deployment(num_replicas=1,
                          ray_actor_options={"num_cpus": 0.1})
        class Before:
            def __call__(self, x):
                return {"app": "before", "x": x}

        serve.run(Before.bind(), name="before")
        proxy, port = serve.start_proxy(
            actor_options={"resources": {"replica_pool": 0.2}},
            host="127.0.0.1",
        )

        def post(route, payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/{route}",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(urllib.request.urlopen(req, timeout=60).read())

        assert post("before", 1)["result"] == {"app": "before", "x": 1}

        @serve.deployment(num_replicas=1,
                          ray_actor_options={"num_cpus": 0.1})
        class After:
            def __call__(self, x):
                return {"app": "after", "x": x}

        serve.run(After.bind(), name="after")
        deadline = time.monotonic() + 30
        out = None
        while time.monotonic() < deadline:
            try:
                out = post("after", 2)["result"]
                break
            except Exception:
                time.sleep(0.3)  # proxy's route poll hasn't ticked yet
        assert out == {"app": "after", "x": 2}
        ray_tpu.get(proxy.stop.remote(), timeout=30)

    def test_replica_handle_composition_across_hosts(self, serve_cluster):
        """Model composition: a replica on a joined host resolves ANOTHER
        deployment's handle and calls through it (the pattern the r4
        worker-API block made impossible; reference: serve model
        composition via DeploymentHandle in replicas)."""
        rt, procs = serve_cluster
        from ray_tpu import serve

        @serve.deployment(
            num_replicas=1,
            ray_actor_options={"num_cpus": 0,
                               "resources": {"replica_pool": 0.3}},
        )
        class Downstream:
            def __call__(self, x):
                return {"doubled": x * 2, "pid": os.getpid()}

        @serve.deployment(
            num_replicas=1,
            ray_actor_options={"num_cpus": 0,
                               "resources": {"replica_pool": 0.3}},
        )
        class Upstream:
            def __init__(self):
                from ray_tpu import serve as s

                self._down = s.get_deployment_handle("Downstream")

            def __call__(self, x):
                inner = self._down.remote(x).result(timeout=30)
                return {"inner": inner, "pid": os.getpid()}

        serve.run(Downstream.bind(), name="xh-down")
        up = serve.run(Upstream.bind(), name="xh-up")
        out = up.remote(21).result(timeout=60)
        worker_pids = {p.pid for p in procs}
        assert out["inner"]["doubled"] == 42
        assert out["pid"] in worker_pids  # upstream replica off-head
        assert out["inner"]["pid"] in worker_pids
