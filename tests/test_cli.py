"""CLI smoke tests (`ray-tpu ...` console entry; reference:
`python/ray/scripts/scripts.py`). Each invocation is a subprocess, matching
how operators run it."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts", *args],
        capture_output=True, text=True, timeout=timeout,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "")},
    )


def test_status_live():
    r = run_cli("status")
    assert r.returncode == 0, r.stderr
    s = json.loads(r.stdout)
    assert "nodes" in s or "num_nodes" in s or s  # summary shape is flexible


def test_list_nodes():
    r = run_cli("list", "nodes")
    assert r.returncode == 0, r.stderr
    assert "NODE" in r.stdout.upper() or "(none)" in r.stdout


def test_submit_runs_entrypoint():
    r = run_cli("submit", "--", sys.executable, "-c", "print('hello-from-job')")
    assert r.returncode == 0, r.stderr
    assert "hello-from-job" in r.stdout
    assert "SUCCEEDED" in r.stderr


def test_submit_failure_exit_code():
    r = run_cli("submit", "--", sys.executable, "-c", "raise SystemExit(3)")
    assert r.returncode == 1
    assert "FAILED" in r.stderr


def test_status_snapshot(tmp_path):
    snap = str(tmp_path / "cp.snap")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import ray_tpu\n"
        "from ray_tpu.core import persistence\n"
        "rt = ray_tpu.init(num_cpus=2, num_tpus=0)\n"
        "rt.control_plane.kv_put('k', b'v')\n"
        "persistence.write_snapshot(rt, %r)\n" % (REPO, snap)
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    r = run_cli("status", "--snapshot", snap)
    assert r.returncode == 0, r.stderr
    assert "kv entries:    1" in r.stdout
    r = run_cli("list", "jobs", "--snapshot", snap)
    assert r.returncode == 0, r.stderr


def test_timeline_merges_session_dumps(tmp_path):
    evdir = str(tmp_path / "events")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import ray_tpu\n"
        "ray_tpu.init(num_cpus=2, num_tpus=0,"
        " system_config={'event_log_dir': %r})\n"
        "@ray_tpu.remote\n"
        "def f(): return 1\n"
        "ray_tpu.get(f.remote())\n"
        "ray_tpu.shutdown()\n" % (REPO, evdir)
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
    out = str(tmp_path / "merged.json")
    r = run_cli("timeline", out, "--events-dir", evdir)
    assert r.returncode == 0, r.stderr
    doc = json.load(open(out))
    assert any(e["cat"] == "task" for e in doc["traceEvents"])


def test_cmd_memory_lists_objects(capsys):
    import numpy as np

    import ray_tpu
    from ray_tpu.scripts import main

    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        ref = ray_tpu.put(np.arange(1000))
        assert main(["memory"]) == 0
        out = capsys.readouterr().out
        assert ref.object_id.hex()[:16] in out
        assert "total:" in out
    finally:
        ray_tpu.shutdown()


class TestStartAddressCLI:
    def test_start_address_joins_as_worker(self, tmp_path):
        """`ray-tpu start --address` is the operator's worker-join path
        (cross-host plane): the process joins, serves dispatched tasks,
        and exits when the head goes away."""
        import subprocess
        import sys
        import textwrap
        import time

        import ray_tpu

        rt = ray_tpu.init(
            num_cpus=1, num_tpus=0,
            system_config={"control_plane_rpc_port": 0, "worker_processes": 0},
        )
        try:
            addr = rt._cp_server.address
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
                       RAY_TPU_WORKER_PROCESSES="0")
            proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.scripts", "start",
                 "--address", addr, "--num-cpus", "3", "--num-tpus", "0"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                env=env,
            )
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if len(rt.control_plane.alive_nodes()) == 2:
                    break
                time.sleep(0.2)
            nodes = rt.control_plane.alive_nodes()
            assert len(nodes) == 2, nodes
            assert any(n.resources_total.get("CPU") == 3.0 for n in nodes)

            @ray_tpu.remote(num_cpus=2)  # only fits the CLI-joined worker
            def where():
                return os.getpid()

            assert ray_tpu.get(where.remote(), timeout=60) == proc.pid
        finally:
            ray_tpu.shutdown()
            try:
                proc.wait(timeout=20)  # head death stops the worker
            except subprocess.TimeoutExpired:
                proc.kill()
        assert proc.returncode == 0
