"""Multi-host gang bootstrap executed for REAL: two OS processes join one
jax.distributed world and run a sharded train step on the global mesh.

This is the executable version of the reference's multi-host setup path
(upstream ray `python/ray/train/torch/config.py :: _setup_torch_process_group`
+ `ray/util/collective` group init; SURVEY.md §7.2 stage 6): until round 2
the `comm/bootstrap.py` jax.distributed path had never run (VERDICT item 4).
"""

import os
import re
import subprocess
import sys

import pytest

from ray_tpu.comm.bootstrap import free_port

_WORKER = os.path.join(os.path.dirname(__file__), "_bootstrap_worker.py")


@pytest.mark.slow
def test_two_process_gang_one_mesh_one_step():
    coord = f"127.0.0.1:{free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, coord, str(i), "2"],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker {p.args[-2]} failed:\n{out}"
    losses = []
    for out in outs:
        m = re.search(r"GANG_LOSS ([\d.]+)", out)
        assert m, f"no loss line in:\n{out}"
        losses.append(float(m.group(1)))
    # SPMD: every process computes the same global step -> identical loss
    assert losses[0] == pytest.approx(losses[1], abs=1e-6), losses


def test_coordinator_publish_lookup(ray_start_regular):
    from ray_tpu.comm import bootstrap

    addr = bootstrap.publish_coordinator("kv-gang")
    assert ":" in addr
    assert bootstrap.lookup_coordinator("kv-gang", timeout_s=5) == addr


def test_lookup_times_out(ray_start_regular):
    from ray_tpu.comm import bootstrap

    with pytest.raises(TimeoutError):
        bootstrap.lookup_coordinator("never-published", timeout_s=0.2)
