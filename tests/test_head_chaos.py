"""Head-death chaos (ISSUE 4 acceptance): SIGKILL the head OS process
mid-gang-train, restart it with ``resume_from`` the latest snapshot, and
assert — WITHOUT restarting the worker-host processes — that the joined
hosts reconnect, re-register, re-advertise their held objects, resubscribe,
and the JaxTrainer gang resumes from its checkpoint to completion.

Drives examples/head_chaos.py (supervisor role spawns head1 / workers /
head2 and does the killing via ray_tpu.util.chaos). Reference analogue:
upstream Ray's GCS-FT release tests (kill the GCS under load, assert
raylets survive on the Redis-backed tables; SURVEY §5.3)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.chaos
@pytest.mark.slow
def test_head_sigkill_mid_train_workers_survive_and_resume(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["TMPDIR"] = str(tmp_path)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "examples", "head_chaos.py"),
         "--workers", "3", "--steps", "6"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=900)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-6000:]
    # the full recovery sequence, in order
    for marker in ("HEAD-UP", "PROBE-SET", "HEAD2-UP", "NODES-REJOINED",
                   "PROBE-RELOCATED", "HEAD-CHAOS-OK", "SUPERVISOR-OK"):
        assert marker in out, f"missing {marker}:\n{out[-6000:]}"
    assert out.index("NODES-REJOINED") < out.index("PROBE-RELOCATED")
