"""Pod-shape proof (VERDICT r4 #1): 8 runtimes running the real stack.

Drives examples/pod_cluster.py — 1 head + 7 joined worker runtimes in
separate OS processes; JaxTrainer (train/worker_group.py, NOT hand-rolled
actors) places an 8-member gang via a STRICT_SPREAD placement group (one
bundle per runtime), each member a dedicated actor process joining a
spanning jax.distributed mesh (dp=8, one virtual CPU device per runtime)
and stepping the real sharded LM on tokens pulled from a streaming_split
Data pipeline over the transfer plane; then one worker host is SIGKILLed
after the first checkpoint, the health monitor reaps it, and the gang
restarts from the orbax sharded checkpoint on a freshly-joined
replacement host and finishes every step.

Reference analogue: Ray Train's multi-node gang over raylets
(`python/ray/train/_internal/worker_group.py`,
`_internal/backend_executor.py`) + release-test scale checks
(SURVEY.md §7.3's v5p-64 = 8-host north star).
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_pod_shape_8_runtimes_train_ingest_restart(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["TMPDIR"] = str(tmp_path)  # pod storage + worker logs stay scoped
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "examples", "pod_cluster.py"),
         "--workers", "7", "--steps", "6", "--kill"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        out, _ = proc.communicate(timeout=1150)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-4000:]
    assert "POD-OK" in out, out[-4000:]
    assert '"world": 8' in out, out[-2000:]
    assert '"restarted": true' in out, out[-2000:]
