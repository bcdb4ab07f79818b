"""North-star end-to-end slice (BASELINE.md): pretrain via JaxTrainer on
a sharded mesh with Dataset ingest -> orbax checkpoint -> the trained
weights served by the paged-KV engine. Drives examples/pretrain_and_serve.py
the way a user would run it.

Reference analogue: the reference's flagship Train -> Checkpoint -> Serve
workflow (`train/base_trainer.py` -> `Checkpoint` -> `serve.run`)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_pretrain_checkpoint_serve_end_to_end(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-u",
         os.path.join(_REPO, "examples", "pretrain_and_serve.py"),
         "--mesh", "fsdp=-1", "--steps", "8",
         "--storage", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "pretrain -> checkpoint -> serve: OK" in proc.stdout
    assert "trained 8 steps" in proc.stdout
