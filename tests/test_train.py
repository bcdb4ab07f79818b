"""Train library tests: session/report flow, checkpointing (incl. resharding
restore), gang restart fault tolerance, and a real sharded training run."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.comm.mesh import MeshSpec, build_mesh
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    CheckpointManager,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    load_pytree,
    save_pytree,
)
from ray_tpu.train.lm import (
    init_train_state,
    make_optimizer,
    make_train_step,
    synthetic_batch,
)


class TestCheckpointIO:
    def test_pytree_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((4, 4))}}
        p = save_pytree(tree, str(tmp_path / "ck"))
        restored = load_pytree(p)
        np.testing.assert_allclose(restored["a"], tree["a"])
        np.testing.assert_allclose(restored["b"]["c"], tree["b"]["c"])

    def test_resharding_restore(self, tmp_path, cpu_mesh_devices):
        from jax.sharding import NamedSharding, PartitionSpec

        mesh_a = build_mesh(MeshSpec.create(dp=8), devices=cpu_mesh_devices)
        x = jax.device_put(
            jnp.arange(64.0).reshape(8, 8),
            NamedSharding(mesh_a, PartitionSpec("dp", None)),
        )
        path = save_pytree({"x": x}, str(tmp_path / "ck"))

        # restore onto a DIFFERENT mesh shape (4x2) with a different layout
        mesh_b = build_mesh(MeshSpec.create(dp=4, tp=2), devices=cpu_mesh_devices)
        target = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        shardings = {"x": NamedSharding(mesh_b, PartitionSpec("dp", "tp"))}
        restored = load_pytree(path, target=target, shardings=shardings)
        np.testing.assert_allclose(np.asarray(restored["x"]), np.arange(64.0).reshape(8, 8))
        assert restored["x"].sharding.mesh.shape == {"dp": 4, "tp": 2}

    def test_manager_topk(self, tmp_path):
        mgr = CheckpointManager(num_to_keep=2, score_attribute="acc")
        paths = []
        for i, acc in enumerate([0.1, 0.9, 0.5]):
            p = tmp_path / f"ck{i}"
            p.mkdir()
            paths.append(str(p))
            mgr.register(Checkpoint(str(p)), {"acc": acc})
        kept = {c.path for c in mgr.all()}
        assert kept == {paths[1], paths[2]}
        assert mgr.best.path == paths[1]
        assert mgr.latest.path == paths[2]


class TestTrainerFlow:
    def test_report_and_context(self, ray_start_regular, tmp_path):
        def train_func(config):
            from ray_tpu import train

            ctx = train.get_context()
            for step in range(3):
                train.report({"step": step, "rank": ctx.get_world_rank(),
                              "world": ctx.get_world_size()})

        trainer = JaxTrainer(
            train_func,
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="t", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.error is None
        assert len(result.metrics_history) == 3  # rank-0 reports only
        assert result.metrics_history[-1] == {"step": 2, "rank": 0, "world": 2}

    def test_worker_exception_surfaces(self, ray_start_regular, tmp_path):
        def train_func(config):
            raise ValueError("boom")

        trainer = JaxTrainer(
            train_func,
            run_config=RunConfig(name="f", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.error is not None
        assert "boom" in str(result.error)

    def test_gang_restart_resumes_from_checkpoint(self, ray_start_regular, tmp_path):
        marker = tmp_path / "failed_once"

        def train_func(config):
            from ray_tpu import train

            ckpt = train.get_checkpoint()
            start = 0
            if ckpt is not None:
                start = ckpt.get_metadata()["step"] + 1
            for step in range(start, 4):
                ckpt_dir = os.path.join(config["dir"], f"ck_{step}")
                os.makedirs(ckpt_dir, exist_ok=True)
                c = train.Checkpoint(ckpt_dir)
                c.set_metadata({"step": step})
                train.report({"step": step, "resumed": start > 0}, checkpoint=c)
                if step == 2 and not marker.exists():
                    marker.write_text("x")
                    raise RuntimeError("injected failure")

        trainer = JaxTrainer(
            train_func,
            train_loop_config={"dir": str(tmp_path)},
            run_config=RunConfig(
                name="ft",
                storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=1),
            ),
        )
        result = trainer.fit()
        assert result.error is None
        steps = [m["step"] for m in result.metrics_history]
        assert steps[-1] == 3
        # second attempt resumed from the step-2 checkpoint, not from zero
        resumed = [m for m in result.metrics_history if m.get("resumed")]
        assert resumed and resumed[0]["step"] == 3


class TestLMTrainStep:
    def test_sharded_training_runs_and_learns(self, cpu_mesh_devices):
        from ray_tpu.models import get_config

        cfg = get_config("tiny-llama")
        mesh = build_mesh(MeshSpec.create(fsdp=4, tp=2), devices=cpu_mesh_devices)
        opt = make_optimizer(learning_rate=1e-2, warmup_steps=2, total_steps=40)
        state, shardings = init_train_state(cfg, mesh, jax.random.PRNGKey(0), opt)
        # params really are distributed
        leaf = state["params"]["layers"]["wq"]
        assert len(leaf.sharding.device_set) > 1
        step = jax.jit(make_train_step(cfg, opt), donate_argnums=0)
        batch = synthetic_batch(cfg, batch_size=8, seq_len=32)
        with mesh:
            losses = []
            for _ in range(15):
                state, metrics = step(state, batch)
                losses.append(float(metrics["ce_loss"]))
        assert losses[-1] < losses[0] * 0.7, losses
        assert int(state["step"]) == 15


def test_factored_optimizer_learns(cpu_mesh_devices):
    """make_optimizer(factored=True) — chip_smoke.py's train recipe — must
    actually descend, guarding the two adafactor traps (parameter-scale
    multipliers and per-step weight_decay_rate, both of which froze
    learning when first wired)."""
    import jax

    from ray_tpu.comm.mesh import MeshSpec, build_mesh, set_mesh
    from ray_tpu.models import get_config
    from ray_tpu.train.lm import (
        init_train_state,
        make_optimizer,
        make_train_step,
        synthetic_batch,
    )

    cfg = get_config("tiny-llama")
    mesh = build_mesh(MeshSpec.create(dp=1), devices=cpu_mesh_devices[:1])
    set_mesh(mesh)
    opt = make_optimizer(total_steps=60, factored=True)
    state, _ = init_train_state(cfg, mesh, jax.random.PRNGKey(0), opt)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=0)
    batch = synthetic_batch(cfg, 4, 32)
    with mesh:
        state, m0 = step(state, batch)
        first = float(m0["loss"])
        for _ in range(39):
            state, m = step(state, batch)
    assert float(m["loss"]) < first - 0.3, (first, float(m["loss"]))
