"""Pod-shape cluster: 1 head + N joined worker runtimes (8 total by
default — the v5p-64 host count, SURVEY.md §7.3) running the REAL stack:

- `JaxTrainer` (not hand-rolled actors) places an (N+1)-member gang via
  ScalingConfig -> placement group (STRICT_SPREAD, one bundle per
  runtime), each member a dedicated actor process that joins a spanning
  jax.distributed mesh and runs the real sharded LM train step (dp over
  all members).
- Data ingest feeds training: the dataset is streaming_split across the
  gang; every rank pulls ITS shard's blocks over the transfer plane from
  wherever the read tasks ran, builds its slice of the global batch, and
  the loss is computed on pipeline tokens, not synthetic data.
- Fault tolerance: with --kill, one worker host is SIGKILLed after the
  first checkpoint; the health monitor reaps it, the gang restarts from
  the orbax sharded checkpoint on a replacement host (spawned like an
  autoscaled node), and training finishes all steps.

Reference analogue: upstream ray Train's multi-node path
(`python/ray/train/_internal/worker_group.py` gang over raylets +
backend_executor process-group setup), re-shaped for TPU pods: one gang
member per host, GSPMD over the spanning mesh, orbax for sharded
save/restore (SURVEY.md §3.4, §7.4.1).

Usage:
    python examples/pod_cluster.py --workers 7 --steps 6 --kill

On real hardware the worker processes become `ray-tpu start --address
<head-ip>:<port>` on each TPU host and `workers_in_process=True` puts
gang members in the device-owning runtimes; nothing else changes.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# one virtual device per runtime: the pod shape (1 host = 1 device here;
# a real TPU host contributes its local chips instead).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

import ray_tpu  # noqa: E402


def train_func(config):
    """Runs on every gang member (its own OS process)."""
    import os
    import time

    import jax
    import numpy as np

    from ray_tpu import train as rt_train
    from ray_tpu.comm.mesh import MeshSpec, build_mesh
    from ray_tpu.models import get_config
    from ray_tpu.train.checkpoint import Checkpoint, load_pytree, save_pytree
    from ray_tpu.train.lm import (
        batch_shardings,
        init_train_state,
        make_global_batch,
        make_optimizer,
        make_train_step,
    )

    ctx = rt_train.get_context()
    world, rank = ctx.get_world_size(), ctx.get_world_rank()
    cfg = get_config("tiny-llama")
    seq = config["seq_len"]
    total_steps = config["total_steps"]

    mesh = build_mesh(MeshSpec.create(dp=world))
    opt = make_optimizer(total_steps=total_steps)
    state, shardings = init_train_state(cfg, mesh, jax.random.PRNGKey(0), opt)
    step_fn = jax.jit(
        make_train_step(cfg, opt),
        donate_argnums=0,
        in_shardings=(shardings, batch_shardings(mesh)),
    )

    start_step = 0
    ck = rt_train.get_checkpoint()
    if ck is not None:
        meta = ck.get_metadata()
        start_step = int(meta.get("step", 0))
        # every process participates in the sharded restore (orbax places
        # each leaf straight into this mesh's shardings)
        state = load_pytree(os.path.join(ck.as_directory(), "state"),
                            target=state, shardings=shardings)

    # ---- data: THIS rank's shard of the split pipeline ----
    data_it = config["datasets"]["train"]
    batches = data_it.iter_batches(batch_size=seq + 1, drop_last=True)

    b_shardings = batch_shardings(mesh)
    for step in range(start_step, total_steps):
        if config.get("step_delay"):
            # chaos runs: keep the gang in-flight long enough for the
            # killer to land mid-training (steps are sub-ms on CPU)
            time.sleep(config["step_delay"])
        rows = next(batches)
        ids = np.asarray(rows["id"], dtype=np.int32) % cfg.vocab_size
        # global batch is (world, seq); this process owns row `rank` —
        # other rows are never read (make_global_batch only pulls the
        # addressable shard), so zeros elsewhere are fine
        host_tokens = np.zeros((world, seq), np.int32)
        host_targets = np.zeros((world, seq), np.int32)
        host_tokens[rank] = ids[:-1]
        host_targets[rank] = ids[1:]
        batch = make_global_batch(
            {"tokens": host_tokens, "targets": host_targets}, b_shardings)
        with mesh:
            state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])

        checkpoint = None
        if (step + 1) % config["checkpoint_every"] == 0 or step == total_steps - 1:
            ckpt_dir = os.path.join(ctx.get_trial_dir(), f"ckpt-{step + 1}")
            # all processes join the sharded save; rank 0 owns metadata
            save_pytree(state, os.path.join(ckpt_dir, "state"))
            if rank == 0:
                checkpoint = Checkpoint.from_directory(ckpt_dir)
                checkpoint.set_metadata({"step": step + 1})
        rt_train.report(
            {"step": step, "loss": loss, "start_step": start_step,
             "rank": rank},
            checkpoint=checkpoint,
        )


def spawn_worker(addr: str, tag: str) -> subprocess.Popen:
    code = textwrap.dedent(f"""
        import ray_tpu
        w = ray_tpu.init(address={addr!r}, num_cpus=2, num_tpus=0,
                         resources={{"pod_host": 1.0}})
        w.wait(timeout=900)
    """)
    log = open(os.path.join(tempfile.gettempdir(), f"pod_worker_{tag}.log"), "w")
    return subprocess.Popen(
        [sys.executable, "-c", code], env=dict(os.environ),
        stdout=log, stderr=subprocess.STDOUT, text=True,
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=7,
                    help="joined worker runtimes (gang = workers + 1)")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--kill", action="store_true",
                    help="SIGKILL one worker host after the first "
                         "checkpoint; training must resume and finish")
    args = ap.parse_args()
    world = args.workers + 1

    from ray_tpu import data
    from ray_tpu.train import (
        CheckpointConfig,
        FailureConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )

    rt = ray_tpu.init(
        num_cpus=2, num_tpus=0, resources={"pod_host": 1.0},
        system_config={
            "control_plane_rpc_port": 0,
            "worker_processes": 0,
            "health_check_timeout_ms": 3000,
        },
    )
    addr = rt._cp_server.address
    print(f"head up at {addr}; spawning {args.workers} worker runtimes")
    procs = [spawn_worker(addr, str(i)) for i in range(args.workers)]
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if len(rt.control_plane.alive_nodes()) >= world:
            break
        time.sleep(0.2)
    nodes = rt.control_plane.alive_nodes()
    assert len(nodes) >= world, f"only {len(nodes)} runtimes up"
    print(f"pod shape reached: {len(nodes)} runtimes")

    # tokens for every (rank, step) come out of the data plane: read/map
    # tasks run wherever the scheduler puts them (any of the 8 runtimes),
    # and each gang member pulls its OWN shard's blocks over the transfer
    # plane from the producing host
    rows_per_rank = args.steps * (args.seq_len + 1)
    ds = data.range(world * rows_per_rank, parallelism=world).map_batches(
        lambda b: {"id": b["id"]}
    )

    storage = tempfile.mkdtemp(prefix="pod_train_")
    trainer = JaxTrainer(
        train_func,
        train_loop_config={
            "total_steps": args.steps,
            "seq_len": args.seq_len,
            "checkpoint_every": 2,
            "step_delay": 0.8 if args.kill else 0.0,
        },
        scaling_config=ScalingConfig(
            num_workers=world,
            resources_per_worker={"CPU": 1.0},
            placement_strategy="STRICT_SPREAD",
            distributed_bootstrap=True,
            workers_in_process=False,  # fresh jax world per gang attempt
        ),
        run_config=RunConfig(
            name="pod-train",
            storage_path=storage,
            failure_config=FailureConfig(max_failures=1 if args.kill else 0),
            checkpoint_config=CheckpointConfig(num_to_keep=2),
        ),
        datasets={"train": ds},
    )

    killer_state = {"killed": False}
    if args.kill:
        def killer():
            trial_dir = os.path.join(storage, "pod-train")
            while not killer_state["killed"]:
                time.sleep(0.5)
                try:
                    ckpts = [d for d in os.listdir(trial_dir)
                             if d.startswith("ckpt-")
                             and os.path.exists(os.path.join(
                                 trial_dir, d, ".ray_tpu_checkpoint.json"))]
                except OSError:
                    continue
                if not ckpts:
                    continue
                victim = procs[0]
                print(f"checkpoint {sorted(ckpts)[-1]} on disk; "
                      f"SIGKILLing worker host pid={victim.pid}")
                from ray_tpu.util import chaos

                chaos.kill_worker_host(victim)
                killer_state["killed"] = True
                time.sleep(1.0)
                print("spawning replacement worker host")
                procs.append(spawn_worker(addr, "replacement"))

        threading.Thread(target=killer, daemon=True).start()

    result = trainer.fit()
    assert result.error is None, f"training failed: {result.error}"
    hist = result.metrics_history
    final = hist[-1]
    assert final["step"] == args.steps - 1, final
    restarted = any(h.get("start_step", 0) > 0 for h in hist)
    if args.kill:
        assert killer_state["killed"], "killer never fired"
        assert restarted, f"gang never resumed from checkpoint: {hist}"
        print(f"gang restarted from checkpoint and resumed at step "
              f"{next(h['start_step'] for h in hist if h.get('start_step', 0) > 0)}")
    print(json.dumps({"steps": len(hist), "final_loss": final["loss"],
                      "world": world, "restarted": restarted}))
    print("POD-OK")

    ray_tpu.shutdown()
    for p in procs:
        if p.poll() is None:
            p.terminate()
        try:
            p.wait(timeout=20)
        except subprocess.TimeoutExpired:
            p.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
