"""North-star slice in one file: pretrain -> checkpoint -> serve.

The BASELINE.md end-to-end story (Llama pretrain + serve with no GPU in
the loop), scaled to run anywhere: a Dataset streams token batches into
a JaxTrainer gang that trains the real sharded transformer and reports
orbax checkpoints; the best checkpoint then loads into the
continuous-batching LLM engine behind a Serve deployment, and a greedy
completion is served from the weights just trained.

    # one real chip (or default devices)
    python examples/pretrain_and_serve.py --model tiny-llama --steps 30

    # virtual 8-device CPU mesh, fsdp sharding
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pretrain_and_serve.py --mesh fsdp=-1 --steps 30

Reference analogue: Ray Train -> Checkpoint -> Ray Serve handoff
(`train/base_trainer.py` fit -> `Checkpoint` -> `serve.run`), the
reference's own flagship workflow, with vLLM replaced by the native
paged-KV engine.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--mesh", default="dp=-1",
                   help="mesh axes for the gang, e.g. fsdp=-1 or dp=2,tp=2")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--storage", default="/tmp/ray_tpu_pretrain_and_serve")
    args = p.parse_args()

    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu import serve
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    # logical CPUs oversubscribed: the gang worker holds one while the
    # Dataset's read/map tasks need their own — on a small host a 1-CPU
    # default would starve the data plane behind the trainer
    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 1))
    mesh_axes = {k: int(v) for k, v in
                 (kv.split("=") for kv in args.mesh.split(","))}

    # -- data: a token stream through the Dataset machinery ---------------
    rng = np.random.default_rng(0)
    vocab_hint = 256  # tiny synthetic corpus; real runs read_parquet(...)
    rows = [{"tokens": rng.integers(1, vocab_hint, args.seq + 1)}
            for _ in range(args.batch * args.steps)]
    ds = rt_data.from_items(rows)

    # -- train: the real sharded LM under JaxTrainer -----------------------
    def train_loop(config):
        import jax
        import numpy as np

        from ray_tpu import train
        from ray_tpu.comm.mesh import MeshSpec, build_mesh, set_mesh
        from ray_tpu.models import get_config
        from ray_tpu.train.checkpoint import save_pytree
        from ray_tpu.train.lm import (
            init_train_state,
            make_optimizer,
            make_train_step,
        )

        cfg = get_config(config["model"])
        mesh = build_mesh(MeshSpec.create(**config["mesh_axes"]))
        set_mesh(mesh)
        opt = make_optimizer(learning_rate=1e-3, warmup_steps=5,
                             total_steps=config["steps"])
        state, _ = init_train_state(cfg, mesh, jax.random.PRNGKey(0), opt)
        step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=0)

        ctx = train.get_context()
        it = train.get_dataset_shard("train").iter_batches(
            batch_size=config["batch"])
        with mesh:
            for step, batch in enumerate(it):
                toks = np.stack([np.asarray(t) for t in batch["tokens"]])
                toks = np.remainder(toks, cfg.vocab_size).astype(np.int32)
                model_batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
                state, metrics = step_fn(state, model_batch)
                if step % 10 == 0 or step == config["steps"] - 1:
                    ckpt_dir = os.path.join(config["storage"],
                                            f"params_step{step}")
                    if ctx.get_world_rank() == 0:
                        save_pytree(state["params"], ckpt_dir)
                    ckpt = train.Checkpoint(ckpt_dir)
                    ckpt.set_metadata({"step": step})
                    train.report(
                        {"step": step, "loss": float(metrics["loss"])},
                        checkpoint=ckpt,
                    )

    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"model": args.model, "mesh_axes": mesh_axes,
                           "steps": args.steps, "batch": args.batch,
                           "storage": args.storage},
        scaling_config=ScalingConfig(num_workers=1, mesh_shape=mesh_axes),
        run_config=RunConfig(name="pretrain", storage_path=args.storage),
        datasets={"train": ds},
    )
    result = trainer.fit()
    if result.error is not None:
        raise SystemExit(f"training failed: {result.error}")
    losses = [m["loss"] for m in result.metrics_history]
    print(f"trained {args.steps} steps: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    ckpt_path = result.checkpoint.path

    # -- serve: the trained weights behind the paged-KV engine -------------
    def load_trained():
        import jax

        from ray_tpu.models import get_config, init_params
        from ray_tpu.train.checkpoint import load_pytree

        cfg = get_config(args.model)
        template = init_params(cfg, jax.random.PRNGKey(0))
        params = load_pytree(ckpt_path, target=template)
        return params, cfg

    app = serve.LLMServer.bind(
        params_fn=load_trained,
        engine_config=dict(max_batch_size=4, max_seq_len=256,
                           page_size=16),
    )
    handle = serve.run(app, name="pretrained")
    out = handle.remote({"prompt_ids": [5, 6, 7, 8], "max_tokens": 12,
                         "temperature": 0.0}).result()
    print(f"served from the trained checkpoint: {out['token_ids']} "
          f"(ttft {out['ttft_s']*1000:.0f}ms)")
    serve.shutdown()
    ray_tpu.shutdown()
    print("pretrain -> checkpoint -> serve: OK")


if __name__ == "__main__":
    main()
