"""MPMD pipeline-parallel trainer (train/pipeline.py + parallel/zero.py).

Numerics contract under test:
- a 2-stage x 2-microbatch pipeline run is loss-identical (fp tolerance)
  to the equivalent single-gang run, with activations demonstrably
  crossing DistChannels (channel metrics move);
- ZeRO-1 sharded updates match replicated updates EXACTLY (bit-equal
  params), both standalone and through the dp=2 pipeline;
- checkpoint resume reproduces the uninterrupted run exactly;
- a killed stage-gang worker never hangs the pipeline: fail-fast with
  TrainingFailedError, or resume from the last per-stage checkpoint.
"""

import os
import queue
import threading
import time

import numpy as np
import pytest

from ray_tpu.parallel import zero
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
)
from ray_tpu.train.lm import make_optimizer, synthetic_batch
from ray_tpu.train.pipeline import (
    DEFAULT_STAGE_RULES,
    LMStageModule,
    PipelineConfig,
    PipelineTrainer,
    match_stage_rules,
    split_stage_params,
)
from ray_tpu.train.trainer import TrainingFailedError

pytestmark = pytest.mark.pipeline

OPT = dict(learning_rate=1e-2, warmup_steps=0, total_steps=100)


def _cfg():
    from ray_tpu.models import get_config

    return get_config("tiny-llama")


def _data_fn(cfg, batch, seq, base_seed):
    def data(step):
        b = synthetic_batch(cfg, batch, seq, seed=base_seed + step)
        return {k: np.asarray(v) for k, v in b.items()}

    return data


def _trainer(tmp_path, module, pcfg, data_fn, name, *, max_failures=0,
             seed=0, resume=None):
    return PipelineTrainer(
        module,
        pipeline=pcfg,
        optimizer_kwargs=dict(OPT),
        run_config=RunConfig(
            name=name, storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=max_failures),
        ),
        data_fn=data_fn,
        seed=seed,
        resume_from_checkpoint=resume,
    )


def _fast_pcfg(**kw):
    kw.setdefault("num_stages", 2)
    kw.setdefault("num_microbatches", 2)
    kw.setdefault("stages_in_process", True)
    kw.setdefault("recv_timeout_s", 30.0)
    kw.setdefault("put_timeout_s", 30.0)
    kw.setdefault("step_timeout_s", 120.0)
    return PipelineConfig(**kw)


# ---------------------------------------------------------------------------
# Stage partition rules
# ---------------------------------------------------------------------------


class TestStageRules:
    def test_default_rules_partition_tiny_llama(self):
        cfg = _cfg()
        module = LMStageModule(cfg, 2)
        full = module.init_full(seed=0)
        stages = module.partition(full)
        assert "embed" in stages[0] and "embed" not in stages[1]
        assert "lm_head" in stages[1] and "final_norm" in stages[1]
        assert "lm_head" not in stages[0]
        # layer stack split into contiguous halves that stitch back
        for path, leaf in full.items():
            if not path.startswith("layers/"):
                continue
            a, b = stages[0][path], stages[1][path]
            assert a.shape[0] == b.shape[0] == leaf.shape[0] // 2
            np.testing.assert_array_equal(np.concatenate([a, b]), leaf)

    def test_unmatched_param_is_an_error(self):
        flat = {"embed": np.zeros(2), "mystery": np.zeros(2)}
        with pytest.raises(ValueError, match="mystery"):
            match_stage_rules(((r"^embed$", "first"),), flat, 2)

    def test_explicit_int_placement(self):
        flat = {"a": np.zeros(3), "b": np.zeros(3)}
        rules = ((r"^a$", 1), (r"^b$", "first"))
        stages = split_stage_params(flat, 2, rules)
        assert list(stages[0]) == ["b"] and list(stages[1]) == ["a"]
        with pytest.raises(ValueError, match="outside"):
            match_stage_rules(((r"^a$", 7), (r".", "first")), flat, 2)

    def test_split_requires_divisible_leading_axis(self):
        flat = {"layers/w": np.zeros((3, 4))}
        with pytest.raises(ValueError, match="divisible"):
            split_stage_params(flat, 2, DEFAULT_STAGE_RULES)

    def test_module_rejects_tied_and_indivisible(self):
        import dataclasses

        cfg = _cfg()
        with pytest.raises(ValueError, match="layers"):
            LMStageModule(cfg, 3)  # 2 layers, 3 stages
        tied = dataclasses.replace(cfg, tie_embeddings=True)
        with pytest.raises(ValueError, match="tie_embeddings"):
            LMStageModule(tied, 2)


# ---------------------------------------------------------------------------
# ZeRO-1 machinery (no actors)
# ---------------------------------------------------------------------------


class TestZero1:
    def _params(self):
        rng = np.random.RandomState(0)
        return {
            "embed": rng.randn(16, 8).astype(np.float32),
            "layers/w1": rng.randn(4, 8, 8).astype(np.float32),
            "layers/w2": rng.randn(4, 8, 8).astype(np.float32),
            "head": rng.randn(8, 16).astype(np.float32),
            "norm": rng.randn(8).astype(np.float32),
        }

    def test_partition_covers_each_leaf_once_balanced(self):
        params = self._params()
        assign = zero.partition_leaves(params, 2)
        assert set(assign) == set(params)
        assert set(assign.values()) <= {0, 1}
        loads = {0: 0, 1: 0}
        for p, r in assign.items():
            loads[r] += params[p].nbytes
        largest = max(v.nbytes for v in params.values())
        assert abs(loads[0] - loads[1]) <= largest
        # deterministic: same inputs, same assignment
        assert assign == zero.partition_leaves(params, 2)

    def test_sharded_update_matches_replicated_exactly(self):
        import jax.numpy as jnp
        import optax

        params = self._params()
        rng = np.random.RandomState(1)
        world = 2
        opt = make_optimizer(grad_clip=None, **OPT)

        # replicated reference: full-tree state on every rank
        ref = {p: jnp.asarray(v) for p, v in params.items()}
        ref_state = opt.init(ref)
        # sharded: per-rank optimizer state over owned leaves only
        assign = zero.partition_leaves(params, world)
        shard = {p: jnp.asarray(v) for p, v in params.items()}
        shard_states = [
            opt.init({p: shard[p] for p, r in assign.items() if r == rank})
            for rank in range(world)
        ]
        for _ in range(3):
            per_rank = [
                {p: rng.randn(*v.shape).astype(np.float32)
                 for p, v in params.items()}
                for _ in range(world)
            ]
            mean = zero.group_mean(per_rank)

            g = {p: jnp.asarray(v) for p, v in mean.items()}
            updates, ref_state = opt.update(g, ref_state, ref)
            ref = optax.apply_updates(ref, updates)

            gathered = {}
            for rank in range(world):
                owned = sorted(p for p, r in assign.items() if r == rank)
                og = {p: jnp.asarray(
                    zero.group_mean([c for c in
                                     ({q: pr[q] for q in owned}
                                      for pr in per_rank)])[p])
                    for p in owned}
                op = {p: shard[p] for p in owned}
                upd, shard_states[rank] = opt.update(
                    og, shard_states[rank], op)
                gathered.update(optax.apply_updates(op, upd))
            shard = gathered

        for p in params:
            np.testing.assert_array_equal(
                np.asarray(ref[p]), np.asarray(shard[p]))

    def test_leaf_sq_norms_match_global_norm(self):
        import optax

        grads = self._params()
        sq = zero.leaf_sq_norms(grads)
        got = np.sqrt(sum(sq[p] for p in sorted(sq)))
        want = float(optax.global_norm(grads))
        np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# Channel metrics satellite (deterministic, no actors)
# ---------------------------------------------------------------------------


class TestChannelMetrics:
    def test_send_recv_metrics_move(self):
        from ray_tpu.core import channels

        addr = channels.service_address() or channels.ensure_service()
        chan = channels.DistChannel(addr, maxsize=4)
        before = channels.channel_stats()
        payload = np.zeros(1024, np.float32)
        chan.put(("arr", 0, payload))
        got = chan.get(timeout=2.0)
        after = channels.channel_stats()
        assert np.array_equal(got[2], payload)
        assert after["send_bytes"] - before["send_bytes"] >= payload.nbytes
        assert after["recv_count"] - before["recv_count"] == 1
        chan.close()

    def test_capacity_reached_counter(self):
        from ray_tpu.core import channels

        addr = channels.service_address() or channels.ensure_service()
        chan = channels.DistChannel(addr, maxsize=1)
        before = channels.channel_stats()
        chan.put("fills")
        with pytest.raises(queue.Full):
            chan.put("overflows", timeout=0.05)
        after = channels.channel_stats()
        assert after["capacity_reached"] - before["capacity_reached"] >= 1
        chan.close()

    def test_recv_wait_recorded_on_timeout(self):
        from ray_tpu.core import channels

        addr = channels.service_address() or channels.ensure_service()
        chan = channels.DistChannel(addr, maxsize=1)
        before = channels.channel_stats()
        with pytest.raises(queue.Empty):
            chan.get(timeout=0.05)
        after = channels.channel_stats()
        assert after["recv_count"] - before["recv_count"] == 1
        assert after["recv_wait_seconds"] - before["recv_wait_seconds"] \
            >= 0.04
        chan.close()


# ---------------------------------------------------------------------------
# Pipeline numerics vs the single-gang baseline
# ---------------------------------------------------------------------------


def _single_gang_baseline(cfg, data_fn, steps):
    """The equivalent one-program run: full batch, optax's own global-norm
    clip (grad_clip=1.0 matches PipelineConfig's default)."""
    import jax
    import optax

    from ray_tpu.models import init_params, loss_fn

    opt = make_optimizer(grad_clip=1.0, **OPT)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt.init(params)

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, _mets), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for t in range(steps):
        params, opt_state, loss = step_fn(params, opt_state, data_fn(t))
        losses.append(float(loss))
    return losses, {p: np.asarray(v)
                    for p, v in zero.flatten_tree(params).items()}


class TestPipelineParity:
    def test_two_stage_matches_single_gang(self, tmp_path,
                                           ray_start_regular):
        from ray_tpu.core import channels

        cfg = _cfg()
        steps, batch, seq = 4, 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=7_000)
        base_losses, base_params = _single_gang_baseline(cfg, data_fn, steps)

        module = LMStageModule(cfg, 2)
        trainer = _trainer(tmp_path, module, _fast_pcfg(), data_fn, "parity")
        before = channels.channel_stats()
        result = trainer.fit(steps, global_batch=batch, seq_len=seq)
        after = channels.channel_stats()

        assert result.error is None
        pipe_losses = [m["loss"] for m in result.metrics_history]
        np.testing.assert_allclose(pipe_losses, base_losses,
                                   rtol=2e-4, atol=1e-5)
        # the updated model matches too, stage by stage
        expected = split_stage_params(base_params, 2, module.rules)
        for si in range(2):
            for path, want in expected[si].items():
                np.testing.assert_allclose(
                    trainer.final_state[si][path], want,
                    rtol=1e-2, atol=1e-4)
        # activations/gradients demonstrably crossed DistChannels:
        # 2 stages x 2 microbatches x 4 steps of [B/1, T, D] tensors
        assert after["send_bytes"] - before["send_bytes"] > 0
        assert after["recv_count"] - before["recv_count"] \
            >= steps * 2 * 2  # act + grad frames per microbatch
        # every step reported schedule health
        for m in result.metrics_history:
            assert 0.0 <= m["bubble_fraction"] <= 1.0
            assert m["step_seconds"] > 0

    def test_single_stage_degenerate_matches(self, tmp_path,
                                             ray_start_regular):
        """S=1 reduces to pure microbatch grad accumulation — same loss
        curve, no channels at all."""
        cfg = _cfg()
        steps, batch, seq = 2, 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=9_000)
        base_losses, _ = _single_gang_baseline(cfg, data_fn, steps)
        module = LMStageModule(cfg, 1)
        trainer = _trainer(
            tmp_path, module,
            _fast_pcfg(num_stages=1, num_microbatches=2),
            data_fn, "degenerate")
        result = trainer.fit(steps, global_batch=batch, seq_len=seq)
        assert result.error is None
        np.testing.assert_allclose(
            [m["loss"] for m in result.metrics_history], base_losses,
            rtol=2e-4, atol=1e-5)


class TestZero1Pipeline:
    def test_zero1_on_off_bit_identical(self, tmp_path, ray_start_regular):
        cfg = _cfg()
        steps, batch, seq = 2, 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=11_000)
        module = LMStageModule(cfg, 2)

        runs = {}
        for zero1 in (False, True):
            trainer = _trainer(
                tmp_path, module,
                _fast_pcfg(dp=2, zero1=zero1),
                data_fn, f"zero1_{zero1}")
            result = trainer.fit(steps, global_batch=batch, seq_len=seq)
            assert result.error is None
            runs[zero1] = (result, trainer)

        losses_off = [m["loss"] for m in runs[False][0].metrics_history]
        losses_on = [m["loss"] for m in runs[True][0].metrics_history]
        assert losses_off == losses_on  # same forwards, same params
        for si in range(2):
            off = runs[False][1].final_state[si]
            on = runs[True][1].final_state[si]
            for path in off:
                np.testing.assert_array_equal(off[path], on[path])
        # all-gather leaves every ZeRO replica holding the full new params
        all_on = runs[True][1].final_state_all
        for si in range(2):
            for path in all_on[(si, 0)]:
                np.testing.assert_array_equal(
                    all_on[(si, 0)][path], all_on[(si, 1)][path])


# ---------------------------------------------------------------------------
# Checkpoint / resume
# ---------------------------------------------------------------------------


class TestCheckpointResume:
    def test_resume_reproduces_uninterrupted_run(self, tmp_path,
                                                 ray_start_regular):
        cfg = _cfg()
        batch, seq = 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=13_000)
        module = LMStageModule(cfg, 2)

        # uninterrupted 4-step run
        straight = _trainer(tmp_path, module, _fast_pcfg(), data_fn,
                            "straight")
        res_straight = straight.fit(4, global_batch=batch, seq_len=seq)
        assert res_straight.error is None

        # 2 steps with a checkpoint, then resume for steps 2..3
        first = _trainer(tmp_path, module,
                         _fast_pcfg(checkpoint_every=2), data_fn, "leg1")
        res1 = first.fit(2, global_batch=batch, seq_len=seq)
        assert res1.error is None
        assert res1.checkpoint is not None
        assert res1.checkpoint.get_metadata()["step"] == 1

        second = _trainer(tmp_path, module, _fast_pcfg(), data_fn, "leg2",
                          resume=res1.checkpoint)
        res2 = second.fit(4, global_batch=batch, seq_len=seq)
        assert res2.error is None
        assert [m["step"] for m in res2.metrics_history] == [2, 3]
        np.testing.assert_allclose(
            [m["loss"] for m in res2.metrics_history],
            [m["loss"] for m in res_straight.metrics_history[2:]],
            rtol=0, atol=0)
        for si in range(2):
            for path in straight.final_state[si]:
                np.testing.assert_array_equal(
                    straight.final_state[si][path],
                    second.final_state[si][path])


# ---------------------------------------------------------------------------
# Chaos: dead stage-gang worker must never hang the pipeline
# ---------------------------------------------------------------------------


def _fit_in_thread(trainer, steps, batch, seq):
    box = {}

    def run():
        try:
            box["result"] = trainer.fit(steps, global_batch=batch,
                                        seq_len=seq)
        except BaseException as e:  # noqa: BLE001 — surfaced to the test
            box["raised"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def _wait_for(predicate, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.chaos
class TestPipelineChaos:
    def test_killed_worker_fails_fast(self, tmp_path, ray_start_regular):
        """SIGKILL one stage gang member mid-run with max_failures=0: the
        driver must surface TrainingFailedError promptly — no hang on the
        dead peer's channels (recv/put deadlines) or on the driver get
        (step timeout)."""
        from ray_tpu.util import chaos

        cfg = _cfg()
        data_fn = _data_fn(cfg, 8, 16, base_seed=17_000)
        module = LMStageModule(cfg, 2)
        pcfg = _fast_pcfg(
            stages_in_process=False,  # real OS processes, real SIGKILL
            recv_timeout_s=5.0, put_timeout_s=5.0, step_timeout_s=90.0)
        trainer = _trainer(tmp_path, module, pcfg, data_fn, "chaos_fast",
                           max_failures=0)
        thread, box = _fit_in_thread(trainer, 50, 8, 16)
        _wait_for(lambda: len(trainer.worker_pids) == 2, 60,
                  "stage workers to spawn")
        victim = trainer.worker_pids[(1, 0)]
        t_kill = time.monotonic()
        chaos.kill_worker_host(victim)
        thread.join(timeout=120)
        assert not thread.is_alive(), "pipeline hung on a dead stage gang"
        assert "raised" not in box, box.get("raised")
        result = box["result"]
        assert isinstance(result.error, TrainingFailedError)
        assert "pipeline training failed" in str(result.error)
        # fail-fast, not a 300s channel-default crawl
        assert time.monotonic() - t_kill < 100

    @pytest.mark.slow
    def test_killed_worker_resumes_from_checkpoint(self, tmp_path,
                                                   ray_start_regular):
        """With max_failures=1 and per-step checkpoints, a SIGKILLed
        worker costs one gang restart: training resumes from the last
        per-stage checkpoint and completes every step."""
        from ray_tpu.util import chaos

        cfg = _cfg()
        data_fn = _data_fn(cfg, 8, 16, base_seed=19_000)
        module = LMStageModule(cfg, 2)
        pcfg = _fast_pcfg(
            stages_in_process=False, checkpoint_every=1,
            recv_timeout_s=5.0, put_timeout_s=5.0, step_timeout_s=90.0)
        trainer = _trainer(tmp_path, module, pcfg, data_fn, "chaos_resume",
                           max_failures=1)
        thread, box = _fit_in_thread(trainer, 6, 8, 16)
        storage = os.path.join(str(tmp_path), "chaos_resume")
        _wait_for(lambda: len(trainer.worker_pids) == 2, 60,
                  "stage workers to spawn")
        first_pids = dict(trainer.worker_pids)
        _wait_for(
            lambda: any(name.startswith("step_")
                        for name in os.listdir(storage)),
            120, "first per-stage checkpoint")
        chaos.kill_worker_host(first_pids[(0, 0)])
        thread.join(timeout=300)
        assert not thread.is_alive(), "pipeline hung after worker kill"
        assert "raised" not in box, box.get("raised")
        result = box["result"]
        assert result.error is None
        assert trainer.restarts >= 1
        assert [m["step"] for m in result.metrics_history] == list(range(6))
        assert trainer.worker_pids != first_pids  # a fresh gang ran


# ---------------------------------------------------------------------------
# Tracing: a traced step shows the full stage timeline
# ---------------------------------------------------------------------------


class TestPipelineTracing:
    def test_traced_step_contains_stage_and_channel_spans(
            self, tmp_path, ray_start_regular):
        from ray_tpu.util import tracing

        cfg = _cfg()
        data_fn = _data_fn(cfg, 8, 16, base_seed=23_000)
        module = LMStageModule(cfg, 2)
        trainer = _trainer(tmp_path, module, _fast_pcfg(), data_fn,
                           "traced")
        with tracing.start_span("pipeline_test_root") as root:
            result = trainer.fit(1, global_batch=8, seq_len=16)
        assert result.error is None
        names = {s["name"] for s in tracing.get_spans(root.trace_id)}
        assert "pipeline.step" in names
        assert "pipeline.stage_step" in names
        assert "channel_send" in names
        assert "channel_recv" in names
        stage_spans = [s for s in tracing.get_spans(root.trace_id)
                       if s["name"] == "pipeline.stage_step"]
        assert {s["attrs"]["stage"] for s in stage_spans} == {0, 1}


# ---------------------------------------------------------------------------
# Cross-host: stage gangs on distinct joined hosts, channels over TCP
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestPipelineCrossHost:
    @pytest.fixture
    def pipeline_cluster(self):
        import subprocess
        import sys
        import textwrap

        import ray_tpu

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

        def worker_env():
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["RAY_TPU_WORKER_PROCESSES"] = "0"
            env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
            env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
            return env

        ray_tpu.shutdown()
        rt = ray_tpu.init(
            num_cpus=0, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={rt._cp_server.address!r}, num_cpus=2,
                             num_tpus=0)
            w.wait(timeout=600)
        """)
        procs = [subprocess.Popen(
            [sys.executable, "-c", code], env=worker_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ) for _ in range(2)]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if len(rt.control_plane.alive_nodes()) >= 3:
                break
            time.sleep(0.1)
        try:
            yield rt
        finally:
            import ray_tpu

            ray_tpu.shutdown()
            for p in procs:
                if p.poll() is None:
                    p.kill()

    def test_two_stages_across_hosts(self, tmp_path, pipeline_cluster):
        """Each stage lands on its own joined host (STRICT_SPREAD over 2
        one-CPU-bundle stages); activations/gradients ride the remote
        channel path (TCP to the consumer's ChannelService)."""
        cfg = _cfg()
        data_fn = _data_fn(cfg, 8, 16, base_seed=29_000)
        base_losses, _ = _single_gang_baseline(cfg, data_fn, 2)
        module = LMStageModule(cfg, 2)
        pcfg = PipelineConfig(
            num_stages=2, num_microbatches=2,
            recv_timeout_s=120.0, put_timeout_s=120.0,
            step_timeout_s=300.0)
        trainer = _trainer(tmp_path, module, pcfg, data_fn, "crosshost")
        result = trainer.fit(2, global_batch=8, seq_len=16)
        assert result.error is None
        np.testing.assert_allclose(
            [m["loss"] for m in result.metrics_history], base_losses,
            rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Interleaved virtual-stage schedule (parallel/pipeline.py generator)
# ---------------------------------------------------------------------------


class TestInterleavedSchedule:
    def test_v1_reduces_to_classic_1f1b(self):
        from ray_tpu.parallel.pipeline import interleaved_schedule

        S, M = 4, 8
        for rank in range(S):
            sched = interleaved_schedule(S, 1, M, rank)
            # classic warmup: S-1-rank forwards, then the steady-state
            # F/B alternation — the first backward lands right after the
            # first steady-state forward
            warm = min(S - 1 - rank, M)
            first_b = next(i for i, e in enumerate(sched) if e[0] == "B")
            assert first_b == warm + 1
            assert all(e[1] == 0 for e in sched)  # v=1: one local chunk
            assert sched[:warm] == [("F", 0, m) for m in range(warm)]

    def test_every_unit_scheduled_exactly_once(self):
        from ray_tpu.parallel.pipeline import interleaved_schedule

        for S, v, M in ((2, 2, 4), (2, 3, 4), (4, 2, 8), (3, 2, 6)):
            for rank in range(S):
                sched = interleaved_schedule(S, v, M, rank)
                fwd = [(c, m) for k, c, m in sched if k == "F"]
                bwd = [(c, m) for k, c, m in sched if k == "B"]
                want = [(c, m) for c in range(v) for m in range(M)]
                assert sorted(fwd) == want, (S, v, M, rank)
                assert sorted(bwd) == want, (S, v, M, rank)

    def test_microbatches_must_divide_when_interleaving(self):
        from ray_tpu.parallel.pipeline import interleaved_schedule

        with pytest.raises(ValueError, match="divisible"):
            interleaved_schedule(2, 2, 3, 0)

    def test_validate_grid_is_deadlock_free(self):
        from ray_tpu.parallel.pipeline import validate_interleaved

        for S in (1, 2, 3, 4):
            for v in (1, 2, 3):
                for M in (S, 2 * S, 4 * S):
                    validate_interleaved(S, v, M, capacity=S * v + 2)

    def test_validate_flags_starved_capacity(self):
        from ray_tpu.parallel.pipeline import validate_interleaved

        with pytest.raises(ValueError, match="deadlock"):
            validate_interleaved(2, 1, 2, capacity=0)


# ---------------------------------------------------------------------------
# In-stage SPMD sharding: sharded stage == replicated stage numerics
# ---------------------------------------------------------------------------


class TestShardedStageParity:
    @pytest.mark.parametrize("axes", ["dp=2", "fsdp=2", "tp=2"])
    def test_sharded_matches_replicated(self, tmp_path, ray_start_regular,
                                        axes):
        """with_sharding_constraint + param shardings must be numerically
        invisible: an 8-step 2-stage run with each stage gang sharded over
        the named mesh matches the single-gang replicated run to fp
        tolerance (the 8 virtual CPU devices carve real submeshes)."""
        cfg = _cfg()
        steps, batch, seq = 8, 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=31_000)
        base_losses, _ = _single_gang_baseline(cfg, data_fn, steps)
        module = LMStageModule(cfg, 2)
        trainer = _trainer(
            tmp_path, module,
            _fast_pcfg(stage_mesh_axes=axes),
            data_fn, f"shard_{axes.replace('=', '')}")
        result = trainer.fit(steps, global_batch=batch, seq_len=seq)
        assert result.error is None
        np.testing.assert_allclose(
            [m["loss"] for m in result.metrics_history], base_losses,
            rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Interleaved virtual stages: v=2 numerics vs v=1
# ---------------------------------------------------------------------------


class TestVirtualStagesParity:
    def test_v2_matches_v1(self, tmp_path, ray_start_regular):
        """Splitting each worker's layers into two non-contiguous chunks
        reorders nothing mathematically: same microbatch grad mean, same
        updates — the v=2 loss curve must match v=1 to fp tolerance (jit
        partition boundaries move, so bitwise equality is not promised)."""
        import dataclasses

        cfg = dataclasses.replace(_cfg(), n_layers=4)
        steps, batch, seq = 4, 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=33_000)

        losses = {}
        for v in (1, 2):
            module = LMStageModule(cfg, 2, virtual_stages=v)
            trainer = _trainer(
                tmp_path, module, _fast_pcfg(virtual_stages=v),
                data_fn, f"virt{v}")
            result = trainer.fit(steps, global_batch=batch, seq_len=seq)
            assert result.error is None
            losses[v] = [m["loss"] for m in result.metrics_history]
        np.testing.assert_allclose(losses[2], losses[1],
                                   rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# In-XLA ZeRO collectives vs host-channel collectives
# ---------------------------------------------------------------------------


class TestInXlaZero:
    def test_inxla_matches_channel_path(self, tmp_path, ray_start_regular,
                                        monkeypatch):
        """The psum_scatter/all_gather ZeRO path must be numerically
        identical to the host DistChannel group-mean path: same losses,
        bit-equal final params on every dp rank."""
        from ray_tpu.train import pipeline as tp

        cfg = _cfg()
        steps, batch, seq = 2, 8, 16
        data_fn = _data_fn(cfg, batch, seq, base_seed=35_000)
        module = LMStageModule(cfg, 2)

        joins = []
        real_join = tp._ProcGroup.join.__func__

        def counting_join(cls, key, world, mesh_fn):
            joins.append(key)
            return real_join(cls, key, world, mesh_fn)

        monkeypatch.setattr(tp._ProcGroup, "join",
                            classmethod(counting_join))

        runs = {}
        for inxla in (False, True):
            trainer = _trainer(
                tmp_path, module,
                _fast_pcfg(dp=2, zero1=True, use_inxla_collectives=inxla),
                data_fn, f"inxla_{inxla}")
            result = trainer.fit(steps, global_batch=batch, seq_len=seq)
            assert result.error is None
            runs[inxla] = (result, trainer)
        # the True run actually exercised the in-XLA group
        assert joins, "in-XLA path never joined a _ProcGroup"

        losses_ch = [m["loss"] for m in runs[False][0].metrics_history]
        losses_xla = [m["loss"] for m in runs[True][0].metrics_history]
        assert losses_ch == losses_xla
        all_ch = runs[False][1].final_state_all
        all_xla = runs[True][1].final_state_all
        assert set(all_ch) == set(all_xla)
        for key in all_ch:
            for path in all_ch[key]:
                np.testing.assert_array_equal(
                    all_ch[key][path], all_xla[key][path])


# ---------------------------------------------------------------------------
# Chaos: a SIGKILLed worker of a *sharded* gang still fail-fasts
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestShardedGangChaos:
    def test_killed_sharded_worker_fails_fast(self, tmp_path,
                                              ray_start_regular):
        """Same bounded fail-fast contract as the unsharded chaos test,
        but with per-stage SPMD meshes active (stage_mesh_axes=dp=2): the
        mesh adds no new hang paths."""
        from ray_tpu.util import chaos

        cfg = _cfg()
        data_fn = _data_fn(cfg, 8, 16, base_seed=37_000)
        module = LMStageModule(cfg, 2)
        pcfg = _fast_pcfg(
            stages_in_process=False, stage_mesh_axes="dp=2",
            recv_timeout_s=5.0, put_timeout_s=5.0, step_timeout_s=90.0)
        trainer = _trainer(tmp_path, module, pcfg, data_fn,
                           "chaos_sharded", max_failures=0)
        thread, box = _fit_in_thread(trainer, 50, 8, 16)
        _wait_for(lambda: len(trainer.worker_pids) == 2, 60,
                  "stage workers to spawn")
        victim = trainer.worker_pids[(1, 0)]
        t_kill = time.monotonic()
        chaos.kill_worker_host(victim)
        thread.join(timeout=120)
        assert not thread.is_alive(), "pipeline hung on a dead sharded gang"
        assert "raised" not in box, box.get("raised")
        result = box["result"]
        assert isinstance(result.error, TrainingFailedError)
        assert "pipeline training failed" in str(result.error)
        assert time.monotonic() - t_kill < 100
