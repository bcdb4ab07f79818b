"""What chip_smoke.py decides, on the CPU: its checks on hand-made numbers,
its last line and exit code, where the compile cache goes, and that the
runtime's child processes can never open the chip.
"""

import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


def _top2(margins):
    """[n, 2] reference logits whose top-1 leads top-2 by `margins`."""
    margins = np.asarray(margins, np.float32)
    return np.stack([5.0 + margins, np.full_like(margins, 5.0)], axis=1)


class TestMarginCheck:
    def test_agreeing_tokens_pass_and_are_counted(self):
        out = cs.check_margin(_top2([1.0, 0.1, 0.3]), [7, 8, 9], [7, 8, 9])
        assert out == {"positions": 3, "confident": 2}

    def test_disagreement_below_the_margin_is_allowed(self):
        out = cs.check_margin(_top2([1.0, 0.1]), [7, 8], [7, 99])
        assert out == {"positions": 2, "confident": 1}

    def test_disagreement_above_the_margin_fails(self):
        with pytest.raises(cs.SmokeFailure, match="not the reference's argmax"):
            cs.check_margin(_top2([1.0, 0.26]), [7, 8], [7, 99])

    def test_margin_is_strict(self):
        # exactly MARGIN is not "exceeds": the token is free to differ
        cs.check_margin(_top2([cs.MARGIN]), [7], [99])

    @pytest.mark.parametrize("confident,ok", [(48, True), (47, False), (0, False)])
    def test_vacuous_when_under_three_quarters_confident(self, confident, ok):
        counts = [{"positions": 64, "confident": confident}]
        if ok:
            assert cs.check_not_vacuous(counts)["confident_share"] == 0.75
        else:
            with pytest.raises(cs.SmokeFailure, match="vacuous"):
                cs.check_not_vacuous(counts)

    def test_no_positions_is_vacuous(self):
        with pytest.raises(cs.SmokeFailure, match="vacuous"):
            cs.check_not_vacuous([])


class TestLossCheck:
    GOOD = [11.9, 11.8, 11.0, 9.0, 7.0, 6.0, 5.5, 5.0]

    def test_falling_loss_from_the_expected_start_passes(self):
        out = cs.check_losses(self.GOOD, 11.76)
        assert out["mean_last_two"] == pytest.approx(5.25)

    @pytest.mark.parametrize("losses,match", [
        ([11.9, 11.8, float("nan"), 5.0], "not finite"),
        ([11.9, 11.8, float("inf"), 5.0], "not finite"),
        ([12.3] + GOOD[1:], "first loss"),
        ([11.2] + GOOD[1:], "first loss"),
        ([11.9, 11.8, 11.7, 11.6, 11.5, 11.5], "did not fall"),
        ([], "missing"),
    ])
    def test_bad_losses_fail(self, losses, match):
        with pytest.raises(cs.SmokeFailure, match=match):
            cs.check_losses(losses, 11.76)

    def test_expected_first_loss_is_the_issues_constant(self):
        assert cs.Plan().expected_first_loss() == pytest.approx(math.log(128256))
        assert round(cs.Plan().expected_first_loss(), 2) == 11.76


class TestOtherChecks:
    def test_kernel_tolerance(self):
        ref = np.linspace(-1, 1, 64, dtype=np.float32)
        assert cs.check_kernel("k", ref + 0.01, ref) == pytest.approx(0.01, rel=1e-3)
        with pytest.raises(cs.SmokeFailure, match="relative error"):
            cs.check_kernel("k", ref + 0.03, ref)
        with pytest.raises(cs.SmokeFailure, match="relative error"):
            cs.check_kernel("k", ref * np.nan, ref)

    def test_fsdp_losses_agree_per_step(self):
        assert cs.check_fsdp_losses([3.0, 2.0], [3.01, 1.99]) == pytest.approx(0.01)
        with pytest.raises(cs.SmokeFailure, match="disagree"):
            cs.check_fsdp_losses([3.0, 2.0], [3.0, 2.05])
        with pytest.raises(cs.SmokeFailure, match="step counts"):
            cs.check_fsdp_losses([3.0, 2.0], [3.0])

    def test_everything_on_the_first_device_fails(self):
        assert cs.check_bytes_spread("x", [100, 110, 105, 120]) == pytest.approx(1.2)
        with pytest.raises(cs.SmokeFailure, match="spread"):
            cs.check_bytes_spread("x", [400, 100, 100, 100])
        with pytest.raises(cs.SmokeFailure, match="holds nothing"):
            cs.check_bytes_spread("x", [400, 0, 0, 0])

    def test_block_until_ready_that_returned_early_fails(self):
        cs.check_readback(step_s=1.0, readback_s=0.0004)
        with pytest.raises(cs.SmokeFailure, match="returned early"):
            cs.check_readback(step_s=1.0, readback_s=0.9)

    def test_the_plan_is_the_published_widths_at_reduced_depth(self):
        cfg = cs.Plan().cfg()
        assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hdim, cfg.d_ff,
                cfg.vocab_size, cfg.rope_theta) == (
            4096, 32, 8, 128, 14336, 128256, 500000.0)
        assert cfg.n_layers == cs.N_LAYERS

    def test_prompts_take_both_prefill_paths_and_are_seeded(self):
        from ray_tpu.serve.engine import EngineConfig

        plan = cs.Plan()
        chunk = EngineConfig(**plan.engine).prefill_chunk
        lens = [len(p) for p in plan.prompts()]
        assert min(lens) == 32 and max(lens) == 1500 and len(lens) == 8
        assert any(n <= chunk for n in lens) and any(n > chunk for n in lens)
        assert plan.prompts() == cs.Plan().prompts()
        assert plan.prompts() != cs.Plan(seed=1).prompts()
        assert plan.corpus().shape == (4, 2049)


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def quiet_main(monkeypatch):
    """main() without its process-wide side effects: the persistent cache
    directory, the compile listeners, and adopting, stopping and killing
    the children of the process it runs in (here pytest's)."""
    import ray_tpu.util.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "/unused")
    monkeypatch.setattr(cs, "_watch_compiles", lambda: None)
    monkeypatch.setattr(cs, "adopt_orphans", lambda: None)
    monkeypatch.setattr(cs, "stop_children",
                        lambda: {"alive_after_shutdown": []})


class TestLastLineAndExitCode:
    def test_last_line_format(self):
        assert cs.last_line(True, TPU) == (
            '{"ok": true, "device": {"platform": "tpu", '
            '"kind": "TPU v5 lite", "count": 1}}')

    def test_device_phase_refuses_a_cpu_backend(self):
        with pytest.raises(cs.SmokeFailure, match="no TPU"):
            cs.phase_device(1)

    def test_without_a_chip_main_fails_before_any_model_code(
            self, capsys, monkeypatch, quiet_main):
        def never(*_a, **_k):
            raise AssertionError("model code ran without a chip")

        monkeypatch.setattr(cs, "phase_kernels", never)
        monkeypatch.setattr(cs, "run_one_chip", never)
        assert cs.main([]) == 1
        last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert last["ok"] is False and last["device"] is None
        assert "no TPU" in last["error"]

    def test_a_raising_phase_gives_exit_1_and_ok_false(
            self, capsys, monkeypatch, quiet_main):
        def boom(_plan):
            raise RuntimeError("kernel exploded")

        monkeypatch.setattr(cs, "phase_device", lambda chips: TPU)
        monkeypatch.setattr(cs, "phase_kernels", boom)
        monkeypatch.setattr(cs, "run_one_chip", lambda plan: None)
        assert cs.main([]) == 1
        last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert last["ok"] is False and last["device"] == TPU
        assert "kernel exploded" in last["error"]

    @pytest.mark.parametrize("argv,ran", [
        ([], ["kernels", "one"]), (["--chips", "4"], ["four"])])
    def test_passing_run_prints_exactly_the_contract_line(
            self, capsys, monkeypatch, quiet_main, argv, ran):
        calls = []
        device = dict(TPU, count=4 if argv else 1)
        monkeypatch.setattr(cs, "phase_device", lambda chips: device)
        monkeypatch.setattr(cs, "phase_kernels", lambda p: calls.append("kernels"))
        monkeypatch.setattr(cs, "run_one_chip", lambda p: calls.append("one"))
        monkeypatch.setattr(cs, "run_four_chips", lambda p: calls.append("four"))
        assert cs.main(argv) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert calls == ran  # --chips 4 runs that path and no other phase
        assert lines[-1] == cs.last_line(True, device)
        assert json.loads(lines[-1]) == {"ok": True, "device": device}
        assert any(json.loads(x).get("reduced") == "n_layers 32 -> 8"
                   for x in lines[:-1])


class TestCompileCache:
    def test_a_set_directory_is_left_alone(self, monkeypatch):
        import jax

        from ray_tpu.util.compile_cache import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_it_is_the_checkouts_fixed_directory(self, monkeypatch):
        import jax

        from ray_tpu.util.compile_cache import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            path = enable_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert enable_compile_cache() == path  # fixed: same every call
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def test_pool_worker_and_isolated_actor_are_pinned_to_the_cpu():
    """A fresh runtime whose own environment names the TPU (as the process
    that owns the chip may): its forkserver children must still see
    JAX_PLATFORMS=cpu before any user code runs."""
    prog = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "tpu"
        import ray_tpu
        ray_tpu.init(num_cpus=2, num_tpus=0,
                     system_config={"worker_processes": 1})

        @ray_tpu.remote
        def task_env():
            return os.environ.get("JAX_PLATFORMS"), os.getpid()

        @ray_tpu.remote
        class Probe:
            def env(self):
                return os.environ.get("JAX_PLATFORMS"), os.getpid()

        probe = Probe.options(in_process=False).remote()
        t, a = ray_tpu.get(task_env.remote()), ray_tpu.get(probe.env.remote())
        print("RESULT", t[0], a[0], t[1] != os.getpid(), a[1] != os.getpid())
        ray_tpu.shutdown()
    """)
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", prog], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = [x for x in proc.stdout.splitlines() if x.startswith("RESULT")]
    assert result == ["RESULT cpu cpu True True"], proc.stdout + proc.stderr[-2000:]


def _session_members(sid):
    out = subprocess.run(["ps", "-eo", "sid=,pid=,args="], capture_output=True,
                         text=True, check=True).stdout
    return [line for line in out.splitlines() if line.split()[0] == str(sid)]


def _run_in_own_session(prog):
    """-> (returncode, stdout, stderr tail, what is left of its session the
    moment it has exited) — what the driver looks at after chip_smoke.py."""
    proc = subprocess.Popen([sys.executable, "-c", textwrap.dedent(prog)],
                            env=dict(os.environ, PYTHONPATH=REPO), text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            start_new_session=True)
    out, err = proc.communicate(timeout=120)
    return proc.returncode, out, err[-2000:], _session_members(proc.pid)


def test_stop_children_leaves_nothing_of_the_runtime_running():
    """ray_tpu.shutdown() leaves the pool's forkserver and the resource
    tracker alive; stop_children ends both, so nothing of the run is left
    the moment the process exits."""
    rc, out, err, left = _run_in_own_session("""
        import chip_smoke as cs
        import ray_tpu
        cs.adopt_orphans()
        ray_tpu.init(num_cpus=2, num_tpus=0,
                     system_config={"worker_processes": 1})

        @ray_tpu.remote
        def one():
            return 1

        assert ray_tpu.get(one.remote()) == 1
        ray_tpu.shutdown()
        before = sorted(cs._children().values())
        report = cs.stop_children()
        print("RESULT", len(before), report["alive_after_shutdown"] == before,
              cs._children())
    """)
    assert rc == 0, err
    assert "RESULT 2 True {}" in out, out + err
    assert left == []


def test_stop_children_kills_and_reports_an_orphan_it_did_not_expect():
    rc, out, err, left = _run_in_own_session("""
        import subprocess
        import chip_smoke as cs
        cs.adopt_orphans()
        cs.EXIT_GRACE_S = 0.5
        # the shell exits at once; its `sleep` falls to this process
        subprocess.run(["sh", "-c", "sleep 300 & exit 0"], check=True)
        try:
            cs.stop_children()
        except cs.SmokeFailure as e:
            print("RESULT", "sleep" in str(e), cs._children())
    """)
    assert rc == 0, err
    assert "RESULT True {}" in out, out + err
    assert left == []

