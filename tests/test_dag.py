"""Compiled graphs (P6; reference: python/ray/dag + experimental/channel):
bind-once, execute-repeatedly actor pipelines over channels."""

import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode


@pytest.fixture
def rt():
    r = ray_tpu.init(num_cpus=8, num_tpus=0)
    yield r
    ray_tpu.shutdown()


class TestCompiledDag:
    def test_two_stage_pipeline(self, rt):
        @ray_tpu.remote
        class Doubler:
            def process(self, x):
                return x * 2

        @ray_tpu.remote
        class AddOne:
            def process(self, x):
                return x + 1

        a, b = Doubler.remote(), AddOne.remote()
        with InputNode() as inp:
            mid = a.process.bind(inp)
            out = b.process.bind(mid)
        dag = out.experimental_compile()
        assert dag.execute(5).get() == 11
        # repeated executions stream through the same compiled graph
        refs = [dag.execute(i) for i in range(10)]
        assert [r.get() for r in refs] == [i * 2 + 1 for i in range(10)]

    def test_stages_pipeline_concurrently(self, rt):
        @ray_tpu.remote
        class Slow:
            def work(self, x):
                time.sleep(0.05)
                return x

        a, b = Slow.remote(), Slow.remote()
        with InputNode() as inp:
            out = b.work.bind(a.work.bind(inp))
        dag = out.experimental_compile()
        dag.execute(0).get()  # warm both lanes
        t0 = time.monotonic()
        refs = [dag.execute(i) for i in range(8)]
        assert [r.get() for r in refs] == list(range(8))
        wall = time.monotonic() - t0
        # two pipelined 50ms stages over 8 items: ~(8+1)*50ms, not 8*100ms
        assert wall < 0.75, f"stages did not overlap: {wall:.2f}s"

    def test_user_error_propagates_to_get(self, rt):
        @ray_tpu.remote
        class Boom:
            def go(self, x):
                raise ValueError("kaput")

        @ray_tpu.remote
        class After:
            def go(self, x):
                return x

        a, b = Boom.remote(), After.remote()
        with InputNode() as inp:
            out = b.go.bind(a.go.bind(inp))
        dag = out.experimental_compile()
        with pytest.raises(ValueError, match="kaput"):
            dag.execute(1).get()
        # the graph survives an error: next execution still works
        ref = dag.execute(2)
        with pytest.raises(ValueError):
            ref.get()

    def test_actor_stays_usable_for_normal_calls(self, rt):
        @ray_tpu.remote(max_concurrency=2)
        class Dual:
            def process(self, x):
                return x * 10

            def ping(self):
                return "pong"

        a = Dual.remote()
        with InputNode() as inp:
            out = a.process.bind(inp)
        dag = out.experimental_compile()
        assert dag.execute(3).get() == 30
        assert ray_tpu.get(a.ping.remote()) == "pong"
        assert dag.execute(4).get() == 40

    def test_refs_resolve_correctly_out_of_order(self, rt):
        # envelope routing: each ref gets ITS execution's result even when
        # consumed out of submission order or completed out of order
        @ray_tpu.remote(max_concurrency=4)
        class Jittery:
            def work(self, x):
                time.sleep(0.02 if x % 2 == 0 else 0.001)
                return x * 3

        a = Jittery.remote()
        with InputNode() as inp:
            out = a.work.bind(inp)
        dag = out.experimental_compile()
        refs = [dag.execute(i) for i in range(8)]
        # consume in reverse submission order
        for i in reversed(range(8)):
            assert refs[i].get() == i * 3


class TestCrossHostDag:
    """VERDICT r4 #8 done-criterion: a compiled-graph pipeline SPANNING
    TWO RUNTIMES (head + joined OS process) with channels over the
    distributed channel plane (core/channels.py), results matching the
    local run. Reference: experimental/channel cross-node transport under
    dag/compiled_dag_node.py."""

    def test_pipeline_spans_two_runtimes(self):
        import os
        import subprocess
        import sys
        import textwrap
        import time as _time

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = ray_tpu.init(
            num_cpus=2, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["RAY_TPU_WORKER_PROCESSES"] = "0"
        env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={r._cp_server.address!r}, num_cpus=2,
                             num_tpus=0, resources={{"dag_host": 1.0}})
            w.wait(timeout=300)
        """)
        proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        try:
            deadline = _time.monotonic() + 60
            while _time.monotonic() < deadline:
                if any("dag_host" in n.resources_total
                       for n in r.control_plane.alive_nodes()):
                    break
                _time.sleep(0.1)

            @ray_tpu.remote(num_cpus=0, in_process=True)
            class Stage:
                def __init__(self, k, tag):
                    self.k = k
                    self.tag = tag

                def process(self, x):
                    return {"v": (x if isinstance(x, int) else x["v"]) + self.k,
                            "pids": ([] if isinstance(x, int) else x["pids"])
                            + [(self.tag, os.getpid())]}

            # stage A on the HEAD, stage B on the JOINED host
            a = Stage.options(num_cpus=0.1).remote(1, "a")
            b = Stage.options(resources={"dag_host": 0.5}).remote(10, "b")
            with InputNode() as inp:
                mid = a.process.bind(inp)
                out = b.process.bind(mid)
            dag = out.experimental_compile()

            results = [dag.execute(i).get(timeout=60) for i in range(6)]
            for i, res in enumerate(results):
                assert res["v"] == i + 11, res  # same math as a local run
                tags = [t for t, _ in res["pids"]]
                assert tags == ["a", "b"]
                pids = dict(res["pids"])
                assert pids["a"] == os.getpid()
                assert pids["b"] == proc.pid  # stage B really ran remotely

            # pipelined executes keep envelope->ref routing intact
            refs = [dag.execute(100 + i) for i in range(5)]
            vals = [ref.get(timeout=60)["v"] for ref in refs]
            assert vals == [111 + i for i in range(5)]
        finally:
            ray_tpu.shutdown()
            if proc.poll() is None:
                proc.kill()
