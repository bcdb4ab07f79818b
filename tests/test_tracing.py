"""Distributed tracing (§5 aux; reference:
`python/ray/util/tracing/tracing_helper.py`): span context injected at
.remote() and extracted around user-function execution, so one trace id
covers the whole causality chain — driver span -> task execute -> nested
task execute — across the task plane."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu.util import tracing


@pytest.fixture
def rt():
    # worker_processes=0: tasks execute on threads in THIS process, so
    # the per-process span buffer sees the whole chain (pool workers
    # record their execute spans in their own processes)
    r = ray_tpu.init(num_cpus=4, num_tpus=0,
                     system_config={"worker_processes": 0})
    tracing.clear()
    yield r
    tracing.clear()
    ray_tpu.shutdown()


class TestTracing:
    def test_local_span_nesting(self, rt):
        with tracing.start_span("outer", {"k": 1}) as outer:
            with tracing.start_span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        spans = tracing.get_spans(outer.trace_id)
        assert {s["name"] for s in spans} == {"outer", "inner"}
        assert all(s["end_us"] is not None for s in spans)

    def test_task_execution_joins_the_trace(self, rt):
        @ray_tpu.remote
        def work(x):
            return x + 1

        with tracing.start_span("request") as root:
            assert ray_tpu.get(work.remote(1), timeout=30) == 2
        spans = tracing.get_spans(root.trace_id)
        execs = [s for s in spans if s["name"].startswith("execute:")]
        assert len(execs) == 1
        assert execs[0]["parent_id"] == root.span_id
        assert execs[0]["attrs"]["kind"] == "normal"

    def test_nested_submission_chains(self, rt):
        @ray_tpu.remote
        def child():
            return "leaf"

        @ray_tpu.remote
        def parent():
            # submitted while the parent's execute span is current
            return ray_tpu.get(child.remote(), timeout=30)

        with tracing.start_span("root") as root:
            assert ray_tpu.get(parent.remote(), timeout=30) == "leaf"
        spans = tracing.get_spans(root.trace_id)
        p = next(s for s in spans if s["name"].endswith(".parent"))
        c = next(s for s in spans if s["name"].endswith(".child"))
        assert p["parent_id"] == root.span_id
        assert c["parent_id"] == p["span_id"]  # three-deep causality chain

    def test_actor_calls_join_the_trace(self, rt):
        @ray_tpu.remote(in_process=True)
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        ray_tpu.get(c.bump.remote(), timeout=30)  # untraced warm call
        with tracing.start_span("actor-req") as root:
            assert ray_tpu.get(c.bump.remote(), timeout=30) == 2
        spans = tracing.get_spans(root.trace_id)
        execs = [s for s in spans if s["name"] == "execute:Counter.bump"]
        assert len(execs) == 1
        assert execs[0]["parent_id"] == root.span_id

    def test_untraced_submission_has_no_ctx(self, rt):
        @ray_tpu.remote
        def plain():
            return 1

        before = len(tracing.get_spans())
        assert ray_tpu.get(plain.remote(), timeout=30) == 1
        assert len(tracing.get_spans()) == before  # zero-overhead path

    def test_export_to_timeline(self, rt):
        @ray_tpu.remote
        def t():
            return 0

        with tracing.start_span("exported"):
            ray_tpu.get(t.remote(), timeout=30)
        assert tracing.export_to_timeline() >= 2

    def test_get_trace_returns_sorted_tree(self, rt):
        with tracing.start_span("root") as root:
            with tracing.start_span("second-started"):
                time.sleep(0.002)
            with tracing.start_span("third-started"):
                pass
        tree = tracing.get_trace(root.trace_id)
        assert len(tree) == 1 and tree[0]["name"] == "root"
        kids = tree[0]["children"]
        assert [k["name"] for k in kids] == ["second-started",
                                             "third-started"]
        assert kids[0]["start_us"] <= kids[1]["start_us"]
        # a unique prefix resolves too (X-Request-Id embeds the full id,
        # dashboards may hold a truncation)
        assert tracing.get_trace(root.trace_id[:12]) == tree

    def test_remote_call_span_parents_across_processes(self, rt):
        """The explicit cross-process assertion: a `.remote()` call into a
        child-process actor yields an execute span recorded in ANOTHER
        process that parents under the submitting span (the child flushes
        its spans back on the call reply)."""

        @ray_tpu.remote
        class W:
            def pid(self):
                return os.getpid()

        a = W.remote()
        child_pid = ray_tpu.get(a.pid.remote(), timeout=60)
        assert child_pid != os.getpid()  # really a separate process
        with tracing.start_span("xproc") as root:
            ray_tpu.get(a.pid.remote(), timeout=60)
        spans = tracing.get_spans(root.trace_id)
        execs = [s for s in spans if s["name"] == "execute:W.pid"]
        assert len(execs) == 1
        assert execs[0]["parent_id"] == root.span_id
        child = [s for s in spans if s["name"] == "actor_exec:pid"]
        assert len(child) == 1
        assert child[0]["pid"] == child_pid
        assert child[0]["parent_id"] == execs[0]["span_id"]
        # and the tree view chains all three levels
        tree = tracing.get_trace(root.trace_id)
        assert tree[0]["children"][0]["children"][0]["name"] == \
            "actor_exec:pid"


# --------------------------------------------------------------------------
# telemetry federation: worker span/timeline buffers flush to the head
# --------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_WORKER_PROCESSES"] = "0"
    env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
    env["RAY_TPU_TELEMETRY_REPORT_PERIOD_S"] = "0.2"  # fast federation
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


class TestFederation:
    @pytest.fixture
    def fed_cluster(self):
        rt = ray_tpu.init(
            num_cpus=1, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        tracing.clear()
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={rt._cp_server.address!r}, num_cpus=4,
                             num_tpus=0, resources={{"magic": 1.0}})
            w.wait(timeout=300)
        """)
        proc = subprocess.Popen(
            [sys.executable, "-c", code], env=_worker_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if len(rt.control_plane.alive_nodes()) >= 2:
                break
            time.sleep(0.1)
        else:
            proc.kill()
            ray_tpu.shutdown()
            raise AssertionError("worker never joined")
        try:
            yield rt
        finally:
            tracing.clear()
            ray_tpu.shutdown()
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()

    def test_worker_spans_and_timeline_reach_head(self, fed_cluster, tmp_path):
        """A task traced on the head but executed on a joined worker HOST:
        its execute span arrives at the head via heartbeat telemetry,
        parented under the submitting span, and the worker's timeline
        events land in a per-node lane of the merged export."""

        @ray_tpu.remote(resources={"magic": 1})
        def over_there():
            import os as _os

            from ray_tpu.util import timeline
            with timeline.span("worker-side-step"):
                pass
            return _os.getpid()

        with tracing.start_span("fed-root") as root:
            worker_pid = ray_tpu.get(over_there.remote(), timeout=60)
        assert worker_pid != os.getpid()

        execs = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            spans = tracing.get_spans(root.trace_id)
            execs = [s for s in spans if s["name"].startswith("execute:")]
            if execs:
                break
            time.sleep(0.25)
        assert execs, "worker execute span never federated to the head"
        assert execs[0]["parent_id"] == root.span_id
        assert execs[0]["pid"] == worker_pid  # recorded in the worker

        # merged timeline: the worker's explicit span shows up under a
        # node lane ('<node>/<pid>'), alongside head-local events
        path = str(tmp_path / "merged.json")
        deadline = time.monotonic() + 30
        lane_events = []
        while time.monotonic() < deadline:
            import json

            ray_tpu.timeline(path)
            events = json.load(open(path))["traceEvents"]
            lane_events = [e for e in events
                           if e.get("name") == "worker-side-step"
                           and "/" in str(e.get("pid", ""))]
            if lane_events:
                break
            time.sleep(0.25)
        assert lane_events, "worker timeline event never federated"
        assert len({str(e.get("pid")) for e in events}) >= 2
