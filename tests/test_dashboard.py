"""Dashboard-lite tests (reference: dashboard head + metrics module):
HTML status, state API over HTTP, Prometheus passthrough, Grafana export."""

import json
import os
import urllib.request

import pytest

import ray_tpu
from ray_tpu.dashboard import (
    build_dashboards,
    start_dashboard,
    stop_dashboard,
    write_grafana_dashboards,
)


@pytest.fixture
def dash(ray_start_regular):
    port = start_dashboard()
    yield port
    stop_dashboard()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return r.status, r.read()


class TestHTTP:
    def test_html_status_page(self, dash):
        status, body = _get(dash, "/")
        assert status == 200
        text = body.decode()
        assert "ray_tpu session" in text and "nodes" in text

    def test_state_api_json(self, dash):
        @ray_tpu.remote
        class Marker:
            def ping(self):
                return True

        a = Marker.options(name="dash_marker").remote()
        ray_tpu.get(a.ping.remote())
        status, body = _get(dash, "/api/v0/actors")
        assert status == 200
        actors = json.loads(body)
        assert any("dash_marker" in str(row) for row in actors)
        status, body = _get(dash, "/api/v0/summary")
        assert status == 200
        assert json.loads(body)["nodes_alive"] >= 1

    def test_metrics_passthrough(self, dash):
        status, body = _get(dash, "/metrics")
        assert status == 200
        assert b"ray_tpu_nodes" in body

    def test_unknown_resource_404(self, dash):
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(dash, "/api/v0/nope")
        assert ei.value.code == 404

    def test_trace_route_phase_breakdown(self, dash):
        import urllib.error

        from ray_tpu.util import tracing

        tracing.clear()
        with tracing.start_span("req") as root:
            with tracing.start_span("phase_a"):
                pass
            with tracing.start_span("phase_a"):
                pass
        status, body = _get(dash, f"/api/v0/traces/{root.trace_id}")
        assert status == 200
        payload = json.loads(body)
        assert payload["trace_id"] == root.trace_id
        assert payload["phases"]["phase_a"]["count"] == 2
        assert payload["phases"]["req"]["total_ms"] >= 0
        assert payload["spans"][0]["name"] == "req"
        # the wire form (X-Request-Id) resolves to the same trace
        _, body2 = _get(dash, f"/api/v0/traces/cmpl-{root.trace_id}")
        assert json.loads(body2)["trace_id"] == root.trace_id
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(dash, "/api/v0/traces/00000000deadbeef")
        assert ei.value.code == 404


class TestMetricsFederation:
    """Worker registries piggyback snapshots on heartbeat telemetry; the
    head's /metrics merges them tagged with node_id/role."""

    def test_worker_counter_reaches_head_metrics(self):
        import subprocess
        import sys
        import textwrap
        import time

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["RAY_TPU_WORKER_PROCESSES"] = "0"
        env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
        env["RAY_TPU_TELEMETRY_REPORT_PERIOD_S"] = "0.2"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

        ray_tpu.shutdown()
        rt = ray_tpu.init(
            num_cpus=1, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={rt._cp_server.address!r}, num_cpus=4,
                             num_tpus=0, resources={{"magic": 1.0}})
            w.wait(timeout=300)
        """)
        proc = subprocess.Popen(
            [sys.executable, "-c", code], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        port = None
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if len(rt.control_plane.alive_nodes()) >= 2:
                    break
                time.sleep(0.1)
            else:
                raise AssertionError("worker never joined")

            @ray_tpu.remote(resources={"magic": 1})
            def bump():
                from ray_tpu.core.metrics import Counter, registry

                c = registry.get("dash_fed_total")
                if c is None:
                    c = Counter("dash_fed_total", "worker-only counter")
                c.inc(3)
                return True

            assert ray_tpu.get(bump.remote(), timeout=60) is True
            port = start_dashboard(port=0)
            body = b""
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                _, body = _get(port, "/metrics")
                if b"dash_fed_total" in body:
                    break
                time.sleep(0.25)
            text = body.decode()
            assert "dash_fed_total" in text, "worker metric never federated"
            line = next(ln for ln in text.splitlines()
                        if ln.startswith("dash_fed_total{"))
            assert 'node_id="' in line and 'role="worker"' in line
            assert line.endswith(" 3.0")
            # the head never incremented it: only the tagged series exists
            assert "\ndash_fed_total " not in text
        finally:
            if port is not None:
                stop_dashboard()
            ray_tpu.shutdown()
            if proc.poll() is None:
                proc.kill()


class TestGrafana:
    def test_dashboards_reference_real_metrics(self):
        import ray_tpu.core.aggregator  # noqa: F401 — registers pod-aggregator metrics
        import ray_tpu.core.channels  # noqa: F401 — registers channel metrics
        import ray_tpu.core.cross_host  # noqa: F401 — registers metrics
        import ray_tpu.core.shard  # noqa: F401 — registers shard federation metrics
        import ray_tpu.core.memory_monitor  # noqa: F401 — registers metrics
        import ray_tpu.core.object_transfer  # noqa: F401 — registers metrics
        import ray_tpu.data.executor  # noqa: F401 — registers data metrics
        import ray_tpu.serve.disagg  # noqa: F401 — registers disagg metrics
        import ray_tpu.rl.online  # noqa: F401 — registers RL loop metrics
        import ray_tpu.serve.engine  # noqa: F401 — registers serve metrics
        import ray_tpu.train.pipeline  # noqa: F401 — registers pipeline metrics
        import ray_tpu.util.profiler  # noqa: F401 — registers profiler gauges
        from ray_tpu.core.metrics import registry

        known = set(registry._metrics)
        for name, dash in build_dashboards().items():
            for panel in dash["panels"]:
                for target in panel["targets"]:
                    expr = target["expr"]
                    base = [m for m in known if m in expr]
                    assert base, f"{name}/{panel['title']}: {expr} names no real metric"

    def test_write_provisioning_tree(self, tmp_path):
        written = write_grafana_dashboards(str(tmp_path / "grafana"))
        names = sorted(os.path.basename(p) for p in written)
        assert "provisioning.yaml" in names
        jsons = [p for p in written if p.endswith(".json")]
        assert len(jsons) == 11  # core, data, serve, disagg, health, profiling, objects, fleet, rl, federation, ingest
        for p in jsons:
            dash = json.load(open(p))
            assert dash["panels"], p


class TestJobREST:
    """Job submission over the dashboard's REST surface (reference:
    dashboard/modules/job HTTP routes): a client with NO runtime in its
    process drives submit/status/logs/stop against a running session."""

    def test_submit_status_logs_over_http(self, ray_start_regular):
        import sys

        from ray_tpu.dashboard import start_dashboard, stop_dashboard
        from ray_tpu.job_submission import JobSubmissionClient

        port = start_dashboard(port=0)
        try:
            url = f"http://127.0.0.1:{port}"
            client = JobSubmissionClient(address=url)  # REST mode
            job_id = client.submit_job(
                entrypoint=f"{sys.executable} -c \"print('rest job ran')\"")
            assert job_id.startswith("raytpu-job-")
            status = client.wait_until_finish(job_id, timeout_s=120)
            assert status == "SUCCEEDED"
            assert "rest job ran" in client.get_job_logs(job_id)
        finally:
            stop_dashboard()

    def test_stop_over_http(self, ray_start_regular):
        import sys

        from ray_tpu.dashboard import start_dashboard, stop_dashboard
        from ray_tpu.job_submission import JobSubmissionClient

        port = start_dashboard(port=0)
        try:
            client = JobSubmissionClient(address=f"http://127.0.0.1:{port}")
            job_id = client.submit_job(
                entrypoint=f"{sys.executable} -c \"import time; time.sleep(60)\"")
            assert client.stop_job(job_id) is True
            assert client.wait_until_finish(job_id, timeout_s=60) == "STOPPED"
        finally:
            stop_dashboard()
