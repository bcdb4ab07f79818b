"""Cluster profiling plane (util/profiler.py + the profile_start /
profile_fetch RPC surface): live stack dumps, sampling CPU profiles,
signal-driven subprocess dumps, the goodput ledger, auto-dump on health
alerts, and the bench history/regression ledger.

The acceptance test deliberately hangs a pool worker inside a named
function and stack-dumps it LIVE through both the dashboard HTTP API
and the `ray-tpu profile` CLI — the dump must name the function.
"""

import json
import os
import signal
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import flight_recorder, profiler

pytestmark = pytest.mark.profile


# -- module-level canaries: their NAMES are what the dumps must show --------

def _stuck_in_named_function(evt):
    evt.wait(120.0)


def _busy_spin(stop):
    x = 0
    while not stop.is_set():
        x += 1
    return x


def _child_canary_loop():
    t0 = time.time()
    while time.time() - t0 < 120.0:
        time.sleep(0.005)


def _child_entry(log_dir, ready_path):
    from ray_tpu.util import profiler as _p

    _p.install_child_handlers(log_dir)
    with open(ready_path, "w") as f:
        f.write(str(os.getpid()))
    _child_canary_loop()


def _hung_canary_fn(seconds):
    time.sleep(seconds)


@ray_tpu.remote
def _hang_task(pid_path, seconds):
    with open(pid_path, "w") as f:
        f.write(str(os.getpid()))
    _hung_canary_fn(seconds)
    return os.getpid()


# ---------------------------------------------------------------------------
# Live stack dumps (in-process)
# ---------------------------------------------------------------------------

class TestStackDumps:
    def test_dump_names_stuck_thread(self):
        evt = threading.Event()
        t = threading.Thread(target=_stuck_in_named_function, args=(evt,),
                             name="stuck-canary", daemon=True)
        t.start()
        try:
            time.sleep(0.05)
            dump = profiler.dump_stacks()
            assert dump["pid"] == os.getpid()
            by_name = {th["name"]: th for th in dump["threads"]}
            assert "stuck-canary" in by_name
            funcs = [fr["func"] for fr in by_name["stuck-canary"]["frames"]]
            assert "_stuck_in_named_function" in funcs
            text = profiler.format_stacks(dump)
            assert "stuck-canary" in text
            assert "_stuck_in_named_function" in text
        finally:
            evt.set()
            t.join(timeout=5)


# ---------------------------------------------------------------------------
# Sampling CPU profiler + collapsed-stack algebra
# ---------------------------------------------------------------------------

class TestSamplingProfiler:
    def test_sampler_catches_busy_function(self):
        stop = threading.Event()
        t = threading.Thread(target=_busy_spin, args=(stop,), daemon=True)
        p = profiler.SamplingProfiler(hz=200.0)
        t.start()
        try:
            p.start(duration_s=10.0)
            time.sleep(0.4)
        finally:
            collapsed = p.stop()
            stop.set()
            t.join(timeout=5)
        assert p.sample_count > 5
        assert any("_busy_spin" in stack for stack in collapsed)

    def test_process_singleton_start_fetch(self):
        stop = threading.Event()
        t = threading.Thread(target=_busy_spin, args=(stop,), daemon=True)
        t.start()
        try:
            out = profiler.start_profile(duration_s=10.0, hz=200.0)
            assert out["running"] and out["pid"] == os.getpid()
            # idempotent restart: a second start must not reset the window
            profiler.start_profile(duration_s=10.0, hz=200.0)
            time.sleep(0.3)
            f = profiler.fetch_profile(stop=True)
        finally:
            stop.set()
            t.join(timeout=5)
        assert f["samples"] > 0 and not f["running"]
        # the wire form is collapsed TEXT; parse_collapsed is its inverse
        collapsed = profiler.parse_collapsed(f["collapsed"])
        assert sum(collapsed.values()) > 0
        assert any("_busy_spin" in stack for stack in collapsed)

    def test_parse_and_merge_collapsed(self):
        text = "a;b 2\nc 1\n\na;b 1\n"
        assert profiler.parse_collapsed(text) == {"a;b": 3, "c": 1}
        merged = profiler.merge_collapsed({"a;b": 2}, {"a;b": 3, "c": 1}, {})
        assert merged == {"a;b": 5, "c": 1}


# ---------------------------------------------------------------------------
# Subprocess workers: signal-driven dump + profile toggle (no runtime)
# ---------------------------------------------------------------------------

class TestChildSignals:
    def test_dump_and_profile_a_live_subprocess(self, tmp_path):
        from ray_tpu.core.process_pool import _mp_context

        session = str(tmp_path / "session")
        log_dir = os.path.join(session, "logs")
        os.makedirs(log_dir, exist_ok=True)
        ready = str(tmp_path / "ready.txt")
        ctx = _mp_context()
        proc = ctx.Process(target=_child_entry, args=(log_dir, ready),
                           daemon=True)
        proc.start()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not os.path.exists(ready):
                time.sleep(0.05)
            assert os.path.exists(ready), "child never installed handlers"
            time.sleep(0.1)  # let it enter the canary loop

            # live stack dump: SIGUSR2 -> faulthandler append -> parent read
            text = profiler.dump_child(proc.pid, session, timeout_s=10.0)
            assert "_child_canary_loop" in text

            # sampling profile: SIGUSR1 start, SIGUSR1 stop + persist
            profiler.toggle_child_profile(proc.pid)
            time.sleep(0.5)
            prof = profiler.read_child_profile(proc.pid, session,
                                               timeout_s=10.0)
            collapsed = profiler.parse_collapsed(
                "\n".join(l for l in prof.splitlines()
                          if not l.startswith("#")))
            assert sum(collapsed.values()) > 0
            assert any("_child_canary_loop" in s for s in collapsed)
        finally:
            proc.terminate()
            proc.join(timeout=10)


# ---------------------------------------------------------------------------
# Goodput / MFU ledger
# ---------------------------------------------------------------------------

class TestGoodputLedger:
    def test_components_partition_wall_exactly(self):
        led = profiler.goodput_ledger(10.0, data_stall_s=2.0,
                                      channel_wait_s=1.0,
                                      bubble_fraction=0.1, migration_s=0.5)
        total = sum(led[c] for c in profiler.LEDGER_COMPONENTS)
        assert total == pytest.approx(led["wall_seconds"], abs=1e-9)
        assert led["compute"] == pytest.approx(5.5)
        assert led["goodput_fraction"] == pytest.approx(0.55)
        assert led["overcommit_seconds"] == 0.0

    def test_overcommitted_stalls_scale_down(self):
        # concurrent stalls measured on separate threads exceed wall time:
        # the ledger scales them into a partition and reports the excess
        led = profiler.goodput_ledger(2.0, data_stall_s=6.0,
                                      channel_wait_s=4.0)
        total = sum(led[c] for c in profiler.LEDGER_COMPONENTS)
        assert total == pytest.approx(2.0, abs=1e-9)
        assert led["compute"] == pytest.approx(0.0)
        assert led["overcommit_seconds"] == pytest.approx(8.0)
        # proportions survive the scale-down
        assert led["data_stall"] == pytest.approx(1.2)
        assert led["channel_wait"] == pytest.approx(0.8)

    def test_ledger_from_metric_families(self):
        fams = [
            {"name": "train_stage_step_seconds", "samples": [
                ("train_stage_step_seconds", [("stage", "0")], 4.0),
                ("train_stage_step_seconds", [("stage", "1")], 6.0)]},
            {"name": "data_stage_stall_seconds", "samples": [
                ("data_stage_stall_seconds", [], 1.0)]},
            {"name": "channel_recv_wait_seconds", "samples": [
                ("channel_recv_wait_seconds_sum", [], 0.5),
                ("channel_recv_wait_seconds_count", [], 7.0)]},
            {"name": "train_pipeline_bubble_fraction", "samples": [
                ("train_pipeline_bubble_fraction", [], 0.2),
                ("train_pipeline_bubble_fraction", [], 0.4)]},
        ]
        led = profiler.ledger_from_samples(fams)
        # wall defaults to the busiest stage (stages run concurrently)
        assert led["wall_seconds"] == pytest.approx(6.0)
        assert led["data_stall"] == pytest.approx(1.0)
        assert led["channel_wait"] == pytest.approx(0.5)  # _sum only
        assert led["bubble"] == pytest.approx(0.3 * 6.0)  # mean fraction
        assert led["compute"] == pytest.approx(6.0 - 1.0 - 0.5 - 1.8)
        total = sum(led[c] for c in profiler.LEDGER_COMPONENTS)
        assert total == pytest.approx(6.0, abs=1e-9)


# ---------------------------------------------------------------------------
# Host CPU / RSS / device-memory gauges
# ---------------------------------------------------------------------------

class TestResourceGauges:
    def test_update_resource_gauges(self):
        row = profiler.update_resource_gauges()
        assert row["process_rss_bytes"] > 0
        assert 0.0 <= row["host_cpu_used_fraction"] <= 1.0
        from ray_tpu.core.metrics import registry

        names = {fam["name"] for fam in registry.snapshot()}
        assert {"host_cpu_used_fraction", "process_rss_bytes"} <= names

    def test_device_memory_snapshot_counts_live_arrays(self):
        import jax.numpy as jnp

        keep = jnp.ones((256,), dtype=jnp.float32)
        snap = profiler.device_memory_snapshot()
        assert snap["pid"] == os.getpid()
        assert snap["live_arrays"] >= 1
        assert snap["live_bytes"] >= keep.nbytes
        del keep


# ---------------------------------------------------------------------------
# Health-plane loop closure: auto stack dump on a firing stall alert
# ---------------------------------------------------------------------------

class TestAutoDump:
    def test_stall_alert_triggers_stack_dump_postmortem(self):
        from ray_tpu.core.health import HealthPlane, Rule

        stall = {"v": 0.0}

        def metrics_fn():
            return [("data_stage_stall_seconds", {"stage": "tokenize"},
                     stall["v"])]

        plane = HealthPlane(
            rules=[Rule("data_stall_rising",
                        "delta(data_stage_stall_seconds) > 1.0 for 2",
                        group_by=("stage",))],
            metrics_fn=metrics_fn, digests_fn=lambda: [], period_s=60.0)
        assert profiler.install_auto_dump(plane) is True
        flight_recorder.drain_postmortems()  # isolate from other tests

        # delta() needs a baseline pass, then two consecutive breaches
        for v in (0.0, 5.0, 10.0):
            stall["v"] = v
            active = plane.evaluate()
        assert any(a["rule"] == "data_stall_rising" for a in active)

        arts = flight_recorder.drain_postmortems()
        dumps = [a for a in arts
                 if a.get("cause") == "auto_dump:data_stall_rising"]
        assert dumps, f"no auto-dump artifact in {[a.get('cause') for a in arts]}"
        art = dumps[0]
        assert art["pid"] == os.getpid()
        assert art["alert"]["labels"].get("stage") == "tokenize"
        # the dump body is this process's all-threads traceback
        assert any("MainThread" in line or "Thread" in line
                   for line in art["stack_dump"])

    def test_auto_dump_respects_config_gate(self, monkeypatch):
        from ray_tpu.core.config import config
        from ray_tpu.core.health import HealthPlane

        monkeypatch.setattr(config, "profiler_auto_dump", False)
        plane = HealthPlane(rules=[], metrics_fn=lambda: [],
                            digests_fn=lambda: [], period_s=60.0)
        assert profiler.install_auto_dump(plane) is False


# ---------------------------------------------------------------------------
# status()/summary() surfacing
# ---------------------------------------------------------------------------

class TestStatusSurfacing:
    def test_summary_has_utilization(self, ray_start_regular):
        from ray_tpu.util.state import summary

        payload = summary()
        util = payload.get("utilization", {})
        assert util, "summary() lost its utilization section"
        head = util.get("head") or next(iter(util.values()))
        assert head.get("rss_bytes", 0) > 0

    def test_health_payload_has_profiling_sections(self):
        from ray_tpu.core.health import HealthPlane

        plane = HealthPlane(rules=[], metrics_fn=lambda: [],
                            digests_fn=lambda: [], period_s=60.0)
        payload = plane.payload()
        assert "utilization" in payload
        assert "goodput" in payload


# ---------------------------------------------------------------------------
# ACCEPTANCE: stack-dump a deliberately-hung pool worker, live, via both
# the dashboard HTTP API and the `ray-tpu profile` CLI
# ---------------------------------------------------------------------------

class TestHungWorkerE2E:
    def test_hung_pool_worker_dumped_via_http_and_cli(
            self, ray_start_regular, tmp_path, capsys):
        from ray_tpu.dashboard import start_dashboard, stop_dashboard
        from ray_tpu import scripts

        rt = ray_start_regular
        pid_path = str(tmp_path / "hung_pid.txt")
        ref = _hang_task.options(max_retries=0).remote(pid_path, 600.0)

        # the worker reports its own pid, then wedges in _hung_canary_fn
        deadline = time.monotonic() + 120
        pid = 0
        while time.monotonic() < deadline and not pid:
            try:
                with open(pid_path) as f:
                    pid = int(f.read().strip() or 0)
            except (OSError, ValueError):
                pass
            if not pid:
                time.sleep(0.05)
        assert pid and pid != os.getpid(), "hang task never reached a pool worker"
        time.sleep(0.2)  # let it enter the canary sleep

        # resolve which (virtual) node's agent can profile that pid
        node_hex = ""
        while time.monotonic() < deadline and not node_hex:
            with rt._lock:
                agents = dict(rt.agents)
            for nid, agent in agents.items():
                try:
                    pids = agent.profilable_pids()
                except Exception:
                    continue
                if pid in pids.get("pool", []):
                    node_hex = nid.hex()
                    break
            if not node_hex:
                time.sleep(0.1)
        assert node_hex, "no agent lists the hung worker as profilable"

        port = start_dashboard(port=0)
        try:
            url = (f"http://127.0.0.1:{port}/api/v0/profile/"
                   f"{node_hex[:12]}/{pid}?kind=stack")
            with urllib.request.urlopen(url, timeout=60) as r:
                out = json.loads(r.read())
            assert out.get("pid") == pid and out.get("kind") == "stack"
            assert "_hung_canary_fn" in out.get("text", ""), out

            # same dump through the CLI (in-process runtime path)
            assert scripts.main(
                ["profile", node_hex[:12], str(pid), "--kind", "stack"]) == 0
            cli_out = capsys.readouterr().out
            assert "_hung_canary_fn" in cli_out
        finally:
            stop_dashboard()
            os.kill(pid, signal.SIGKILL)
        # max_retries=0: the crash surfaces instead of rescheduling the hang
        with pytest.raises(Exception):
            ray_tpu.get(ref)

    def test_pids_listing_and_bad_node_prefix(self, ray_start_regular):
        from ray_tpu.core import core_worker
        from ray_tpu.core.cross_host import HeadService

        svc = HeadService(core_worker.get_runtime())
        pids = svc.profile_fetch(node="", kind="pids")
        assert pids["agent"] == os.getpid()
        with pytest.raises(KeyError):
            svc.profile_fetch(node="zzzz-no-such-node", kind="pids")
