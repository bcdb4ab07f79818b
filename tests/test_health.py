"""SLO health plane: digests, alert rules, routing health, postmortems.

Covers the four layers of the plane end to end at unit scope — the
streaming quantile sketches (util/slo.py), the head-side rule engine
(core/health.py HealthPlane), client-side routing health (ReplicaHealth +
Pow2Router quarantine), the telemetry byte budget and DEAD/stale snapshot
eviction (core/cross_host.py + control_plane), trace-id log stamping
(core/logging.py), and the flight recorder -> crash postmortem path
(util/flight_recorder.py, reaped from an actually SIGKILLed actor
process). The full cluster chaos scenario (kill a joined worker host
under a live head: alert before DEAD, resolve on restart) lives in the
slow+chaos tier at the bottom.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import health as health_mod
from ray_tpu.core.control_plane import ControlPlane, NodeInfo, NodeState
from ray_tpu.core.health import (
    HealthPlane,
    ReplicaHealth,
    Rule,
    parse_rule,
)
from ray_tpu.core.ids import NodeID
from ray_tpu.util import flight_recorder, slo

pytestmark = pytest.mark.health

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_slo_registry():
    slo.clear()
    yield
    slo.clear()


# ---------------------------------------------------------------------------
# util/slo.py — digests
# ---------------------------------------------------------------------------


class TestDigest:
    def test_quantiles_within_bucket_error(self):
        import random

        rng = random.Random(7)
        values = [rng.uniform(0.001, 1.0) for _ in range(5000)]
        d = slo.Digest("lat", window_s=600)
        for v in values:
            d.add(v)
        values.sort()
        for q in (0.5, 0.95, 0.99):
            exact = values[int(q * (len(values) - 1))]
            est = d.quantile(q)
            assert est is not None
            # bucket layout guarantees <= ~12% relative error
            assert abs(est - exact) / exact < 0.15, (q, est, exact)

    def test_merge_equals_single_digest(self):
        import random

        rng = random.Random(11)
        values = [rng.uniform(0.002, 0.5) for _ in range(3000)]
        whole = slo.Digest("lat", window_s=600)
        parts = [slo.Digest("lat", window_s=600) for _ in range(3)]
        for i, v in enumerate(values):
            whole.add(v)
            parts[i % 3].add(v)
        merged = slo.merge_snapshots([p.to_snapshot() for p in parts])
        (key, m), = merged.items()
        assert key[0] == "lat"
        assert m["count"] == whole.count == len(values)
        assert m["sum"] == pytest.approx(whole.sum)
        for q in (0.5, 0.95):
            assert slo.quantile_from_counts(m["counts"], q) == pytest.approx(
                whole.quantile(q))

    def test_wire_form_is_sparse_and_roundtrips(self):
        d = slo.Digest("ttft", tags={"role": "decode"}, window_s=600)
        for v in (0.01, 0.012, 0.011, 3.0):
            d.add(v)
        snap = d.to_snapshot()
        assert snap["name"] == "ttft"
        assert dict(snap["tags"]) == {"role": "decode"}
        assert all(c > 0 for c in snap["counts"].values())
        assert len(snap["counts"]) <= 4  # sparse, not 122 entries
        # survives JSON (what the dashboard serves)
        snap2 = json.loads(json.dumps(snap))
        merged = slo.merge_snapshots([snap2])
        (_, m), = merged.items()
        assert m["count"] == 4
        assert slo.quantile_from_counts(m["counts"], 0.5) == pytest.approx(
            d.quantile(0.5))

    def test_window_expiry(self):
        d = slo.Digest("lat", window_s=6.0)  # 1s slices
        d.add(0.1, now=100.0)
        assert sum(d.window_counts(now=100.5)) == 1
        # rotate past the whole window: old slice falls out
        for t in (101.1, 102.2, 103.3, 104.4, 105.5, 106.6, 107.7):
            d.add(0.2, now=t)
        counts = d.window_counts(now=107.7)
        assert counts[slo._bucket(0.1)] == 0
        assert counts[slo._bucket(0.2)] > 0

    def test_count_weighted_add(self):
        d = slo.Digest("tbt", window_s=600)
        d.add(0.005, n=40)
        assert d.count == 40
        assert d.quantile(0.5) == pytest.approx(0.005, rel=0.15)

    def test_registry_snapshot_skips_empty(self):
        slo.digest("never_observed")
        slo.observe("seen", 0.1)
        names = [s["name"] for s in slo.snapshot()]
        assert names == ["seen"]

    def test_engine_requests_fill_the_ttft_and_e2e_digests(self):
        """Every finished request of an engine is one sample of each
        latency digest under the engine's role, and an engine whose switch
        is off observes nothing: the alert rules read these counts."""
        import jax

        from ray_tpu.models import get_config, init_params
        from ray_tpu.serve.engine import EngineConfig, InferenceEngine

        cfg = get_config("tiny-llama")
        engine = InferenceEngine(
            init_params(cfg, jax.random.PRNGKey(0)), cfg,
            EngineConfig(max_batch_size=4, page_size=8, max_pages=64,
                         max_seq_len=96, prefill_buckets=(16,)))

        def counts():
            return {s["name"]: s["count"] for s in slo.snapshot()
                    if dict(s["tags"]) == {"role": "engine"}}

        three_each = {"serve_ttft_seconds": 3, "serve_e2e_seconds": 3}
        try:
            assert engine._slo_on
            for n in (3, 5, 7):
                engine.generate(list(range(1, 1 + n)), max_tokens=4)
            assert counts() == three_each
            engine._slo_on = False
            engine.generate([1, 2, 3], max_tokens=4)
            assert counts() == three_each
        finally:
            engine.stop()


# ---------------------------------------------------------------------------
# core/health.py — rule parsing + rule engine
# ---------------------------------------------------------------------------


class TestRuleParsing:
    def test_plain_value_rule(self):
        p = parse_rule("serve_disagg_queue_depth{role=prefill} > 64 for 2")
        assert p == {"fn": "value", "name": "serve_disagg_queue_depth",
                     "tags": {"role": "prefill"}, "op": ">",
                     "threshold": 64.0, "for_periods": 2}

    def test_quantile_and_delta_rules(self):
        p = parse_rule("p95(serve_ttft_seconds{role=decode}) >= 0.5")
        assert p["fn"] == "p95" and p["op"] == ">=" and p["for_periods"] == 1
        p = parse_rule("delta(control_plane_reconnects_total) > 2 for 3 periods")
        assert p["fn"] == "delta" and p["for_periods"] == 3

    def test_malformed_rules_raise(self):
        for bad in ("", "foo", "foo >", "> 3", "p95(foo > 3", "foo == 3"):
            with pytest.raises(ValueError):
                parse_rule(bad)


def _plane(rules, metrics=lambda: [], digests=lambda: []):
    """A plane with injected sources and no background thread."""
    return HealthPlane(rules=rules, period_s=60.0, metrics_fn=metrics,
                       digests_fn=digests)


class TestHealthPlane:
    def test_sustain_fire_and_resolve(self):
        samples = []
        plane = _plane([Rule("hot", "temp > 10 for 2")],
                       metrics=lambda: list(samples))
        samples[:] = [("temp", {}, 50.0)]
        assert plane.evaluate(now=1.0) == []          # 1st breach: pending
        active = plane.evaluate(now=2.0)              # 2nd: fires
        assert [a["rule"] for a in active] == ["hot"]
        assert active[0]["state"] == "firing"
        assert active[0]["value"] == 50.0
        samples[:] = [("temp", {}, 1.0)]
        assert plane.evaluate(now=3.0) == []          # one clear pass resolves
        hist = plane.history()
        assert [h["state"] for h in hist] == ["firing", "resolved"]
        assert hist[-1]["resolve_reason"] == "cleared"

    def test_group_by_and_no_data_resolve(self):
        samples = [("age", {"node_id": "a"}, 9.0),
                   ("age", {"node_id": "b"}, 1.0)]
        plane = _plane([Rule("gap", "age > 5", group_by=("node_id",))],
                       metrics=lambda: list(samples))
        active = plane.evaluate(now=1.0)
        assert len(active) == 1
        assert active[0]["labels"] == {"node_id": "a"}
        # node a vanishes (purged on DEAD): the alert resolves, not freezes
        samples[:] = [("age", {"node_id": "b"}, 1.0)]
        assert plane.evaluate(now=2.0) == []
        assert plane.history()[-1]["resolve_reason"] == "no_data"

    def test_delta_rule_fires_on_increase_only(self):
        box = {"v": 100.0}
        plane = _plane([Rule("spike", "delta(reconnects) > 2")],
                       metrics=lambda: [("reconnects", {}, box["v"])])
        assert plane.evaluate(now=1.0) == []   # no previous value yet
        assert plane.evaluate(now=2.0) == []   # delta 0
        box["v"] = 105.0
        assert len(plane.evaluate(now=3.0)) == 1   # delta 5 > 2
        box["v"] = 105.5
        assert plane.evaluate(now=4.0) == []   # delta 0.5: resolved

    def test_quantile_rule_reads_digests(self):
        d = slo.Digest("serve_ttft_seconds", tags={"role": "decode"},
                       window_s=600)
        for _ in range(100):
            d.add(0.8)
        plane = _plane(
            [Rule("slo", "p95(serve_ttft_seconds) > 0.5", group_by=("role",))],
            digests=lambda: [d.to_snapshot()])
        active = plane.evaluate(now=1.0)
        assert len(active) == 1
        assert active[0]["labels"] == {"role": "decode"}
        assert active[0]["value"] > 0.5

    def test_inject_persists_and_expires(self):
        plane = _plane([Rule("memory_pressure", "host_mem > 0.9",
                             group_by=("node_id",))])
        plane.period_s = 1.0
        alert = plane.inject("memory_pressure",
                             {"source": "memory_monitor"}, 0.97)
        assert alert["state"] == "firing"
        # the rule's own no_data sweep must NOT resolve the injected alert
        assert len(plane.evaluate(now=time.time())) == 1
        # ...but without re-injection it expires after 3 periods
        assert plane.evaluate(now=time.time() + 10.0) == []
        assert plane.history()[-1]["resolve_reason"] == "expired"

    def test_subscribe_and_pending_demand(self):
        seen = []
        samples = [("queue", {"role": "decode"}, 100.0)]
        plane = _plane(
            [Rule("backlog", "queue > 10", group_by=("role",),
                  demand={"CPU": 2.0})],
            metrics=lambda: list(samples))
        plane.subscribe(seen.append)
        plane.evaluate(now=1.0)
        assert seen and seen[0]["state"] == "firing"
        assert plane.pending_demand() == [{"CPU": 2.0}]
        samples[:] = []
        plane.evaluate(now=2.0)
        assert seen[-1]["state"] == "resolved"
        assert plane.pending_demand() == []

    def test_payload_shape(self):
        plane = _plane([])
        p = plane.payload()
        assert set(p) >= {"generated_at", "nodes", "alerts", "digests",
                          "scores"}


# ---------------------------------------------------------------------------
# ReplicaHealth + Pow2Router — quarantine / probe / recovery
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestReplicaHealth:
    def test_errors_quarantine_then_probe_recovers(self):
        clk = _Clock()
        h = ReplicaHealth(quarantine_s=5.0, now_fn=clk)
        h.record_error("r1")
        h.record_error("r1")  # score 0.0625 < 0.3 -> quarantined
        assert h.quarantined("r1")
        assert h.eligible(["r1", "r2"]) == ["r2"]
        clk.t = 6.0  # probe window opens: exactly one probe passes
        assert h.eligible(["r1", "r2"]) == ["r1", "r2"]
        assert h.eligible(["r1", "r2"]) == ["r2"]  # second ask: still probing
        h.observe("r1", latency_s=0.01, ok=True)   # probe succeeded
        assert not h.quarantined("r1")
        assert h.eligible(["r1", "r2"]) == ["r1", "r2"]

    def test_failed_probe_doubles_backoff(self):
        clk = _Clock()
        h = ReplicaHealth(quarantine_s=5.0, now_fn=clk)
        h.quarantine("r1", duration=5.0)
        clk.t = 6.0
        assert "r1" in h.eligible(["r1", "r2"])  # probe
        h.record_error("r1")                     # probe failed
        assert h.quarantined("r1")
        clk.t = 12.0  # old backoff would have opened; doubled one has not
        assert h.eligible(["r1", "r2"]) == ["r2"]
        clk.t = 17.0
        assert "r1" in h.eligible(["r1", "r2"])

    def test_fails_open_when_all_quarantined(self):
        h = ReplicaHealth(quarantine_s=100.0, now_fn=_Clock())
        h.quarantine("a")
        h.quarantine("b")
        assert h.eligible(["a", "b"]) == ["a", "b"]

    def test_penalty_scales_with_score(self):
        h = ReplicaHealth(quarantine_s=5.0, now_fn=_Clock())
        assert h.penalty("fresh") == 0
        h.record_error("bad")
        assert h.penalty("bad") >= 5  # score 0.25 -> 6 load units
        h.observe("bad", ok=True)
        h.observe("bad", ok=True)

    def test_observe_records_replica_latency_digest(self):
        h = ReplicaHealth(quarantine_s=5.0, now_fn=_Clock())
        h.observe("r9", latency_s=0.05, ok=True, role="decode")
        snaps = slo.snapshot()
        assert any(s["name"] == "serve_replica_latency_seconds"
                   and dict(s["tags"])["replica"] == "r9" for s in snaps)


class _FakeReplica:
    def __init__(self, name, log):
        self._actor_id = name
        self._log = log
        self.handle_request = self

    def remote(self, *a, **k):
        self._log.append(self._actor_id)
        return object()


class TestRouterQuarantine:
    def _router(self, n=3):
        from ray_tpu.serve.router import Pow2Router

        log = []
        r = Pow2Router("dep")
        clk = _Clock()
        r.health = ReplicaHealth(quarantine_s=5.0, now_fn=clk)
        r.update_replicas([_FakeReplica(f"r{i}", log) for i in range(n)], 1)
        return r, log, clk

    def _drain(self, router):
        # fake refs can't go through api.wait — drop them between assigns
        router._inflight = {i: [] for i in range(len(router._replicas))}

    def test_quarantined_replica_is_not_selected(self):
        router, log, _clk = self._router()
        router.health.quarantine("r1", duration=1000.0)
        for _ in range(40):
            router.assign("m", (), {})
            self._drain(router)
        assert "r1" not in log
        assert {"r0", "r2"} <= set(log)

    def test_recovery_after_probe(self):
        router, log, clk = self._router(n=2)
        router.note_result(router._replicas[1], ok=False)
        router.note_result(router._replicas[1], ok=False)
        assert router.health.quarantined("r1")
        for _ in range(20):
            router.assign("m", (), {})
            self._drain(router)
        assert "r1" not in log
        clk.t = 6.0  # probe window: the next assigns let r1 back in
        del log[:]
        for _ in range(20):
            router.assign("m", (), {})
            self._drain(router)
            router.note_result(router._replicas[1], latency_s=0.01, ok=True)
        assert "r1" in log

    def test_degraded_replica_loses_pow2_ties(self):
        router, log, _clk = self._router(n=2)
        # score 0.25 => +6 load-unit penalty: with both queues empty the
        # pow2 comparison always prefers the healthy replica
        router.health.record_error("r1")
        for _ in range(30):
            router.assign("m", (), {})
            self._drain(router)
        assert log.count("r0") == 30


# ---------------------------------------------------------------------------
# telemetry: byte budget, digests + postmortems transport, eviction
# ---------------------------------------------------------------------------


class TestTelemetryBudget:
    def test_oldest_dropped_first_and_counted(self):
        from ray_tpu.core.cross_host import _cap_telemetry, _m_tele_dropped

        spans = [{"i": i, "pad": "x" * 200} for i in range(10)]
        events = [{"j": j, "pad": "y" * 200} for j in range(10)]
        before_s = _m_tele_dropped.get(tags={"kind": "spans"})
        before_e = _m_tele_dropped.get(tags={"kind": "events"})
        kept_spans, kept_events = _cap_telemetry([], spans, events, 1200)
        assert 0 < len(kept_spans) < 10
        # newest survive
        assert kept_spans[-1]["i"] == 9
        assert kept_spans == spans[10 - len(kept_spans):]
        dropped_s = _m_tele_dropped.get(tags={"kind": "spans"}) - before_s
        dropped_e = _m_tele_dropped.get(tags={"kind": "events"}) - before_e
        assert dropped_s == 10 - len(kept_spans)
        assert dropped_e == 10 - len(kept_events)

    def test_no_budget_is_passthrough(self):
        from ray_tpu.core.cross_host import _cap_telemetry

        spans, events = [{"a": 1}], [{"b": 2}]
        assert _cap_telemetry([], spans, events, 0) == (spans, events)


def _node(hexbyte: bytes = None) -> NodeInfo:
    nid = NodeID(os.urandom(NodeID.SIZE)) if hexbyte is None else NodeID(hexbyte)
    return NodeInfo(node_id=nid, address="", resources_total={"CPU": 1.0})


class TestControlPlaneTelemetry:
    def test_digests_and_postmortems_federate(self):
        cp = ControlPlane()
        info = _node()
        cp.register_node(info)
        hexid = info.node_id.hex()
        art = {"pid": 123, "cause": "test", "written_at": 1.0,
               "spans": [], "logs": ["boom"], "events": [],
               "stdout_tail": []}
        cp.report_telemetry(hexid, role="decode", metrics=[],
                            digests=[{"name": "d", "tags": [],
                                      "counts": {0: 1}, "count": 1,
                                      "sum": 0.1, "min": 0.1, "max": 0.1}],
                            postmortems=[art])
        snap = cp.telemetry_snapshots()[hexid]
        assert snap["digests"][0]["name"] == "d"
        pms = cp.postmortems()
        assert len(pms) == 1 and pms[0]["node_id"] == hexid[:12]
        # an RPC-retried flush must not duplicate the artifact
        cp.report_telemetry(hexid, role="decode", metrics=[],
                            postmortems=[art])
        assert len(cp.postmortems()) == 1

    def test_mark_node_dead_purges_telemetry(self):
        cp = ControlPlane()
        info = _node()
        cp.register_node(info)
        cp.report_telemetry(info.node_id.hex(), metrics=[])
        assert info.node_id.hex() in cp.telemetry_snapshots()
        cp.mark_node_dead(info.node_id, reason="test")
        assert info.node_id.hex() not in cp.telemetry_snapshots()

    def test_stale_snapshots_evicted(self):
        from ray_tpu.core.config import config

        cp = ControlPlane()
        info = _node()
        cp.register_node(info)
        cp.report_telemetry(info.node_id.hex(), metrics=[])
        horizon = (float(config.telemetry_stale_factor)
                   * float(config.telemetry_report_period_s))
        with cp._lock:
            cp._telemetry[info.node_id.hex()]["reported_at"] -= horizon + 1
        assert info.node_id.hex() not in cp.telemetry_snapshots()


# ---------------------------------------------------------------------------
# logging <-> tracing — trace_id stamping
# ---------------------------------------------------------------------------


class TestLogTraceStamp:
    def test_log_lines_carry_trace_id_inside_span(self):
        import io
        import logging as pylog

        from ray_tpu.core import logging as core_logging
        from ray_tpu.util import tracing

        logger = core_logging.get_logger("health_stamp_test")
        buf = io.StringIO()
        h = pylog.StreamHandler(buf)
        h.setFormatter(pylog.Formatter(core_logging._FMT))
        logger.addHandler(h)
        try:
            logger.warning("outside")
            with tracing.start_span("op") as span:
                logger.warning("inside")
            out = buf.getvalue().splitlines()
        finally:
            logger.removeHandler(h)
        assert "trace_id=" not in out[0]
        assert f"trace_id={span.trace_id}" in out[1]


# ---------------------------------------------------------------------------
# flight recorder -> postmortems
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_mirror_and_postmortem_roundtrip(self, tmp_path):
        session = tmp_path / "session"
        logs = session / "logs"
        logs.mkdir(parents=True)
        (logs / f"actor-{os.getpid()}.out").write_text("stdout line\n")
        flight_recorder.attach(str(logs), component="test")
        flight_recorder.record("custom", detail="before-crash")
        mirror = flight_recorder.mirror_path_for(os.getpid(), str(session))
        assert os.path.exists(mirror)
        # reaper folds mirror + stdout tail into one artifact
        flight_recorder._reaped.discard(os.getpid())
        path = flight_recorder.write_postmortem(
            os.getpid(), "unit-test", exitcode=-9, session=str(session),
            stdout_hint="actor")
        assert path and os.path.exists(path)
        art = flight_recorder.load_postmortem(path)
        assert art["cause"] == "unit-test" and art["exitcode"] == -9
        assert any(e.get("detail") == "before-crash" for e in art["events"])
        assert art["stdout_tail"] == ["stdout line"]
        # artifact queued for the next telemetry flush, then requeue-able
        drained = flight_recorder.drain_postmortems()
        assert any(a["pid"] == os.getpid() for a in drained)
        flight_recorder.requeue_postmortems(drained)
        assert flight_recorder.drain_postmortems() == drained
        # same pid is reaped once
        assert flight_recorder.write_postmortem(
            os.getpid(), "again", session=str(session)) is None

    def test_listing(self, tmp_path):
        assert flight_recorder.list_postmortems(str(tmp_path)) == []


class _Sleeper:
    def pid(self):
        return os.getpid()

    def work(self):
        time.sleep(30)


class TestActorProcessPostmortem:
    def test_sigkilled_actor_leaves_postmortem(self):
        from ray_tpu.core.actor_process import ActorProcess, ActorProcessCrash
        from ray_tpu.core.logging import session_dir

        proc = ActorProcess(_Sleeper, (), {})
        pid = proc.pid
        try:
            assert proc.call("pid", (), {}) == pid
            # the child's flight mirror exists (attach ran in _child_main)
            assert os.path.exists(
                flight_recorder.mirror_path_for(pid, session_dir()))
            os.kill(pid, signal.SIGKILL)
            with pytest.raises(ActorProcessCrash):
                proc.call("pid", (), {}, timeout=30)
            deadline = time.monotonic() + 10
            art_path = None
            while time.monotonic() < deadline and art_path is None:
                for p in flight_recorder.list_postmortems():
                    if f"postmortem-{pid}-" in p:
                        art_path = p
                        break
                time.sleep(0.05)
            assert art_path, "no postmortem artifact written for killed actor"
            art = flight_recorder.load_postmortem(art_path)
            assert art["pid"] == pid
            assert art["exitcode"] == -signal.SIGKILL
            # the child recorded its attach event before dying
            assert any(e.get("kind") == "attach" for e in art["events"])
        finally:
            proc.terminate()

    def test_terminate_is_not_a_crash(self):
        from ray_tpu.core.actor_process import ActorProcess

        proc = ActorProcess(_Sleeper, (), {})
        pid = proc.pid
        proc.terminate()
        time.sleep(0.3)
        assert not any(f"postmortem-{pid}-" in p
                       for p in flight_recorder.list_postmortems())


# ---------------------------------------------------------------------------
# memory monitor — gauge + pre-kill alert
# ---------------------------------------------------------------------------


class TestMemoryMonitor:
    def test_gauge_and_prekill_alert(self):
        from ray_tpu.core.memory_monitor import MemoryMonitor, _m_used_fraction

        plane = _plane([])
        old = health_mod._plane
        health_mod._plane = plane
        kills = []
        try:
            mon = MemoryMonitor(kill_fn=lambda: kills.append(1) or 4242,
                                threshold=0.9, interval_s=0.01,
                                probe=lambda: 0.97)
            mon.start()
            deadline = time.monotonic() + 5
            while not kills and time.monotonic() < deadline:
                time.sleep(0.01)
            mon.stop()
            assert kills
            assert _m_used_fraction.get() == pytest.approx(0.97)
            active = plane.active()
            assert any(a["rule"] == "memory_pressure"
                       and a["severity"] == "critical" for a in active)
        finally:
            health_mod._plane = old

    def test_flight_event_recorded(self):
        from ray_tpu.core.memory_monitor import MemoryMonitor

        mon = MemoryMonitor(kill_fn=lambda: None, threshold=0.5,
                            interval_s=0.01, probe=lambda: 0.6)
        mon.start()
        time.sleep(0.1)
        mon.stop()
        assert any(e["kind"] == "memory_pressure"
                   for e in flight_recorder.snapshot())


# ---------------------------------------------------------------------------
# autoscaler demand merge
# ---------------------------------------------------------------------------


class _StubRuntime:
    autoscaling_enabled = False

    class control_plane:  # noqa: N801 — attribute stand-in
        @staticmethod
        def alive_nodes():
            return []

    @staticmethod
    def pending_resource_demand():
        return [{"CPU": 1.0}]


class TestAutoscalerHealthDemand:
    def test_health_demand_merges_into_pending(self):
        from ray_tpu.autoscaler import Autoscaler, NodeProvider

        plane = _plane([Rule("backlog", "q > 1", demand={"TPU": 4.0})],
                       metrics=lambda: [("q", {}, 10.0)])
        plane.evaluate(now=1.0)
        a = Autoscaler([], NodeProvider(), runtime=_StubRuntime(),
                       health_plane=plane)
        assert a.pending_demand() == [{"CPU": 1.0}, {"TPU": 4.0}]

    def test_no_plane_is_unchanged(self):
        from ray_tpu.autoscaler import Autoscaler, NodeProvider

        a = Autoscaler([], NodeProvider(), runtime=_StubRuntime())
        assert a.pending_demand() == [{"CPU": 1.0}]


# ---------------------------------------------------------------------------
# status() + dashboard routes
# ---------------------------------------------------------------------------


class TestStatusAndRoutes:
    def test_status_renders_payload(self, capsys):
        slo.observe("serve_ttft_seconds", 0.05, tags={"role": "decode"})
        try:
            payload = ray_tpu.status(as_dict=True)
            assert ray_tpu.status() is None  # text mode prints
            out = capsys.readouterr().out
        finally:
            health_mod.shutdown_health_plane()
        assert "ray_tpu health" in out
        assert "serve_ttft_seconds" in out
        assert set(payload) >= {"nodes", "alerts", "digests", "scores"}

    def test_dashboard_health_routes(self):
        from urllib.request import urlopen

        from ray_tpu import dashboard

        port = dashboard.start_dashboard(port=0)
        try:
            base = f"http://127.0.0.1:{port}"
            with urlopen(f"{base}/api/v0/health", timeout=10) as r:
                health = json.loads(r.read())
            assert set(health) >= {"nodes", "alerts", "digests", "scores"}
            with urlopen(f"{base}/api/v0/alerts", timeout=10) as r:
                alerts = json.loads(r.read())
            assert set(alerts) == {"active", "history"}
            with urlopen(f"{base}/api/v0/postmortems", timeout=10) as r:
                pms = json.loads(r.read())
            assert set(pms) == {"federated", "local_paths"}
        finally:
            dashboard.stop_dashboard()
            health_mod.shutdown_health_plane()

    def test_health_board_in_grafana_set(self):
        from ray_tpu.dashboard import build_dashboards

        dashes = build_dashboards()
        assert "health" in dashes
        exprs = [t["expr"] for p in dashes["health"]["panels"]
                 for t in p["targets"]]
        assert any("health_alerts_firing" in e for e in exprs)
        assert any("slo_quantile_seconds" in e for e in exprs)
        assert any("host_memory_used_fraction" in e for e in exprs)


# ---------------------------------------------------------------------------
# chaos e2e: kill a joined worker host under a live head
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
class TestChaosHealthE2E:
    def test_killed_worker_alerts_before_dead_and_resolves_on_restart(self):
        """SIGKILL a worker host: heartbeat_gap fires within ~2 eval
        periods while the node is still ALIVE (the health plane beats the
        control plane's DEAD declaration), resolves once the node is
        reaped+purged, and a restarted worker reads healthy."""
        env_cfg = {
            # heartbeat every 200ms; DEAD only after 5s of silence
            "RAY_TPU_HEALTH_CHECK_PERIOD_MS": "200",
            "RAY_TPU_HEALTH_CHECK_TIMEOUT_MS": "5000",
            "RAY_TPU_TELEMETRY_REPORT_PERIOD_S": "0.2",
            # keep stale eviction far beyond the alert threshold so the
            # silent node's snapshot (and its heartbeat-age sample)
            # outlives the 3x-period gap rule
            "RAY_TPU_TELEMETRY_STALE_FACTOR": "50",
            "RAY_TPU_HEALTH_EVAL_PERIOD_S": "0.2",
        }
        # config resolves env on every get(), so these apply immediately
        old_env = {k: os.environ.get(k) for k in env_cfg}
        os.environ.update(env_cfg)
        rt = ray_tpu.init(
            num_cpus=2, num_tpus=0,
            system_config={"control_plane_rpc_port": 0,
                           "worker_processes": 0},
        )
        plane = HealthPlane(control_plane=rt.control_plane, period_s=0.2)
        plane.start()
        proc = None
        proc2 = None
        try:
            proc = self._spawn_worker(rt._cp_server.address)
            self._wait_alive_nodes(rt, 2)
            victim_hex = self._worker_node_hex(rt)
            # wait for the worker's first telemetry flush (the gap rule
            # only watches nodes that federate telemetry)
            self._wait_for(
                lambda: victim_hex in rt.control_plane.telemetry_snapshots(),
                10, "worker never reported telemetry")

            proc.kill()  # SIGKILL: no goodbye, heartbeats just stop
            # alert within ~2 telemetry periods of the 3x-gap threshold,
            # long before the 5s DEAD timeout
            self._wait_for(
                lambda: any(a["rule"] == "heartbeat_gap"
                            and a["labels"].get("node_id") == victim_hex[:12]
                            for a in plane.active()),
                3.0, "heartbeat_gap never fired")
            states = {n.node_id.hex(): n.state
                      for n in rt.control_plane.all_nodes()}
            assert states[victim_hex] is NodeState.ALIVE, \
                "alert must fire BEFORE the control plane marks the node DEAD"

            # the reaper marks it DEAD and purges telemetry -> no_data
            self._wait_for(
                lambda: not any(a["rule"] == "heartbeat_gap"
                                for a in plane.active()),
                15, "alert never resolved after node death")
            reasons = [h.get("resolve_reason") for h in plane.history()
                       if h["rule"] == "heartbeat_gap"
                       and h["state"] == "resolved"]
            assert "no_data" in reasons

            # a restarted worker joins clean: telemetry flows, no alert
            proc2 = self._spawn_worker(rt._cp_server.address)
            self._wait_alive_nodes(rt, 2)
            new_hex = self._worker_node_hex(rt)
            self._wait_for(
                lambda: new_hex in rt.control_plane.telemetry_snapshots(),
                10, "restarted worker never reported telemetry")
            time.sleep(1.0)  # several eval periods with live heartbeats
            assert not any(a["rule"] == "heartbeat_gap"
                           for a in plane.active())
        finally:
            plane.stop()
            for p in (proc, proc2):
                if p is not None and p.poll() is None:
                    p.kill()
            ray_tpu.shutdown()
            for k, v in old_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # -- helpers ------------------------------------------------------------

    @staticmethod
    def _spawn_worker(addr):
        code = textwrap.dedent(f"""
            import ray_tpu
            w = ray_tpu.init(address={addr!r}, num_cpus=2, num_tpus=0)
            w.wait(timeout=300)
        """)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["RAY_TPU_WORKER_PROCESSES"] = "0"
        env.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        return subprocess.Popen([sys.executable, "-c", code], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    @staticmethod
    def _wait_alive_nodes(rt, n, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(rt.control_plane.alive_nodes()) >= n:
                return
            time.sleep(0.05)
        raise AssertionError(f"never reached {n} alive nodes")

    @staticmethod
    def _worker_node_hex(rt):
        head_hex = rt.head_node_id.hex()
        for n in rt.control_plane.alive_nodes():
            if n.node_id.hex() != head_hex:
                return n.node_id.hex()
        raise AssertionError("no worker node found")

    @staticmethod
    def _wait_for(cond, timeout, msg):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.05)
        raise AssertionError(msg)
