"""Fleet actuation plane (serve/fleet.py + the disagg coordinator's
live-resume / drain / adapter machinery).

Covers the kill-resume chaos contract (a decode replica dying mid-stream
resumes on a healthy peer with a token stream IDENTICAL to an
uninterrupted run — and a resume storm where N concurrent streams share
one death all survive), the autoscale policy (scale up on an injected
queue-depth alert, scale down on idle, NO oscillation across consecutive
quiet periods, cooldown + step-max hysteresis), graceful scale-down
(busy replicas drain before their caches drop), gauge hygiene under
cancel/abandon, LoRA hot-swap distribution + residency routing, and the
quarantine→drain→restart→rejoin remediation pipeline.
"""

import threading
import time

import numpy as np
import pytest

import jax

from ray_tpu.core.metrics import registry
from ray_tpu.models import get_config, init_params
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.disagg import DisaggCoordinator, EngineWorker
from ray_tpu.serve.fleet import FleetConfig, FleetController

pytestmark = pytest.mark.fleet


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny-llama")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    defaults = dict(max_batch_size=4, page_size=8, max_pages=64,
                    max_seq_len=96, prefill_buckets=(16, 32))
    defaults.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**defaults))


def _prompts(cfg, lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab_size, size=n)) for n in lengths]


class _MortalWorker(EngineWorker):
    """EngineWorker whose decode streams die (raise) once `kill()` is
    called — the in-process stand-in for a SIGKILLed replica: every
    in-flight stream's next pull fails, exactly what the coordinator's
    resume loop must absorb."""

    def __init__(self, engine, name="mortal"):
        super().__init__(engine, name)
        self.killed = threading.Event()
        self.deaths = 0

    def _mortal(self, inner):
        for item in inner:
            if self.killed.is_set():
                self.deaths += 1
                raise RuntimeError(f"{self.name} SIGKILLed mid-stream")
            yield item

    def decode_stream(self, request):
        return self._mortal(super().decode_stream(request))

    def generate_stream(self, request):
        return self._mortal(super().generate_stream(request))


# --------------------------------------------------------------------------
# policy doubles (no engines): the autoscale/remediation tests exercise
# the controller's decisions, not inference
# --------------------------------------------------------------------------


class _FakeWorker:
    _n = 0

    def __init__(self, load=0):
        _FakeWorker._n += 1
        self.key = f"fake-{_FakeWorker._n}"
        self._load = load
        self.retired = False

    def load(self):
        return self._load

    def list_adapters(self):
        return []

    def cancel(self, request_id):
        return False


class _FakePlane:
    """HealthPlane double: the test scripts which alerts are firing and
    delivers them to subscribers on demand."""

    def __init__(self):
        self.alerts = []
        self._subs = []

    def active(self):
        return [dict(a) for a in self.alerts]

    def subscribe(self, fn):
        self._subs.append(fn)

    def fire(self, alert):
        self.alerts.append(alert)
        for fn in list(self._subs):
            fn(dict(alert))


def _qd_alert(role="decode", value=9.0):
    return {"rule": "queue_depth", "expr": "injected", "state": "firing",
            "severity": "critical", "labels": {"role": role},
            "value": value, "threshold": 4.0, "since": 0.0, "at": 0.0,
            "demand": {"CPU": 1.0}}


def _policy_fleet(co, plane, spawned, retired, **cfg):
    defaults = dict(min_replicas=1, max_replicas=4, idle_periods=2,
                    cooldown_s=0.0, step_max=1, eval_period_s=0.05)
    defaults.update(cfg)

    def spawn(role):
        w = _FakeWorker()
        spawned.append((role, w))
        return w

    def retire(role, w):
        w.retired = True
        retired.append((role, w))

    return FleetController(co, defaults, spawn_fn=spawn, retire_fn=retire,
                           plane=plane)


# --------------------------------------------------------------------------
# kill-resume chaos: the tentpole's headline contract
# --------------------------------------------------------------------------


class TestKillResume:
    def test_mid_stream_death_resumes_token_identical(self, tiny):
        """SIGKILL a decode replica mid-stream: the resumed continuation
        must be token-identical to an uninterrupted run — a latency
        blip, never a failed request."""
        cfg, params = tiny
        pe = _engine(cfg, params)
        de1 = _engine(cfg, params)
        de2 = _engine(cfg, params, page_size=4, max_pages=96)
        ref = _engine(cfg, params)
        mortal = _MortalWorker(de1, "mortal0")
        healthy = EngineWorker(de2, "healthy0")
        co = DisaggCoordinator([EngineWorker(pe, "prefill0")], [mortal],
                               {"small_blob_bytes": 0})
        resumes = registry.get("serve_fleet_resumes")
        r0 = resumes.get()
        try:
            prompt = _prompts(cfg, (9,))[0]
            want = ref.generate(prompt, max_tokens=12)["token_ids"]
            ds = co.open_stream(prompt, max_tokens=12)
            it = ds.tokens()
            got = [next(it) for _ in range(3)]
            # the only decode replica dies; a healthy peer joins
            co.add_worker("decode", healthy)
            mortal.killed.set()
            got.extend(it)
            assert got == want
            assert ds.finish_reason == "length"
            assert ds.error is None
            assert mortal.deaths >= 1
            assert resumes.get() - r0 >= 1
            # the dead replica is quarantined out of future picks
            assert co.health.quarantined(mortal.key)
            # load accounting unwinds on BOTH sides of the resume: a
            # leaked count would pin the replica "busy" and block fleet
            # scale-down forever
            assert healthy.load() == 0
            assert mortal.load() == 0
        finally:
            co.close()
            pe.stop(), de1.stop(), de2.stop(), ref.stop()

    @pytest.mark.parametrize("deaths", [1, 2])
    def test_resume_storm_all_streams_survive(self, tiny, deaths):
        """N concurrent streams in flight, the replicas under them dying
        one after another (a healthy peer joins at the first death):
        every stream resumes on a survivor and stays token-identical.
        Zero failed requests through two deaths is the whole of what a
        fleet owes a burst."""
        cfg, params = tiny
        pe = _engine(cfg, params)
        des = [_engine(cfg, params) for _ in range(deaths)]
        de2 = _engine(cfg, params, max_pages=96)
        ref = _engine(cfg, params)
        mortals = [_MortalWorker(de, f"mortal1{i}")
                   for i, de in enumerate(des)]
        healthy = EngineWorker(de2, "healthy1")
        co = DisaggCoordinator([EngineWorker(pe, "prefill1")], mortals,
                               {"small_blob_bytes": 0})
        try:
            prompts = _prompts(cfg, (5, 9, 13, 7)[:2 + deaths], seed=11)
            wants = [ref.generate(p, max_tokens=10)["token_ids"]
                     for p in prompts]
            streams = [co.open_stream(p, max_tokens=10) for p in prompts]
            its = [ds.tokens() for ds in streams]
            heads = [[next(it)] for it in its]  # all in flight on mortals
            co.add_worker("decode", healthy)
            for i, mortal in enumerate(mortals):
                if i:  # the survivors of one death are mid-stream again
                    for head, it in zip(heads, its):
                        head.append(next(it))
                mortal.killed.set()
            outs, errs = {}, {}

            def drain(i):
                try:
                    outs[i] = heads[i] + list(its[i])
                except Exception as e:  # noqa: BLE001
                    errs[i] = e

            ts = [threading.Thread(target=drain, args=(i,))
                  for i in range(len(streams))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120.0)
            assert not errs, f"failed streams: {errs}"
            assert [outs[i] for i in range(len(wants))] == wants
            assert all(ds.error is None and ds.finish_reason == "length"
                       for ds in streams)
            assert all(m.deaths >= 1 for m in mortals), "a kill hit nothing"
        finally:
            co.close()
            for e in (pe, de2, ref, *des):
                e.stop()

    def test_resume_disabled_propagates_death(self, tiny):
        cfg, params = tiny
        pe = _engine(cfg, params)
        de = _engine(cfg, params)
        mortal = _MortalWorker(de, "mortal2")
        co = DisaggCoordinator([EngineWorker(pe, "prefill2")], [mortal],
                               {"small_blob_bytes": 0, "live_resume": False})
        try:
            prompt = _prompts(cfg, (9,), seed=3)[0]
            ds = co.open_stream(prompt, max_tokens=8)
            it = ds.tokens()
            next(it)
            mortal.killed.set()
            with pytest.raises(RuntimeError, match="SIGKILL"):
                list(it)
        finally:
            co.close()
            pe.stop(), de.stop()


# --------------------------------------------------------------------------
# gauge hygiene (satellite: cancel paths must not drift demand signals)
# --------------------------------------------------------------------------


class TestGaugeHygiene:
    def test_cancel_and_abandon_leave_gauges_flat(self, tiny):
        cfg, params = tiny
        pe = _engine(cfg, params)
        de = _engine(cfg, params)
        co = DisaggCoordinator([EngineWorker(pe, "prefill3")],
                               [EngineWorker(de, "decode3")],
                               {"small_blob_bytes": 0})
        qd = registry.get("serve_disagg_queue_depth")
        inflight = registry.get("serve_disagg_inflight")
        tags = {"role": "decode"}
        q0, i0 = qd.get(tags=tags), inflight.get(tags=tags)
        try:
            prompt = _prompts(cfg, (9,), seed=5)[0]
            # consumed to completion
            list(co.open_stream(prompt, max_tokens=4).tokens())
            # cancelled after a couple of tokens
            ds = co.open_stream(prompt, max_tokens=8)
            it = ds.tokens()
            next(it), next(it)
            ds.cancel()
            it.close()
            # opened but never iterated, then cancelled (abandoned)
            co.open_stream(prompt, max_tokens=8).cancel()
            assert qd.get(tags=tags) == q0
            assert inflight.get(tags=tags) == i0
        finally:
            co.close()
            pe.stop(), de.stop()


# --------------------------------------------------------------------------
# autoscale policy: converge, don't flap
# --------------------------------------------------------------------------


class TestAutoscalePolicy:
    def _co(self):
        return DisaggCoordinator([_FakeWorker()], [_FakeWorker()],
                                 {"small_blob_bytes": 0})

    def test_converges_up_then_down_without_oscillation(self):
        plane = _FakePlane()
        spawned, retired = [], []
        fleet = _policy_fleet(self._co(), plane, spawned, retired)
        # injected queue-depth alert -> scale up
        plane.alerts = [_qd_alert("decode")]
        targets = fleet.evaluate_once()
        assert targets["decode"] == 2
        assert len(fleet.co.workers("decode")) == 2
        assert [r for r, _ in spawned] == ["decode"]
        # alert clears, fleet idle -> scale back down after idle_periods
        plane.alerts = []
        fleet.evaluate_once()
        targets = fleet.evaluate_once()
        assert targets["decode"] == 1
        assert len(fleet.co.workers("decode")) == 1
        assert retired and retired[0][1].retired
        # acceptance: no oscillation across 3 consecutive quiet periods
        history = [fleet.evaluate_once()["decode"] for _ in range(3)]
        assert history == [1, 1, 1]

    def test_cooldown_blocks_immediate_rescale(self):
        plane = _FakePlane()
        spawned, retired = [], []
        fleet = _policy_fleet(self._co(), plane, spawned, retired,
                              cooldown_s=60.0)
        plane.alerts = [_qd_alert("decode")]
        assert fleet.evaluate_once()["decode"] == 2
        # still firing, but inside the cooldown window: target holds
        for _ in range(3):
            assert fleet.evaluate_once()["decode"] == 2
        # past the cooldown the next wave launches
        fleet._last_scale_up["decode"] = float("-inf")
        assert fleet.evaluate_once()["decode"] == 3

    def test_step_max_bounds_one_wave(self):
        plane = _FakePlane()
        spawned, retired = [], []
        fleet = _policy_fleet(self._co(), plane, spawned, retired,
                              step_max=2)
        qd = registry.get("serve_disagg_queue_depth")
        qd.add(10, tags={"role": "decode"})
        try:
            # demand says "want 5 replicas"; step_max caps the wave at 2
            assert fleet.evaluate_once()["decode"] == 3
        finally:
            qd.add(-10, tags={"role": "decode"})

    def test_scale_down_respects_min_replicas(self):
        plane = _FakePlane()
        spawned, retired = [], []
        fleet = _policy_fleet(self._co(), plane, spawned, retired)
        for _ in range(10):
            targets = fleet.evaluate_once()
        assert targets == {"prefill": 1, "decode": 1}
        assert not retired

    def test_global_knobs_are_the_default(self):
        from ray_tpu.core.config import config

        fleet = FleetController(self._co(), {}, plane=_FakePlane())
        assert fleet._cooldown_s() == config.get("autoscale_cooldown_s")
        assert fleet._step_max() == config.get("autoscale_step_max")

    def test_config_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown fleet option"):
            FleetConfig.parse({"max_replicaz": 3})
        with pytest.raises(ValueError, match="idle_periods"):
            FleetConfig(idle_periods=0)

    def test_serve_mode_actuates_through_set_target(self):
        calls = []

        class _Ctrl:
            def set_target(self, name, target):
                calls.append((name, target))
                return True

        plane = _FakePlane()
        plane.alerts = [_qd_alert("decode")]
        fleet = FleetController(
            self._co(),
            {"cooldown_s": 0.0, "step_max": 1, "idle_periods": 2},
            controller=_Ctrl(), deployments={"decode": "llm-decode"},
            plane=plane)
        fleet.evaluate_once()
        assert calls == [("llm-decode", 2)]


# --------------------------------------------------------------------------
# graceful scale-down: drain before drop
# --------------------------------------------------------------------------


class TestGracefulScaleDown:
    def test_busy_replica_drains_then_drops(self):
        busy = _FakeWorker(load=1)
        idle = _FakeWorker(load=0)
        co = DisaggCoordinator([_FakeWorker()], [busy, idle],
                               {"small_blob_bytes": 0, "drain_grace_s": 60})
        co._kv_dest_cache[busy.key] = object()  # simulate a warm channel
        removed = co.remove_worker("decode", key=busy.key)
        assert removed is busy
        # out of the pick set immediately, but parked draining with its
        # caches intact while the in-flight stream finishes
        assert busy not in co.workers("decode")
        assert str(busy.key) in co.stats()["draining"]
        assert busy.key in co._kv_dest_cache
        # the stream finishes -> the next sweep drops the caches
        busy._load = 0
        assert co.stats()["draining"] == []
        assert busy.key not in co._kv_dest_cache

    def test_idle_replica_drops_immediately(self):
        idle = _FakeWorker(load=0)
        co = DisaggCoordinator([_FakeWorker()], [idle, _FakeWorker()],
                               {"small_blob_bytes": 0})
        co._kv_dest_cache[idle.key] = object()
        assert co.remove_worker("decode", key=idle.key) is idle
        assert co.stats()["draining"] == []
        assert idle.key not in co._kv_dest_cache

    def test_remove_without_key_takes_least_loaded(self):
        a, b = _FakeWorker(load=3), _FakeWorker(load=0)
        co = DisaggCoordinator([_FakeWorker()], [a, b],
                               {"small_blob_bytes": 0})
        assert co.remove_worker("decode") is b
        assert co.workers("decode") == [a]


# --------------------------------------------------------------------------
# LoRA hot-swap: distribution + residency routing
# --------------------------------------------------------------------------


class TestAdapterHotSwap:
    def test_distribute_and_residency_routing(self, tiny, monkeypatch):
        cfg, params = tiny
        pe = _engine(cfg, params)
        de1 = _engine(cfg, params)
        de2 = _engine(cfg, params)
        ref = _engine(cfg, params)
        resident = EngineWorker(de1, "resident")
        bare = EngineWorker(de2, "bare")
        co = DisaggCoordinator([EngineWorker(pe, "prefill4")],
                               [resident, bare],
                               {"small_blob_bytes": 0,
                                "adapter_gossip_s": 0.0})
        fleet = FleetController(co, {}, plane=_FakePlane())
        from ray_tpu.serve import disagg, fleet as fleet_mod

        broadcasts = []
        monkeypatch.setattr(fleet_mod.api, "put", lambda v: {"ref": v})
        monkeypatch.setattr(
            fleet_mod.api, "broadcast",
            lambda ref, **kw: broadcasts.append(ref)
            or {"warmed": [], "failed": []})
        monkeypatch.setattr(disagg.api, "get",
                            lambda ref, timeout=None: ref["ref"])
        try:
            out = fleet.distribute_adapter("ada-1", weights={"rank": 4},
                                           roles=("decode",))
            assert sorted(out["loaded"]) == sorted(
                [str(resident.key), str(bare.key)])
            assert out["failed"] == []
            assert broadcasts  # pre-seeded over the relay tree
            assert resident.list_adapters() == ["ada-1"]
            # drop it from one replica: routing must prefer the replica
            # still gossiping it resident
            with bare._adapter_lock:
                bare._adapters.clear()
            prompt = _prompts(cfg, (9,), seed=9)[0]
            want = ref.generate(prompt, max_tokens=4)["token_ids"]
            for _ in range(4):
                got = co.generate(prompt, max_tokens=4,
                                  adapter_id="ada-1")
                # a route to "bare" would raise (no adapter_ref to pull)
                assert got["token_ids"] == want
            assert co.adapter_residency()[str(resident.key)] == ["ada-1"]
            assert bare.list_adapters() == []
        finally:
            co.close()
            pe.stop(), de1.stop(), de2.stop(), ref.stop()

    def test_non_resident_without_ref_fails_clearly(self, tiny):
        cfg, params = tiny
        pe = _engine(cfg, params)
        de = _engine(cfg, params)
        co = DisaggCoordinator([EngineWorker(pe, "prefill5")],
                               [EngineWorker(de, "decode5")],
                               {"small_blob_bytes": 0})
        try:
            prompt = _prompts(cfg, (9,), seed=13)[0]
            with pytest.raises(ValueError, match="not resident"):
                co.generate(prompt, max_tokens=4, adapter_id="ghost")
        finally:
            co.close()
            pe.stop(), de.stop()


# --------------------------------------------------------------------------
# auto-remediation: quarantine -> drain -> restart -> rejoin
# --------------------------------------------------------------------------


class TestRemediation:
    def test_alert_drives_full_pipeline(self):
        plane = _FakePlane()
        spawned, retired = [], []
        sick = _FakeWorker()
        co = DisaggCoordinator([_FakeWorker()], [sick, _FakeWorker()],
                               {"small_blob_bytes": 0})
        fleet = _policy_fleet(co, plane, spawned, retired)
        rem = registry.get("serve_fleet_remediations")
        stages = {s: rem.get(tags={"stage": s})
                  for s in ("quarantine", "drain", "restart", "rejoin")}
        plane.fire({"rule": "replica_errors", "state": "firing",
                    "severity": "critical",
                    "labels": {"replica": str(sick.key)}})
        assert sick not in co.workers("decode")
        assert sick.retired
        assert co.health.quarantined(sick.key)
        # the replacement joined the pick set
        assert len(co.workers("decode")) == 2
        assert spawned and spawned[0][0] == "decode"
        for s, before in stages.items():
            assert rem.get(tags={"stage": s}) - before == 1, s
        kinds = [a["kind"] for a in fleet.status()["actions"]]
        assert "remediate" in kinds

    def test_remediate_is_reentrancy_safe(self):
        plane = _FakePlane()
        spawned, retired = [], []
        sick = _FakeWorker()
        co = DisaggCoordinator([_FakeWorker()], [sick],
                               {"small_blob_bytes": 0})
        fleet = _policy_fleet(co, plane, spawned, retired)
        assert fleet.remediate("decode", sick.key) is True
        # the same key mid-remediation (or already handled) is a no-op
        fleet._remediating.add("busy-key")
        assert fleet.remediate("decode", "busy-key") is False


# --------------------------------------------------------------------------
# controller loop plumbing
# --------------------------------------------------------------------------


class TestLoop:
    def test_start_stop_evaluates_periodically(self):
        plane = _FakePlane()
        spawned, retired = [], []
        co = DisaggCoordinator([_FakeWorker()], [_FakeWorker()],
                               {"small_blob_bytes": 0})
        fleet = _policy_fleet(co, plane, spawned, retired,
                              eval_period_s=0.02, cooldown_s=60.0)
        plane.alerts = [_qd_alert("decode")]
        fleet.start()
        try:
            deadline = time.monotonic() + 10.0
            while (len(co.workers("decode")) < 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert len(co.workers("decode")) == 2
        finally:
            fleet.stop()
        st = fleet.status()
        assert st["targets"]["decode"] == 2
        assert st["live"]["decode"] == 2
