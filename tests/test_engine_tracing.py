"""The engine's regions, stage stamps and counters: a dozen requests over
both prefill paths through InferenceEngine on the CPU, with the JAX
profiler open, so the loop's phases are read back from the xplane's host
plane by the benchmark's own reader (benchmark/program_spans.py)."""

import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.core.metrics import registry
from ray_tpu.models import get_config, init_params
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.util import tracing

B, SPAN = 4, 4
PROMPTS = (5, 20, 40, 70, 100, 12, 33, 64, 8, 90, 30, 50)  # 32 splits paths


def _counter(name, suffix="", **tags):
    """Sum of the metric's samples `name + suffix` whose tags hold `tags`."""
    total = 0.0
    for sample, t, v in registry.get(name).samples():
        if sample == name + suffix and set(tags.items()) <= set(t):
            total += v
    return total


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-llama")
    return init_params(cfg, jax.random.PRNGKey(0)), cfg


def _engine(model, **kw):
    params, cfg = model
    ecfg = dict(max_batch_size=B, max_pages=64, max_seq_len=160,
                prefill_buckets=(16, 32), prefill_chunk=32, page_size=16,
                decode_span=SPAN, adaptive_span=False)
    ecfg.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**ecfg))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, n).tolist() for n in lens]


@pytest.fixture(scope="module")
def traced_run(model, tmp_path_factory):
    """A dozen streaming requests with the profiler open -> the requests,
    counter deltas over the run, and the xplane file."""
    engine = _engine(model)
    prompts = _prompts(model[1], PROMPTS)
    for p in (prompts[0], prompts[3]):  # compile both paths first
        engine.generate(p, max_tokens=6)
    logdir = str(tmp_path_factory.mktemp("xplane"))
    before = {"steps": engine.stats()["steps"], "tps": engine._tps_steps,
              "active": _counter("serve_decode_slot_steps", state="active"),
              "empty": _counter("serve_decode_slot_steps", state="empty")}
    done = []

    def ask(p):
        req, stream = engine.open_stream(p, max_tokens=6)
        done.append((req, list(stream)))

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # it taxes the bytecode between phases
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        threads = [threading.Thread(target=ask, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        jax.profiler.stop_trace()
    after = {"steps": engine.stats()["steps"], "tps": engine._tps_steps,
             "active": _counter("serve_decode_slot_steps", state="active"),
             "empty": _counter("serve_decode_slot_steps", state="empty")}
    engine.stop()
    (xplane,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    assert len(done) == len(prompts)
    return {"requests": [r for r, _ in done], "before": before,
            "after": after, "xplane": xplane}


def test_stages_tile_the_requests_life(traced_run):
    seen = set()
    for req in traced_run["requests"]:
        assert req.error is None and req.stage is None
        stages = req.stage_seconds
        seen |= set(stages)
        assert sum(stages.values()) == pytest.approx(
            req.finished_at - req.submitted_at, abs=1e-3)
        to_first = sum(stages.get(s, 0.0) for s in (
            "pending", "waiting_for_pages", "chunk_wait", "prefill"))
        assert to_first == pytest.approx(
            req.first_token_at - req.submitted_at, abs=1e-3)
        chunked = len(req.prompt) > 32
        assert ("chunk_wait" in stages) == chunked
        assert {"pending", "prefill", "ready", "decode"} <= set(stages)
    assert "chunk_wait" in seen  # both prefill paths ran


def test_slot_steps_are_slots_times_span_over_dispatches(traced_run):
    b, a = traced_run["before"], traced_run["after"]
    dispatches = a["steps"] - b["steps"]
    active, empty = a["active"] - b["active"], a["empty"] - b["empty"]
    assert dispatches > 0
    assert active + empty == B * SPAN * dispatches
    assert active == a["tps"] - b["tps"]  # span x active slots, per dispatch


def test_the_xplane_holds_the_loop_and_the_reader_nests_it(traced_run):
    from benchmark import program_spans

    spans = program_spans.read_file(traced_run["xplane"])
    iters = spans.named("engine.iter")
    assert len(iters) >= 3
    tiles = {"engine.chunk", "engine.install", "engine.cancel_check",
             "engine.build", "engine.dispatch", "engine.readback",
             "engine.commit"}
    for it in iters:
        names = [c.name for c in it.children]
        assert set(names) <= tiles and names[:3] == [
            "engine.chunk", "engine.install", "engine.cancel_check"]
        assert all(it.start <= c.start and c.end <= it.end
                   for c in it.children)
    # the phases tile the iteration: what they leave out is the bytecode
    # between two `with` blocks (microseconds; 99.9% covered on the chip,
    # PERF.md). On a loaded test machine a 20 ms iteration can lose the
    # CPU there to five other test workers, so no single iteration is
    # held to a share: the sum over all of them is.
    assert sum(c.seconds for it in iters for c in it.children) >= \
        0.9 * sum(it.seconds for it in iters)
    assert any("engine.dispatch" in [c.name for c in it.children]
               for it in iters)
    # a prompt's last chunk reads its logits back inside engine.chunk
    (parents,) = {r.name for r in spans.all()
                  if any(c.name == "engine.chunk.readback"
                         for c in r.children)}
    assert parents == "engine.chunk"
    # the prefill thread's phases are on a line of their own
    prefill = {r.name for r in spans.all() if r.name.startswith("prefill.")}
    assert {"prefill.admit", "prefill.dispatch", "prefill.readback",
            "prefill.publish"} <= prefill
    threads = {r.name.split(".")[0]: r.thread for r in spans.roots}
    assert threads["engine"] != threads["prefill"]
    assert spans.busy == [] and spans.idle() == []  # no device plane here


def test_a_pool_too_small_defers_requests(model):
    engine = _engine(model, max_pages=9, prefix_caching=False)
    before = _counter("serve_requests_deferred", reason="no_pages")
    out = []
    prompts = _prompts(model[1], (20, 24, 28, 30, 22, 26), seed=1)
    # 20..30 + 40 tokens need 4 or 5 of the 8 usable pages each
    threads = [threading.Thread(
        target=lambda p=p: out.append(engine.generate(p, max_tokens=40)))
        for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    engine.stop()
    assert len(out) == len(prompts)
    assert _counter("serve_requests_deferred", reason="no_pages") > before
    assert registry.get("serve_request_stage_seconds").count(
        {"stage": "waiting_for_pages"}) > 0


def test_streaming_and_blocking_requests_yield_the_same_stage_spans(model):
    engine = _engine(model)
    short, long_ = _prompts(model[1], (20, 70), seed=2)
    tracing.clear()
    roots = {}
    with tracing.start_span("caller") as caller:
        for kind in ("blocking", "streaming"):
            for label, p in (("short", short), ("long", long_)):
                rid = f"{kind}-{label}"
                if kind == "blocking":
                    engine.generate(p, max_tokens=5, request_id=rid)
                else:
                    list(engine.generate_stream(p, max_tokens=5,
                                                request_id=rid))
    engine.stop()
    (tree,) = tracing.get_trace(caller.trace_id)
    for node in tree["children"]:
        assert node["name"] == "engine.request"
        roots[node["attrs"]["request_id"]] = node
    assert len(roots) == 4

    def stages(rid):
        node = roots[rid]
        kids = node["children"]
        # the stage spans tile their request's span
        # `pending` opens when the Request is made, the span at add_request
        assert 0 <= node["start_us"] - kids[0]["start_us"] < 50_000
        assert kids[-1]["end_us"] == pytest.approx(node["end_us"], abs=1)
        for a, b in zip(kids, kids[1:]):
            assert a["end_us"] == b["start_us"]
        return [k["name"] for k in kids]

    assert stages("blocking-short") == stages("streaming-short") == [
        "engine.stage.pending", "engine.stage.prefill", "engine.stage.ready",
        "engine.stage.decode"]
    assert stages("blocking-long") == stages("streaming-long") == [
        "engine.stage.pending", "engine.stage.chunk_wait",
        "engine.stage.prefill", "engine.stage.ready", "engine.stage.decode"]
    assert roots["streaming-long"]["attrs"]["finish_reason"] == "length"
    assert not any(s["name"] == "engine.generate" for s in tracing.get_spans())


def test_stats_counts_the_chunk_queue(model):
    engine = _engine(model)
    assert engine.stats()["chunk_queue"] == 0
    assert engine.stats()["chunking"] is None
    # park two chunked prompts without a decode thread to drain them
    engine._ensure_loop = lambda: None
    from ray_tpu.serve.engine import Request

    for i, p in enumerate(_prompts(model[1], (70, 100), seed=3)):
        engine.add_request(Request(f"q{i}", p, max_tokens=4))
    engine._prefill_batch([engine.pending.get(), engine.pending.get()])
    stats = engine.stats()
    assert stats["chunk_queue"] == 2 and stats["pending"] == 0
    assert stats["chunking"] == [0, 3]  # 70 tokens: chunk 0 of 3
    engine._advance_chunk()
    assert engine.stats()["chunking"] == [1, 3]


# -- the token ledger ---------------------------------------------------------

PARTS = ("chunk_host", "chunk_device_wait", "host", "dispatch",
         "device_wait", "loop")


def _ledger():
    out = {p: _counter("serve_token_wait_seconds", part=p) for p in PARTS}
    out["decode_stage"] = _counter("serve_request_stage_seconds", "_sum",
                                   stage="decode")
    for shared in ("0", "1"):
        out["span_s", shared] = _counter("serve_decode_span_seconds",
                                         prefill=shared)
        out["span_n", shared] = _counter("serve_decode_span_steps",
                                         prefill=shared)
    out["interleaved"] = _counter("serve_decode_interleaved_prefill_tokens")
    for phase in ("iter", "chunk", "chunk_readback", "install",
                  "cancel_check", "build", "dispatch", "readback", "commit"):
        out["loop", phase] = _counter("serve_engine_loop_seconds", "_sum",
                                      thread="decode", phase=phase)
    return out


def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


def _by_hand(model, **kw):
    """An engine whose threads never start: the test runs the prefill and
    each iteration of the decode loop itself."""
    from ray_tpu.serve.engine import Request

    engine = _engine(model, **kw)
    engine._ensure_loop = lambda: None

    def prefill(lens, max_tokens, seed):
        reqs = [Request(f"r{seed}-{i}", p, max_tokens=max_tokens)
                for i, p in enumerate(_prompts(model[1], lens, seed=seed))]
        for r in reqs:
            engine.add_request(r)
        engine._prefill_batch([engine.pending.get() for _ in reqs])
        return reqs

    return engine, prefill


def test_the_ledgers_parts_sum_to_the_decode_stage(model):
    engine = _engine(model, max_seq_len=256, max_pages=96)
    lens = (5, 20, 40, 70, 100, 12, 33, 64)

    def run(seed):
        prompts = _prompts(model[1], lens, seed=seed)
        threads = [threading.Thread(
            target=lambda p=p: engine.generate(p, max_tokens=60))
            for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        # the last iteration closes its row after the last answer is out
        while engine._has_work() or engine._live_at_end:
            time.sleep(0.01)
        time.sleep(0.05)

    run(4)  # every shape compiled, inside no phase of the measured run
    before = _ledger()
    run(5)
    d = _delta(before, _ledger())
    engine.stop()
    # 8 answers of 15 spans over 4 slots: they overlap, join and leave
    assert d["decode_stage"] > 0 and all(d[p] > 0 for p in PARTS)
    assert sum(d[p] for p in PARTS) == pytest.approx(d["decode_stage"],
                                                     rel=0.01)
    # the device's share of a span is in the spans' wall time too
    assert d["span_s", "0"] + d["span_s", "1"] == pytest.approx(
        d["loop", "dispatch"] + d["loop", "readback"], rel=1e-6)
    assert (d["span_n", "0"] + d["span_n", "1"]) % SPAN == 0


def test_an_iteration_with_three_live_slots_adds_three_times_its_phases(
        model):
    engine, prefill = _by_hand(model)
    prefill((5, 9, 14), 40, seed=6)
    engine._iterate()  # installs the three and decodes a span
    assert engine.stats()["active"] == 3
    between = tracing.now_ns()
    time.sleep(0.02)  # the time between two iterations counts
    before = _ledger()
    engine._iterate()
    d = _delta(before, _ledger())
    engine.stop()
    loop = {k[1]: v for k, v in d.items() if isinstance(k, tuple)
            and k[0] == "loop"}
    assert d["device_wait"] == pytest.approx(3 * loop["readback"])
    assert d["dispatch"] == pytest.approx(3 * loop["dispatch"])
    assert d["host"] == pytest.approx(3 * (
        loop["install"] + loop["cancel_check"] + loop["build"]
        + loop["commit"]))
    assert d["chunk_host"] == pytest.approx(3 * loop["chunk"])
    assert d["chunk_device_wait"] == 0
    phases = sum(loop[p] for p in ("chunk", "install", "cancel_check",
                                   "build", "dispatch", "readback", "commit"))
    waited = (tracing.now_ns() - between) * 1e-9
    assert 3 * (loop["iter"] - phases + 0.02) <= d["loop"] <= 3 * waited
    assert sum(d[p] for p in PARTS) <= 3 * waited
    # three sequences, no prefill since the last span: 4 clean steps
    assert (d["span_n", "0"], d["span_n", "1"]) == (SPAN, 0)
    assert d["span_s", "0"] == pytest.approx(
        loop["dispatch"] + loop["readback"])
    assert _counter("serve_decode_span_steps", live_le="4", prefill="0") > 0


def test_a_span_after_prefill_counts_as_shared_with_its_tokens(model):
    engine, prefill = _by_hand(model)
    prefill((5, 9), 40, seed=7)
    before = _ledger()
    engine._iterate()
    d = _delta(before, _ledger())
    # the bucket program of the two prompts went out before the first span:
    # 2 rows of the 16-token bucket, and two sequences waited behind them
    assert (d["span_n", "0"], d["span_n", "1"]) == (0, SPAN)
    assert d["interleaved"] == 2 * (2 * 16)
    before = _ledger()
    engine._iterate()
    d = _delta(before, _ledger())
    assert (d["span_n", "0"], d["span_n", "1"]) == (SPAN, 0)
    assert d["interleaved"] == 0
    # a prompt of three chunks: each iteration runs one chunk of 32, then
    # a span of the two live sequences
    prefill((70,), 4, seed=8)
    for live in (2, 2, 3):  # the last chunk's sequence joins its span
        before = _ledger()
        engine._iterate()
        d = _delta(before, _ledger())
        assert (d["span_n", "0"], d["span_n", "1"]) == (0, SPAN)
        assert d["interleaved"] == live * 32
        assert d["chunk_host"] > 0
    assert d["chunk_device_wait"] > 0  # the last chunk reads its logits back
    engine.stop()


def test_dispatch_carries_the_live_slots_and_splits_put_from_call(traced_run):
    from benchmark import program_spans

    spans = program_spans.read_file(traced_run["xplane"])
    dispatches = spans.named("engine.dispatch")
    assert dispatches
    for r in dispatches:
        assert 1 <= int(r.attrs["live"]) <= B
        assert int(r.attrs["steps"]) == SPAN
        assert int(r.attrs["prefill_tokens"]) % 16 == 0
        assert [c.name for c in r.children] == [
            "engine.dispatch.put", "engine.dispatch.call"]
    # both prefill paths ran beside the decode spans
    assert any(int(r.attrs["prefill_tokens"]) > 0 for r in dispatches)
    chunks = [r for r in spans.named("engine.chunk") if r.children]
    assert chunks
    for r in chunks:
        assert [c.name for c in r.children][:2] == [
            "engine.chunk.put", "engine.chunk.call"]


def test_no_annotation_is_built_without_a_profiler_session(model,
                                                           monkeypatch):
    built = []

    class Annotation:
        enabled = False

        def __init__(self, name, **attrs):
            built.append((name, attrs))

        @classmethod
        def is_enabled(cls):
            return cls.enabled

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_annotation", Annotation)
    engine, prefill = _by_hand(model)
    prefill((5,), 40, seed=9)
    engine._iterate()
    assert built == []
    Annotation.enabled = True
    engine._iterate()
    engine.stop()
    names = [n for n, _ in built]
    assert {"engine.iter", "engine.dispatch", "engine.dispatch.put",
            "engine.dispatch.call"} <= set(names)
    assert dict(built)["engine.dispatch"] == {
        "live": 1, "steps": SPAN, "prefill_tokens": 0}
