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
             "engine.build", "engine.dispatch", "engine.chunk.readback",
             "engine.readback", "engine.commit"}
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
    # a prompt's last chunk is not waited for where it is dispatched: its
    # first token is read in the iteration itself, once the span that the
    # sequence joined is out and before the span before it is read
    behind = [names for names in (
        [c.name for c in it.children] for it in iters)
        if "engine.chunk.readback" in names]
    assert any("engine.dispatch" in names for names in behind)
    for names in behind:  # (no dispatch: every live slot ends in the span
        at = names.index("engine.chunk.readback")  # that is still unread)
        assert names[at - 1] in ("engine.dispatch", "engine.build")
        after = [n for n in names[at:] if n != "engine.chunk.readback"]
        assert after[:1] in ([], ["engine.readback"])
    assert not any(c.name == "engine.chunk.readback"
                   for r in spans.named("engine.chunk") for c in r.children)
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


def _overlapping(engine, model, lens, max_tokens, seed):
    """Answers to prompts of `lens`, all asked at once; returns when the
    decode loop has closed the last iteration's row."""
    out = {}
    prompts = _prompts(model[1], lens, seed=seed)
    threads = [threading.Thread(
        target=lambda i=i, p=p: out.__setitem__(
            i, engine.generate(p, max_tokens=max_tokens)))
        for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    while engine._has_work() or engine._live_at_end:
        time.sleep(0.01)
    time.sleep(0.05)
    return [out[i] for i in range(len(prompts))]


def test_the_ledgers_parts_sum_to_the_decode_stage(model):
    engine = _engine(model, max_seq_len=256, max_pages=128)
    lens = (5, 20, 40, 70, 100, 12, 33, 64)
    _overlapping(engine, model, lens, 120, seed=4)  # every shape compiled
    before, t0 = _ledger(), tracing.now_ns()
    _overlapping(engine, model, lens, 120, seed=5)
    d, wall = _delta(before, _ledger()), (tracing.now_ns() - t0) * 1e-9
    engine.stop()
    # 8 answers of 30 spans over 4 slots: they overlap, join and leave.
    # Whether a host phase ran under an unfinished span (and so is
    # `device_wait`) or not is the machine's to say; the sum is not
    assert d["decode_stage"] > 0
    assert d["device_wait"] > 0 and d["chunk_host"] + d["chunk_device_wait"] > 0
    assert sum(d[p] for p in PARTS) == pytest.approx(d["decode_stage"],
                                                     rel=0.01)
    # a span's wall time is its stay on the device's queue: from its
    # dispatch, or the readback before it, to its own readback. So the
    # spans' times are disjoint pieces of the run that hold every readback
    assert d["loop", "readback"] <= d["span_s", "0"] + d["span_s", "1"] <= wall
    assert (d["span_n", "0"] + d["span_n", "1"]) % SPAN == 0


@pytest.mark.parametrize("busy", [False, True],
                         ids=["device_idle", "span_unfinished"])
def test_an_iteration_with_three_live_slots_adds_three_times_its_phases(
        model, busy):
    engine, prefill = _by_hand(model)
    prefill((5, 9, 14), 40, seed=6)
    engine._iterate()  # installs the three and dispatches their first span
    assert engine.stats()["active"] == 3 and engine._inflight is not None
    second = tracing.now_ns()
    engine._iterate()  # the second goes out, the first is read and committed
    # what the sequences waited for while a host phase ran, by decree: on
    # the CPU a span of this model is over before the host looks
    engine._device_busy = lambda: busy
    time.sleep(0.02)  # the time between two iterations counts
    before = _ledger()
    engine._iterate()
    d = _delta(before, _ledger())
    engine.stop()
    loop = {k[1]: v for k, v in d.items() if isinstance(k, tuple)
            and k[0] == "loop"}
    host = (loop["install"] + loop["cancel_check"] + loop["build"]
            + loop["commit"])
    phases = sum(loop[p] for p in ("chunk", "install", "cancel_check",
                                   "build", "dispatch", "readback", "commit"))
    # an upper bound that a descheduled test cannot break: everything
    # since before the second iteration
    waited = (tracing.now_ns() - second) * 1e-9
    rest = 3 * (loop["iter"] - phases + 0.02)  # and the time between two
    if busy:
        # the device set the pace: nothing is the host's
        assert d["host"] == d["dispatch"] == d["chunk_host"] == d["loop"] == 0
        assert d["chunk_device_wait"] == pytest.approx(3 * loop["chunk"])
        assert 3 * (loop["readback"] + loop["dispatch"] + host) + rest \
            <= d["device_wait"] <= 3 * waited
    else:
        assert d["device_wait"] == pytest.approx(3 * loop["readback"])
        assert d["dispatch"] == pytest.approx(3 * loop["dispatch"])
        assert d["host"] == pytest.approx(3 * host)
        assert d["chunk_host"] == pytest.approx(3 * loop["chunk"])
        assert d["chunk_device_wait"] == 0
        assert rest <= d["loop"] <= 3 * waited
    assert sum(d[p] for p in PARTS) <= 3 * waited
    # the span read in this iteration is the second: three sequences, no
    # prefill before it since the first, 4 clean steps; on the queue from
    # the first's readback to its own, the sleep between the two included
    assert (d["span_n", "0"], d["span_n", "1"]) == (SPAN, 0)
    assert 0.02 + loop["readback"] <= d["span_s", "0"] <= waited
    assert _counter("serve_decode_span_steps", live_le="4", prefill="0") > 0


def test_a_span_after_prefill_counts_as_shared_with_its_tokens(model):
    engine, prefill = _by_hand(model)
    prefill((5, 9), 40, seed=7)

    def iterate():
        before = _ledger()
        engine._iterate()
        return _delta(before, _ledger())

    # a span is filed when it is read back, an iteration after it went out
    d = iterate()
    assert (d["span_n", "0"], d["span_n", "1"], d["interleaved"]) == (0, 0, 0)
    # the bucket program of the two prompts went out before the first span:
    # 2 rows of the 16-token bucket, and two sequences waited behind them
    d = iterate()
    assert (d["span_n", "0"], d["span_n", "1"]) == (0, SPAN)
    assert d["interleaved"] == 2 * (2 * 16)
    d = iterate()
    assert (d["span_n", "0"], d["span_n", "1"]) == (SPAN, 0)
    assert d["interleaved"] == 0
    # a prompt of three chunks: each iteration runs one chunk of 32, then
    # dispatches a span of the live sequences, and reads the span before
    prefill((70,), 4, seed=8)
    d = iterate()  # chunk 1; the span read went out before it
    assert (d["span_n", "0"], d["span_n", "1"]) == (SPAN, 0)
    # the last chunk's sequence is installed in the iteration that ran the
    # chunk and joins the span dispatched there, which the next one reads
    for live in (2, 2, 3):
        d = iterate()
        assert (d["span_n", "0"], d["span_n", "1"]) == (0, SPAN)
        assert d["interleaved"] == live * 32
        if live == 2:
            assert d["chunk_host"] + d["chunk_device_wait"] > 0
    d = iterate()  # its 4 tokens end inside that span: it is in no other
    assert (d["span_n", "0"], d["span_n", "1"]) == (SPAN, 0)
    assert _counter("serve_decode_span_steps", live_le="2", prefill="0") > 0
    engine.stop()


def test_the_last_chunk_reads_its_logits_back_as_device_wait(model):
    engine, prefill = _by_hand(model)
    prefill((5,), 40, seed=10)
    engine._iterate()
    prefill((40,), 4, seed=11)  # two chunks of 32
    engine._iterate()
    before = _ledger()
    engine._iterate()
    d = _delta(before, _ledger())
    engine.stop()
    # one sequence waited through it (float sums: to a part in a million)
    assert d["loop", "chunk_readback"] > 0
    assert d["chunk_device_wait"] >= (1 - 1e-6) * d["loop", "chunk_readback"]


# -- one span ahead -----------------------------------------------------------


def _reference(model, prompt, max_tokens, **kw):
    engine = _engine(model, **kw)
    out = engine.generate(prompt, max_tokens=max_tokens)
    engine.stop()
    return out["token_ids"]


def _watched(engine):
    """Log the engine's readbacks ("read", the span's requests) and page
    frees ("free", the pages) in the order they happen."""
    log = []
    finish, free = engine._finish_span, engine._free_pages_and_revive

    def finish_span(span):
        log.append(("read", [r.request_id for r in span.members.values()]))
        return finish(span)

    def free_pages(pages):
        log.append(("free", list(pages)))
        return free(pages)

    engine._finish_span, engine._free_pages_and_revive = finish_span, \
        free_pages
    return log


def test_an_ending_by_max_tokens_is_left_out_of_the_next_span(model):
    engine, prefill = _by_hand(model)
    short, = prefill((5,), 1 + SPAN, seed=20)  # ends inside its first span
    long_, = prefill((9,), 40, seed=21)
    engine._iterate()
    assert list(engine._inflight.members.values()) == [short, long_]
    held = engine.stats()["free_pages"]
    log = _watched(engine)
    engine._iterate()
    # the second span went out without it, BEFORE the first was read
    assert list(engine._inflight.members.values()) == [long_]
    assert short.done.is_set() and short.finish_reason == "length"
    assert len(short.output) == 1 + SPAN
    # and its pages were freed at that commit: nothing rode a span
    assert [e[0] for e in log] == ["read", "free"]
    assert engine.stats()["free_pages"] == held + len(log[1][1]) > held
    assert engine._inflight.release == []
    engine.stop()


@pytest.mark.parametrize("ending", ["stop", "eos", "cancel"])
def test_an_unforeseen_ending_rides_one_span_and_emits_nothing_from_it(
        model, ending):
    (prompt,) = _prompts(model[1], (9,), seed=22)
    ref = _reference(model, prompt, 40)
    at = next(i for i in range(2, SPAN + 1) if ref[i] not in ref[:i])
    from ray_tpu.serve.engine import Request

    engine = _engine(model, **(
        {"eos_token_id": ref[at]} if ending == "eos" else {}))
    engine._ensure_loop = lambda: None
    req = Request("r", prompt, max_tokens=40,
                  stop=[[ref[at]]] if ending == "stop" else None)
    emitted = []
    req._emit = emitted.append
    engine.add_request(req)
    engine._prefill_batch([engine.pending.get()])
    free0 = engine.stats()["free_pages"]
    log = _watched(engine)
    engine._iterate()  # span 1 goes out
    engine._iterate()  # span 2 goes out with it; span 1 is read, committed
    if ending == "cancel":
        assert emitted == ref[:1 + SPAN] and not req.done.is_set()
        engine.cancel("r")
        engine._iterate()  # swept at the iteration's start: span 2 is unread
        assert req.finish_reason == "cancelled"
        assert req.output == ref[:1 + SPAN]
    else:
        # it ended inside span 1, which nobody could know when span 2 went
        assert req.done.is_set() and req.finish_reason == "stop"
        assert req.output == ref[:at] and emitted == ref[:at] + [None]
        # finished, and its pages still ride span 2
        assert engine.stats()["free_pages"] == free0
        assert engine._inflight is not None and engine._inflight.release
        engine._iterate()  # nothing live: the loop drains
    assert engine._inflight is None and not engine._has_work()
    # span 2 was read back before the pages went, and gave the request
    # nothing
    assert [e[0] for e in log] == ["read", "read", "free"]
    assert log[1][1] == ["r"]
    assert emitted[-1] is None and len(emitted) == len(req.output) + 1
    assert engine.stats()["free_pages"] > free0
    engine.stop()


def test_a_slot_taken_again_gets_none_of_the_span_its_last_holder_rode(model):
    first, second = _prompts(model[1], (9, 12), seed=23)
    ref1 = _reference(model, first, 40, max_batch_size=1)
    ref2 = _reference(model, second, 1 + 2 * SPAN, max_batch_size=1)
    at = next(i for i in range(2, SPAN + 1) if ref1[i] not in ref1[:i])
    from ray_tpu.serve.engine import Request

    engine = _engine(model, max_batch_size=1)
    engine._ensure_loop = lambda: None
    r1 = Request("r1", first, max_tokens=40, stop=[[ref1[at]]])
    r2 = Request("r2", second, max_tokens=1 + 2 * SPAN)
    for r in (r1, r2):
        engine.add_request(r)
    engine._prefill_batch([engine.pending.get(), engine.pending.get()])
    engine._iterate()  # r1 takes the one slot; span 1
    engine._iterate()  # span 2 with r1; commit 1 ends r1 by its stop
    assert r1.done.is_set() and engine.slots[0].request is None
    assert engine._inflight.members == {0: r1}
    engine._iterate()  # r2 takes the slot; span 3; span 2 is committed
    assert engine.slots[0].request is r2
    assert r2.output == ref2[:1]  # r1's column of span 2 went nowhere
    assert r1.output == ref1[:at]
    engine._iterate()
    engine._iterate()
    assert r2.done.is_set() and r2.output == ref2
    engine.stop()


def test_update_params_commits_the_span_in_flight_under_the_old_version(
        model):
    params, cfg = model
    (prompt,) = _prompts(cfg, (9,), seed=24)
    ref = _reference(model, prompt, 40)
    from ray_tpu.serve.engine import Request

    engine = _engine(model)
    engine._ensure_loop = lambda: None
    req = Request("r", prompt, max_tokens=40)
    seen = []
    req._emit = lambda tok: seen.append((tok, engine.weights_version))
    engine.add_request(req)
    engine._prefill_batch([engine.pending.get()])
    engine._iterate()  # span 1 is on the device, computed by version 0
    assert engine._inflight is not None
    other = init_params(cfg, jax.random.PRNGKey(1))
    assert engine.update_params(other) == 1
    # drained first: what version 0 computed is committed as version 0's
    assert engine._inflight is None and engine.weights_version == 1
    assert seen == [(t, 0) for t in ref[:1 + SPAN]]
    engine._iterate()
    engine._iterate()
    engine.stop()
    later = seen[1 + SPAN:]
    assert len(later) == SPAN and all(v == 1 for _t, v in later)
    assert [t for t, _v in later] != ref[1 + SPAN:1 + 2 * SPAN]


def test_update_params_with_no_loop_thread_takes_no_time_from_the_ledger(
        model):
    """The drain `update_params` runs on the caller's thread is outside
    every iteration: its readback was filed into the NEXT iteration's row
    and taken from that iteration's own time (`rest` in `_account`, some
    -18 ms), which a part of `serve_token_wait_seconds` then refused
    (`counters only increase`) whenever the device's state put it beside
    nothing larger: on a decode thread, the thread's end. 200 rounds of the
    test above's sequence on one engine: no part ever goes back."""
    params, cfg = model
    from ray_tpu.serve.engine import Request

    (prompt,) = _prompts(cfg, (9,), seed=24)
    engine = _engine(model)
    engine._ensure_loop = lambda: None
    parts = _ledger()
    for i in range(200):
        req = Request(f"r{i}", prompt, max_tokens=1 + 2 * SPAN)
        engine.add_request(req)
        engine._prefill_batch([engine.pending.get()])
        engine._iterate()  # a span on the device
        assert engine._inflight is not None
        assert engine.update_params(params) == i + 1  # drained, outside
        while engine._has_work():
            engine._iterate()  # the first of them closes the row at fault
        assert req.finish_reason == "length" and req.error is None
        now = _ledger()
        assert all(now[p] >= parts[p] for p in PARTS), (i, parts, now)
        parts = now
    engine.stop()


def test_update_params_from_another_thread_while_the_loop_runs(model):
    params, cfg = model
    engine = _engine(model, max_seq_len=256, max_pages=96)
    seen = {}

    def ask(i, p):
        req, stream = engine.open_stream(p, max_tokens=60)
        seen[i] = [(tok, engine.weights_version) for tok in stream]

    prompts = _prompts(cfg, (5, 20, 40, 12), seed=28)
    threads = [threading.Thread(target=ask, args=(i, p))
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    versions = []
    for _ in range(3):  # swaps land between iterations of a busy loop
        time.sleep(0.02)
        versions.append(engine.update_params(params))
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    engine.stop()
    assert versions == [1, 2, 3] and engine.weights_version == 3
    for i in range(4):
        assert len(seen[i]) == 60
        stamps = [v for _tok, v in seen[i]]
        assert stamps == sorted(stamps)  # a reader never sees one go back
    # a stopped engine binds on the caller's thread
    engine._loop_thread.join(timeout=30)
    assert not engine._loop_thread.is_alive()
    assert engine.update_params(params) == 4


def _ahead():
    return (_counter("serve_decode_ahead_steps"),
            _counter("serve_decode_span_steps"))


def test_with_speculation_no_span_is_dispatched_ahead(model):
    engine = _engine(model, max_seq_len=256, max_pages=96,
                     speculation={"mode": "ngram",
                                  "num_speculative_tokens": 3})
    # Two things, asserted apart. Depth: no span goes out with another
    # unread. The counter: it counts a span that found the device fed, and
    # at depth 0 only a chunk that is not its prompt's last (the 40-token
    # prompt's first: the last is read where it goes out) can have fed it
    unread, behind_a_chunk, chunked = [], [0], [False]
    advance, open_span = engine._advance_chunks, engine._open_span

    def advance_chunks():
        chunked[0] = advance()
        return chunked[0]

    def opened(steps, members=None):
        unread.append(engine._inflight)
        if chunked[0] and members is not None:  # a plain span, no round
            behind_a_chunk[0] += steps
        return open_span(steps, members)

    engine._advance_chunks, engine._open_span = advance_chunks, opened
    ahead0, steps0 = _ahead()
    outs = _overlapping(engine, model, (5, 20, 40, 12), 24, seed=25)
    engine.stop()
    ahead, steps = _ahead()
    assert all(len(o["token_ids"]) == 24 for o in outs)
    assert steps > steps0
    assert unread and all(span is None for span in unread)
    assert ahead - ahead0 <= behind_a_chunk[0]


def test_eight_requests_over_four_slots_keep_the_loop_a_span_ahead(model):
    engine = _engine(model, max_seq_len=256, max_pages=96, decode_span=8,
                     busy_span=4, adaptive_span=True)
    lens = (5, 20, 40, 70, 100, 12, 33, 64)
    _overlapping(engine, model, lens, 60, seed=26)  # every shape compiled
    ahead0, steps0 = _ahead()
    _overlapping(engine, model, lens, 60, seed=27)
    engine.stop()
    ahead, steps = _ahead()
    assert steps > steps0
    assert ahead - ahead0 > 0.5 * (steps - steps0)


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
    held = set()
    for r in chunks:
        assert [c.name for c in r.children][:2] == [
            "engine.chunk.put", "engine.chunk.call"]
        # a chunk's rows, and the tokens they hold
        call = r.children[1]
        assert int(call.attrs["padded"]) == 32
        assert 0 < int(call.attrs["tokens"]) <= 32
        held.add(int(call.attrs["tokens"]))
    assert 32 in held and min(held) < 32  # full chunks, and a prompt's last


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
