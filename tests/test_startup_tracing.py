"""A replica's start traced from inside (PR 50): `start_engine` opens ONE
trace, `replica.start`, whose regions tile it and whose `xla.*` spans say
which program took which stage's seconds; the regions' seconds are
`serve_replica_start_seconds{phase}`; the controller observes spawn to
readiness; the trainer files `train.start`; and the benchmark's readers of
`setup_s`'s parts read those series from a snapshot at the window's start."""

import time

import pytest

from benchmark import common
from ray_tpu.core.metrics import registry
from ray_tpu.util import tracing

ENGINE = dict(page_size=4, prefill_buckets=(8, 16), prefill_chunk=16,
              max_batch_size=4, max_pages=64)
# what `warmup` compiles for tiny-lfm2 under ENGINE: the decode program of
# each sampler (a span's length is their argument since PR 53, where two
# lengths x two samplers were four programs), both chunk programs (its
# experts run as groups: `_wide`), and the program that hands a slot its conv
# state, and the one that hands a slot's row of the loop's carry a first
# token from the device
PROGRAMS = {"decode_span", "decode_span_adv", "chunk_prefill_16",
            "chunk_prefill_32", "join_carry", "install_state"}
PHASES = {"params": "replica.start.params", "engine": "replica.start.engine",
          "warmup": "engine.warmup"}


def _phase_seconds():
    m = registry.get("serve_replica_start_seconds")
    return {phase: m.get({"phase": phase}) for phase in PHASES}


@pytest.fixture(scope="module")
def started():
    """One tiny replica's start: the server, its trace as a tree, and what
    the start added to each phase's counter."""
    import jax

    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.llm import LLMServer

    def weights():
        # a jit of its own, so the loader compiles whatever this process
        # has compiled before
        cfg = get_config("tiny-lfm2")
        return jax.jit(lambda key: init_params(cfg, key))(
            jax.random.PRNGKey(0)), cfg

    before = _phase_seconds()
    server = LLMServer._target(params_fn=weights, engine_config=ENGINE)
    after = _phase_seconds()
    trace_id = server.engine.stats()["startup_trace_id"]
    tree = tracing.get_trace(trace_id)
    yield server, tree, {p: after[p] - before[p] for p in PHASES}
    server.shutdown()


def _seconds(span):
    return (span["end_us"] - span["start_us"]) / 1e6


def _walk(node):
    yield node
    for child in node["children"]:
        yield from _walk(child)


def test_a_replicas_start_is_one_trace_whose_phases_tile_the_root(started):
    server, tree, _ = started
    (root,) = tree
    assert root["name"] == "replica.start" and root["parent_id"] is None
    assert root["attrs"] == {"role": "colocated"}
    phases = [c for c in root["children"] if c["name"] in PHASES.values()]
    assert [c["name"] for c in phases] == list(PHASES.values())
    # one after the other, and together the root within 5%
    assert all(a["end_us"] <= b["start_us"] + 1.0
               for a, b in zip(phases, phases[1:]))
    assert sum(map(_seconds, phases)) == pytest.approx(_seconds(root),
                                                       rel=0.05)
    assert sum(map(_seconds, phases)) <= _seconds(root)
    params, engine, warmup = phases
    assert params["attrs"]["bytes"] > 0
    assert engine["attrs"]["pool_bytes"] == (
        server.engine.k_pages.nbytes + server.engine.v_pages.nbytes)
    assert engine["attrs"]["state_bytes"] == server.engine.stats()[
        "state_bytes"] > 0
    assert warmup["attrs"] == {}  # its programs are its children


def test_every_program_warmup_compiled_is_a_region_that_names_it(started):
    _, (root,), _ = started
    (warmup,) = [c for c in root["children"] if c["name"] == "engine.warmup"]
    regions = [c for c in warmup["children"]
               if c["name"] == "engine.warmup.program"]
    assert len(regions) == len(warmup["children"])  # nothing beside them
    assert {r["attrs"]["program"] for r in regions} == PROGRAMS
    assert len(regions) == len(PROGRAMS)
    by_program = {r["attrs"]["program"]: r for r in regions}
    # warmed at K, the longest span the loop picks (`EngineConfig.span_rows`)
    assert by_program["decode_span_adv"]["attrs"]["steps"] == 8
    assert by_program["chunk_prefill_32"]["attrs"]["rows"] == 32
    # the regions tile the warm-up (what is left is the loop around them)
    assert sum(map(_seconds, regions)) == pytest.approx(_seconds(warmup),
                                                        rel=0.05)
    for program, region in by_program.items():
        stages = {c["name"]: c for c in region["children"]
                  if c["attrs"].get("program") == program}
        # its own jit was traced, lowered and compiled inside ITS region
        assert set(stages) == {"xla.trace", "xla.lower", "xla.compile"}, (
            program, [c["name"] for c in region["children"]])
        assert all(c["attrs"]["cache"] in ("hit", "miss", "off")
                   for c in stages.values())
        assert sum(map(_seconds, stages.values())) <= _seconds(region)
    # and no program of the engine's compiled anywhere else in the start
    for span in _walk(root):
        if span["name"].startswith("xla.") and \
                span["attrs"]["program"] in PROGRAMS:
            assert span["parent_id"] == by_program[
                span["attrs"]["program"]]["span_id"]


def test_every_xla_span_of_the_start_carries_its_program(started):
    _, (root,), _ = started
    spans = [s for s in _walk(root) if s["name"].startswith("xla.")]
    assert len(spans) >= 3 * len(PROGRAMS)
    assert all(isinstance(s["attrs"]["program"], str)
               and s["attrs"]["program"] and "jit(" not in s["attrs"]["program"]
               for s in spans)
    assert {s["name"] for s in spans} == {"xla.trace", "xla.lower",
                                          "xla.compile"}
    # the weights' loader compiles under the params phase
    under_params = [s for s in _walk(root["children"][0])
                    if s["name"] == "xla.compile"]
    assert "<lambda>" in {s["attrs"]["program"] for s in under_params}


def test_the_phase_counters_are_the_regions_seconds(started):
    _, (root,), added = started
    by_name = {c["name"]: c for c in root["children"]}
    for phase, region in PHASES.items():
        assert added[phase] == pytest.approx(_seconds(by_name[region]),
                                             abs=1e-5)
    assert sum(added.values()) <= _seconds(root)


def test_an_engine_built_bare_has_no_startup_trace_and_files_no_phase(
        started):
    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    import jax

    server, tree, _ = started
    cfg = get_config("tiny-llama")
    engine = InferenceEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                             EngineConfig(page_size=4, max_pages=16,
                                          max_batch_size=2,
                                          prefill_buckets=(8,)))
    assert engine.stats()["startup_trace_id"] is None
    assert engine.startup_trace == []
    assert len(server.engine.stats()["startup_trace_id"]) == 32
    # the phases are `start_engine`'s: a later warm-up of more shapes is
    # not a replica's start, and adds to none of them
    before = _phase_seconds()
    with tracing.start_span("later") as root:
        engine.warmup(buckets=[8], batch_sizes=[1])
    assert _phase_seconds() == before
    # its programs are regions all the same, children on a traced thread
    (later,) = tracing.get_trace(root.trace_id)
    programs = [c["attrs"] for c in later["children"]
                if c["name"] == "engine.warmup.program"]
    assert programs[0] == {"program": "prefill_bucket_8x1", "rows": 8}
    assert [p for p in programs if p["program"].startswith("decode_span")] \
        == [{"program": "decode_span", "steps": 8},
            {"program": "decode_span_adv", "steps": 8}]  # one a sampler
    engine.stop()


def test_the_start_outlives_the_span_ring(started):
    server, tree, _ = started
    trace_id = server.engine.stats()["startup_trace_id"]
    kept = server.startup_trace()
    assert [s["span_id"] for s in _walk(kept[0])] == [
        s["span_id"] for s in _walk(tree[0])]
    spans = tracing.get_spans()
    try:
        tracing.clear()  # as 10,000 later spans would
        assert tracing.get_trace(trace_id) == []
        assert server.startup_trace() == kept
    finally:
        tracing.ingest(spans)


def test_speculations_programs_are_regions_of_the_warm_up():
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer._target(
        model_name="tiny-llama",
        engine_config=dict(page_size=4, max_pages=32, max_batch_size=2,
                           prefill_buckets=(8,)),
        speculation={"mode": "draft", "num_speculative_tokens": 2})
    try:
        (root,) = server.startup_trace()
        programs = [s["attrs"]["program"] for s in _walk(root)
                    if s["name"] == "engine.warmup.program"]
        assert programs[-4:] == ["draft.chunk_step", "draft.propose",
                                 "verify_2", "verify_2_adv"]
    finally:
        server.shutdown()


def test_the_openai_replica_starts_through_the_same_trace():
    from ray_tpu.serve.openai_api import OpenAIServer

    before = _phase_seconds()
    server = OpenAIServer._target(
        model_name="tiny-llama",
        engine_config=dict(page_size=4, max_pages=16, max_batch_size=2,
                           prefill_buckets=(8,), chunked_prefill=False))
    try:
        (root,) = tracing.get_trace(server.engine.stats()["startup_trace_id"])
        assert root["name"] == "replica.start"
        assert [c["name"] for c in root["children"]
                if c["name"] in PHASES.values()] == list(PHASES.values())
        assert all(v > before[p] for p, v in _phase_seconds().items())
        assert server.engine.ecfg.eos_token_id == server.tokenizer.eos_token_id
    finally:
        server.engine.stop()


def test_the_controller_observes_spawn_to_readiness_once_a_replica(
        ray_start_regular):
    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class Slow:
        def __init__(self):
            time.sleep(0.3)

        def __call__(self, request):
            return 1

    ready = registry.get("serve_replica_ready_seconds")
    tags = {"deployment": "slow-start"}
    try:
        handle = serve.run(Slow.options(name="slow-start").bind(), name="s")
        assert handle.remote({}).result(timeout=120) == 1
        deadline = time.monotonic() + 30
        while ready.count(tags) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(1.0)  # four more reconcile periods observe nothing more
        assert ready.count(tags) == 2
        # each waited for its __init__, and for little else
        assert 0.6 <= ready.sum(tags) < 20.0
    finally:
        serve.shutdown()


def test_the_trainers_start_is_one_region_to_the_loops_first_line(
        ray_start_regular, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    seen = {}

    def loop(config):
        from ray_tpu import train

        seen[train.get_context().get_world_rank()] = tracing.now_ns()
        train.report({"ok": 1})

    started = registry.get("train_start_seconds")
    before = started.get({"phase": "gang"})
    with tracing.start_span("root") as root:
        t0 = tracing.now_ns()
        result = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=2,
                                         workers_in_process=True),
            run_config=RunConfig(name="s", storage_path=str(tmp_path)),
        ).fit()
    assert result.error is None and set(seen) == {0, 1}
    added = started.get({"phase": "gang"}) - before
    # rank 0's alone: fit()'s entry to its loop's first line
    assert 0 < added <= (seen[0] - t0) / 1e9
    (span,) = [s for s in tracing.get_spans(root.trace_id)
               if s["name"] == "train.start"]
    assert _seconds(span) == pytest.approx(added, abs=1e-5)
    assert t0 <= span["start_us"] * 1e3 and span["end_us"] * 1e3 <= seen[0]


# -- the benchmark's readers of `setup_s`'s parts ---------------------------

AT_START = {
    ("serve_replica_ready_seconds_sum", (("deployment", "llm"),)): 11.0,
    ("serve_replica_ready_seconds_count", (("deployment", "llm"),)): 1.0,
    ("serve_replica_start_seconds", (("phase", "params"),)): 3.0,
    ("serve_replica_start_seconds", (("phase", "engine"),)): 0.5,
    ("serve_replica_start_seconds", (("phase", "warmup"),)): 7.0,
    ("xla_program_seconds", (("cache", "off"), ("stage", "trace"),
                             ("under", "engine.warmup.program"))): 2.0,
    ("xla_program_seconds", (("cache", "off"), ("stage", "trace"),
                             ("under", "prefill.run"))): 0.5,
    ("xla_program_seconds", (("cache", "off"), ("stage", "lower"),
                             ("under", "engine.warmup.program"))): 1.25,
    ("xla_program_seconds", (("cache", "hit"), ("stage", "compile"),
                             ("under", "engine.warmup.program"))): 0.75,
    ("xla_program_seconds", (("cache", "miss"), ("stage", "compile"),
                             ("under", "none"))): 30.0,
    ("xla_programs", (("cache", "hit"), ("stage", "compile"),
                      ("under", "engine.warmup.program"))): 6.0,
    ("xla_programs", (("cache", "miss"), ("stage", "compile"),
                      ("under", "none"))): 2.0,
    ("xla_programs", (("cache", "off"), ("stage", "trace"),
                      ("under", "none"))): 90.0,
    ("serve_tokens_generated", ()): 12.0,
}
# the window's end: what it adds is not the set-up's
AT_END = {k: v * 3 for k, v in AT_START.items()}
# in the manifest's order
READ = {"setup_replica_ready_s": 11.0, "setup_params_s": 3.0,
        "setup_engine_init_s": 0.5, "setup_warmup_s": 7.0,
        "setup_trace_lower_s": 3.75, "setup_compile_s": 30.75,
        "setup_cache_hit_share": 75.0}


@pytest.mark.parametrize("name", sorted(READ))
def test_a_setup_reader_reads_the_snapshot_at_the_windows_start(name):
    read = common.load_reader(name)
    assert read({"counters": (AT_START, AT_END)}) == pytest.approx(READ[name])
    # a program from before PR 50 has no such series: nothing, not 0
    parent = {k: v for k, v in AT_START.items()
              if k[0] == "serve_tokens_generated"}
    assert read({"counters": (parent, parent)}) is None
    assert read({"counters": None}) is None and read({}) is None


def test_the_trainers_reader_reads_the_registry_at_the_runs_end(monkeypatch):
    read = common.load_reader("setup_trainer_start_s")
    monkeypatch.setattr(common, "counters", lambda: {
        ("train_start_seconds", (("phase", "gang"),)): 0.75,
        ("xla_compiles", (("under", "none"),)): 4.0})
    assert read({"counters": None}) == 0.75
    monkeypatch.setattr(common, "counters", lambda: {
        ("xla_compiles", (("under", "none"),)): 4.0})
    assert read({"counters": None}) is None


def test_the_eight_are_entries_that_move_setup_s_in_their_cells():
    # the manifest as PR 50 left it: PR 52's cell and its reader, and PR
    # 54's and PR 58's cells, appended behind these
    # (tests/test_solar_open2_model.py holds that its cell is listed by all
    # seven serve entries, tests/test_trinity_model.py and
    # tests/test_xing4_model.py that the train cells' readers list theirs)
    from tests.test_benchmark_families import LATER_CELLS, manifest_without

    manifest = manifest_without(LATER_CELLS[-3:])
    names = [m["name"] for m in manifest["per_layer"]]
    mine = [*READ, "setup_trainer_start_s"]
    assert names[-8:] == mine  # appended, in the issue's order
    serve = [w["name"] for w in manifest["workloads"]
             if w["name"] != "mistral-7b.train-packed"]
    assert len(serve) == 9
    for m in manifest["per_layer"][-8:]:
        assert (m["moves"], m["source"]) == ("setup_s", "program_counter")
        assert m["workloads"] == (["mistral-7b.train-packed"]
                                  if m["name"] == "setup_trainer_start_s"
                                  else serve)
        assert callable(common.load_reader(m["name"]))
    # the layers are the manifest's own names
    assert {m["layer"] for m in manifest["per_layer"][-8:]} == {
        "serve front", "engine", "model step, serve", "trainer"}
    assert all(m["moves"] != "setup_s" for m in manifest["per_layer"][:-8])

