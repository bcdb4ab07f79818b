"""tracing.region, the hot-path primitive: times its body once on the one
clock `Span` and `util/timeline.py` share, becomes a child span only on a
traced thread, and names the region a compile fell under."""

import pytest

from ray_tpu.core.metrics import Histogram, MetricsRegistry, registry
from ray_tpu.util import timeline, tracing


@pytest.fixture(autouse=True)
def _clean():
    tracing.clear()
    timeline.clear()
    yield
    tracing.clear()
    timeline.clear()


def test_regions_nest_and_parent_under_the_threads_span():
    with tracing.start_span("root") as root:
        with tracing.region("layer.outer", k=1) as outer:
            with tracing.region("layer.inner"):
                pass
            with tracing.region("layer.inner"):
                pass
    by_name = {}
    for s in tracing.get_spans(root.trace_id):
        by_name.setdefault(s["name"], []).append(s)
    (o,) = by_name["layer.outer"]
    assert o["parent_id"] == root.span_id and o["attrs"] == {"k": 1}
    assert [s["parent_id"] for s in by_name["layer.inner"]] == [o["span_id"]] * 2
    # the span's interval IS the region's one reading
    assert o["start_us"] == outer.start_ns / 1e3
    assert o["end_us"] - o["start_us"] == pytest.approx(
        outer.elapsed_ns / 1e3, abs=0.6)  # float us since the epoch: 0.25 us ulp
    (tree,) = tracing.get_trace(root.trace_id)
    assert [c["name"] for c in tree["children"]] == ["layer.outer"]
    assert tracing.current_span() is None


def test_an_untraced_region_buffers_nothing_and_hands_out_its_time():
    cursor, _ = tracing.drain_since(0)
    sink = Histogram("t_region_seconds", "", registry_=MetricsRegistry()
                     ).labels(phase="x")
    for _ in range(1000):
        with tracing.region("layer.hot") as r:
            assert tracing.current_span() is None
        sink.observe(r.elapsed_s)
    assert tracing.drain_since(0)[0] == cursor and tracing.get_spans() == []
    assert r.elapsed_ns > 0 and r.elapsed_s == r.elapsed_ns * 1e-9
    assert sink._metric.count({"phase": "x"}) == 1000


def test_spans_regions_and_the_timeline_share_one_clock():
    before = tracing.now_ns()
    with tracing.start_span("root") as root, tracing.region("layer.a") as r:
        with timeline.span("inside"):
            pass
        mid = timeline._now_us()
    after = tracing.now_ns()
    (ev,) = [e for e in timeline.drain_since(0)[1] if e["name"] == "inside"]
    assert r.start_ns / 1e3 <= ev["ts"] <= ev["ts"] + ev["dur"] <= mid
    assert before <= r.start_ns <= r.start_ns + r.elapsed_ns <= after
    (rec,) = [s for s in tracing.get_spans() if s["name"] == "layer.a"]
    assert rec["start_us"] <= ev["ts"] and ev["ts"] + ev["dur"] <= rec["end_us"]
    assert root.start_us <= rec["start_us"]
    # anchored to the wall once: within a second of time.time() here
    import time
    assert abs(tracing.now_ns() / 1e9 - time.time()) < 1.0


def test_ids_are_unique_without_uuid4():
    spans = [tracing.Span("s") for _ in range(100_000)]  # never finished
    assert len({s.span_id for s in spans}) == len(spans)
    assert len({s.trace_id for s in spans}) == len(spans)
    assert all(len(s.span_id) == 16 and len(s.trace_id) == 32
               for s in spans[:100])
    int(spans[0].trace_id, 16), int(spans[0].span_id, 16)
    assert tracing.get_spans() == []


def test_record_child_takes_the_callers_two_readings():
    with tracing.start_span("root") as root:
        t0 = tracing.now_ns()
        tracing.record_child(root, "stage.x", t0, t0 + 5_000_000, {"n": 1})
    (rec,) = [s for s in tracing.get_spans() if s["name"] == "stage.x"]
    assert rec["parent_id"] == root.span_id and rec["trace_id"] == root.trace_id
    assert rec["end_us"] - rec["start_us"] == pytest.approx(5000.0, abs=0.6)


def test_a_compile_is_counted_under_the_innermost_open_region():
    import jax
    import jax.numpy as jnp

    compiles = registry.get("xla_compiles")
    with tracing.region("test.warm"):
        jnp.ones(3).block_until_ready()  # resolves the listener; may compile
    f = jax.jit(lambda x: x * 3 + 1)
    x, x2 = jnp.ones((7, 3)), jnp.ones((5, 2))
    before = compiles.get({"under": "test.inner"})
    outer_before = compiles.get({"under": "test.outer"})
    with tracing.start_span("root") as root, tracing.region("test.outer"):
        with tracing.region("test.inner"):
            f(x).block_until_ready()
        assert compiles.get({"under": "test.inner"}) == before + 1
        with tracing.region("test.inner"):
            f(x).block_until_ready()  # same shape: no re-trace, no count
        assert compiles.get({"under": "test.inner"}) == before + 1
        with tracing.region("test.inner"):
            f(x2).block_until_ready()  # forced re-trace
    assert compiles.get({"under": "test.inner"}) == before + 2
    assert compiles.get({"under": "test.outer"}) == outer_before
    # on a traced thread the compile is a child span of that region too
    recs = tracing.get_spans(root.trace_id)
    inner_ids = {s["span_id"] for s in recs if s["name"] == "test.inner"}
    found = [s for s in recs if s["name"] == "xla.compile"]
    assert len(found) == 2 and {s["parent_id"] for s in found} <= inner_ids


def test_named_gives_a_partial_a_module_name():
    import functools

    import jax
    import jax.numpy as jnp

    def body(x, k):
        return x * k

    fn = jax.jit(tracing.named(functools.partial(body, k=2), "double_it"))
    assert "module @jit_double_it" in fn.lower(jnp.ones(3)).as_text()


# -- the compile listener: every stage timed, named and filed (PR 50) --------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def _programs(under):
    """xla_programs and xla_program_seconds under one region, by
    (stage, cache)."""
    out = {}
    for name in ("xla_programs", "xla_program_seconds"):
        for _n, tags, value in registry.get(name).samples():
            tags = dict(tags)
            if tags["under"] == under:
                out[name, tags["stage"], tags["cache"]] = value
    return out


def test_the_three_stages_are_filed_once_outermost_and_named(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(tracing, "_STAGE_SPAN_FLOOR_S", 0.0)
    tracing.watch_compiles()

    def body(x):  # every jnp call is a jit of its own, traced inside this one
        return jnp.where(x > 0, x, 0.0) * 2 + jnp.take_along_axis(
            x, jnp.zeros((4, 1), jnp.int32), 1).sum()

    x = jnp.ones((4, 4))
    fn = jax.jit(tracing.named(body, "staged_program"))
    compiles = registry.get("xla_compiles")
    counted = compiles.get({"under": "test.stages"})
    with tracing.start_span("root") as root, tracing.region("test.stages"):
        fn(x).block_until_ready()
    got = _programs("test.stages")
    for stage in ("trace", "lower", "compile"):
        # whatever a cache another test left on answered the compile
        filed = {k: v for k, v in got.items() if k[1] == stage}
        assert sum(v for k, v in filed.items()
                   if k[0] == "xla_programs") == 1, (stage, got)
        assert all(v > 0 for v in filed.values())
        assert stage == "compile" or {k[2] for k in filed} == {"off"}
    assert compiles.get({"under": "test.stages"}) == counted + 1
    spans = [s for s in tracing.get_spans(root.trace_id)
             if s["name"].startswith("xla.")]
    assert [s["name"] for s in spans] == ["xla.trace", "xla.lower",
                                          "xla.compile"]
    assert all(s["attrs"]["program"] == "staged_program" for s in spans)
    assert [s["attrs"]["cache"] for s in spans[:2]] == ["off", "off"]
    (region_span,) = [s for s in tracing.get_spans(root.trace_id)
                      if s["name"] == "test.stages"]
    assert {s["parent_id"] for s in spans} == {region_span["span_id"]}
    # the spans lie on the region's clock, inside it, in their order
    assert region_span["start_us"] <= spans[0]["start_us"]
    assert spans[0]["end_us"] <= spans[1]["end_us"] <= spans[2]["end_us"] \
        <= region_span["end_us"] + 1.0


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compile cache in a directory of the test's own,
    every program kept; what was there before is put back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_compile_is_labelled_by_the_persistent_caches_answer(
        persistent_cache, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(tracing, "_STAGE_SPAN_FLOOR_S", 0.0)
    tracing.watch_compiles()

    def body(x):
        return jnp.tanh(x) @ x.T + 50.0

    x = jnp.ones((6, 6))
    with tracing.region("test.cache.first"):
        jax.jit(tracing.named(body, "cached_program"))(x).block_until_ready()
    first = _programs("test.cache.first")
    assert first["xla_programs", "compile", "miss"] == 1, first
    assert ("xla_programs", "compile", "hit") not in first
    jax.clear_caches()  # the next jit traces, lowers and asks the cache again
    with tracing.start_span("root") as root, tracing.region("test.cache.again"):
        jax.jit(tracing.named(body, "cached_program"))(x).block_until_ready()
    again = _programs("test.cache.again")
    assert again["xla_programs", "compile", "hit"] == 1, again
    assert ("xla_programs", "compile", "miss") not in again
    # no cache holds a trace or a lowering: paid again, filed `off`
    assert again["xla_programs", "trace", "off"] == 1
    assert again["xla_programs", "lower", "off"] == 1
    (loaded,) = [s for s in tracing.get_spans(root.trace_id)
                 if s["name"] == "xla.compile"]
    assert loaded["attrs"]["program"] == "cached_program"
    assert loaded["attrs"]["cache"] == "hit"
    assert loaded["attrs"]["retrieval_s"] > 0


@pytest.mark.parametrize("events, label", [
    (("/jax/compilation_cache/compile_requests_use_cache",
      "/jax/compilation_cache/cache_hits"), "hit"),
    (("/jax/compilation_cache/compile_requests_use_cache",
      "/jax/compilation_cache/cache_misses"), "miss"),
    ((), "off"),
])
def test_the_caches_answer_inside_the_interval_labels_that_compile(
        events, label):
    """jax's events as it raises them, in their order: the stage's start,
    the cache's answer INSIDE the backend-compile interval, its end; the
    answer is consumed, so the next compile starts from `off`."""
    under = "test.flag." + label
    with tracing.region(under):
        tracing._on_jax_stage_start(_COMPILE, 0.0, fun_name="jit(f)")
        for event in events:
            tracing._on_jax_event(event)
        if label == "hit":
            tracing._on_jax_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        tracing._on_jax_duration(_COMPILE, 0.5, fun_name="jit(f)")
        tracing._on_jax_stage_start(_COMPILE, 0.0, fun_name="jit(g)")
        tracing._on_jax_duration(_COMPILE, 0.25, fun_name="jit(g)")
    got = _programs(under)
    if label == "off":
        assert got == {("xla_programs", "compile", "off"): 2,
                       ("xla_program_seconds", "compile", "off"): 0.75}
    else:
        assert got == {("xla_programs", "compile", label): 1,
                       ("xla_program_seconds", "compile", label): 0.5,
                       ("xla_programs", "compile", "off"): 1,
                       ("xla_program_seconds", "compile", "off"): 0.25}


def test_a_stage_inside_another_stages_interval_is_that_stages_time():
    """A trace holds the traces of the jits it calls and the compiles of
    what it evaluates eagerly: one stage is filed, the outermost, and a
    nested compile still counts in `xla_compiles` and still consumes the
    cache's answer."""
    compiles = registry.get("xla_compiles")
    with tracing.start_span("root") as root, tracing.region("test.nested"):
        counted = compiles.get({"under": "test.nested"})
        tracing._on_jax_stage_start(_TRACE, 0.0, fun_name="outer")
        tracing._on_jax_stage_start(_TRACE, 0.0, fun_name="inner")
        tracing._on_jax_duration(_TRACE, 0.25, fun_name="inner")
        tracing._on_jax_stage_start(_COMPILE, 0.0, fun_name="jit(eager)")
        tracing._on_jax_event("/jax/compilation_cache/cache_hits")
        tracing._on_jax_duration(_COMPILE, 0.25, fun_name="jit(eager)")
        tracing._on_jax_duration(_TRACE, 1.0, fun_name="outer")
        tracing._on_jax_stage_start(_COMPILE, 0.0, fun_name="jit(outer)")
        tracing._on_jax_duration(_COMPILE, 2.0, fun_name="jit(outer)")
        # an end whose start the listeners never saw opens nothing
        tracing._on_jax_duration(_LOWER, 0.5, fun_name="jit(late)")
    assert _programs("test.nested") == {
        ("xla_programs", "trace", "off"): 1,
        ("xla_program_seconds", "trace", "off"): 1.0,
        ("xla_programs", "compile", "off"): 1,
        ("xla_program_seconds", "compile", "off"): 2.0,
        ("xla_programs", "lower", "off"): 1,
        ("xla_program_seconds", "lower", "off"): 0.5}
    assert compiles.get({"under": "test.nested"}) == counted + 2
    named = [(s["name"], s["attrs"]["program"])
             for s in tracing.get_spans(root.trace_id)
             if s["name"].startswith("xla.")]
    assert named == [("xla.trace", "outer"), ("xla.compile", "outer"),
                     ("xla.lower", "late")]


def test_watch_compiles_twice_registers_once():
    from jax._src import monitoring

    tracing.watch_compiles()
    tracing.watch_compiles()
    with tracing.region("test.watch"):
        pass  # `_resolve_annotation` asks for the listeners too
    assert [cb for cb in monitoring.get_event_duration_listeners()
            ].count(tracing._on_jax_duration) == 1
    assert [cb for cb in monitoring.get_event_listeners()
            ].count(tracing._on_jax_event) == 1
    assert [cb for cb in monitoring.get_scalar_listeners()
            ].count(tracing._on_jax_stage_start) == 1


def test_a_compile_before_the_first_region_is_counted_under_none():
    """In a process of its own: `watch_compiles()` and then a jit, no
    region ever opened. Before PR 50 the listener was registered by the
    first region, and what compiled before it was never seen."""
    import subprocess
    import sys

    code = (
        "import jax, jax.numpy as jnp\n"
        "from ray_tpu.util import tracing\n"
        "from ray_tpu.core.metrics import registry\n"
        "tracing.watch_compiles()\n"
        "jax.jit(tracing.named(lambda x: x * 2 + 1, 'early'))("
        "jnp.ones((3, 3))).block_until_ready()\n"
        "assert tracing._annotation is None  # no region resolved anything\n"
        "print(registry.get('xla_compiles').get({'under': 'none'}),\n"
        "      registry.get('xla_programs').get({'stage': 'compile',\n"
        "          'cache': 'off', 'under': 'none'}))\n")
    import os

    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    compiles, filed = (float(v) for v in out.stdout.split()[-2:])
    assert compiles >= 1 and filed >= 1


def test_a_region_takes_notes_and_closes_from_another_threads_reading():
    with tracing.start_span("root") as root:
        with tracing.region("layer.sized", kind="pool") as r:
            r.note(bytes=4096)
        t0 = tracing.now_ns() - 5_000_000
        seconds = tracing.region_since("layer.handed_over", t0, rank=0)
    assert r.attrs == {"kind": "pool", "bytes": 4096}
    by_name = {s["name"]: s for s in tracing.get_spans(root.trace_id)}
    assert by_name["layer.sized"]["attrs"] == {"kind": "pool", "bytes": 4096}
    handed = by_name["layer.handed_over"]
    assert handed["attrs"] == {"rank": 0} and handed["start_us"] == t0 / 1e3
    assert seconds == pytest.approx(
        (handed["end_us"] - handed["start_us"]) / 1e6, abs=1e-6)
    assert 0.005 <= seconds < 1.0
    # untraced: the seconds alone, and a start ahead of this clock is 0
    assert tracing.region_since("layer.x", tracing.now_ns() + 10**9) == 0.0
    with tracing.region("layer.untraced") as r:
        r.note(n=1)
    assert r.attrs == {"n": 1}
