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
