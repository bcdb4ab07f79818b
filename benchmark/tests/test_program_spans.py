"""The readers of what the program reports about itself: its regions in a
small trace recorded on a TPU v5e (benchmark/tools/record_tiny_spans.py: a
toy engine serving four requests over both prefill paths), and its counters
in hand-made snapshots. A reader that finds nothing returns None."""

import os

import pytest

from benchmark import common, program_spans

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiny_serve_spans.xplane.pb")
OLD_TRACE = os.path.join(os.path.dirname(__file__), "data",
                         "tiny_train.xplane.pb")  # recorded before regions


@pytest.fixture(scope="module")
def spans():
    return program_spans.read_file(TRACE)


def _reader(name, spans=None, monkeypatch=None):
    read = common.load_reader(name)
    if monkeypatch is not None:
        monkeypatch.setattr(program_spans, "read", lambda cell: spans)
    return read


def test_regions_nest_per_thread_line(spans):
    iters = spans.named("engine.iter")
    assert len(iters) >= 3
    tiles = {"engine.chunk", "engine.install", "engine.cancel_check",
             "engine.build", "engine.dispatch", "engine.readback",
             "engine.commit"}
    for it in iters:
        assert {c.name for c in it.children} <= tiles
        # the toy's shortest iteration is 5 ms: microseconds between its
        # phases weigh more than in a cell's 300 ms
        assert sum(c.seconds for c in it.children) >= 0.9 * it.seconds
        assert it.self_seconds == pytest.approx(
            it.seconds - sum(c.seconds for c in it.children))
    assert sum(c.seconds for it in iters for c in it.children) >= \
        0.99 * sum(it.seconds for it in iters)
    readbacks = [r for r in spans.all() if r.name == "engine.chunk.readback"]
    assert readbacks and all(
        any(r in c.children for c in spans.named("engine.chunk"))
        for r in readbacks)
    decode = {it.thread for it in iters}
    prefill = {r.thread for r in spans.named("prefill.dispatch")}
    assert len(decode) == 1 and len(prefill) == 1 and decode != prefill
    assert spans.seconds("engine.iter") == pytest.approx(
        sum(it.seconds for it in iters))


def test_idle_time_is_laid_against_the_regions(spans):
    assert len(spans.busy) > 10
    gaps = spans.idle()
    assert all(lo < hi for lo, hi in gaps)
    assert all(a[1] < b[0] for a, b in zip(spans.busy, spans.busy[1:]))
    phases = [c for it in spans.named("engine.iter") for c in it.children]
    inside = spans.idle_inside(phases)
    assert 0 < inside["inside"] <= inside["total"]
    # one thread's phases do not overlap: by name they add up to `inside`
    by_name = sum(v for k, v in inside.items()
                  if k not in ("inside", "total"))
    assert by_name == pytest.approx(inside["inside"], rel=1e-9)
    # with a region that spans the whole trace every idle second is inside
    whole = program_spans.Region("engine.all", spans.busy[0][0],
                                 spans.busy[-1][1], "t", {})
    assert spans.idle_inside([whole])["inside"] == pytest.approx(
        inside["total"])


def test_span_readers_on_the_recorded_trace(spans, monkeypatch):
    ctx = {"cell": {"name": "x"}, "run": {"traced_steps": 4}}
    host = _reader("engine_loop_host_ms_per_iter", spans, monkeypatch)(ctx)
    iters = spans.named("engine.iter")
    mean_ms = 1000 * sum(it.seconds for it in iters) / len(iters)
    assert 0 < host < mean_ms
    share = _reader("engine_idle_gap_attributed_share", spans,
                    monkeypatch)(ctx)
    assert 50 < share <= 100
    # a serve trace holds no data or trainer region
    assert _reader("data_wait_ms_per_step", spans, monkeypatch)(ctx) is None
    assert _reader("batch_place_ms_per_step", spans, monkeypatch)(ctx) is None


def test_step_readers_divide_region_seconds_by_traced_steps(monkeypatch):
    R = program_spans.Region
    made = program_spans.Spans(
        [R("data.next", 0, 2_000_000, "a", {}),
         R("train.place_batch", 3_000_000, 4_000_000, "a", {}),
         R("data.next", 5_000_000, 11_000_000, "a", {})], [])
    ctx = {"cell": {"name": "x"}, "run": {"traced_steps": 4}}
    assert _reader("data_wait_ms_per_step", made, monkeypatch)(ctx) == \
        pytest.approx(2.0)
    assert _reader("batch_place_ms_per_step", made, monkeypatch)(ctx) == \
        pytest.approx(0.25)
    assert _reader("data_wait_ms_per_step", made, monkeypatch)(
        {"cell": {"name": "x"}, "run": {"traced_steps": 0}}) is None


@pytest.mark.parametrize("name", [
    "engine_loop_host_ms_per_iter", "engine_idle_gap_attributed_share",
    "data_wait_ms_per_step", "batch_place_ms_per_step"])
def test_a_trace_without_regions_reads_as_nothing(name, monkeypatch):
    assert program_spans.read_file(OLD_TRACE) is None
    assert program_spans.read("no-such-cell") is None
    ctx = {"cell": {"name": "no-such-cell"}, "run": {"traced_steps": 4}}
    assert common.load_reader(name)(ctx) is None
    assert _reader(name, None, monkeypatch)(ctx) is None


def _snap(**series):
    """{'name{k=v}': value} -> the shape common.counters() returns."""
    out = {}
    for key, value in series.items():
        name, _, tags = key.partition("__")
        pairs = tuple(sorted(tuple(t.split("_", 1)) for t in tags.split("__")
                             if t))
        out[(name, pairs)] = float(value)
    return out


STAGE = "serve_request_stage_seconds"


def test_counter_readers_on_hand_made_snapshots():
    before = _snap(**{
        "serve_ttft_seconds_count": 10, "serve_ttft_seconds_sum": 5.0,
        STAGE + "_sum__stage_pending": 1.0, STAGE + "_count__stage_pending": 10,
        "serve_decode_slot_steps__state_active": 100,
        "serve_decode_slot_steps__state_empty": 300,
        "serve_kv_page_steps__state_reserved": 1000,
        "serve_kv_page_steps__state_written": 100,
        "serve_front_seconds_sum__leg_inbound": 1.0,
        "serve_front_seconds_count__leg_inbound": 10,
        "serve_front_seconds_sum__leg_outbound": 1.0,
        "serve_front_seconds_count__leg_outbound": 10})
    after = _snap(**{
        "serve_ttft_seconds_count": 14, "serve_ttft_seconds_sum": 9.0,
        STAGE + "_sum__stage_pending": 1.4, STAGE + "_count__stage_pending": 15,
        STAGE + "_sum__stage_waiting": 0.0,
        STAGE + "_sum__stage_chunk": 1.2, STAGE + "_count__stage_chunk": 2,
        STAGE + "_sum__stage_prefill": 2.4, STAGE + "_count__stage_prefill": 4,
        STAGE + "_sum__stage_decode": 50.0, STAGE + "_count__stage_decode": 4,
        "serve_decode_slot_steps__state_active": 164,
        "serve_decode_slot_steps__state_empty": 492,
        "serve_kv_page_steps__state_reserved": 3000,
        "serve_kv_page_steps__state_written": 600,
        "serve_front_seconds_sum__leg_inbound": 1.008,
        "serve_front_seconds_count__leg_inbound": 14,
        "serve_front_seconds_sum__leg_outbound": 1.002,
        "serve_front_seconds_count__leg_outbound": 12})
    # the snapshot helper spells tag values without underscores
    after = {(n, tuple((k, {"waiting": "waiting_for_pages",
                            "chunk": "chunk_wait"}.get(v, v)) for k, v in t)): x
             for (n, t), x in after.items()}
    ctx = {"counters": (before, after)}
    read = common.load_reader
    # (0.4 pending + 1.2 chunk_wait) over 4 first tokens; 2.4 prefill over 4
    assert read("request_queue_wait_ms")(ctx) == pytest.approx(400.0)
    assert read("request_prefill_ms")(ctx) == pytest.approx(600.0)
    # together they are the engine's own mean time to first token
    assert 400.0 + 600.0 == pytest.approx(1000 * (9.0 - 5.0) / 4)
    assert read("decode_slot_occupancy")(ctx) == pytest.approx(25.0)
    assert read("kv_pages_written_share")(ctx) == pytest.approx(25.0)
    assert read("front_span_ms")(ctx) == pytest.approx(2.0 + 1.0)


@pytest.mark.parametrize("name", [
    "request_queue_wait_ms", "request_prefill_ms", "decode_slot_occupancy",
    "kv_pages_written_share", "front_span_ms"])
def test_counter_readers_return_nothing_from_a_program_without_them(name):
    # the parent commit: a time to first token, none of the new series
    before = _snap(serve_ttft_seconds_count=1, serve_ttft_seconds_sum=0.5)
    after = _snap(serve_ttft_seconds_count=5, serve_ttft_seconds_sum=2.5)
    read = common.load_reader(name)
    assert read({"counters": (before, after)}) is None
    assert read({"counters": None}) is None and read({}) is None


def test_every_new_metric_has_a_reader_and_an_entry():
    manifest = common.load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    new = ["engine_loop_host_ms_per_iter", "engine_idle_gap_attributed_share",
           "request_queue_wait_ms", "request_prefill_ms",
           "decode_slot_occupancy", "kv_pages_written_share", "front_span_ms",
           "data_wait_ms_per_step", "batch_place_ms_per_step"]
    cells = {c["name"]: common.load_cell(c["name"])
             for c in manifest["workloads"]}
    for name in new:
        assert callable(common.load_reader(name))
        entry = entries[name]
        for cell in entry["workloads"]:
            reported = {m["name"] for m in cells[cell]["end_to_end"]}
            assert entry["moves"] in reported, (name, cell)
    # new entries went to the end of the list, after the thirteen
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(new[0]) == 13 and names[13:13 + len(new)] == new
