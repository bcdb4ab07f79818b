"""The eight readers of the engine's token ledger (benchmark/token_ledger.py)
on hand-made counter snapshots, the load generator's records and a small
trace recorded on a TPU v5e with the ledger's attributes on its
`engine.dispatch` regions (benchmark/tools/record_tiny_spans.py). Snapshots
and traces from before the ledger read as None."""

import os

import pytest

from benchmark import common, program_spans

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "tiny_ledger_spans.xplane.pb")
OLD_TRACE = os.path.join(DATA, "tiny_serve_spans.xplane.pb")  # no attributes

WAIT, STAGE = "serve_token_wait_seconds", "serve_request_stage_seconds"
SPAN_S, SPAN_N = "serve_decode_span_seconds", "serve_decode_span_steps"


def _snap(series):
    """{(name, 'k=v,k=v'): value} -> the shape common.counters() returns."""
    return {(name, tuple(sorted(tuple(t.split("=")) for t in tags.split(",")
                                if t))): float(value)
            for (name, tags), value in series.items()}


# a tree from before the ledger: the stage histogram and the token counts
OLD_BEFORE = {("serve_tokens_generated", ""): 100,
              ("serve_ttft_seconds_count", ""): 10,
              (STAGE + "_sum", "stage=ready"): 1.0,
              (STAGE + "_sum", "stage=decode"): 7.0}
OLD_AFTER = {("serve_tokens_generated", ""): 1120,  # 1000 decode tokens
             ("serve_ttft_seconds_count", ""): 30,
             (STAGE + "_sum", "stage=ready"): 1.5,
             (STAGE + "_sum", "stage=decode"): 17.0}
BEFORE = {**OLD_BEFORE, **{
    (WAIT, "part=device_wait"): 2.0, (WAIT, "part=chunk_device_wait"): 0.0,
    (WAIT, "part=host"): 1.0, (WAIT, "part=dispatch"): 0.0,
    (WAIT, "part=chunk_host"): 0.0, (WAIT, "part=loop"): 0.0,
    (SPAN_S, "live_le=4,prefill=0"): 1.0, (SPAN_N, "live_le=4,prefill=0"): 64,
    ("serve_decode_interleaved_prefill_tokens", ""): 1000}}
AFTER = {**OLD_AFTER, **{
    (WAIT, "part=device_wait"): 9.0, (WAIT, "part=chunk_device_wait"): 0.5,
    (WAIT, "part=host"): 1.5, (WAIT, "part=dispatch"): 0.75,
    (WAIT, "part=chunk_host"): 0.5, (WAIT, "part=loop"): 0.25,
    (SPAN_S, "live_le=4,prefill=0"): 2.0, (SPAN_N, "live_le=4,prefill=0"): 264,
    (SPAN_S, "live_le=8,prefill=0"): 3.0, (SPAN_N, "live_le=8,prefill=0"): 300,
    (SPAN_S, "live_le=8,prefill=1"): 1.4, (SPAN_N, "live_le=8,prefill=1"): 100,
    ("serve_decode_interleaved_prefill_tokens", ""): 257000}}
# two answers of 501 tokens, 6 s and 6.5 s after their first tokens, and
# one that failed: 12.5 ms a gap
RECORDS = [{"ok": True, "tokens": 501, "first_s": 1.0, "last_s": 7.0},
           {"ok": True, "tokens": 501, "first_s": 2.0, "last_s": 8.5},
           {"ok": True, "tokens": 1, "first_s": 1.0, "last_s": 1.0},
           {"ok": False, "tokens": 0, "first_s": None, "last_s": None}]

# name -> what the snapshots, records and trace above read as
EXPECTED = {
    "tpot_device_wait_ms": 7.5,  # (7.0 + 0.5) s over 1000 tokens
    "tpot_host_ms": 2.0,  # 0.5 + 0.75 + 0.5 + 0.25
    "tpot_ready_ms": 0.5,
    "tpot_unaccounted_ms": 2.5,  # 12.5 - 7.5 - 2.0 - 0.5
    "decode_step_wall_ms.clean": 8.0,  # (1.0 + 3.0) s over 200 + 300 steps
    "decode_step_wall_ms.shared": 14.0,
    "interleaved_prefill_tokens_per_token": 256.0,
    # the recorded file's six dispatches, each of 4 steps, held 2, 2, 1, 1,
    # 1, 1 live sequences (read off a listing of its regions)
    "decode_live_slots.traced": 8 / 6,
}


def _ctx(before, after):
    return {"cell": {"name": "x"}, "counters": (_snap(before), _snap(after)),
            "run": {"records": RECORDS}}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_ledger_readers_on_snapshots_records_and_a_recorded_trace(
        name, monkeypatch):
    read = common.load_reader(name)
    spans = program_spans.read_file(TRACE)
    monkeypatch.setattr(program_spans, "read", lambda cell: spans)
    assert read(_ctx(BEFORE, AFTER)) == pytest.approx(EXPECTED[name])
    dispatches = spans.named("engine.dispatch")
    assert len(dispatches) == 6 and all(
        {"live", "steps", "prefill_tokens"} <= set(r.attrs)
        for r in dispatches)
    # a tree from before the ledger: no series, no attribute -> nothing
    old = program_spans.read_file(OLD_TRACE)
    monkeypatch.setattr(program_spans, "read", lambda cell: old)
    if name == "tpot_ready_ms":  # the stage histogram is older than the ledger
        assert read(_ctx(OLD_BEFORE, OLD_AFTER)) == pytest.approx(0.5)
    else:
        assert read(_ctx(OLD_BEFORE, OLD_AFTER)) is None
    monkeypatch.setattr(program_spans, "read", lambda cell: None)
    no_counters = {"cell": {"name": "x"}, "counters": None,
                   "run": {"records": RECORDS}}
    assert read(no_counters) is None


def test_the_recorded_trace_splits_put_from_call_under_their_phases():
    spans = program_spans.read_file(TRACE)
    for parent in ("engine.dispatch", "engine.chunk"):
        held = [r for r in spans.named(parent) if r.children]
        assert held
        for r in held:
            assert [c.name for c in r.children][:2] == [
                parent + ".put", parent + ".call"]
    parts = spans.named("engine.dispatch.put") \
        + spans.named("engine.dispatch.call")
    inside = spans.idle_inside(parts)
    whole = spans.idle_inside(spans.named("engine.dispatch"))
    # the two tile their phase but for the bytecode around them
    assert 0 < inside["inside"] <= whole["inside"]
    assert inside["inside"] >= 0.9 * whole["inside"]


def test_the_eight_are_entries_of_the_five_serve_cells_and_no_train_cell():
    manifest = common.load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    serve = [w["name"] for w in manifest["workloads"]
             if common.load_cell(w["name"])["kind"] == "serve"]
    assert [m["name"] for m in manifest["per_layer"]][-8:] == [
        "tpot_device_wait_ms", "tpot_host_ms", "tpot_ready_ms",
        "tpot_unaccounted_ms", "decode_step_wall_ms.clean",
        "decode_step_wall_ms.shared", "interleaved_prefill_tokens_per_token",
        "decode_live_slots.traced"]
    for name in EXPECTED:
        assert callable(common.load_reader(name))
        entry = entries[name]
        assert (entry["layer"], entry["moves"]) == ("engine", "tpot_mean_ms")
        assert entry["workloads"] == serve and len(serve) == 5
