"""`decode_span_ahead_share` on hand-made counter snapshots: the share of a
known split, and nothing on a tree without the series; and what it does to
a trace whose busy time is over the driver's window."""

import pytest

from benchmark import common
from benchmark.tests.test_token_ledger import _snap

AHEAD, STEPS = "serve_decode_ahead_steps", "serve_decode_span_steps"
NAME = "decode_span_ahead_share"


def _ctx(before, after):
    return {"cell": {"name": "x"}, "counters": (_snap(before), _snap(after))}


# 1000 steps in the window under three label sets, 640 of them dispatched
# behind an unfinished span
BEFORE = {(AHEAD, ""): 36, (STEPS, "live_le=4,prefill=0"): 64}
AFTER = {(AHEAD, ""): 676, (STEPS, "live_le=4,prefill=0"): 464,
         (STEPS, "live_le=8,prefill=0"): 500,
         (STEPS, "live_le=8,prefill=1"): 100}


def test_the_share_of_a_known_split():
    read = common.load_reader(NAME)
    assert read(_ctx(BEFORE, AFTER)) == pytest.approx(64.0)
    # a loop that never got ahead: the series is there and reads 0
    flat = {**AFTER, (AHEAD, ""): 36}
    assert read(_ctx(BEFORE, flat)) == 0.0


def test_a_tree_without_the_series_reads_nothing():
    read = common.load_reader(NAME)
    old = {k: v for k, v in AFTER.items() if k[0] != AHEAD}
    assert read(_ctx({}, old)) is None
    assert read({"cell": {"name": "x"}, "counters": None}) is None
    # the series without a span in the window: no share to take
    assert read(_ctx(AFTER, AFTER)) is None


@pytest.mark.parametrize("busy, window_after", [
    (4.866, 5.0),              # an idle device: nothing moves
    (5.0, 5.0),
    (5.004647903, 5.004647903),  # the profiler's start and stop: idle 0
    (5.05, 5.05),              # the most they may be
    (5.051, 5.0),              # more than they can be: left to be refused
    (0.0, 5.0),
])
def test_a_window_shorter_than_the_busy_time_is_that_busy_time(
        busy, window_after):
    read = common.load_reader(NAME)
    trace = {"busy_s": busy, "window_s": 5.0, "ops": {"a": [busy, 1]}}
    # on the parent too, where the reader has no series to read
    assert read({"cell": {"name": "x"}, "counters": None,
                 "trace": trace}) is None
    assert trace == {"busy_s": busy, "window_s": window_after,
                     "ops": {"a": [busy, 1]}}
    assert trace["busy_s"] <= trace["window_s"] or busy == 5.051
    # an untraced context has no trace to look at
    assert read({"cell": {"name": "x"}, "counters": None,
                 "trace": None}) is None


def test_it_is_an_entry_of_the_five_serve_cells_and_no_train_cell():
    # found by its name: where in the list it stands is no one's to rely on
    # (a later PR's entry goes to the end)
    manifest = common.load_manifest()
    [entry] = [m for m in manifest["per_layer"] if m["name"] == NAME]
    serve = [w["name"] for w in manifest["workloads"]
             if common.load_cell(w["name"])["kind"] == "serve"]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "tpot_mean_ms", "workloads": serve}
    assert len(serve) == 5
