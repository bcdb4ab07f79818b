"""Every cell's control flow end to end at a tiny size on the CPU: the same
drivers, load generator, warm-up, window, replay and comparison with the
plain reference as on the chip. Reachable only from here: `drive.measure`
is handed a tiny cell and a made-up device, returns the driver's result and
prints no result line; benchmark/run.py itself fails without a TPU. No
number of these runs is a device metric, and none is kept."""

import argparse
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import common, drive
from benchmark.tests.tiny import tiny_cell

CELLS = [w["name"] for w in common.load_manifest()["workloads"]]


@pytest.fixture(scope="module")
def runtime():
    import ray_tpu

    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def watch():
    return common.CompileWatch()


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_and_is_correct(name, runtime, watch, capsys):
    cell = tiny_cell(name)
    args = argparse.Namespace(seed=2**31 + 11, seconds=2.0, trace=0,
                              sweep="")
    out = drive.measure(cell, args, {"platform": "cpu"}, watch,
                        time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    wanted = {m["name"] for m in cell["end_to_end"]}
    assert wanted <= set(out["end_to_end"])
    printed = capsys.readouterr().out
    assert '"correct"' not in printed  # progress lines only, no result line


@pytest.mark.parametrize("mix", ["sessions", "longprompt", "burst"])
def test_the_serve_driver_takes_other_mixes_as_data(mix, runtime, watch):
    """Sessions over shared prefixes, a mixture with long prompts and bursty
    arrivals go through the same generator, warm-up, window, replay and
    comparison with no code of their own; nothing compiles in the window
    (`correct` includes that), prefix hits included."""
    cell = tiny_cell("mistral-7b.serve-chat")
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "traffic", mix + ".json")) as f:
        cell["traffic"] = json.load(f)
    args = argparse.Namespace(seed=2**31 + 12, seconds=2.0, trace=0, sweep="")
    out = drive.measure(cell, args, {"platform": "cpu"}, watch,
                        time.perf_counter())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    hits = common.counter_delta(*out["counters"], "serve_prefix_cache_hit_tokens")
    assert (hits > 0) == (mix == "sessions")


def test_the_command_fails_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=common.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 1
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr
