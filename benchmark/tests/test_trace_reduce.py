"""The reduction from the profiler's xplane file to numbers, on a small
trace recorded on a TPU v5e (benchmark/tools/record_tiny_trace.py: two
steps of a toy train step with flash attention forward and backward at the
published head geometry, T = 1024, an idle pause after each; recorded anew
in PR 27, since PR 24's file predated the kernels' names)."""

import os

import pytest

from benchmark import common, flops, trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny_train.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce(TRACE, 1)


def test_busy_time_is_the_union_and_self_times_add_up_to_it(trace):
    assert trace["busy_s"] == pytest.approx(0.001528744, rel=1e-6)
    assert sum(v[0] for v in trace["ops"].values()) == pytest.approx(
        trace["busy_s"], rel=1e-9)
    # two steps with a 20 ms pause: the device is idle most of the window
    assert trace["busy_s"] < 0.1 * trace["window_s"]
    (name, (seconds, calls)), = trace["modules"].items()
    assert trace_reduce.short_name(name) == "jit_toy_step"
    assert seconds == pytest.approx(0.001529349) and calls == 2
    # every operation is filed under the program that ran it
    assert set(trace["module_ops"][name]) == set(trace["ops"])


def test_kernels_are_found_by_their_names(trace):
    fwd = trace_reduce.group_seconds(trace, "flash_fwd")
    bwd = trace_reduce.group_seconds(trace, "flash_bwd")
    count = trace_reduce.group_seconds(trace, "flash_bwd_count")
    assert fwd[1] == 2 and bwd[1] == 4 and count[1] == 2  # per step: 1, 2, 1
    assert fwd[0] == pytest.approx(0.00024939, rel=1e-6)
    assert bwd[0] == pytest.approx(0.000644167, rel=1e-6)
    assert trace_reduce.group_seconds(trace, "no_such_group") == (0.0, 0.0)


def test_roofline_share_of_the_recorded_kernels_is_below_100(trace):
    spec = common.load_json("configs", "mistral-7b.json")
    peaks = common.peaks_for("TPU v5 lite")
    seconds, calls = trace_reduce.group_seconds(trace, "flash_fwd")
    work = common.family(spec).work["flash_fwd"](spec, 1, 1024)
    ideal = flops.roofline_seconds(work, peaks)
    share = 100 * ideal["seconds"] * calls / seconds
    assert ideal["bound"] == "compute" and 20 < share < 100


def test_breakdown_has_the_contracts_shape(trace):
    b = trace_reduce.breakdown(trace)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    name, seconds = b["device_ops"][0]
    assert name.startswith("flash_bwd: ") and seconds > 0
    assert all(isinstance(n, str) and s > 0 for n, s in b["idle_gaps"])


def test_short_names():
    assert trace_reduce.short_name(
        "%fusion.4 = bf16[8,4]{1,0:T(8,128)(2,1)} fusion(bf16[8] %x), kind=kLoop"
    ) == "fusion.4 fusion"
    assert trace_reduce.short_name(
        "%f.8 = (f32[1,2]{1,0}, f32[1,2]{1,0}) custom-call(bf16[1] %y)"
    ) == "f.8 custom-call"
    assert trace_reduce.short_name("jit_step(1669211627038880688)") == "jit_step"


def test_a_file_without_a_device_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    import glob

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    with pytest.raises(common.BenchFailure, match="no device plane"):
        trace_reduce.reduce(path, 1)
