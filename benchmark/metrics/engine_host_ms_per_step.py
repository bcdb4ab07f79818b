"""Host time of the engine's loop per engine step (one decode span):
`serve_decode_step_phase_seconds` sums of every phase but `sample` (which
waits for the device) over the steps counted in the same window."""

from benchmark import common


def read(ctx):
    before, after = ctx["counters"]
    name = "serve_decode_step_phase_seconds"
    host = common.counter_delta(before, after, name + "_sum") \
        - common.counter_delta(before, after, name + "_sum", phase="sample")
    steps = common.counter_delta(before, after, name + "_count", phase="sample")
    if not steps:
        return None
    return 1000.0 * host / steps
