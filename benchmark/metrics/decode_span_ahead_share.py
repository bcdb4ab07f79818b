"""Share of the decode steps that the device found queued when it finished
the span before them: `serve_decode_ahead_steps` (steps of the spans that
the engine dispatched while the span before them was unfinished, so the
host's build, placements, readback and commit passed under a running
program) over `serve_decode_span_steps` (every span's steps, all labels),
from the window's start to the end of its drain. A tree whose loop reads a
span back before it builds the next has no such series and reads nothing.

With the loop a span ahead, a cell that always has work keeps the device
busy for the whole traced part, and the serve driver's clock then reads a
little LESS than the trace saw: it is read after `start_trace` returns, and
the device's tracer records from some milliseconds before that (3.9 ms of
busy time before the driver's `sleep` began and none after its end, in the
run that looked; the train driver blocks the device at both ends, a server
cannot). `window_covers_busy` states the one thing that is known then: the
traced part lasted at least as long as the device was seen busy in it."""

from benchmark import common

# the most that the profiler's start and stop can add to what the driver's
# clock saw, as a share of it (1.3 to 3.7 ms of 5.0 s measured, up to 0.07%);
# a busy time further over the window is a fault and is left to be refused
TRACE_EDGES_SHARE = 0.01


def window_covers_busy(trace) -> None:
    """Where the trace's busy time is over the driver's window by no more
    than the profiler's own start and stop, the window is that busy time (an
    idle share of 0, not a negative one). Nothing else of the trace moves:
    the operations' seconds and `busy_s` are the trace's, whole."""
    if not trace:
        return
    busy, window = trace.get("busy_s"), trace.get("window_s")
    if busy and window and window < busy <= window * (1 + TRACE_EDGES_SHARE):
        trace["window_s"] = busy


def read(ctx):
    window_covers_busy(ctx.get("trace"))
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    name = "serve_decode_ahead_steps"
    if not any(n == name for n, _tags in after):
        return None
    steps = common.counter_delta(before, after, "serve_decode_span_steps")
    if not steps:
        return None
    return 100.0 * common.counter_delta(before, after, name) / steps
