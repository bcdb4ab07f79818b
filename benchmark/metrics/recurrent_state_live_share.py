"""Share of the recurrent state a decode step could touch that belongs to
a live sequence: `serve_recurrent_state_slot_steps` live over held, both
added on the host at every decode dispatch (live: slots that hold a
sequence x steps; held: `max_batch_size` x steps), from the window's start
to the end of its drain. A program that lacks the counter (no recurrent
state, or a tree from before it) reads nothing."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    held = common.counter_delta(before, after,
                                "serve_recurrent_state_slot_steps",
                                state="held")
    if not held:
        return None
    return 100.0 * common.counter_delta(
        before, after, "serve_recurrent_state_slot_steps",
        state="live") / held
