"""Share of dispatched decode slot-steps that held a request:
`serve_decode_slot_steps` active over active + empty, counted by the engine
at every dispatch of the window (no polling)."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    active = common.counter_delta(before, after, "serve_decode_slot_steps",
                                  state="active")
    total = common.counter_delta(before, after, "serve_decode_slot_steps")
    if not total:
        return None
    return 100.0 * active / total
