"""Pages that active sequences hold keys in, per window layer, over the
most they may: active sequences x (window / page_size + 1)
(`serve_window_page_steps` held over bound, summed once an engine
iteration). At or under 100 is a bounded window; more would be a leak."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    held = common.counter_delta(before, after, "serve_window_page_steps",
                                state="held")
    bound = common.counter_delta(before, after, "serve_window_page_steps",
                                 state="bound")
    if not bound:
        return None
    return 100.0 * held / bound
