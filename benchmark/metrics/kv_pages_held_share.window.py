"""Pages the live sequences hold keys in, in the window page space, over
what caching every key would hold there: sum of min(ceil(n / page_size),
ring) over sum of ceil(n / page_size) over the live sequences' lengths n,
summed once an engine iteration (`serve_window_page_steps` held over
full_length). The saving of allocating a window layer's pages as a ring,
which `window_pages_held_share` (held over the ring's bound) cannot state.
100 where no sequence has passed its ring."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    full_length = common.counter_delta(
        *ctx["counters"], "serve_window_page_steps", state="full_length")
    if not full_length:
        return None
    return 100.0 * common.counter_delta(
        *ctx["counters"], "serve_window_page_steps", state="held") / full_length
