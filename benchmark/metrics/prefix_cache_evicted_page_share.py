"""Pages the allocator took from the prefix cache's LRU over pages the
window's requests entered into the cache, from the window's start to the end
of its drain: `serve_prefix_cache_evicted_pages` over
`serve_prefix_cache_registered_pages`. A session's history is let go between
its turns and waits in the LRU; 0 means every history survived its gap (the
pool never ran out of free pages), 100 that the cache held nothing until it
was asked for again. A program that lacks the counters, or a window that
registered nothing, reads nothing."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    registered = common.counter_delta(
        *ctx["counters"], "serve_prefix_cache_registered_pages")
    if not registered:
        return None
    return 100.0 * common.counter_delta(
        *ctx["counters"], "serve_prefix_cache_evicted_pages") / registered
