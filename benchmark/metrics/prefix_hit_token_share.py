"""Share of the window's prompt tokens that the prefix cache served:
`serve_prefix_cache_hit_tokens` (the engine adds a request's cached tokens
when it admits it) from the window's start to the end of its drain, over
the prompt tokens of the window's requests as the client sent them. The
warm-up's requests come before the first snapshot and the replay's after
the second. A cell whose traffic shares no prefix reads 0, and nothing
where the run kept no counters."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    prompt = sum(q["prompt_len"] for q in ctx["run"].get("requests", []))
    if not prompt:
        return None
    hits = common.counter_delta(*ctx["counters"],
                                "serve_prefix_cache_hit_tokens")
    return 100.0 * hits / prompt
