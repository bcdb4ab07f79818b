"""What the serve front adds around the engine, measured inside the
program: mean `serve_front_seconds{leg="inbound"}` (the proxy's receipt of
the POST to `engine.add_request`) plus mean `{leg="outbound"}` (the
engine's first token to the first SSE chunk written)."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    total = 0.0
    for leg in ("inbound", "outbound"):
        count = common.counter_delta(before, after,
                                     "serve_front_seconds_count", leg=leg)
        if not count:
            return None
        total += common.counter_delta(before, after,
                                      "serve_front_seconds_sum", leg=leg) / count
    return 1000.0 * total
