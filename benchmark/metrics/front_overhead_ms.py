"""What the serve front adds to the first token: mean over the window's
completed requests of the client's first token minus the instant it SENT
(not due: lateness of the generator is not the front's), minus the mean of
the engine's own time to first token (`serve_ttft_seconds` sum / count,
delta over the same requests). Proxy, router, replica and handle."""

from benchmark import common


def read(ctx):
    before, after = ctx["counters"]
    count = common.counter_delta(before, after, "serve_ttft_seconds_count")
    total = common.counter_delta(before, after, "serve_ttft_seconds_sum")
    ok = [r for r in ctx["run"]["records"] if r["ok"]]
    if not count or not ok:
        return None
    client = sum(r["first_s"] - r["sent_late_s"] for r in ok) / len(ok)
    return 1000.0 * (client - total / count)
