"""Device time of the engine's decode-span program per decode step (one
token for every active sequence). A span is 4 or 16 steps and both have
one module name, so steps are counted by the paged decode-attention kernel:
the family says how often a step calls it (once per layer that attends)."""

from benchmark import trace_reduce


def read(ctx):
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "decode_span")
    _, kernel_calls = trace_reduce.group_seconds(ctx["trace"], "paged_decode")
    steps = kernel_calls / ctx["family"].calls_per_pass(ctx["spec"],
                                                       "paged_decode")
    if not seconds or not steps:
        return None
    return 1000.0 * seconds / steps
