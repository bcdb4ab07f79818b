"""Experts that at least one live row of a decode step chose, over the
experts the layers hold x expert layers x decode steps
(`serve_moe_expert_steps` touched over held): the device counts the first
beside the tokens and a span's readback brings it. What a product that
skips the unchosen experts would still read of the experts' weights: with
k of E chosen by each of n live rows, about 1 - (1 - k / E)^n. A program
that lacks the counter reads nothing."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    held = common.counter_delta(*ctx["counters"], "serve_moe_expert_steps",
                                state="held")
    if not held:
        return None
    return 100.0 * common.counter_delta(
        *ctx["counters"], "serve_moe_expert_steps", state="touched") / held
