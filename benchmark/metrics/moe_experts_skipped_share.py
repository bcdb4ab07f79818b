"""Experts x expert layers x decode steps whose weights a step left unread,
over those the layers hold (`serve_moe_expert_steps`: held - touched over
held): a decode step's expert product visits the experts that at least one
live row chose, from the kernel's own list, which the device adds up
beside the tokens and a span's readback brings. With k of E chosen by each
of n live rows an expert is skipped with probability about (1 - k / E)^n.
A program that lacks the counter (a model without experts, a tree whose
step visits every expert and does not count) reads nothing."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    held = common.counter_delta(*ctx["counters"], "serve_moe_expert_steps",
                                state="held")
    if not held:
        return None
    touched = common.counter_delta(
        *ctx["counters"], "serve_moe_expert_steps", state="touched")
    return 100.0 * (held - touched) / held
