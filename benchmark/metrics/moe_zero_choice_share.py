"""Of the choices the live tokens made in a layer that holds a share of the
experts (experts a token x tokens x expert layers, decode spans and prefill
programs, window start to drain's end), the share that fell on zero-compute
(identity) experts: `serve_moe_choices{kind="zero"}` over `{kind="all"}`.
The device counts them in the program and they come back with the span's
tokens or the prefill's logits. A program without such a layer has no such
series and reads nothing."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    made = common.counter_delta(*ctx["counters"], "serve_moe_choices",
                                kind="all")
    if not made:
        return None
    return 100.0 * common.counter_delta(*ctx["counters"], "serve_moe_choices",
                                        kind="zero") / made
