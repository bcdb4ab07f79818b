"""`moe_zero_choice_share`'s twin: the share of the live tokens' choices
that fell on experts THIS chip holds (`serve_moe_choices{kind="held"}` over
`{kind="all"}`): the rows its expert products had a use for. The rest fell
on experts held elsewhere or on identity experts."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    made = common.counter_delta(*ctx["counters"], "serve_moe_choices",
                                kind="all")
    if not made:
        return None
    return 100.0 * common.counter_delta(*ctx["counters"], "serve_moe_choices",
                                        kind="held") / made
