"""Rows the expert products computed over rows the tokens were routed to,
over every expert layer of every program dispatched in the window:
`serve_moe_rows_computed` over `serve_moe_rows_routed`. The engine adds to
both on the host at each dispatch, from the program's static shape (rows x
experts x capacity) and the live tokens x experts a token it carried. 1 is
a dropless dispatch that pads nothing; a program that lacks the counters
(no experts, or a tree from before them) reads nothing."""

from benchmark import common


def read(ctx):
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    routed = common.counter_delta(before, after, "serve_moe_rows_routed")
    if not routed:
        return None
    return common.counter_delta(before, after,
                                "serve_moe_rows_computed") / routed
