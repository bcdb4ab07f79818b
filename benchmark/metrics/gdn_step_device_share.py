"""Share of the device's busy time spent in the delta-rule decode update
(the `gdn_step` kernel: one grid program a decode slot, once a linear
layer and step; an empty slot's program moves nothing)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "gdn_step")
