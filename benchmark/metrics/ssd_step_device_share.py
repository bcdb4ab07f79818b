"""Share of the device's busy time spent in the state-space decode update
(the `ssd_step` kernel: one grid program a decode slot, once a Mamba-2
layer and step; an empty slot's program moves nothing)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "ssd_step")
