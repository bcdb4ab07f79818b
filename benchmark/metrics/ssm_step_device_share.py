"""Share of the device's busy time spent in the decode state update (the
`ssm_step` kernel: one pass over a Mamba layer's state for every decode
slot, once a layer and step)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "ssm_step")
