"""Share of the device's busy time in the traced steps spent in the expert
layers' own work, forward and backward: the router, the sort and the
gather of the rows, the grouped products, the scatter-add back, and the
shared expert (the operations benchmark/trace_names/afmoe.json lists under
`moe_ffn_train`)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "moe_ffn_train")
