"""Share of the device's busy time spent in the expert FFN and its
gather/scatter (the operations that trace_names.json lists under
`moe_ffn`). Listed (`workloads`) only for cells whose configuration has
experts: the group's shapes match a dense FFN of the same widths too."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "moe_ffn")
