"""Share of the device's busy time spent in the expert FFN and its
gather/scatter (the operations that trace_names.json lists under
`moe_ffn`). Nothing to read in a configuration without experts."""

from benchmark import trace_reduce


def read(ctx):
    if not ctx["spec"].get("num_local_experts"):
        return None
    return trace_reduce.group_share(ctx["trace"], "moe_ffn")
