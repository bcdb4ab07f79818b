"""`moe_ffn_device_share` for a cell whose judged metric is the time per
output token (its answers outlast the window, so the tokens that reach the
client inside it are not judged there): the same reading, the share of the
device's busy time spent in the expert products and the gather and
scatter-add of their rows (the operations that trace_names*.json list
under `moe_ffn`), under the end-to-end metric it moves in such a cell."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "moe_ffn")
