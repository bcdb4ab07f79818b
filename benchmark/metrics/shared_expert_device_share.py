"""Share of the device's busy time in the traced part spent in the shared
experts' product: the operations that benchmark/trace_names/
mla_shared_moe.json lists under `shared_experts` (the dense product of width
n x w beside the routed sum, one more pass over a program's rows and 18.9 MB
of weights a layer at the published widths). A program whose shared experts
ride in the routed experts' kernels, or that has none, runs no such
operation and reads nothing."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "shared_experts")
