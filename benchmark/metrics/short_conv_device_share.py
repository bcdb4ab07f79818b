"""Share of the device's busy time spent in the gated short convolution:
its in-projection, the gate-and-taps fusion and its out-projection (the
operations that trace_names/lfm2.json lists under `short_conv`, by shape:
they are XLA fusions and carry no name of the program's)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "short_conv")
