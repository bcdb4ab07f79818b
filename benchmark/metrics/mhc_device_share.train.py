"""Share of the device's busy time in the traced steps spent in the residual
paths of a model with several residual streams (manifold-constrained
hyper-connections), forward and backward: the norm of the 4-wide token and
its projection onto the mixing coefficients, the Sinkhorn rounds, the
pre-mix that makes a sublayer's input, the res- and post-mix that writes
the streams, and their gradients (the operations
benchmark/trace_names/xing4.json lists under `mhc_train`)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "mhc_train")
