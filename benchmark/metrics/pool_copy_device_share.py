"""Share of the device's busy time spent copying the page pool (whole, or
a layer's slab of it) inside the engine's decode and chunk programs: the
operations trace_names.json lists under `pool_copy`. Found in PR 24: the
programs scan over layers with the pool as scanned input AND output, and
XLA keeps a second copy of it."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "pool_copy")
