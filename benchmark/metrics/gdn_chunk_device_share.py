"""Share of the device's busy time in the traced part spent in the
delta-rule prefill recurrence (the `gdn_chunk` kernel: one grid program a
head and block of 64 positions, once a linear layer and chunk or bucket).
Chunks run between decode spans, so its time is every decoding sequence's
too; `gdn_step_device_share` is its twin for the decode update. A program
without the kernel (or a trace that caught no prefill) reads nothing."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "gdn_chunk")
