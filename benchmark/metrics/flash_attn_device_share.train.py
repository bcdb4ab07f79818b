"""Share of the device's busy time in the traced steps spent in the flash
attention kernels, both kinds of call (over a window and over every key),
forward and backward."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "flash_attn_train")
