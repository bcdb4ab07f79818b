"""Share of the device's busy time spent in latent attention's kernels:
decode, chunk prefill and a bucket's flash kernel together (the operations
that benchmark/trace_names/longcat_flash.json lists under `mla_attn`)."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.group_share(ctx["trace"], "mla_attn")
