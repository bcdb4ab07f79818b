"""The flash-attention backward kernels' share of their roofline in the
train step. The backward of one forward call may be several kernels (dq;
dk and dv): their times are summed, and the calls counted are those of
the group's `count` entry (one per backward pass)."""

from benchmark import flops, trace_reduce


def read(ctx):
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "flash_bwd")
    _, calls = trace_reduce.group_seconds(ctx["trace"], "flash_bwd_count")
    if not seconds or not calls:
        return None
    mix = ctx["cell"]["traffic"]
    work = ctx["family"].work["flash_bwd"](
        ctx["spec"], mix["rows_per_step"], mix["row_tokens"])
    ideal = flops.roofline_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * ideal * calls / seconds
