"""The flash-attention forward kernel's share of its roofline in the train
step: the least time the chip could take for the calls the trace holds
(operations and bytes from shapes, by the configuration's family) over the
time they took. Compute-bound at these shapes."""

from benchmark import flops, trace_reduce


def read(ctx):
    seconds, calls = trace_reduce.group_seconds(ctx["trace"], "flash_fwd")
    if not seconds:
        return None
    mix = ctx["cell"]["traffic"]
    work = ctx["family"].work["flash_fwd"](
        ctx["spec"], mix["rows_per_step"], mix["row_tokens"])
    ideal = flops.roofline_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * ideal * calls / seconds
