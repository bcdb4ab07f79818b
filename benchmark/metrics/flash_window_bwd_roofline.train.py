"""The WINDOW flash-attention backward kernels' share of their roofline in
the train step (`flash_bwd_window_dq`, `flash_bwd_window_dkv`): their times
summed, the calls those of the group's `count` entry (one a backward pass),
the work the family's count of the pairs inside the window."""

from benchmark import flops, trace_reduce


def read(ctx):
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "flash_window_bwd")
    _, calls = trace_reduce.group_seconds(ctx["trace"], "flash_window_bwd_count")
    count = getattr(ctx["family"], "work", {}).get("flash_window_bwd")
    if not seconds or not calls or count is None:
        return None
    mix = ctx["cell"]["traffic"]
    work = count(ctx["spec"], mix["rows_per_step"], mix["row_tokens"])
    ideal = flops.roofline_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * ideal * calls / seconds
