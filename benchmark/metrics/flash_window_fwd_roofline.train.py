"""The WINDOW flash-attention forward kernel's share of its roofline in the
train step (`flash_fwd_window`, ops/attention.py: the key blocks a query
block's window reaches and no others): the least time the chip could take
for the calls the trace holds, by the family's count of the pairs inside the
window and of the bytes of the blocks a window visits, over the time they
took. The full layers' calls are `flash_fwd_roofline.train`'s: the two are
never summed into one share."""

from benchmark import flops, trace_reduce


def read(ctx):
    seconds, calls = trace_reduce.group_seconds(ctx["trace"], "flash_window_fwd")
    count = getattr(ctx["family"], "work", {}).get("flash_window_fwd")
    if not seconds or count is None:
        return None
    mix = ctx["cell"]["traffic"]
    work = count(ctx["spec"], mix["rows_per_step"], mix["row_tokens"])
    ideal = flops.roofline_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * ideal * calls / seconds
