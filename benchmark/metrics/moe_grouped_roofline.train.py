"""The grouped expert products' share of their roofline in the train step:
the nine products of an expert layer (`moe_gmm` forward, `moe_gmm_dx` the
rows' gradient, `moe_gmm_dw` the weights'; ops/moe.py). The work is counted
from the rows the program COUNTED: `moe_choices_held` of the train step's own
metrics on the run's first batch (the one step whose metrics the driver's
loop reads whole; the held share moves by a few hundredths from batch to
batch), over the expert layers, times the traced steps; never from an
even-routing guess, which a piled-up router would read over 100%. A group's
padding to whole tiles is the kernel's cost, not the algorithm's, so it
counts as time and not as work."""

from benchmark import flops, trace_reduce


def read(ctx):
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "moe_grouped")
    count = getattr(ctx["family"], "work", {}).get("moe_grouped")
    rows = (ctx["run"].get("first_metrics") or {}).get("moe_choices_held")
    traced = ctx["run"].get("traced_steps")
    if not seconds or count is None or not rows or not traced:
        return None
    layers = ctx["family"].calls_per_pass(ctx["spec"], "moe_grouped")
    work = count(ctx["spec"], rows / layers)
    ideal = flops.roofline_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * ideal * layers * traced / seconds
