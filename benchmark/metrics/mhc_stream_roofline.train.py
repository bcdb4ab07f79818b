"""The residual paths' share of their roofline in the train step: the bytes
the minimal passes over the streams move in one step (the family's
`work["mhc"]`: a sublayer reads the n streams for the norm, the projection
and the pre-mix, reads them again and writes them for the res- and
post-mix, its backward as much again; from shapes alone, so the same bytes
whatever implements the path) against the memory's peak, over the time the
trace holds under `mhc_train` (benchmark/trace_names/xing4.json). Memory-
bound at these shapes; passes XLA makes beyond the minimal ones, and
recomputation under remat, count as time and not as work."""

from benchmark import flops, trace_reduce


def read(ctx):
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "mhc_train")
    count = getattr(ctx["family"], "work", {}).get("mhc")
    traced = ctx["run"].get("traced_steps")
    if not seconds or count is None or not traced:
        return None
    mix = ctx["cell"]["traffic"]
    work = count(ctx["spec"], mix["rows_per_step"], mix["row_tokens"])
    ideal = flops.roofline_seconds(work, ctx["peaks"])["seconds"]
    return 100.0 * ideal * traced / seconds
