"""The latent chunk-prefill kernel's share of its roofline (compute-bound:
a chunk's 256 x 64 query rows meet every cached row once). What a chunk
attends over cannot be told from the client's records here: a prompt whose
context was asked before resumes past the prefix cache's hits, so the
positions are the engine's own, the `start` and `tokens` (the chunk as the
engine pads it) of every `engine.chunk.call` region of the traced part.
Operations and bytes of a call (query row r against the start + r + 1 rows
it sees), and the calls of a pass, by the configuration's family. A bucket
prefill runs the accepted flash kernel and is not read here (this cell's
traffic sends no prompt under `prefill_chunk`)."""

from benchmark import flops, program_spans, trace_reduce


def read(ctx):
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "mla_chunk")
    spans = program_spans.read(ctx["cell"]["name"])
    if not seconds or not spans:
        return None
    family, spec = ctx["family"], ctx["spec"]
    calls = family.calls_per_pass(spec, "mla_chunk")
    work = {"flops": 0.0, "bytes": 0.0}
    for region in spans.named("engine.chunk.call"):
        if "start" in region.attrs and "tokens" in region.attrs:
            one = family.work["mla_chunk"](spec, float(region.attrs["start"]),
                                           float(region.attrs["tokens"]))
            for k in work:
                work[k] += one[k] * calls
    if not work["flops"]:
        return None
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
