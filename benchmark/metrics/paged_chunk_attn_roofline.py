"""The paged chunk-attention kernels' share of their roofline, windowed or
not (`paged_chunk` in benchmark/trace_names*: %paged_chunk and
%paged_chunk_window): a chunk's query rows against the cached keys each
sees, every key before it in a full layer, the last window's in a window
layer. Where a chunk stands cannot be told from the client's records (which
chunk of which prompt ran inside the traced part), so the positions are the
engine's own: `start` and `tokens` of every `engine.chunk.call` region of
the traced part. Operations and bytes of a chunk's calls, every layer's, by
the configuration's family (`chunk_attention_work`). A bucket prefill runs
the flash kernel and is not read here."""

from benchmark import flops, program_spans, trace_reduce


def read(ctx):
    family, spec = ctx["family"], ctx["spec"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "paged_chunk")
    spans = program_spans.read(ctx["cell"]["name"])
    if not seconds or not spans or not hasattr(family, "chunk_attention_work"):
        return None
    work = {"flops": 0.0, "bytes": 0.0}
    for region in spans.named("engine.chunk.call"):
        if "start" in region.attrs and "tokens" in region.attrs:
            one = family.chunk_attention_work(
                spec, int(region.attrs["start"]), int(region.attrs["tokens"]))
            for k in work:
                work[k] += one[k]
    if not work["flops"]:
        return None
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
