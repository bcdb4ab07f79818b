"""The latent decode kernel's share of its roofline. Every head reads the
same row, so a cached token costs 2 x 64 x (576 + 512) operations for 1280
bytes (109 a byte as the bytes are counted, each row ONCE as stored, 640
lanes): under a v5e's ridge of 240, memory-bound by the count. The cached
tokens read are counted from the client's records as `paged_decode_roofline`
counts them: token j of a request with prompt p, arriving inside the traced
part, was one step of one sequence over p + j cached rows, in every
attention. Operations and bytes of a call, and the calls of a step, by the
configuration's family."""

from benchmark import flops, trace_reduce


def read(ctx):
    run = ctx["run"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "mla_decode")
    lo, hi = run.get("traced_from_s"), run.get("traced_to_s")
    if not seconds or lo is None:
        return None
    context = steps = 0
    for q, r in zip(run["requests"], run["records"]):
        for j, t in enumerate(r["token_s"]):
            if j and lo <= t < hi:  # token 0 comes from the prefill program
                context += q["prompt_len"] + j
                steps += 1
    if not context:
        return None
    family, spec = ctx["family"], ctx["spec"]
    calls = family.calls_per_pass(spec, "mla_decode")
    work = {k: v * calls for k, v in
            family.work["mla_decode"](spec, context, steps).items()}
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
