"""The paged decode-attention kernel's share of its roofline: memory-bound
(it reads every cached key and value once). The cached tokens read are
counted from the client's records: a token j of a request with prompt p,
arriving inside the traced part, was one decode step over p + j cached
tokens, in every layer that calls the kernel. Bytes and operations of a
call, and the calls of a step, by the configuration's family."""

from benchmark import flops, trace_reduce


def read(ctx):
    run = ctx["run"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "paged_decode")
    lo, hi = run.get("traced_from_s"), run.get("traced_to_s")
    if not seconds or lo is None:
        return None
    context = 0
    for q, r in zip(run["requests"], run["records"]):
        for j, t in enumerate(r["token_s"]):
            if j and lo <= t < hi:  # token 0 comes from the prefill program
                context += q["prompt_len"] + j
    if not context:
        return None
    family, spec = ctx["family"], ctx["spec"]
    calls = family.calls_per_pass(spec, "paged_decode")
    work = {k: v * calls
            for k, v in family.work["paged_decode"](spec, context).items()}
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
