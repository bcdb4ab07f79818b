"""The paged decode-attention kernels' share of their roofline where the
layers that attend differ: per decoded token the window layers read the
last `window` cached tokens and the full and cross layers all of them.
What a decoded token reads is counted from the client's records (token j of
a request with prompt p, arriving inside the traced part, was one decode
step over p + j cached tokens) by the family's own `decode_attention_tokens`
and `work`; the time is every paged decode-attention call's, windowed or
not (`paged_decode` in benchmark/trace_names*)."""

from benchmark import flops, trace_reduce


def read(ctx):
    run = ctx["run"]
    family, spec = ctx["family"], ctx["spec"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "paged_decode")
    lo, hi = run.get("traced_from_s"), run.get("traced_to_s")
    if not seconds or lo is None or not hasattr(family,
                                                "decode_attention_tokens"):
        return None
    read_tokens = {}
    for q, r in zip(run["requests"], run["records"]):
        for j, t in enumerate(r["token_s"]):
            if j and lo <= t < hi:  # token 0 comes from the prefill program
                for group, n in family.decode_attention_tokens(
                        spec, q["prompt_len"] + j).items():
                    read_tokens[group] = read_tokens.get(group, 0) + n
    if not read_tokens:
        return None
    work = {"flops": 0.0, "bytes": 0.0}
    for group, n in read_tokens.items():
        for k, v in family.work[group](spec, n).items():
            work[k] += v
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
