"""The state-space prefill kernel's share of its roofline in the prefill
programs (chunks run between decode spans, so its time is decode's too).
The positions are counted from the client's records, as
`gdn_chunk_roofline` counts them: requests whose first token reached the
client inside the traced part, each prompt padded as the engine pads it
(whole chunks above `prefill_chunk`, else its bucket). Operations and bytes
of a call (the dual form's products, the operands in float32, the state
once) and the calls of a pass by the configuration's family. The kernel's
products run in float32 a head at a time, so against the chip's bfloat16
peak and its memory this share is small by construction."""

from benchmark import flops, trace_reduce


def read(ctx):
    from ray_tpu.serve.engine import EngineConfig

    run = ctx["run"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "ssd_chunk")
    lo, hi = run.get("traced_from_s"), run.get("traced_to_s")
    if not seconds or lo is None:
        return None
    ecfg = EngineConfig(**ctx["cell"]["engine"])
    C = ecfg.prefill_chunk

    def padded(n):
        if ecfg.chunked_prefill and n > C:
            return -(-n // C) * C
        return next((b for b in ecfg.prefill_buckets if b >= n), n)

    tokens = sum(padded(q["prompt_len"])
                 for q, r in zip(run["requests"], run["records"])
                 if r["first_s"] is not None
                 and lo <= r["due_s"] + r["first_s"] < hi)
    if not tokens:
        return None
    family, spec = ctx["family"], ctx["spec"]
    calls = family.calls_per_pass(spec, "ssd_chunk")
    work = {k: v * calls
            for k, v in family.work["ssd_chunk"](spec, tokens).items()}
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
