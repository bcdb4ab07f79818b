"""The selective-scan kernel's share of its roofline in the prefill
programs (chunks run between decode spans, so its time is decode's too).
The positions scanned are counted from the client's records, as
`prefill_device_ms_per_ktok` counts prompts: requests whose first token
reached the client inside the traced part, each prompt padded as the engine
pads it (whole chunks above `prefill_chunk`, else its bucket). Operations
and bytes of a call, and the calls of a pass, by the configuration's
family."""

from benchmark import flops, trace_reduce


def read(ctx):
    from ray_tpu.serve.engine import EngineConfig

    run = ctx["run"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "ssm_scan")
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
    calls = family.calls_per_pass(spec, "ssm_scan")
    work = {k: v * calls
            for k, v in family.work["ssm_scan"](spec, tokens).items()}
    return 100.0 * flops.roofline_seconds(work, ctx["peaks"])["seconds"] / seconds
