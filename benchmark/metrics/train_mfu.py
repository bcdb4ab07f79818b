"""Model FLOP/s utilization of the window: the operations forward and
backward need per token (the family's count under benchmark/flops.py's
conventions: active experts only, no embedding lookup, no recomputation)
times tokens per second, over chips times the bf16 peak."""


def read(ctx):
    run = ctx["run"]
    if not run.get("tokens_per_s"):
        return None
    per_token = ctx["family"].train_flops_per_token(
        ctx["spec"], ctx["cell"]["traffic"]["row_tokens"])
    return 100.0 * per_token * run["tokens_per_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
