"""Model FLOP/s utilization of the window: the operations forward and
backward need per token (benchmark/flops.py: active experts only, no
embedding lookup, no recomputation) times tokens per second, over chips
times the bf16 peak."""

from benchmark import flops


def read(ctx):
    run = ctx["run"]
    if not run.get("tokens_per_s"):
        return None
    per_token = flops.train_flops_per_token(
        ctx["spec"], ctx["cell"]["traffic"]["row_tokens"])
    return 100.0 * per_token * run["tokens_per_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
