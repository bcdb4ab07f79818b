"""Device time of the chunk and bucket prefill programs per thousand
prompt tokens. The tokens are those of requests whose first token reached
the client inside the traced part of the window (a chunked prompt that
straddles its edge is counted whole or not at all)."""

from benchmark import trace_reduce


def read(ctx):
    run = ctx["run"]
    seconds, _ = trace_reduce.group_seconds(ctx["trace"], "prefill")
    lo, hi = run.get("traced_from_s"), run.get("traced_to_s")
    if not seconds or lo is None:
        return None
    tokens = sum(q["prompt_len"] for q, r in zip(run["requests"], run["records"])
                 if r["first_s"] is not None
                 and lo <= r["due_s"] + r["first_s"] < hi)
    if not tokens:
        return None
    return 1000.0 * seconds / (tokens / 1000.0)
