"""Seconds of the replica's start spent in `InferenceEngine.warmup`: every
decode span, chunk program and state hand-over traced, lowered, compiled or
loaded, and run once (`serve_replica_start_seconds{phase=warmup}`, the region
`engine.warmup`).

Read from the counters' snapshot AT THE WINDOW'S START (`ctx["counters"][0]`):
everything the process did before the first timed instant, which is what
`setup_s` spans. Nothing where the run kept no counters, or where the
program has no such series (a program from before PR 50)."""


def read(ctx):
    at_start = (ctx.get("counters") or ({},))[0]
    found = [v for (name, tags), v in at_start.items()
             if name == "serve_replica_start_seconds"
             and ("phase", "warmup") in tags]
    return sum(found) if found else None
