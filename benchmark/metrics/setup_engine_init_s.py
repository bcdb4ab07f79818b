"""Seconds of the replica's start spent in `InferenceEngine.__init__`: pools,
state, slot tables, jit wrappers (`serve_replica_start_seconds{phase=engine}`,
the region `replica.start.engine` of `ray_tpu/serve/llm.py start_engine`).

Read from the counters' snapshot AT THE WINDOW'S START (`ctx["counters"][0]`):
everything the process did before the first timed instant, which is what
`setup_s` spans. Nothing where the run kept no counters, or where the
program has no such series (a program from before PR 50)."""


def read(ctx):
    at_start = (ctx.get("counters") or ({},))[0]
    found = [v for (name, tags), v in at_start.items()
             if name == "serve_replica_start_seconds"
             and ("phase", "engine") in tags]
    return sum(found) if found else None
