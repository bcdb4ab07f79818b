"""Seconds before the window that jax spent in the backend's compile or in
the persistent cache's load, over every program of the process whatever
region it fell under (`xla_program_seconds{stage=compile}`, hit, miss and
off): the part of `setup_s` that a warm cache removes.

Read from the counters' snapshot AT THE WINDOW'S START (`ctx["counters"][0]`):
everything the process did before the first timed instant, which is what
`setup_s` spans. Nothing where the run kept no counters, or where the
program has no such series (a program from before PR 50)."""


def read(ctx):
    at_start = (ctx.get("counters") or ({},))[0]
    found = [v for (name, tags), v in at_start.items()
             if name == "xla_program_seconds"
             and ("stage", "compile") in tags]
    return sum(found) if found else None
