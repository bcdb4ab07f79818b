"""Seconds before the window that jax spent tracing Python to jaxprs and
lowering them to MLIR modules (Pallas kernels are lowered here), over every
program of the process (`xla_program_seconds{stage=trace}` + `{stage=lower}`):
the part of `setup_s` that no cache holds, paid warm and cold alike.

Read from the counters' snapshot AT THE WINDOW'S START (`ctx["counters"][0]`):
everything the process did before the first timed instant, which is what
`setup_s` spans. Nothing where the run kept no counters, or where the
program has no such series (a program from before PR 50)."""


def read(ctx):
    at_start = (ctx.get("counters") or ({},))[0]
    found = [v for (name, tags), v in at_start.items()
             if name == "xla_program_seconds"
             and (("stage", "trace") in tags or ("stage", "lower") in tags)]
    return sum(found) if found else None
