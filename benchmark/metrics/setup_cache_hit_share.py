"""Share of the programs compiled before the window that the persistent
compile cache answered: `xla_programs{stage=compile, cache=hit}` over hit +
miss. 100 where every program of the run was found, near 0 on a fresh
directory (two wrappers that lower to one module may hit inside a run).

Read from the counters' snapshot AT THE WINDOW'S START (`ctx["counters"][0]`):
everything the process did before the first timed instant, which is what
`setup_s` spans. Nothing where the run kept no counters, or where the
program has no such series (a program from before PR 50)."""


def read(ctx):
    at_start = (ctx.get("counters") or ({},))[0]
    answered = {cache: sum(v for (name, tags), v in at_start.items()
                           if name == "xla_programs"
                           and ("stage", "compile") in tags
                           and ("cache", cache) in tags)
                for cache in ("hit", "miss")}
    total = answered["hit"] + answered["miss"]
    return 100.0 * answered["hit"] / total if total else None
