"""Seconds from the controller's spawn of the replica to its readiness
(`serve_replica_ready_seconds`, summed over the deployment's replicas: the
cells run one): the part of `setup_s` an autoscaler waits for a replica.

Read from the counters' snapshot AT THE WINDOW'S START (`ctx["counters"][0]`):
everything the process did before the first timed instant, which is what
`setup_s` spans. Nothing where the run kept no counters, or where the
program has no such series (a program from before PR 50)."""


def read(ctx):
    at_start = (ctx.get("counters") or ({},))[0]
    found = [v for (name, tags), v in at_start.items()
             if name == "serve_replica_ready_seconds_sum"]
    return sum(found) if found else None
