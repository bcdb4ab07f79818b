"""Mean share of the engine's decode slots that hold a request, from the
engine's own `stats()["active"]` sampled every 50 ms of the traced run's
window, over `max_batch_size`."""


def read(ctx):
    run = ctx["run"]
    polls = [p for p in run.get("polls", [])
             if run["t0"] <= p["t"] < run["t0"] + run["seconds"]]
    if not polls:
        return None
    mean_active = sum(p["active"] for p in polls) / len(polls)
    return 100.0 * mean_active / run["max_batch_size"]
