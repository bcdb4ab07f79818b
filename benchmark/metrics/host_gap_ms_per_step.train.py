"""Wall time of the traced steps minus the time an operation ran on the
device, per step: what the trainer's host side leaves the chip waiting."""


def read(ctx):
    run, trace = ctx["run"], ctx["trace"]
    if not run.get("traced_steps"):
        return None
    return 1000.0 * (trace["window_s"] - trace["busy_s"]) / run["traced_steps"]
