"""Host time the train loop spends fetching and placing the next batch
(`next(batches)` + device_put), per step: the benchmark's own span around
the call into ray_tpu.data. It runs while the device computes the step
just dispatched, so it costs throughput only where it outlasts the step."""


def read(ctx):
    run = ctx["run"]
    if not run.get("steps"):
        return None
    return 1000.0 * run["input_wait_s"] / run["steps"]
