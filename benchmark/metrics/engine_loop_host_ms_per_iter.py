"""Host time of one iteration of the engine's decode loop: mean over the
`engine.iter` regions of the trace of the region's duration minus the two
phases that wait for the device inside it (`engine.readback`, and
`engine.chunk.readback` on a prompt's last chunk). What is left is the
chunk scheduler, installs, the numpy batch build, dispatches and the commit
loop: the time the device may stand idle for."""

from benchmark import program_spans

WAITS = ("engine.readback", "engine.chunk.readback")


def read(ctx):
    spans = program_spans.read(ctx["cell"]["name"])
    iters = spans.named("engine.iter") if spans else []
    if not iters:
        return None
    host = sum(it.seconds - sum(r.seconds for r in it.walk()
                                if r.name in WAITS) for it in iters)
    return 1000.0 * host / len(iters)
