"""Share of the device's idle seconds in the trace that lie inside a named
phase of the engine: a child of `engine.iter` on the decode thread (also
one whose iteration began before the trace did, so that its `engine.iter`
is not in the file), or a `prefill.*` region on the prefill thread. The two
waits for work, `engine.idle` and `prefill.idle`, name no cause and are left
out. The rest is host time no region covers."""

from benchmark import program_spans

NOT_A_PHASE = ("engine.iter", "engine.idle", "prefill.idle")


def read(ctx):
    spans = program_spans.read(ctx["cell"]["name"])
    if not spans or len(spans.busy) < 2:
        return None
    phases = [r for r in spans.all()
              if r.name.startswith(("engine.", "prefill."))
              and r.name not in NOT_A_PHASE]
    idle = spans.idle_inside(phases)
    if not idle["total"]:
        return None
    return 100.0 * idle["inside"] / idle["total"]
