"""Live slots of the traced part's decode steps: the `live` attribute of
the trace's `engine.dispatch` regions, weighed by their `steps`. The exact
twin of the 50 ms poll of `stats()["active"]`."""

from benchmark import token_ledger


def read(ctx):
    return token_ledger.traced_live_slots(ctx)
