"""The state-space decode kernel's share of its MEMORY roofline: the state
of every LIVE slot read and written once a call, its operands beside it
(the family's `work["ssd_step"]`, from the equations). Calls are the
trace's; the live slots of a call are the engine's own `active`, polled
every 50 ms and averaged over the traced part, as `gdn_step_roofline` takes
them. A kernel that moved every slot's state, live or not, reads low
here."""

from benchmark import flops, trace_reduce


def read(ctx):
    run = ctx["run"]
    seconds, calls = trace_reduce.group_seconds(ctx["trace"], "ssd_step")
    lo, hi = run.get("traced_from_s"), run.get("traced_to_s")
    if not seconds or lo is None:
        return None
    polls = [p["active"] for p in run.get("polls", [])
             if lo <= p["t"] - run["t0"] < hi]
    if not polls:
        return None
    live_slot_calls = calls * sum(polls) / len(polls)
    work = ctx["family"].work["ssd_step"](ctx["spec"], live_slot_calls)
    roof = flops.roofline_seconds(work, ctx["peaks"])
    return 100.0 * roof["seconds"] / seconds
