"""How full the sorted row buffers ran: the choices that fell on held
experts over the buffers' rows (`moe_choices_held` over `moe_rows_bound`,
both summed over the expert layers), from the train step's own metrics on
the run's first batch. The buffer is a static bound; past 100% a step
fails."""


def read(ctx):
    step = ctx["run"].get("first_metrics") or {}
    if not step.get("moe_rows_bound"):
        return None
    return 100.0 * step["moe_choices_held"] / step["moe_rows_bound"]
