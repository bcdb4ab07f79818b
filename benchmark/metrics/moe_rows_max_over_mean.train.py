"""What imbalance costs a grouped product: the rows of the fullest held
expert over the mean of the held experts' rows (`moe_rows_max` over
`moe_choices_held` / held experts, both summed over the expert layers), from
the train step's own metrics on the run's first batch, the one step whose
metrics the driver's loop reads whole. 1.0 is an even routing."""


def read(ctx):
    step = ctx["run"].get("first_metrics") or {}
    held = getattr(ctx["family"], "held_experts", None)
    rows = step.get("moe_choices_held")
    if not rows or held is None:
        return None
    return step["moe_rows_max"] * held(ctx["spec"]) / rows
