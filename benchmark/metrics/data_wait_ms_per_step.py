"""Time the train loop's thread blocked on the input pipeline, per traced
step: seconds of the `data.next` regions (the consumer's wait in
`PrefetchIterator.__next__`) in the trace over the steps traced."""

from benchmark import program_spans


def read(ctx):
    return program_spans.region_ms_per_step(ctx, "data.next")
