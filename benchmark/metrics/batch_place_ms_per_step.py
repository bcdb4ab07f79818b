"""Host time placing a batch on the device, per traced step: seconds of the
`train.place_batch` regions (`train/lm.py::make_global_batch`) in the trace
over the steps traced."""

from benchmark import program_spans


def read(ctx):
    return program_spans.region_ms_per_step(ctx, "train.place_batch")
