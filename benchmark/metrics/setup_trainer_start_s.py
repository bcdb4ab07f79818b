"""Seconds from `JaxTrainer.fit()`'s entry to the first line of the cell's
loop on the gang member: placement group, worker actor, the dataset shard's
hand-over (`train_start_seconds{phase=gang}`, the region `train.start` of
`ray_tpu/train/worker_group.py`). Read at the run's end from the program's
registry: the train driver keeps no snapshot, and nothing after the loop's
first line adds to the series. Nothing where the program has no such series
(a program from before PR 50)."""

from benchmark import common


def read(ctx):
    found = [v for (name, tags), v in common.counters().items()
             if name == "train_start_seconds" and ("phase", "gang") in tags]
    return sum(found) if found else None
