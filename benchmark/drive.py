"""One run of one cell: the driver of its kind measures, the profiler's
trace (--trace 1) is reduced, the per-layer readers run, every process the
run started is stopped, and the last line is printed."""

from __future__ import annotations

import glob
import os
import shutil
from typing import Any, Dict, Optional

from . import common

TRACE_DIR = os.path.join(common.ROOT, ".bench_trace")


class Tracer:
    """jax's profiler over part of the window; the driver calls start and
    stop from the thread that runs the window."""

    def __init__(self, cell: Dict[str, Any]):
        self.dir = os.path.join(TRACE_DIR, cell["name"])
        shutil.rmtree(self.dir, ignore_errors=True)
        self.seconds = float(cell["trace_seconds"])
        self.start_step = int(cell.get("trace_start_step", 0))
        self.started = self.stopped = False

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self.started = True

    def stop(self) -> None:
        import jax

        if self.started and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def xplane(self) -> str:
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(found) != 1:
            raise common.BenchFailure(f"expected one xplane file, found {found}")
        return found[0]


def measure(cell: Dict[str, Any], args, device: Dict[str, Any], watch,
            t_start: float) -> Dict[str, Any]:
    """-> the driver's result (None after a sweep), with `trace` (the
    reduced trace) in a traced run. Prints progress lines only, never the
    result line."""
    if cell["kind"] == "train":
        from . import train_driver as driver
    elif cell["kind"] == "serve":
        from . import serve_driver as driver
    else:
        raise common.BenchFailure(f"unknown cell kind {cell['kind']!r}")
    tracer: Optional[Tracer] = Tracer(cell) if args.trace else None
    try:
        out = driver.run(cell, args, device, watch, t_start, tracer)
    finally:
        if tracer:
            tracer.stop()
    if tracer and out is not None:
        from . import trace_reduce

        # the xplane file stays in .bench_trace/<cell>/ until the cell's next
        # traced run, for tools/trace_dump.py
        out["trace"] = trace_reduce.reduce(tracer.xplane(), cell["chips"])
        # the driver's own clock around the traced part (blocked at both
        # ends) is the window; the trace's first-to-last event understates it
        out["trace"]["window_s"] = out["run"]["traced_s"]
    return out


def per_layer(cell, out, device) -> Dict[str, Dict[str, Any]]:
    ctx = {"cell": cell, "spec": cell["config"],
           "family": common.family(cell["config"]), "chips": cell["chips"],
           "peaks": common.peaks_for(device["kind"]), "run": out["run"],
           "trace": out["trace"], "counters": out.get("counters")}
    metrics = {}
    for m in cell["per_layer"]:
        value = common.load_reader(m["name"])(ctx)
        if value is not None:  # a reader that finds nothing returns nothing
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(cell, args, device, watch, t_start) -> int:
    out = measure(cell, args, device, watch, t_start)
    alive = common.stop_children()
    common.say(processes_alive_after_shutdown=alive)
    if out is None:  # a sweep printed its table
        return 0
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        from . import trace_reduce

        metrics = per_layer(cell, out, device)
        dev.update(busy_s=out["trace"]["busy_s"],
                   window_s=out["trace"]["window_s"])
        breakdown = trace_reduce.breakdown(out["trace"])
    else:
        metrics = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    print(common.result_line(out["correct"], out["attempted"], out["failed"],
                             metrics, dev, breakdown), flush=True)
    return 0
