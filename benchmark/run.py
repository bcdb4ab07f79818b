#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object that BENCHMARK.json's
contract fixes. With --trace 0 its metrics are the cell's end-to-end
metrics; with --trace 1 the profiler runs over part of the window and the
metrics are the cell's per-layer metrics. There is no CPU mode: without a
TPU (or with fewer chips than the cell asks for) the run prints no result
and exits 1. `--sweep` (serve cells) prints the table of a ladder of rates
in place of a result; PERF.md says how the knee is read from it."""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's clock."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - ticks / os.sysconf("SC_CLK_TCK")


def main(argv=None) -> int:
    t_start = time.perf_counter() - process_age_s()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep", default="",
                        help="serve cells: comma-separated rates (requests/s), "
                             "each offered for --seconds; prints a table")
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import common

    try:
        cell = common.load_cell(args.workload)
        device = common.require_tpu(cell["chips"])
        common.adopt_orphans()
        cache_dir = common.enable_cache()
        watch = common.CompileWatch()
        common.say(workload=cell["name"], seed=args.seed, device=device,
                   compile_cache=cache_dir)
        from benchmark import drive

        return drive.run_cell(cell, args, device, watch, t_start)
    except BaseException as e:  # noqa: BLE001 — no result line, exit 1
        traceback.print_exc()
        print(f"benchmark failed: {e!r}", file=sys.stderr, flush=True)
        try:
            common.stop_children()
        except Exception:  # noqa: BLE001 — already failing
            traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
