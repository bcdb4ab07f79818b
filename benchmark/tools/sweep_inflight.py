#!/usr/bin/env python3
"""A serve cell's ladder of rates with BOTH of the knee's signs by quarter
of the window: the requests due without a first token (the client's clock,
as `run.py --sweep` prints them) and the sequences in flight (the engine's
own `active`, polled every 0.1 s). Where prompts go before decoders the
first alone cannot find a knee (PERF.md section 6, PR 39): what fills first
is the decode side. One process and one set-up; one line a rate; no result
line.

    python3 benchmark/tools/sweep_inflight.py --workload <cell> --seed <n> \
        --seconds 40 --rates 1,1.5,2

The benchmark's own runs never run this."""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark import common, serve_driver, traffic  # noqa: E402


def active_by_quarter(polls, t0: float, seconds: float, parts: int = 4):
    """Mean of the polled `active` in each of `parts` equal parts of the
    window (None: no poll fell there)."""
    t = np.array([p["t"] - t0 for p in polls])
    active = np.array([p["active"] for p in polls], np.float64)
    edges = np.linspace(0, seconds, parts + 1)
    return [float(active[(t >= lo) & (t < hi)].mean())
            if np.any((t >= lo) & (t < hi)) else None
            for lo, hi in zip(edges[:-1], edges[1:])]


def main() -> int:
    from ray_tpu.serve.engine import EngineConfig

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", required=True)
    args = parser.parse_args()
    cell = common.load_cell(args.workload)
    common.require_tpu(cell["chips"])
    common.adopt_orphans()
    common.enable_cache()
    spec, mix = cell["config"], cell["traffic"]
    ecfg = EngineConfig(**cell["engine"])
    ladder = [float(x) for x in args.rates.split(",")]
    plans = [traffic.requests(mix, args.seed + i, rate, args.seconds,
                              spec["vocab_size"])
             for i, rate in enumerate(ladder)]
    handle, url = serve_driver.start_server(cell, args.seed)
    try:
        warm = serve_driver.warm_set([r for p in plans for r in p], ecfg,
                                     spec["vocab_size"], args.seed)
        serve_driver.offer(url, [dict(w, due_s=0.0) for w in warm], 1.0, 300.0)
        for rate, requests in zip(ladder, plans):
            poller = serve_driver.Poller(handle, period_s=0.1)
            try:
                result = serve_driver.offer(
                    url, requests, args.seconds, cell["drain_cap_s"],
                    lambda t0, poller=poller: (poller.start(),
                                               time.sleep(args.seconds)))
            finally:
                if poller.is_alive():
                    poller.stop()
            numbers = serve_driver.summarize(
                result["records"], requests, args.seconds,
                args.seconds + cell["drain_cap_s"])
            numbers.pop("errors")
            full = [p["active"] >= ecfg.max_batch_size for p in poller.samples]
            common.say(
                sweep_rate_rps=rate, **numbers,
                awaiting_first_by_quarter=serve_driver.awaiting_first(
                    result["records"], args.seconds),
                active_by_quarter=active_by_quarter(
                    poller.samples, result["t0"], args.seconds),
                all_slots_full_share=float(np.mean(full)) if full else None,
                drained_s=result["elapsed_s"])
    finally:
        serve_driver.stop_server()
    common.say(processes_alive_after_shutdown=common.stop_children())
    return 0


if __name__ == "__main__":
    sys.exit(main())
