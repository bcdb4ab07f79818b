#!/usr/bin/env python3
"""Builder's tool: run sets of the benchmark's runs one after another, as
the driver would, and keep what each printed. Every chip reading of PERF.md
from the review session of PR 24 on was made by one call of this.

    python3 benchmark/tools/sets.py --tag <name> [--dir <checkout>] \\
        [--seconds <s>] <cell>:<set>:<first seed>-<last seed>:<trace> ...

Each run is the benchmark's own command (BENCHMARK.json's `command` with
--workload --seed --seconds --trace), a new process, from `--dir` (a copy
of the committed files, for the proof that they are enough). Under
chiprun_out/<tag>/ it writes the command lines (commands.jsonl), each
run's output and errors, the per-request records the run left in
.bench_runs/, and summary.jsonl: exit code, wall seconds, the result line
and the run's `window` line. `spreads` at the end gives, per cell, set and
metric, the median and the spread (IQR / median, statistics.quantiles)."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def last_json(text: str, key: str):
    for line in reversed(text.splitlines()):
        if line.startswith("{") and f'"{key}"' in line:
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if key in obj:
                return obj
    return None


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tag", required=True)
    parser.add_argument("--dir", default=".")
    parser.add_argument("--seconds", type=int, default=0,
                        help="default: BENCHMARK.json's run_seconds")
    parser.add_argument("runs", nargs="+")
    args = parser.parse_args()
    checkout = os.path.abspath(os.path.join(ROOT, args.dir))
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    summaries, failures = [], 0
    for spec in args.runs:
        cell, set_name, seeds, trace = spec.split(":")
        first, _, last = seeds.partition("-")
        for seed in range(int(first), int(last or first) + 1):
            command = manifest["command"] + [
                "--workload", cell, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", trace]
            stem = os.path.join(out_dir, f"{cell}.{set_name}.{seed}.t{trace}")
            with open(os.path.join(out_dir, "commands.jsonl"), "a") as f:
                f.write(json.dumps({"cwd": args.dir, "command": command}) + "\n")
            records = os.path.join(checkout, ".bench_runs", cell + ".json")
            if os.path.exists(records):
                os.remove(records)  # an earlier run's
            t0 = time.time()
            proc = subprocess.run(command, cwd=checkout, capture_output=True,
                                  text=True, timeout=1500)
            wall = time.time() - t0
            with open(stem + ".out", "w") as f:
                f.write(proc.stdout)
            with open(stem + ".err", "w") as f:
                f.write(proc.stderr[-20000:])
            if trace == "0" and os.path.exists(records):
                shutil.copyfile(records, stem + ".records.json")
            result = last_json(proc.stdout, "correct")
            window = last_json(proc.stdout, "window")
            row = {"cell": cell, "set": set_name, "seed": seed, "trace": trace,
                   "rc": proc.returncode, "wall_s": round(wall, 1),
                   "result": result, "window": window and window["window"]}
            summaries.append(row)
            with open(os.path.join(out_dir, "summary.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
            metrics = {k: v["value"] for k, v in
                       (result or {}).get("metrics", {}).items()}
            print(json.dumps({k: row[k] for k in
                              ("cell", "set", "seed", "trace", "rc", "wall_s")}
                             | {"correct": result and result["correct"],
                                "metrics": metrics}), flush=True)
            if proc.returncode != 0:
                print(proc.stderr[-1500:], flush=True)
                failures += 1
                if failures == 2:  # a fault of the tree, not of one run
                    print("two runs failed: stopping", flush=True)
                    return 1
    groups = {}
    for row in summaries:
        if row["result"] and row["trace"] == "0":
            for name, m in row["result"]["metrics"].items():
                groups.setdefault((row["cell"], row["set"], name), []).append(
                    m["value"])
    for (cell, set_name, name), values in sorted(groups.items()):
        print(json.dumps({
            "spreads": cell, "set": set_name, "metric": name, "n": len(values),
            "median": statistics.median(values),
            "spread": spread(values) if len(values) >= 3 else None}), flush=True)
    return 0 if all(r["rc"] == 0 for r in summaries) else 1


if __name__ == "__main__":
    sys.exit(main())
