"""From the profiler's xplane file to numbers: device busy time, time by
operation and by XLA module, and the longest idle gaps by what the host was
doing. Every PR's per-layer metrics go through this one reduction.

Which operation belongs to which kernel or program is data:
benchmark/trace_names.json and every benchmark/trace_names/*.json map a
group to regular expressions over operation and module names. The files
are merged group by group, so a PR that brings a kernel or a family adds a
file, which can add groups and entries to groups and can remove nothing.
Kernels are keyed on the Pallas `name=` that reaches the trace as the HLO
instruction's name (`%paged_decode.5 = ...`), programs on their XLA module
name (`jit_step`)."""

from __future__ import annotations

import bisect
import collections
import functools
import glob
import json
import os
import re
from typing import Any, Dict, List, Tuple

from . import common

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def _union_seconds(intervals: List[Tuple[int, int]]) -> Tuple[float, List[Tuple[int, int]]]:
    """intervals (start_ns, end_ns) -> (seconds covered, merged intervals)."""
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged) / 1e9, [tuple(m) for m in merged]


def _by_name(events) -> Dict[str, List[float]]:
    """name -> [self seconds, calls]. Events of one line nest (a `while`
    holds the operations of its body): an event's self time is its
    duration minus what its children cover, so the sum over names is the
    line's busy time and nothing is counted twice."""
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    stack: List[List[Any]] = []  # [name, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name][0] += self_ns / 1e9
            out[name][1] += 1

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= end - start
        stack.append([name, end, end - start])
    close(1 << 62)
    return dict(out)


def short_name(name: str) -> str:
    """`%fusion.4 = bf16[..] fusion(...)` -> `fusion.4 fusion`;
    `jit_step(123)` -> `jit_step`."""
    m = re.match(r"^%([\w.\-]+) = (?:\([^=]*?\)|\S+) ([\w\-]+)\(", name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return re.sub(r"\(\d+\)$", "", name)[:80]


@functools.lru_cache(maxsize=None)
def load_names(tree: str = common.HERE) -> Dict[str, Any]:
    """<tree>/trace_names.json and <tree>/trace_names/*.json (in the order
    of their names), merged: a later file's entries are appended to the
    group of the same name, its `host_waiting` to that list."""
    paths = [os.path.join(tree, "trace_names.json")] + sorted(
        glob.glob(os.path.join(tree, "trace_names", "*.json")))
    merged: Dict[str, Any] = {"groups": {}, "host_waiting": []}
    for path in paths:
        with open(path) as f:
            table = json.load(f)
        for group, entries in table.get("groups", {}).items():
            merged["groups"].setdefault(group, []).extend(entries)
        merged["host_waiting"].extend(table.get("host_waiting", []))
    return merged


def _line_events(line) -> List[Tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def reduce(path: str, chips: int) -> Dict[str, Any]:
    """-> {"busy_s", "window_s", "ops", "modules", "gaps"}; busy_s is the
    mean over the chips used of the time in which an operation ran,
    window_s the span from the first device event to the last."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_events = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices[int(m.group(1))] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_events.extend(_line_events(line))
    if not devices:
        raise common.BenchFailure(
            f"no device plane with an {OPS_LINE!r} line in {path}: "
            f"{[p.name for p in data.planes]}")
    used = sorted(devices)[:chips]
    busy, ops, modules, first, last = [], [], [], None, 0
    merged0: List[Tuple[int, int]] = []
    module_ops: Dict[str, set] = collections.defaultdict(set)
    for d in used:
        dev_ops = _line_events(devices[d][OPS_LINE])
        seconds, merged = _union_seconds([(s, e) for _, s, e in dev_ops])
        busy.append(seconds)
        if d == used[0]:
            merged0 = merged
        ops.extend(dev_ops)
        if MODULES_LINE in devices[d]:
            dev_modules = sorted(_line_events(devices[d][MODULES_LINE]),
                                 key=lambda e: e[1])
            modules.extend(dev_modules)
            starts = [e[1] for e in dev_modules]
            for name, s, _ in dev_ops:  # which program ran this operation
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < dev_modules[i][2]:
                    module_ops[dev_modules[i][0]].add(name)
        if dev_ops:
            lo = min(s for _, s, _ in dev_ops)
            first = lo if first is None else min(first, lo)
            last = max(last, max(e for _, _, e in dev_ops))
    n = len(used)
    return {
        "busy_s": sum(busy) / n,
        "window_s": (last - (first or 0)) / 1e9,
        # seconds and calls by name, per chip (summed over chips / chips)
        "ops": {k: [v[0] / n, v[1] / n] for k, v in _by_name(ops).items()},
        # one entry per compiled program: its name carries its fingerprint
        "modules": {k: [v[0] / n, v[1] / n]
                    for k, v in _by_name(modules).items()},
        "module_ops": {k: sorted(v) for k, v in module_ops.items()},
        "gaps": _gaps(merged0, host_events),
    }


def _gaps(merged: List[Tuple[int, int]], host_events) -> List[List[Any]]:
    """The idle gaps of the first chip, summed by what the host was doing:
    for each gap the host event that overlaps it most (the shortest such,
    so the most specific)."""
    waiting = [re.compile(e["match"]) for e in load_names()["host_waiting"]]
    host_events = [ev for ev in host_events
                   if not any(w.search(ev[0]) for w in waiting)]
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:100]  # the longest
    if gaps:  # an event shorter than half the shortest gap covers none
        shortest = gaps[-1][1] - gaps[-1][0]
        host_events = [ev for ev in host_events
                       if 2 * (ev[2] - ev[1]) >= shortest]
    total: Dict[str, float] = collections.defaultdict(float)
    for g0, g1 in gaps:
        best, best_key = "unattributed", (0, 0)
        for name, s, e in host_events:
            overlap = min(e, g1) - max(s, g0)
            if 2 * overlap >= g1 - g0:  # covers half the gap or more
                key = (1, -(e - s))     # the shortest such: most specific
                if key > best_key:
                    best, best_key = name, key
        total[best] += (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def breakdown(trace: Dict[str, Any]) -> Dict[str, Any]:
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1][0])[:TOP]
    return {"device_ops": [[label(k), v[0]] for k, v in ops],
            "idle_gaps": [[k[:120], v] for k, v in trace["gaps"][:TOP]]}


def label(op_name: str) -> str:
    """The short name, with the first group of the names' files that holds
    it."""
    for group, entries in load_names()["groups"].items():
        if any(e["where"] == "ops" and re.search(e["match"], op_name)
               for e in entries):
            return f"{group}: {short_name(op_name)}"
    return short_name(op_name)


def group_share(trace: Dict[str, Any], group: str):
    """Percent of the device's busy time spent in the group's operations;
    None where the trace holds none of them."""
    seconds, _ = group_seconds(trace, group)
    if not seconds or not trace["busy_s"]:
        return None
    return 100.0 * seconds / trace["busy_s"]


def group_seconds(trace: Dict[str, Any], group: str) -> Tuple[float, float]:
    """(seconds, calls) per chip of the operations or modules that the
    group's entries in the names' files match. An entry matches names of
    `where` ('ops' or 'modules'); with `contains` true it matches a module
    by the names of the operations that ran inside it. A name that several
    entries of the group match is counted once, by the first of them."""
    seconds = calls = 0.0
    counted = set()
    for entry in load_names()["groups"].get(group, []):
        pattern = re.compile(entry["match"])
        for name, (s, c) in trace[entry["where"]].items():
            # a module may be matched by an operation it holds (`contains`):
            # a span of 4 and one of 16 steps, or the chunk program of any
            # width, are one group whatever their module names
            names = (trace["module_ops"].get(name, [])
                     if entry.get("contains") else [name])
            if (entry["where"], name) not in counted and any(
                    pattern.search(x) for x in names):
                counted.add((entry["where"], name))
                seconds += s
                calls += c * entry.get("calls", 1)
    return seconds, calls
