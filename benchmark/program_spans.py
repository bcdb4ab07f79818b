"""The program's own regions in the profiler's xplane file, beside the
device's busy intervals: what `ray_tpu.util.tracing.region` wrote into the
`/host:CPU` plane while the traced run's profiler session was open.

A region is a `jax.profiler.TraceAnnotation`: an event named `engine.iter`,
`prefill.dispatch`, `data.next`, ... on the line of the thread that ran it,
on the clock the device plane uses. Here they are nested per thread line
(a parent holds the regions that ran inside it), and the device's idle time
is laid against them. A file without such events (the parent commit's, or a
trace of a program that never entered a region) reads as None everywhere:
the readers under benchmark/metrics/ then leave their metric out. Two
reductions that several readers share are at the end."""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import common, trace_reduce

# the program's layers name their regions <layer>.<what>
REGION = re.compile(r"^(engine|prefill|front|data|train|xla)\.[\w.]+$")


class Region:
    """One region: its interval in ns, the thread line it ran on, the
    regions that ran inside it."""

    __slots__ = ("name", "start", "end", "thread", "attrs", "children")

    def __init__(self, name: str, start: int, end: int, thread: str,
                 attrs: Dict[str, Any]):
        self.name, self.start, self.end = name, start, end
        self.thread, self.attrs = thread, attrs
        self.children: List["Region"] = []

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    @property
    def self_seconds(self) -> float:
        return self.seconds - sum(c.seconds for c in self.children)

    def walk(self) -> Iterable["Region"]:
        yield self
        for child in self.children:
            yield from child.walk()

    def __repr__(self) -> str:
        return f"Region({self.name!r}, {self.seconds * 1e3:.3f} ms, " \
               f"{len(self.children)} children)"


def find_xplane(cell_name: str) -> Optional[str]:
    """The newest xplane file the cell's traced run left behind."""
    found = glob.glob(os.path.join(
        common.ROOT, ".bench_trace", cell_name, "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _nest(events: List[Tuple[str, int, int, Dict[str, Any]]],
          thread: str) -> List[Region]:
    """Events of one thread line -> its top-level regions, each holding
    the regions that lie inside it."""
    roots: List[Region] = []
    stack: List[Region] = []
    for name, start, end, attrs in sorted(events,
                                          key=lambda e: (e[1], -e[2])):
        while stack and stack[-1].end <= start:
            stack.pop()
        region = Region(name, start, end, thread, attrs)
        (stack[-1].children if stack else roots).append(region)
        stack.append(region)
    return roots


class Spans:
    """The regions of one xplane file, and the first device's busy
    intervals (empty where the file has no device plane)."""

    def __init__(self, roots: List[Region], busy: List[Tuple[int, int]]):
        self.roots = roots
        self.busy = busy

    def all(self) -> Iterable[Region]:
        for root in self.roots:
            yield from root.walk()

    def named(self, name: str) -> List[Region]:
        return [r for r in self.all() if r.name == name]

    def seconds(self, name: str) -> float:
        return sum(r.seconds for r in self.named(name))

    def idle(self) -> List[Tuple[int, int]]:
        """The gaps between the device's busy intervals."""
        return [(self.busy[i][1], self.busy[i + 1][0])
                for i in range(len(self.busy) - 1)]

    def idle_inside(self, regions: Iterable[Region]) -> Dict[str, float]:
        """Seconds of device idle time lying inside the given regions, by
        region name, plus `total` (all idle seconds) and `inside` (idle
        seconds inside any of them, an instant under two regions of two
        threads counted once)."""
        gaps = self.idle()
        regions = list(regions)
        out: Dict[str, float] = {}
        covered: List[Tuple[int, int]] = []
        for r in regions:
            for lo, hi in _clip(gaps, r.start, r.end):
                out[r.name] = out.get(r.name, 0.0) + (hi - lo) / 1e9
                covered.append((lo, hi))
        out["inside"] = trace_reduce._union_seconds(covered)[0]
        out["total"] = sum(hi - lo for lo, hi in gaps) / 1e9
        return out


def _clip(intervals: List[Tuple[int, int]], lo: int, hi: int):
    """The parts of sorted, disjoint `intervals` inside [lo, hi)."""
    i = bisect.bisect_right(intervals, (lo, lo)) - 1
    for a, b in intervals[max(i, 0):]:
        if a >= hi:
            break
        if b > lo:
            yield max(a, lo), min(b, hi)


@functools.lru_cache(maxsize=4)
def _load(path: str, _mtime: float) -> Optional[Spans]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    roots: List[Region] = []
    busy: List[Tuple[int, int]] = []
    devices = {}
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices[int(m.group(1))] = line
        elif plane.name == trace_reduce.HOST_PLANE:
            for i, line in enumerate(plane.lines):
                events = [(ev.name, int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns),
                           {k: v for k, v in ev.stats})
                          for ev in line.events if REGION.match(ev.name)]
                # two threads may share a name: the line's index tells
                # them apart
                roots.extend(_nest(events, f"{line.name}#{i}"))
    if not roots:
        return None
    if devices:
        ops = trace_reduce._line_events(devices[min(devices)])
        busy = trace_reduce._union_seconds([(s, e) for _, s, e in ops])[1]
    return Spans(roots, busy)


def read(cell_name: str) -> Optional[Spans]:
    """The regions of the cell's newest traced run; None where no file or
    no region is found."""
    path = find_xplane(cell_name)
    if path is None:
        return None
    return _load(path, os.path.getmtime(path))


def read_file(path: str) -> Optional[Spans]:
    return _load(path, os.path.getmtime(path))


# -- shared by several readers -----------------------------------------------


def region_ms_per_step(ctx, name: str) -> Optional[float]:
    """Train cells: seconds of the regions called `name` in the trace over
    the steps traced."""
    steps = ctx["run"].get("traced_steps")
    spans = read(ctx["cell"]["name"])
    if not steps or not spans or not spans.named(name):
        return None
    return 1000.0 * spans.seconds(name) / steps


def stage_ms_per_first_token(ctx, stages) -> Optional[float]:
    """Serve cells: `serve_request_stage_seconds` sums of `stages` over the
    first tokens counted in the same window (`serve_ttft_seconds_count`)."""
    if not ctx.get("counters"):
        return None
    before, after = ctx["counters"]
    first_tokens = common.counter_delta(before, after,
                                        "serve_ttft_seconds_count")
    name = "serve_request_stage_seconds"
    if not first_tokens or not common.counter_delta(before, after,
                                                    name + "_count"):
        return None
    seconds = sum(common.counter_delta(before, after, name + "_sum", stage=s)
                  for s in stages)
    return 1000.0 * seconds / first_tokens
