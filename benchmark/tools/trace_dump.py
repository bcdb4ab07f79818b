#!/usr/bin/env python3
"""Print the structure of an xplane file: planes, lines, and on each line
the event names that took most time with the names of their stats. For
looking at a trace by hand before writing a reader against it.

    python3 benchmark/tools/trace_dump.py <file.xplane.pb> [top]"""

import collections
import sys

from jax.profiler import ProfileData


def main(path: str, top: int = 12) -> None:
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            total = collections.Counter()
            count = collections.Counter()
            stats = {}
            first, last, n = None, None, 0
            for ev in line.events:
                n += 1
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                first = ev.start_ns if first is None else min(first, ev.start_ns)
                last = max(last or 0, ev.start_ns + ev.duration_ns)
                if ev.name not in stats:
                    stats[ev.name] = {k: str(v)[:80] for k, v in ev.stats}
            span = (last - first) / 1e9 if n else 0.0
            print(f"  LINE {line.name!r}: {n} events over {span:.3f}s")
            for name, ns in total.most_common(top):
                print(f"    {ns / 1e6:10.3f} ms x{count[name]:<6} {name[:90]!r}")
                print(f"        stats: {stats[name]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
