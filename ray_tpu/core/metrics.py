"""Tagged metrics with Prometheus text exposition.

Equivalent of the reference's metric pipeline (upstream ray
`src/ray/stats/metric.h :: stats::Metric`, `metric_defs.cc`, and the Python
`ray/util/metrics.py :: Counter/Gauge/Histogram`): one registry per process,
metrics carry tag sets, and the whole registry renders to the Prometheus text
format for scraping by the node agent.
"""

from __future__ import annotations

import bisect
import contextlib
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "MICRO_BUCKETS", "render_merged",
]

TagMap = Tuple[Tuple[str, str], ...]


def _tags(tags: Optional[Dict[str, str]]) -> TagMap:
    return tuple(sorted((tags or {}).items()))


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, description: str = "", registry_: "MetricsRegistry | None" = None):
        self.name = name
        self.description = description
        self._lock = threading.Lock()
        (registry_ or registry).register(self)

    def samples(self) -> Iterable[Tuple[str, TagMap, float]]:
        raise NotImplementedError

    def reset(self) -> None:
        """Zero accumulated values while staying registered — the
        between-tests reset (`registry.fresh()`) that, unlike `clear()`,
        does not orphan module-level metric objects."""
        raise NotImplementedError

    def labels(self, **tags: str) -> "_Bound":
        """A child bound to one tag set, resolved once: `inc`/`set`/
        `observe` on it skip the per-call dict sort, for observations
        inside a loop (the engine's phases)."""
        return _Bound(self, _tags(tags))


class _Bound:
    __slots__ = ("_metric", "_tags")

    def __init__(self, metric: _Metric, tags: TagMap):
        self._metric = metric
        self._tags = tags

    def inc(self, value: float = 1.0) -> None:
        self._metric._inc(self._tags, value)

    def set(self, value: float) -> None:
        self._metric._set(self._tags, value)

    def observe(self, value: float) -> None:
        self._metric._observe(self._tags, value)


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name, description="", registry_=None):
        self._values: Dict[TagMap, float] = {}
        super().__init__(name, description, registry_)

    def inc(self, value: float = 1.0, tags: Optional[Dict[str, str]] = None) -> None:
        self._inc(_tags(tags), value)

    def _inc(self, key: TagMap, value: float) -> None:
        if value < 0:
            raise ValueError("counters only increase")
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def get(self, tags: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return self._values.get(_tags(tags), 0.0)

    def samples(self):
        with self._lock:
            return [(self.name, k, v) for k, v in self._values.items()]

    def reset(self):
        with self._lock:
            self._values.clear()


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name, description="", registry_=None):
        self._values: Dict[TagMap, float] = {}
        super().__init__(name, description, registry_)

    def set(self, value: float, tags: Optional[Dict[str, str]] = None) -> None:
        self._set(_tags(tags), value)

    def _set(self, key: TagMap, value: float) -> None:
        with self._lock:
            self._values[key] = float(value)

    def add(self, delta: float, tags: Optional[Dict[str, str]] = None) -> None:
        key = _tags(tags)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta

    def get(self, tags: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return self._values.get(_tags(tags), 0.0)

    @contextlib.contextmanager
    def track(self, tags: Optional[Dict[str, str]] = None):
        """In-flight tracking: +1 on entry, -1 on exit (exception included).
        The gauge reads as the number of bodies currently executing."""
        self.add(1, tags)
        try:
            yield
        finally:
            self.add(-1, tags)

    def samples(self):
        with self._lock:
            return [(self.name, k, v) for k, v in self._values.items()]

    def reset(self):
        with self._lock:
            self._values.clear()


_DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 60, 300)

# For sub-millisecond distributions (KV-cache migration, object pulls):
# the defaults bottom out at 1ms, which flattens a 2.9ms-mean migration
# and a sub-ms pull into two buckets.
MICRO_BUCKETS = (
    1e-6, 5e-6, 1e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.5, 1, 5, 30,
)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, description="", buckets: Sequence[float] = _DEFAULT_BUCKETS, registry_=None):
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[TagMap, List[int]] = {}
        self._sums: Dict[TagMap, float] = {}
        self._totals: Dict[TagMap, int] = {}
        super().__init__(name, description, registry_)

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None) -> None:
        self._observe(_tags(tags), value)

    def _observe(self, key: TagMap, value: float) -> None:
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            idx = bisect.bisect_left(self.buckets, value)
            if idx < len(counts):
                counts[idx] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1

    def count(self, tags: Optional[Dict[str, str]] = None) -> int:
        with self._lock:
            return self._totals.get(_tags(tags), 0)

    def sum(self, tags: Optional[Dict[str, str]] = None) -> float:
        with self._lock:
            return self._sums.get(_tags(tags), 0.0)

    def samples(self):
        out = []
        with self._lock:
            for key, counts in self._counts.items():
                cumulative = 0
                for bound, c in zip(self.buckets, counts):
                    cumulative += c
                    out.append(
                        (f"{self.name}_bucket", key + (("le", repr(bound)),), float(cumulative))
                    )
                out.append((f"{self.name}_bucket", key + (("le", "+Inf"),), float(self._totals[key])))
                out.append((f"{self.name}_sum", key, self._sums[key]))
                out.append((f"{self.name}_count", key, float(self._totals[key])))
        return out

    def reset(self):
        with self._lock:
            self._counts.clear()
            self._sums.clear()
            self._totals.clear()


class MetricsRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def register(self, metric: _Metric) -> None:
        with self._lock:
            existing = self._metrics.get(metric.name)
            if existing is not None and existing is not metric:
                raise ValueError(f"metric already registered: {metric.name}")
            self._metrics[metric.name] = metric

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def unregister(self, name: str) -> bool:
        """Drop one metric by name so a fresh object may re-register it.
        Returns whether it was present."""
        with self._lock:
            return self._metrics.pop(name, None) is not None

    def clear(self) -> None:
        """Forget every metric. NOTE: module-level metric objects created
        at import time keep pointing at this registry but are no longer
        in it — their samples silently stop being exported, and creating
        a same-named replacement raises. Tests that want a clean slate
        should call `fresh()` instead."""
        with self._lock:
            self._metrics.clear()

    def fresh(self) -> None:
        """Zero every registered metric's accumulated values while
        keeping registrations intact — the safe between-tests reset."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m.reset()

    def snapshot(self) -> List[Dict[str, Any]]:
        """A plain-data dump of every metric family (wire-friendly: only
        dicts/lists/tuples/scalars) for telemetry shipping to the head."""
        with self._lock:
            metrics = list(self._metrics.values())
        out = []
        for m in sorted(metrics, key=lambda m: m.name):
            out.append({
                "name": m.name,
                "kind": m.kind,
                "description": m.description,
                "samples": [(sname, list(tags), float(value))
                            for sname, tags, value in m.samples()],
            })
        return out

    def render_prometheus(self) -> str:
        lines: List[str] = []
        with self._lock:
            metrics = list(self._metrics.values())
        for m in sorted(metrics, key=lambda m: m.name):
            if m.description:
                lines.append(f"# HELP {m.name} {m.description}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for name, tags, value in m.samples():
                lines.append(_sample_line(name, tags, value))
        return "\n".join(lines) + "\n"


def _sample_line(name: str, tags, value: float) -> str:
    if tags:
        tag_str = ",".join(f'{k}="{v}"' for k, v in tags)
        return f"{name}{{{tag_str}}} {value}"
    return f"{name} {value}"


def render_merged(local: MetricsRegistry,
                  remote_snapshots: Dict[str, Dict[str, Any]]) -> str:
    """Prometheus text for the whole cluster: the local (head) registry
    plus per-node `registry.snapshot()` payloads shipped via telemetry
    (`remote_snapshots`: node_id -> {"role": ..., "metrics": [...]}).
    Remote samples gain `node_id`/`role` tags; each family gets one
    HELP/TYPE header even when several processes export it."""
    families: Dict[str, Dict[str, Any]] = {}

    def _add_family(name: str, kind: str, desc: str):
        fam = families.get(name)
        if fam is None:
            fam = families[name] = {"kind": kind, "desc": desc, "lines": []}
        return fam

    with local._lock:
        local_metrics = list(local._metrics.values())
    for m in local_metrics:
        fam = _add_family(m.name, m.kind, m.description)
        for sname, tags, value in m.samples():
            fam["lines"].append(_sample_line(sname, tags, value))

    for node_id, snap in sorted(remote_snapshots.items()):
        extra = (("node_id", node_id[:12]),)
        role = snap.get("role")
        if role:
            extra += (("role", role),)
        for fam_snap in snap.get("metrics", []):
            fam = _add_family(fam_snap["name"], fam_snap["kind"],
                              fam_snap.get("description", ""))
            for sname, tags, value in fam_snap["samples"]:
                merged = tuple(sorted(list(map(tuple, tags)) + list(extra)))
                fam["lines"].append(_sample_line(sname, merged, value))

    lines: List[str] = []
    for name in sorted(families):
        fam = families[name]
        if fam["desc"]:
            lines.append(f"# HELP {name} {fam['desc']}")
        lines.append(f"# TYPE {name} {fam['kind']}")
        lines.extend(fam["lines"])
    return "\n".join(lines) + "\n"


registry = MetricsRegistry()
