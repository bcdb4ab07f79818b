"""What every driver of the benchmark shares: finding a cell's files by the
names in BENCHMARK.json, finding a configuration's family by the path in its
file, the device check, the compile watcher, the result line, and leaving no
process behind."""

from __future__ import annotations

import ctypes
import functools
import importlib.util
import json
import os
import time
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# after the runtime's shutdown, what it started may take this long to be gone
# (a pool worker polls for its parent once a second); then it is a leak
EXIT_GRACE_S = 10.0


class BenchFailure(Exception):
    """The run cannot give a result: no result line, exit code 1."""


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str, manifest: Optional[Dict[str, Any]] = None,
              tree: str = HERE) -> Dict[str, Any]:
    """One entry of BENCHMARK.json's `workloads`, joined with the files it
    names: the cell's own file, its configuration, its traffic mix and the
    metrics that list it. The configuration's family is loaded here, before
    anything is timed. `manifest` and `tree` are for tests: a manifest of
    their own, and the directory that holds its workloads/ and traffic/."""
    manifest = manifest or load_manifest()
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{[w['name'] for w in manifest['workloads']]}")
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])

    def read(*parts: str) -> Dict[str, Any]:
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    config = read(ROOT, config_entry["file"])
    cell = read(tree, "workloads", name + ".json")
    _holds(family(config),
           FAMILY_HOLDS_TO_TRAIN if cell["kind"] == "train" else ())
    cell.update(name=name, chips=entry["chips"], config_name=entry["config"],
                config=config, traffic_name=entry["traffic"],
                traffic=read(tree, "traffic", entry["traffic"] + ".json"))

    def listed(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if listed(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if listed(m)]
    return cell


def _load_file(kind: str, name: str, path: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric_name: str):
    """benchmark/metrics/<name>.py, which holds `read(ctx) -> float | None`."""
    return _load_file("metric", metric_name, os.path.join(
        HERE, "metrics", metric_name + ".py")).read


# what a family's file has to hold (benchmark/families/mistral.py states the
# contract), and what more where a train cell uses it
FAMILY_HOLDS = ("model_config", "init_weights", "PAD_TO", "logits_at",
                "modes", "work", "calls_per_pass", "tiny")
FAMILY_HOLDS_TO_TRAIN = ("nll_and_norm_grads", "program_probe",
                         "train_flops_per_token")


def _holds(module: ModuleType, names) -> None:
    missing = [a for a in names if not hasattr(module, a)]
    if missing:
        raise BenchFailure(f"family file {module.__file__} lacks {missing}")


@functools.lru_cache(maxsize=None)
def load_family(path: str) -> ModuleType:
    """The family's file, by its path from the repo's root: how the
    benchmark reaches a model of that family."""
    full = os.path.join(ROOT, path)
    if not os.path.isfile(full):
        folder = os.path.dirname(full)
        found = sorted(f for f in os.listdir(folder) if f.endswith(".py")) \
            if os.path.isdir(folder) else []
        raise BenchFailure(f"no family file {path}; {os.path.dirname(path)}/ "
                           f"has {found}")
    module = _load_file("family", os.path.basename(path)[:-3], full)
    _holds(module, FAMILY_HOLDS)
    return module


def family(spec: Dict[str, Any]) -> ModuleType:
    """The family whose file the configuration's own file names
    (`"family"`, as it names its `"reference"`)."""
    if "family" not in spec:
        raise BenchFailure("the configuration's file names no \"family\"; "
                           "see benchmark/families/")
    return load_family(spec["family"])


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json("peaks.json")
    if kind not in table or kind == "source":
        raise BenchFailure(f"no published peak for device kind {kind!r}; "
                           f"have {sorted(k for k in table if k != 'source')}")
    return table[kind]


# -- the device --------------------------------------------------------------


def require_tpu(chips: int) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu":
        raise BenchFailure(f"no TPU: jax reports {device}")
    if len(devices) < chips:
        raise BenchFailure(f"the cell needs {chips} chips: {device}")
    return device


def memory_peak_bytes(chips: int) -> int:
    """Peak on the fullest chip. The TPU runtime keeps two books: buffers
    (`peak_bytes_in_use`) and what running programs reserve for their
    temporaries (`peak_bytes_reserved`); a train step's peak is both at
    once (PR 24: 4.07 GB of state and 6.23 GB reserved by the step)."""
    import jax

    def peak(device) -> int:
        stats = device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)) \
            + int(stats.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in jax.devices()[:chips])


def wait_for_free_memory(chips: int, timeout_s: float = 60.0) -> int:
    """After the program under test is shut down, wait until its arrays are
    gone from the device (its threads let go of them a moment after
    `stop()`), so that the reference finds room. -> bytes still in use."""
    import gc

    import jax

    deadline = time.monotonic() + timeout_s
    while True:
        gc.collect()
        in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                     for d in jax.devices()[:chips])
        if in_use < 64 * 2 ** 20 or time.monotonic() > deadline:
            return in_use
        time.sleep(0.25)


def enable_cache() -> str:
    """The persistent compile cache, at the program's fixed place inside the
    checkout. Every program is kept, however quick its compile: the engine
    makes many small ones, and each would otherwise compile in every run."""
    import jax

    from ray_tpu.util.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileWatch:
    """Counts backend compilations and cache hits from jax's own events."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        # a hit of the persistent cache still raises one (short)
        # backend_compile event, so what compiled anew is the difference
        # (chip, PR 24: 17 events and 17 hits in a run that found every
        # program of the replica in the cache, 1.5 s together)
        return {"compiles": self.compiles - self.cache_hits,
                "seconds": self.seconds, "cache_hits": self.cache_hits}


# -- counters of the program -------------------------------------------------


def counters() -> Dict[Any, float]:
    """Every sample of the program's metrics registry, keyed (name, tags)."""
    from ray_tpu.core.metrics import registry

    out = {}
    with registry._lock:
        metrics = list(registry._metrics.values())
    for metric in metrics:
        for name, tags, value in metric.samples():
            out[(name, tags)] = value
    return out


def counter_delta(before, after, name: str, **tags: str) -> float:
    """Sum over tag sets that contain `tags` of after - before."""
    want = set(tags.items())
    total = 0.0
    for (n, t), v in after.items():
        if n == name and want <= set(t):
            total += v - before.get((n, t), 0.0)
    return total


# -- leave nothing running ---------------------------------------------------


def adopt_orphans() -> None:
    """PR_SET_CHILD_SUBREAPER: a descendant whose own parent dies falls to
    this process, so `children()` sees every process the run started."""
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(36, 1, 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_CHILD_SUBREAPER)")


def children() -> Dict[int, str]:
    """pid -> name of every live child of this process; zombies are reaped."""
    me, out = os.getpid(), {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                stat = f.read()
        except OSError:  # gone since listdir
            continue
        name = stat[stat.index("(") + 1: stat.rindex(")")]
        state, ppid = stat[stat.rindex(")") + 2:].split()[:2]
        if int(ppid) != me:
            continue
        if state == "Z":
            try:
                os.waitpid(int(entry), os.WNOHANG)
            except ChildProcessError:  # its owner reaped it meanwhile
                pass
            continue
        out[int(entry)] = name
    return out


def stop_children() -> List[str]:
    """Stop what outlives `ray_tpu.shutdown()` by design (the pool's
    forkserver and multiprocessing's resource tracker), then wait for every
    other child to be gone. What is left after EXIT_GRACE_S is killed, and
    the run fails. (Copied from chip_smoke.py, PR 23.) -> names alive at entry."""
    from multiprocessing import forkserver, resource_tracker

    at_entry = children()
    forkserver._forkserver._stop()
    resource_tracker._resource_tracker._stop()
    deadline = time.monotonic() + EXIT_GRACE_S
    while (left := children()) and time.monotonic() < deadline:
        time.sleep(0.05)
    for pid in left:
        try:
            os.kill(pid, 9)
            os.waitpid(pid, 0)
        except (ProcessLookupError, ChildProcessError):
            pass
    if left:
        raise BenchFailure(
            f"still running {EXIT_GRACE_S}s after shutdown, killed: {left}")
    return sorted(at_entry.values())


# -- the last line -----------------------------------------------------------


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)


def keep_run(cell_name: str, run_info: Dict[str, Any]) -> None:
    """The newest run's per-request records, in .bench_runs/<cell>.json
    inside the checkout until the cell's next run: what a builder reads
    another statistic of the same window from."""
    os.makedirs(os.path.join(ROOT, ".bench_runs"), exist_ok=True)
    with open(os.path.join(ROOT, ".bench_runs", cell_name + ".json"), "w") as f:
        json.dump(run_info, f)


def say(**obj: Any) -> None:
    """One JSON object per line of progress, before the last line."""
    print(json.dumps(obj), flush=True)


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]
