"""Distributed tracing: span propagation through task submit/execute.

Reference analogue: `python/ray/util/tracing/tracing_helper.py` — the
reference wraps task submission and worker execution in OpenTelemetry
spans so one request's causality chain is visible across processes. Same
shape here without the OTel dependency (zero-egress image): W3C-style
ids, a thread-local current span, automatic context injection at
`.remote()` (api.RemoteFunction / core_worker.submit_actor_task) and
extraction around user-function execution
(`node_agent._call_user_function`, `actor_process._child_main`), around
each disaggregated-serving leg (`serve/disagg.py`: `disagg.admit` /
`disagg.queue_wait` / `disagg.route` / `disagg.prefill` /
`disagg.kv_export` / `disagg.kv_migration` / `disagg.kv_import` /
`disagg.decode` — under the stream transport `disagg.kv_migration`
overlaps `disagg.prefill` in the same trace), and through the
pipeline trainer (`train/pipeline.py`): a traced `pipeline.step` fans
out into per-worker `pipeline.stage_step` spans with nested
`channel_send`/`channel_recv` spans from `core/channels.py`, so one
trace shows the whole 1F1B timeline. Spans buffer
per process; worker processes flush them to the head with their
heartbeat telemetry (`cross_host.WorkerRuntime`, ingested by
`control_plane.report_telemetry`), so `get_trace()` at the head sees one
connected tree spanning every process a request touched. They are also
exportable as chrome-trace events alongside the timeline
(`util/timeline.py`), so one `ray-tpu timeline` capture shows both
profiling spans AND request causality.

Usage:

    from ray_tpu.util import tracing

    with tracing.start_span("handle_request", {"route": "/chat"}):
        ref = my_task.remote(x)       # ctx injected automatically
        ray_tpu.get(ref)
    tree = tracing.get_trace(...)     # incl. the task's execute span
                                      # (same trace_id, parented here)

Span lifecycle invariant (machine-enforced by `ray_tpu.tools.raylint`
rule R5): a span bound manually — `maybe_begin(...)` / `Span(...)`
instead of the `start_span` context manager — must reach `finish()` on
every path, i.e. in a `finally` or via an owner that finishes it later;
a return/raise edge that skips `finish()` leaks the span out of the
telemetry flush. `finish()` is idempotent, so the fix is mechanical:
wrap the body in try/finally.

Propagation is on only while a span is active — zero overhead otherwise
(the spec field stays None). Serve entry points additionally open root
spans for a `config.trace_sample_rate` fraction of requests (default 0:
off, the zero-overhead fast path).

Hot paths (the engine's loop phases, the data iterator's wait, the
trainer's batch placement) use `region(name, **attrs)` instead of a
span: it times its body ONCE on `now_ns()` — the one clock `Span` and
`util/timeline.py` share — and hands the reading to the caller's
counter (`r.elapsed_s`). While a JAX profiler session is open the region
is also a `jax.profiler.TraceAnnotation`, i.e. an event on the calling
thread's line of the xplane's `/host:CPU` plane, on the device's clock
by construction; while the thread carries a (sampled or propagated)
span it is a child `Span` in the buffer. With neither it costs a flag
test and two clock reads. A backend compile inside a region is counted
as `xla_compiles{under=<region name>}`, and every stage of a program's
way to the device (trace, lower, compile, with the persistent cache's
answer) is timed and named the same way (see `watch_compiles`)."""

from __future__ import annotations

import os
import random
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from ..core.metrics import Counter

_local = threading.local()
_lock = threading.Lock()
_spans: List[Dict[str, Any]] = []
_total = 0  # spans ever buffered (monotone; _spans may have been trimmed)
_MAX_SPANS = 10_000
# util/flight_recorder.attach() points this at its ring so finished spans
# land in the per-process crash record; None = zero-overhead default
_flight_sink = None


# The one clock of spans, regions and the timeline: monotonic
# (perf_counter), anchored to the wall once per process so records from
# different processes line up to wall-clock accuracy and never step.
_ANCHOR_NS = time.time_ns() - time.perf_counter_ns()


def now_ns() -> int:
    return _ANCHOR_NS + time.perf_counter_ns()


# Ids: 128/64 random bits from a per-process generator (a uuid4 per id
# cost two urandom syscalls a span); a forked child draws its own seed.
_ids = random.Random(os.urandom(16))
os.register_at_fork(after_in_child=lambda: _ids.seed(os.urandom(16)))

_m_compiles = Counter(
    "xla_compiles",
    "XLA backend compilations (a persistent-cache load counts: it raises "
    "the same jax event), by the innermost tracing.region open on the "
    "compiling thread (`under`, or \"none\").")
_m_program_seconds = Counter(
    "xla_program_seconds",
    "Seconds jax spent bringing programs to the device, by `stage` (trace: "
    "Python to jaxpr; lower: jaxpr to an MLIR module, Pallas kernels "
    "included; compile: the backend's compile, or the persistent cache's "
    "load), by the persistent cache's answer (`cache`: hit, miss = "
    "compiled and written, off = no cache holds it: every trace and "
    "lower, and a compile with the cache off or under its thresholds) and "
    "by the innermost tracing.region open on the thread (`under`). A stage "
    "is filed once, outermost: what jax traces or compiles inside another "
    "stage's interval is that stage's time.")
_m_programs = Counter(
    "xla_programs",
    "Events beside xla_program_seconds: stages filed, same tags.")


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "start_us", "end_us")

    def __init__(self, name: str, trace_id: Optional[str] = None,
                 parent_id: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None,
                 start_ns: Optional[int] = None):
        self.trace_id = trace_id or "%032x" % _ids.getrandbits(128)
        self.span_id = "%016x" % _ids.getrandbits(64)
        self.parent_id = parent_id
        self.name = name
        self.attrs = dict(attrs or {})
        self.start_us = (now_ns() if start_ns is None else start_ns) / 1e3
        self.end_us: Optional[float] = None

    def context(self) -> Dict[str, str]:
        """The wire form (W3C traceparent shape, dict-framed)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def finish(self, end_ns: Optional[int] = None) -> None:
        if self.end_us is not None:
            return  # idempotent: stream teardown paths may race
        self.end_us = (now_ns() if end_ns is None else end_ns) / 1e3
        rec = {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "attrs": self.attrs, "start_us": self.start_us,
            "end_us": self.end_us, "pid": os.getpid(),
        }
        global _total
        with _lock:
            _spans.append(rec)
            _total += 1
            if len(_spans) > _MAX_SPANS:
                del _spans[: len(_spans) - _MAX_SPANS]
        if _flight_sink is not None:
            try:
                _flight_sink(rec)
            except Exception:
                pass  # the flight recorder must never break tracing


class _RemoteParent:
    """A remote span context installed as this thread's parent without
    recording a span (see `activate`): just enough surface for
    `start_span` / `current_context` to chain under it."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def context(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}


def current_span() -> Optional[Span]:
    return getattr(_local, "span", None)


def record_child(parent, name: str, start_ns: int, end_ns: int,
                 attrs: Optional[Dict[str, Any]] = None) -> None:
    """Buffer a finished child of `parent` (a Span or remote context
    object) from two readings the caller already took — how an owner
    that stamps instants (the engine's per-request stages) turns them
    into spans without timing anything twice."""
    Span(name, trace_id=parent.trace_id, parent_id=parent.span_id,
         attrs=attrs, start_ns=start_ns).finish(end_ns)


def region_since(name: str, start_ns: int, **attrs: Any) -> float:
    """Close here a region that another thread or process opened by
    reading `now_ns()` (the clock is anchored to the wall, so the two
    readings compare): a child span where this thread carries one.
    -> its seconds, for the caller's counter."""
    end = max(now_ns(), start_ns)
    parent = getattr(_local, "span", None)
    if parent is not None:
        record_child(parent, name, start_ns, end, attrs)
    return (end - start_ns) * 1e-9


def named(fn, name: str):
    """`fn` under a function name. jax names a jit's XLA module after the
    traced function (`jit_<name>` in a profile); a functools.partial has
    none and every such program reads `jit__unknown`."""

    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


# jax.profiler.TraceAnnotation once jax is imported (never imported from
# here: a process that runs no jax pays nothing for it)
_annotation = None


def _resolve_annotation():
    global _annotation
    if "jax" not in sys.modules:
        return None
    import jax

    watch_compiles()
    _annotation = jax.profiler.TraceAnnotation
    return _annotation


# jax's three stages of a program's way to the device, each raised with
# `fun_name` as a scalar at its start and as a duration at its end
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_ANSWERS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# a stage shorter than this is counted and not made a span: a start
# re-traces hundreds of `jnp` wrappers in microseconds each
_STAGE_SPAN_FLOOR_S = 1e-3
_watching = False


def watch_compiles() -> None:
    """Register the compile listeners with jax, once a process. Call it
    before the first jit (`enable_compile_cache()`, `serve/llm.py
    start_engine` and the trainer's worker do): what compiled before is
    never seen."""
    global _watching
    import jax

    with _lock:
        if _watching:
            return
        _watching = True
    jax.monitoring.register_scalar_listener(_on_jax_stage_start)
    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _on_jax_stage_start(event: str, _value: float, **_kw: Any) -> None:
    """A stage opens on this thread. jax traces the jits a function calls
    (every `jnp` function is one) inside its own trace, and compiles what
    a trace evaluates eagerly inside it: the depth tells the outermost
    stage, the one that is filed, from what it holds."""
    if event in _STAGES:
        _local.stage_depth = getattr(_local, "stage_depth", 0) + 1


def _on_jax_event(event: str, **_kw: Any) -> None:
    """The persistent cache's answer, raised on the compiling thread
    INSIDE the backend-compile interval it belongs to (a miss: when the
    compiled program is written): kept for that interval's end."""
    answer = _CACHE_ANSWERS.get(event)
    if answer is not None:
        _local.cache_answer = answer


def _on_jax_duration(event: str, seconds: float, fun_name: str = "",
                     **_kw: Any) -> None:
    """A stage ends on this thread: count it under the innermost open
    region, and put an `xla.<stage>` child span that names the program
    into a traced thread's tree: "which step recompiled", timed."""
    stage = _STAGES.get(event)
    if stage is None:
        if event == "/jax/compilation_cache/cache_retrieval_time_sec":
            _local.cache_retrieval_s = seconds
        return
    loc = _local
    depth = loc.stage_depth = max(0, getattr(loc, "stage_depth", 0) - 1)
    inner = getattr(loc, "region", None)
    under = inner.name if inner is not None else "none"
    cache = "off"
    attrs: Dict[str, Any] = {}
    if stage == "compile":
        _m_compiles.inc(tags={"under": under})
        cache, loc.cache_answer = getattr(loc, "cache_answer", "off"), "off"
        if cache == "hit":
            attrs["retrieval_s"], loc.cache_retrieval_s = getattr(
                loc, "cache_retrieval_s", 0.0), 0.0
    if depth:
        return  # inside another stage's interval: that stage's time
    tags = {"stage": stage, "cache": cache, "under": under}
    _m_program_seconds.inc(seconds, tags=tags)
    _m_programs.inc(tags=tags)
    parent = getattr(loc, "span", None)
    if parent is not None and seconds >= _STAGE_SPAN_FLOOR_S:
        end = now_ns()
        # jax names the function at the trace and `jit(<function>)` after
        program = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
        record_child(parent, "xla." + stage, end - int(seconds * 1e9), end,
                     {"program": program, "cache": cache, **attrs})


class region:
    """Time the body once; see the module docstring for the sinks.

        with tracing.region("engine.dispatch", span=16) as r:
            ...
        histogram_child.observe(r.elapsed_s)
    """

    __slots__ = ("name", "attrs", "start_ns", "elapsed_ns", "_outer",
                 "_ann", "_span", "_parent")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self.elapsed_ns = 0

    def __enter__(self) -> "region":
        loc = _local
        self._outer = getattr(loc, "region", None)
        loc.region = self
        ann = _annotation or _resolve_annotation()
        if ann is not None and ann.is_enabled():  # a profiler session is open
            ann = ann(self.name, **self.attrs)
            ann.__enter__()
        else:
            ann = None
        self._ann = ann
        parent = self._parent = getattr(loc, "span", None)
        self.start_ns = now_ns()
        if parent is not None:
            loc.span = self._span = Span(
                self.name, trace_id=parent.trace_id,
                parent_id=parent.span_id, attrs=self.attrs,
                start_ns=self.start_ns)
        return self

    def __exit__(self, *exc) -> bool:
        end = now_ns()
        self.elapsed_ns = end - self.start_ns
        if self._parent is not None:
            self._span.finish(end)
            _local.span = self._parent
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _local.region = self._outer
        return False

    def note(self, **attrs: Any) -> None:
        """Attributes the body learns (sizes, counts), onto the child
        span where the thread carries one. The xplane's annotation was
        written at entry and keeps what it was given there."""
        self.attrs.update(attrs)
        if self._parent is not None:
            self._span.attrs.update(attrs)

    @property
    def elapsed_s(self) -> float:
        return self.elapsed_ns * 1e-9

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.elapsed_ns


def current_context() -> Optional[Dict[str, str]]:
    """ctx dict to stamp into an outgoing TaskSpec (None when tracing is
    inactive on this thread — the common, zero-overhead case)."""
    span = current_span()
    return span.context() if span is not None else None


def should_sample() -> bool:
    """Head-based sampling decision for a NEW request root
    (config.trace_sample_rate). The rate-0 default short-circuits before
    touching the RNG — the provably-zero-overhead path."""
    from ..core.config import config

    rate = float(config.trace_sample_rate)
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return random.random() < rate


def maybe_begin(name: str, attrs: Optional[Dict[str, Any]] = None
                ) -> Optional[Span]:
    """Request-entry hook for serve surfaces: returns an OPEN span (not
    thread-current, not auto-finished — the caller owns `finish()`, via
    `activate()` for the synchronous part and a finally for streams)
    when this thread is already traced or the sampler fires; None on the
    untraced fast path."""
    parent = current_span()
    if parent is not None:
        return Span(name, trace_id=parent.trace_id,
                    parent_id=parent.span_id, attrs=attrs)
    if should_sample():
        return Span(name, attrs=attrs)
    return None


@contextmanager
def start_span(name: str, attrs: Optional[Dict[str, Any]] = None,
               context: Optional[Dict[str, str]] = None):
    """Open a span. `context` parents it under a REMOTE span (extracted
    from an incoming TaskSpec or serve request dict); otherwise it nests
    under this thread's current span (or starts a fresh trace)."""
    parent = current_span()
    if context is not None:
        span = Span(name, trace_id=context["trace_id"],
                    parent_id=context["span_id"], attrs=attrs)
    elif parent is not None:
        span = Span(name, trace_id=parent.trace_id,
                    parent_id=parent.span_id, attrs=attrs)
    else:
        span = Span(name, attrs=attrs)
    prev = parent
    _local.span = span
    try:
        yield span
    finally:
        span.finish()
        _local.span = prev


@contextmanager
def span_if_traced(name: str, attrs: Optional[Dict[str, Any]] = None,
                   context: Optional[Dict[str, str]] = None):
    """`start_span`, but only when a trace is already active — an
    explicit remote `context` or a thread-current span. The untraced
    path yields None without touching the buffer or the RNG, so hot
    paths (object pulls, channel sends, disagg legs) can instrument
    unconditionally at zero cost."""
    if context is None and getattr(_local, "span", None) is None:
        yield None
        return
    with start_span(name, attrs, context=context) as s:
        yield s


@contextmanager
def activate(span_or_ctx):
    """Make an already-open span (or a bare remote context dict) current
    on this thread WITHOUT finishing it on exit — re-entry for request
    work that resumes on other threads (stream generators, get() pool
    workers). Accepts None as a no-op so callers can write
    `with tracing.activate(maybe_begin(...)):` unconditionally."""
    if span_or_ctx is None:
        yield None
        return
    if isinstance(span_or_ctx, dict):
        span_or_ctx = _RemoteParent(span_or_ctx["trace_id"],
                                    span_or_ctx["span_id"])
    prev = current_span()
    _local.span = span_or_ctx
    try:
        yield span_or_ctx
    finally:
        _local.span = prev


def get_spans(trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    with _lock:
        out = list(_spans)
    if trace_id is not None:
        out = [s for s in out if s["trace_id"] == trace_id]
    return out


def get_trace(trace_id: str) -> List[Dict[str, Any]]:
    """The trace as a TREE: root span records (those whose parent is
    absent from the buffer) each carrying a recursively-nested
    `children` list; every level sorted by start time. `trace_id` may be
    a unique prefix (the OpenAI `X-Request-Id` embeds the full id, but
    dashboards may hold a truncation)."""
    with _lock:
        recs = [dict(s) for s in _spans
                if s["trace_id"] == trace_id
                or s["trace_id"].startswith(trace_id)]
    by_id = {s["span_id"]: s for s in recs}
    roots: List[Dict[str, Any]] = []
    for s in recs:
        s.setdefault("children", [])
    for s in recs:
        parent = by_id.get(s["parent_id"]) if s["parent_id"] else None
        if parent is not None and parent is not s:
            parent["children"].append(s)
        else:
            roots.append(s)

    def _sort(nodes: List[Dict[str, Any]]) -> None:
        nodes.sort(key=lambda n: n["start_us"])
        for n in nodes:
            _sort(n["children"])

    _sort(roots)
    return roots


def drain_since(cursor: int) -> Tuple[int, List[Dict[str, Any]]]:
    """Span records buffered after `cursor` (a value this function
    previously returned; start at 0) plus the new cursor. Read-only —
    the caller owns the cursor, so a failed flush can simply retry with
    the old one (ingest() dedupes by span_id)."""
    with _lock:
        dropped = _total - len(_spans)
        start = max(0, cursor - dropped)
        return _total, list(_spans[start:])


def ingest(records: List[Dict[str, Any]]) -> int:
    """Merge span records flushed from another process into this
    buffer (head side of telemetry federation). Deduped by span_id so a
    retried flush is harmless. Returns the number actually added."""
    if not records:
        return 0
    global _total
    added = 0
    with _lock:
        seen = {s["span_id"] for s in _spans}
        for rec in records:
            sid = rec.get("span_id")
            if sid is None or sid in seen:
                continue
            seen.add(sid)
            _spans.append(dict(rec))
            _total += 1
            added += 1
        if len(_spans) > _MAX_SPANS:
            del _spans[: len(_spans) - _MAX_SPANS]
    return added


def clear() -> None:
    with _lock:
        _spans.clear()


def export_to_timeline() -> int:
    """Mirror buffered spans into the chrome-trace timeline (one lane
    per SOURCE process: pid 'trace/<ospid>', tid = trace id prefix) so
    `ray-tpu timeline` renders request causality next to task/profiling
    spans — federated spans land in their origin process's lane."""
    from . import timeline

    n = 0
    for s in get_spans():
        timeline.record(
            s["name"], "X", cat="trace", ts_us=s["start_us"],
            dur_us=(s["end_us"] or s["start_us"]) - s["start_us"],
            pid=f"trace/{s['pid']}", tid=s["trace_id"][:8],
            args={"span": s["span_id"], "parent": s["parent_id"],
                  **{k: v for k, v in s["attrs"].items()
                     if isinstance(v, (int, float, str))}},
        )
        n += 1
    return n
