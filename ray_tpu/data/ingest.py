"""Shared multi-tenant ingest service: one autoscaled CPU-host data fleet
feeding trainers, the RL loop, and batch inference with provable fair-share.

Reference: tf.data service (arXiv:2210.14826) — preprocessing disaggregates
onto a shared worker pool, jobs register datasets against a dispatcher, and
the dispatcher divides pool throughput by job weight. Mapped onto ray_tpu:

- `IngestWorker` actors (CPU-host, ``in_process``) hold installed pipeline
  stages and execute one *block* per task: read (or take an input block),
  then run every fused map stage, sealing the preprocessed block into the
  object plane of a dedicated ingest node.
- `IngestService` is the head-side dispatcher: `register(dataset, tenant=)`
  compiles the dataset's fused plan into a shippable blob, and an admission
  loop thread dispatches pending block tasks by deficit round-robin over
  tenants (data/tenant.py) under per-tenant in-flight byte budgets — a hog
  tenant gets exactly its weight share and nobody starves.
- Completed blocks are cached ephemeral in the object plane under the
  `PIN_INGEST` ledger reason: a repeat epoch streams straight from cache
  (near-free), the driver's pull-through replica makes repeat *gets* count
  as `object_cache_hits`, and the PR 10 cold-cache sweep plus this module's
  janitor keep abandoned blocks from leaking.
- An autoscale controller thread watches per-tenant
  `data_stage_stall_seconds{stage="ingest",tenant=}` deltas (the same
  signal the health plane's tenant-scoped `data_stall_rising` rule groups
  by) and grows the worker pool within ``ingest_pool_min..max`` under the
  fleet knobs `autoscale_cooldown_s` / `autoscale_step_max`, retiring
  workers back down after sustained idleness.

The client surface is a drop-in `DataIterator`: ``it = IngestClient()
.register(ds, tenant="trainer", weight=3)`` then ``it.iter_batches(...)``
exactly like a local iterator — each epoch re-streams from the service.
"""

from __future__ import annotations

import dataclasses
import inspect
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

from .. import api
from ..core import core_worker, object_ledger
from ..core.config import config
from ..core.logging import get_logger
from ..core.metrics import Counter, Gauge
from ..core.task_spec import NodeAffinitySchedulingStrategy
from .block import Block, BlockAccessor
from .executor import _m_stall, _nbytes_of
from .iterator import DataIterator
from .logical import InputData, MapBatches, Read, compile_stage, fuse
from .tenant import FairShareScheduler, TenantSpec

logger = get_logger("data.ingest")

# how often the admission loop runs cache janitoring (TTL + condemned)
_JANITOR_PERIOD_S = 1.0
# consecutive quiet controller evals before the pool scales back down
# (mirrors FleetController's idle_periods debounce)
_IDLE_PERIODS = 3

_m_rows = Counter(
    "ingest_rows_total",
    "Rows produced by ingest preprocess tasks, per tenant (fresh blocks "
    "only — cache hits are ingest_cache_hits_total).")
_m_tasks = Counter(
    "ingest_preprocess_tasks_total",
    "Preprocess block tasks executed on ingest workers, per tenant.")
_m_bytes = Counter(
    "ingest_tenant_bytes_total",
    "Output bytes of completed ingest blocks, per tenant (the fair-share "
    "currency).")
_m_hits = Counter(
    "ingest_cache_hits_total",
    "Epoch block requests served from the ephemeral ingest cache, per "
    "tenant.")
_m_miss = Counter(
    "ingest_cache_misses_total",
    "Epoch block requests that needed a fresh preprocess task, per tenant.")
_m_evicted = Counter(
    "ingest_cache_evicted_total",
    "Cached ingest blocks freed by the janitor (TTL expiry or tenant "
    "deregistration).")
_m_pool = Gauge(
    "ingest_pool_size",
    "Live (non-retiring) ingest workers in the shared pool.")
_m_fair = Gauge(
    "ingest_fair_share_ratio",
    "Served-byte share divided by weight share per tenant (1.0 = exactly "
    "fair).")


@api.remote(num_cpus=0, in_process=True)
class IngestWorker:
    """One worker of the shared ingest pool.

    Pipelines install once per (worker, registration): the blob carries the
    dataset's read tasks plus its fused map segments; callable-class
    ``map_batches(compute="actors")`` fns instantiate HERE, once per worker
    (the ActorPoolMapOperator property — model/vocab loads amortize across
    every block this worker preprocesses)."""

    def __init__(self):
        self._pipelines: Dict[str, Tuple[List[Any], List[Any]]] = {}

    def install(self, reg_id: str, blob: bytes) -> bool:
        if reg_id in self._pipelines:
            return True
        import cloudpickle

        read_tasks, segments = cloudpickle.loads(blob)
        stages: List[Any] = []
        for seg in segments:
            if isinstance(seg, MapBatches):
                if inspect.isclass(seg.fn):
                    seg = dataclasses.replace(seg, fn=seg.fn())
                stages.append(compile_stage([seg]))
            else:
                stages.append(seg)  # already a fused callable
        self._pipelines[reg_id] = (list(read_tasks), stages)
        return True

    def uninstall(self, reg_id: str) -> bool:
        self._pipelines.pop(reg_id, None)
        return True

    def run_block(self, reg_id: str, idx: int, tenant: str,
                  block: Optional[Block] = None) -> Block:
        read_tasks, stages = self._pipelines[reg_id]
        if block is None:
            out = read_tasks[idx]()
            if hasattr(out, "__next__"):
                parts = list(out)
                block = parts[0] if len(parts) == 1 else BlockAccessor.concat(parts)
            else:
                block = out
        for stage in stages:
            block = stage(block)
        tags = {"tenant": tenant}
        _m_tasks.inc(1.0, tags=tags)
        try:
            _m_rows.inc(float(BlockAccessor(block).num_rows()), tags=tags)
        except Exception:  # noqa: BLE001 — exotic block types still flow
            pass
        return block

    def ping(self) -> bool:
        """FIFO barrier: completes only after every prior task."""
        return True


class _Registration:
    """One registered dataset of one tenant (service-lock owned)."""

    def __init__(self, reg_id: str, tenant: str, n_blocks: int, blob: bytes,
                 input_refs: Optional[List[Any]]):
        self.reg_id = reg_id
        self.tenant = tenant
        self.n_blocks = n_blocks
        self.blob = blob
        self.input_refs = input_refs  # InputData sources; None for Read
        self.active = True
        self.cache: Dict[int, Any] = {}      # idx -> block ObjectRef
        self.cache_t: Dict[int, float] = {}  # idx -> last-touch monotonic
        self.epochs = 0


class _Worker:
    def __init__(self, handle):
        self.handle = handle
        self.outstanding = 0
        self.retiring = False
        self.installed: Set[str] = set()


class _Flight:
    """One dispatched-but-unfinished block task."""

    def __init__(self, key, tenant, ref, worker, charged):
        self.key = key          # (reg_id, idx)
        self.tenant = tenant
        self.ref = ref
        self.worker = worker
        self.charged = charged  # byte estimate taken at dispatch


class IngestService:
    """Head-side dispatcher + autoscaler of the shared ingest fleet."""

    def __init__(self, *, pool_min: Optional[int] = None,
                 pool_max: Optional[int] = None, autoscale: bool = True,
                 quantum_bytes: Optional[int] = None):
        self._rt = core_worker.get_runtime()
        self._pool_min = max(1, int(pool_min if pool_min is not None
                                    else config.get("ingest_pool_min")))
        self._pool_max = max(self._pool_min,
                             int(pool_max if pool_max is not None
                                 else config.get("ingest_pool_max")))
        # quantum sized to ~a block keeps DRR granularity tight; the knob
        # default suits MB-scale blocks, tiny-block tests pass their own
        self._sched = FairShareScheduler(quantum_bytes=quantum_bytes)
        self._lock = threading.RLock()
        self._regs: Dict[str, _Registration] = {}
        self._reg_seq = 0
        # (reg_id, idx) keys currently queued or in flight — dedups work
        # when several epochs want the same not-yet-built block
        self._keyed: Set[Tuple[str, int]] = set()
        # key -> epoch queues waiting for that block
        self._waiters: Dict[Tuple[str, int], List[queue.Queue]] = {}
        self._flights: Dict[Any, _Flight] = {}  # object_id -> flight
        self._workers: List[_Worker] = []
        # (refs, eviction deadline) of deregistered tenants' cached blocks
        self._condemned: List[Tuple[List[Any], float]] = []
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._stall_prev: Dict[str, float] = {}
        self._idle = 0
        self._last_scale_up = float("-inf")
        self.scale_events: List[Dict[str, Any]] = []

        # Dedicated CPU:0 node for the pool: worker output seals OFF the
        # driver agent, so the driver's first get of each block pull-through
        # caches it locally (PIN_CACHE + pulled_through) and every repeat-
        # epoch get counts as an object_cache_hit — the cache-economics
        # proof (and the PR 10 sweep) ride on blocks having a remote origin.
        self._node = self._rt.add_node(resources={"CPU": 0.0},
                                       labels={"ray_tpu.role": "ingest"})
        self._affinity = NodeAffinitySchedulingStrategy(
            node_id=self._node.info.node_id)
        with self._lock:
            for _ in range(self._pool_min):
                self._spawn_worker_locked()
            _m_pool.set(float(len(self._workers)))

        self._admission = threading.Thread(
            target=self._admission_loop, daemon=True, name="ingest-admission")
        self._admission.start()
        self._controller: Optional[threading.Thread] = None
        if autoscale:
            self._controller = threading.Thread(
                target=self._controller_loop, daemon=True,
                name="ingest-autoscaler")
            self._controller.start()

    # -- registration -----------------------------------------------------

    def register(self, dataset, *, tenant: str = "default",
                 weight: float = 0.0,
                 max_in_flight_bytes: int = 0) -> "IngestIterator":
        """Register a dataset for a tenant; returns a DataIterator drop-in
        whose every epoch streams preprocessed blocks from the shared
        pool under fair-share admission."""
        if self._stop.is_set():
            raise RuntimeError("ingest service is shut down")
        segments = fuse(dataset._plan)
        source, rest = segments[0], segments[1:]
        for seg in rest:
            if not (callable(seg) or isinstance(seg, MapBatches)):
                raise ValueError(
                    "ingest pipelines support per-block (map-style) "
                    f"operators only; found all-to-all op {seg!r} — "
                    "materialize() the dataset first")
        if isinstance(source, Read):
            read_tasks = list(source.read_tasks)
            input_refs: Optional[List[Any]] = None
            n = len(read_tasks)
        elif isinstance(source, InputData):
            read_tasks = []
            input_refs = list(source.blocks)
            n = len(input_refs)
        else:
            raise ValueError(
                f"ingest pipelines need a Read or InputData source, got "
                f"{source!r}")
        if n == 0:
            raise ValueError("cannot register an empty dataset")
        import cloudpickle

        blob = cloudpickle.dumps((read_tasks, rest))
        self._sched.ensure_tenant(
            TenantSpec(tenant, weight, max_in_flight_bytes))
        with self._lock:
            reg_id = f"{tenant}-r{self._reg_seq}"
            self._reg_seq += 1
            self._regs[reg_id] = _Registration(
                reg_id, tenant, n, blob, input_refs)
        logger.info("ingest register %s: tenant=%s blocks=%d stages=%d",
                    reg_id, tenant, n, len(rest))
        return IngestIterator(self, reg_id, tenant)

    def deregister(self, reg_id: str, *, grace_s: float = 0.0) -> None:
        """Drop a registration. Its cached blocks are condemned: freed by
        the janitor once `grace_s` elapses (0 = next pass). In-flight
        blocks complete but are not cached."""
        with self._lock:
            reg = self._regs.pop(reg_id, None)
            if reg is None:
                return
            reg.active = False
            refs = list(reg.cache.values())
            reg.cache.clear()
            reg.cache_t.clear()
            if refs:
                self._condemned.append(
                    (refs, time.monotonic() + float(grace_s)))
            inflight = {fl.key for fl in self._flights.values()}
            self._keyed = {k for k in self._keyed
                           if k[0] != reg_id or k in inflight}
            workers = list(self._workers)
        for w in workers:
            if reg_id in w.installed:
                try:
                    w.handle.uninstall.remote(reg_id)
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
                w.installed.discard(reg_id)
        logger.info("ingest deregister %s: condemned=%d grace=%.1fs",
                    reg_id, len(refs), grace_s)

    def deregister_tenant(self, tenant: str, *, grace_s: float = 0.0) -> None:
        """Drop every registration of a tenant plus its scheduler state."""
        with self._lock:
            rids = [rid for rid, r in self._regs.items() if r.tenant == tenant]
        for rid in rids:
            self.deregister(rid, grace_s=grace_s)
        self._sched.drop_tenant(tenant)

    # -- epoch streaming --------------------------------------------------

    def _epoch_stream(self, reg_id: str):
        """One epoch of one registration: yield every block ref — cached
        blocks immediately, missing blocks as the fair-share admission
        loop completes them (completion order)."""
        ep_q: queue.Queue = queue.Queue()
        to_enqueue: List[Tuple[str, int]] = []
        cached: List[Any] = []
        with self._lock:
            reg = self._regs.get(reg_id)
            if reg is None or not reg.active:
                raise RuntimeError(
                    f"unknown or deregistered ingest registration {reg_id}")
            tenant = reg.tenant
            reg.epochs += 1
            now = time.monotonic()
            waiting = 0
            for idx in range(reg.n_blocks):
                ref = reg.cache.get(idx)
                if ref is not None:
                    reg.cache_t[idx] = now
                    cached.append(ref)
                    continue
                waiting += 1
                key = (reg_id, idx)
                self._waiters.setdefault(key, []).append(ep_q)
                if key not in self._keyed:
                    self._keyed.add(key)
                    to_enqueue.append(key)
        tags = {"tenant": tenant}
        if cached:
            _m_hits.inc(float(len(cached)), tags=tags)
        if waiting:
            _m_miss.inc(float(waiting), tags=tags)
        for key in to_enqueue:
            self._sched.enqueue(tenant, key)
        if to_enqueue:
            self._wake.set()

        def gen():
            for ref in cached:
                yield ref
            remaining = waiting
            while remaining:
                t0 = time.perf_counter()
                try:
                    item = ep_q.get(timeout=0.05)
                except queue.Empty:
                    item = None
                # every moment blocked here is demand on the shared pool:
                # the per-tenant stall signal the autoscaler (and health's
                # tenant-scoped data_stall_rising rule) watches — counted
                # on successful gets too, or a steady sub-timeout trickle
                # from an undersized pool would look like zero stall
                _m_stall.inc(time.perf_counter() - t0,
                             tags={"stage": "ingest", "tenant": tenant})
                if item is None:
                    if self._stop.is_set():
                        raise RuntimeError(
                            "ingest service shut down mid-epoch")
                    if not reg.active:
                        raise RuntimeError(
                            f"ingest registration {reg_id} deregistered "
                            "mid-epoch")
                    continue
                remaining -= 1
                yield item[1]
        return gen()

    # -- admission loop ---------------------------------------------------

    def _admission_loop(self) -> None:
        last_janitor = 0.0
        while not self._stop.is_set():
            try:
                if core_worker._global_runtime is not self._rt:
                    return
                progressed = self._poll_completions()
                progressed |= self._dispatch()
                self._reap_retiring()
                now = time.monotonic()
                if now - last_janitor >= _JANITOR_PERIOD_S:
                    last_janitor = now
                    self.evict()
                if not progressed:
                    with self._lock:
                        refs = [fl.ref for fl in self._flights.values()]
                    if refs:
                        api.wait(refs, num_returns=1, timeout=0.02)
                    else:
                        self._wake.wait(0.01)
                    self._wake.clear()
            except Exception:  # noqa: BLE001 — the loop must survive
                if (self._stop.is_set()
                        or core_worker._global_runtime is not self._rt):
                    return
                logger.exception("ingest admission iteration failed")
                time.sleep(0.05)

    def _dispatch(self) -> bool:
        progressed = False
        while not self._stop.is_set():
            with self._lock:
                live = [w for w in self._workers if not w.retiring]
                if not live or len(self._flights) >= 2 * len(live):
                    return progressed
            nxt = self._sched.next()
            if nxt is None:
                return progressed
            tenant, key, charged = nxt
            reg_id, idx = key
            cancelled = False
            with self._lock:
                reg = self._regs.get(reg_id)
                if reg is None or not reg.active:
                    self._keyed.discard(key)
                    cancelled = True
                elif idx in reg.cache:
                    # a racing epoch already built it
                    self._keyed.discard(key)
                    self._deliver_locked(key, reg.cache[idx])
                    cancelled = True
                else:
                    live = ([w for w in self._workers if not w.retiring]
                            or self._workers)
                    w = min(live, key=lambda x: x.outstanding)
                    if reg_id not in w.installed:
                        # FIFO actor mailbox: install lands before run_block
                        w.handle.install.remote(reg_id, reg.blob)
                        w.installed.add(reg_id)
                    if reg.input_refs is not None:
                        ref = w.handle.run_block.remote(
                            reg_id, idx, tenant, reg.input_refs[idx])
                    else:
                        ref = w.handle.run_block.remote(reg_id, idx, tenant)
                    self._flights[ref.object_id] = _Flight(
                        key, tenant, ref, w, charged)
                    w.outstanding += 1
            if cancelled:
                self._sched.cancel(tenant, charged)
            progressed = True
        return progressed

    def _poll_completions(self) -> bool:
        with self._lock:
            refs = [fl.ref for fl in self._flights.values()]
        if not refs:
            return False
        done, _ = api.wait(refs, num_returns=len(refs), timeout=0)
        for ref in done:
            self._finish(ref)
        return bool(done)

    def _finish(self, ref) -> None:
        oid = ref.object_id
        with self._lock:
            fl = self._flights.pop(oid, None)
        if fl is None:
            return
        err = None
        try:
            fut = self._rt._futures.get(oid)
            err = fut.error if fut is not None else None
        except Exception:  # noqa: BLE001
            err = None
        nbytes = None
        if err is None:
            try:
                nbytes = _nbytes_of(self._rt, ref)
            except Exception:  # noqa: BLE001
                nbytes = None
            self._annotate_ingest(oid)
            self._cache_to_driver(oid)
            self._sched.complete(fl.tenant, nbytes, fl.charged)
            if nbytes:
                _m_bytes.inc(float(nbytes), tags={"tenant": fl.tenant})
        else:
            # failed work earns no fair-share credit and is never cached
            self._sched.cancel(fl.tenant, fl.charged)
        with self._lock:
            fl.worker.outstanding = max(0, fl.worker.outstanding - 1)
            self._keyed.discard(fl.key)
            reg = self._regs.get(fl.key[0])
            if err is None and reg is not None and reg.active:
                reg.cache[fl.key[1]] = ref
                reg.cache_t[fl.key[1]] = time.monotonic()
            # errored refs still deliver: the consumer's get raises the
            # task error instead of the epoch hanging forever
            self._deliver_locked(fl.key, ref)

    def _deliver_locked(self, key, ref) -> None:
        for ep_q in self._waiters.pop(key, []):
            ep_q.put((key[1], ref))

    def _cache_to_driver(self, oid) -> None:
        """Push the completed block into the driver-side pull-through
        cache. Virtual in-process agents short-circuit `_pull_through`
        (their stores read directly, so a cross-node get never seals a
        driver replica) — the service pre-seals one itself, exactly what a
        remote pull-through would have done: repeat-epoch gets then hit
        locally and count as `object_cache_hits`."""
        try:
            rt = self._rt
            agent = rt.driver_agent
            if getattr(agent, "is_remote", False) or agent.store.contains(oid):
                return
            holder = rt.directory.locate(oid, prefer_local=False)
            if holder is None or holder.node_id == agent.node_id:
                return
            raw = holder.store.get_raw(oid, timeout=10.0)
            agent.store.put(oid, raw)
            agent.store.annotate(oid, pin_reason=object_ledger.PIN_INGEST)
            rt.directory.add_location(oid, agent.node_id)
            with rt._cache_lock:
                rt._pulled_through.add(oid)
        except Exception:  # noqa: BLE001 — caching is best-effort
            logger.debug("driver-cache of %s failed", oid, exc_info=True)

    def _annotate_ingest(self, oid) -> None:
        try:
            for nid in self._rt.directory.locations(oid):
                agent = self._rt.agents.get(nid)
                store = getattr(agent, "store", None)
                if store is not None:
                    store.annotate(oid, pin_reason=object_ledger.PIN_INGEST)
        except Exception:  # noqa: BLE001 — annotation is advisory
            pass

    # -- cache janitor ----------------------------------------------------

    def evict(self, force: bool = False) -> int:
        """Free condemned blocks past their grace deadline plus any cached
        block idle past ``ingest_cache_ttl_s``. ``force=True`` frees every
        condemned batch now (the deregistration test path)."""
        now = time.monotonic()
        freed: List[Any] = []
        with self._lock:
            keep: List[Tuple[List[Any], float]] = []
            for refs, deadline in self._condemned:
                if force or now >= deadline:
                    freed.extend(refs)
                else:
                    keep.append((refs, deadline))
            self._condemned = keep
            ttl = float(config.get("ingest_cache_ttl_s"))
            for reg in self._regs.values():
                for idx, touched in list(reg.cache_t.items()):
                    if now - touched > ttl and (reg.reg_id, idx) not in self._waiters:
                        ref = reg.cache.pop(idx, None)
                        reg.cache_t.pop(idx, None)
                        if ref is not None:
                            freed.append(ref)
        if freed:
            try:
                api._free(freed)
            except Exception:  # noqa: BLE001 — frees are best-effort
                logger.exception("ingest cache eviction failed")
            _m_evicted.inc(float(len(freed)))
        return len(freed)

    # -- pool management --------------------------------------------------

    def _spawn_worker_locked(self) -> _Worker:
        handle = IngestWorker.options(
            scheduling_strategy=self._affinity).remote()
        w = _Worker(handle)
        self._workers.append(w)
        return w

    def _reap_retiring(self) -> None:
        dead: List[_Worker] = []
        with self._lock:
            for w in list(self._workers):
                if w.retiring and w.outstanding == 0:
                    self._workers.remove(w)
                    dead.append(w)
        for w in dead:
            try:
                api.kill(w.handle)
            except Exception:  # noqa: BLE001
                pass

    def pool_size(self) -> int:
        with self._lock:
            return len([w for w in self._workers if not w.retiring])

    def shares(self) -> Dict[str, Dict[str, float]]:
        return self._sched.shares()

    # -- autoscale controller ---------------------------------------------

    def _controller_loop(self) -> None:
        period = float(config.get("ingest_eval_period_s"))
        while not self._stop.wait(period):
            try:
                if core_worker._global_runtime is not self._rt:
                    return
                self._evaluate_scaling()
                for name, row in self._sched.shares().items():
                    _m_fair.set(row["ratio"], tags={"tenant": name})
            except Exception:  # noqa: BLE001 — the loop must survive
                if (self._stop.is_set()
                        or core_worker._global_runtime is not self._rt):
                    return
                logger.exception("ingest autoscaler evaluation failed")

    def _evaluate_scaling(self) -> None:
        thr = float(config.get("ingest_stall_scale_threshold"))
        cooldown = float(config.get("autoscale_cooldown_s"))
        step_max = max(1, int(config.get("autoscale_step_max")))
        # per-tenant stall delta over one eval period, read from the shared
        # data_stage_stall_seconds counter (stage=ingest) — the same signal
        # health's tenant-scoped data_stall_rising rule groups by
        cur: Dict[str, float] = {}
        for _name, tag_map, val in _m_stall.samples():
            tags = dict(tag_map)
            if tags.get("stage") != "ingest":
                continue
            t = tags.get("tenant", "")
            cur[t] = cur.get(t, 0.0) + val
        pressured = sorted(t for t, v in cur.items()
                           if v - self._stall_prev.get(t, 0.0) > thr)
        self._stall_prev = cur
        backlog = self._sched.pending_total()
        in_flight = self._sched.in_flight_total()
        now = time.monotonic()
        n = self.pool_size()

        if pressured and backlog > 0 and n < self._pool_max:
            if now - self._last_scale_up >= cooldown:
                add = min(step_max, self._pool_max - n)
                with self._lock:
                    for _ in range(add):
                        self._spawn_worker_locked()
                total = self.pool_size()
                self._last_scale_up = now
                self._idle = 0
                self.scale_events.append(
                    {"t": now, "from": n, "to": total, "dir": "up",
                     "tenants": pressured})
                _m_pool.set(float(total))
                logger.info("ingest scale-up %d -> %d (stalling tenants: %s)",
                            n, total, ", ".join(pressured))
                self._wake.set()
            return

        if not pressured and backlog == 0 and in_flight == 0:
            self._idle += 1
        else:
            self._idle = 0
        if self._idle >= _IDLE_PERIODS and n > self._pool_min:
            drop = min(step_max, n - self._pool_min)
            with self._lock:
                live = [w for w in self._workers if not w.retiring]
                for w in live[len(live) - drop:]:
                    w.retiring = True
            total = self.pool_size()
            self._idle = 0
            self.scale_events.append(
                {"t": now, "from": n, "to": total, "dir": "down",
                 "tenants": []})
            _m_pool.set(float(total))
            logger.info("ingest scale-down %d -> %d (idle)", n, total)

    # -- lifecycle --------------------------------------------------------

    @property
    def is_running(self) -> bool:
        return (not self._stop.is_set()
                and core_worker._global_runtime is self._rt)

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop both service threads, drain + kill the pool, and free every
        cached block (the cache is ephemeral by contract)."""
        if self._stop.is_set():
            return
        self._stop.set()
        self._wake.set()
        for th in (self._admission, self._controller):
            if th is not None:
                th.join(timeout=timeout)
        rt_alive = core_worker._global_runtime is self._rt
        with self._lock:
            regs = list(self._regs.values())
            workers = list(self._workers)
            self._workers = []
            refs: List[Any] = []
            for reg in regs:
                reg.active = False
                refs.extend(reg.cache.values())
                reg.cache.clear()
                reg.cache_t.clear()
            for batch, _deadline in self._condemned:
                refs.extend(batch)
            self._condemned = []
            self._regs.clear()
            self._waiters.clear()
            self._keyed.clear()
            self._flights.clear()
        if rt_alive and workers:
            try:
                # FIFO ping barrier: in-flight blocks finish before kills
                api.get([w.handle.ping.remote() for w in workers], timeout=30)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            for w in workers:
                try:
                    api.kill(w.handle)
                except Exception:  # noqa: BLE001
                    pass
        if rt_alive and refs:
            try:
                api._free(refs)
            except Exception:  # noqa: BLE001
                pass
        _m_pool.set(0.0)


class IngestIterator(DataIterator):
    """DataIterator drop-in whose epochs stream from the shared service."""

    def __init__(self, service: IngestService, reg_id: str, tenant: str):
        super().__init__(lambda: service._epoch_stream(reg_id), tenant=tenant)
        self._service = service
        self.registration_id = reg_id
        self.tenant = tenant

    def deregister(self, *, grace_s: float = 0.0) -> None:
        """Unregister from the service (and close local prefetch)."""
        self.close()
        self._service.deregister(self.registration_id, grace_s=grace_s)


class IngestClient:
    """Thin tenant-facing handle on the (usually singleton) service."""

    def __init__(self, service: Optional[IngestService] = None):
        self._service = service or get_ingest_service()

    @property
    def service(self) -> IngestService:
        return self._service

    def register(self, dataset, *, tenant: str = "default",
                 weight: float = 0.0,
                 max_in_flight_bytes: int = 0) -> IngestIterator:
        return self._service.register(
            dataset, tenant=tenant, weight=weight,
            max_in_flight_bytes=max_in_flight_bytes)

    def deregister(self, iterator: IngestIterator, *,
                   grace_s: float = 0.0) -> None:
        iterator.deregister(grace_s=grace_s)

    def shares(self) -> Dict[str, Dict[str, float]]:
        return self._service.shares()


# -- module singleton ------------------------------------------------------

_singleton_lock = threading.Lock()
_singleton: Optional[IngestService] = None


def get_ingest_service(create: bool = True,
                       **kwargs) -> Optional[IngestService]:
    """The process-wide shared service (created on first use). A stale
    singleton — shut down, or bound to a previous runtime cycle — is
    replaced, so tests cycling api.init()/shutdown() get a fresh fleet."""
    global _singleton
    with _singleton_lock:
        cur = _singleton
        if cur is not None and not cur.is_running:
            cur = _singleton = None
        if cur is None and create:
            cur = _singleton = IngestService(**kwargs)
        return cur


def shutdown_ingest_service() -> None:
    global _singleton
    with _singleton_lock:
        cur, _singleton = _singleton, None
    if cur is not None:
        cur.shutdown()
