"""DataIterator: batch iteration + threaded host prefetch + double-
buffered HBM prefetch.

Reference: `python/ray/data/iterator.py :: DataIterator.iter_batches` /
`iter_torch_batches`. Host-side batch assembly (`api.get`, block concat,
the user transform) runs on a bounded background thread — the prefetch
stage — so it overlaps the consumer's device compute; the TPU-native part
is `iter_device_batches`: host batches are `jax.device_put` one step
ahead of consumption (double buffering) on the consumer side, optionally
sharded straight onto a mesh — the device never waits on the input
pipeline.
"""

from __future__ import annotations

import collections
import queue as _queue
import threading
import weakref
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from .. import api
from ..core.config import config
from ..util import tracing
from .block import BlockAccessor
from .executor import _m_stall


_DONE = object()


def _bounded_put(q: _queue.Queue, stop: threading.Event, item) -> bool:
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return True
        except _queue.Full:
            continue
    return False


def _prefetch_produce(make_iter, q: _queue.Queue,
                      stop: threading.Event) -> None:
    try:
        for item in make_iter():
            if not _bounded_put(q, stop, (None, item)):
                return
        _bounded_put(q, stop, (_DONE, None))
    except BaseException as e:  # noqa: BLE001 — re-raised at consumer
        _bounded_put(q, stop, (e, None))


class PrefetchIterator:
    """Iterator over a bounded background-thread producer with an
    explicit lifecycle.

    Runs `make_iter()` on a daemon thread, handing items through a queue
    bounded at `depth` (the producer runs at most `depth` items ahead).
    Producer exceptions re-raise at the consumer's next pull; consumer-
    side blocking time accumulates into
    data_stage_stall_seconds{stage=,tenant=}.

    Unlike the old generator shape, the producer thread is joinable from
    EVERY abandonment path: `close()` (idempotent), `with` blocks, and
    GC of a never-started or half-consumed iterator all set the stop
    flag, drain the queue so a parked `put()` unblocks, and join the
    thread — an abandoned iterator can no longer leak a thread parked on
    a full queue."""

    def __init__(self, make_iter: Callable[[], Iterator[Any]], depth: int,
                 stage: str = "host_prefetch", tenant: str = ""):
        self._q: _queue.Queue = _queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._closed = False
        self._stage = stage
        self._stall = _m_stall.labels(stage=stage, tenant=tenant)
        self._make_iter = make_iter
        # the thread target closes over the queue + stop event ONLY, never
        # self: a bound-method target would keep the iterator reachable
        # for the thread's whole lifetime and the __del__ safety net could
        # never fire on an abandoned iterator
        self._thread = threading.Thread(
            target=_prefetch_produce, args=(make_iter, self._q, self._stop),
            daemon=True, name="data-host-prefetch")
        self._thread.start()

    # ------------------------------------------------------------ consumer

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self) -> Any:
        if self._closed:
            raise StopIteration
        with tracing.region("data.next", stage=self._stage) as wait:
            kind, item = self._q.get()
        self._stall.inc(wait.elapsed_s)
        if kind is _DONE:
            self.close()
            raise StopIteration
        if kind is not None:
            self.close()
            raise kind
        return item

    # ----------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Stop the producer and join its thread. Idempotent; safe from
        any state (unstarted, mid-stream, exhausted)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        try:  # unblock a producer parked on a full queue
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=1.0)

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # GC safety net for abandoned iterators
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def _iter_in_background(make_iter: Callable[[], Iterator[Any]], depth: int,
                        stage: str = "host_prefetch",
                        tenant: str = "") -> PrefetchIterator:
    """Back-compat shim: see PrefetchIterator."""
    return PrefetchIterator(make_iter, depth, stage=stage, tenant=tenant)


class DataIterator:
    """Iterates blocks from a ref-producing factory (re-iterable).

    `tenant` tags every stall sample this iterator emits (multi-tenant
    ingest demand signals). The iterator is also a context manager:
    `close()` tears down every live prefetch thread it spawned, so a
    consumer that abandons an epoch mid-stream can release the
    `data-host-prefetch` threads deterministically instead of waiting
    for GC."""

    def __init__(self, ref_stream_factory: Callable[[], Iterator[Any]],
                 tenant: str = ""):
        self._factory = ref_stream_factory
        self._tenant = tenant
        self._live: "weakref.WeakSet[PrefetchIterator]" = weakref.WeakSet()

    def _background(self, make_iter: Callable[[], Iterator[Any]],
                    depth: int) -> PrefetchIterator:
        it = PrefetchIterator(make_iter, depth, tenant=self._tenant)
        self._live.add(it)
        return it

    def close(self) -> None:
        """Join every prefetch thread spawned by this iterator's batch
        streams. Idempotent; live streams raise StopIteration after."""
        for it in list(self._live):
            it.close()

    def __enter__(self) -> "DataIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def iter_block_refs(self) -> Iterator[Any]:
        return self._factory()

    def iter_blocks(self) -> Iterator[Any]:
        for ref in self._factory():
            yield api.get(ref)

    def iter_rows(self) -> Iterator[Any]:
        for block in self.iter_blocks():
            yield from BlockAccessor(block).iter_rows()

    def iter_batches(
        self,
        batch_size: int = 256,
        batch_format: str = "numpy",
        drop_last: bool = False,
        local_shuffle_buffer_size: Optional[int] = None,
        local_shuffle_seed: Optional[int] = None,
        prefetch_batches: int = 1,
    ) -> Iterator[Any]:
        """Re-chunk the block stream into exact-size batches.

        prefetch_batches > 0 moves batch assembly (`api.get`, block
        concat, re-chunking) onto a bounded background thread running
        that many batches ahead, so host assembly overlaps the caller's
        step; the batch sequence is identical either way. 0 assembles
        inline on the calling thread."""
        if prefetch_batches and prefetch_batches > 0:
            return self._background(
                lambda: self._iter_batches_inline(
                    batch_size=batch_size,
                    batch_format=batch_format,
                    drop_last=drop_last,
                    local_shuffle_buffer_size=local_shuffle_buffer_size,
                    local_shuffle_seed=local_shuffle_seed,
                ),
                prefetch_batches,
            )
        return self._iter_batches_inline(
            batch_size=batch_size,
            batch_format=batch_format,
            drop_last=drop_last,
            local_shuffle_buffer_size=local_shuffle_buffer_size,
            local_shuffle_seed=local_shuffle_seed,
        )

    def _iter_batches_inline(
        self,
        batch_size: int = 256,
        batch_format: str = "numpy",
        drop_last: bool = False,
        local_shuffle_buffer_size: Optional[int] = None,
        local_shuffle_seed: Optional[int] = None,
    ) -> Iterator[Any]:
        rng = np.random.default_rng(local_shuffle_seed)
        buf: list = []
        buffered_rows = 0

        def emit_from(rows_blocks):
            return BlockAccessor.batch_of(BlockAccessor.concat(rows_blocks), batch_format)

        pending: list = []
        pending_rows = 0
        for block in self.iter_blocks():
            acc = BlockAccessor(block)
            if acc.num_rows() == 0:
                continue
            if local_shuffle_buffer_size:
                buf.append(block)
                buffered_rows += acc.num_rows()
                if buffered_rows >= max(local_shuffle_buffer_size, batch_size):
                    merged = BlockAccessor.concat(buf)
                    macc = BlockAccessor(merged)
                    order = rng.permutation(macc.num_rows())
                    merged = _take_order(merged, order)
                    buf, buffered_rows = [], 0
                    block, acc = merged, BlockAccessor(merged)
                else:
                    continue
            pending.append(block)
            pending_rows += acc.num_rows()
            while pending_rows >= batch_size:
                merged = BlockAccessor.concat(pending)
                macc = BlockAccessor(merged)
                yield BlockAccessor.batch_of(macc.take(batch_size), batch_format)
                rest = macc.slice(batch_size, macc.num_rows())
                pending = [rest]
                pending_rows = BlockAccessor(rest).num_rows()
        if buf:
            # drain the shuffle buffer: the tail still gets permuted
            merged = BlockAccessor.concat(buf)
            order = rng.permutation(BlockAccessor(merged).num_rows())
            pending.append(_take_order(merged, order))
            pending_rows = sum(BlockAccessor(b).num_rows() for b in pending)
            while pending_rows >= batch_size:
                merged = BlockAccessor.concat(pending)
                macc = BlockAccessor(merged)
                yield BlockAccessor.batch_of(macc.take(batch_size), batch_format)
                rest = macc.slice(batch_size, macc.num_rows())
                pending = [rest]
                pending_rows = BlockAccessor(rest).num_rows()
        if pending_rows and not drop_last:
            yield emit_from(pending)

    def iter_torch_batches(
        self,
        batch_size: int = 256,
        dtypes: Optional[Dict[str, Any]] = None,
        device: Optional[str] = None,
        drop_last: bool = False,
        local_shuffle_buffer_size: Optional[int] = None,
        local_shuffle_seed: Optional[int] = None,
    ) -> Iterator[Any]:
        """Batches as torch tensors (reference: `iter_torch_batches`).

        On this stack torch is the HOST-side interop format (CPU feature
        pipelines, torch-native eval code); the accelerator path is
        `iter_device_batches` (jax / HBM prefetch). dtypes maps column ->
        torch dtype; device is a torch device string."""
        import torch

        def to_torch(col, name):
            arr = np.asarray(col)
            if arr.dtype == object:
                raise TypeError(
                    f"column {name!r} is not tensor-convertible (object "
                    "dtype); map it to numeric first"
                )
            t = torch.from_numpy(np.ascontiguousarray(arr))
            if dtypes and name in dtypes:
                t = t.to(dtypes[name])
            if device:
                t = t.to(device)
            return t

        for batch in self.iter_batches(
            batch_size=batch_size,
            batch_format="numpy",
            drop_last=drop_last,
            local_shuffle_buffer_size=local_shuffle_buffer_size,
            local_shuffle_seed=local_shuffle_seed,
        ):
            if isinstance(batch, dict):
                yield {k: to_torch(v, k) for k, v in batch.items()}
            else:
                yield to_torch(batch, "<batch>")

    def iter_device_batches(
        self,
        batch_size: int,
        sharding: Optional[Any] = None,
        prefetch: Optional[int] = None,
        drop_last: bool = True,
        transform: Optional[Callable[[Dict[str, np.ndarray]], Any]] = None,
        host_prefetch_batches: int = 2,
    ) -> Iterator[Any]:
        """Host batches -> HBM, `prefetch` steps ahead of the consumer.

        The host stage (`api.get`, block concat, the user `transform`)
        runs `host_prefetch_batches` deep on a background thread; the
        consumer side only dispatches `device_put` (async) and keeps the
        `prefetch`-deep HBM double buffer — so decode, batch assembly,
        and H2D transfer all overlap device compute. 0 assembles inline.

        sharding: a jax Sharding (or pytree of) for device_put — pass the
        gang mesh batch sharding for SPMD ingestion.
        """
        import jax

        if prefetch is None:
            prefetch = config.device_prefetch_depth

        def host_iter():
            for batch in self._iter_batches_inline(
                    batch_size=batch_size, drop_last=drop_last):
                # user transform belongs to the host stage: it runs on
                # the prefetch thread, not the consumer thread
                yield transform(batch) if transform is not None else batch

        if host_prefetch_batches and host_prefetch_batches > 0:
            host_batches: Iterator[Any] = self._background(
                host_iter, host_prefetch_batches)
        else:
            host_batches = host_iter()

        def put(batch):
            if sharding is None:
                return jax.tree.map(jax.numpy.asarray, batch)
            return jax.device_put(batch, sharding)

        window: collections.deque = collections.deque()
        for batch in host_batches:
            with tracing.region("data.device_put"):
                window.append(put(batch))  # async dispatch; no host block
            if len(window) > prefetch:
                yield window.popleft()
        while window:
            yield window.popleft()


def _take_order(block, order):
    acc = BlockAccessor(block)
    if acc.is_tabular:
        return {k: np.asarray(v)[order] for k, v in block.items()}
    return [block[i] for i in order]
