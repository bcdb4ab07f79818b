"""Checkpoints: directory-backed handles + sharding-aware pytree IO.

Reference analogue: `python/ray/train/_checkpoint.py :: Checkpoint` and
`train/_internal/storage.py :: StorageContext`. The TPU-native part
(SURVEY.md §5.4): pytree save/restore goes through orbax (TensorStore/
OCDBT), which writes per-host shards of GSPMD arrays and can restore onto
a *different* mesh shape — resharding restore is just passing the new
shardings at load time.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

_METADATA_FILE = ".ray_tpu_checkpoint.json"


class Checkpoint:
    """A directory full of files, with optional metadata."""

    def __init__(self, path: str):
        self.path = os.path.abspath(os.path.expanduser(path))

    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        return cls(path)

    def as_directory(self) -> str:
        return self.path

    def to_directory(self, dest: str) -> str:
        dest = os.path.abspath(os.path.expanduser(dest))
        if dest != self.path:
            shutil.copytree(self.path, dest, dirs_exist_ok=True)
        return dest

    def set_metadata(self, metadata: Dict[str, Any]) -> None:
        with open(os.path.join(self.path, _METADATA_FILE), "w") as f:
            json.dump(metadata, f)

    def get_metadata(self) -> Dict[str, Any]:
        p = os.path.join(self.path, _METADATA_FILE)
        if not os.path.exists(p):
            return {}
        with open(p) as f:
            return json.load(f)

    def __repr__(self):
        return f"Checkpoint({self.path})"


# ---------------------------------------------------------------------------
# Cluster-wide restore (object-plane broadcast)
# ---------------------------------------------------------------------------


def broadcast_checkpoint(checkpoint: Checkpoint, *, timeout: float = 120.0):
    """Stage a checkpoint directory into the object plane and push it to
    every node through the collective relay tree (api.broadcast), so a
    gang restart restores from a same-host replica — zero-copy shm on
    the local node, one pipelined tree instead of N full pulls from the
    head — rather than every worker re-reading shared storage at once.
    Returns the ObjectRef to hand to `restore_checkpoint` on workers."""
    import io
    import tarfile

    from .. import api

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        tar.add(checkpoint.path, arcname=".")
    ref = api.put(buf.getvalue())
    try:
        api.broadcast(ref, timeout=timeout)
    except Exception:  # noqa: BLE001 — pre-seeding is best-effort
        pass  # workers fall back to on-demand pulls of the same ref
    return ref


def restore_checkpoint(ref, dest: str) -> Checkpoint:
    """Materialize a broadcast checkpoint (see `broadcast_checkpoint`)
    into `dest`. The get() resolves against the nearest replica — the
    local store when the broadcast reached this host."""
    import io
    import tarfile

    from .. import api

    blob = api.get(ref)
    dest = os.path.abspath(os.path.expanduser(dest))
    os.makedirs(dest, exist_ok=True)
    with tarfile.open(fileobj=io.BytesIO(blob), mode="r") as tar:
        tar.extractall(dest)  # noqa: S202 — trusted intra-cluster payload
    return Checkpoint(dest)


# ---------------------------------------------------------------------------
# Sharded pytree IO (orbax)
# ---------------------------------------------------------------------------


# orbax names a save by ONE operation id a process (`OperationIdGenerator`:
# class attributes): of two saves that run at once in one process, as the
# stage workers of an in-process gang do under one `step_NNNNNN`, each may
# read the other's id, make the other's temporary directory a second time
# (`FileExistsError`) or remove it under its writer ("Directory not empty").
# A process saves one tree at a time; processes save side by side.
_save_lock = threading.Lock()


def save_pytree(tree: Any, path: str, *, force: bool = True) -> str:
    """Write a (possibly sharded) pytree under `path` (orbax OCDBT)."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(os.path.expanduser(path))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with _save_lock, ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, tree, force=force)
    return path


def load_pytree(
    path: str,
    target: Any = None,
    shardings: Any = None,
) -> Any:
    """Restore a pytree.

    - target: template pytree (for structure/dtypes); optional.
    - shardings: pytree of NamedSharding to place leaves on load — pass a
      layout for a DIFFERENT mesh than the save-time one to reshard on
      restore (elastic resume after slice-count change).
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(os.path.expanduser(path))
    with ocp.StandardCheckpointer() as ckptr:
        if target is None and shardings is None:
            return ckptr.restore(path)
        if shardings is not None:
            template = jax.tree.map(
                lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
                target,
                shardings,
            )
        else:
            template = jax.tree.map(
                lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), target
            )
        return ckptr.restore(path, template)


class AsyncCheckpointWriter:
    """Fire-and-forget checkpoint writes on a background thread.

    The device→host copy happens synchronously (cheap relative to a step);
    serialization/IO overlaps with subsequent training steps. wait() drains.
    """

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def save(self, tree: Any, path: str) -> None:
        self.wait()
        host_tree = jax.tree.map(lambda x: jax.device_get(x), tree)

        def _write():
            try:
                save_pytree(host_tree, path)
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err


# ---------------------------------------------------------------------------
# Top-k retention
# ---------------------------------------------------------------------------


class CheckpointManager:
    """Tracks reported checkpoints, keeps top-k by score (or newest-k)."""

    def __init__(
        self,
        num_to_keep: Optional[int] = None,
        score_attribute: Optional[str] = None,
        score_order: str = "max",
    ):
        self.num_to_keep = num_to_keep
        self.score_attribute = score_attribute
        self.score_order = score_order
        self._entries: List[Tuple[float, float, Checkpoint, Dict[str, Any]]] = []

    def register(self, checkpoint: Checkpoint, metrics: Dict[str, Any]) -> None:
        if self.score_attribute and self.score_attribute in metrics:
            score = float(metrics[self.score_attribute])
            if self.score_order == "min":
                score = -score
        else:
            score = float("-inf")  # fall back to recency ordering
        self._entries.append((score, time.monotonic(), checkpoint, dict(metrics)))
        if self.num_to_keep is not None and len(self._entries) > self.num_to_keep:
            self._entries.sort(key=lambda e: (e[0], e[1]))
            evicted = self._entries.pop(0)
            shutil.rmtree(evicted[2].path, ignore_errors=True)

    @property
    def latest(self) -> Optional[Checkpoint]:
        if not self._entries:
            return None
        return max(self._entries, key=lambda e: e[1])[2]

    @property
    def best(self) -> Optional[Checkpoint]:
        if not self._entries:
            return None
        return max(self._entries, key=lambda e: (e[0], e[1]))[2]

    def all(self) -> List[Checkpoint]:
        return [e[2] for e in self._entries]
