"""Multi-host gang bootstrap.

Equivalent of the reference's process-group setup inside Train workers
(upstream ray `python/ray/train/torch/config.py ::
_setup_torch_process_group` and `ray/util/collective`'s group init): every
host of a gang must call ``jax.distributed.initialize`` with the same
coordinator before building a global mesh. The worker-group leader (host 0)
publishes its address through the control-plane KV; followers poll it.
"""

from __future__ import annotations

import os
import socket
import time
from typing import Optional

from ..core import core_worker as _cw
from ..core.logging import get_logger

logger = get_logger("bootstrap")

_COORD_KEY = "comm/coordinator/{gang}"


def _control_plane():
    """The cluster KV, from whichever runtime this process can reach: the
    head driver's, a joined worker host's remote client
    (cross_host.WorkerRuntime), or — in a dedicated actor/pool worker
    process — the head back-channel (api._pool_worker_client). Train
    workers run either in the device-owning runtime process (real TPU) or
    in per-member actor processes (ScalingConfig.workers_in_process=False),
    so the rendezvous must work from all three."""
    if _cw.runtime_initialized():
        return _cw.get_runtime().control_plane
    from .. import api

    if api._worker_runtime is not None:
        return api._worker_runtime.control_plane
    client = (
        api._pool_worker_client()
        if os.environ.get("RAY_TPU_IN_POOL_WORKER")
        else None
    )
    if client is not None:
        return client.control_plane
    raise RuntimeError(
        "no runtime in this process: gang rendezvous needs the cluster KV "
        "(head driver, a joined worker host, or a worker process with the "
        "head back-channel)"
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def publish_coordinator(gang_name: str, address: Optional[str] = None) -> str:
    """Host 0 of a gang: publish the coordinator address into cluster KV."""
    cp = _control_plane()
    if address is None:
        address = f"{socket.gethostbyname(socket.gethostname())}:{free_port()}"
    cp.kv_put(_COORD_KEY.format(gang=gang_name), address.encode())
    return address


def lookup_coordinator(gang_name: str, timeout_s: float = 60.0) -> str:
    cp = _control_plane()
    deadline = time.monotonic() + timeout_s
    key = _COORD_KEY.format(gang=gang_name)
    while time.monotonic() < deadline:
        raw = cp.kv_get(key)
        if raw:
            return raw.decode()
        time.sleep(0.05)
    raise TimeoutError(f"coordinator for gang {gang_name!r} never published")


def init_distributed(
    gang_name: str,
    num_processes: int,
    process_id: int,
    coordinator_address: Optional[str] = None,
) -> None:
    """Bring this process into the gang's jax.distributed world.

    Single-process gangs (and the virtual CPU mesh used in tests) skip the
    coordination service entirely — jax already sees all devices.
    """
    if num_processes <= 1:
        logger.info("gang %s: single process, skipping jax.distributed", gang_name)
        return
    import jax

    # CPU-simulated pods (JAX_PLATFORMS=cpu, one forced host device per
    # process): jax's default cpu collectives impl is "none", which fails
    # any cross-process computation at compile time. Gloo ships in jaxlib;
    # opt in before the backend is created. Real TPU paths are untouched.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if "cpu" in platforms.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    if coordinator_address is None:
        if process_id == 0:
            coordinator_address = publish_coordinator(gang_name)
        else:
            coordinator_address = lookup_coordinator(gang_name)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info(
        "gang %s: process %d/%d joined via %s",
        gang_name, process_id, num_processes, coordinator_address,
    )
