"""Multi-host SPMD bootstrap: ``jax.distributed`` over the cluster control
plane.

This is the TPU-native analogue of the reference's NCCL process-group
rendezvous (``python/ray/train/torch/config.py:65`` wired from
``python/ray/train/_internal/backend_executor.py:129``): one JAX process per
slice host joins a coordination service, after which ``jax.devices()`` is the
*global* device set and a single jitted program spans every host — XLA places
the collectives on ICI (SURVEY.md §2.3, §7 step 5).

Two layers:

* :func:`initialize` / :func:`shutdown` — thin, platform-aware wrappers over
  ``jax.distributed`` (on the cpu platform they switch on gloo cross-process
  collectives so virtual multi-host meshes work on one box / in CI);
* :func:`rendezvous_via_kv` — the address-agreement step, riding the cluster
  KV exactly like the TF_CONFIG and torch-gloo rendezvous in
  ``ray_tpu/train/{tensorflow,torch}_trainer.py``.
"""

from __future__ import annotations

import os
import socket
import time
from typing import Optional

_NAMESPACE = "jax_rendezvous"
_initialized = False


def is_initialized() -> bool:
    return _initialized


def free_port() -> int:
    """Reserve an ephemeral port (closed before use; same accepted race as the
    reference's ``setup_address``)."""
    s = socket.socket()
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    *,
    local_device_ids: Optional[list] = None,
) -> None:
    """Join the JAX coordination service.

    After this returns on every process, ``jax.devices()`` is the global
    device list across all processes and jitted programs gang-execute.
    On the cpu platform, gloo cross-process collectives are enabled first
    (the virtual-slice test path; real TPU slices use ICI natively).
    """
    global _initialized
    import jax

    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # a reused pool worker may already have run a jax computation
        # (backend init is process-wide and first-use);
        # jax.distributed.initialize refuses once backends exist, so on
        # the virtual-cpu path drop them — the cpu backend rebuilds
        # cheaply and no device buffers can span the reset (this process
        # has not joined a mesh yet)
        import jax.extend.backend

        jax.extend.backend.clear_backends()

    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
    _initialized = True


def shutdown() -> None:
    global _initialized
    if not _initialized:
        return
    import jax

    try:
        jax.distributed.shutdown()
    finally:
        _initialized = False


def rendezvous_via_kv(
    rt,
    key: str,
    rank: int,
    world: int,
    *,
    node_ip: str = "127.0.0.1",
    timeout_s: float = 120.0,
) -> str:
    """Agree on a coordinator address through the cluster KV.

    Rank 0 reserves a port and publishes ``ip:port`` under ``key``; everyone
    polls until it appears. Returns the coordinator address. ``rt`` is the
    worker runtime (``ray_tpu._private.worker.get_runtime()``).
    """
    if rank == 0:
        addr = f"{node_ip}:{free_port()}"
        rt.rpc("kv_put", _NAMESPACE, key.encode(), addr.encode(), True)
        return addr
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        raw = rt.rpc("kv_get", _NAMESPACE, key.encode())
        if raw:
            return raw.decode()
        time.sleep(0.05)
    raise RuntimeError(f"jax.distributed rendezvous timed out on key {key!r}")


def release_rendezvous(rt, key: str) -> None:
    """Drop the published coordinator address (rank 0, after shutdown)."""
    try:
        rt.rpc("kv_del", _NAMESPACE, key.encode())
    except Exception:
        pass
