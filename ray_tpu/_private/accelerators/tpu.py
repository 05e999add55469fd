"""TPU accelerator manager: chip detection, visibility, pod topology.

Design parity: ``TPUAcceleratorManager`` (``python/ray/_private/accelerators/
tpu.py:71``): chip count via /dev/accel* or vfio, ``TPU_VISIBLE_CHIPS``
visibility control, pod type from GCE metadata (``tpu.py:48``), worker id, and
the ``TPU-{pod}-head`` gang-scheduling resource (``tpu.py:334``). Detection
here never imports jax (the core runtime must not initialize the device).

One process per chip: a worker that holds no ``TPU`` resource is pinned to
JAX's CPU backend at start-up (``set_worker_platform``), and one that holds
chips gets the visibility AND per-process bounds libtpu needs to open just
those chips (``visible_chip_env``).
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Optional, Sequence

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
# libtpu opens a SUBSET of a host's chips only when told the subset's shape
# (parity: the reference's set_current_process_visible_accelerator_ids);
# with the host's own bounds left in place a one-chip process claims every
# chip of the host
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
GCE_TPU_ACCELERATOR_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-64"
GCE_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
GCE_TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"

# chips per host for known generations (v4/v5p: 4 chips/host; v5e/v6e: up to 8)
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8, "v6e": 8}


def _visible_chips() -> Optional[List[str]]:
    raw = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if raw is None or raw == "":
        return None
    return [c for c in raw.split(",") if c != ""]


def host_chip_count() -> int:
    """Chips the host's /dev shows, whatever this process may see of them:
    /dev/accelN (older TPU VMs) or one numbered IOMMU group per chip under
    /dev/vfio/ next to the "vfio" control node (the v5e machines this repo
    runs on show /dev/vfio/3 + /dev/vfio/vfio for one chip)."""
    paths = glob.glob("/dev/accel*") or [
        p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()
    ]
    return len(paths)


def detect_chip_count() -> int:
    """Number of TPU chips this process may use (0 if none)."""
    vis = _visible_chips()
    if vis is not None:
        return len(vis)
    return host_chip_count()


def detect_pod_type() -> Optional[str]:
    """Accelerator type string like ``v5litepod-64`` (None off-TPU-VM).

    The reference queries the GCE metadata server (``tpu.py:48``); we read the
    env vars the TPU VM runtime populates to stay dependency-free, falling
    back to metadata only if explicitly enabled.
    """
    return os.environ.get(GCE_TPU_ACCELERATOR_ENV) or None


def detect_worker_id() -> int:
    return int(os.environ.get(GCE_TPU_WORKER_ID_ENV, "0"))


def detect_topology() -> Optional[str]:
    return os.environ.get(GCE_TPU_TOPOLOGY_ENV) or None


def pod_chip_count(pod_type: str) -> int:
    """Total chips in a pod slice, e.g. v5litepod-64 -> 64."""
    try:
        return int(pod_type.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 0


def pod_host_count(pod_type: str) -> int:
    gen = pod_type.rsplit("-", 1)[0]
    chips = pod_chip_count(pod_type)
    per_host = _CHIPS_PER_HOST.get(gen, 4)
    return max(1, chips // per_host)


def visible_chip_env(chips: Sequence[int], host_chips: int) -> Dict[str, str]:
    """Env that makes libtpu open exactly ``chips`` of a ``host_chips`` host.
    The whole host needs nothing (libtpu's defaults are the host's bounds);
    a subset needs its indices plus the bounds of the sub-mesh it forms."""
    if host_chips and len(chips) >= host_chips:
        return {}
    env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chips)}
    bounds = _SUBSET_BOUNDS.get(len(chips))
    if bounds:
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
    return env


_inherited_platforms: Optional[str] = None


def set_worker_platform(holds_tpu: bool) -> None:
    """Bind this worker process's JAX platform to the resources it holds.

    Without chips the worker is held to the CPU backend, so nothing it
    imports can open (and lock) a chip that a replica or trainer needs.
    With chips it gets back the platform list it was started with — or the
    strict ``tpu,cpu`` when there was none, under which JAX fails at
    backend start-up instead of warning and carrying on on the CPU. A
    backend that is already up keeps its platform;
    ``train.jax_utils.ensure_platform`` refuses that mismatch."""
    global _inherited_platforms
    if _inherited_platforms is None:
        _inherited_platforms = os.environ.get("JAX_PLATFORMS", "")
    plat = (_inherited_platforms or "tpu,cpu") if holds_tpu else "cpu"
    os.environ["JAX_PLATFORMS"] = plat
    # a jax that is still mid-import on another thread has no config yet and
    # reads the env when it gets there
    config = getattr(sys.modules.get("jax"), "config", None)
    if config is not None:
        config.update("jax_platforms", plat)


def get_current_pod_name() -> Optional[str]:
    pod = detect_pod_type()
    return f"TPU-{pod}-head" if pod else None
