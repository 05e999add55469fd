"""Device mesh construction with TPU slice topology awareness.

The canonical axes (scaling-book convention):

* ``data``     — batch (pure DP; gradients all-reduced by XLA)
* ``fsdp``     — batch + parameter sharding (ZeRO-3 equivalent via GSPMD)
* ``tensor``   — within-layer model parallelism (Megatron-style, over ICI)
* ``context``  — sequence/context parallelism (ring attention)
* ``expert``   — MoE expert parallelism
* ``pipeline`` — pipeline stages

The reference has no equivalent; its analogue is the NCCL process-group setup
in ``python/ray/train/torch/config.py:65`` plus app-composed TP/PP
(SURVEY.md §2.3). Here a mesh is the single source of truth for every
parallelism dimension, and XLA inserts the collectives.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TENSOR = "tensor"
AXIS_CONTEXT = "context"
AXIS_EXPERT = "expert"
AXIS_PIPELINE = "pipeline"

# ICI-friendly ordering: axes that want the most bandwidth (tensor, context)
# are placed innermost so they map onto the torus's nearest-neighbor links.
CANONICAL_ORDER = (
    AXIS_PIPELINE,
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_EXPERT,
    AXIS_CONTEXT,
    AXIS_TENSOR,
)


@dataclass
class MeshConfig:
    """Axis sizes; -1 on at most one axis means "use remaining devices"."""

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    context: int = 1
    expert: int = 1
    pipeline: int = 1

    def sizes(self) -> Dict[str, int]:
        return {
            AXIS_DATA: self.data,
            AXIS_FSDP: self.fsdp,
            AXIS_TENSOR: self.tensor,
            AXIS_CONTEXT: self.context,
            AXIS_EXPERT: self.expert,
            AXIS_PIPELINE: self.pipeline,
        }

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = self.sizes()
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("at most one axis may be -1")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh axes product {fixed} != device count {n_devices}"
            )
        return sizes


def create_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence] = None,
    drop_trivial_axes: bool = False,
    **axis_sizes: int,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` over the canonical axes.

    ``create_mesh(data=-1, tensor=4)`` → mesh with tensor=4 innermost and all
    remaining devices on data. Uses ``mesh_utils.create_device_mesh`` so the
    assignment follows the physical ICI topology on real TPU slices.
    """
    if config is None:
        config = MeshConfig(**{k: axis_sizes.get(k, 1) for k in MeshConfig().sizes()})
        for k in axis_sizes:
            if k not in config.sizes():
                raise ValueError(f"unknown mesh axis {k}")
    if devices is None:
        devices = jax.devices()
    sizes = config.resolve(len(devices))
    names = [a for a in CANONICAL_ORDER if not (drop_trivial_axes and sizes[a] == 1)]
    shape = [sizes[a] for a in names]
    if math.prod(shape) != len(devices):
        # all axes trivial-dropped but devices remain
        names, shape = [AXIS_DATA], [len(devices)]
    # follows the physical ICI topology on a TPU slice (a plain reshape on
    # other platforms); a shape the slice cannot take is an error, never a
    # silent reshape of the flat device list
    dev_array = mesh_utils.create_device_mesh(
        shape, devices=list(devices), allow_split_physical_axes=True
    )
    return Mesh(dev_array, tuple(names))


def mesh_from_pod_type(pod_type: str, config: Optional[MeshConfig] = None) -> Mesh:
    """Mesh for a full pod slice, e.g. ``v5litepod-64`` → 64-device mesh.
    Validates that the visible devices actually form the named slice."""
    from ray_tpu._private.accelerators import tpu as tpu_accel

    want = tpu_accel.pod_chip_count(pod_type)
    devices = jax.devices()
    if want and len(devices) != want:
        raise ValueError(
            f"pod type {pod_type} has {want} chips but {len(devices)} devices "
            f"are visible. Multi-host slices need jax.distributed initialized "
            f"on every slice host first: use ScalingConfig("
            f"use_jax_distributed=True) in JaxTrainer, or call "
            f"ray_tpu.parallel.distributed.initialize(coord, n_procs, rank) "
            f"directly — afterwards jax.devices() is the global set."
        )
    return create_mesh(config or MeshConfig(data=-1), devices=devices)


def local_device_count() -> int:
    return jax.local_device_count()
