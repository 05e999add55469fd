"""JAX-specific training utilities: pytree checkpoints, mesh helpers.

Parity note: plays the role of ``python/ray/train/torch/train_loop_utils.py``
(prepare_model / prepare_data_loader) for the JAX world — but "preparation"
here is sharding annotation, not module wrapping (SURVEY.md §2.3 FSDP row).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax


def ensure_platform() -> None:
    """Fail unless JAX runs this worker on what its resources name: a TPU
    when it holds ``TPU`` chips, anything but a TPU when it holds none.

    The platform itself is bound at worker start-up and at assignment
    (``accelerators.tpu.set_worker_platform``); this is the check that a
    backend which came up before, or under a forced ``JAX_PLATFORMS``, did
    not leave the process computing somewhere else in silence. It starts
    the backend, so it is called only where a worker is about to use JAX
    anyway — and tells the memory plane it may now sweep device stats. The
    driver holds no assignment and is left alone."""
    from ray_tpu._private import memplane
    from ray_tpu._private import worker as worker_mod

    backend = jax.default_backend()
    memplane.note_jax_backend_up()
    rt = worker_mod._worker_runtime
    if rt is None:
        return
    chips = (getattr(rt, "_accel_alloc", None) or {}).get("TPU")
    if chips and backend != "tpu":
        raise RuntimeError(
            f"this worker holds TPU chips {[i for i, _ in chips]} but JAX's "
            f"default backend is {backend!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}): refusing to run off the chip"
        )
    if not chips and backend == "tpu":
        raise RuntimeError(
            "this worker holds no TPU resource but JAX started the TPU "
            "backend in it: ask for the chip (num_tpus / use_tpu) instead "
            "of taking it from the process that did"
        )


def save_pytree(state: Any, path: str) -> None:
    """Save a pytree of arrays to ``path`` (orbax if available, else msgpack
    via flax, else numpy .npz of flattened leaves)."""
    os.makedirs(path, exist_ok=True)
    try:
        import orbax.checkpoint as ocp
    except ImportError:
        ocp = None
    if ocp is not None:
        # real save failures (disk full, permissions, serialization bugs) must
        # propagate — only a missing orbax falls back to npz
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(os.path.join(os.path.abspath(path), "state"), state, force=True)
        ckptr.wait_until_finished()
        return
    import numpy as np

    leaves, treedef = jax.tree.flatten(state)
    np.savez(
        os.path.join(path, "state.npz"),
        *[np.asarray(l) for l in leaves],
        treedef=str(treedef),
    )


def load_pytree(path: str, target: Optional[Any] = None) -> Any:
    """Load a pytree saved by :func:`save_pytree`. ``target`` (a pytree of
    like-shaped arrays or ShapeDtypeStructs) guides orbax restoration and
    sharding."""
    orbax_path = os.path.join(os.path.abspath(path), "state")
    if os.path.exists(orbax_path):
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        if target is not None:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None))
                if hasattr(x, "shape")
                else x,
                target,
            )
            return ckptr.restore(orbax_path, abstract)
        return ckptr.restore(orbax_path)
    import numpy as np

    npz = np.load(os.path.join(path, "state.npz"), allow_pickle=True)
    leaves = [npz[k] for k in npz.files if k != "treedef"]
    if target is None:
        raise ValueError("numpy-fallback checkpoints need a target pytree")
    treedef = jax.tree.structure(target)
    return jax.tree.unflatten(treedef, leaves)
