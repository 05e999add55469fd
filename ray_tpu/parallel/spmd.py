"""SPMD train-step builder: one jit'd program over the whole mesh.

This is the TPU-native replacement for the reference's
DataParallelTrainer/NCCL stack (``python/ray/train/data_parallel_trainer.py:25``,
``torch/config.py:65``): instead of N processes exchanging NCCL messages, the
train step is a single XLA program whose in_shardings place batch on
``(data, fsdp)``, parameters on ``fsdp``/``tensor``, and sequence on
``context``; XLA inserts the reduce-scatter/all-gather/psum pattern over ICI.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.models import transformer as tfm
from ray_tpu.parallel.mesh import AXIS_CONTEXT
from ray_tpu.parallel.sharding import (
    DEFAULT_LM_RULES,
    Rules,
    batch_sharding,
    infer_param_sharding,
    logical_to_mesh_spec,
    replicated,
)


@dataclass
class TrainStepBundle:
    """Everything a trainer worker needs to run sharded steps."""

    mesh: Mesh
    init_fn: Callable[[jax.Array], Any]  # key -> sharded TrainState
    step_fn: Callable[[Any, jax.Array, jax.Array], Tuple[Any, Dict[str, jax.Array]]]
    param_shardings: Any
    batch_shard: NamedSharding
    config: Any

    init_seed_fn: Optional[Callable[[int], Any]] = None

    def init_state(self, seed: int = 0):
        """Initialize the sharded train state from an integer seed.

        Multi-host safe: the PRNG key is derived *inside* the jitted program
        from the static seed, so there are no host-local array inputs — every
        process traces the identical program and XLA materializes each
        parameter shard on its owner. Prefer this over ``init_fn(PRNGKey)``
        when the mesh spans processes.
        """
        if self.init_seed_fn is not None:
            return self.init_seed_fn(seed)
        return self.init_fn(jax.random.PRNGKey(seed))

    def shard_batch(self, tokens, targets):
        return (
            put_global(tokens, self.batch_shard),
            put_global(targets, self.batch_shard),
        )


def put_global(host_array, sharding: NamedSharding):
    """Place a host array under ``sharding``, including meshes that span
    processes (multi-host SPMD): every process passes the same *global* value
    and only its addressable shards are materialized. Single-host shardings
    take the fast batched ``device_put`` path."""
    if sharding.is_fully_addressable:
        return jax.device_put(host_array, sharding)
    import numpy as np

    host_array = np.asarray(host_array)
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx]
    )


def build_lm_train_step(
    cfg: tfm.TransformerConfig,
    mesh: Mesh,
    *,
    rules: Rules = DEFAULT_LM_RULES,
    optimizer: Optional[optax.GradientTransformation] = None,
    learning_rate: float = 1e-4,
    context_parallel: bool = False,
) -> TrainStepBundle:
    """Build init/step functions jitted over ``mesh`` for the LM in
    ``ray_tpu.models.transformer``."""
    if optimizer is None:
        optimizer = optax.adamw(learning_rate, weight_decay=0.01)

    logical = tfm.param_logical_axes(cfg)
    p_shard = infer_param_sharding(logical, rules, mesh)
    b_shard = batch_sharding(mesh, rules)
    ctx_axis = (
        AXIS_CONTEXT
        if context_parallel and AXIS_CONTEXT in mesh.axis_names and mesh.shape[AXIS_CONTEXT] > 1
        else None
    )

    def constrain(params):
        return jax.tree.map(jax.lax.with_sharding_constraint, params, p_shard)

    def init(key):
        params = constrain(tfm.init_params(key, cfg))
        # the moments are sharded like their parameters; left to XLA's
        # propagation they come out of init replicated (zeros have no
        # producer to inherit from) and the first step compiles twice
        opt_state = optax.tree_utils.tree_map_params(
            optimizer,
            jax.lax.with_sharding_constraint,
            optimizer.init(params),
            p_shard,
        )
        return {"params": params, "opt": opt_state, "step": jnp.zeros((), jnp.int32)}

    if ctx_axis is not None:
        # ring attention over the context axis (partial-manual shard_map inside
        # the jitted program); RoPE sees global positions, attention the ring
        def loss(params, tokens, targets):
            return tfm.loss_fn(
                params, tokens, targets, cfg, context_axis=ctx_axis, mesh=mesh
            )
    else:
        # per-shard attention on a mesh of several devices: the flash kernel
        # cannot be partitioned by GSPMD (batch and heads split, the rest whole)
        attn_spec = (
            logical_to_mesh_spec(["batch", None, "heads", "head_dim"], rules, mesh)
            if mesh.size > 1
            else None
        )

        def loss(params, tokens, targets):
            return tfm.loss_fn(
                params, tokens, targets, cfg, mesh=mesh, attn_spec=attn_spec
            )

    def step(state, tokens, targets):
        lossval, grads = jax.value_and_grad(loss)(state["params"], tokens, targets)
        grads = constrain(grads)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state["opt"], state["params"])
            new_params = constrain(optax.apply_updates(state["params"], updates))
        gnorm = optax.global_norm(grads)
        return (
            {"params": new_params, "opt": new_opt, "step": state["step"] + 1},
            {"loss": lossval, "grad_norm": gnorm},
        )

    # shardings flow: init commits params with p_shard (constraint inside the
    # program), step infers in_shardings from the committed state + batch
    init_jit = jax.jit(init)
    step_jit = jax.jit(step, donate_argnums=(0,))
    # seed-static variant: no array inputs, so it is valid on meshes that
    # span processes (a host-local PRNGKey array would not be)
    init_seed_jit = jax.jit(
        lambda seed: init(jax.random.PRNGKey(seed)), static_argnums=0
    )

    return TrainStepBundle(
        mesh=mesh,
        init_fn=init_jit,
        step_fn=step_jit,
        param_shardings=p_shard,
        batch_shard=b_shard,
        config=cfg,
        init_seed_fn=init_seed_jit,
    )
