"""Pipeline parallelism over the ``pipeline`` mesh axis.

The reference has no in-tree PP; its building block is compiled DAGs with
NCCL p2p channels between actors (``compiled_dag_node.py:391``,
``torch_tensor_nccl_channel.py`` — SURVEY.md §2.3). TPU-native design: the
whole pipeline is ONE jitted SPMD program; each device on the ``pipeline``
axis holds one stage's parameters, microbatches circulate stage-to-stage with
``ppermute`` (ICI neighbor transfers), GPipe-schedule over M microbatches in
M + P - 1 ticks. XLA overlaps the permute with the next tick's compute.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    axis_name: str = "pipeline",
) -> jax.Array:
    """Run inside shard_map: this device applies its stage to the stream.

    ``stage_params``: this device's stage parameters (leading stage axis
    already split by shard_map). ``microbatches``: (M, mb, ...) — the same
    full input on every stage (stage 0 consumes it; later stages consume
    their ppermute'd inputs). Returns (M, mb, ...) outputs valid on the LAST
    stage (zeros elsewhere).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage_idx = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    total_ticks = M + n_stages - 1

    out_shape = jax.eval_shape(lambda x: stage_fn(stage_params, x), microbatches[0])
    outputs0 = jnp.zeros((M,) + tuple(out_shape.shape), out_shape.dtype)
    state0 = jnp.zeros(out_shape.shape, out_shape.dtype)
    outputs0 = jax.lax.pcast(outputs0, (axis_name,), to="varying")
    state0 = jax.lax.pcast(state0, (axis_name,), to="varying")

    def tick(carry, t):
        outputs, incoming = carry
        # stage 0 injects microbatch t (while t < M); other stages take the
        # activation forwarded from stage-1 last tick
        mb_idx = jnp.clip(t, 0, M - 1)
        inject = jnp.asarray(microbatches[mb_idx], out_shape.dtype)
        x = jnp.where(stage_idx == 0, inject.astype(out_shape.dtype), incoming)
        y = stage_fn(stage_params, x)
        # last stage records microbatch t - (P-1) when in range
        out_idx = t - (n_stages - 1)
        write = (stage_idx == n_stages - 1) & (out_idx >= 0) & (out_idx < M)
        outputs = jax.lax.cond(
            write,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(out_idx, 0, M - 1), 0
            ),
            lambda o: o,
            outputs,
        )
        # forward activations one hop around the ring
        fwd = jax.lax.ppermute(
            y, axis_name, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return (outputs, fwd), None

    (outputs, _), _ = jax.lax.scan(
        tick, (outputs0, state0), jnp.arange(total_ticks)
    )
    return outputs


def make_pipeline_fn(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    axis_name: str = "pipeline",
    params_stage_axis: int = 0,
):
    """Build a global-array pipeline function.

    ``stage_fn(stage_params, x) -> y`` must be shape-preserving (x and y share
    shape/dtype) so activations can circulate the ring. Stacked params have a
    leading stage dimension sharded over the pipeline axis; microbatches are
    replicated in, outputs gathered from the last stage.
    """
    pspec = P(axis_name)
    mspec = P()  # microbatches replicated; stage 0 consumes

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pspec, mspec),
        out_specs=P(axis_name),
        axis_names={axis_name},
    )
    def run(stacked_params, microbatches):
        my_params = jax.tree.map(lambda p: p[0], stacked_params)
        out = pipeline_apply(
            stage_fn, my_params, microbatches, axis_name=axis_name
        )
        return out[None]  # (1, M, ...) per stage; global (P, M, ...)

    def pipeline(stacked_params, microbatches):
        all_stage_outputs = run(stacked_params, microbatches)
        return all_stage_outputs[-1]  # only the last stage's outputs are real

    return pipeline
