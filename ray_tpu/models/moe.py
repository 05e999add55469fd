"""The expert layer, told which experts it holds.

A router over ``n_routed + n_zero`` outputs chooses ``top_k`` of them for
every token; the first ``n_routed`` are SwiGLU experts, the rest are
zero-compute (identity) experts that add ``w * u`` with no matmul (a model
may have none: ``n_zero`` is what the router has beyond ``n_routed``). Two
routing rules stand side by side and the layer's caller names one: ``route``
(LongCat-Flash: a softmax over every output, weights not renormalised) and
``route_sigmoid`` (DeepSeek-V3's and Kimi-K2's ``noaux_tc``: sigmoid scores,
weights renormalised over the chosen); in both a bias moves the choice and
never the weights. A shared expert is no part of this layer: it is a dense
SwiGLU that the kind's own layer adds for every token. A chip
holds experts ``[expert_offset, expert_offset + held)`` of a layer that is
shared over several chips: it routes over all the outputs, computes its own
experts' part of the result for the tokens that chose them and every identity
expert's part (those have no weights, so each chip adds them for its own
tokens), and adds nothing for a choice of an expert that lives elsewhere. On
one chip the layer runs without its exchange.

No capacity and no ``(tokens, experts, capacity)`` tensor: the chosen rows are
sorted by expert and go through a grouped matmul over the experts held
(``jax.lax.ragged_dot``), so no token is ever dropped, whatever the routing.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import swiglu

# what ``expert_layer`` counts of its routing, in this order: (token, choice)
# rows sent to held, identity and absent experts; held experts with at least one
# row; the rows of the held expert that got the most (over ``held / touched``
# it says how uneven the choice leaves the load: 1 = even)
COUNTS = ("held", "zero", "absent", "touched", "peak")


# The seeded router: logits of deviation ``ROUTER_SCALE``, so that a token's
# chosen weights span two orders of magnitude with the last choice the
# smallest, as a trained router's do (a flat softmax gives every chosen expert
# ``scale / n_outputs``, and an expert's part then vanishes beside the dense
# paths); the choice bias ``BIAS_SCALE`` x normal, small beside a chosen ``p``.
ROUTER_SCALE = 4.0
BIAS_SCALE = 1e-5


def init_expert_params(key, d_model: int, d_ff: int, held: int, n_outputs: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Seeded weights of one layer: 1/sqrt(fan-in) scales, the router's
    columns times ``ROUTER_SCALE``, the choice bias ``BIAS_SCALE`` x normal.
    ``e_down`` is not scaled down with depth as a dense path's output
    projection is: what an expert writes is weighted by ``scale * p`` first."""
    kr, kb, kg, ku, kd = jax.random.split(key, 5)
    s_in, s_ff = d_model ** -0.5, d_ff ** -0.5
    return {
        "router": (jax.random.normal(kr, (d_model, n_outputs)) * s_in * ROUTER_SCALE).astype(dtype),
        "router_bias": jax.random.normal(kb, (n_outputs,)) * BIAS_SCALE,
        "e_gate": (jax.random.normal(kg, (held, d_model, d_ff)) * s_in).astype(dtype),
        "e_up": (jax.random.normal(ku, (held, d_model, d_ff)) * s_in).astype(dtype),
        "e_down": (jax.random.normal(kd, (held, d_ff, d_model)) * s_ff).astype(dtype),
    }


_copy = jax.jit(lambda x: x + 0)


def routing_counts(pool: Dict):
    """A copy of a pool's ``moe_counts`` (``COUNTS`` summed on the device by
    the decode steps' expert layers) that outlives the pool's donation to the
    next step: enqueued behind whatever writes the pool now, so reading it
    later waits for nothing that step would not have finished anyway."""
    return _copy(pool["moe_counts"])


def expert_param_logical_axes() -> Dict[str, Tuple]:
    return {
        "router": ("embed", None),
        "router_bias": (None,),
        "e_gate": ("expert", "embed", "mlp"),
        "e_up": ("expert", "embed", "mlp"),
        "e_down": ("expert", "mlp", "embed"),
    }


def route(u: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, scale: float):
    """``u`` (T, D) -> (weights (T, K) float32, experts (T, K) int32).
    Softmax in float32 over every output; the ``top_k`` of ``p + bias`` are
    chosen (the bias moves the choice only); a chosen output's weight is
    ``scale * p``, not renormalised over the chosen."""
    z = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(z, axis=-1)
    _, experts = jax.lax.top_k(p + bias.astype(jnp.float32), top_k)
    return scale * jnp.take_along_axis(p, experts, axis=-1), experts


def route_sigmoid(u: jax.Array, router: jax.Array, bias: jax.Array, *, top_k: int, scale: float):
    """The second rule, same signature. Sigmoid scores in float32 over every
    output; the ``top_k`` of ``s + bias`` are chosen (one group of experts:
    the published rule first keeps the ``topk_group`` best of ``n_group``
    groups, which at 1 of 1 keeps all; the kind refuses other values); a
    chosen output's weight is ``scale * s / (sum of the chosen s + 1e-20)``."""
    z = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(z)
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20), experts


def expert_layer(params: Dict[str, jax.Array], u: jax.Array, *, n_routed: int, top_k: int, scale: float,
                 expert_offset: int = 0, live: Optional[jax.Array] = None, layer=None, rule=route):
    """``u`` (T, D) -> (y (T, D), counts uint32 in the order ``COUNTS``).

    ``params``: ``router`` (D, n_routed + n_zero), ``router_bias``, and the
    held experts' ``e_gate``/``e_up`` (held, D, F) and ``e_down`` (held, F,
    D): experts ``expert_offset ..`` of the ``n_routed``. ``live`` (T,) bool
    marks the rows that are tokens (padding and empty decode slots route
    nowhere and are not counted). ``counts``: rows sent to held, identity and
    absent experts, held experts with at least one row, and the most rows any
    held expert got. ``rule``: ``route`` or ``route_sigmoid``.

    ``layer``: the expert tensors are all layers' stacked, (layers, held, ..),
    and this is the layer to use (it may be traced). The grouped matmul then
    runs over ``layers x held`` groups of which only this layer's have rows,
    and reads those experts where they lie: cutting a layer out of the stack
    would copy every held expert, touched or not, once a call."""
    t, d = u.shape
    e_gate, e_up, e_down = params["e_gate"], params["e_up"], params["e_down"]
    held_n = e_gate.shape[0] if layer is None else e_gate.shape[1]
    with jax.named_scope("router"):
        weights, experts = rule(u, params["router"], params["router_bias"], top_k=top_k, scale=scale)
        alive = jnp.ones((t, 1), bool) if live is None else live[:, None]
        is_zero = (experts >= n_routed) & alive
        local = experts - expert_offset
        is_held = (local >= 0) & (local < held_n) & (experts < n_routed) & alive
    with jax.named_scope("zero"):
        # identity experts: the sum of their weights times the token, no matmul
        y = (jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1, keepdims=True) * u.astype(jnp.float32))
    with jax.named_scope("experts"):
        # every (token, choice) pair is a row; the held ones sort to the front,
        # by expert, and the grouped matmul visits those alone
        group = jnp.where(is_held, local, held_n).reshape(t * top_k)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held_n + 1)[:held_n].astype(jnp.int32)
        rows = u[order // top_k]
        groups = sizes
        if layer is not None:
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((e_gate.shape[0] * held_n,), jnp.int32), sizes, (layer * held_n,))
            e_gate, e_up, e_down = (w.reshape(-1, *w.shape[2:]) for w in (e_gate, e_up, e_down))
        hidden = swiglu(jax.lax.ragged_dot(rows, e_gate, groups), jax.lax.ragged_dot(rows, e_up, groups))
        out = jax.lax.ragged_dot(hidden, e_down, groups, preferred_element_type=jnp.float32)
        # back to (token, choice) order; a token's parts add in the order of its
        # choices, whoever else is in the batch
        out = out[jnp.argsort(order)].reshape(t, top_k, d)
        # (rows past the last group are whatever the grouped matmul left there)
        y = y + jnp.sum(jnp.where(is_held[..., None], out * weights[..., None], 0.0), axis=1)
    counts = jnp.stack([
        jnp.sum(is_held), jnp.sum(is_zero), jnp.sum(alive & ~is_held & ~is_zero), jnp.sum(sizes > 0),
        jnp.max(sizes),
    ]).astype(jnp.uint32)
    return y.astype(u.dtype), counts
