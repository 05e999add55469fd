"""Normalization and positional-embedding primitives (pure jnp; XLA fuses)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 accumulation, cast back to input dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def rope_frequencies(
    head_dim: int, max_len: int, theta: float = 10000.0, dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) tables of shape (max_len, head_dim//2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin), each (positions.size, head_dim / 2) float32, of the angles
    ``position x theta^(-2j/d)``: ``apply_rope``'s tables with a call's own
    positions as their rows."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.reshape(-1, 1).astype(jnp.float32) * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Rotary position embedding. x: (..., seq, heads, head_dim);
    cos/sin: (max_len, head_dim//2); positions: (..., seq) absolute indices
    (needed under context parallelism where each shard holds a sequence slice).
    """
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq][:, None, :]
        s = sin[:seq][:, None, :]
    else:
        c = cos[positions][..., :, None, :]
        s = sin[positions][..., :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = c.astype(x.dtype)
    s = s.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    return jax.nn.silu(x_gate) * x_up


def relu2(x: jax.Array) -> jax.Array:
    """relu(x)^2, the ungated activation of a two-matrix MLP: hand it the
    product in float32 so that the square is taken ahead of the rounding."""
    return jnp.square(jax.nn.relu(x))


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)
