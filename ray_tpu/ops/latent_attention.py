"""Latent attention (MLA): two paths over one cache.

A position's cache row is ``[ckv | k_r]``: the normed and scaled latent
(``r_kv`` values, from which every head's key and value are projections) and
the rotated key part all heads share (``d_r`` values).

* **prefill** (one prompt from position 0): per-head keys and values are built
  from the prompt's own latents and attended causally, score width ``d_n +
  d_r``, value width ``d_v``, in blocks of query rows so that no
  ``(heads, S, S)`` float32 tensor exists.
* **decode** (one query a sequence): the absorbed form. The query is carried
  into the latent space (``q_l = q_n Wk^T``), scores and the weighted sum run
  over the latent rows themselves, and the result is carried out through ``Wv``
  by the caller. The cache is never up-projected to per-head keys and values.

Both take the rows as gathered arrays (plain ``jnp``; XLA on every platform).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over pairs ``(2j, 2j+1)`` of the last axis. ``x``
    (..., S, d) or (..., S, H, d); ``positions`` (..., S) absolute."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if x.ndim == positions.ndim + 2:  # a heads axis between S and d
        ang = ang[..., None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def latent_prefill_attention(q_n, q_r, k_n, k_r, v, *, scale: float, block_q: int = 256):
    """Causal attention of one sequence from position 0. ``q_n`` (S, H, d_n),
    ``q_r`` (S, H, d_r) rotated, ``k_n`` (S, H, d_n), ``k_r`` (S, d_r) rotated
    and shared by the heads, ``v`` (S, H, d_v) -> (S, H, d_v). Query rows go in
    blocks of ``block_q``; a block reads the keys up to its own end."""
    s = q_n.shape[0]
    bq = min(block_q, s)
    outs = []
    for lo in range(0, s, bq):
        hi = min(lo + bq, s)
        scores = jnp.einsum("qhd,khd->hqk", q_n[lo:hi], k_n[:hi], preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("qhd,kd->hqk", q_r[lo:hi], k_r[:hi], preferred_element_type=jnp.float32)
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(mask[None], scores * scale, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:hi]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def latent_decode_attention(q_l, q_r, rows, lengths, *, scale: float):
    """The absorbed decode step. ``q_l`` (B, H, r_kv) the query in the latent
    space, ``q_r`` (B, H, d_r) rotated, ``rows`` (B, M, r_kv + d_r) a
    sequence's cache rows with row index == position, ``lengths`` (B,) how
    many of them count (0: an empty slot). -> (B, H, r_kv), the weighted sum
    of the latents, still to be carried out through the value projection."""
    r_kv = q_l.shape[-1]
    latent, k_r = rows[..., :r_kv], rows[..., r_kv:]
    scores = jnp.einsum("bhr,bmr->bhm", q_l, latent, preferred_element_type=jnp.float32)
    scores = scores + jnp.einsum("bhd,bmd->bhm", q_r, k_r, preferred_element_type=jnp.float32)
    mask = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, :], scores * scale, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum("bhm,bmr->bhr", probs, latent)
