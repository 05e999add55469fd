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
``mla`` is the whole attention over a paged pool of such rows, for every model
kind that has one (``models/longcat.py``, ``models/kimi.py``): the caller
gives the rotary and the score scale, which are what the kinds differ in. On
a TPU its decode step reads the pool where it lies, through the Pallas kernel
``ops.paged_attention.paged_latent_attention`` (a sequence's live blocks and
no others; chosen by platform and static shape alone); prefills, and the CPU,
keep the two paths above.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.paged_attention import can_use_latent_kernel, paged_latent_attention

_NEG_INF = -1e30


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over pairs ``(2j, 2j+1)`` of the last axis, at the
    plain frequencies ``theta ** (-2j / d)``. ``x`` (..., S, d) or
    (..., S, H, d); ``positions`` (..., S) absolute."""
    d = x.shape[-1]
    return rotate_pairs(x, positions, 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)))


def rotate_pairs(x: jax.Array, positions: jax.Array, inv_freq, scale: float = 1.0) -> jax.Array:
    """Pairs ``(2j, 2j+1)`` of the last axis rotated by ``positions *
    inv_freq[j]``, cosine and sine times ``scale`` (YaRN's ratio of
    mscales; 1: left out of the program)."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if x.ndim == positions.ndim + 2:  # a heads axis between S and d
        ang = ang[..., None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        c, s = c * scale, s * scale
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, *, factor: float, original_max_position_embeddings: int,
                  beta_fast: float = 32, beta_slow: float = 1, **_) -> np.ndarray:
    """YaRN's frequencies over ``d // 2`` pairs (the public DeepSeek-V3
    modelling code's): the plain ``theta ** (-2j / d)`` where a pair turns more
    than ``beta_fast`` times over the original context, those over ``factor``
    where it turns fewer than ``beta_slow`` times, a linear ramp between.
    Numbers of the config alone: a constant of the program, built on the host
    once a trace, outside the scan over layers."""
    j = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * j / d)

    def pair_that_turns(n):  # the (fractional) pair that turns n times over the original context
        return d * math.log(original_max_position_embeddings / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    return (plain * (1.0 - ramp) + plain / factor * ramp).astype(np.float32)


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


def mla(cfg, w, att_index, h, rows_pool, step, *, rotate, att_scale: float, scale_q: float = 1.0,
        scale_kv: float = 1.0):
    """One latent attention over ``h`` (T, D), its weights read by ``w(name)``
    (``wqa``, ``qa_norm``, ``wqb``, ``wkva``, ``kva_norm``, ``wkvb``, ``wo``,
    the three middle matrices as the decode step reads them: ``wqb`` (heads x
    (d_n + d_r), r_q) and ``wkva`` (r_kv + d_r, D) with the contraction last,
    ``wkvb`` (heads, r_kv, d_n + d_v)): the cache rows scattered into
    attention ``att_index`` of the pool ``rows_pool`` (attentions, blocks,
    block_size, ``cfg.cache_row_stored``); then a prefill (S > 1: one prompt
    from position 0) attends to its own rows per head, and a decode step (S
    == 1) attends in the absorbed form: over each sequence's live blocks
    where they lie (``can_use_latent_kernel``: a TPU), or over its whole
    table, gathered (``max_blocks x block_size`` latent rows) and masked.

    What the model kinds differ in is the caller's: ``rotate(x, positions)``
    (the rotary over ``q_r`` and the shared ``k_r``), ``att_scale`` (what the
    scores are multiplied by) and the factors on the two latent norms. ``cfg``
    gives the sizes under their published names (``num_attention_heads``,
    ``qk_nope_head_dim``, ``kv_lora_rank``, ``rms_norm_eps``) and the row
    (``cache_row``, ``cache_row_stored``, ``dtype``); ``step`` is
    ``models.paged.Step``."""
    b, s = step.positions.shape
    t, bs = b * s, step.block_size
    if s > 1 and b != 1:
        raise ValueError("a prefill takes one prompt")
    heads, eps, dn, rkv = cfg.num_attention_heads, cfg.rms_norm_eps, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    cq = rms_norm(h @ w("wqa"), w("qa_norm") * scale_q, eps)
    q = jnp.einsum("tr,kr->tk", cq, w("wqb")).reshape(b, s, heads, -1)
    q_n, q_r = q[..., :dn], rotate(q[..., dn:], step.positions)
    kva = jnp.einsum("td,rd->tr", h, w("wkva")).reshape(b, s, -1)
    ckv = rms_norm(kva[..., :rkv], w("kva_norm") * scale_kv, eps)
    k_r = rotate(kva[..., rkv:], step.positions)
    new_rows = jnp.concatenate([ckv, k_r], axis=-1).astype(cfg.dtype)
    with jax.named_scope("latent_scatter"):
        flat = jnp.pad(new_rows.reshape(t, -1), ((0, 0), (0, cfg.cache_row_stored - cfg.cache_row)))
        rows_pool = rows_pool.at[att_index, step.write_slots // bs, step.write_slots % bs].set(flat)
    wkvb = w("wkvb")
    if s == 1:
        with jax.named_scope("latent_attn"):
            q_l = jnp.einsum("bhn,hrn->bhr", q_n[:, 0], wkvb[..., :dn])
            if can_use_latent_kernel(s, rkv, rows_pool):
                o_l = paged_latent_attention(q_l, q_r[:, 0], rows_pool, att_index, step.block_tables, step.lengths,
                                             scale=att_scale)
            else:
                with jax.named_scope("latent_gather"):
                    rows = rows_pool[att_index, step.block_tables].reshape(b, -1, cfg.cache_row_stored)
                o_l = latent_decode_attention(q_l, q_r[:, 0], rows[..., :cfg.cache_row], step.lengths, scale=att_scale)
            att = jnp.einsum("bhr,hrv->bhv", o_l, wkvb[..., dn:])
    else:
        with jax.named_scope("latent_attn"):
            kv = jnp.einsum("sr,hrk->shk", new_rows[0, :, :rkv], wkvb)
            att = latent_prefill_attention(q_n[0], q_r[0], kv[..., :dn], new_rows[0, :, rkv:], kv[..., dn:],
                                           scale=att_scale)
    return att.reshape(t, -1) @ w("wo"), rows_pool
