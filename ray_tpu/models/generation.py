"""Autoregressive decoding with a KV cache.

The inference half of the model family: prefill + single-token decode steps
over a static-shape cache, jit-compiled once (cache donated between steps so
decode is in-place on device). The reference serves LLMs by delegating to
external engines on top of Serve; here the decode path is in-tree and
TPU-native: static shapes for XLA, masked attention over the cache instead
of data-dependent slicing, bf16 weights with fp32 logits.

Layout: cache k/v are (L, B, max_len, kv_heads, head_dim).

Two cache layouts share the same attention math:

* dense (``init_kv_cache`` + ``make_decode_fns``): per-batch contiguous
  cache, all sequences advance in lockstep — the static-batch demo path.
* paged (``init_paged_pool`` + ``make_paged_fns``): one device-wide pool of
  fixed-size blocks; each sequence owns a block table mapping absolute
  positions to pool slots. Shapes stay static (block tables are dense
  int32 arrays padded with the reserved null block 0), so the serve
  plane's continuous-batching engine reuses one compiled decode step no
  matter which sequences occupy the batch slots.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig, init_params  # noqa: F401 - a paged model's module has it
from ray_tpu.ops.layers import apply_rope, gelu, rms_norm, rope_frequencies, swiglu
from ray_tpu.ops.paged_attention import can_use_paged_kernel, paged_decode_attention

_NEG_INF = -1e30


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict:
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _stacked(params):
    return {
        k: v
        for k, v in params.items()
        if k not in ("embed", "unembed", "final_norm")
    }


def _mlp(cfg, layer, m):
    if cfg.use_swiglu:
        ff = swiglu(
            jnp.einsum("bsd,df->bsf", m, layer["w_gate"]),
            jnp.einsum("bsd,df->bsf", m, layer["w_up"]),
        )
    else:
        ff = gelu(jnp.einsum("bsd,df->bsf", m, layer["w_up"]))
    return jnp.einsum("bsf,fd->bsd", ff, layer["w_down"])


def _cached_attention(q, ck, cv, cache_positions, q_positions):
    """q (B,S,H,Hd) against the full cache (B,M,KV,Hd), masked to entries at
    cache_positions <= q_positions (causal over absolute positions) and
    cache_positions < written length."""
    n_rep = q.shape[2] // ck.shape[2]
    if n_rep > 1:
        b, m, kv, d = ck.shape
        ck = jnp.broadcast_to(ck[:, :, :, None, :], (b, m, kv, n_rep, d)).reshape(
            b, m, kv * n_rep, d
        )
        cv = jnp.broadcast_to(cv[:, :, :, None, :], (b, m, kv, n_rep, d)).reshape(
            b, m, kv * n_rep, d
        )
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck, preferred_element_type=jnp.float32)
    scores = scores * scale
    mask = cache_positions[None, :] <= q_positions[:, None]  # (S, M)
    scores = jnp.where(mask[None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, cv)


def _forward_cached(params, tokens, positions, cache, cfg: TransformerConfig):
    """Run the model over ``tokens`` (B,S) at absolute ``positions`` (S,),
    reading+writing the KV cache. Returns (logits (B,S,V), cache)."""
    x = params["embed"][tokens]
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    max_len = cache["k"].shape[2]
    cache_positions = jnp.arange(max_len)
    start = cache["pos"]

    def body(carry, layer_inputs):
        x = carry
        layer, ck, cv = layer_inputs
        h = rms_norm(x, layer["attn_norm"])
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"])
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        # write this step's k/v into the cache at [start, start+S)
        ck = jax.lax.dynamic_update_slice(ck, k, (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, start, 0, 0))
        att = _cached_attention(q, ck, cv, cache_positions, positions)
        att_out = jnp.einsum("bshk,hkd->bsd", att, layer["wo"])
        if cfg.parallel_block:
            m = h
            x_out = x + att_out + _mlp(cfg, layer, m)
        else:
            x1 = x + att_out
            m = rms_norm(x1, layer["mlp_norm"])
            x_out = x1 + _mlp(cfg, layer, m)
        return x_out, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (_stacked(params), cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"])
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, unembed).astype(jnp.float32)
    new_cache = {"k": new_k, "v": new_v, "pos": start + tokens.shape[1]}
    return logits, new_cache


def make_decode_fns(cfg: TransformerConfig, max_len: int):
    """Returns (prefill, decode_step), both jitted with donated caches.

    prefill(params, tokens, cache) -> (last_logits (B,V), cache)
    decode_step(params, token (B,1), cache) -> (logits (B,V), cache)
    """

    @functools.partial(jax.jit, donate_argnums=(2,))
    def prefill(params, tokens, cache):
        positions = jnp.arange(tokens.shape[1])
        logits, cache = _forward_cached(params, tokens, positions, cache, cfg)
        return logits[:, -1, :], cache

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode_step(params, token, cache):
        positions = cache["pos"][None]
        logits, cache = _forward_cached(params, token, positions, cache, cfg)
        return logits[:, -1, :], cache

    return prefill, decode_step


# -- paged KV cache ----------------------------------------------------------
#
# The pool is (L, num_blocks * block_size, kv_heads, head_dim): flat slot
# addressing, where block b covers slots [b*block_size, (b+1)*block_size).
# Block 0 is reserved as the null block: padded block-table entries and
# masked-out writes land there, and its (garbage) contents are always
# behind the causal mask, so attention never reads them.


def _kv_storage_dtype(dtype):
    """Storage dtype for the paged pool: 16-bit floats are stored as their
    raw bits (uint16). XLA's CPU backend expands sub-32-bit float scatters
    into a whole-pool f32 convert/convert-back pair — an O(pool-size)
    memcpy per layer per step — while integer scatters stay native and
    in-place. Bitcasting the few written/gathered rows at the edges is
    free and bitwise-identical to storing the float directly."""
    d = jnp.dtype(dtype)
    return jnp.uint16 if d.itemsize == 2 else d


def init_paged_pool(
    cfg: TransformerConfig, num_blocks: int, block_size: int
) -> Dict:
    """Preallocated device pool for the paged KV cache (block 0 reserved).

    Entries are ``cfg.dtype`` values; 16-bit dtypes are held as raw bits
    (see ``_kv_storage_dtype``) and bitcast at the scatter/gather edges."""
    n_slots = num_blocks * block_size
    shape = (cfg.n_layers, n_slots, cfg.kv_heads, cfg.head_dim)
    st = _kv_storage_dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, st), "v": jnp.zeros(shape, st)}


def paged_block_bytes(cfg: TransformerConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows over all layers."""
    row = cfg.kv_heads * cfg.head_dim * jnp.dtype(_kv_storage_dtype(cfg.dtype)).itemsize
    return 2 * cfg.n_layers * block_size * row


def _paged_attention(q, gk, gv, q_positions):
    """q (B,S,H,Hd) against gathered block rows (B,M,KV,Hd) whose row index
    IS the absolute position (block p of a table covers positions
    [p*bs, (p+1)*bs)); causal mask row <= q_position per batch element.
    Same scale/mask/softmax forms as ``_cached_attention`` so dense and
    paged decode agree tokenwise."""
    n_rep = q.shape[2] // gk.shape[2]
    if n_rep > 1:
        b, m, kv, d = gk.shape
        gk = jnp.broadcast_to(gk[:, :, :, None, :], (b, m, kv, n_rep, d)).reshape(
            b, m, kv * n_rep, d
        )
        gv = jnp.broadcast_to(gv[:, :, :, None, :], (b, m, kv, n_rep, d)).reshape(
            b, m, kv * n_rep, d
        )
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, gk, preferred_element_type=jnp.float32)
    scores = scores * scale
    m = gk.shape[1]
    mask = jnp.arange(m)[None, None, :] <= q_positions[:, :, None]  # (B,S,M)
    scores = jnp.where(mask[:, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, gv)


def _forward_paged(
    params,
    tokens,
    positions,
    write_mask,
    block_tables,
    pool,
    cfg: TransformerConfig,
    block_size: int,
):
    """Run the model over ``tokens`` (B,S) at per-sequence absolute
    ``positions`` (B,S), scattering k/v into the block pool and attending
    over each sequence's blocks. ``write_mask`` (B,S) diverts padded rows
    to the null block; ``block_tables`` (B, max_blocks) maps block index ->
    pool block (0-padded). Returns (logits (B,S,V), pool).

    Attention takes one of two paths, by platform and static shape alone
    (``ops.paged_attention.can_use_paged_kernel``): with S == 1 on a TPU
    (the decode steps) the Pallas kernel reads each sequence's live blocks
    in place, positions [0, position] of a row whose ``write_mask`` is set
    and nothing of one whose mask is clear (its output is 0); otherwise
    (every prefill, the CPU backend) the table's ``max_blocks x
    block_size`` rows are gathered out of the pool and attended to as a
    masked dense block (``_paged_attention``). A chosen kernel that fails
    raises."""
    b, s = tokens.shape
    mb = block_tables.shape[1]
    x = params["embed"][tokens]
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    # flat slot destination per (b, s) token; masked rows -> null block 0
    pidx = jnp.clip(positions // block_size, 0, mb - 1)
    slot = (
        jnp.take_along_axis(block_tables, pidx, axis=1) * block_size
        + positions % block_size
    )
    null_slot = jnp.arange(b * s, dtype=slot.dtype) % block_size
    write_slots = jnp.where(write_mask.reshape(-1), slot.reshape(-1), null_slot)

    # gathered pool rows per sequence: row index == absolute position
    gather_idx = (
        block_tables[:, :, None] * block_size
        + jnp.arange(block_size)[None, None, :]
    ).reshape(b, mb * block_size)
    # positions [0, position] of a sequence count; an inactive slot has none
    lengths = jnp.where(write_mask[:, 0], positions[:, 0] + 1, 0)

    # The pool rides in the scan CARRY (updated at a dynamic layer index),
    # not in the per-layer ys: stacked scan outputs allocate a fresh slab
    # and copy every layer's full k/v through it, which defeats buffer
    # donation and turns each decode step into an O(pool-size) memcpy.
    # Carry-threaded updates alias in place under ``donate_argnums``.
    @jax.named_scope("block")
    def body(carry, layer_inputs):
        x, pk, pv = carry
        layer, li = layer_inputs
        h = rms_norm(x, layer["attn_norm"])
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"])
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        # round to cfg.dtype, then scatter/gather in the pool's STORAGE
        # dtype (raw bits for 16-bit floats): float16-family scatters are
        # expanded by the CPU backend into whole-pool convert pairs, so
        # only the written/gathered rows may change representation here
        bits = pk.dtype != jnp.dtype(cfg.dtype)
        kw = k.reshape(b * s, *k.shape[2:]).astype(cfg.dtype)
        vw = v.reshape(b * s, *v.shape[2:]).astype(cfg.dtype)
        with jax.named_scope("paged_scatter"):
            if bits:
                kw = jax.lax.bitcast_convert_type(kw, pk.dtype)
                vw = jax.lax.bitcast_convert_type(vw, pv.dtype)
            pk = pk.at[li, write_slots].set(kw)
            pv = pv.at[li, write_slots].set(vw)
        # One query position a sequence on a TPU (the decode steps): the
        # kernel reads each sequence's live blocks where they lie in the
        # pool. Anything else (every prefill, the CPU backend) gathers the
        # table's rows. Chosen from platform and shape alone; a chosen
        # kernel that fails raises.
        use_kernel = can_use_paged_kernel(q, pk, block_size)
        with jax.named_scope("paged_gather"):
            if not use_kernel:
                gk, gv = pk[li][gather_idx], pv[li][gather_idx]
                if bits:
                    gk = jax.lax.bitcast_convert_type(gk, cfg.dtype)
                    gv = jax.lax.bitcast_convert_type(gv, cfg.dtype)
        with jax.named_scope("paged_attn"):
            if use_kernel:
                att = paged_decode_attention(
                    q[:, 0], pk, pv, li, block_tables, lengths,
                    block_size=block_size,
                )[:, None]
            else:
                att = _paged_attention(q, gk, gv, positions)
        att_out = jnp.einsum("bshk,hkd->bsd", att, layer["wo"])
        with jax.named_scope("mlp"):
            if cfg.parallel_block:
                mlp_out = _mlp(cfg, layer, h)
            else:
                x = x + att_out
                mlp_out = _mlp(cfg, layer, rms_norm(x, layer["mlp_norm"]))
        if cfg.parallel_block:
            return (x + att_out + mlp_out, pk, pv), None
        return (x + mlp_out, pk, pv), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body,
        (x, pool["k"], pool["v"]),
        (_stacked(params), jnp.arange(cfg.n_layers)),
    )
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"])
        unembed = params.get("unembed")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, unembed).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def make_paged_fns(cfg: TransformerConfig, *, block_size: int):
    """Returns (prefill, decode_step, decode_step_greedy) over a paged
    pool, jitted with the pool donated (in-place on device between steps).

    prefill(params, tokens (1,S), block_table (1,MB), pool, length ())
        -> (logits at position length-1 (1,V), pool)
    decode_step(params, tokens (B,), positions (B,), block_tables (B,MB),
        pool, active (B,) bool) -> (logits (B,V), pool)
    decode_step_greedy(same args) -> (next tokens (B,) int32, pool)
        — argmax fused on device so a greedy batch ships B ints to the
        host per step instead of B x vocab logits (the hot serving path;
        identical tokens to argmax over ``decode_step``'s logits).

    Shapes are static per (S, MB, B): the engine buckets prompt lengths
    and runs decode at a fixed max batch, so each compiles exactly once.
    On a TPU the two decode steps attend through the paged-attention kernel
    (``ops/paged_attention.py``: time follows the sequences' live blocks,
    not MB); the prefill gathers (see ``_forward_paged``).
    """

    @functools.partial(jax.jit, donate_argnums=(3,))
    def prefill(params, tokens, block_table, pool, length):
        s = tokens.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], tokens.shape)
        write_mask = positions < length
        logits, pool = _forward_paged(
            params, tokens, positions, write_mask, block_table, pool, cfg, block_size
        )
        last = jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1, keepdims=False)
        return last, pool

    @functools.partial(jax.jit, donate_argnums=(4,))
    def decode_step(params, tokens, positions, block_tables, pool, active):
        logits, pool = _forward_paged(
            params,
            tokens[:, None],
            positions[:, None],
            active[:, None],
            block_tables,
            pool,
            cfg,
            block_size,
        )
        return logits[:, 0, :], pool

    @functools.partial(jax.jit, donate_argnums=(4,))
    def decode_step_greedy(params, tokens, positions, block_tables, pool, active):
        logits, pool = _forward_paged(
            params,
            tokens[:, None],
            positions[:, None],
            active[:, None],
            block_tables,
            pool,
            cfg,
            block_size,
        )
        return jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32), pool

    return prefill, decode_step, decode_step_greedy


# -- sampling ----------------------------------------------------------------


def sample_token(
    logits,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    key: Optional[jax.Array] = None,
):
    """Next-token selection from ``logits`` (..., V): greedy argmax when
    temperature <= 0 (the bitwise-stable default), else temperature
    scaling with optional top-k filtering before categorical sampling."""
    if not temperature or temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    if key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    scaled = logits / temperature
    if top_k and top_k > 0:
        kth = jax.lax.top_k(scaled, int(top_k))[0][..., -1:]
        scaled = jnp.where(scaled < kth, _NEG_INF, scaled)
    return jax.random.categorical(key, scaled, axis=-1)


def sequence_key(seed: int, step: int) -> jax.Array:
    """Per-sequence PRNG stream, deterministic in (seed, step) and
    independent of batch composition — continuous batching samples the
    same tokens for a sequence no matter which neighbours share the
    decode step."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(step))


def generate(
    params,
    prompt_tokens,
    cfg: TransformerConfig,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    key: Optional[jax.Array] = None,
    fns: Optional[Tuple] = None,
) -> jnp.ndarray:
    """Greedy (temperature 0) or sampled decoding; returns (B, new) tokens."""
    import numpy as np

    prompt_tokens = jnp.asarray(prompt_tokens)
    if prompt_tokens.ndim == 1:
        prompt_tokens = prompt_tokens[None, :]
    b, s = prompt_tokens.shape
    max_len = s + max_new_tokens
    if max_len > cfg.max_seq_len:
        # the rope tables are sized to max_seq_len; jit's clamped gathers
        # would silently reuse the last position's rotary embedding
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len})"
        )
    prefill, decode_step = fns or make_decode_fns(cfg, max_len)
    cache = init_kv_cache(cfg, b, max_len)
    logits, cache = prefill(params, prompt_tokens, cache)
    out = []
    if key is None:
        key = jax.random.PRNGKey(0)
    for i in range(max_new_tokens):
        if temperature and temperature > 0:
            key, sub = jax.random.split(key)
            tok = sample_token(
                logits, temperature=temperature, top_k=top_k, key=sub
            )
        else:
            tok = jnp.argmax(logits, axis=-1)
        out.append(tok)
        if i + 1 < max_new_tokens:  # the last token needs no further logits
            logits, cache = decode_step(params, tok[:, None], cache)
    return jnp.stack(out, axis=1)
