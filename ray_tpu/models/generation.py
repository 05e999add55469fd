"""Autoregressive decoding with a KV cache: GPT-J/Llama's inference half.

The block is ``transformer._block``; this module gives it the two ``attend``s
that keep keys and values between calls. Static shapes for XLA, masked
attention over the cache instead of data-dependent slicing, bf16 weights with
fp32 logits.

* dense (``init_kv_cache`` + ``make_decode_fns``, ``generate``): a contiguous
  cache (L, B, max_len, kv_heads, head_dim) a batch, all sequences advancing in
  lockstep, jitted with the cache donated. The static-batch path: the tests'
  reference for the engine, and the examples'.
* paged (``paged_layer``, ``init_paged_pool``, ``paged_block_bytes``,
  ``paged_layouts``: what ``models/paged.py`` asks of a model kind): the serve
  plane's pool, (L, num_blocks * block_size, kv_heads, head_dim) in
  ``cfg.dtype``, block ``b`` covering slots ``[b * block_size, (b + 1) *
  block_size)``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import paged
from ray_tpu.models.transformer import TransformerConfig, _block, init_params  # noqa: F401 - a kind's module has them
from ray_tpu.ops.attention import attention
from ray_tpu.ops.layers import rope_frequencies
from ray_tpu.ops.paged_attention import can_use_paged_kernel, paged_decode_attention

_NEG_INF = -1e30


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int) -> Dict:
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _forward_cached(params, tokens, positions, cache, cfg: TransformerConfig):
    """Run the model over ``tokens`` (B,S) at absolute ``positions`` (S,),
    reading+writing the KV cache. Returns (logits (B,S,V), cache)."""
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    start = cache["pos"]
    # causal over absolute positions, which also hides the rows not yet written
    mask = jnp.arange(cache["k"].shape[2])[None, :] <= positions[:, None]  # (S, M)

    def attend(q, k, v, layer_cache):
        # write this step's k/v into the cache at [start, start+S)
        ck, cv = (jax.lax.dynamic_update_slice(c, new, (0, start, 0, 0)) for c, new in zip(layer_cache, (k, v)))
        return attention(q, ck, cv, causal=False, mask=mask), (ck, cv)

    def body(x, layer_inputs):
        layer, *layer_cache = layer_inputs
        return _block(cfg, x, layer, cos, sin, positions, attend, layer_cache)

    stacked = {k: v for k, v in params.items() if k not in paged.UNSTACKED}
    x, (new_k, new_v) = jax.lax.scan(body, params["embed"][tokens], (stacked, cache["k"], cache["v"]))
    return paged.head(cfg, params, x), {"k": new_k, "v": new_v, "pos": start + tokens.shape[1]}


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


# -- the paged pool: what ``models/paged.py`` asks of a kind -------------------


def init_paged_pool(cfg: TransformerConfig, num_blocks: int, block_size: int) -> Dict:
    """Preallocated device pool for the paged KV cache (block 0 reserved):
    one array, a layer's keys in plane 0 and its values in plane 1, so that
    the decode kernel brings a block's keys and values in under one copy."""
    return {"kv": jnp.zeros((cfg.n_layers, 2, num_blocks * block_size, cfg.kv_heads, cfg.head_dim), cfg.dtype)}


def paged_block_bytes(cfg: TransformerConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows over all layers."""
    return 2 * cfg.n_layers * block_size * cfg.kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize


def paged_layouts(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    """``wq``, ``wk``, ``wv`` heads-major on the device. They are (L, D, heads,
    head_dim) and ``_block`` contracts them over D, which the default layout
    leaves outside the tiles (the device tiles the two minor dimensions: the
    heads lie in the sublanes, and the dot wants its operand staged in fast
    memory). As (L, heads, D, head_dim) in memory a head is a D x head_dim
    matrix, read in place like ``wo`` (L, heads, head_dim, D) and the MLP's
    tensors, whose contraction already lies in the tiles: nothing for those.
    The rule is about where a projection's contraction lies, whatever the
    number of heads, kv heads or their size."""
    return {name: (0, 2, 1, 3) for name in ("wq", "wk", "wv")}


def attend_pool(q, k, v, pool, *, li, step: paged.Step, rows):
    """The paged pool's ``attend``: k and v scattered to the step's slots of
    layer ``li``, then attention by one of two paths, chosen from platform and
    static shape alone (``can_use_paged_kernel``; a chosen kernel that fails
    raises). With S == 1 on a TPU (the decode steps) the Pallas kernel reads
    each sequence's live blocks where they lie: positions [0, position] of a
    live row, nothing of an inactive slot (its output is 0). Anything else
    (every prefill, the CPU backend) gathers the table's ``rows`` (B,
    max_blocks x block_size; row index == absolute position) and attends to
    them as a masked dense block."""
    kv = pool["kv"]
    with jax.named_scope("paged_scatter"):
        for plane, t in enumerate((k, v)):
            kv = kv.at[li, plane, step.write_slots].set(t.reshape(-1, *t.shape[2:]).astype(kv.dtype))
    if can_use_paged_kernel(q, kv, step.block_size):
        with jax.named_scope("paged_attn"):
            att = paged_decode_attention(q[:, 0], kv, li, step.block_tables, step.lengths,
                                         block_size=step.block_size)[:, None]
    else:
        with jax.named_scope("paged_gather"):
            gk, gv = kv[li, 0][rows], kv[li, 1][rows]
        with jax.named_scope("paged_attn"):
            mask = jnp.arange(rows.shape[1]) <= step.positions[:, None, :, None]  # (B, 1, S, M)
            att = attention(q, gk, gv, causal=False, mask=mask)
    return att, {"kv": kv}


def paged_layer(cfg: TransformerConfig, params, step: paged.Step):
    """The block over the paged pool, for one call of a paged program."""
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    stacked = {k: v for k, v in params.items() if k not in paged.UNSTACKED}
    bs = step.block_size
    rows = (step.block_tables[:, :, None] * bs + jnp.arange(bs)).reshape(len(step.block_tables), -1)

    def layer(x, pool, li):
        weights = {k: jax.lax.dynamic_index_in_dim(v, li, keepdims=False) for k, v in stacked.items()}
        attend = functools.partial(attend_pool, li=li, step=step, rows=rows)
        return _block(cfg, x, weights, cos, sin, step.positions, attend, pool)

    return layer


def make_paged_fns(cfg: TransformerConfig, *, block_size: int):
    """``paged.make_paged_fns`` over this kind's layer, for a caller that holds
    this module alone (tests; the engine asks ``models/paged.py`` itself)."""
    return paged.make_paged_fns(paged_layer, cfg, block_size=block_size)


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
