"""The flat K/V pool of a paged program: the one module that knows its format,
which the kinds with plain attention over K/V rows call
(``models/exaone_moe.py``'s full layers, ``falcon_h1.py``, ``granite_hybrid.py``
and ``nemotron_h.py`` through ``attend``; ``lfm2_moe.py``, whose kernel call
packs its queries, and ``phi4flash.py``, whose attention is differential,
through ``write_rows`` and ``gather_rows`` around a call of their own). A
change here is judged at all six at once:

    kv (layers, 2, blocks x block_size x kv_heads, head_dim) in the served type

keys in plane 0 and values in plane 1 of one array (``paged_decode_attention``
brings a block's keys and values in under one copy), each plane *flat*: slot
``p`` of ``models/paged.py``'s ``Step`` is rows ``p x kv_heads .. (p + 1) x
kv_heads``, a block ``block_size x kv_heads`` consecutive rows. ``kv_heads`` is
the rows a position holds in the pool and ``head_dim`` the values a row holds,
whatever a kind lays in them: a K/V head a row (eight heads are no whole sublane
tile of bfloat16, ten pairs neither, and ``ops/paged_attention.py`` takes a
flat pool of any row count whose block is whole tiles), or LFM2's two heads of
64 to a row of 128, ``G / P`` rows of ``P x d``. A call's K and V come as (B, S,
heads, d) and are laid into rows as they lie.

On a TPU a decode step's own row is written by ``paged_decode_attention``, into
the blocks it scores; elsewhere, and in every prefill, rows are scattered
(``write_rows``) and a decode step gathers its table's (``gather_rows``). The
choice is made from the platform and static shapes alone
(``can_use_paged_kernel``). ``generation.attend_pool`` (the 5-D pool of GPT-J
and the hybrid, a slot's heads a dimension of their own) is not this format:
moving those two kinds here re-lays their pools and is ``ROADMAP.md`` D23's
second step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention as causal_attention
from ray_tpu.ops.paged_attention import can_use_paged_kernel, paged_decode_attention
from ray_tpu.ops.window_attention import window_attention_rows, write_spans


def init_pool(layers: int, num_blocks: int, block_size: int, kv_heads: int, head_dim: int, dtype):
    """The ``"kv"`` array of ``layers`` layers (module docstring), zeros."""
    return jnp.zeros((layers, 2, num_blocks * block_size * kv_heads, head_dim), dtype)


def block_bytes(layers: int, block_size: int, kv_heads: int, head_dim: int, dtype) -> int:
    """Bytes one block holds over ``layers`` layers: K and V rows."""
    return 2 * layers * block_size * kv_heads * head_dim * jnp.dtype(dtype).itemsize


def write_rows(kv, layer, step, k, v, kv_heads: int):
    """``kv`` with the call's ``k`` and ``v`` (B, S, heads, d) written as layer
    ``layer``'s rows at ``step`` (``paged.Step``). A decode step's rows, and a
    prompt's that is no whole number of blocks, go a position a span to
    ``step.write_slots`` (a masked row's lies in the null block); an aligned
    prompt's go a block a span."""
    b, s = step.positions.shape
    bs, wide = step.block_size, kv.shape[-1]
    with jax.named_scope("paged_scatter"):
        if s == 1 or s % bs:
            starts, spans = step.write_slots * kv_heads, (k.reshape(b * s, kv_heads, wide), v.reshape(b * s, kv_heads, wide))
        else:  # a block a window: a prompt's rows past its length lie behind the mask where they land
            starts = (step.block_tables[:, :s // bs] * (bs * kv_heads)).reshape(-1)
            spans = (k.reshape(-1, bs * kv_heads, wide), v.reshape(-1, bs * kv_heads, wide))
        for plane, t in enumerate(spans):
            kv = write_spans(kv, (layer, plane), starts, t)
    return kv


def gather_rows(kv, layer, step, kv_heads: int):
    """Layer ``layer``'s rows of every block of the step's tables: (keys,
    values (B, M, ``kv_heads``, head_dim), M the tables' positions in order;
    live (B, M) bool: positions [0, length) of each sequence)."""
    b, bs = len(step.block_tables), step.block_size
    with jax.named_scope("paged_gather"):
        slots = (step.block_tables[:, :, None] * bs + jnp.arange(bs)).reshape(b, -1)
        mine = slots[:, :, None] * kv_heads + jnp.arange(kv_heads)  # (B, M, kv_heads): where each position's rows lie
        keys, values = jax.lax.dynamic_index_in_dim(kv, layer, keepdims=False)[:, mine]
    return keys, values, jnp.arange(slots.shape[1])[None, :] < step.lengths[:, None]


def attend(kv, layer, step, q, k, v, *, kv_heads: int, scale=None):
    """Softmax attention of ``q`` (B, S, H, d) over the pool's layer ``layer``
    with the call's own ``k`` and ``v`` (B, S, G, d) in it: (o (B, S, H, d),
    the pool with the rows written). A prefill scores its own rows, causally; a
    decode step positions [0, length) of its table's blocks, by the paged
    kernel where it can run and over the gathered rows, as heads of ``k``'s
    shape, elsewhere. ``scale``: the softmax's, where it is not d^-1/2."""
    b, s = q.shape[:2]
    decode = s == 1
    kernel = decode and can_use_paged_kernel(q, kv, step.block_size, kv_heads)
    if not kernel:
        kv = write_rows(kv, layer, step, k, v, kv_heads)
    with jax.named_scope("paged_attn"):
        if not decode:
            o = causal_attention(q, k, v, causal=True, scale=scale)
        elif kernel:  # the kernel puts the row in its block and scores the blocks with it there
            o, kv = paged_decode_attention(
                q[:, 0], kv, layer, step.block_tables, step.lengths, block_size=step.block_size, kv_heads=kv_heads,
                scale=scale, new_k=k[:, 0], new_v=v[:, 0])
            o = o[:, None]
        else:
            keys, values, live = gather_rows(kv, layer, step, kv_heads)
            heads = (b, -1, *k.shape[2:])
            o = window_attention_rows(q[:, 0], keys.reshape(heads), values.reshape(heads), live,
                                      scale=q.shape[-1] ** -0.5 if scale is None else scale)[:, None]
    return o, kv
