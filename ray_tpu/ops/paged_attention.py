"""Decode-time attention over the paged KV pool: a Pallas TPU kernel.

One query position a sequence. The pool (L, slots, kv_heads, head_dim)
stays in HBM; for each sequence the kernel copies its *live* blocks, and
only those, into VMEM where they lie (a block is ``block_size`` consecutive
slots: one contiguous piece of a layer), several blocks a chunk and
double-buffered, and keeps a running maximum, sum and output in float32
(online softmax). It never reads ``pool[layer]``
as a value, so no layer slab and no gathered copy is made.

Heads stay interleaved as the pool stores them: a chunk is read as
(rows * kv_heads, head_dim) and every head is scored against every column
on the MXU; the columns of other kv heads are masked with the rows beyond
the sequence's length. That spends ``kv_heads`` times the arithmetic a
per-head layout would and no relayout; with one query row a sequence the
MXU has it to spare, and the copies set the kernel's time.

A sequence's output depends on its own length, table and rows alone: it
walks its own blocks in table order in chunks of a fixed size, whatever its
neighbours hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_CHUNK_BYTES = 1 << 20  # of K (and of V) in one VMEM buffer; two buffers each


def can_use_paged_kernel(q, pool_k, block_size: int) -> bool:
    """Platform and static shape alone, as ``ops.attention._can_use_flash``:
    a TPU, one query position, a head_dim of whole lanes, and kv heads that
    fill whole sublane tiles of the pool's type (so that a chunk
    flattens to (rows * kv_heads, head_dim) without a relayout)."""
    if jax.default_backend() != "tpu":
        return False
    _, s, heads, head_dim = q.shape
    kv_heads = pool_k.shape[2]
    sublanes = 32 // jnp.dtype(pool_k.dtype).itemsize
    return (
        s == 1
        and head_dim % 128 == 0
        and heads % kv_heads == 0
        and kv_heads % sublanes == 0
        and (block_size * kv_heads) % 128 == 0
    )


def _kernel(
    li_ref, len_ref, tbl_ref,  # scalar prefetch
    q_ref, pk_ref, pv_ref,  # q (1, H, Hd) in VMEM; the pools in HBM
    o_ref,
    kbuf, vbuf, sem,  # (2, rows, KV, Hd) each; DMA semaphores (2, 2)
    *, block_size, chunk_blocks,
):
    b = pl.program_id(0)
    li = li_ref[0]
    length = len_ref[b]
    n_blocks = (length + block_size - 1) // block_size
    n_chunks = (n_blocks + chunk_blocks - 1) // chunk_blocks
    _, heads, head_dim = q_ref.shape
    _, rows, kv_heads, _ = kbuf.shape
    n_rep = heads // kv_heads
    cols = rows * kv_heads

    # A chunk's dead rows keep what the buffer held before, and a weight of
    # exactly 0 times that must be 0: nothing but zeros and pool rows is ever
    # in the V buffer. (K's dead columns are replaced after the product.)
    @pl.when(b == 0)
    def _():
        vbuf[...] = jnp.zeros_like(vbuf)

    def for_live_blocks(chunk, slot, act):
        for j in range(chunk_blocks):
            i = chunk * chunk_blocks + j

            @pl.when(i < n_blocks)
            def _():
                src = pl.ds(tbl_ref[b, i] * block_size, block_size)
                dst = pl.ds(j * block_size, block_size)
                act(pltpu.make_async_copy(pk_ref.at[li, src], kbuf.at[slot, dst], sem.at[0, slot]))
                act(pltpu.make_async_copy(pv_ref.at[li, src], vbuf.at[slot, dst], sem.at[1, slot]))

    @pl.when(n_chunks > 0)
    def _():
        for_live_blocks(0, 0, lambda c: c.start())

    q = q_ref[0]
    scale = 1.0 / (head_dim**0.5)
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 0)
    own_head = jax.lax.rem(col, kv_heads) == jax.lax.div(head, n_rep)
    row = jax.lax.div(col, kv_heads)

    def chunk_step(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            for_live_blocks(c + 1, 1 - slot, lambda d: d.start())

        for_live_blocks(c, slot, lambda d: d.wait())
        s = jax.lax.dot_general(
            q, kbuf[slot].reshape(cols, head_dim), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(own_head & (c * rows + row < length), s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(vbuf.dtype), vbuf[slot].reshape(cols, head_dim), preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_step,
        (
            jnp.full((heads, 1), _NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, head_dim), jnp.float32),
        ),
    )
    # an inactive slot (length 0) read nothing: its output is 0, not 0/0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(
    q, pool_k, pool_v, layer, block_tables, lengths, *, block_size: int, interpret=False
):
    """q (B, H, Hd) against the pools (L, slots, KV, Hd), both in the model's
    dtype, at layer ``layer``.
    ``block_tables`` (B, MB) maps a sequence's block index to a pool block;
    ``lengths`` (B,) is how many positions of each sequence count (0: an
    inactive slot, whose output is 0), at most MB x ``block_size``; both
    are the caller's to keep in range (``BlockTable`` does). Returns
    (B, H, Hd) in q's dtype: softmax(q k^T / sqrt(Hd)) v over positions
    [0, length), scores and softmax in float32, the weights in q's dtype
    into the weighted sum."""
    b, heads, head_dim = q.shape
    _, _, kv_heads, _ = pool_k.shape
    block_bytes = block_size * kv_heads * head_dim * jnp.dtype(pool_k.dtype).itemsize
    chunk_blocks = max(1, min(block_tables.shape[1], _CHUNK_BYTES // block_bytes))
    rows = chunk_blocks * block_size
    kernel = functools.partial(_kernel, block_size=block_size, chunk_blocks=chunk_blocks)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, head_dim), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, head_dim), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, kv_heads, head_dim), pool_k.dtype),
                pltpu.VMEM((2, rows, kv_heads, head_dim), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        # the V buffer is zeroed at the first sequence and the buffers pass
        # from one sequence to the next: the grid runs in order on one core
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        q, pool_k, pool_v,
    )
