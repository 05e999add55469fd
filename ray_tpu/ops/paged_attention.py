"""Decode-time attention over the paged pools: two Pallas TPU kernels that
share one walk (a sequence's live blocks in table order, in chunks of a fixed
size, under an online softmax): ``paged_decode_attention`` over a pool of keys
and one of values (GPT-J, Llama), described here, and
``paged_latent_attention`` over the latent cache's one pool of rows (LongCat,
Kimi-K2), at the end of the file.

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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_CHUNK_BYTES = 1 << 20  # of K (and of V) in one VMEM buffer; two buffers each


def can_use_paged_kernel(q, pool_k, block_size: int, kv_heads: int = 0) -> bool:
    """Platform and static shape alone, as ``ops.attention._can_use_flash``:
    a TPU, one query position, a head_dim of whole lanes, and kv heads that
    fill whole sublane tiles of the pool's type (so that a chunk
    flattens to (rows * kv_heads, head_dim) without a relayout). A **flat**
    pool (layers, slots x ``kv_heads``, head_dim) lies flattened already, so
    any head count will do whose block is whole sublane tiles (10 heads of a
    block of 16: ``paged_decode_attention``)."""
    if jax.default_backend() != "tpu":
        return False
    _, s, heads, head_dim = q.shape
    sublanes = 32 // jnp.dtype(pool_k.dtype).itemsize
    if pool_k.ndim == 3:
        tiles = (block_size * kv_heads) % sublanes == 0
    else:
        kv_heads = pool_k.shape[2]
        tiles = kv_heads % sublanes == 0 and (block_size * kv_heads) % 128 == 0
    return s == 1 and head_dim % 128 == 0 and heads % kv_heads == 0 and tiles


def chunk_blocks_for(max_blocks: int, block_bytes: int, chunk_bytes: int = _CHUNK_BYTES, whole: int = 1) -> int:
    """How many of a table's blocks one VMEM buffer takes: ``chunk_bytes`` of
    them, a whole table at the most, one at the least (``whole``: and a
    multiple of that many). Fixed by shapes, so a sequence walks its blocks in
    the same chunks whoever shares the step."""
    return max(whole, min(max_blocks, chunk_bytes // block_bytes) // whole * whole)


def for_live_blocks(tbl_ref, seq, n_blocks, chunk, chunk_blocks: int, copies, act):
    """``act`` on every copy of chunk ``chunk`` of sequence ``seq``'s table:
    for the chunk's ``j``-th block, where it is live, ``copies(block, j)``
    names the descriptors that take pool block ``block`` to the buffer's
    ``j``-th place (one a pool). Started and waited for through the same walk."""
    for j in range(chunk_blocks):
        i = chunk * chunk_blocks + j

        @pl.when(i < n_blocks)
        def _():
            for copy in copies(tbl_ref[seq, i], j):
                act(copy)


def online_softmax_weights(m, l, s):
    """One chunk of the online softmax: ``s`` (heads, cols) float32 scores,
    the dead columns already at ``_NEG_INF``, against the running maximum
    ``m`` and sum ``l`` (float32). -> the new maximum, the factor on what was
    summed before, the chunk's weights (float32: the caller casts them to the
    pool's type into its weighted sum) and the new sum."""
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    return m_new, alpha, p, alpha * l + p.sum(axis=-1, keepdims=True)


def softmax_start(heads: int, width: int):
    """The online softmax before its first chunk: maximum, sum, output."""
    return (
        jnp.full((heads, 1), _NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, width), jnp.float32),
    )


def _kernel(
    li_ref, len_ref, tbl_ref,  # scalar prefetch
    q_ref, pk_ref, pv_ref,  # q (1, H, Hd) in VMEM; the pools in HBM
    o_ref,
    kbuf, vbuf, sem,  # (2, rows, KV, Hd) each, (2, rows x KV, Hd) over flat pools; DMA semaphores (2, 2)
    *, block_size, chunk_blocks, kv_heads, scale,
):
    b = pl.program_id(0)
    li = li_ref[0]
    length = len_ref[b]
    n_blocks = (length + block_size - 1) // block_size
    n_chunks = (n_blocks + chunk_blocks - 1) // chunk_blocks
    _, heads, head_dim = q_ref.shape
    flat = len(kbuf.shape) == 3  # the pools hold a slot's heads as rows: a block is copied as block_size x KV of them
    rows = chunk_blocks * block_size
    n_rep = heads // kv_heads
    cols = rows * kv_heads
    each = block_size * kv_heads if flat else block_size  # rows of the pool (and of a buffer) a block is

    # A chunk's dead rows keep what the buffer held before, and a weight of
    # exactly 0 times that must be 0: nothing but zeros and pool rows is ever
    # in the V buffer. (K's dead columns are replaced after the product.)
    @pl.when(b == 0)
    def _():
        vbuf[...] = jnp.zeros_like(vbuf)

    def chunk_copies(chunk, slot, act):
        def copies(block, j):
            src = pl.ds(block * each, each)
            dst = pl.ds(j * each, each)
            return (
                pltpu.make_async_copy(pk_ref.at[li, src], kbuf.at[slot, dst], sem.at[0, slot]),
                pltpu.make_async_copy(pv_ref.at[li, src], vbuf.at[slot, dst], sem.at[1, slot]),
            )

        for_live_blocks(tbl_ref, b, n_blocks, chunk, chunk_blocks, copies, act)

    @pl.when(n_chunks > 0)
    def _():
        chunk_copies(0, 0, lambda c: c.start())

    q = q_ref[0]
    # over flat pools a head count need not be a power of two: the column's head and row are worked out for
    # one row of columns, and broadcast in the comparisons
    col = jax.lax.broadcasted_iota(jnp.int32, (1 if flat else heads, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1 if flat else cols), 0)
    own_head = jax.lax.rem(col, kv_heads) == jax.lax.div(head, n_rep)
    row = jax.lax.div(col, kv_heads)

    def chunk_step(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            chunk_copies(c + 1, 1 - slot, lambda d: d.start())

        chunk_copies(c, slot, lambda d: d.wait())
        s = jax.lax.dot_general(
            q, kbuf[slot].reshape(cols, head_dim), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (a flat buffer is (cols, head_dim) as it lies)
        s = jnp.where(own_head & (c * rows + row < length), s, _NEG_INF)
        m, alpha, p, l = online_softmax_weights(m, l, s)
        acc = alpha * acc + jnp.dot(
            p.astype(vbuf.dtype), vbuf[slot].reshape(cols, head_dim), preferred_element_type=jnp.float32
        )
        return m, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_chunks, chunk_step, softmax_start(heads, head_dim))
    # an inactive slot (length 0) read nothing: its output is 0, not 0/0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(
    q, pool_k, pool_v, layer, block_tables, lengths, *, block_size: int, kv_heads: int = 0, scale=None,
    interpret=False,
):
    """q (B, H, Hd) against the pools (L, slots, KV, Hd), both in the model's
    dtype, at layer ``layer``; or against **flat** pools (L, slots x
    ``kv_heads``, Hd), a slot's heads as consecutive rows, which is how a head
    count off the sublane tile lies without padding (10 heads: the device pads
    a (slots, 10, Hd) array's heads to 16). ``scale`` where it is not
    Hd^-1/2 (packed pairs of half-heads: ``ops/window_attention.py``).
    ``block_tables`` (B, MB) maps a sequence's block index to a pool block;
    ``lengths`` (B,) is how many positions of each sequence count (0: an
    inactive slot, whose output is 0), at most MB x ``block_size``; both
    are the caller's to keep in range (``BlockTable`` does). Returns
    (B, H, Hd) in q's dtype: softmax(q k^T / sqrt(Hd)) v over positions
    [0, length), scores and softmax in float32, the weights in q's dtype
    into the weighted sum."""
    b, heads, head_dim = q.shape
    flat = pool_k.ndim == 3
    if not flat:
        _, _, kv_heads, _ = pool_k.shape
    block_bytes = block_size * kv_heads * head_dim * jnp.dtype(pool_k.dtype).itemsize
    # over flat pools a chunk's columns (rows x heads) are whole lane tiles by the count of blocks
    whole = 128 // math.gcd(block_size * kv_heads, 128) if flat else 1
    chunk_blocks = chunk_blocks_for(block_tables.shape[1], block_bytes, whole=whole)
    rows = chunk_blocks * block_size
    buffer = (2, rows * kv_heads, head_dim) if flat else (2, rows, kv_heads, head_dim)
    kernel = functools.partial(_kernel, block_size=block_size, chunk_blocks=chunk_blocks, kv_heads=kv_heads,
                               scale=scale or 1.0 / (head_dim**0.5))
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
                pltpu.VMEM(buffer, pool_k.dtype),
                pltpu.VMEM(buffer, pool_v.dtype),
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


# -- the latent cache (ops/latent_attention.py:mla) ----------------------------

_LATENT_CHUNK_BYTES = 640 << 10  # of cache rows in one VMEM buffer (512 rows in bfloat16); two buffers


def can_use_latent_kernel(s: int, r_kv: int, rows_pool) -> bool:
    """Platform and static shape alone, as ``can_use_paged_kernel``: a TPU,
    one query position, a latent and a stored row of whole lane tiles (the
    kernel cuts the row between them), and blocks that fill whole sublane
    tiles of the pool's type (a block is copied as it lies)."""
    if jax.default_backend() != "tpu":
        return False
    _, _, block_size, stored = rows_pool.shape
    sublanes = 32 // jnp.dtype(rows_pool.dtype).itemsize
    return s == 1 and r_kv % 128 == 0 and stored % 128 == 0 and stored > r_kv and block_size % sublanes == 0


def _latent_kernel(
    ai_ref, len_ref, tbl_ref,  # scalar prefetch
    ql_ref, qr_ref, pool_ref,  # (1, H, r_kv) and (1, H, stored - r_kv) in VMEM; the pool in HBM
    o_ref,
    buf, sem, ahead,  # (2, rows, stored); DMA semaphores (2,); SMEM (2,): see below
    *, block_size, chunk_blocks, scale,
):
    """One sequence a grid step, as ``_kernel``, with two differences the
    latent cache asks for. A row is key and value at once (scores over all of
    it, the weighted sum over its first ``r_kv`` values) and all heads share
    it: one buffer, no other head's columns. And a whole sequence is a chunk
    or two, so the copies run ahead *across* sequences: before a sequence's
    last chunk is computed, the next sequence's first is started into the
    other buffer. ``ahead`` carries that from one grid step to the next: the
    buffer this sequence's first chunk is in, and whether it is under way."""
    b, last = pl.program_id(0), pl.num_programs(0) - 1
    ai = ai_ref[0]
    _, heads, r_kv = ql_ref.shape
    _, rows, _ = buf.shape

    def blocks_and_chunks(seq):
        n_blocks = (len_ref[seq] + block_size - 1) // block_size
        return n_blocks, (n_blocks + chunk_blocks - 1) // chunk_blocks

    # dead rows of a chunk keep what the buffer held before, and a weight of
    # exactly 0 times that must be 0: nothing but zeros and pool rows is ever
    # in the buffer
    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        ahead[0] = 0
        ahead[1] = 0

    def chunk_copies(seq, seq_blocks, chunk, slot, act):
        def copies(block, j):
            dst = pl.ds(j * block_size, block_size)
            return (pltpu.make_async_copy(pool_ref.at[ai, block], buf.at[slot, dst], sem.at[slot]),)

        for_live_blocks(tbl_ref, seq, seq_blocks, chunk, chunk_blocks, copies, act)

    length = len_ref[b]
    n_blocks, n_chunks = blocks_and_chunks(b)
    first = ahead[0]

    @pl.when((n_chunks > 0) & (ahead[1] == 0))
    def _():
        chunk_copies(b, n_blocks, 0, first, lambda d: d.start())

    # an empty slot starts nothing for its neighbour, which then starts its own
    nxt = jnp.minimum(b + 1, last)
    nxt_blocks, nxt_chunks = blocks_and_chunks(nxt)
    run_ahead = (b < last) & (n_chunks > 0) & (nxt_chunks > 0)

    q = jnp.concatenate([ql_ref[0], qr_ref[0]], axis=-1)
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)

    def chunk_step(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(first + c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            chunk_copies(b, n_blocks, c + 1, 1 - slot, lambda d: d.start())

        @pl.when((c + 1 == n_chunks) & run_ahead)
        def _():
            chunk_copies(nxt, nxt_blocks, 0, 1 - slot, lambda d: d.start())

        chunk_copies(b, n_blocks, c, slot, lambda d: d.wait())
        kv = buf[slot]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        s = jnp.where(c * rows + col < length, s, _NEG_INF)
        m, alpha, p, l = online_softmax_weights(m, l, s)
        acc = alpha * acc + jnp.dot(p.astype(kv.dtype), kv[:, :r_kv], preferred_element_type=jnp.float32)
        return m, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_chunks, chunk_step, softmax_start(heads, r_kv))
    # an empty slot (length 0) read nothing: its output is 0, not 0/0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    ahead[0] = jax.lax.rem(first + n_chunks, 2)
    ahead[1] = run_ahead.astype(jnp.int32)


def paged_latent_attention(
    q_l, q_r, rows_pool, att_index, block_tables, lengths, *, scale: float, interpret=False
):
    """The absorbed decode step over the pool where it lies. ``q_l`` (B, H,
    r_kv) the query in the latent space, ``q_r`` (B, H, d_r) rotated, against
    attention ``att_index`` (traced) of ``rows_pool`` (attentions, blocks,
    block_size, stored): a row is ``[ckv | k_r | zeros]``. ``block_tables``
    (B, MB) and ``lengths`` (B,) as ``paged_decode_attention`` takes them.
    Returns (B, H, r_kv) in ``q_l``'s dtype, what
    ``latent_attention.latent_decode_attention`` gives over the gathered
    rows: softmax(([q_l | q_r] . row) x ``scale``) over positions [0, length)
    in float32, the weights in the pool's dtype into the sum of the rows'
    first ``r_kv`` values; 0 for a length of 0."""
    b, heads, r_kv = q_l.shape
    _, _, block_size, stored = rows_pool.shape
    # the rotated part as wide as the rest of the stored row: the row's pad is zeros, so is the query's
    q_r = jnp.pad(q_r, ((0, 0), (0, 0), (0, stored - r_kv - q_r.shape[-1])))
    block_bytes = block_size * stored * jnp.dtype(rows_pool.dtype).itemsize
    chunk_blocks = chunk_blocks_for(block_tables.shape[1], block_bytes, _LATENT_CHUNK_BYTES)
    kernel = functools.partial(_latent_kernel, block_size=block_size, chunk_blocks=chunk_blocks, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q_l.shape, q_l.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, r_kv), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, heads, stored - r_kv), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, r_kv), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk_blocks * block_size, stored), rows_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        # buffers, copies under way and ``ahead`` pass from one sequence to the next: in order, on one core
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_latent_attention",
        interpret=interpret,
    )(
        jnp.asarray(att_index, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        q_l, q_r, rows_pool,
    )
