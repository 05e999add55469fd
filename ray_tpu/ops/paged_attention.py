"""Decode-time attention over the paged pools: two Pallas TPU kernels that
share one walk (a sequence's live blocks in table order, in chunks of a fixed
size, under an online softmax): ``paged_decode_attention`` over the pool of keys
and values (GPT-J, Llama and the kinds with a flat pool), described here, and
``paged_latent_attention`` over the latent cache's one pool of rows (LongCat,
Kimi-K2), at the end of the file.

One query position a sequence. The pool (L, 2, slots, kv_heads, head_dim), a
layer's keys in plane 0 and its values in plane 1, stays in HBM; for each
sequence the kernel copies its *live* blocks, and only those, into VMEM where
they lie (a block is ``block_size`` consecutive slots: one contiguous piece of
each plane of a layer), several blocks a chunk and double-buffered, and keeps
a running maximum, sum and output in float32 (online softmax). It never reads
``pool[layer]`` as a value, so no layer slab and no gathered copy is made.

**A block's keys and values come in under one copy**, a descriptor of two runs
into the two planes of one buffer: the scalar core reads the table once a block
and issues one descriptor, and what a descriptor costs to issue is paid half as
often a byte. With blocks of 16 KB a plane (four heads of 128, bfloat16), where
two copies a block took twice their bytes' time, the kernel alone went from 51%
to 67% of it on the chip, and from 75% to 80% at 32 KB; from 40 KB a plane up
nothing moved. A block's keys then its values contiguous (one run of 32 KB)
read the same to a point: what is paid for is the descriptor, not its runs.

Heads stay interleaved as the pool stores them: a chunk is read as
(rows * kv_heads, head_dim) and every head is scored against every column
on the MXU; the columns of other kv heads are masked with the rows beyond
the sequence's length. That spends ``kv_heads`` times the arithmetic a
per-head layout would and no relayout; with one query row a sequence the
MXU has it to spare, and the copies set the kernel's time.

A sequence's output depends on its own length, table and rows alone: it
walks its own blocks in table order in chunks of a fixed size, whatever its
neighbours hold.

Both kernels take that walk's carry from one place (``chunk_walk``), and the
copies run ahead *across* sequences. A sequence is only a few chunks (one to
six at the served contexts), so before its last chunk is waited for and
scored, the next sequence's first is started into the other buffer, and two
words in SMEM carry from one grid step to the next which buffer that is and
whether the copy is under way (``ahead``). A sequence behind an empty slot
starts its own first chunk. Which buffer a chunk lands in reaches no output.

How a chunk's live blocks are gone through is a kernel's own, as its scoring
is (``chunk_copies``), and the chip chose for each. ``_kernel`` goes in a
loop: the traced kernel then holds one copy at each of the three sites that
start and the one that waits, whatever the table's width, and what a
replica's start pays to trace and lower a kernel is the count of its binds.
``_latent_kernel`` goes unrolled over the chunk's places: its copies are one
block of 20 KB each, the scalar core's issue of them is on every chunk's path,
and on the chip a call took 18-23% longer with the loop; it waits for a chunk
by its bytes and not copy by copy, and scores a chunk's live rows alone (PR 63:
at ``_LATENT_CHUNK_BYTES``).

Handed the decode step's own K and V row (``new_k``, ``new_v``; a flat pool),
``paged_decode_attention`` writes it too: into the two planes of the last
chunk's buffer before that chunk is scored, and from there back to the pool
under one copy, which is then the call's output in place of its input
(``_kernel``). The models' layers then scatter nothing in a decode step.
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_CHUNK_BYTES = 1 << 20  # of K (and of V) in one VMEM buffer's plane; two buffers
# what ``chunk_walk`` hands a kernel's ``chunk_copies`` to do with a copy: the same two objects at every site, so a
# kernel that waits for a chunk otherwise than copy by copy can tell which it was given (``act is WAIT``)
START, WAIT = operator.methodcaller("start"), operator.methodcaller("wait")


def can_use_paged_kernel(q, pool, block_size: int, kv_heads: int = 0) -> bool:
    """Platform and static shape alone, as ``ops.attention._can_use_flash``:
    a TPU, one query position, a head_dim of whole lanes, and kv heads that
    fill whole sublane tiles of the pool's type (so that a chunk
    flattens to (rows * kv_heads, head_dim) without a relayout). A **flat**
    pool (layers, 2, slots x ``kv_heads``, head_dim) lies flattened already, so
    any head count will do whose block is whole sublane tiles (10 heads of a
    block of 16: ``paged_decode_attention``)."""
    if jax.default_backend() != "tpu":
        return False
    _, s, heads, head_dim = q.shape
    sublanes = tile_rows(pool.dtype)
    if pool.ndim == 4:
        tiles = (block_size * kv_heads) % sublanes == 0
    else:
        kv_heads = pool.shape[3]
        tiles = kv_heads % sublanes == 0 and (block_size * kv_heads) % 128 == 0
    return s == 1 and head_dim % 128 == 0 and heads % kv_heads == 0 and tiles


def tile_rows(dtype) -> int:
    """Rows of a tile of the chip's memory in ``dtype``: 8 of 32 bits, 16 of 16."""
    return 32 // jnp.dtype(dtype).itemsize


def covering_span(n_rows: int, sublanes: int) -> int:
    """Rows of the whole sublane tiles that cover ``n_rows`` consecutive rows
    wherever they start, the start a multiple of ``n_rows``: one tile of 16
    for 8 rows, two for 10 (which can start at row 14 of a tile)."""
    furthest = sublanes - math.gcd(n_rows, sublanes)  # into a tile that a multiple of ``n_rows`` can start
    return -(-(furthest + n_rows) // sublanes) * sublanes


def chunk_blocks_for(max_blocks: int, block_bytes: int, chunk_bytes: int = _CHUNK_BYTES, whole: int = 1) -> int:
    """How many of a table's blocks one VMEM buffer takes: ``chunk_bytes`` of
    them, a whole table at the most, one at the least (``whole``: and a
    multiple of that many). Fixed by shapes, so a sequence walks its blocks in
    the same chunks whoever shares the step."""
    return max(whole, min(max_blocks, chunk_bytes // block_bytes) // whole * whole)


def chunk_walk(b, last, len_ref, ahead, *, block_size: int, chunk_blocks: int, chunk_copies, zero_buffers):
    """One grid step's (one sequence's) walk over its chunks and the carry
    from one grid step to the next, for both kernels. ``b`` is the grid step
    and ``last`` the grid's last one; ``len_ref`` (B,) the scalar-prefetched
    lengths; ``ahead`` the two SMEM words: the buffer this sequence's first
    chunk is in, and whether its copy is under way. A kernel hands in what is
    its own: ``chunk_copies(seq, seq_blocks, chunk, slot, act)``, which calls
    ``act`` (``START`` or ``WAIT``) on the copy of every live block (the first
    ``seq_blocks`` of its table) of chunk ``chunk`` of sequence ``seq`` into
    buffer ``slot``, or waits for as many bytes as those copies bring
    (``_latent_kernel``); and ``zero_buffers()``, called
    at the first grid step before anything is started (a chunk's dead rows keep
    what the buffer held before: what that may be is the kernel's argument).

    Stated here and nowhere else: a sequence's live blocks and chunks from its
    length; the start of this sequence's first chunk unless its predecessor
    started it (an empty slot starts nothing for its neighbour, which then
    starts its own); inside the chunk loop, the start of this sequence's next
    chunk or, at its last, of the next sequence's first into the other buffer,
    then the wait for this chunk; and what the next grid step finds in
    ``ahead``.

    -> ``first_slot`` and ``n_chunks`` (chunk ``c`` lies in buffer
    ``(first_slot + c) % 2``; what is started ahead during the last chunk lands
    in the other one), ``over_chunks(score, carry)``, which runs ``score(c,
    slot, carry) -> carry`` on every chunk once it has come in, and
    ``close()``, the grid step's last words."""
    def blocks_and_chunks(seq):
        n_blocks = (len_ref[seq] + block_size - 1) // block_size
        return n_blocks, (n_blocks + chunk_blocks - 1) // chunk_blocks

    n_blocks, n_chunks = blocks_and_chunks(b)

    @pl.when(b == 0)
    def _():
        zero_buffers()
        ahead[0] = 0
        ahead[1] = 0

    first_slot = ahead[0]

    @pl.when((n_chunks > 0) & (ahead[1] == 0))
    def _():
        chunk_copies(b, n_blocks, 0, first_slot, START)

    nxt = jnp.minimum(b + 1, last)
    nxt_blocks, nxt_chunks = blocks_and_chunks(nxt)
    run_ahead = (b < last) & (n_chunks > 0) & (nxt_chunks > 0)

    def over_chunks(score, carry):
        def chunk_step(c, carry):
            slot = jax.lax.rem(first_slot + c, 2)

            # a site each, so that whose chunk it is is the site's own: at one site under scalar selects the latent
            # kernel's call took 7-9% longer on the chip (``_kernel``'s 0-2% shorter: under 0.05% of any cell's step)
            @pl.when(c + 1 < n_chunks)
            def _():
                chunk_copies(b, n_blocks, c + 1, 1 - slot, START)

            @pl.when((c + 1 == n_chunks) & run_ahead)
            def _():
                chunk_copies(nxt, nxt_blocks, 0, 1 - slot, START)

            chunk_copies(b, n_blocks, c, slot, WAIT)
            return score(c, slot, carry)

        return jax.lax.fori_loop(0, n_chunks, chunk_step, carry)

    def close():
        ahead[0] = jax.lax.rem(first_slot + n_chunks, 2)
        ahead[1] = run_ahead.astype(jnp.int32)

    return first_slot, n_chunks, over_chunks, close


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
    q_ref,  # q (1, H, Hd) in VMEM
    *refs,
    block_size, chunk_blocks, kv_heads, scale,
):
    """``refs``: ``pool_ref`` (the pool in HBM: keys in plane 0, values in
    plane 1), ``o_ref``, ``buf`` ((2, 2, rows, KV, Hd): a buffer's two planes;
    (2, 2, rows x KV, Hd) over a flat pool), ``sem`` (DMA semaphores (2,), one
    a buffer). Where the call **writes the step's own row** (a flat pool):
    ``nk_ref, nv_ref`` ((B, KV, Hd): every sequence's new row) ahead of the
    pool, ``pool_out`` (the pool again, the same buffer as the input) behind
    ``o_ref`` and ``back_sem`` (one DMA semaphore) last. A block's keys and
    values come in under one copy, plane by plane into the buffer's planes, and
    the row goes back under one. The row of position ``length - 1`` lies in the
    sequence's last live block, so in its last chunk's buffer: once that chunk
    has come in, the whole sublane tiles that cover the row (``span`` rows,
    inside the block: a block is whole tiles and came in whole) are read,
    patched row by row and stored in both planes, those tiles start on their
    way back to the pool, the chunk is scored as it now lies, and the copy is
    waited for before the grid step ends: the next sequence's second chunk
    lands in that buffer. An inactive slot patches and writes nothing: the
    null block is no sequence's.

    The walk and the carry are ``chunk_walk``'s, so the copies run ahead
    *across* sequences: before a sequence's last chunk is waited for and scored,
    the next sequence's first is started into the other buffer (never the one
    the row is patched in and the tiles go back from, ``last_slot``; live
    sequences hold distinct last blocks, so it reads no row this step writes).
    ``ahead`` (SMEM (2,), after ``sem``) carries that from one grid step to the
    next. Which of the two buffers a chunk lands in reaches no output: see the
    dead rows below."""
    writes = len(refs) > 5
    if writes:
        nk_ref, nv_ref, pool_ref, o_ref, pool_out, buf, sem, ahead, back_sem = refs
    else:
        pool_ref, o_ref, buf, sem, ahead = refs
    b, last = pl.program_id(0), pl.num_programs(0) - 1
    li = li_ref[0]
    length = len_ref[b]
    _, heads, head_dim = q_ref.shape
    flat = len(buf.shape) == 4  # the pool holds a slot's heads as rows: a block is copied as block_size x KV of them
    rows = chunk_blocks * block_size
    n_rep = heads // kv_heads
    cols = rows * kv_heads
    each = block_size * kv_heads if flat else block_size  # rows of a plane (and of a buffer's) a block is

    def chunk_copies(seq, seq_blocks, chunk, slot, act):
        """In a loop over the chunk's live blocks, one copy a block: the traced kernel then holds one copy at each of
        ``chunk_walk``'s sites whatever the table's width, and what a replica's start pays to trace and lower a kernel
        is the count of its binds."""
        at_block = chunk * chunk_blocks

        def one(j, _):
            src = pl.ds(tbl_ref[seq, at_block + j] * each, each)
            dst = pl.ds(pl.multiple_of(j * each, each), each)
            act(pltpu.make_async_copy(pool_ref.at[li, :, src], buf.at[slot, :, dst], sem.at[slot]))

        jax.lax.fori_loop(0, jnp.clip(seq_blocks - at_block, 0, chunk_blocks), one, None)

    def zero_buffers():
        # A chunk's dead rows keep what the buffer held before, and a weight of
        # exactly 0 times that must be 0: nothing but zeros and pool rows is ever
        # in the values' plane. (The keys' dead columns are replaced after the product.)
        buf[:, 1] = jnp.zeros_like(buf[:, 1])

    first_slot, n_chunks, over_chunks, close = chunk_walk(
        b, last, len_ref, ahead, block_size=block_size, chunk_blocks=chunk_blocks, chunk_copies=chunk_copies,
        zero_buffers=zero_buffers)

    if writes:
        sublanes = tile_rows(buf.dtype)
        span = covering_span(kv_heads, sublanes)
        at = jnp.maximum(length - 1, 0)  # the step's own position (an inactive slot: nothing below is started)
        in_chunk = jax.lax.rem(at, rows)
        first = in_chunk * kv_heads  # the new row's heads are rows first .. first + kv_heads - 1 of the last chunk's buffer
        block_start = in_chunk // block_size * each
        start = pl.multiple_of(jnp.minimum(first // sublanes * sublanes, block_start + each - span), sublanes)
        tiles, last_slot = pl.ds(start, span), jax.lax.rem(first_slot + jnp.maximum(n_chunks - 1, 0), 2)
        home = pl.ds(pl.multiple_of(tbl_ref[b, at // block_size] * each + (start - block_start), sublanes), span)
        back = pltpu.make_async_copy(buf.at[last_slot, :, tiles], pool_out.at[li, :, home], back_sem.at[0])

        def write_row():
            place = jax.lax.broadcasted_iota(jnp.int32, (span, head_dim), 0) - (first - start)
            for plane, new_ref in enumerate((nk_ref, nv_ref)):
                # through float32, which holds every value of the pool's type: a select of packed rows is not every chip's
                new, held = new_ref[b].astype(jnp.float32), buf[last_slot, plane, tiles, :].astype(jnp.float32)
                for j in range(kv_heads):
                    held = jnp.where(place == j, new[j:j + 1], held)
                buf[last_slot, plane, tiles, :] = held.astype(buf.dtype)
            back.start()

    q = q_ref[0]
    # over a flat pool a head count need not be a power of two: the column's head and row are worked out for
    # one row of columns, and broadcast in the comparisons
    col = jax.lax.broadcasted_iota(jnp.int32, (1 if flat else heads, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1 if flat else cols), 0)
    own_head = jax.lax.rem(col, kv_heads) == jax.lax.div(head, n_rep)
    row = jax.lax.div(col, kv_heads)

    def score(c, slot, carry):
        m, l, acc = carry
        if writes:
            pl.when(c == n_chunks - 1)(write_row)
        s = jax.lax.dot_general(
            q, buf[slot, 0].reshape(cols, head_dim), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (a flat buffer's plane is (cols, head_dim) as it lies)
        s = jnp.where(own_head & (c * rows + row < length), s, _NEG_INF)
        m, alpha, p, l = online_softmax_weights(m, l, s)
        acc = alpha * acc + jnp.dot(
            p.astype(buf.dtype), buf[slot, 1].reshape(cols, head_dim), preferred_element_type=jnp.float32
        )
        return m, l, acc

    m, l, acc = over_chunks(score, softmax_start(heads, head_dim))
    # an inactive slot (length 0) read nothing: its output is 0, not 0/0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if writes:
        pl.when(length > 0)(back.wait)
    close()


def paged_decode_attention(
    q, pool, layer, block_tables, lengths, *, block_size: int, kv_heads: int = 0, scale=None,
    new_k=None, new_v=None, interpret=False,
):
    """q (B, H, Hd) against the pool (L, 2, slots, KV, Hd) in the model's
    dtype, keys in plane 0 and values in plane 1, at layer ``layer``; or
    against a **flat** pool (L, 2, slots x ``kv_heads``, Hd), a slot's heads as
    consecutive rows, which is how a head count off the sublane tile lies
    without padding (10 heads: the device pads a (slots, 10, Hd) array's heads
    to 16). ``scale`` where it is not Hd^-1/2 (packed pairs of half-heads:
    ``ops/window_attention.py``). ``block_tables`` (B, MB) maps a sequence's
    block index to a pool block; ``lengths`` (B,) is how many positions of each
    sequence count (0: an inactive slot, whose output is 0), at most MB x
    ``block_size``; both are the caller's to keep in range (``BlockTable``
    does). Returns (B, H, Hd) in q's dtype: softmax(q k^T / sqrt(Hd)) v over
    positions [0, length), scores and softmax in float32, the weights in q's
    dtype into the weighted sum.

    **With ``new_k``, ``new_v``** (B, ``kv_heads``, Hd), a flat pool only: the
    call writes them first, cast to the pool's type, as position ``length -
    1``'s row of each sequence (stale as the pool comes in), and returns
    ``(o, pool)``: the pool in place (``input_output_aliases``), bit for bit
    what a scatter of the rows of the live sequences leaves (only the whole
    sublane tiles that cover a row are written, from the chunk the kernel
    scored; an inactive slot writes nothing), ``o`` bit for bit this call's
    without rows over that pool. Live sequences hold distinct last blocks. A
    call again at the same position writes the same row. A scatter ahead of the
    kernel did the same for ~1.1 us an index on the chip behind a bounds check
    and a select, 48 sequences x K and V a layer: as much as the attention took."""
    b, heads, head_dim = q.shape
    flat = pool.ndim == 4
    if not flat:
        kv_heads = pool.shape[3]
    block_bytes = block_size * kv_heads * head_dim * jnp.dtype(pool.dtype).itemsize  # of a plane
    # over a flat pool a chunk's columns (rows x heads) are whole lane tiles by the count of blocks
    whole = 128 // math.gcd(block_size * kv_heads, 128) if flat else 1
    chunk_blocks = chunk_blocks_for(block_tables.shape[1], block_bytes, whole=whole)
    rows = chunk_blocks * block_size
    buffer = (2, 2, rows * kv_heads, head_dim) if flat else (2, 2, rows, kv_heads, head_dim)
    kernel = functools.partial(_kernel, block_size=block_size, chunk_blocks=chunk_blocks, kv_heads=kv_heads,
                               scale=scale or 1.0 / (head_dim**0.5))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    o_shape, o_spec = jax.ShapeDtypeStruct(q.shape, q.dtype), pl.BlockSpec((1, heads, head_dim), lambda i, *_: (i, 0, 0))
    news, writing = [], {}
    if new_k is not None:
        sublanes = tile_rows(pool.dtype)
        if not flat or (block_size * kv_heads) % sublanes or covering_span(kv_heads, sublanes) > block_size * kv_heads:
            raise ValueError(f"a step's row is written into a flat pool whose blocks are whole tiles: {pool.shape}, "
                             f"blocks of {block_size} x {kv_heads} rows")
        news = [(new.astype(pool.dtype), pl.BlockSpec(new.shape, lambda i, *_: (0, 0, 0)))  # whole, every step
                for new in (new_k, new_v)]
        o_shape, o_spec = (o_shape, jax.ShapeDtypeStruct(pool.shape, pool.dtype)), (o_spec, in_hbm)
        writing = {"input_output_aliases": {6: 1}}  # the pool, the last operand, counted with the three scalars
    return pl.pallas_call(
        kernel,
        out_shape=o_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, head_dim), lambda i, *_: (i, 0, 0)),
                *(spec for _, spec in news),
                in_hbm,
            ],
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM(buffer, pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                *([pltpu.SemaphoreType.DMA((1,))] if news else []),
            ],
        ),
        # the values' plane is zeroed at the first sequence, and the buffers, copies under way and
        # ``ahead`` pass from one sequence to the next: the grid runs in order on one core
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret,
        **writing,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        q, *(x for x, _ in news), pool,
    )


# -- the latent cache (ops/latent_attention.py:mla) ----------------------------

# Timed alone on the chip (``tools/latent_time.py``, PR 63) a call's time is the scalar core's and the softmax's, not the
# bytes': ~17 ns a copy issued, ~15 ns a guard that skips one, ~0.4 us a chunk's chain of scores, softmax and sum
# whatever its rows, ~0.1 us a further 128 rows scored; copies and scores do not overlap (one instruction stream).
_LATENT_CHUNK_BYTES = 1280 << 10  # of cache rows in one VMEM buffer (1,024 rows in bfloat16: a chain a sequence at most served lengths); two buffers
_LATENT_GROUP = 8  # blocks one guard skips together: a short sequence pays a guard a dead group, not one a dead block
_LATENT_PREFIX = 256  # rows: a chunk is scored over its live rows rounded up to this, never over the dead rows past them


def can_use_latent_kernel(s: int, r_kv: int, rows_pool) -> bool:
    """Platform and static shape alone, as ``can_use_paged_kernel``: a TPU,
    one query position, a latent and a stored row of whole lane tiles (the
    kernel cuts the row between them), and blocks that fill whole sublane
    tiles of the pool's type (a block is copied as it lies)."""
    if jax.default_backend() != "tpu":
        return False
    _, _, block_size, stored = rows_pool.shape
    sublanes = tile_rows(rows_pool.dtype)
    return s == 1 and r_kv % 128 == 0 and stored % 128 == 0 and stored > r_kv and block_size % sublanes == 0


def _latent_kernel(
    ai_ref, len_ref, tbl_ref,  # scalar prefetch
    ql_ref, qr_ref, pool_ref,  # (1, H, r_kv) and (1, H, stored - r_kv) in VMEM; the pool in HBM
    o_ref,
    buf, sem, ahead,  # (2, rows, stored); DMA semaphores (2,); SMEM (2,): ``chunk_walk``
    *, block_size, chunk_blocks, scale,
):
    """One sequence a grid step, as ``_kernel`` and over the same
    ``chunk_walk``, with two differences the latent cache asks for: a row is
    key and value at once (scores over all of it, the weighted sum over its
    first ``r_kv`` values), and all heads share it: one buffer, no other
    head's columns. And three the chip asked for (PR 63: the numbers above):
    a chunk's copies are waited for by their bytes, in as many waits as the
    live count has binary digits; a guard skips ``_LATENT_GROUP`` dead blocks
    at once; and a chunk is scored over a static prefix of its buffer, the
    live rows rounded up to ``_LATENT_PREFIX``, under one softmax."""
    b, last = pl.program_id(0), pl.num_programs(0) - 1
    ai = ai_ref[0]
    length = len_ref[b]
    _, heads, r_kv = ql_ref.shape
    _, rows, _ = buf.shape

    def chunk_copies(seq, seq_blocks, chunk, slot, act):
        """Laid out over the chunk's places, every index but the table's static: a copy is one block of 20 KB, the scalar
        core's issue of them is on every chunk's path, and on the chip a call took 18-23% longer with ``_kernel``'s loop.
        The wait needs no descriptor a block: every copy signals the buffer's semaphore by its bytes, so the chunk is
        waited for in the binary digits of its live count, each a wait of that many blocks' bytes (7 guards for 64)."""
        at_block = chunk * chunk_blocks
        if act is WAIT:
            live = jnp.minimum(seq_blocks - at_block, chunk_blocks)
            n = 1 << (chunk_blocks.bit_length() - 1)
            while n:
                @pl.when((live & n) != 0)
                def _():
                    landed = buf.at[slot, pl.ds(0, n * block_size)]  # a descriptor for its size alone
                    pltpu.make_async_copy(landed, landed, sem.at[slot]).wait()

                n >>= 1
            return
        group = math.gcd(chunk_blocks, _LATENT_GROUP)

        def copy(j):
            dst = pl.ds(pl.multiple_of(j * block_size, block_size), block_size)
            pltpu.make_async_copy(pool_ref.at[ai, tbl_ref[seq, at_block + j]], buf.at[slot, dst], sem.at[slot]).start()

        def a_group(g, _):
            @pl.when(at_block + g * group < seq_blocks)  # the group's guard is its first block's
            def _():
                copy(g * group)

                def another(k, _):
                    i = at_block + g * group + k  # ahead of the condition, as the compare is: inside it, it is a cycle a block on the copies' path
                    pl.when(i < seq_blocks)(lambda: copy(g * group + k))

                jax.lax.fori_loop(1, group, another, None, unroll=True)

        # laid out whole where the kernel is lowered (every place's index a constant there, as if written out here), and
        # traced once a site: a replica's start pays a kernel's binds, and a guarded copy a place was 18 ms of it each
        jax.lax.fori_loop(0, chunk_blocks // group, a_group, None, unroll=True)

    def zero_buffers():
        # dead rows of a chunk's scored prefix keep what the buffer held before, and a
        # weight of exactly 0 times that must be 0: nothing but zeros and pool rows is
        # ever in the buffer (the rows past the prefix are not read at all)
        buf[...] = jnp.zeros_like(buf)

    _, _, over_chunks, close = chunk_walk(
        b, last, len_ref, ahead, block_size=block_size, chunk_blocks=chunk_blocks, chunk_copies=chunk_copies,
        zero_buffers=zero_buffers)

    q = jnp.concatenate([ql_ref[0], qr_ref[0]], axis=-1)
    prefixes = [*range(_LATENT_PREFIX, rows, _LATENT_PREFIX), rows]

    def score(c, slot, carry):
        live = length - c * rows  # of this chunk's rows, at least one; more than ``rows``: all

        def over(end, carry):
            m, l, acc = carry
            kv = buf[slot, :end]
            s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
            s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < live, s, _NEG_INF)
            m, alpha, p, l = online_softmax_weights(m, l, s)
            acc = alpha * acc + jnp.dot(p.astype(kv.dtype), kv[:, :r_kv], preferred_element_type=jnp.float32)
            return m, l, acc

        def among(ends, carry):
            """The shortest of ``ends`` that holds the live rows, found by halves: a branch taken costs the scalar core
            as much as a copy issued, and a chain a 128 rows (the online softmax carried across sub-chunks) took 0.4 us
            each on the chip where a longer prefix under one chain takes 0.1 a 128 rows."""
            if len(ends) == 1:
                return over(ends[0], carry)
            half = len(ends) // 2
            return jax.lax.cond(live <= ends[half - 1], functools.partial(among, ends[:half]),
                                functools.partial(among, ends[half:]), carry)

        return among(prefixes, carry)

    m, l, acc = over_chunks(score, softmax_start(heads, r_kv))
    # an empty slot (length 0) read nothing: its output is 0, not 0/0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    close()


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
