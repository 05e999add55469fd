"""The decode step's paged-attention kernel (``ops/paged_attention.py``) in
Pallas interpret mode on the CPU, against the path it replaces on a TPU: the
table's rows gathered out of the pool and attended to as a masked dense block
(``ops.attention.attention`` under the table's mask); the form that writes
the decode step's own K and V row into a flat pool, against a scatter of that
row (``ops.window_attention.write_spans``) and then the form without rows; and
both forms bit for bit against the walk written out plainly (a sequence's
chunks gathered one at a time under an online softmax). The pool is one array,
a layer's keys in plane 0 and its values in plane 1. The kernel's compile for
the chip is in ``test_tpu_compile.py``; its speed is the benchmark's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import attention
from ray_tpu.ops.paged_attention import (
    can_use_paged_kernel, chunk_blocks_for, covering_span, paged_decode_attention, paged_latent_attention,
)
from ray_tpu.ops.window_attention import write_spans

# a chunk of the kernel is 16 of these blocks: a full table is two chunks and a half
BLOCK, TABLE, LAYERS, POOL_BLOCKS, HEAD_DIM = 16, 40, 2, 176, 128
FULL = BLOCK * TABLE
# four sequences a case; 0 is an inactive slot
LENGTHS = {
    "one": [1, 2, 5, 9],
    "a_block": [16, 32, 48, 16],
    "a_block_and_one": [17, 33, 1, 49],
    "a_full_table": [FULL, FULL - 1, FULL - BLOCK + 1, FULL],
    "chunks": [16 * BLOCK, 16 * BLOCK + 1, 32 * BLOCK, 17 * BLOCK],  # whole, and one block into the next
    "inactive_slots": [0, 40, 0, 7],
    # the first chunk carried from one sequence to the next (``ahead``): into either buffer, not at all, past nobody
    "a_chunk_each": [16 * BLOCK, 255, 200, 16 * BLOCK],  # every first chunk but the first slot's is started by its predecessor
    "one_two_and_three_chunks": [16 * BLOCK, FULL, 32 * BLOCK, 1],  # a first chunk in the second buffer, then the first
    "an_empty_slot_between": [300, 0, FULL, 0],  # nothing started ahead: the third starts its own, where the first left off
    "every_slot_empty": [0, 0, 0, 0],
}


def _pool(dtype, kv_heads, seed):
    """A pool of random rows in ``dtype``, as the engine holds it: (layers, 2, slots, heads, head_dim)."""
    rng = np.random.default_rng(seed)
    shape = (LAYERS, POOL_BLOCKS * BLOCK, kv_heads, HEAD_DIM)
    return jnp.stack([jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(2)], axis=1)


def _tables(lengths, seed):
    """Each sequence's blocks drawn without order from the pool (never the
    null block 0), the rest of its table padded with 0."""
    rng = np.random.default_rng(seed)
    free = list(rng.permutation(np.arange(1, POOL_BLOCKS)))
    tables = np.zeros((len(lengths), TABLE), np.int32)
    for i, n in enumerate(lengths):
        for j in range(-(-n // BLOCK)):
            tables[i, j] = free.pop()
    return tables


@jax.jit
def _kernel(q, pool, tables, lengths):
    return paged_decode_attention(q, pool, 1, tables, lengths, block_size=BLOCK, interpret=True)


@jax.jit
def _gathered(q, pool, tables, lengths):
    idx = (tables[:, :, None] * BLOCK + jnp.arange(BLOCK)[None, None, :]).reshape(len(tables), -1)
    mask = jnp.arange(idx.shape[1]) < lengths[:, None, None, None]  # (B, 1, 1, M)
    return attention(q[:, None], pool[1, 0][idx], pool[1, 1][idx], causal=False, mask=mask)[:, 0]


@pytest.mark.parametrize("case", list(LENGTHS))
@pytest.mark.parametrize("n_rep", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_kernel_agrees_with_attention_over_the_gathered_rows(dtype, n_rep, case):
    kv_heads = 32 // jnp.dtype(dtype).itemsize  # one sublane tile of the pool's type
    lengths = np.asarray(LENGTHS[case], np.int32)
    pool = _pool(dtype, kv_heads, seed=1)
    tables = _tables(lengths, seed=2)
    q = jnp.asarray(
        np.random.default_rng(3).standard_normal((len(lengths), kv_heads * n_rep, HEAD_DIM)), dtype
    )
    got = np.asarray(_kernel(q, pool, tables, lengths).astype(jnp.float32))
    want = np.asarray(_gathered(q, pool, tables, lengths).astype(jnp.float32))
    active = lengths > 0
    assert np.isfinite(got).all()
    assert not got[~active].any()  # an inactive slot reads nothing and gives 0
    # float32: the same sums in another order. bfloat16: the weights are
    # rounded before the chunk's sum is divided by the whole, not after
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)


# The sequence ("X", of one, one, two and three chunks in turn) among neighbours of these lengths ("x": of its own): who
# starts its first chunk, into which buffer, and whether it starts a neighbour's (C: a chunk's positions).
C = 16 * BLOCK


def crowds(c, full):
    """The neighbours' lengths by a chunk's positions ``c`` and a table's ``full`` (an odd count of chunks): the latent
    kernel's test takes the same crowds at its own chunk size."""
    return {
        "behind_a_full_table_and_one": [full, 33, "X", 100],  # an even number of chunks before it: started ahead, into the first buffer
        "behind_one_chunk": [c, "X", 2 * c, 7],  # an odd number before it: started ahead into the second buffer
        "behind_two_chunks": [2 * c, "X", 1, 0],  # an even number: into the first
        "behind_an_empty_slot": [100, 0, "X", 50],  # it starts its own, in the second buffer, and its successor's
        "before_an_empty_slot": [c + 1, "X", 0, 77],  # started ahead, and starts nothing
        "in_the_last_slot": [17, 2 * c, full, "X"],  # nothing to start
        "in_the_first_slot": ["X", full, 0, c],
        "the_others_empty": [0, 0, "X", 0],
        "a_chunk_a_neighbour": [c, 17, "X", 1],
        "among_its_like": ["x", "x", "X", "x"],  # neighbours of its own length: every slot ends and starts its chunks in step
    }


CROWDS = crowds(C, FULL)


@pytest.mark.parametrize("crowd", list(CROWDS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_a_sequence_reads_the_same_alone_and_among_neighbours(dtype, crowd):
    """Bit for bit: in another slot, beside neighbours of other lengths whose
    rows passed through the same buffers, with the pool's other blocks changed,
    whichever buffer its first chunk lands in and whoever started it."""
    kv_heads = 32 // jnp.dtype(dtype).itemsize
    assert C == BLOCK * chunk_blocks_for(TABLE, BLOCK * kv_heads * HEAD_DIM * jnp.dtype(dtype).itemsize)
    rng = np.random.default_rng(4)
    pool = _pool(dtype, kv_heads, seed=5)
    slot = CROWDS[crowd].index("X")
    for length in (1, 17, 300, FULL):
        lengths = np.asarray([length, 0, 0, 0], np.int32)
        tables = _tables(lengths, seed=6)
        q = jnp.asarray(rng.standard_normal((4, kv_heads, HEAD_DIM)), dtype)
        alone = np.asarray(_kernel(q, pool, tables, lengths).astype(jnp.float32))[0]

        among = np.asarray([length if n in ("X", "x") else n for n in CROWDS[crowd]], np.int32)
        among_tables = _tables(among, seed=7)
        among_tables[slot] = tables[0]
        spare = [b for b in range(1, POOL_BLOCKS) if b not in tables[0]]
        for row, n in enumerate(among):  # off the sequence's own blocks
            if row != slot:
                among_tables[row, : -(-n // BLOCK)] = rng.permutation(spare)[: -(-n // BLOCK)]
        order = [0 if row == slot else 1 + row % 3 for row in range(4)]  # the sequence's query in its slot
        got = _kernel(q[jnp.asarray(order)], pool, among_tables, among)
        assert np.array_equal(alone, np.asarray(got.astype(jnp.float32))[slot]), length


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and in every jaxpr its equations hold (loops, conditionals)."""
    return sum((eqn.primitive.name == primitive) + sum(_count(sub, primitive) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def _decode_call(tables, lengths):
    q, pool = jnp.zeros((2, 16, HEAD_DIM), jnp.bfloat16), jnp.zeros((1, 2, 64 * BLOCK, 16, HEAD_DIM), jnp.bfloat16)
    return functools.partial(paged_decode_attention, layer=0, block_tables=tables, lengths=lengths, block_size=BLOCK), (q, pool)


def _decode_call_with_the_steps_row(tables, lengths):
    q, pool = jnp.zeros((2, 16, HEAD_DIM), jnp.bfloat16), jnp.zeros((1, 2, 64 * BLOCK * 8, HEAD_DIM), jnp.bfloat16)
    new = jnp.zeros((2, 8, HEAD_DIM), jnp.bfloat16)
    return functools.partial(paged_decode_attention, layer=0, block_tables=tables, lengths=lengths, block_size=BLOCK, kv_heads=8,
                             new_k=new, new_v=new), (q, pool)


def _latent_call(tables, lengths):
    q_l, q_r = jnp.zeros((2, 8, 512), jnp.bfloat16), jnp.zeros((2, 8, 64), jnp.bfloat16)
    rows = jnp.zeros((1, 64, BLOCK, 640), jnp.bfloat16)
    return functools.partial(paged_latent_attention, att_index=0, block_tables=tables, lengths=lengths, scale=1.0), (q_l, q_r, rows)


@pytest.mark.parametrize(
    "call,unrolled,back", [(_decode_call, False, 0), (_decode_call_with_the_steps_row, False, 1), (_latent_call, True, 0)],
    ids=["keys_and_values_in_a_loop", "keys_and_values_and_the_steps_row", "latent_rows_unrolled"])
def test_a_traced_kernel_holds_the_copy_sites_of_its_walk(call, unrolled, back):
    """``chunk_walk``'s sites, counted in the traced kernel: the first chunk's
    start, this sequence's next chunk's, the next sequence's first chunk's, and
    the wait. What a replica's start pays to trace and lower a kernel is the
    count of its binds. A kernel that walks a chunk's live blocks in a loop
    holds one copy a site whatever the table's width (a chunk of 4, 8 or 16
    blocks: a block's keys and values come in under one copy); the latent
    kernel, where the chip reads a loop over the blocks a fifth slower, lays
    its copies out a place of the chunk in the lowered kernel (``test_tpu_compile``
    counts them there) from two binds a site, a group's first place and the
    body of the loop over its others, both loops unrolled where the kernel is
    lowered (PR 63: 18 ms of a replica's start a traced copy), and waits one
    wait a binary digit of a chunk's live count, each for that many blocks'
    bytes (3, 4, 6). The step's own row goes back under one copy more, keys and
    values together."""
    for width, chunk_blocks in ((4, 4), (8, 8), (40, 40 if unrolled else 16)):
        fn, args = call(jnp.zeros((2, width), jnp.int32), jnp.zeros((2,), jnp.int32))
        (kernel,) = [eqn for eqn in jax.make_jaxpr(fn)(*args).eqns if eqn.primitive.name == "pallas_call"]
        starts, waits = (2, chunk_blocks.bit_length()) if unrolled else (1, 1)
        counted = _count(kernel.params["jaxpr"], "dma_start"), _count(kernel.params["jaxpr"], "dma_wait")
        assert counted == (3 * starts + back, waits + back), width


# The step's own row: position ``length - 1`` of four sequences, R a chunk's positions (16 x 32, 16, 24 or 12 blocks
# by heads and type: the table is a chunk and a part, or three and a part), FULL the table's.
ROWS = {
    "first_in_a_block": (17, 33, 1, 49),
    "last_in_a_block": (16, 32, 48, 16),
    "last_of_a_chunks_last_block": ("R", "FULL", "R", 16),
    "first_of_a_new_chunk": ("R+1", 1, "R+1", "FULL-15"),
    "an_inactive_slot_among_live_ones": (0, 40, 0, 7),
    "two_calls_at_one_position": (5, "R+1", 16, 33),
    # a neighbour's first chunk started ahead while this sequence's tiles go back: from either buffer, past an empty
    # slot (not started), and no slot live
    "a_neighbour_started_ahead_from_either_buffer": ("R", "R+1", "FULL", "R+1"),
    "nothing_started_past_an_empty_slot": ("R", 0, "R+1", 0),
    "two_chunks_then_an_empty_slot": ("R+1", 0, "FULL", 33),
    "every_slot_empty": (0, 0, 0, 0),
}


@functools.partial(jax.jit, static_argnums=0)  # one trace a form (with rows, without), a head count and a type
def _flat_kernel(kv_heads, q, tables, lengths, pool, **rows):
    return paged_decode_attention(q, pool, 1, tables, lengths, block_size=BLOCK, kv_heads=kv_heads, interpret=True, **rows)


def _bits(x):
    """An array's values as the integers they are stored as: -0.0 is not 0.0, and no NaN compares unequal to itself."""
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


def _scattered(pool, tables, lengths, kv_heads, new):
    """A flat ``pool`` with the live sequences' rows ``new`` (keys, values) scattered to position ``length - 1``, a plane
    each: what the kernel's write has to leave, bit for bit."""
    held = np.flatnonzero(lengths)
    at = lengths[held] - 1
    starts = jnp.asarray((tables[held, at // BLOCK] * BLOCK + at % BLOCK) * kv_heads)
    for plane, x in enumerate(new):
        pool = write_spans(pool, (1, plane), starts, x[held])
    return pool


@pytest.mark.parametrize("case", list(ROWS))
@pytest.mark.parametrize("kv_heads", [8, 10], ids=["8_heads_half_a_bfloat16_tile", "10_pairs_across_two_tiles"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_the_kernel_writes_the_steps_own_row_as_a_scatter_would_and_attends_over_it(dtype, kv_heads, case):
    """The pool that comes back is bit for bit ``write_spans``' over the live
    sequences in each plane (every other row, the null block's among them, as
    it came), and ``o`` is bit for bit the kernel's without rows over it: the
    new row is scored where the stale one lay."""
    chunk = BLOCK * chunk_blocks_for(TABLE, BLOCK * kv_heads * HEAD_DIM * jnp.dtype(dtype).itemsize,
                                     whole=128 // np.gcd(BLOCK * kv_heads, 128))
    assert chunk < FULL and covering_span(kv_heads, 32 // jnp.dtype(dtype).itemsize) == {
        (8, 2): 16, (10, 2): 32, (8, 4): 8, (10, 4): 16}[kv_heads, jnp.dtype(dtype).itemsize]
    lengths = np.asarray([{"R": chunk, "R+1": chunk + 1, "FULL": FULL, "FULL-15": FULL - 15}.get(n, n) for n in ROWS[case]],
                         np.int32)
    rng = np.random.default_rng(8)
    pool = jnp.stack([jnp.asarray(rng.standard_normal((LAYERS, POOL_BLOCKS * BLOCK * kv_heads, HEAD_DIM)), dtype)
                      for _ in range(2)], axis=1)
    tables = _tables(lengths, seed=9)
    q = jnp.asarray(rng.standard_normal((4, 2 * kv_heads, HEAD_DIM)), dtype)
    new = [jnp.asarray(rng.standard_normal((4, kv_heads, HEAD_DIM)), jnp.float32).at[0, 0, 0].set(-0.0) for _ in range(2)]
    kernel = functools.partial(_flat_kernel, kv_heads, q, tables, lengths)

    o, got = kernel(pool, new_k=new[0], new_v=new[1])
    if case == "two_calls_at_one_position":  # the replay: the same row again, the same output
        once, (o, got) = o, kernel(got, new_k=new[0], new_v=new[1])
        np.testing.assert_array_equal(_bits(o), _bits(once))
    want = _scattered(pool, tables, lengths, kv_heads, new)
    assert got.dtype == pool.dtype and got.shape == pool.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(got[:, :, :BLOCK * kv_heads]), _bits(pool[:, :, :BLOCK * kv_heads]))  # the null block
    assert (_bits(got) != _bits(pool)).any(axis=-1).sum() == 2 * np.count_nonzero(lengths) * kv_heads  # and nothing but the rows
    np.testing.assert_array_equal(_bits(o), _bits(kernel(want)))
    assert not np.asarray(o.astype(jnp.float32))[lengths == 0].any()


# -- the walk written out plainly: what the kernel's arithmetic is, whatever brings the rows in --------------------------


def _plain_walk(q, pool, tables, lengths, *, kv_heads, scale=None):
    """``paged_decode_attention`` without a kernel, a buffer or a copy, over layer 1 of a flat ``pool``: a sequence's
    live blocks gathered a chunk at a time (a dead block's rows zeros), every head scored against every row of the chunk,
    the other heads' rows and the positions past the length masked, under the online softmax in float32 with the weights
    cast to the pool's type. The chunk is the kernel's (``chunk_blocks_for``), so the sums are taken in its order."""
    _, heads, head_dim = q.shape
    each = BLOCK * kv_heads
    blocks = chunk_blocks_for(tables.shape[1], each * head_dim * pool.dtype.itemsize, whole=128 // np.gcd(each, 128))
    rows, cols = blocks * BLOCK, blocks * each
    keys, values = (pool[1, plane].reshape(-1, each, head_dim) for plane in (0, 1))
    own_head = (jnp.arange(cols) % kv_heads)[None, :] == (jnp.arange(heads) // (heads // kv_heads))[:, None]
    out = []
    for b, length in enumerate(lengths):
        m, l = jnp.full((heads, 1), -1e30, jnp.float32), jnp.zeros((heads, 1), jnp.float32)
        acc = jnp.zeros((heads, head_dim), jnp.float32)
        n_blocks = -(-int(length) // BLOCK)
        for c in range(-(-n_blocks // blocks)):
            mine = np.zeros((blocks,), np.int32)
            live = np.arange(c * blocks, min((c + 1) * blocks, n_blocks))
            mine[: len(live)] = tables[b, live]
            dead = (jnp.arange(blocks) >= len(live))[:, None, None]
            k, v = (jnp.where(dead, 0, x[mine]).reshape(cols, head_dim) for x in (keys, values))
            s = jax.lax.dot_general(q[b], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = s * (scale or 1.0 / head_dim**0.5)
            s = jnp.where(own_head & (c * rows + jnp.arange(cols) // kv_heads < length)[None, :], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(pool.dtype), v, preferred_element_type=jnp.float32)
            m = m_new
        out.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
    return jnp.stack(out)


WALKS = ["one", "a_block_and_one", "chunks", "a_full_table", "inactive_slots", "an_empty_slot_between", "every_slot_empty"]


@pytest.mark.parametrize("case", WALKS)
@pytest.mark.parametrize(
    "layout,kv_heads,rows",  # a step's row is written into a flat pool: GPT-J's and the hybrid's layers scatter theirs
    [("flat", 4, False), ("flat", 4, True), ("flat", 10, False), ("flat", 10, True), ("stored", 16, False)],
    ids=["4_heads_flat", "4_heads_flat_with_the_steps_row", "10_heads_flat_off_the_tile",
         "10_heads_flat_off_the_tile_with_the_steps_row", "16_heads_stored"])
def test_one_copy_a_block_leaves_the_output_and_the_pool_what_the_walk_written_out_gives(layout, kv_heads, rows, case):
    """Bit for bit: the output against the plain walk over the pool as the
    step leaves it, and the pool against a scatter of the step's rows (a call
    without rows: the pool as it came). Keys and values of a block come in
    under one copy and the row goes back under one; what they hold and the
    order of every sum is what two pools and two copies gave. Four heads: a
    block of one plane is 16 KB, where the copies were furthest from their
    bytes' time."""
    lengths = np.asarray(LENGTHS[case], np.int32)
    rng = np.random.default_rng(54)
    pool = jnp.stack([jnp.asarray(rng.standard_normal((LAYERS, POOL_BLOCKS * BLOCK * kv_heads, HEAD_DIM)), jnp.bfloat16)
                      for _ in range(2)], axis=1)
    tables = _tables(lengths, seed=55)
    q = jnp.asarray(rng.standard_normal((4, 2 * kv_heads, HEAD_DIM)), jnp.bfloat16)
    new = [jnp.asarray(rng.standard_normal((4, kv_heads, HEAD_DIM)), jnp.bfloat16) for _ in range(2)]
    after = _scattered(pool, tables, lengths, kv_heads, new) if rows else pool
    if layout == "flat":
        got = _flat_kernel(kv_heads, q, tables, lengths, pool, **({"new_k": new[0], "new_v": new[1]} if rows else {}))
    else:
        got = _kernel(q, pool.reshape(LAYERS, 2, POOL_BLOCKS * BLOCK, kv_heads, HEAD_DIM), tables, lengths)
    o, left = got if rows else (got, pool)
    np.testing.assert_array_equal(_bits(left), _bits(after))
    np.testing.assert_array_equal(_bits(o), _bits(_plain_walk(q, after, tables, lengths, kv_heads=kv_heads)))


def test_rows_are_written_into_a_flat_pool_of_whole_tiles_alone():
    q, new = jnp.zeros((2, 16, 128), jnp.bfloat16), jnp.zeros((2, 16, 128), jnp.bfloat16)
    tables, lengths = jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32)
    stored = jnp.zeros((1, 2, 64, 16, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="flat pool"):
        paged_decode_attention(q, stored, 0, tables, lengths, block_size=16, new_k=new, new_v=new, interpret=True)
    flat = jnp.zeros((1, 2, 64 * 10, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole tiles"):  # blocks of 5 x 10 rows
        paged_decode_attention(q[:, :10], flat, 0, tables, lengths, block_size=5, kv_heads=10, new_k=new[:, :10],
                               new_v=new[:, :10], interpret=True)


GPTJ_Q, LLAMA7B_Q = (8, 1, 16, 256), (8, 1, 32, 128)


@pytest.mark.parametrize(
    "backend,q_shape,kv_heads,pool_dtype,want",
    [
        ("tpu", GPTJ_Q, 16, jnp.bfloat16, True),  # GPT-J-6B's decode step
        ("tpu", LLAMA7B_Q, 32, jnp.bfloat16, True),
        ("tpu", (8, 1, 8, 128), 8, jnp.float32, True),
        ("cpu", GPTJ_Q, 16, jnp.bfloat16, False),  # tier-1, the rehearsals
        ("tpu", (1, 512, 16, 256), 16, jnp.bfloat16, False),  # a prefill: S is the bucket
        ("tpu", (8, 1, 64, 128), 8, jnp.bfloat16, False),  # Llama-2-70B: 8 kv heads are half a tile
        ("tpu", (8, 1, 16, 64), 16, jnp.bfloat16, False),  # head_dim under a lane tile
    ],
)
def test_the_path_is_chosen_by_platform_and_shape(monkeypatch, backend, q_shape, kv_heads, pool_dtype, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((2, 2, 64 * 16, kv_heads, q_shape[-1]), pool_dtype)
    assert can_use_paged_kernel(q, pool, 16) is want
