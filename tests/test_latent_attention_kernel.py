"""The decode step's kernel over the latent cache (``ops/paged_attention.py:
paged_latent_attention``) in Pallas interpret mode on the CPU, against the path
it replaces on a TPU: every block of a table gathered out of the pool and
attended to under a mask (``latent_attention.latent_decode_attention``), at the
published widths of LongCat-Flash and Kimi-K2 (64 heads, a latent of 512, a
rotated part of 64, rows stored 640 wide). The kernel's compile for the chip is
in ``test_tpu_compile.py``; its speed is the benchmark's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.latent_attention import latent_decode_attention
from ray_tpu.ops.paged_attention import _LATENT_CHUNK_BYTES, can_use_latent_kernel, chunk_blocks_for, paged_latent_attention
from test_paged_attention_kernel import CROWDS, crowds

HEADS, R_KV, D_R, STORED = 64, 512, 64, 640
# a chunk of the kernel is 32 of these blocks in bfloat16 and 16 in float32: a full table is two chunks and a quarter, or four and a half
BLOCK, TABLE, ATTENTIONS, POOL_BLOCKS = 16, 72, 2, 300
FULL = BLOCK * TABLE
# four sequences a case; 0 is an empty slot
LENGTHS = {
    "one": [1, 2, 5, 9],
    "a_block": [16, 32, 48, 16],
    "a_block_and_one": [17, 33, 1, 49],
    "a_full_table": [FULL, FULL - 1, FULL - BLOCK + 1, FULL],
    "chunks": [32 * BLOCK, 32 * BLOCK + 1, 64 * BLOCK, 33 * BLOCK],  # whole, and one block into the next (bfloat16)
    "chunks_of_float32": [16 * BLOCK, 16 * BLOCK + 1, 48 * BLOCK, 17 * BLOCK],
    "inactive_slots": [0, 40, 0, 7],
}
# longcat's 1 / sqrt(d_n + d_r) and that times Kimi's YaRN m^2: the kernel takes the caller's
SCALES = {"plain": 192 ** -0.5, "yarn": 1.813 * 192 ** -0.5}


def _pool(dtype, seed):
    """A pool of random rows ``[ckv | k_r | zeros]`` in ``dtype``, as the engine holds it."""
    rows = np.random.default_rng(seed).standard_normal((ATTENTIONS, POOL_BLOCKS, BLOCK, STORED))
    rows[..., R_KV + D_R:] = 0.0
    return jnp.asarray(rows, dtype)


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


def _queries(n, dtype, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, HEADS, R_KV)), dtype),
            jnp.asarray(rng.standard_normal((n, HEADS, D_R)), dtype))


@jax.jit(static_argnames="scale")
def _kernel(q_l, q_r, pool, tables, lengths, scale=SCALES["plain"]):
    return paged_latent_attention(q_l, q_r, pool, jnp.int32(1), tables, lengths, scale=scale, interpret=True)


@jax.jit(static_argnames="scale")
def _gathered(q_l, q_r, pool, tables, lengths, scale=SCALES["plain"]):
    rows = pool[1, tables].reshape(len(tables), -1, STORED)[..., :R_KV + D_R]
    return latent_decode_attention(q_l, q_r, rows, lengths, scale=scale)


@pytest.mark.parametrize("case", list(LENGTHS))
@pytest.mark.parametrize("scale", list(SCALES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_kernel_agrees_with_latent_attention_over_the_gathered_rows(dtype, scale, case):
    lengths = np.asarray(LENGTHS[case], np.int32)
    pool, tables = _pool(dtype, seed=1), _tables(lengths, seed=2)
    q_l, q_r = _queries(len(lengths), dtype, seed=3)
    got = np.asarray(_kernel(q_l, q_r, pool, tables, lengths, scale=SCALES[scale]).astype(jnp.float32))
    want = np.asarray(_gathered(q_l, q_r, pool, tables, lengths, scale=SCALES[scale]).astype(jnp.float32))
    active = lengths > 0
    assert got.shape == (len(lengths), HEADS, R_KV) and np.isfinite(got).all()
    assert not got[~active].any()  # an empty slot reads nothing and gives 0
    # float32: the same sums in another order. bfloat16: the weights are
    # rounded before the chunk's sum is divided by the whole, not after
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)


@pytest.mark.parametrize("crowd", list(CROWDS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_a_sequence_reads_the_same_alone_and_among_neighbours(dtype, crowd):
    """Bit for bit: in another slot, among ``test_paged_attention_kernel``'s
    crowds at this kernel's chunk size (the walk and the carry are one
    function's for both kernels): whoever started its first chunk, into
    whichever buffer, its neighbours' rows having passed through both."""
    chunk = BLOCK * chunk_blocks_for(TABLE, BLOCK * STORED * jnp.dtype(dtype).itemsize, _LATENT_CHUNK_BYTES)
    assert chunk == {2: 32, 4: 16}[jnp.dtype(dtype).itemsize] * BLOCK
    among_whom = crowds(chunk, FULL)[crowd]
    slot = among_whom.index("X")
    rng = np.random.default_rng(4)
    pool = _pool(dtype, seed=5)
    for length in (1, 17, 600, FULL):
        lengths = np.asarray([length, 0, 0, 0], np.int32)
        tables = _tables(lengths, seed=6)
        q_l, q_r = _queries(4, dtype, seed=7)
        alone = np.asarray(_kernel(q_l, q_r, pool, tables, lengths).astype(jnp.float32))[0]

        among = np.asarray([length if n in ("X", "x") else n for n in among_whom], np.int32)
        among_tables = _tables(among, seed=8)
        among_tables[slot] = tables[0]
        spare = [b for b in range(1, POOL_BLOCKS) if b not in tables[0]]
        for row, n in enumerate(among):  # the neighbours off the sequence's own blocks
            if row != slot:
                among_tables[row, : -(-n // BLOCK)] = rng.permutation(spare)[: -(-n // BLOCK)]
        order = jnp.asarray([0 if row == slot else 1 + row % 3 for row in range(4)])  # the sequence's query in its slot
        got = _kernel(q_l[order], q_r[order], pool, among_tables, among)
        assert np.array_equal(alone, np.asarray(got.astype(jnp.float32))[slot]), length


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_dead_table_entries_and_dead_rows_change_nothing(dtype):
    """Garbage where nothing is live: a table's entries past the last live
    block (never followed), the rows of the last live block past the length
    (copied, and given a weight of exactly 0), the null block."""
    lengths = np.asarray([1, 17, 32 * BLOCK + 5, FULL - 3], np.int32)
    pool, tables = _pool(dtype, seed=9), _tables(lengths, seed=10)
    q_l, q_r = _queries(4, dtype, seed=11)
    clean = np.asarray(_kernel(q_l, q_r, pool, tables, lengths).astype(jnp.float32))
    rng = np.random.default_rng(12)
    dirty_tables, dirty = tables.copy(), np.array(pool.astype(jnp.float32))
    dirty[:, 0] = 1e4 * rng.standard_normal(dirty[:, 0].shape)
    for i, n in enumerate(lengths):
        live = -(-n // BLOCK)
        dirty_tables[i, live:] = rng.integers(0, POOL_BLOCKS, TABLE - live)
        dirty[:, tables[i, live - 1], n - (live - 1) * BLOCK:] = 1e4 * rng.standard_normal(STORED)
    got = _kernel(q_l, q_r, jnp.asarray(dirty, dtype), dirty_tables, lengths)
    assert np.array_equal(clean, np.asarray(got.astype(jnp.float32)))


POOL = (7, 64, 16, 640)


@pytest.mark.parametrize(
    "backend,s,r_kv,pool_shape,pool_dtype,want",
    [
        ("tpu", 1, 512, POOL, jnp.bfloat16, True),  # Kimi-K2's and LongCat-Flash's decode step
        ("tpu", 1, 512, POOL, jnp.float32, True),
        ("tpu", 1, 512, (7, 64, 8, 640), jnp.float32, True),
        ("cpu", 1, 512, POOL, jnp.bfloat16, False),  # tier-1, the rehearsals
        ("tpu", 512, 512, POOL, jnp.bfloat16, False),  # a prefill: S is the bucket
        ("tpu", 1, 512, (7, 64, 16, 576), jnp.bfloat16, False),  # rows stored as wide as they are: half a lane tile over
        ("tpu", 1, 512, (7, 64, 8, 640), jnp.bfloat16, False),  # a block of half a sublane tile
        ("tpu", 1, 16, (3, 128, 4, 24), jnp.float32, False),  # the tests' tiny twins
    ],
)
def test_the_path_is_chosen_by_platform_and_shape(monkeypatch, backend, s, r_kv, pool_shape, pool_dtype, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert can_use_latent_kernel(s, r_kv, jax.ShapeDtypeStruct(pool_shape, pool_dtype)) is want
