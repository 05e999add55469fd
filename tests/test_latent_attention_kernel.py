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
from ray_tpu.ops.paged_attention import (
    _LATENT_CHUNK_BYTES, _LATENT_PREFIX, can_use_latent_kernel, chunk_blocks_for, paged_latent_attention,
)
from test_paged_attention_kernel import CROWDS, crowds

HEADS, R_KV, D_R, STORED = 64, 512, 64, 640
# a chunk of the kernel is 64 of these blocks in bfloat16 and 32 in float32 (1,024 and 512 rows, scored over the live rows
# rounded up to 256): a full table is two chunks and an eighth, or four and a quarter
BLOCK, TABLE, ATTENTIONS, POOL_BLOCKS = 16, 136, 2, 600
FULL = BLOCK * TABLE
# four sequences a case; 0 is an empty slot
LENGTHS = {
    "one": [1, 2, 5, 9],
    "a_block": [16, 32, 48, 16],
    "a_block_and_one": [17, 33, 1, 49],
    "a_full_table": [FULL, FULL - 1, FULL - BLOCK + 1, FULL],
    "chunks": [64 * BLOCK, 64 * BLOCK + 1, 128 * BLOCK, 65 * BLOCK],  # whole, and one block into the next (bfloat16)
    "chunks_of_float32": [32 * BLOCK, 32 * BLOCK + 1, 96 * BLOCK, 33 * BLOCK],
    "chunks_of_512_rows": [32 * BLOCK, 32 * BLOCK + 1, 64 * BLOCK, 33 * BLOCK],  # the chunks of before PR 63
    "chunks_of_256_rows": [16 * BLOCK, 16 * BLOCK + 1, 48 * BLOCK, 17 * BLOCK],
    "inactive_slots": [0, 40, 0, 7],
    "neighbours_that_end_in_different_prefixes": [100, 300, 600, 900],
    "neighbours_that_end_in_different_prefixes_of_later_chunks": [1024 + 100, 512 + 300, 2048 + 90, 1900],
}
# the edges of a block, of a scored prefix (256 rows and the 128 of the MXU's tile), of a chunk in either type and of
# the tables the engines serve with (96 blocks: 1,536 rows; 128: 2,048)
EDGES = [1, 16, 127, 128, 129, 255, 256, 257, 511, 512, 513, 640, 767, 768, 769, 1023, 1024, 1025, 1535, 1536, 2047, 2048,
         2049, FULL]
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


@pytest.mark.parametrize("length", EDGES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_kernel_agrees_at_the_edges_of_blocks_prefixes_and_chunks(dtype, length):
    """A length at an edge, beside three neighbours at other edges, against the gathered path."""
    at = EDGES.index(length)
    lengths = np.asarray([length, EDGES[(at + 7) % len(EDGES)], EDGES[(at + 13) % len(EDGES)], length], np.int32)
    pool, tables = _pool(dtype, seed=13), _tables(lengths, seed=14 + at)
    q_l, q_r = _queries(len(lengths), dtype, seed=15)
    got = np.asarray(_kernel(q_l, q_r, pool, tables, lengths).astype(jnp.float32))
    want = np.asarray(_gathered(q_l, q_r, pool, tables, lengths).astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("crowd", list(CROWDS))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_a_sequence_reads_the_same_alone_and_among_neighbours(dtype, crowd):
    """Bit for bit: in another slot, among ``test_paged_attention_kernel``'s
    crowds at this kernel's chunk size (the walk and the carry are one
    function's for both kernels): whoever started its first chunk, into
    whichever buffer, its neighbours' rows having passed through both."""
    chunk = BLOCK * chunk_blocks_for(TABLE, BLOCK * STORED * jnp.dtype(dtype).itemsize, _LATENT_CHUNK_BYTES)
    assert chunk == {2: 64, 4: 32}[jnp.dtype(dtype).itemsize] * BLOCK
    among_whom = crowds(chunk, FULL)[crowd]
    slot = among_whom.index("X")
    rng = np.random.default_rng(4)
    pool = _pool(dtype, seed=5)
    for length in (1, 17, 600, 1030, FULL):
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


@pytest.mark.parametrize("garbage", [np.nan, 1e30], ids=["nan", "1e30"])
@pytest.mark.parametrize("length", [1, 100, 256, 520, 700, 1024 + 50, 1024 + 600, 2048 + 100])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16_pool", "float32_pool"])
def test_rows_past_the_scored_prefix_are_never_read(dtype, length, garbage):
    """A chunk is scored over its live rows rounded up to ``_LATENT_PREFIX`` and
    the buffer's rows past that are not read: two neighbours ahead of the
    sequence, a full table each, bring NaN (or 1e30, whose square is infinite
    and whose zero multiple of that NaN) through both buffers at every row past
    the prefix the sequence's last chunk is scored over, the sequence's dead
    table entries and the null block hold the same, and its output is bit for
    bit what it is alone over a clean pool. (Scored whole, as before PR 63, the
    chunk's dead rows would weigh exactly 0 and give 0 x NaN.)"""
    chunk = BLOCK * chunk_blocks_for(TABLE, BLOCK * STORED * jnp.dtype(dtype).itemsize, _LATENT_CHUNK_BYTES)
    in_last = (length - 1) % chunk + 1  # live rows of the sequence's last chunk
    prefix = min(-(-in_last // _LATENT_PREFIX) * _LATENT_PREFIX, chunk)
    assert prefix < chunk  # else there is no row past it
    lengths = np.asarray([length, 0, 0, 0], np.int32)
    pool, tables = _pool(dtype, seed=16), _tables(lengths, seed=17)
    q_l, q_r = _queries(4, dtype, seed=18)
    alone = np.asarray(_kernel(q_l, q_r, pool, tables, lengths).astype(jnp.float32))[0]

    among = np.asarray([FULL, FULL, length, 0], np.int32)
    among_tables = _tables(among, seed=19)
    spare = [b for b in range(1, POOL_BLOCKS) if b not in tables[0]]
    dirty = np.array(pool.astype(jnp.float32))
    dirty[:, 0] = garbage  # the null block
    for row in (0, 1):
        among_tables[row] = spare[row * TABLE:(row + 1) * TABLE]
        for j, block in enumerate(among_tables[row]):
            if j * BLOCK % chunk >= prefix:
                dirty[:, block] = garbage
    among_tables[2] = tables[0]
    dead = spare[2 * TABLE]
    dirty[:, dead] = garbage
    among_tables[2, -(-length // BLOCK):] = dead  # entries never followed
    got = _kernel(q_l[jnp.asarray([1, 2, 0, 3])], q_r[jnp.asarray([1, 2, 0, 3])], jnp.asarray(dirty, dtype), among_tables, among)
    assert np.array_equal(alone, np.asarray(got.astype(jnp.float32))[2])


@pytest.mark.parametrize("config", ["kimi-k2-7l", "longcat-flash-omni-4l"])
def test_the_timer_rehearses_off_the_chip(tmp_path, config):
    """``tools/latent_time.py`` at a cell's configuration, off the chip: an
    eighth of the heads and four slots in interpret mode, a profile of each
    form drawn as on the chip, every call held against the gathered path; no
    time is read (``"not measured"``), so there is nothing to fit."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location("latent_time", os.path.join(root, "tools", "latent_time.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "latent_time.json"
    tool.main(["--config", os.path.join(root, "benchmarks", "configs", config + ".json"), "--out", str(out),
               "--also", os.path.join(root, "ray_tpu", "ops", "paged_attention.py")])
    report = json.loads(out.read_text())
    assert report["mix"] == {"kimi-k2-7l": "reasoning", "longcat-flash-omni-4l": "longanswer"}[config]
    for label in ("tree", "also"):
        timed = report["kernels"][label]
        assert timed["chunk_rows"] == 1024 and timed["fit"] == "not measured"
        assert [ln["profile"] for ln in timed["lines"]] == ["cell_0", "full_1", "full_1_and_a_block", "one_block"]
        for ln in timed["lines"]:
            assert ln["us_a_call"] == "not measured" and ln["sequences"] == 4 and ln["err_to_gathered"] < 0.05
            assert ln["rows"] >= ln["live_rows"] > ln["rows"] - 4 * 16 and ln["bytes_us"] > 0
    assert [ln["rows"] for ln in timed["lines"]][1:] == [4 * 512, 4 * 528, 4 * 16]
    # the fit finds what a call is made of: 2 ns a row, 0.5 us a chunk, 0.25 us a sequence
    lines = [dict(rows=r, chunks=c, sequences=48, us_a_call=2e-3 * r + 0.5 * c + 0.25 * 48)
             for r, c in [(24576, 48), (25344, 48), (49152, 48), (49920, 96), (768, 48), (33000, 60)]]
    fitted = tool.fit(lines)
    assert abs(fitted["ns_a_row"] - 2.0) < 1e-6 and abs(fitted["us_a_chunk"] - 0.5) < 1e-6
    assert abs(fitted["us_a_sequence"] - 0.25) < 1e-6 and fitted["residual_us_max"] < 1e-6


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
