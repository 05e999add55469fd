"""The grouped matmul's kernel call: rows sorted by group, times each group's
own matrix.

The kernel body and the group metadata are those of JAX's Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox.gmm``), without what
``models/moe.py:grouped_matmul`` never asks of it (a transposed or sharded
stack, an output to add to, a contraction that is no whole tiles). The
``pallas_call`` is this file's because the library's states no
``vmem_limit_bytes``: under the compiler's default of 16 MiB a weight tile of
more than ~3 MB, double-buffered beside the rows and the result, does not
compile. At the library's own tiling this call takes the library's time on the
chip (PERF.md section 6, PR 51), and the held rows come out the same bits.

The grid is (column tiles, visited (row tile, group) pairs, contraction tiles):
a pair is visited only where the group has a row in the tile, in the groups'
order, so a row tile's result block stays in fast memory while its groups go by
and each group's matrix is read from where it lies in the stack, once a pair.
Rows past the last group are left as whatever was there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

VMEM_BUDGET = 96 << 20  # of a v5e core's 128 MiB: the most a call may state
_COMPILER_SCRATCH = 2 << 20  # the compiler's own beside the buffers (0.05-0.5 MB at the tilings compiled for a v5e)


def vmem_bytes(tiling, itemsize: int, out_itemsize: int) -> int:
    """Fast memory of one call at ``tiling`` (row tile, contraction tile,
    column tile): two buffers each of the weight tile, the rows' tile and the
    result's, the float32 accumulator and the product the kernel adds to it,
    and the compiler's scratch. A call states it as its ``vmem_limit_bytes``."""
    tm, tk, tn = tiling
    return 2 * (tk * tn + tm * tk) * itemsize + 2 * tm * tn * out_itemsize + 2 * tm * tn * 4 + _COMPILER_SCRATCH


def unwritten(shape, dtype):
    """An array nobody has written, for a caller that writes what it will read
    (the windows' results, ``models/moe.py``): on a TPU a kernel call with no
    body, whose result is the memory as the allocator hands it out (whatever
    was there: mask what was not written, never multiply it by zero), where
    ``jnp.zeros`` writes every byte first (a layer's 117 MB at Kimi-K2's 512
    bucket, 0.17 ms at the chip's bandwidth; PERF.md section 6, PR 58).
    Elsewhere zeros."""
    if jax.default_backend() != "tpu":
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(lambda out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
                          out_specs=pl.BlockSpec(memory_space=pl.ANY), name="unwritten")()


@functools.partial(jax.jit, static_argnames=("preferred_element_type", "tiling", "interpret"))
def gmm(lhs, rhs, group_sizes, *, preferred_element_type, tiling, interpret: bool = False):
    """``lhs`` (m, k) times ``rhs`` (groups, k, n) by ``group_sizes`` (groups,)
    int32 -> (m, n) of ``preferred_element_type``, float32 accumulation.
    ``tiling`` divides (m, k, n). Jitted, as the library's is: a program's
    calls of one shape are traced once and lowered as one function (not
    jitted, a start spent 8 s more tracing and lowering LFM2's 18 calls a
    program: PERF.md section 6, PR 51)."""
    (m, k), n = lhs.shape, rhs.shape[-1]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide (m, k, n) = {(m, k, n)}")
    tiles_k = k // tk
    metadata, visited = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0), num_nonzero_groups=rhs.shape[0],
        visit_empty_groups=False)

    def kernel(group_offsets, group_ids, m_tile_ids, lhs, rhs, out, acc):
        pair, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(lhs[...], rhs[...], preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # the rows of the tile that are this group's: the others keep what the groups before left there
            group = group_ids[pair]
            row = m_tile_ids[pair] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
            mine = (row >= group_offsets[group]) & (row < group_offsets[group + 1])
            out[...] = jnp.where(mine, acc[...], out[...].astype(jnp.float32)).astype(out.dtype)

    itemsize, out_itemsize = jnp.dtype(lhs.dtype).itemsize, jnp.dtype(preferred_element_type).itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), preferred_element_type),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, pair, k_i, offsets, ids, tiles: (tiles[pair], k_i)),
                pl.BlockSpec((None, tk, tn), lambda n_i, pair, k_i, offsets, ids, tiles: (ids[pair], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, pair, k_i, offsets, ids, tiles: (tiles[pair], n_i)),
            grid=(n // tn, visited, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(tiling, itemsize, out_itemsize),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + k * n * metadata[1].size) * itemsize + m * n * out_itemsize),
        interpret=interpret,
        name="gmm",
    )(*metadata, lhs, rhs)
