"""Training's flash attention as two Pallas TPU kernels of the repo's own:
the forward pass that keeps the softmax's log-sum-exp, and the backward pass in
one kernel: a pair of blocks builds its scores and their gradient once and
gives dQ, dK and dV from them, five matmuls where the library's two passes
(``flash_mha_bwd_dkv``, ``flash_mha_bwd_dq``) spend seven.

In both, one grid step is one (batch, head): its q, k, v (and dO) stay in VMEM
whole (1 MB each at 2,048 positions of 256), so each is read once, and the
walk over block pairs is the kernel's own loop, from or to the diagonal: no
step is spent on a pair the causal mask empties, and only the pairs the
diagonal crosses build the mask.

The backward pass, for a block of keys, walks the blocks of queries from the
diagonal on. dK and dV of the key block accumulate in float32 scratch and
leave when its queries are done; dQ accumulates in float32 scratch over the
key blocks, in place, and leaves once at the end. The pair is worked
transposed, keys down the rows and queries along the lanes: the softmax's
statistics of a query (its log-sum-exp, and ``di`` = rowsum(O * dO)) are then
rows of lanes that broadcast down the sublanes for nothing, they come in as
(..., blocks, block_q) arrays of 8 KB a head where the library's kernels take
them broadcast to 128 lanes (134 MB each a layer, and 1 GB of ``di`` in its dq
pass), and dV = P^T dO and dK = dS^T Q are plain products; only dQ = dS K
contracts over the rows.

The forward pass, for a block of queries, walks the blocks of keys up to the
diagonal under an online softmax whose sums divide the output once, at the
end; the statistics' column is turned into that row of lanes as it leaves.

Arithmetic as the library's: scores and sums in float32, P and dS rounded to
the inputs' type before their products, float32 accumulators. The kernels'
names hold ``flash_attention`` and ``flash_mha``: the benchmark's
``flash_attn_roofline`` finds them by those.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_VMEM_BUDGET = 96 << 20  # of a v5e core's 128 MiB
_MASKED = -1e30  # a score the causal mask hides; finite, so that exp(m - m) of a row not yet begun is 1


def _vmem_bytes(q_seq: int, kv_seq: int, head_dim: int, block_q: int, block_k: int, itemsize: int) -> int:
    """What a grid step of the backward kernel holds (the forward holds less):
    q, dO, dQ and k, v, dK, dV whole, twice (the pipeline's two buffers), the
    three float32 accumulators, and a handful of (block_k, block_q) float32
    intermediates."""
    rows = 3 * q_seq + 4 * kv_seq
    return (
        2 * rows * head_dim * itemsize
        + (q_seq + 2 * block_k) * head_dim * 4
        + 8 * block_q * block_k * 4
    )


def _per_head(kernel, q, k, block_q: int, block_k: int, **params):
    """``(pallas_call, spec)`` for ``kernel`` over a grid of (batch, head):
    ``spec(rows, width)`` is a head's whole (rows, width) array a grid step."""
    b, h, q_seq, d = q.shape
    kv_seq = k.shape[2]
    if q_seq % block_q or kv_seq % block_k:
        raise ValueError(f"blocks ({block_q}, {block_k}) must divide ({q_seq}, {kv_seq})")
    need = _vmem_bytes(q_seq, kv_seq, d, block_q, block_k, q.dtype.itemsize)
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"a head of {q_seq} x {kv_seq} positions of {d} needs {need >> 20} MiB of VMEM whole, over the "
            f"kernels' {_VMEM_BUDGET >> 20}: split the sequence over a context axis (ring_attention)"
        )
    call = functools.partial(
        pl.pallas_call,
        functools.partial(kernel, block_q=block_q, block_k=block_k, **params),
        grid=(b, h),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(_VMEM_BUDGET, max(32 << 20, need + (need >> 1))),
        ),
    )
    return call, lambda rows, width: pl.BlockSpec((1, 1, rows, width), lambda bi, hi: (bi, hi, 0, 0))


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,  # inputs
    dq_ref, dk_ref, dv_ref,  # outputs
    dq_acc, dk_acc, dv_acc,  # float32 scratch
    *, causal: bool, sm_scale: float, block_q: int, block_k: int,
):
    q_seq, kv_seq = q_ref.shape[2], k_ref.shape[2]
    n_q, n_k = q_seq // block_q, kv_seq // block_k
    dtype = q_ref.dtype
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def kv_block(j, _):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, 0, rows, :]
        v = v_ref[0, 0, rows, :]
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

        def pair(i, _, *, masked):
            cols = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            q = q_ref[0, 0, cols, :]
            do = do_ref[0, 0, cols, :]
            lse = lse_ref[0, 0, pl.ds(i, 1), :]  # (1, block_q)
            di = di_ref[0, 0, pl.ds(i, 1), :]
            s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
            p = jnp.exp(s * sm_scale - lse)  # (block_k, block_q)
            if masked:
                key = j * block_k + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
                query = i * block_q + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
                p = jnp.where(key <= query, p, 0.0)
            dv_acc[...] += jnp.dot(p.astype(dtype), do, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
            ds = ((dp - di) * p * sm_scale).astype(dtype)
            dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq_acc[cols, :] += jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

        if causal:
            # query blocks with a position at or past this key block's first,
            # and of those the ones wholly past its last (no mask to apply)
            first = (j * block_k) // block_q
            first_whole = jnp.minimum(((j + 1) * block_k - 1 + block_q - 1) // block_q, n_q)
            jax.lax.fori_loop(first, first_whole, functools.partial(pair, masked=True), None)
            jax.lax.fori_loop(first_whole, n_q, functools.partial(pair, masked=False), None)
        else:
            jax.lax.fori_loop(0, n_q, functools.partial(pair, masked=False), None)
        dk_ref[0, 0, rows, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, rows, :] = dv_acc[...].astype(dv_ref.dtype)

    jax.lax.fori_loop(0, n_k, kv_block, None)
    dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd(
    q, k, v, do, lse, di, *, causal: bool, sm_scale: float, block_q: int, block_k: int,
    interpret: bool = False,
):
    """dQ, dK, dV of ``softmax(sm_scale * q k^T [causal]) v`` under the
    cotangent ``do``. q, do (B, H, Sq, D) and k, v (B, H, Sk, D); ``lse`` the
    forward's log-sum-exp of the scaled scores and ``di`` = rowsum(o * do),
    (B, H, Sq) float32 each. Blocks divide the sequences."""
    b, h, q_seq, d = q.shape
    kv_seq = k.shape[2]
    call, spec = _per_head(_bwd_kernel, q, k, block_q, block_k, causal=causal, sm_scale=sm_scale)
    # a query block's statistics as one row of lanes: (B, H, blocks, block_q)
    lse, di = (x.astype(jnp.float32).reshape(b, h, q_seq // block_q, block_q) for x in (lse, di))
    qo, kv, stat = spec(q_seq, d), spec(kv_seq, d), spec(q_seq // block_q, block_q)
    pairs = b * h * q_seq * kv_seq // (2 if causal else 1)
    return call(
        in_specs=[qo, kv, kv, qo, stat, stat],
        out_specs=[qo, kv, kv],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((q_seq, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(  # five products of 2 * D a pair of positions
            flops=10 * pairs * d,
            transcendentals=pairs,
            bytes_accessed=(4 * q.size + 4 * k.size) * q.dtype.itemsize + 2 * lse.size * 4,
        ),
        name="flash_mha_bwd",
        interpret=interpret,
    )(q, k, v, do, lse, di)


def _lanes(x, width: int):
    """A (rows, 128) array of row statistics, one value a row in every lane,
    as wide as ``width`` lanes."""
    return jnp.tile(x, (1, width // 128)) if width % 128 == 0 else x[:, :width]


def _fwd_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref, lse_ref,  # outputs
    acc, m_acc, l_acc,  # float32 scratch
    *, causal: bool, sm_scale: float, block_q: int, block_k: int,
):
    q_seq, kv_seq, head_dim = q_ref.shape[2], k_ref.shape[2], q_ref.shape[3]
    n_q, n_k = q_seq // block_q, kv_seq // block_k

    def q_block(i, _):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, 0, rows, :]
        acc[...] = jnp.zeros_like(acc)
        m_acc[...] = jnp.full_like(m_acc, _MASKED)
        l_acc[...] = jnp.zeros_like(l_acc)

        def pair(j, _, *, masked):
            cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            k = k_ref[0, 0, cols, :]
            v = v_ref[0, 0, cols, :]
            s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * sm_scale
            if masked:
                query = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                key = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(key <= query, s, _MASKED)
            m_prev = m_acc[...]  # (block_q, 128)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_next, block_k))
            alpha = jnp.exp(m_prev - m_next)
            l_acc[...] = alpha * l_acc[...] + jnp.sum(p, axis=1, keepdims=True)
            m_acc[...] = m_next
            acc[...] = acc[...] * _lanes(alpha, head_dim) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )

        if causal:
            # key blocks wholly at or before this query block's first position
            # (no mask to apply), then those that reach its last
            whole = jnp.minimum((i * block_q + 1) // block_k, n_k)
            reach = jnp.minimum(((i + 1) * block_q - 1) // block_k + 1, n_k)
            jax.lax.fori_loop(0, whole, functools.partial(pair, masked=False), None)
            jax.lax.fori_loop(whole, reach, functools.partial(pair, masked=True), None)
        else:
            jax.lax.fori_loop(0, n_k, functools.partial(pair, masked=False), None)
        l = l_acc[...]
        o_ref[0, 0, rows, :] = (acc[...] / _lanes(l, head_dim)).astype(o_ref.dtype)
        # a row of lanes a query block: the statistics' column, turned
        lse_ref[0, 0, pl.ds(i, 1), :] = (m_acc[...] + jnp.log(l)).T[:1]

    jax.lax.fori_loop(0, n_q, q_block, None)


def flash_attention_fwd(q, k, v, *, causal: bool, sm_scale: float, block_q: int, block_k: int, interpret: bool = False):
    """``softmax(sm_scale * q k^T [causal]) v`` and the log-sum-exp of its
    scaled scores, (B, H, Sq) float32: what ``flash_attention_bwd`` reads."""
    b, h, q_seq, d = q.shape
    kv_seq = k.shape[2]
    call, spec = _per_head(_fwd_kernel, q, k, block_q, block_k, causal=causal, sm_scale=sm_scale)
    pairs = b * h * q_seq * kv_seq // (2 if causal else 1)
    o, lse = call(
        in_specs=[spec(q_seq, d), spec(kv_seq, d), spec(kv_seq, d)],
        out_specs=[spec(q_seq, d), spec(q_seq // block_q, block_q)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, q_seq // block_q, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * d,
            transcendentals=pairs,
            bytes_accessed=(2 * q.size + 2 * k.size) * q.dtype.itemsize + b * h * q_seq * 4,
        ),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return o, lse.reshape(b, h, q_seq)
