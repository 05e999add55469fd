"""Attention: XLA einsum path, Pallas flash path, and ring attention for
context parallelism.

The reference has no in-tree attention/sequence-parallel implementation
(SURVEY.md §5 "Long-context" — absent); here it is first-class. Ring
attention passes KV blocks around the ``context`` mesh axis with
``jax.lax.ppermute`` over ICI while maintaining a numerically-stable online
softmax (flash-attention style m/l accumulators), so sequence length scales
linearly with the number of devices on the axis.

Convention: q/k/v are (batch, seq, heads, head_dim) [BSHD].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """Grouped-query attention: repeat kv heads to match q heads."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    mask: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    use_flash: bool = True,
) -> jax.Array:
    """Multi-head attention. On TPU with supported shapes, dispatches to the
    Pallas flash kernel; otherwise a fused-by-XLA einsum softmax. The choice
    is made from platform and shape alone (``_can_use_flash``): a kernel
    that was chosen and fails raises, it never gives way to the other path
    — a run must be able to tell which one it timed."""
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    # the flash path implements only plain (optionally causal) attention —
    # custom masks / explicit positions must take the einsum path
    if (
        use_flash
        and mask is None
        and q_positions is None
        and kv_positions is None
        and _can_use_flash(q, k)
    ):
        return _flash(q, k, v, causal=causal)
    return _einsum_attention(
        q, k, v, causal=causal, mask=mask, q_positions=q_positions, kv_positions=kv_positions
    )


def _can_use_flash(q, k) -> bool:
    if jax.default_backend() != "tpu":
        return False
    head_dim = q.shape[-1]
    if head_dim % 128 == 0:
        return q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
    # head_dim 64 (e.g. d_model 1024 / 16 heads): the stock block sizes lose
    # to the XLA einsum path, but 512-blocks win (measured ~1.4x on v5e at
    # seq 1k; see _tuned_block_sizes) — require 512-divisible sequences
    if head_dim == 64:
        return q.shape[1] % 512 == 0 and k.shape[1] % 512 == 0
    return False


def _tuned_block_sizes(head_dim: int, q_seq: int, kv_seq: int):
    """Measured on v5e: the library defaults underfill the MXU at both ends
    of the head_dim range. head_dim 64: 512 blocks throughout beat defaults
    and the einsum path (~1.4x at seq 1k). head_dim 256 (GPT-J geometry):
    block_q 512 / block_k 1024 in all passes cuts the 6B-shaped train step
    ~19% vs defaults (957 -> 773 ms, seq 2048, with dots-saveable remat).
    None = library defaults."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    def pick(seq: int, *prefs: int):
        # largest preferred block that tiles the sequence (the kernel
        # requires block | seq); a short sequence is its own block
        for p in prefs:
            if seq % p == 0:
                return p
        return seq if seq <= prefs[0] else None

    if head_dim == 256:
        bq = pick(q_seq, 512, 256)
        bk = pick(kv_seq, 1024, 512, 256)
    elif head_dim == 64:
        bq = pick(q_seq, 512, 256)
        bk = pick(kv_seq, 512, 256)
    else:
        return None
    if bq is None or bk is None:
        return None  # library defaults
    return BlockSizes(
        block_q=bq,
        block_k_major=bk,
        block_k=bk,
        block_b=1,
        block_q_major_dkv=bq,
        block_k_major_dkv=bk,
        block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk,
        block_k_dq=bk,
        block_q_dq=bq,
    )


def _flash(q, k, v, *, causal):
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    # pallas kernel wants BHSD
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    with jax.named_scope("flash"):  # the kernels keep the names they give themselves
        out = flash_attention(
            qt,
            kt,
            vt,
            causal=causal,
            sm_scale=1.0 / (q.shape[-1] ** 0.5),
            block_sizes=_tuned_block_sizes(q.shape[-1], q.shape[1], k.shape[1]),
        )
    return jnp.swapaxes(out, 1, 2)


def _einsum_attention(
    q, k, v, *, causal, mask=None, q_positions=None, kv_positions=None
):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if causal:
        if q_positions is None:
            q_positions = jnp.arange(q.shape[1])
        if kv_positions is None:
            kv_positions = jnp.arange(k.shape[1])
        causal_mask = q_positions[:, None] >= kv_positions[None, :]
        scores = jnp.where(causal_mask[None, None, :, :], scores, _NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# ring attention (context parallelism)
# ---------------------------------------------------------------------------


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = True,
) -> jax.Array:
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    Must be called inside ``shard_map`` (or an equivalent SPMD context) where
    ``q``/``k``/``v`` are the *local* sequence shards, laid out so device i on
    the ring holds tokens [i*S, (i+1)*S). Each step computes one KV block's
    contribution with online-softmax accumulation, then rotates K/V one hop
    around the ring via ``ppermute`` (ICI neighbor transfer); compute and
    transfer overlap under XLA's async collective scheduling.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s, h, d = q.shape
    n_rep = h // k.shape[2]

    scale = 1.0 / (d**0.5)
    q32 = q.astype(jnp.float32) * scale

    q_pos = my_idx * s + jnp.arange(s)

    def step(carry, _):
        o, m, l, k_blk, v_blk, blk_idx = carry
        kv_pos = blk_idx * s + jnp.arange(s)
        kf = _repeat_kv(k_blk, n_rep).astype(jnp.float32)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, kf)
        if causal:
            visible = q_pos[:, None] >= kv_pos[None, :]
            scores = jnp.where(visible[None, None, :, :], scores, _NEG_INF)
        blk_max = jnp.max(scores, axis=-1)  # (b, h, q)
        m_new = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * correction + jnp.sum(p, axis=-1)
        vf = _repeat_kv(v_blk, n_rep).astype(jnp.float32)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
        o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
        # rotate kv to the next device on the ring (device r receives from r-1)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        blk_next = (blk_idx - 1) % axis_size
        return (o_new, m_new, l_new, k_next, v_next, blk_next), None

    o0 = jnp.zeros((b, s, h, d), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    # constants start axis-unvarying under shard_map's vma typing; the carry
    # becomes varying after step 1, so mark them varying up front
    o0, m0, l0 = (jax.lax.pcast(x, (axis_name,), to="varying") for x in (o0, m0, l0))
    (o, m, l, _, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v, my_idx), None, length=axis_size
    )
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def make_context_parallel_attention(mesh, axis_name: str = "context", causal: bool = True):
    """Wrap ``ring_attention`` in shard_map for direct use on global arrays."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # ring attention is manual over the context axis only; other mesh
        # axes (batch/model) stay under GSPMD
        axis_names={axis_name},
    )
    def cp_attention(q, k, v):
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

    return cp_attention
