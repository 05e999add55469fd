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
    scale: Optional[float] = None,
) -> jax.Array:
    """Multi-head attention. On TPU with supported shapes, dispatches to the
    Pallas flash kernel; otherwise a fused-by-XLA einsum softmax. The choice
    is made from platform and shape alone (``_can_use_flash``): a kernel
    that was chosen and fails raises, it never gives way to the other path
    — a run must be able to tell which one it timed. ``scale``: what the
    scores are multiplied by ahead of the softmax (None: ``head_dim ** -0.5``;
    a model states its own where it is not that: Granite 4.0's
    ``attention_multiplier``)."""
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
        return _flash(q, k, v, causal=causal, scale=scale)
    return _einsum_attention(
        q, k, v, causal=causal, mask=mask, q_positions=q_positions, kv_positions=kv_positions, scale=scale
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
    """The library's forward kernel's blocks where no gradient is taken: 512
    x 512 for head_dim 256 (GPT-J) and 64; None = the library's defaults (128
    throughout), which head_dim 128 keeps (the hybrid's prefill of 128-512
    positions, not measured alone). Measured on a v5e, kernels alone at (8, 16,
    2048, 256) causal, tree of 2026-09-30 (PR 40), ms a call with the
    statistics kept, (block_q, block_k_major, block_k): (512, 512, 512) 4.26,
    (1024, 1024, 512) 4.24, (1024, 1024, 1024) 4.28, (512, 1024, 1024) 4.46
    (what ran until then: 6 of 8 block pairs where 512 x 512 runs 10 of 16),
    (512, 1024, 512) 4.58, (256, 512, 512) 4.99, (512, 256, 256) 4.91, (256,
    256, 256) 5.79; 2048 on either side does not fit VMEM; (512, 1024, 1024)
    without the statistics 3.69. At head_dim 128 (8, 16, 2048, 128): defaults
    13.17, (512, 512, 512) 3.25, (1024, 1024, 512) 3.15. head_dim 64 at 512
    beat the defaults and the einsum path ~1.4x at 1,024 positions (measured
    before the benchmark existed)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    def pick(seq: int):
        # the larger block that tiles the sequence (the kernels require
        # block | seq); a short sequence is its own block
        for p in (512, 256):
            if seq % p == 0:
                return p
        return seq if seq <= 512 else None

    bq, bk = pick(q_seq), pick(kv_seq)
    if head_dim not in (64, 256) or bq is None or bk is None:
        return None  # library defaults
    return BlockSizes(block_q=bq, block_k_major=bk, block_k=bk, block_b=1)


# the one name the forward rule gives what it keeps for the backward pass: a
# ``jax.checkpoint`` policy that saves it (``transformer.forward``'s "dots")
# does not run the forward kernel again when it recomputes a block
FLASH_RESIDUALS = "flash_residuals"


def _flash(q, k, v, *, causal, scale=None):
    with jax.named_scope("flash"):  # the kernels keep the names they give themselves
        return _flash_bshd(q, k, v, causal, scale)


def _sm_scale(q, scale):
    """The softmax's scale: the caller's, or ``head_dim ** -0.5``."""
    return q.shape[-1] ** -0.5 if scale is None else scale


def _heads_major(*xs):
    """(batch, seq, heads, head_dim) <-> (batch, heads, seq, head_dim), the
    layout the Pallas kernels take."""
    return tuple(jnp.swapaxes(x, 1, 2) for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bshd(q, k, v, causal, scale=None):
    """Flash attention over (batch, seq, heads, head_dim). Without a gradient
    it is the library's forward kernel and keeps nothing."""
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    q, k, v = _heads_major(q, k, v)
    (out,) = _heads_major(flash_attention(
        q, k, v, causal=causal, sm_scale=_sm_scale(q, scale),
        block_sizes=_tuned_block_sizes(q.shape[3], q.shape[2], k.shape[2]),
    ))
    return out


def _training_blocks(q, k):
    """(block_q, block_k) of the repo's own kernels: 512 where it tiles the
    sequence, else 256 or 128. Measured as ``_tuned_block_sizes``' (same
    shapes, day and chip), ms a call. head_dim 256, forward: (512, 512) 3.42,
    (256, 512) 3.49, (512, 256) 3.50, (256, 256) 3.70, (1024, 512) 3.81, (512,
    1024) 3.82, (1024, 1024) 3.92 (the library's best 4.24); backward with
    ``di``'s sum: (256, 256) 6.37, (512, 512) 6.41, (512, 256) 6.51, (256, 512)
    6.52, (1024, 1024) 7.15, (512, 1024) 7.21, (1024, 512) 7.27, (128, 512)
    8.07, (2048, 512) 8.85 (the library's two passes 12.99 at (512, 1024),
    12.59 at (512, 512)). head_dim 128, forward: (512, 512) 2.47, (256, 512)
    2.50, (1024, 512) 2.56, (512, 256) 2.95, (256, 256) 3.51; backward: (512,
    512) 3.81, (1024, 512) 4.26, (512, 256) 4.46, (256, 512) 4.62, (256, 256)
    5.62, (128, 128) 9.08 (the library's 8.83 at 512, 29.11 at its defaults)."""
    return tuple(next(b for b in (512, 256, 128) if x.shape[2] % b == 0) for x in (q, k))


def _flash_fwd(q, k, v, causal, scale):
    """Under a gradient: the forward kernel that keeps the softmax's
    log-sum-exp, (B, H, S) float32, beside its output. Both are named for a
    ``jax.checkpoint`` policy to keep."""
    from jax.ad_checkpoint import checkpoint_name

    from ray_tpu.ops import flash_kernels

    qt, kt, vt = _heads_major(q, k, v)
    block_q, block_k = _training_blocks(qt, kt)
    o, lse = flash_kernels.flash_attention_fwd(
        qt, kt, vt, causal=causal, sm_scale=_sm_scale(q, scale), block_q=block_q, block_k=block_k
    )
    out, lse = checkpoint_name((*_heads_major(o), lse), FLASH_RESIDUALS)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, residuals, dout):
    from ray_tpu.ops import flash_kernels

    q, k, v, out, lse = residuals
    di = jnp.swapaxes(jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1), 1, 2)  # (B, H, S)
    q, k, v, do = _heads_major(q, k, v, dout)
    block_q, block_k = _training_blocks(q, k)
    return _heads_major(*flash_kernels.flash_attention_bwd(
        q, k, v, do, lse, di, causal=causal, sm_scale=_sm_scale(q, scale), block_q=block_q, block_k=block_k
    ))


_flash_bshd.defvjp(_flash_fwd, _flash_bwd)


def _einsum_attention(
    q, k, v, *, causal, mask=None, q_positions=None, kv_positions=None, scale=None
):
    if scale is None:
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
