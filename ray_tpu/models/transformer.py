"""Decoder-only transformer LM, TPU-first.

Design choices for the MXU/XLA (SURVEY.md §7, BASELINE.md north-star GPT-J):

* params are a flat dict of stacked per-layer arrays scanned with
  ``jax.lax.scan`` — one compiled block body regardless of depth (one
  device's stack of at most ``UNROLLED_LAYERS`` is laid out whole: see there);
* every parameter has a logical-axes tuple (``param_logical_axes``) consumed
  by ``ray_tpu.parallel.sharding`` so DP/FSDP/TP/CP are pure annotation
  changes;
* bfloat16 activations/weights with fp32 norm/softmax accumulation;
* GPT-J-style *parallel* attention+MLP block (``parallel_block=True``) or
  Llama-style sequential block; RoPE positions are explicit so context
  parallelism can feed absolute positions per shard;
* attention dispatches to the Pallas flash kernel on TPU, or ring attention
  when a ``context`` axis is active (``context_axis`` argument).

Config presets cover the benchmark models named in BASELINE.json: GPT-J-6B
(fine-tune target) and Llama-2-7B (serve target), plus tiny variants for CI.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import FLASH_RESIDUALS, attention, ring_attention
from ray_tpu.ops.layers import apply_rope, gelu, rms_norm, rope_frequencies, swiglu


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None  # None = MHA
    d_ff: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    parallel_block: bool = False  # True = GPT-J style
    use_swiglu: bool = True  # False = gelu MLP (GPT-J)
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True  # jax.checkpoint each block (HBM <-> FLOPs trade)
    # None = full recompute; "dots" saves matmul outputs so the backward pass
    # re-runs only cheap elementwise work (~6N total FLOPs instead of ~8N) at
    # the cost of keeping per-layer projection outputs in HBM
    remat_policy: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def num_params(self) -> int:
        p = self.vocab_size * self.d_model  # embed
        if not self.tie_embeddings:
            p += self.vocab_size * self.d_model
        per_layer = (
            self.d_model * self.n_heads * self.head_dim  # wq
            + 2 * self.d_model * self.kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * self.d_model  # wo
            + (3 if self.use_swiglu else 2) * self.d_model * self.d_ff
            + 2 * self.d_model  # norms
        )
        return p + self.n_layers * per_layer + self.d_model


# -- presets (shapes match the public model cards; cited for parity with
# BASELINE.json configs, not copied code) -----------------------------------

GPTJ_6B = TransformerConfig(
    vocab_size=50400,
    d_model=4096,
    n_layers=28,
    n_heads=16,
    d_ff=16384,
    max_seq_len=2048,
    parallel_block=True,
    use_swiglu=False,
    tie_embeddings=False,
)

LLAMA2_7B = TransformerConfig(
    vocab_size=32000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    d_ff=11008,
    max_seq_len=4096,
)

TINY = TransformerConfig(
    vocab_size=256,
    d_model=128,
    n_layers=2,
    n_heads=4,
    d_ff=512,
    max_seq_len=128,
    remat=False,
)


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict[str, jax.Array]:
    """Stacked-layer parameter dict."""
    keys = jax.random.split(key, 10)
    L, D, H, KV, Hd, F = (
        cfg.n_layers,
        cfg.d_model,
        cfg.n_heads,
        cfg.kv_heads,
        cfg.head_dim,
        cfg.d_ff,
    )
    dt = cfg.dtype
    s_in = 1.0 / math.sqrt(D)
    s_ff = 1.0 / math.sqrt(F)
    params = {
        "embed": _init(keys[0], (cfg.vocab_size, D), 0.02, dt),
        "wq": _init(keys[1], (L, D, H, Hd), s_in, dt),
        "wk": _init(keys[2], (L, D, KV, Hd), s_in, dt),
        "wv": _init(keys[3], (L, D, KV, Hd), s_in, dt),
        "wo": _init(keys[4], (L, H, Hd, D), s_in / math.sqrt(2 * L), dt),
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_up": _init(keys[5], (L, D, F), s_in, dt),
        "w_down": _init(keys[6], (L, F, D), s_ff / math.sqrt(2 * L), dt),
        "final_norm": jnp.ones((D,), jnp.float32),
    }
    if cfg.use_swiglu:
        params["w_gate"] = _init(keys[7], (L, D, F), s_in, dt)
    if not cfg.tie_embeddings:
        params["unembed"] = _init(keys[8], (D, cfg.vocab_size), s_in, dt)
    return params


def param_logical_axes(cfg: TransformerConfig) -> Dict[str, Tuple]:
    """Logical sharding axes per parameter (see parallel/sharding.py rules)."""
    axes = {
        "embed": ("vocab", "embed"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "attn_norm": ("layers", "norm"),
        "mlp_norm": ("layers", "norm"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
        "final_norm": ("norm",),
    }
    if cfg.use_swiglu:
        axes["w_gate"] = ("layers", "embed", "mlp")
    if not cfg.tie_embeddings:
        axes["unembed"] = ("embed", "vocab")
    return axes


@jax.named_scope("block")
def _block(cfg: TransformerConfig, x, layer, cos, sin, positions, attend, cache=None):
    """One transformer block, the only statement of the GPT-J/Llama layer.
    x: (B, S, D). What differs between training, the dense cache and the paged
    pool is ``attend(q, k, v, cache) -> (attention (B, S, H, Hd), cache)``
    over the rotated q and k: how this step's keys and values meet the ones
    before them, and where they are kept. Returns (x, cache). The named
    scopes (``block``, ``block/attn``, ``block/mlp``; ``attend`` names its
    own) label the block's device operations in a profiler trace, forward and
    backward; they change no value."""
    with jax.named_scope("attn"):
        h = rms_norm(x, layer["attn_norm"])
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"])
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    att, cache = attend(q, k, v, cache)
    with jax.named_scope("attn"):
        att_out = jnp.einsum("bshk,hkd->bsd", att, layer["wo"])

    if cfg.parallel_block:
        # GPT-J: MLP reads the same normed input; both branches add to residual
        m = h
    else:
        x = x + att_out
        m = rms_norm(x, layer["mlp_norm"])
    with jax.named_scope("mlp"):
        if cfg.use_swiglu:
            ff = swiglu(
                jnp.einsum("bsd,df->bsf", m, layer["w_gate"]),
                jnp.einsum("bsd,df->bsf", m, layer["w_up"]),
            )
        else:
            ff = gelu(jnp.einsum("bsd,df->bsf", m, layer["w_up"]))
        mlp_out = jnp.einsum("bsf,fd->bsd", ff, layer["w_down"])
    if cfg.parallel_block:
        return x + att_out + mlp_out, cache
    return x + mlp_out, cache


@jax.named_scope("attn")
def _attend_sequence(q, k, v, cache, *, context_axis, mesh, attn_spec):
    """Training's ``attend``: causal attention of the sequence over itself;
    nothing is kept."""
    if context_axis is not None:
        # partial-manual shard_map: only the context axis goes manual (ring
        # ppermute over ICI); batch/model axes stay under GSPMD
        from jax.sharding import PartitionSpec as P

        spec = P(None, context_axis, None, None)
        att = jax.shard_map(
            functools.partial(ring_attention, axis_name=context_axis, causal=True),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            axis_names={context_axis},
        )(q, k, v)
    elif attn_spec is not None:
        # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
        # shard_map"): attention runs per shard — batch and heads are split,
        # sequence and head_dim whole, so no term crosses a shard
        att = jax.shard_map(
            functools.partial(attention, causal=True),
            mesh=mesh,
            in_specs=(attn_spec, attn_spec, attn_spec),
            out_specs=attn_spec,
            check_vma=False,
        )(q, k, v)
    else:
        att = attention(q, k, v, causal=True)
    return att, cache


def _recomputed(body, remat_policy):
    """``body`` under ``jax.checkpoint``. "dots" keeps the matmuls' results
    and what the flash kernel's forward rule names for its backward pass (a
    kernel is no dot: unnamed, the backward pass would run it again); anything
    else keeps nothing."""
    if remat_policy != "dots":
        return jax.checkpoint(body)
    policies = jax.checkpoint_policies
    return jax.checkpoint(
        body,
        policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(FLASH_RESIDUALS),
        ),
    )


@jax.custom_vjp
def _gradients_together(x, w):
    """(x, w) as they are. Their gradients leave the backward pass together:
    the head's weight gradient is then made before the layers' backward scan
    starts, and the logits it reads (1.65 GB at 8 x 2048 x 50432) are not held
    through the scan. Left to itself the compiler put that product after the
    scan, ran out of room for the logits and made them again (37 ms of a 750
    ms step on a v5e; PERF.md, PR 40)."""
    return x, w


_gradients_together.defvjp(
    lambda x, w: ((x, w), None),
    lambda _, grads: jax.lax.optimization_barrier(grads),
)


# The deepest stack whose loop over the layers is laid out whole in the
# program. Rolled, the forward loop stacks what each layer keeps for the
# backward pass and the backward loop takes layer ``i``'s slice at a traced
# index, which the compiler cannot hand to a Pallas call, nor to some fusions,
# in place: it copies the slice out first, and a product that writes its kept
# result into a stack at a traced index runs slower than on a buffer of its
# own (at GPT-J's widths and 8 x 2048 on a v5e: 7.8 ms of copies and 5.5 ms of
# slower products a layer; PERF.md, PR 62). Laid out whole, every layer's kept
# tensors are buffers of their own, read where they were written. The price is
# the compiler's time and the program's size, which grow with the depth (~4 s
# a layer at those widths) where a rolled loop's one body does not: a deep
# model keeps the rolled loop. So does a step that spans a mesh: laid out
# whole, the fsdp 2 x tensor 2 step of four and of eight such layers read
# 0.33 and 0.59 s on four chips where the rolled one reads 0.22 and 0.39
# (every layer's collectives scheduled as one program; CHANGES.md, PR 62).
UNROLLED_LAYERS = 8


def forward(
    params: Dict[str, jax.Array],
    tokens: jax.Array,
    cfg: TransformerConfig,
    *,
    positions: Optional[jax.Array] = None,
    context_axis: Optional[str] = None,
    mesh=None,
    attn_spec=None,
) -> jax.Array:
    """tokens (B, S) -> logits (B, S, vocab). With ``context_axis`` (+``mesh``)
    attention runs as a ring over that axis; ``positions`` must then be the
    absolute token positions of this shard's slice of the sequence. With
    ``attn_spec`` (+``mesh``), the (B, S, H, Hd) PartitionSpec of a
    multi-device mesh, plain attention runs per shard under ``shard_map``."""
    x = params["embed"][tokens]
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    stacked = {
        k: v for k, v in params.items() if k not in ("embed", "unembed", "final_norm")
    }

    attend = functools.partial(_attend_sequence, context_axis=context_axis, mesh=mesh, attn_spec=attn_spec)

    def body(x, layer):
        return _block(cfg, x, layer, cos, sin, positions, attend)

    if cfg.remat:
        body = _recomputed(body, cfg.remat_policy)
    one_device = mesh is None or mesh.size == 1
    x, _ = jax.lax.scan(body, x, stacked, unroll=one_device and cfg.n_layers <= UNROLLED_LAYERS)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"])
        unembed = params.get("unembed")
        if unembed is None:
            unembed = params["embed"].T
        x, unembed = _gradients_together(x, unembed)
        return jnp.einsum("bsd,dv->bsv", x, unembed)


def loss_fn(
    params,
    tokens: jax.Array,
    targets: jax.Array,
    cfg: TransformerConfig,
    *,
    positions=None,
    context_axis=None,
    mesh=None,
    attn_spec=None,
    loss_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Mean next-token cross-entropy in fp32."""
    logits = forward(
        params,
        tokens,
        cfg,
        positions=positions,
        context_axis=context_axis,
        mesh=mesh,
        attn_spec=attn_spec,
    ).astype(jnp.float32)
    with jax.named_scope("loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        # the target's logit through a comparison: a gather's transpose would
        # scatter into a float32 tensor the size of the logits (3.3 GB at
        # 8 x 2048 x 50432), which no fusion takes in
        at_target = jnp.arange(logits.shape[-1]) == targets[..., None]
        gold = jnp.sum(jnp.where(at_target, logits, 0.0), axis=-1)
        nll = logz - gold
        if loss_mask is not None:
            return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)
        return jnp.mean(nll)
