"""LongCat-Flash's language model: the second model kind ``serve.llm`` runs.

One layer holds two latent attentions (MLA), two dense SwiGLU MLPs and one
expert layer whose output skips over the second half of the layer (the
shortcut-connected expert layer: in a deployment its exchange overlaps the
dense path). ``N`` is RMSNorm:

    a = x + MLA_0(N(x))          u = N(a)          m = MoE(u)
    b = a + FFN_0(u)
    c = b + MLA_1(N(b))
    y = c + FFN_1(N(c)) + m

The expert layer is ``models/moe.py`` (a router over routed and zero-compute
experts; this chip's share of the routed ones), the attention
``ops/latent_attention.py``. Key names follow the published ``config.json``.

This module gives ``models/paged.py`` the four things it asks of a model kind.
The paged cache is this model's own shape: one row a position a attention of
``kv_lora_rank + qk_rope_head_dim`` values (the latent after its norm and
scale, and the rotated shared key), ``2 x num_layers`` attentions. The pool
also carries ``moe_counts``: what the decode steps' expert layers counted of
their routing, summed on the device (``routing_counts`` copies them out).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.moe import routing_counts  # noqa: F401 - the engine asks the kind's module for it
from ray_tpu.ops.latent_attention import mla, rope_interleaved
from ray_tpu.ops.layers import rms_norm, swiglu


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    """Published keys (meituan-longcat ``config.json`` names) plus this chip's
    share of each layer's routed experts: ``experts_held`` of the
    ``n_routed_experts``, from ``expert_offset``. The router keeps all
    ``n_routed_experts + zero_expert_num`` outputs whatever is held."""

    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.n_routed_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.n_routed_experts}")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_layers)
    n_expert_layers = property(lambda self: self.num_layers)  # every layer has one: what the routing counts sum over
    max_seq_len = property(lambda self: self.max_position_embeddings)

    @property
    def cache_row(self) -> int:
        """Values one position holds in one attention's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_stored(self) -> int:
        """``cache_row`` rounded up to the TPU's 128 lanes. A pool whose rows
        are no multiple of 128 wide gets another device layout than the one
        the programs compute in, and is re-laid out whole, in and out, every
        step (576 values: 1.2 GB moved a step); the padding is never read."""
        return -(-self.cache_row // 128) * 128

    @property
    def scale_q(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def scale_kv(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0


def init_params(key, cfg: LongcatConfig) -> Dict[str, Any]:
    """Seeded weights, stacked over layers (and over a layer's two attentions
    and two MLPs), by the recipe the benchmark's family seeds with
    (``benchmarks/families/longcat.py`` says why each): 1/sqrt(fan-in), the
    embedding 0.02, the dense paths' projections into the residual stream
    scaled down by sqrt(2 x layers), the two latent norms at ``1 / scale_q``
    and ``1 / scale_kv``, the expert layer as ``moe.init_expert_params`` seeds
    it. Three of an attention's matrices are kept the way the decode step
    reads them, so that no step re-lays them out: ``wqb`` (heads x (d_n +
    d_r), r_q) and ``wkva`` (r_kv + d_r, D) with the contraction last,
    ``wkvb`` (heads, r_kv, d_n + d_v) a matrix a head."""
    L, D, F, H = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_attention_heads
    rq, rkv, dn, dr, dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
    keys = iter(jax.random.split(key, 16))
    s_res = (2 * L) ** -0.5

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(cfg.dtype)

    experts = jax.vmap(lambda k: moe.init_expert_params(
        k, D, cfg.expert_ffn_hidden_size, cfg.experts_held, cfg.n_routed_experts + cfg.zero_expert_num,
        cfg.dtype))(jax.random.split(next(keys), L))
    return {
        "embed": normal((cfg.vocab_size, D), 0.02),
        "in_norm": jnp.ones((L, 2, D), jnp.float32),
        "post_norm": jnp.ones((L, 2, D), jnp.float32),
        "wqa": normal((L, 2, D, rq), D ** -0.5),
        "qa_norm": jnp.full((L, 2, rq), 1 / cfg.scale_q, jnp.float32),
        "wqb": normal((L, 2, H * (dn + dr), rq), rq ** -0.5),
        "wkva": normal((L, 2, rkv + dr, D), D ** -0.5),
        "kva_norm": jnp.full((L, 2, rkv), 1 / cfg.scale_kv, jnp.float32),
        "wkvb": normal((L, 2, H, rkv, dn + dv), rkv ** -0.5),
        "wo": normal((L, 2, H * dv, D), (H * dv) ** -0.5 * s_res),
        "w_gate": normal((L, 2, D, F), D ** -0.5),
        "w_up": normal((L, 2, D, F), D ** -0.5),
        "w_down": normal((L, 2, F, D), F ** -0.5 * s_res),
        **experts,
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, cfg.vocab_size), D ** -0.5),
    }


def init_paged_pool(cfg: LongcatConfig, num_blocks: int, block_size: int) -> Dict:
    """``latent``: (attentions, num_blocks, block_size, cache_row_stored) in
    ``cfg.dtype``, block 0 the null block. A block is the unit the decode step
    gathers. ``moe_counts``: ``moe.COUNTS`` summed over the layers and decode
    steps so far, modulo 2**32."""
    shape = (2 * cfg.num_layers, num_blocks, block_size, cfg.cache_row_stored)
    return {"latent": jnp.zeros(shape, cfg.dtype), "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32)}


def paged_block_bytes(cfg: LongcatConfig, block_size: int) -> int:
    """Bytes one block of the pool holds over all attentions."""
    return 2 * cfg.num_layers * block_size * cfg.cache_row_stored * jnp.dtype(cfg.dtype).itemsize


def ffn(w, h):
    return swiglu(h @ w("w_gate"), h @ w("w_up")) @ w("w_down")


def paged_layer(cfg: LongcatConfig, params, step):
    """The layer (the formula at the top) over ``x`` (B, S, D), for one call of
    a paged program. A layer's two attentions and two MLPs are stacked (layers,
    2, ...): a matmul reads its matrix through one dynamic index over the two
    leading axes merged (``models/paged.py`` says why); the experts' tensors stay
    whole, read in place by the grouped matmul. A decode step adds its routing
    counts to the pool's."""
    eps = cfg.rms_norm_eps
    attend = functools.partial(
        mla, cfg, rotate=functools.partial(rope_interleaved, theta=cfg.rope_theta),
        att_scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5, scale_q=cfg.scale_q, scale_kv=cfg.scale_kv)

    @jax.named_scope("block")
    def layer(x, pool, li):
        shape = x.shape
        x = x.reshape(-1, shape[-1])

        def of(which):  # the layer's first (0) or second (1) attention and MLP
            return lambda name: jax.lax.dynamic_index_in_dim(
                params[name].reshape(-1, *params[name].shape[2:]), 2 * li + which, keepdims=False)

        first, second = of(0), of(1)
        rows_pool, counts = pool["latent"], pool["moe_counts"]
        with jax.named_scope("mla0"):
            att, rows_pool = attend(first, 2 * li, rms_norm(x, first("in_norm"), eps), rows_pool, step)
        a = x + att
        u = rms_norm(a, first("post_norm"), eps)
        with jax.named_scope("moe"):
            router = {k: jax.lax.dynamic_index_in_dim(params[k], li, keepdims=False) for k in ("router", "router_bias")}
            m, routed = moe.expert_layer(
                {**params, **router}, u, layer=li, n_routed=cfg.n_routed_experts, top_k=cfg.moe_topk,
                scale=cfg.routed_scaling_factor, expert_offset=cfg.expert_offset, live=step.live)
        with jax.named_scope("ffn0"):
            x = a + ffn(first, u)
        with jax.named_scope("mla1"):
            att, rows_pool = attend(second, 2 * li + 1, rms_norm(x, second("in_norm"), eps), rows_pool, step)
        x = x + att
        with jax.named_scope("ffn1"):
            x = x + ffn(second, rms_norm(x, second("post_norm"), eps)) + m
        return x.reshape(shape), {"latent": rows_pool, "moe_counts": counts + routed if shape[1] == 1 else counts}

    return layer
