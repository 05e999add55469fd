"""Kimi-K2's language model (``model_type`` ``kimi_k2``, DeepSeek-V3's layer):
the third model kind ``serve.llm`` runs.

``first_k_dense_replace`` dense layers lead, expert layers follow. ``N`` is
RMSNorm:

    h = x + MLA(N(x))
    y = h + F_l(N(h))      F_l a SwiGLU MLP (l < first_k_dense_replace), else
                           sum_k w_k E_k(u) + S(u): the routed experts chosen by
                           sigmoid scores with a choice bias, and a shared expert

The attention is the one latent attention longcat runs too
(``ops/latent_attention.py:mla``) with this model's rotary (YaRN over the
rotary part of a head) and score scale (``m^2 / sqrt(d_n + d_r)``); the expert
layer is ``models/moe.py`` under its second rule (``route_sigmoid``), this
chip's share of the routed experts; the shared expert is a dense SwiGLU here.
Key names follow the published ``config.json``.

This module gives ``models/paged.py`` a kind's four things, and its layer as
**two sections**: the dense layer's function over layers ``0 ..
first_k_dense_replace``, the expert layer's over the rest. What every layer
has (the attention's seven tensors and the two norms) is stacked over all
``num_hidden_layers`` and read by the layer's index, which is also its
attention's place in the pool; a section's own tensors are stacked over that
section's layers and read from its first layer on: the dense MLP's
(``w_gate``, ``w_up``, ``w_down``: (first_k_dense_replace, ...)), the expert
layers' (``router``, ``router_bias``, ``e_*``, ``s_*``: (num_hidden_layers -
first_k_dense_replace, ...)). The pool is longcat's: one row a position an
attention of ``kv_lora_rank + qk_rope_head_dim`` values, and ``moe_counts``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import moe, paged
from ray_tpu.models.moe import routing_counts  # noqa: F401 - the engine asks the kind's module for it
from ray_tpu.ops.latent_attention import mla, rotate_pairs, yarn_inv_freq, yarn_mscale
from ray_tpu.ops.layers import rms_norm, swiglu

YARN = dict(type="yarn", factor=32, original_max_position_embeddings=4096, beta_fast=1, beta_slow=1, mscale=1,
            mscale_all_dim=1)
# The seeded router: scores ``sigmoid`` of logits of deviation ``ROUTER_SCALE``;
# the choice bias ``BIAS_SCALE`` x normal, which changes a fifth of the tokens'
# chosen sets among 384 experts and leaves every expert about its share of rows.
ROUTER_SCALE = 1.5
BIAS_SCALE = 1.5e-3


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    """Published keys (moonshotai ``config.json`` names) plus this chip's share
    of each expert layer's routed experts: ``experts_held`` of the
    ``n_routed_experts``, from ``expert_offset``. The router keeps all
    ``n_routed_experts`` outputs whatever is held. Of the routing keys the
    program runs what the checkpoint states and refuses the rest."""

    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    routed_scaling_factor: float = 2.827
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    rope_scaling: Dict[str, Any] = dataclasses.field(default_factory=lambda: dict(YARN))
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.n_routed_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.n_routed_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"{self.first_k_dense_replace} dense layers of {self.num_hidden_layers}")
        routing = (self.scoring_func, self.topk_method, self.n_group, self.topk_group, self.norm_topk_prob)
        if routing != ("sigmoid", "noaux_tc", 1, 1, True):
            raise ValueError(f"scoring_func, topk_method, n_group, topk_group, norm_topk_prob = {routing}: the "
                             "program routes by sigmoid scores over one group of experts, renormalised")
        if (self.rope_scaling or {}).get("type") != "yarn":
            raise ValueError(f"rope_scaling {self.rope_scaling!r}: the program rotates under yarn")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    n_expert_layers = property(lambda self: self.num_hidden_layers - self.first_k_dense_replace)
    max_seq_len = property(lambda self: self.max_position_embeddings)

    @property
    def cache_row(self) -> int:
        """Values one position holds in one attention's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_stored(self) -> int:
        """``cache_row`` rounded up to the TPU's 128 lanes (``LongcatConfig``
        says why)."""
        return -(-self.cache_row // 128) * 128

    @property
    def att_scale(self) -> float:
        """What the scores are multiplied by: ``1 / sqrt(d_n + d_r)``, times
        ``m^2`` under YaRN with ``m = 0.1 x mscale_all_dim x ln(factor) + 1``."""
        ys = self.rope_scaling
        m = yarn_mscale(ys["factor"], ys["mscale_all_dim"]) if ys.get("mscale_all_dim", 0) else 1.0
        return m * m * (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def rotary(self):
        """``rotate(x, positions)`` over ``qk_rope_head_dim``: YaRN's
        frequencies are a constant, built where this is called."""
        ys, d = self.rope_scaling, self.qk_rope_head_dim
        ratio = yarn_mscale(ys["factor"], ys.get("mscale", 1)) / yarn_mscale(ys["factor"], ys.get("mscale_all_dim", 0))
        return functools.partial(rotate_pairs, inv_freq=yarn_inv_freq(d, self.rope_theta, **ys), scale=ratio)


def init_params(key, cfg: KimiConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own and
    says why each, ``benchmarks/families/kimi.py``): 1/sqrt(fan-in), the
    embedding 0.02, the dense paths' projections into the residual stream
    (``wo``, ``w_down``, ``s_down``) scaled down by sqrt(2 x layers), norms 1
    but the query latent's (``1 / m^2``: the scores keep unit size under YaRN's
    factor), the router's columns ``ROUTER_SCALE`` / sqrt(D), the choice bias
    ``BIAS_SCALE`` x normal. The module's docstring says what is stacked over
    which layers; ``wqb``, ``wkva`` and ``wkvb`` are kept the way the decode
    step reads them (``ops/latent_attention.py:mla``)."""
    L, K, D, H = cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.hidden_size, cfg.num_attention_heads
    F, Fe, Fs = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.n_shared_experts * cfg.moe_intermediate_size
    rq, rkv, dn, dr, dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
    E, held, n = L - K, cfg.experts_held, cfg.n_routed_experts
    keys = iter(jax.random.split(key, 24))
    s_res = (2 * L) ** -0.5

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    return {
        "embed": normal((cfg.vocab_size, D), 0.02),
        "in_norm": jnp.ones((L, D), jnp.float32),
        "post_norm": jnp.ones((L, D), jnp.float32),
        "wqa": normal((L, D, rq), D ** -0.5),
        "qa_norm": jnp.full((L, rq), (dn + dr) ** -0.5 / cfg.att_scale, jnp.float32),
        "wqb": normal((L, H * (dn + dr), rq), rq ** -0.5),
        "wkva": normal((L, rkv + dr, D), D ** -0.5),
        "kva_norm": jnp.ones((L, rkv), jnp.float32),
        "wkvb": normal((L, H, rkv, dn + dv), rkv ** -0.5),
        "wo": normal((L, H * dv, D), (H * dv) ** -0.5 * s_res),
        "w_gate": normal((K, D, F), D ** -0.5),
        "w_up": normal((K, D, F), D ** -0.5),
        "w_down": normal((K, F, D), F ** -0.5 * s_res),
        "router": normal((E, D, n), D ** -0.5 * ROUTER_SCALE),
        "router_bias": normal((E, n), BIAS_SCALE, jnp.float32),
        "e_gate": normal((E, held, D, Fe), D ** -0.5),
        "e_up": normal((E, held, D, Fe), D ** -0.5),
        "e_down": normal((E, held, Fe, D), Fe ** -0.5),
        "s_gate": normal((E, D, Fs), D ** -0.5),
        "s_up": normal((E, D, Fs), D ** -0.5),
        "s_down": normal((E, Fs, D), Fs ** -0.5 * s_res),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, cfg.vocab_size), D ** -0.5),
    }


def init_paged_pool(cfg: KimiConfig, num_blocks: int, block_size: int) -> Dict:
    """``latent``: (layers, num_blocks, block_size, cache_row_stored) in
    ``cfg.dtype``, block 0 the null block; ``moe_counts``: ``moe.COUNTS``
    summed over the expert layers and decode steps so far, modulo 2**32."""
    shape = (cfg.num_hidden_layers, num_blocks, block_size, cfg.cache_row_stored)
    return {"latent": jnp.zeros(shape, cfg.dtype), "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32)}


def paged_block_bytes(cfg: KimiConfig, block_size: int) -> int:
    """Bytes one block of the pool holds over all layers."""
    return cfg.num_hidden_layers * block_size * cfg.cache_row_stored * jnp.dtype(cfg.dtype).itemsize


def paged_layer(cfg: KimiConfig, params, step):
    """The model's two sections for one call of a paged program: (the dense
    layer, ``first_k_dense_replace``) and (the expert layer, the rest); a
    section of no layers is left out. A decode step's expert layers add their
    routing counts to the pool's."""
    eps, dense_layers = cfg.rms_norm_eps, cfg.first_k_dense_replace
    attend = functools.partial(mla, cfg, rotate=cfg.rotary(), att_scale=cfg.att_scale)

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def attention(x, pool, li):
        """The half every layer has: (h, N(h), the pool's rows)."""
        w = at(li)
        with jax.named_scope("mla"):
            att, rows_pool = attend(w, li, rms_norm(x, w("in_norm"), eps), pool["latent"], step)
        h = x + att
        return h, rms_norm(h, w("post_norm"), eps), rows_pool

    @jax.named_scope("block")
    def dense_layer(x, pool, li):
        shape = x.shape
        h, u, rows_pool = attention(x.reshape(-1, shape[-1]), pool, li)
        own = at(li)
        with jax.named_scope("dense_ffn"):
            y = h + swiglu(u @ own("w_gate"), u @ own("w_up")) @ own("w_down")
        return y.reshape(shape), {**pool, "latent": rows_pool}

    @jax.named_scope("block")
    def expert_layer(x, pool, li):
        shape = x.shape
        h, u, rows_pool = attention(x.reshape(-1, shape[-1]), pool, li)
        own = at(li - dense_layers)
        with jax.named_scope("moe"):
            routed, counts = moe.expert_layer(
                {**params, "router": own("router"), "router_bias": own("router_bias")}, u, layer=li - dense_layers,
                n_routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
                expert_offset=cfg.expert_offset, live=step.live, rule=moe.route_sigmoid)
            with jax.named_scope("shared"):
                shared = swiglu(u @ own("s_gate"), u @ own("s_up")) @ own("s_down")
        y = h + routed + shared
        counts = pool["moe_counts"] + counts if shape[1] == 1 else pool["moe_counts"]
        return y.reshape(shape), {"latent": rows_pool, "moe_counts": counts}

    sections = [(dense_layer, dense_layers), (expert_layer, cfg.num_hidden_layers - dense_layers)]
    return [(layer, n) for layer, n in sections if n]
