"""Granite-4.0-H-Small's language model (``model_type`` ``granitemoehybrid``;
"Granite 4.0-H Small 32B-A9B" is the published size the defaults carry): the
ninth model kind ``serve.llm`` runs. A **serial** hybrid: layer ``i`` of a
period of ten is grouped-query attention **without positions** where ``i % 10
== 5`` and a Mamba-2 mixer otherwise, and **every** layer's second half is an
expert layer beside a shared expert. ``N`` is RMSNorm; ``m_e``
(``embedding_multiplier``), ``m_r`` (``residual_multiplier``), ``m_a``
(``attention_multiplier``) and ``m_l`` (``logits_scaling``) are the config's:

    x_0 = m_e E[token]
    h   = x + m_r Mix_i(N_in(x))           Mix_i = Attn where i % 10 == 5, else SSM
    y   = h + m_r ( sum_k w_k E_k(v) + S(v) ),   v = N_post(h)
    logits = E^T N_final(x_L) / m_l        (tied)

    Attn(u): q = W_q u as H heads of d = D / H; k = W_k u, v = W_v u as G heads;
             softmax(m_a q_h . k_{h // (H/G)}) in float32 over positions 0 .. t
             (no rotary, no other position signal: ``position_embedding_type``
             ``nope``); W_o
    SSM(u):  ``models/mamba2.py``'s mixer (which ``models/falcon_h1.py`` runs at
             other numbers), no multiplier inside it
    Router:  l = W_r v (float32); the ``num_experts_per_tok`` largest chosen;
             w = softmax over the chosen logits (``moe.route_topk_softmax``)
    E_k, S:  SwiGLU of width ``intermediate_size`` (an expert's) and
             ``shared_intermediate_size``; the published ``input_linear`` is
             [gate | up] fused, here two matrices

No bias but the short convolution's. The expert layer is ``models/moe.py`` with
this chip's share of the routed experts (``experts_held`` from
``expert_offset``: at the published widths no whole period fits a chip beside
all 72); the shared expert is a dense SwiGLU here. Key names follow the
published ``config.json``.

**Where the multipliers are applied.** ``m_e`` on the residual stream as layer 0
finds it (the lookup is ``models/paged.py``'s); ``m_r`` on both branches'
outputs in float32 ahead of the sum's rounding; ``m_a`` is the softmax's scale
(``ops/attention.py:attention(scale=)``, ``paged_decode_attention(scale=)``);
``1 / m_l`` on the final norm's weight (``cfg.final_norm``, which ``paged.head``
asks for), ahead of the head's matrix, which is the embedding's transpose.

This module gives ``models/paged.py`` a kind's things and its layers as
**sections of whole periods** (``models/exaone_moe.py`` says why no
``lax.cond``): the whole periods as one section whose body is ten layers a
call, each layer's mixer chosen where the program is traced, and a rest. What
every layer has (the two norms, the router, the experts, the shared expert) is
stacked over all layers; the mixers' tensors over the Mamba layers, attention's
over the attention layers, each read by the layer's index among its kind.

**The pool holds two kinds of cache** behind one block table, and the routing
counts:

* ``kv`` (attention layers, 2, slots x G, d): the attention layers' rows a
  position, keys in plane 0 and values in plane 1, each plane flat
  (``models/flat_kv.py``). Attention layer ``i`` is the pool's
  layer ``i // 10``; a block holds those layers' rows alone.
* ``state``, ``conv``, ``state_pos`` (Mamba layers, state rows, ..): a
  sequence's recurrent state, its convolution's window and the positions it has
  consumed, in the sequence's state row (``models/mamba2.py``). Mamba layer
  ``i`` is their layer ``i - (i + 4) // 10``.
* ``moe_counts``: ``moe.COUNTS`` summed over the layers and decode steps.

A prefill starts from an empty state: no chunked prefill, no prefix reuse.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import flat_kv, mamba2, moe, paged
from ray_tpu.models.moe import routing_counts  # noqa: F401 - the engine asks the kind's module for it
from ray_tpu.ops.layers import rms_norm, swiglu

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
ROUTER_SCALE = 1.5


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Published keys (ibm-granite ``config.json`` names) plus this chip's
    share of each layer's routed experts: ``experts_held`` of the
    ``num_local_experts``, from ``expert_offset`` (all of them where none is
    named). Of the keys that choose a path the program runs what the
    checkpoint states and refuses the rest."""

    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768  # one routed expert's width
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    layer_types: Optional[Tuple[str, ...]] = None  # None: the period, ``num_hidden_layers`` long
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    normalization_function: str = "rmsnorm"
    hidden_act: str = "silu"
    position_embedding_type: str = "nope"
    rope_theta: float = 10000.0  # published, and read by nothing: no layer has a rotary
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    tie_word_embeddings: bool = True
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    mamba_d_state: int = 128
    mamba_d_head: int = 64
    mamba_n_heads: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.num_local_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        period = tuple(PERIOD[i % len(PERIOD)] for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", period if self.layer_types is None else tuple(self.layer_types))
        if self.layer_types != period:
            raise ValueError(f"layer_types {self.layer_types}: the program runs the period {PERIOD} from layer 0 on")
        if self.position_embedding_type != "nope" or self.rope_scaling is not None:
            raise ValueError(f"position_embedding_type {self.position_embedding_type!r}, rope_scaling {self.rope_scaling}: "
                             "the program's attention has no position signal")
        if self.attention_bias or self.mamba_proj_bias or not self.mamba_conv_bias or not self.tie_word_embeddings:
            raise ValueError("the program runs one bias, the short convolution's (attention_bias and mamba_proj_bias false, "
                             "mamba_conv_bias true), and a head tied to the embedding")
        if self.normalization_function != "rmsnorm" or self.hidden_act != "silu":
            raise ValueError(f"normalization_function {self.normalization_function!r}, hidden_act {self.hidden_act!r}: "
                             "the program runs RMSNorm and SiLU")
        if self.hidden_size % self.num_attention_heads or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the program runs whole groups of query heads a K/V head")
        if (self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size
                or self.mamba_n_heads % self.mamba_n_groups):
            raise ValueError(f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not mamba_expand x hidden_size "
                             f"{self.mamba_expand * self.hidden_size}, or not whole groups of {self.mamba_n_groups}")
        if not 0 <= self.expert_offset <= self.num_local_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.num_local_experts}")
        if not 0 < self.num_experts_per_tok <= self.num_local_experts:
            raise ValueError(f"{self.num_experts_per_tok} experts a token of {self.num_local_experts}: every layer of "
                             "the program routes")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    n_expert_layers = property(lambda self: self.num_hidden_layers)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    n_attention = property(lambda self: self.layer_types.count("attention"))
    n_mamba = property(lambda self: self.layer_types.count("mamba"))
    kv_row = property(lambda self: self.num_key_value_heads * self.head_dim)  # values of one position's K (or V)
    mamba = property(lambda self: mamba2.Mamba2(
        self.mamba_expand * self.hidden_size, self.mamba_d_state, self.mamba_d_head, self.mamba_n_heads,
        self.mamba_n_groups, self.mamba_d_conv, self.rms_norm_eps, self.dtype))

    def final_norm(self, params, x):
        """The model's last norm with ``1 / logits_scaling`` on its output, in
        float32 ahead of the rounding: ``paged.head``'s."""
        return rms_norm(x, params["final_norm"].astype(jnp.float32) / self.logits_scaling, self.rms_norm_eps)


def is_attention(li: int) -> bool:
    """Whether layer ``li`` is an attention layer."""
    return PERIOD[li % len(PERIOD)] == "attention"


def init_params(key, cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own and
    says why each, ``benchmarks/families/granite_hybrid.py``): 1/sqrt(fan-in),
    the embedding 1 / ``embedding_multiplier``, norms 1, the convolution's bias
    0, ``A`` log-uniform in 1-16 and the step ``dt`` log-uniform in 0.001-0.1 a
    head (the published layer's ranges), ``D`` 1, the router's columns
    ``ROUTER_SCALE`` / sqrt(D). ``wqkv`` is q's, k's and v's columns side by
    side, a head's d values together; ``ssm_in`` is [z | x | B | C | dt]. No
    ``unembed``: the head is the embedding's transpose."""
    L, D, H, G, d = cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Fe, Fs, n, held = cfg.intermediate_size, cfg.shared_intermediate_size, cfg.num_local_experts, cfg.experts_held
    m, nm, na = cfg.mamba, cfg.n_mamba, cfg.n_attention
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def log_uniform(lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), (nm, m.n_heads), jnp.float32, jnp.log(lo), jnp.log(hi)))

    dt = log_uniform(0.001, 0.1)
    return {
        "embed": normal((cfg.vocab_size, D), 1.0 / cfg.embedding_multiplier),
        "in_norm": jnp.ones((L, D), jnp.float32), "post_norm": jnp.ones((L, D), jnp.float32),
        "wqkv": normal((na, D, (H + 2 * G) * d), D ** -0.5), "wo": normal((na, H * d, D), (H * d) ** -0.5),
        "ssm_in": normal((nm, D, m.in_dim), D ** -0.5),
        "ssm_conv": normal((nm, m.d_conv, m.conv_dim), m.d_conv ** -0.5),
        "ssm_conv_b": jnp.zeros((nm, m.conv_dim), jnp.float32),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.log(log_uniform(1.0, 16.0)),
        "ssm_d": jnp.ones((nm, m.n_heads), jnp.float32),
        "ssm_norm": jnp.ones((nm, m.d_ssm), jnp.float32),
        "ssm_out": normal((nm, m.d_ssm, D), m.d_ssm ** -0.5),
        "router": normal((L, D, n), D ** -0.5 * ROUTER_SCALE),
        "e_gate": normal((L, held, D, Fe), D ** -0.5), "e_up": normal((L, held, D, Fe), D ** -0.5),
        "e_down": normal((L, held, Fe, D), Fe ** -0.5),
        "s_gate": normal((L, D, Fs), D ** -0.5), "s_up": normal((L, D, Fs), D ** -0.5),
        "s_down": normal((L, Fs, D), Fs ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
    }


def init_paged_pool(cfg: GraniteHybridConfig, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """The two kinds of cache and the routing counts (module docstring).
    ``state_rows`` counts the null row: the engine asks for ``max_batch + 1``."""
    return {
        "kv": flat_kv.init_pool(cfg.n_attention, num_blocks, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype),
        **mamba2.init_pool(cfg.mamba, cfg.n_mamba, state_rows),
        "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32),
    }


def paged_block_bytes(cfg: GraniteHybridConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows of the attention layers
    alone (a Mamba layer keeps nothing a position)."""
    return flat_kv.block_bytes(cfg.n_attention, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype)


def paged_state_bytes(cfg: GraniteHybridConfig) -> int:
    """Bytes one state row holds: the Mamba layers' states, windows and
    position counts, and nothing of the attention layers."""
    return cfg.n_mamba * mamba2.state_bytes(cfg.mamba)


def _expert_ffn(cfg: GraniteHybridConfig, w, stacks, u, layer, live):
    """A layer's feed-forward half over ``u`` (T, D): (routed + shared,
    counts). ``w`` reads the layer's own router and shared expert, ``stacks``
    holds ``e_gate``, ``e_up``, ``e_down`` (stacked over layers where ``layer``
    is not None)."""
    with jax.named_scope("moe"):
        routed, counts = moe.expert_layer(
            {**stacks, "router": w("router"), "router_bias": None}, u, layer=layer, n_routed=cfg.num_local_experts,
            top_k=cfg.num_experts_per_tok, scale=1.0, expert_offset=cfg.expert_offset, live=live,
            rule=moe.route_topk_softmax)
        with jax.named_scope("shared"):
            shared = swiglu(u @ w("s_gate"), u @ w("s_up")) @ w("s_down")
    return routed + shared, counts


def paged_layer(cfg: GraniteHybridConfig, params, step):
    """The model's sections for one call of a paged program (module
    docstring). A decode step's layers add their routing counts to the
    pool's."""
    eps, dtype, m_r, scale = cfg.rms_norm_eps, cfg.dtype, cfg.residual_multiplier, cfg.attention_multiplier
    H, G, d, mixer, n = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.mamba, len(PERIOD)
    b, s = step.positions.shape
    decode = s == 1

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def attention(u, pool, li):
        """Attention layer ``li``: (out (B, S, D), the pool with its rows written)."""
        ai = li // n
        w = at(ai)
        with jax.named_scope("proj"):
            q, k, v = (t.reshape(b, s, -1, d) for t in jnp.split(u @ w("wqkv"), [H * d, (H + G) * d], axis=-1))
        o, kv = flat_kv.attend(pool["kv"], ai, step, q, k, v, kv_heads=G, scale=scale)
        with jax.named_scope("out"):
            return o.astype(dtype).reshape(b, s, H * d) @ w("wo"), {**pool, "kv": kv}

    def branch(x, out):
        """``x + m_r out``, the product in float32."""
        return x + (out.astype(jnp.float32) * m_r).astype(x.dtype)

    def layer(x, pool, li, attends: bool, first: bool):
        w = at(li)
        if first:  # the lookup's multiplier, on the stream as layer 0 finds it
            x = (x.astype(jnp.float32) * jnp.where(li == 0, cfg.embedding_multiplier, 1.0)).astype(x.dtype)
        u = rms_norm(x, w("in_norm"), eps)
        if attends:
            with jax.named_scope("attn"):
                mixed, pool = attention(u, pool, li)
        else:
            mi = li - (li + 4) // n  # among the Mamba layers: an attention layer a period lies before it, from i % 10 == 6 on
            with jax.named_scope("ssm"):
                mixed, pool = mamba2.mixer(mixer, at(mi), u, pool, mi, step)
        h = branch(x, mixed)
        v = rms_norm(h, w("post_norm"), eps).reshape(b * s, -1)
        y, counts = _expert_ffn(cfg, w, params, v, li, step.live)
        counts = pool["moe_counts"] + counts if decode else pool["moe_counts"]
        return branch(h, y.reshape(h.shape)), {**pool, "moe_counts": counts}

    def section(lo, hi, each):
        """Layers ``lo .. hi`` as one section, ``each`` a call, from a
        period's first layer on: a call's layers' kinds are those of the first
        ``each``, every call."""
        kinds = [is_attention(i) for i in range(lo, lo + each)]

        @jax.named_scope("block")
        def layers(x, pool, li):
            for j, attends in enumerate(kinds):
                x, pool = layer(x, pool, li + j, attends, lo == 0 and j == 0)
            return x, pool

        return layers, hi - lo, each

    whole = cfg.num_hidden_layers // n * n
    runs = [(0, whole, n), (whole, cfg.num_hidden_layers, cfg.num_hidden_layers - whole)]
    return [section(*run) for run in runs if run[1] > run[0]]
