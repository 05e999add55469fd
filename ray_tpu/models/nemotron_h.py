"""Nemotron-H's language model (``model_type`` ``nemotron_h``; "Nemotron 3 Super
120B-A12B" is the published size the defaults carry): the tenth model kind
``serve.llm`` runs. **A layer is one norm and one part**: the published
``hybrid_override_pattern`` names each layer's part, ``M`` a Mamba-2 mixer,
``E`` an expert layer, ``*`` attention, and no layer has two. ``N`` is RMSNorm
(eps ``layer_norm_epsilon``, a learned weight):

    x_0 = E[token]
    x  <- x + Part_i(N_i(x))           Part_i by hybrid_override_pattern[i]
    logits = W_head N_f(x_L)           (untied)

    M(u):  ``models/mamba2.py``'s mixer at ``n_groups`` B/C groups (which
           ``models/falcon_h1.py`` and ``models/granite_hybrid.py`` run at two
           and at one); ``dt = softplus(dt + dt_bias)``, not clamped
    *(u):  q = W_q u as H heads of ``head_dim``; k = W_k u, v = W_v u as G heads;
           softmax(q_h . k_{h // (H/G)} / sqrt(head_dim)) in float32 over
           positions 0 .. t; W_o. **No rotary and no other position signal**:
           the published layer applies none, and ``rope_theta`` and
           ``partial_rotary_factor`` are read by nothing
    E(u):  s = sigmoid(W_r u) in float32 over ``n_routed_experts`` outputs; the
           ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` moves the
           choice, never the weights); w = ``routed_scaling_factor`` s / (sum of
           the chosen s + 1e-20) (``moe.route_sigmoid``: DeepSeek-V3's
           ``noaux_tc`` at one group)
           v = W_in^lat u          (``hidden_size`` -> ``moe_latent_size``)
           E_k(v) = W_down,k relu(W_up,k v)^2        two matrices, no gate, in the latent
           E(u) = W_out^lat (sum_k w_k E_k(v)) + S(u)
           S(u) = W_down^s relu(W_up^s u)^2          the shared expert, every token, weight 1

No bias but the short convolution's. The experts are ``models/moe.py``'s layer
handed the latent rows apart from the router's input and two matrices an
expert, with this chip's share of them (``experts_held`` from
``expert_offset``); the two latent projections and the shared expert are dense
matmuls here. Key names follow the published ``config.json``; **``n_groups`` is
the mixer's B/C groups and ``n_group`` the router's groups of experts** (1 of 1:
the rule keeps all).

This module gives ``models/paged.py`` a kind's things and its layers as **one
section of whole periods** (``models/exaone_moe.py`` says why no ``lax.cond``):
the body is the pattern's shortest period (all of it where it has none: the cut
the benchmark runs is eleven layers in one call), each layer's part chosen where
the program is traced. **The three stacks of weights have different depths**:
the norms are stacked over all layers, the mixers' tensors over the ``M``
layers, attention's over the ``*`` layers, the router, the latent projections,
the experts and the shared expert over the ``E`` layers, each read by the
layer's index among its kind (a period's count times the period, plus the
layer's place among its kind in the period).

**The pool holds each kind of layer's own**, behind one block table:

* ``kv`` (``*`` layers, 2, slots x G, head_dim): the attention layers' rows a
  position, keys in plane 0 and values in plane 1, each plane flat
  (``models/flat_kv.py``); a block holds those layers' rows alone.
* ``state``, ``conv``, ``state_pos`` (``M`` layers, state rows, ..): a
  sequence's recurrent state, its convolution's window and the positions it has
  consumed, in the sequence's state row (``models/mamba2.py``).
* ``moe_counts``: ``moe.COUNTS`` summed over the ``E`` layers and decode steps.

A prefill starts from an empty state: no chunked prefill, no prefix reuse. The
published drafting block (``num_nextn_predict_layers``, ``mtp_hybrid_override_pattern``)
is not written here: a step yields one token a sequence, and the served path
refuses a config that asks for more.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import flat_kv, mamba2, moe, paged
from ray_tpu.models.moe import routing_counts  # noqa: F401 - the engine asks the kind's module for it
from ray_tpu.ops.layers import relu2, rms_norm

PATTERN = "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
ROUTER_SCALE = 1.5


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Published keys (nvidia ``config.json`` names) plus this chip's share of
    each expert layer's routed experts: ``experts_held`` of the
    ``n_routed_experts``, from ``expert_offset`` (all of them where none is
    named). Of the keys that choose a path the program runs what the checkpoint
    states and refuses the rest; the keys that only name the upstream kernels,
    the initialiser or the checkpoint's loading are carried and read by
    nothing."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PATTERN
    max_position_embeddings: int = 262144
    layer_norm_epsilon: float = 1e-5
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    rope_theta: float = 10000.0  # published, and read by nothing: no layer has a rotary
    partial_rotary_factor: float = 1.0  # likewise
    # the Mamba-2 mixer
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8  # B/C groups
    conv_kernel: int = 4
    expand: int = 2
    chunk_size: int = 128  # the upstream kernel's tile: defines no mathematics
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    use_bias: bool = False
    use_mamba_kernels: bool = True
    time_step_min: float = 0.001  # the initialiser's, as are the next two
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    # the expert layer
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    moe_shared_expert_overlap: bool = False  # a schedule of the upstream runtime, no mathematics
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1  # the router's groups of experts
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    intermediate_size: int = 2688  # a dense layer's width ("-" in the pattern): the program runs none
    # the drafting block, and what a checkpoint's loader reads
    num_nextn_predict_layers: int = 0
    mtp_hybrid_override_pattern: str = "*E"
    num_logits_to_keep: int = 1
    rescale_prenorm_residual: bool = True
    residual_in_fp32: bool = False
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.n_routed_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set("ME*") or not pattern:
            raise ValueError(f"hybrid_override_pattern {pattern!r} for {self.num_hidden_layers} layers: the program runs a "
                             "layer a character, each a mixer (M), an expert layer (E) or attention (*)")
        if self.num_nextn_predict_layers:
            raise ValueError("num_nextn_predict_layers must be 0 on the served path: a step yields one token a sequence")
        if (self.attention_bias or self.mamba_proj_bias or self.mlp_bias or self.use_bias or not self.use_conv_bias
                or self.tie_word_embeddings):
            raise ValueError("the program runs one bias, the short convolution's (attention_bias, mamba_proj_bias, "
                             "mlp_bias and use_bias false, use_conv_bias true), and a head of its own")
        if self.mamba_hidden_act != "silu" or self.mlp_hidden_act != "relu2" or self.norm_eps != self.layer_norm_epsilon:
            raise ValueError(f"mamba_hidden_act {self.mamba_hidden_act!r}, mlp_hidden_act {self.mlp_hidden_act!r}, norm_eps "
                             f"{self.norm_eps}: the program runs SiLU in the mixer, relu^2 in the experts and one epsilon")
        if self.sliding_window is not None or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the program's attention sees every position, whole groups of query heads a K/V head")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} mixer heads are not whole groups of {self.n_groups}")
        if (self.n_group, self.topk_group, self.norm_topk_prob, self.n_shared_experts) != (1, 1, True, 1):
            raise ValueError("the program's router chooses among one group of experts (n_group 1, topk_group 1), "
                             "renormalises the chosen weights and adds one shared expert")
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.n_routed_experts}")
        if not 0 < self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError(f"{self.num_experts_per_tok} experts a token of {self.n_routed_experts}")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    n_mamba = property(lambda self: self.hybrid_override_pattern.count("M"))
    n_attention = property(lambda self: self.hybrid_override_pattern.count("*"))
    n_expert_layers = property(lambda self: self.hybrid_override_pattern.count("E"))
    max_seq_len = property(lambda self: self.max_position_embeddings)
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)  # ``paged.head``'s final norm
    kv_row = property(lambda self: self.num_key_value_heads * self.head_dim)  # values of one position's K (or V)
    mamba = property(lambda self: mamba2.Mamba2(
        self.mamba_num_heads * self.mamba_head_dim, self.ssm_state_size, self.mamba_head_dim, self.mamba_num_heads,
        self.n_groups, self.conv_kernel, self.layer_norm_epsilon, self.dtype))

    @property
    def period(self) -> int:
        """Layers of one call of the section's body: the pattern's shortest
        period, all of it where it has none."""
        pattern = self.hybrid_override_pattern
        return next(p for p in range(1, len(pattern) + 1) if pattern[:p] * (len(pattern) // p) == pattern)


def init_params(key, cfg: NemotronHConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own and
    says why each, ``benchmarks/families/nemotron_h.py``): 1/sqrt(fan-in), norms
    1, the convolution's bias 0, ``A`` log-uniform in 1-16 and the step ``dt``
    log-uniform in ``time_step_min``-``time_step_max`` a head (the published
    initialiser's), ``D`` 1, the router's columns ``ROUTER_SCALE`` / sqrt(D),
    the choice bias ``moe.BIAS_SCALE`` x normal. ``wqkv`` is q's, k's and v's
    columns side by side, a head's values together; ``ssm_in`` is [z | x | B |
    C | dt]."""
    L, D, H, G, d = cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    C, Fe, Fs, n, held = (cfg.moe_latent_size, cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size,
                          cfg.n_routed_experts, cfg.experts_held)
    m, nm, na, ne = cfg.mamba, cfg.n_mamba, cfg.n_attention, cfg.n_expert_layers
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def log_uniform(lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), (nm, m.n_heads), jnp.float32, jnp.log(lo), jnp.log(hi)))

    dt = log_uniform(cfg.time_step_min, cfg.time_step_max)
    return {
        "embed": normal((cfg.vocab_size, D), 1.0), "unembed": normal((D, cfg.vocab_size), D ** -0.5),
        "norm": jnp.ones((L, D), jnp.float32), "final_norm": jnp.ones((D,), jnp.float32),
        "wqkv": normal((na, D, (H + 2 * G) * d), D ** -0.5), "wo": normal((na, H * d, D), (H * d) ** -0.5),
        "ssm_in": normal((nm, D, m.in_dim), D ** -0.5),
        "ssm_conv": normal((nm, m.d_conv, m.conv_dim), m.d_conv ** -0.5),
        "ssm_conv_b": jnp.zeros((nm, m.conv_dim), jnp.float32),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.log(log_uniform(1.0, 16.0)),
        "ssm_d": jnp.ones((nm, m.n_heads), jnp.float32),
        "ssm_norm": jnp.ones((nm, m.d_ssm), jnp.float32),
        "ssm_out": normal((nm, m.d_ssm, D), m.d_ssm ** -0.5),
        "router": normal((ne, D, n), D ** -0.5 * ROUTER_SCALE),
        "router_bias": normal((ne, n), moe.BIAS_SCALE, jnp.float32),
        "lat_in": normal((ne, D, C), D ** -0.5), "lat_out": normal((ne, C, D), C ** -0.5),
        "e_up": normal((ne, held, C, Fe), C ** -0.5), "e_down": normal((ne, held, Fe, C), Fe ** -0.5),
        "s_up": normal((ne, D, Fs), D ** -0.5), "s_down": normal((ne, Fs, D), Fs ** -0.5),
    }


def init_paged_pool(cfg: NemotronHConfig, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """Each kind of layer's own cache and the routing counts (module
    docstring). ``state_rows`` counts the null row: the engine asks for
    ``max_batch + 1``."""
    return {
        "kv": flat_kv.init_pool(cfg.n_attention, num_blocks, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype),
        **mamba2.init_pool(cfg.mamba, cfg.n_mamba, state_rows),
        "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32),
    }


def paged_block_bytes(cfg: NemotronHConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows of the attention layers
    alone (a mixer keeps nothing a position, an expert layer nothing at all)."""
    return flat_kv.block_bytes(cfg.n_attention, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype)


def paged_state_bytes(cfg: NemotronHConfig) -> int:
    """Bytes one state row holds: the mixers' states, windows and position
    counts, and nothing of the other layers."""
    return cfg.n_mamba * mamba2.state_bytes(cfg.mamba)


def expert_part(cfg: NemotronHConfig, w, stacks, u, layer, live):
    """An expert layer over ``u`` (T, D), the layer's normed input: (routed +
    shared (T, D), counts). ``w`` reads the layer's own router, latent
    projections and shared expert; ``stacks`` holds ``e_up`` and ``e_down``
    (stacked over the expert layers where ``layer`` is not None)."""
    with jax.named_scope("moe"):
        with jax.named_scope("latent_in"):
            v = u @ w("lat_in")
        mixed, counts = moe.expert_layer(
            {"e_up": stacks["e_up"], "e_down": stacks["e_down"], "router": w("router"), "router_bias": w("router_bias")},
            u, rows=v, layer=layer, n_routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor, expert_offset=cfg.expert_offset, live=live, rule=moe.route_sigmoid)
        with jax.named_scope("latent_out"):
            routed = mixed @ w("lat_out")
        with jax.named_scope("shared"):
            hidden = relu2(jnp.dot(u, w("s_up"), preferred_element_type=jnp.float32))  # the square ahead of the rounding
            shared = hidden.astype(u.dtype) @ w("s_down")
    return routed + shared, counts


def paged_layer(cfg: NemotronHConfig, params, step):
    """The model's one section for one call of a paged program (module
    docstring). A decode step's expert layers add their routing counts to the
    pool's."""
    eps, dtype, scale = cfg.layer_norm_epsilon, cfg.dtype, cfg.head_dim ** -0.5
    H, G, d, mixer = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.mamba
    b, s = step.positions.shape
    decode = s == 1

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def attention(u, pool, ai):
        """Attention layer ``ai`` among the attention layers: (out (B, S, D),
        the pool with its rows written)."""
        w = at(ai)
        with jax.named_scope("proj"):
            q, k, v = (t.reshape(b, s, -1, d) for t in jnp.split(u @ w("wqkv"), [H * d, (H + G) * d], axis=-1))
        o, kv = flat_kv.attend(pool["kv"], ai, step, q, k, v, kv_heads=G, scale=scale)
        with jax.named_scope("out"):
            return o.astype(dtype).reshape(b, s, H * d) @ w("wo"), {**pool, "kv": kv}

    def layer(x, pool, li, kind: str, index):
        """Layer ``li``, the ``index``-th of its kind: one norm, one part."""
        u = rms_norm(x, at(li)("norm"), eps)
        if kind == "M":
            with jax.named_scope("ssm"):
                out, pool = mamba2.mixer(mixer, at(index), u, pool, index, step)
        elif kind == "*":
            with jax.named_scope("attn"):
                out, pool = attention(u, pool, index)
        else:
            out, counts = expert_part(cfg, at(index), params, u.reshape(b * s, -1), index, step.live)
            out = out.reshape(x.shape)
            if decode:
                pool = {**pool, "moe_counts": pool["moe_counts"] + counts}
        return x + out, pool

    each = cfg.period
    kinds = cfg.hybrid_override_pattern[:each]
    before = [kinds[:j].count(kind) for j, kind in enumerate(kinds)]  # a layer's place among its kind in the period

    @jax.named_scope("block")
    def layers(x, pool, li):
        turn = li // each  # the period this call runs
        for j, kind in enumerate(kinds):
            x, pool = layer(x, pool, li + j, kind, turn * kinds.count(kind) + before[j])
        return x, pool

    return [(layers, cfg.num_hidden_layers, each)]
