"""Falcon-H1's language model (``model_type`` ``falcon_h1``; Falcon-H1-34B-Instruct
is the published size the defaults carry): the seventh model kind ``serve.llm``
runs. **Every** layer holds a Mamba-2 mixer and grouped-query attention *side by
side*: both read one normed input and their outputs are summed into the
residual stream, each under a µP multiplier. ``N`` is RMSNorm, ``m_*`` the
config's multipliers:

    x0 = m_emb E[token]
    u  = N_in(x);   h = x + m_so SSM(m_si u) + m_ao Attn(m_ai u)
    y  = h + m_down W_d( W_u v * silu(m_gate W_g v) ),   v = N_ff(h)
    logits = m_head W_head N_final(x_L)        (untied)

    Attn(u): q = W_q u as H heads of d; k = m_key W_k u, v = W_v u as G heads;
             rotary over half-split pairs (j, j + d/2) of q and k at the absolute
             position, theta ``rope_theta``; softmax(q_h . k_{h // (H/G)} /
             sqrt(d)) in float32 over positions 0 .. t; W_o

    SSM(u):  [z | x | B | C | dt] = (W_in u) * m_ssm, widths d_ssm, d_ssm, G_s N,
             G_s N, H_s; m_ssm is ``ssm_multipliers[0..4]`` over the five segments
             [x | B | C] <- silu(b_conv + causal depthwise convolution of width K)
             dt_h = softplus(dt_h + dt_bias_h);  A_h = -exp(A_log_h)
             head h = P channels of x; group g(h) = h // (H_s / G_s) gives B_g, C_g
             S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T         S_h: P x N, float32
             y_h = S_h C_g + D_h x_h
             y <- w * RMSNorm_group(y * silu(z))     the gate first, then a norm
                  over each of the G_s groups' d_ssm / G_s channels
             W_out y

**Where the multipliers are applied.** ``m_emb`` on the residual stream as layer
0 finds it (the lookup is ``models/paged.py``'s; nothing else precedes it);
``m_si`` and ``m_ssm``, and ``m_ai`` and ``m_key``, as one vector over the
*outputs* of the bias-free projections they surround, in float32 (the same
products, one rounding apart); ``m_so``, ``m_ao``, ``m_down`` on their
projections' outputs; ``m_gate`` on the gate ahead of its SiLU; ``m_head`` on the
final norm's output (``cfg.final_norm``, which ``paged.head`` asks for), ahead of
the head's matrix. No weight is changed at load.

This module gives ``models/paged.py`` a kind's four things, one section of one
layer a call. **The pool holds both caches of every layer** behind one block
table:

* ``kv`` (layers, 2, slots x G, d): every layer's rows a position in the flat
  pool (``models/flat_kv.py``: its format, how a call's rows are written and
  read back, which kernel scores them), a K/V head a row (four heads are no
  whole sublane tile), the keys rotated and multiplied before they are written.
* ``state`` (layers, state rows, N, d_ssm) float32, ``conv`` (.., K x (d_ssm + 2
  G_s N)), ``state_pos``: the recurrent state (the state dimension in the
  sublanes, every head's channels side by side in the lanes: a head is one lane
  tile), the short convolution's window and the count of positions consumed, as
  ``models/olmo_hybrid.py`` keeps them and under its rule for a decode step
  dispatched twice at one position. A decode step updates the state in place
  (``ops/selective_scan.py:selective_scan_update``, its decays given a head); a
  prefill computes it in chunks of matrix products (``ops/ssd.py``). The mixer
  itself is ``models/mamba2.py``'s, which ``models/granite_hybrid.py`` calls at
  other numbers; here it takes ``ssm_scales`` over its input projection.

A prefill starts from an empty state: no chunked prefill, no prefix reuse.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import flat_kv, mamba2, paged
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_tables, swiglu


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """Published keys (tiiuae ``config.json`` names). Of the keys that choose a
    path the program runs what the checkpoint states and refuses the rest."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    attn_layer_indices: Optional[list] = None
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_d_head: int = 128
    mamba_n_heads: int = 32
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369, 0.011160714285714284)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "rope_theta", float(self.rope_theta))  # published as an integer past 32 bits
        object.__setattr__(self, "ssm_multipliers", tuple(float(m) for m in self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers", tuple(float(m) for m in self.mlp_multipliers))
        if not self.mamba_rms_norm or self.mamba_norm_before_gate:
            raise ValueError(f"mamba_rms_norm {self.mamba_rms_norm}, mamba_norm_before_gate {self.mamba_norm_before_gate}: "
                             "the program gates the mixer's output and then norms it, a group at a time")
        if self.attention_bias or self.mlp_bias or self.projectors_bias or self.mamba_proj_bias or not self.mamba_conv_bias:
            raise ValueError("the program runs one bias, the short convolution's: attention_bias, mlp_bias, projectors_bias "
                             "and mamba_proj_bias false, mamba_conv_bias true")
        if self.rope_scaling is not None or self.attn_layer_indices is not None or self.tie_word_embeddings:
            raise ValueError(f"rope_scaling {self.rope_scaling}, attn_layer_indices {self.attn_layer_indices}, "
                             f"tie_word_embeddings {self.tie_word_embeddings}: the program runs a plain rotary, attention "
                             "in every layer and an untied head")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("the program runs whole groups of query heads a K/V head, and a rotary over half-split pairs")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm or self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} heads of {self.mamba_d_head} are not mamba_d_ssm {self.mamba_d_ssm}, "
                             f"or not whole groups of {self.mamba_n_groups}")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers are five (z, x, B, C, dt) and mlp_multipliers two (gate, down)")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    kv_row = property(lambda self: self.num_key_value_heads * self.head_dim)  # values of one position's K (or V)
    bc_dim = property(lambda self: self.mamba.bc_dim)  # values of a token's B (or C)
    conv_dim = property(lambda self: self.mamba.conv_dim)  # channels the short convolution runs over
    in_dim = property(lambda self: self.mamba.in_dim)  # [z | x | B | C | dt]
    mamba = property(lambda self: mamba2.Mamba2(
        self.mamba_d_ssm, self.mamba_d_state, self.mamba_d_head, self.mamba_n_heads, self.mamba_n_groups, self.mamba_d_conv,
        self.rms_norm_eps, self.dtype))  # what ``models/mamba2.py`` takes (``mamba_chunk_size`` is the upstream kernel's tile)

    def final_norm(self, params, x):
        """The model's last norm with ``lm_head_multiplier`` on its output, in
        float32 ahead of the rounding: ``paged.head``'s."""
        scaled = params["final_norm"].astype(jnp.float32) * self.lm_head_multiplier
        return rms_norm(x, scaled, self.rms_norm_eps)

    def ssm_scales(self):
        """(in_dim,) float32: ``ssm_in_multiplier`` times ``ssm_multipliers``
        over the five segments of the input projection's output."""
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, self.bc_dim, self.bc_dim, self.mamba_n_heads)
        return jnp.concatenate([jnp.full((w,), self.ssm_in_multiplier * m, jnp.float32)
                                for w, m in zip(widths, self.ssm_multipliers)])

    def qkv_scales(self):
        """((H + 2 G) d,) float32: ``attention_in_multiplier`` over q, k and v
        and ``key_multiplier`` over k besides."""
        q, kv = self.num_attention_heads * self.head_dim, self.kv_row
        ai = self.attention_in_multiplier
        return jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in ((q, ai), (kv, ai * self.key_multiplier), (kv, ai))])


def init_params(key, cfg: FalconH1Config) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own and
    says why each, ``benchmarks/families/falcon_h1.py``): 1/sqrt(fan-in), the
    embedding 0.02, norms 1, the convolution's bias 0, ``A`` log-uniform in 1-16
    and the step ``dt`` log-uniform in 0.001-0.1 a head (the published layer's
    ranges), ``D`` 1. ``wqkv`` is q's, k's and v's columns side by side, a head's
    d values together; ``ssm_in`` is [z | x | B | C | dt]."""
    L, D, F, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, d, Hs, K = cfg.num_attention_heads, cfg.head_dim, cfg.mamba_n_heads, cfg.mamba_d_conv
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def log_uniform(lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), (L, Hs), jnp.float32, jnp.log(lo), jnp.log(hi)))

    dt = log_uniform(0.001, 0.1)
    return {
        "embed": normal((V, D), 0.02),
        "in_norm": jnp.ones((L, D), jnp.float32), "ff_norm": jnp.ones((L, D), jnp.float32),
        "wqkv": normal((L, D, H * d + 2 * cfg.kv_row), D ** -0.5),
        "wo": normal((L, H * d, D), (H * d) ** -0.5),
        "ssm_in": normal((L, D, cfg.in_dim), D ** -0.5),
        "ssm_conv": normal((L, K, cfg.conv_dim), K ** -0.5),
        "ssm_conv_b": jnp.zeros((L, cfg.conv_dim), jnp.float32),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.log(log_uniform(1.0, 16.0)),
        "ssm_d": jnp.ones((L, Hs), jnp.float32),
        "ssm_norm": jnp.ones((L, cfg.mamba_d_ssm), jnp.float32),
        "ssm_out": normal((L, cfg.mamba_d_ssm, D), cfg.mamba_d_ssm ** -0.5),
        "w_gate": normal((L, D, F), D ** -0.5), "w_up": normal((L, D, F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, V), D ** -0.5),
    }


def init_paged_pool(cfg: FalconH1Config, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """Both caches of every layer (module docstring). ``state_rows`` counts the
    null row: the engine asks for ``max_batch + 1``."""
    L = cfg.num_hidden_layers
    return {"kv": flat_kv.init_pool(L, num_blocks, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype),
            **mamba2.init_pool(cfg.mamba, L, state_rows)}


def paged_block_bytes(cfg: FalconH1Config, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows of every layer."""
    return flat_kv.block_bytes(cfg.num_hidden_layers, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype)


def paged_state_bytes(cfg: FalconH1Config) -> int:
    """Bytes one state row holds, over every layer: the float32 state, the
    convolution's window and the position count. A kind that gives this wants
    a row a sequence."""
    return cfg.num_hidden_layers * mamba2.state_bytes(cfg.mamba)


def paged_layer(cfg: FalconH1Config, params, step):
    """The model's layer for one call of a paged program (module docstring)."""
    eps, dtype = cfg.rms_norm_eps, cfg.dtype
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    mixer = cfg.mamba
    gate_mult, down_mult = cfg.mlp_multipliers
    b, s = step.positions.shape
    dot32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    # what is the same for every layer: once a call
    ssm_scales, qkv_scales = cfg.ssm_scales(), cfg.qkv_scales()
    rope = rope_tables(step.positions, d, cfg.rope_theta)

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def attention(u, pool, li):
        """Layer ``li``'s attention over ``u`` = N_in(x): (out, pool)."""
        w = at(li)
        with jax.named_scope("proj"):
            q, k, v = jnp.split(dot32("bsd,dc->bsc", u, w("wqkv")) * qkv_scales, [H * d, H * d + G * d], axis=-1)
        with jax.named_scope("rope"):
            q, k = (apply_rope(t.reshape(b * s, -1, d), *rope).reshape(b, s, -1, d).astype(dtype) for t in (q, k))
            v = v.reshape(b, s, G, d).astype(dtype)
        o, kv = flat_kv.attend(pool["kv"], li, step, q, k, v, kv_heads=G)
        with jax.named_scope("out"):
            return o.astype(dtype).reshape(b, s, H * d) @ w("wo"), {**pool, "kv": kv}

    @jax.named_scope("block")
    def layer(x, pool, li):
        w = at(li)
        # the lookup's multiplier, on the stream as layer 0 finds it
        x = (x.astype(jnp.float32) * jnp.where(li == 0, cfg.embedding_multiplier, 1.0)).astype(x.dtype)
        u = rms_norm(x, w("in_norm"), eps)
        with jax.named_scope("ssm"):
            mixed, pool = mamba2.mixer(mixer, at(li), u, pool, li, step, ssm_scales)
        with jax.named_scope("attn"):
            attended, pool = attention(u, pool, li)
        h = x + (mixed.astype(jnp.float32) * cfg.ssm_out_multiplier
                 + attended.astype(jnp.float32) * cfg.attention_out_multiplier).astype(x.dtype)
        with jax.named_scope("mlp"):
            v = rms_norm(h, w("ff_norm"), eps)
            y = swiglu((v @ w("w_gate")) * gate_mult, v @ w("w_up")) @ w("w_down")
            return h + (y.astype(jnp.float32) * down_mult).astype(x.dtype), pool

    return layer
