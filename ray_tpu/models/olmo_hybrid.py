"""Olmo-Hybrid's language model (``model_type`` ``olmo_hybrid``): the fourth
model kind ``serve.llm`` runs, and the first whose layers keep a state that is
not rows a position.

Layers alternate in a period (published: three ``linear_attention``, one
``full_attention``, eight times). Every layer is OLMo 2's block, the norms on
the branches' outputs (``N`` is RMSNorm):

    h = x + N(mixer(x))
    y = h + N(W_down (silu(W_gate h) * W_up h))

A **linear-attention** mixer is a Gated DeltaNet layer (``ops/gated_delta.py``
has the rule): projections to q, k (H x d_k), v and an output gate z (H x
d_v), and two numbers a head, b and a; a short causal depthwise convolution
(width ``linear_conv_kernel_dim``, then SiLU) over q, k and v; the gated delta
rule on the L2-normalised q and k with ``beta = 2 sigmoid(b)`` and ``alpha =
exp(-exp(A_log) softplus(a + dt_bias))``; ``N_{d_v}(o) * silu(z)`` a head, then
``W_o``. A **full-attention** mixer is causal softmax attention over RMSNorms
of the whole q and k projections, **without rotary** (``rope_theta`` is null:
positions reach it through the recurrent layers ahead of it).

This module gives ``models/paged.py`` a kind's four things, and its layer as
**one section whose body is a period**: the scan runs over periods, the body
is the period's layers in order, each reading its own kind's stacked tensors by
the period's index (``gdn_*`` stacked over the linear layers, ``wq`` .. ``wo``
over the full ones, the norms and the MLP over all).

**The pool holds two kinds of cache** behind one block table:

* ``kv`` (full layers, 2, slots, stored heads, head_dim): GPT-J's pool, keys
  in plane 0 and values in plane 1, and its ``attend``
  (``generation.attend_pool``: the paged kernel for a decode step, which
  brings a block's keys and values in under one copy; the gathered rows
  elsewhere). 30 heads are stored 32 wide (whole
  sublane tiles, ``can_use_paged_kernel``); q is padded alike and the spare
  heads' outputs dropped.
* ``state`` (linear layers, rows, d_k, H x d_v) float32, ``conv`` (linear
  layers, rows, K x C) and ``state_pos`` (linear layers, rows): a **state row**
  a live sequence, row 0 the null row as block 0 is the null block. The row's
  index rides in the block table's last column (``Step.state_rows``;
  ``serve/llm/kv_cache.py`` hands it out with the blocks). ``conv`` is the
  short convolution's window, the last K inputs, the current one among them,
  flat in the lanes (shifting it is a move of whole lane tiles). On a TPU a
  decode step updates each live row's state in place through the Pallas
  kernel ``gated_delta_update`` (read once, written once); a layer's windows,
  4.5 MB, are updated whole (``short_conv_step``: no gather, no scatter).

**A decode step may be dispatched twice at one position** (the benchmark's
replay calls ``decode_step`` and then ``decode_step_greedy`` on the same
arguments): rows of K/V are rewritten the same, a state would be advanced
twice. ``state_pos[l, row]`` counts the positions layer ``l``'s state of that
row has consumed: a prefill of n tokens sets it to n; a step at position p
advances state and window only where it reads p, and sets it to p + 1;
where it does not, the outputs are read from the stored state and window,
which already hold position p. The served path never takes that branch.
A prefill starts from an empty state: no chunked prefill, no prefix reuse.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import paged
from ray_tpu.models.generation import attend_pool
from ray_tpu.ops import gated_delta
from ray_tpu.ops.attention import attention
from ray_tpu.ops.layers import rms_norm, swiglu

LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Published keys (allenai ``config.json`` names). ``layer_types`` left
    out is the published period repeated over ``num_hidden_layers``."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_theta: Optional[float] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            types = tuple(PERIOD[i % len(PERIOD)] for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", tuple(types))
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types {self.layer_types} do not name {self.num_hidden_layers} layers of "
                             f"{LINEAR} and {FULL}")
        if self.rope_theta is not None:
            raise ValueError(f"rope_theta {self.rope_theta}: the program's full-attention layers run no rotary")
        if self.num_key_value_heads != self.num_attention_heads or self.hidden_size % self.num_attention_heads:
            raise ValueError("the program runs as many K/V heads as query heads, of hidden_size / heads values")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the program runs as many linear key heads as value heads")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    n_linear = property(lambda self: self.layer_types.count(LINEAR))
    n_full = property(lambda self: self.layer_types.count(FULL))

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of layer kinds that, repeated, is ``layer_types``."""
        n = self.num_hidden_layers
        return next(self.layer_types[:p] for p in range(1, n + 1)
                    if n % p == 0 and self.layer_types[:p] * (n // p) == self.layer_types)

    @property
    def kv_heads_stored(self) -> int:
        """K/V heads rounded up to whole sublane tiles of the pool's type (30
        stored as 32 in bfloat16): what the paged kernel reads in place."""
        tile = 32 // jnp.dtype(self.dtype).itemsize
        return -(-self.num_key_value_heads // tile) * tile

    @property
    def conv_channels(self) -> int:
        """Channels of the short convolution: q, k and v side by side."""
        return self.linear_num_key_heads * (2 * self.linear_key_head_dim + self.linear_value_head_dim)


def init_params(key, cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own,
    ``benchmarks/families/olmo_hybrid.py``): 1/sqrt(fan-in), the embedding
    0.02, norms 1, the decay's ``A`` in 0.5-2 and its time step in 0.002-0.05
    (log-uniform) so that a state remembers tens to a thousand tokens, the two
    gates' projections a fifth of that (the branches' norms let the residual
    stream grow with depth). The module's docstring says what is stacked over
    which layers."""
    L, Ll, Lf, D, F = cfg.num_hidden_layers, cfg.n_linear, cfg.n_full, cfg.hidden_size, cfg.intermediate_size
    H, dv, K, C = cfg.linear_num_key_heads, cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim, cfg.conv_channels
    A = cfg.num_attention_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 20))

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def log_uniform(shape, lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), shape, jnp.float32, jnp.log(lo), jnp.log(hi)))

    dt = log_uniform((Ll, H), 0.002, 0.05)
    return {
        "embed": normal((cfg.vocab_size, D), 0.02),
        "mixer_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_gate": normal((L, D, F), D ** -0.5),
        "w_up": normal((L, D, F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
        "gdn_qkv": normal((Ll, D, C), D ** -0.5),
        "gdn_gate": normal((Ll, D, H * dv), D ** -0.5),
        "gdn_ba": normal((Ll, D, 2 * H), 0.2 * D ** -0.5),
        "gdn_conv": normal((Ll, K, C), K ** -0.5),
        "gdn_a_log": jnp.log(jax.random.uniform(next(keys), (Ll, H), jnp.float32, 0.5, 2.0)),
        "gdn_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "gdn_onorm": jnp.ones((Ll, dv), jnp.float32),
        "gdn_out": normal((Ll, H * dv, D), (H * dv) ** -0.5),
        "wq": normal((Lf, D, A), D ** -0.5),
        "wk": normal((Lf, D, A), D ** -0.5),
        "wv": normal((Lf, D, A), D ** -0.5),
        "q_norm": jnp.ones((Lf, A), jnp.float32),
        "k_norm": jnp.ones((Lf, A), jnp.float32),
        "wo": normal((Lf, A, D), A ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, cfg.vocab_size), D ** -0.5),
    }


def init_paged_pool(cfg: OlmoHybridConfig, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """The two kinds of cache (module docstring). ``state_rows`` counts the
    null row: the engine asks for ``max_batch + 1``."""
    kv = (cfg.n_full, 2, num_blocks * block_size, cfg.kv_heads_stored, cfg.head_dim)  # keys in plane 0, values in plane 1
    wide = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    return {
        "kv": jnp.zeros(kv, cfg.dtype),
        "state": jnp.zeros((cfg.n_linear, state_rows, cfg.linear_key_head_dim, wide), jnp.float32),
        "conv": jnp.zeros((cfg.n_linear, state_rows, cfg.linear_conv_kernel_dim * cfg.conv_channels), cfg.dtype),
        "state_pos": jnp.zeros((cfg.n_linear, state_rows), jnp.int32),
    }


def paged_block_bytes(cfg: OlmoHybridConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows, as stored, over the
    full-attention layers."""
    return 2 * cfg.n_full * block_size * cfg.kv_heads_stored * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize


def paged_state_bytes(cfg: OlmoHybridConfig) -> int:
    """Bytes one state row holds over the linear layers: the state, the
    convolution's window and the position count. A kind that gives this wants a
    row a sequence (``BlockAllocator(state_rows=)``)."""
    state = cfg.linear_key_head_dim * cfg.linear_num_value_heads * cfg.linear_value_head_dim * 4
    window = cfg.linear_conv_kernel_dim * cfg.conv_channels * jnp.dtype(cfg.dtype).itemsize
    return cfg.n_linear * (state + window + 4)


def paged_layer(cfg: OlmoHybridConfig, params, step):
    """The model's one section for one call of a paged program: (a period's
    layers in order, all the layers, a period's length a call)."""
    eps, period = cfg.rms_norm_eps, cfg.period
    heads, hd, stored = cfg.num_attention_heads, cfg.head_dim, cfg.kv_heads_stored
    H, dk, dv, K, C = (cfg.linear_num_key_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                       cfg.linear_conv_kernel_dim, cfg.conv_channels)
    b, s = step.positions.shape
    rows, live = step.state_rows, step.live.reshape(b, s)
    decode = s == 1
    bs = step.block_size
    table_rows = (step.block_tables[:, :, None] * bs + jnp.arange(bs)).reshape(b, -1)  # a decode step's gather path's
    use_kernel = decode and gated_delta.can_use_gated_delta_kernel(H, dk, dv)

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def heads_of(c):  # (..., C) -> q, k (..., H, d_k), v (..., H, d_v)
        q, k, v = jnp.split(c, [H * dk, 2 * H * dk], axis=-1)
        return q.reshape(*q.shape[:-1], H, dk), k.reshape(*k.shape[:-1], H, dk), v.reshape(*v.shape[:-1], H, dv)

    def gdn(x, pool, ll):
        """The linear-attention mixer of linear layer ``ll``: (out, pool)."""
        w = at(ll)
        with jax.named_scope("proj"):
            u = x @ w("gdn_qkv")  # (B, S, C)
            z = x @ w("gdn_gate")
            strength, decay = jnp.split((x @ w("gdn_ba")).astype(jnp.float32), 2, axis=-1)  # b, a
            g, beta = gated_delta.decay_and_strength(decay, strength, w("gdn_a_log"), w("gdn_dt_bias"),
                                                     cfg.linear_allow_neg_eigval)
        if decode:
            # the batch in the pool's order: who holds which row (an inactive slot none, so the null row has no
            # owner), the position each row's sequence is at, which rows take this step (module docstring)
            seen = pool["state_pos"][ll]
            owner = (rows[None, :] == jnp.arange(len(seen))[:, None]) & live[None, :, 0]
            at_row = jnp.sum(jnp.where(owner, step.positions[None, :, 0], 0), axis=1)
            advance_rows = jnp.any(owner, axis=1) & (seen == at_row)
            seen = jnp.where(advance_rows, at_row + 1, seen)
            advance = jnp.any(owner & advance_rows[:, None], axis=0)
            with jax.named_scope("conv"):
                c, windows = gated_delta.short_conv_step(pool["conv"][ll], u[:, 0], w("gdn_conv"), owner, advance_rows)
                windows = pool["conv"].at[ll].set(windows)
                q, k, v = heads_of(c)
            with jax.named_scope("update"):
                if use_kernel:
                    o, states = gated_delta.gated_delta_update(pool["state"], ll, rows, advance, q, k, v, g[:, 0],
                                                               beta[:, 0])
                else:
                    _, new = gated_delta.gated_delta_step(pool["state"][ll, rows], q, k, v, g[:, 0], beta[:, 0], advance)
                    states = pool["state"].at[ll, rows].set(new)
                    o = gated_delta.gated_delta_read(states[ll, rows], q)  # from the state as stored, as a replay reads it
                o, positions_seen = o[:, None], pool["state_pos"].at[ll].set(seen)
        else:
            length = jnp.sum(live, axis=1)
            with jax.named_scope("conv"):
                padded, taps = jnp.pad(u, ((0, 0), (K, 0), (0, 0))), w("gdn_conv").astype(jnp.float32)
                # position t at index t + K: its K inputs are indices t + 1 .. t + K
                q, k, v = heads_of(jax.nn.silu(sum(padded[:, 1 + j:1 + j + s].astype(jnp.float32) * taps[j] for j in range(K))))
                # the last K inputs of the real tokens: zeros before the sequence's start
                last = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K, axis=0))(padded, length)
                windows = pool["conv"].at[ll, rows].set(last.reshape(b, K * C))
            with jax.named_scope("chunk_scan"):
                o, new = gated_delta.gated_delta_chunked(q, k, v, g, beta, live)
                states = pool["state"].at[ll, rows].set(new)
            positions_seen = pool["state_pos"].at[ll, rows].set(length.astype(jnp.int32))
        pool = {**pool, "state": states, "conv": windows, "state_pos": positions_seen}
        with jax.named_scope("gate"):
            # the gate stays flat: cut into heads of 192 lanes, its whole matrix was re-laid for it (0.5 GB)
            y = rms_norm(o, w("gdn_onorm"), eps).reshape(b, s, H * dv) * jax.nn.silu(z.astype(jnp.float32))
            return y.astype(x.dtype) @ w("gdn_out"), pool

    def attn(x, pool, fi):
        """The full-attention mixer of full layer ``fi``: (out, pool). A decode
        step is GPT-J's ``attend`` over the pool; a prefill starts at position
        0, so it writes its rows and attends to them as they come (no gather
        out of a pool whose layer is 0.6 GB)."""
        w = at(fi)

        def stored_heads(t):  # (B, S, heads, hd) -> (B, S, stored, hd), the spare heads zeros
            return jnp.pad(t, ((0, 0), (0, 0), (0, stored - heads), (0, 0)))

        with jax.named_scope("attn"):
            q = rms_norm(x @ w("wq"), w("q_norm"), eps).reshape(b, s, heads, hd)
            k = rms_norm(x @ w("wk"), w("k_norm"), eps).reshape(b, s, heads, hd)
            v = (x @ w("wv")).reshape(b, s, heads, hd)
        if decode:
            att, kv = attend_pool(stored_heads(q), stored_heads(k), stored_heads(v), pool, li=fi, step=step,
                                  rows=table_rows)
            att = att[:, :, :heads]
        else:
            kv = pool["kv"]
            with jax.named_scope("paged_scatter"):
                for plane, t in enumerate((k, v)):
                    kv = kv.at[fi, plane, step.write_slots].set(stored_heads(t).reshape(b * s, stored, hd))
            kv = {"kv": kv}
            with jax.named_scope("paged_attn"):
                att = attention(q, k, v, causal=True)
        with jax.named_scope("attn"):
            return att.reshape(b, s, heads * hd) @ w("wo"), {**pool, **kv}

    @jax.named_scope("block")
    def period_layers(x, pool, li):
        """Layers ``li .. li + len(period)``; ``li`` a period's first layer."""
        p = li // len(period)
        seen = {LINEAR: 0, FULL: 0}
        for j, kind in enumerate(period):
            own = p * period.count(kind) + seen[kind]  # this layer among its kind's
            seen[kind] += 1
            if kind == LINEAR:
                with jax.named_scope("gdn"):
                    mixed, pool = gdn(x, pool, own)
            else:
                mixed, pool = attn(x, pool, own)
            w = at(li + j)
            h = x + rms_norm(mixed, w("mixer_norm"), eps)
            with jax.named_scope("mlp"):
                ff = swiglu(h @ w("w_gate"), h @ w("w_up")) @ w("w_down")
            x = h + rms_norm(ff, w("mlp_norm"), eps)
        return x, pool

    return [(period_layers, cfg.num_hidden_layers, len(period))]
