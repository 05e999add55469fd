"""Phi-4-mini-flash-reasoning's language model (``model_type`` ``phi4flash``;
SambaY with differential attention, arXiv:2507.06607): the fifth model kind
``serve.llm`` runs, a decoder-hybrid-decoder. Five kinds of mixer, by layer
index ``i`` of ``L`` (published: 32):

    i even, i <= L/2      a **state-space** (Mamba) mixer; layer L/2's scan
                          output ``m`` (before its gate) is handed on
    i odd,  i <  L/2      **window** attention over positions t-W+1 .. t
    i = L/2 + 1           **full** attention; its K and V are the shared cache
    i even, i >= L/2 + 2  a **gated memory unit**: W_out(m * silu(W_in u))
    i odd,  i >= L/2 + 3  **cross** attention: a query alone, over layer
                          L/2 + 1's K and V

Every attention is differential (``ops/window_attention.py``) and **none has a
rotary**: positions reach them through the state-space layers. Every layer is
a pre-norm block, LayerNorm with weight and bias:

    h = x + mixer(LN(x));    y = h + W_d(u * silu(g)),  [g, u] = W_gu LN(h)

This module gives ``models/paged.py`` a kind's four things, and its layers as
**three sections of a period of two**: (state-space, window) x L/4;
(state-space L/2, full L/2 + 1) once; (gated memory, cross) x (L/4 - 1), which a
prefill runs **on its last position alone**: a gated memory unit needs ``m`` at
its own position and a cross layer the shared cache, both of which the first
two sections have left behind, so the second half of the model costs a prefill
one position (``paged.Carried``: ``m`` rides in the scans' carry; the fourth
entry of the last section).

**The pool holds three kinds of cache** behind one block table:

* ``kv`` (1, 2, slots x K/V pairs, 128): **one** layer's rows a position, the
  keys in plane 0 and the values in plane 1 of one array
  (``paged_decode_attention`` brings a block's keys and values in under one
  copy), written by the full layer and read by it and every cross layer (eight
  attentions a decode step over one cache). A K/V pair ``[k1; k2]`` is stored as
  one head of 128; a plane is *flat* (a slot's ten pairs are ten consecutive
  rows) because ten heads are no whole sublane tile and a (slots, 10, 128) array
  is padded to 16 on the device. ``paged_decode_attention`` reads it with the
  queries ``[q1; 0]`` and ``[0; q2]``; the subtraction is here. On a TPU the
  full layer's call writes the decode step's own row too, into the cache it
  scores; elsewhere, and in every prefill, rows are scattered and a decode
  step's gathered back (``models/flat_kv.py``, the flat pool's one owner:
  ``write_rows``, ``gather_rows``).
* ``ring_k``, ``ring_v`` (window layers, state rows, W x K/V pairs, 128): a
  **ring** of the last W positions' rows a sequence a window layer, in the
  sequence's state row. Position p lies at ``p % W``; a decode step attends
  over ``min(p + 1, W)`` rows, its own among them: on a TPU
  ``ring_window_attention`` takes the position's K and V, puts them at
  ``p % W`` of the ring it has brought in, scores it and writes that row back,
  the rings its outputs in place; elsewhere the row is scattered into the ring
  (``_write_spans``) and the ring gathered. A prefill attends over its own
  prompt in a band and leaves its last W rows, one scattered window a ring. A
  ring's size does not grow, nothing is allocated or released while a sequence
  decodes, and a write at one position twice is the same row.
* ``state`` (state-space layers, state rows, N, d_in) float32, ``conv``
  (.., K x d_in), ``state_pos``: the recurrent state, the short convolution's
  window and the count of positions consumed, as ``models/olmo_hybrid.py`` keeps
  them and under its rule for a decode step dispatched twice at one position.

A prefill starts from an empty state: no chunked prefill, no prefix reuse.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import flat_kv, paged
from ray_tpu.ops import selective_scan
from ray_tpu.ops.gated_delta import short_conv_step
from ray_tpu.ops.layers import layer_norm, rms_norm
from ray_tpu.ops.paged_attention import can_use_paged_kernel, paged_decode_attention
from ray_tpu.ops.window_attention import (can_use_ring_kernel, diff_attention_prefill, diff_attention_rows,
                                          ring_window_attention, split_queries, write_spans as _write_spans)

@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """Published keys (microsoft ``config.json`` names) and, below them, the
    sizes the config does not carry (the published modeling file's constants)."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    sliding_window: int = 512
    mb_per_layer: int = 2
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    ssm_state_size: int = 16
    ssm_conv_kernel: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0  # 0: hidden_size / 16
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not self.ssm_dt_rank:
            object.__setattr__(self, "ssm_dt_rank", self.hidden_size // 16)
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8 or self.mb_per_layer != 2:
            raise ValueError(f"{self.num_hidden_layers} layers at mb_per_layer {self.mb_per_layer}: the program runs "
                             "a state-space layer every second layer of a multiple of four layers, eight at the least")
        if self.num_attention_heads != 2 * self.num_key_value_heads or self.num_key_value_heads % 2:
            raise ValueError("the program runs differential attention over pairs: two query heads a K/V head, "
                             "an even number of K/V heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("the program runs heads that fill hidden_size")
        if not self.tie_word_embeddings or self.mlp_bias or self.lm_head_bias:
            raise ValueError("the program ties the embedding and runs no bias in the MLP or the head")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    d_inner = property(lambda self: self.ssm_expand * self.hidden_size)
    # a differential pair is two heads: a stored K/V head, and a query pair, is 2 x 64 = 128 wide
    pair_dim = property(lambda self: 2 * (self.hidden_size // self.num_attention_heads))
    q_pairs = property(lambda self: self.num_attention_heads // 2)
    kv_pairs = property(lambda self: self.num_key_value_heads // 2)
    n_ssm = property(lambda self: self.num_hidden_layers // 4 + 1)
    n_window = property(lambda self: self.num_hidden_layers // 4)
    n_cross = property(lambda self: self.num_hidden_layers // 4 - 1)  # and as many gated memory units
    n_attention = property(lambda self: self.num_hidden_layers // 2)  # window, full, cross: every odd layer

    def final_norm(self, params, x):
        """The model's last norm, a LayerNorm with a bias: ``paged.head``'s."""
        return layer_norm(x, params["final_norm"], params["final_norm_b"], self.layer_norm_eps)


def init_params(key, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own,
    ``benchmarks/families/phi4flash.py``, and says why each choice):
    1/sqrt(fan-in), the embedding 0.02, norms 1 and 0, ``A`` the published
    1..N a state dimension, the step ``dt`` log-uniform in 0.0005-0.01,
    ``D_skip`` 1, the four ``lam`` vectors 0.2."""
    L, D, F, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    d_in, N, K, R = cfg.d_inner, cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_dt_rank
    Ls, Lw, Lc, La = cfg.n_ssm, cfg.n_window, cfg.n_cross, cfg.n_attention
    q, kv = cfg.q_pairs * cfg.pair_dim, cfg.kv_pairs * cfg.pair_dim
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    dt = jnp.exp(jax.random.uniform(next(keys), (Ls, d_in), jnp.float32, jnp.log(0.0005), jnp.log(0.01)))
    ones, zeros = (lambda *s: jnp.ones(s, jnp.float32)), (lambda *s: jnp.zeros(s, jnp.float32))
    return {
        "embed": normal((V, D), 0.02),
        "ln1_w": ones(L, D), "ln1_b": zeros(L, D), "ln2_w": ones(L, D), "ln2_b": zeros(L, D),
        "w_gu": normal((L, D, 2 * F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
        "ssm_in": normal((Ls, D, 2 * d_in), D ** -0.5),
        "ssm_conv": normal((Ls, K, d_in), K ** -0.5),
        "ssm_conv_b": zeros(Ls, d_in),
        "ssm_x": normal((Ls, d_in, R + 2 * N), d_in ** -0.5),
        "ssm_dt": normal((Ls, R, d_in), R ** -0.5),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.broadcast_to(jnp.log(jnp.arange(1.0, N + 1.0))[None, :, None], (Ls, N, d_in)),
        "ssm_d": ones(Ls, d_in),
        "ssm_out": normal((Ls, d_in, D), d_in ** -0.5),
        "wqkv": normal((Lw + 1, D, q + 2 * kv), D ** -0.5), "bqkv": zeros(Lw + 1, q + 2 * kv),
        "wq": normal((Lc, D, q), D ** -0.5), "bq": zeros(Lc, q),
        "wo": normal((La, q, D), q ** -0.5), "bo": zeros(La, D),
        "lam": normal((La, 4, cfg.pair_dim // 2), 0.2, jnp.float32),
        "subln": ones(La, cfg.pair_dim),
        "gmu_in": normal((Lc, D, d_in), D ** -0.5),
        "gmu_out": normal((Lc, d_in, D), d_in ** -0.5),
        "final_norm": ones(D), "final_norm_b": zeros(D),
    }


def init_paged_pool(cfg: Phi4FlashConfig, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """The three kinds of cache (module docstring). ``state_rows`` counts the
    null row: the engine asks for ``max_batch + 1``."""
    wide, pairs = cfg.pair_dim, cfg.kv_pairs
    ring = (cfg.n_window, state_rows, cfg.sliding_window * pairs, wide)
    return {
        "kv": flat_kv.init_pool(1, num_blocks, block_size, pairs, wide, cfg.dtype),
        "ring_k": jnp.zeros(ring, cfg.dtype), "ring_v": jnp.zeros(ring, cfg.dtype),
        "state": jnp.zeros((cfg.n_ssm, state_rows, cfg.ssm_state_size, cfg.d_inner), jnp.float32),
        "conv": jnp.zeros((cfg.n_ssm, state_rows, cfg.ssm_conv_kernel * cfg.d_inner), cfg.dtype),
        "state_pos": jnp.zeros((cfg.n_ssm, state_rows), jnp.int32),
    }


def paged_block_bytes(cfg: Phi4FlashConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows of **one** layer, the
    full layer's, which every cross layer reads."""
    return flat_kv.block_bytes(1, block_size, cfg.kv_pairs, cfg.pair_dim, cfg.dtype)


def paged_ring(cfg: Phi4FlashConfig) -> Dict[str, int]:
    """The rings of a state row: ``rows`` a ring (the window) and ``bytes``
    over the window layers, K and V. A kind that gives this has its decode
    steps' ``ring_rows`` counted by the engine."""
    row = cfg.kv_pairs * cfg.pair_dim * jnp.dtype(cfg.dtype).itemsize
    return {"rows": cfg.sliding_window, "bytes": 2 * cfg.n_window * cfg.sliding_window * row}


def paged_state_bytes(cfg: Phi4FlashConfig) -> int:
    """Bytes one state row holds: the window layers' rings, and over the
    state-space layers the state, the convolution's window and the position
    count. A kind that gives this wants a row a sequence."""
    state = cfg.ssm_state_size * cfg.d_inner * 4
    window = cfg.ssm_conv_kernel * cfg.d_inner * jnp.dtype(cfg.dtype).itemsize
    return paged_ring(cfg)["bytes"] + cfg.n_ssm * (state + window + 4)


def paged_layer(cfg: Phi4FlashConfig, params, step):
    """The model's three sections for one call of a paged program, with the
    memory ``m`` as the carry's third leaf (module docstring)."""
    eps, dtype = cfg.layer_norm_eps, cfg.dtype
    L, W, G, pairs, wide = cfg.num_hidden_layers, cfg.sliding_window, cfg.kv_pairs, cfg.q_pairs, cfg.pair_dim
    d_in, N, K, R = cfg.d_inner, cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_dt_rank
    scale = (wide // 2) ** -0.5
    b, s = step.positions.shape
    rows, live, bs = step.state_rows, step.live.reshape(b, s), step.block_size
    decode = s == 1
    dot32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    scan_kernel = decode and selective_scan.can_use_selective_scan_kernel(d_in, N)
    ring_kernel = decode and can_use_ring_kernel(W, G, wide, dtype)
    decays = -jnp.exp(params["ssm_a_log"].astype(jnp.float32))  # A, every state-space layer's: once a call

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def normed(x, li, which):
        w = at(li)
        return layer_norm(x, w(which + "_w"), w(which + "_b"), eps)

    def block(x, mixed, li):
        """The residual around a mixer's output and the layer's MLP."""
        h = x + mixed.astype(x.dtype)
        with jax.named_scope("mlp"):
            g, u = jnp.split(normed(h, li, "ln2") @ at(li)("w_gu"), 2, axis=-1)
            return h + (u * jax.nn.silu(g)) @ at(li)("w_down")

    # -- the state-space mixer ---------------------------------------------------------

    def ssm(u, pool, si):
        """State-space layer ``si``'s mixer over ``u`` = LN(x): (out, pool,
        the scan's output before the gate (B, S, d_in) float32)."""
        w = at(si)
        with jax.named_scope("proj"):
            xs, z = jnp.split(u @ w("ssm_in"), 2, axis=-1)
        taps, bias = w("ssm_conv"), w("ssm_conv_b")
        if decode:
            # who holds which row, the position each row's sequence is at, which rows take this step
            # (``models/olmo_hybrid.py``: a step dispatched twice at one position)
            seen = pool["state_pos"][si]
            owner = (rows[None, :] == jnp.arange(len(seen))[:, None]) & live[None, :, 0]
            at_row = jnp.sum(jnp.where(owner, step.positions[None, :, 0], 0), axis=1)
            advance_rows = jnp.any(owner, axis=1) & (seen == at_row)
            seen = jnp.where(advance_rows, at_row + 1, seen)
            advance = jnp.any(owner & advance_rows[:, None], axis=0)
            with jax.named_scope("conv"):
                c, windows = short_conv_step(pool["conv"][si], xs[:, 0], taps, owner, advance_rows, bias)
                c, windows = c[:, None], pool["conv"].at[si].set(windows)
        else:
            length = jnp.sum(live, axis=1)
            with jax.named_scope("conv"):
                padded = jnp.pad(xs, ((0, 0), (K, 0), (0, 0)))
                # position t at index t + K: its K inputs are indices t + 1 .. t + K
                c = jax.nn.silu(bias + sum(padded[:, 1 + j:1 + j + s].astype(jnp.float32) * taps[j].astype(jnp.float32)
                                           for j in range(K)))
                # the last K inputs of the real tokens: zeros before the sequence's start
                last = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K, axis=0))(padded, length)
                windows = pool["conv"].at[si, rows].set(last.reshape(b, K * d_in))
        with jax.named_scope("gates"):
            low, bm, cm = jnp.split(dot32("bsc,cr->bsr", c.astype(dtype), w("ssm_x")), [R, R + N], axis=-1)
            dl = jax.nn.softplus(dot32("bsr,rc->bsc", low.astype(dtype), w("ssm_dt")) + w("ssm_dt_b"))
            dl = jnp.where(live[..., None], dl, 0.0)  # a padded position passes the state through
        if decode:
            with jax.named_scope("update"):
                if scan_kernel:
                    y, states = selective_scan.selective_scan_update(
                        pool["state"], si, rows, advance, c[:, 0], dl[:, 0], bm[:, 0], cm[:, 0], decays)
                else:
                    _, new = selective_scan.ssm_step(pool["state"][si, rows], c[:, 0], dl[:, 0], bm[:, 0], cm[:, 0],
                                                     decays[si], advance)
                    states = pool["state"].at[si, rows].set(new)
                    y = selective_scan.ssm_read(states[si, rows], cm[:, 0])  # from the state as stored, as a replay reads it
                y, positions_seen = y[:, None], pool["state_pos"].at[si].set(seen)
        else:
            with jax.named_scope("scan"):
                y, new = selective_scan.selective_scan_chunked(c, dl, bm, cm, decays[si])
                states = pool["state"].at[si, rows].set(new)
            positions_seen = pool["state_pos"].at[si, rows].set(length.astype(jnp.int32))
        pool = {**pool, "state": states, "conv": windows, "state_pos": positions_seen}
        with jax.named_scope("gate"):
            y = y + w("ssm_d") * c
            return (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype) @ w("ssm_out"), pool, y

    # -- the three attentions ------------------------------------------------------------

    def lam_of(vectors, li):
        """(the layer's ``lam``, its ``lam0``), float32 scalars."""
        lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * li.astype(jnp.float32))
        lq1, lk1, lq2, lk2 = vectors.astype(jnp.float32)
        return jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0, lam0

    def out_of(o, ai, lam0):
        """``o`` (B, S, pairs, 128) float32 through the norm after the
        subtraction, its constant factor, and ``W_o``."""
        w = at(ai)
        y = rms_norm(o, w("subln"), eps) * (1.0 - lam0)
        return y.reshape(b, s, pairs * wide).astype(dtype) @ w("wo") + w("bo").astype(dtype)

    def over_shared_cache(qp, pool, lam, **new):
        """One query position a sequence (``qp`` (B, pairs, 128)) over the
        shared cache's positions [0, length): the paged kernel on the packed
        pairs where it can run, the table's rows gathered elsewhere. -> (o, the
        pool). ``new_k``, ``new_v`` (B, G, 128): the position's own row, which
        the kernel writes into the cache before it scores it; where no kernel
        runs the caller has scattered it."""
        kv = pool["kv"]
        if can_use_paged_kernel(qp[:, None], kv, bs, G):
            packed = jnp.stack(split_queries(qp), axis=2).reshape(b, 2 * pairs, wide)
            o = paged_decode_attention(packed, kv, 0, step.block_tables, step.lengths, block_size=bs, kv_heads=G,
                                       scale=scale, **new)
            if new:
                o, kv = o
                pool = {**pool, "kv": kv}
            o = o.reshape(b, pairs, 2, wide).astype(jnp.float32)
            return o[:, :, 0] - lam * o[:, :, 1], pool
        return diff_attention_rows(qp, *flat_kv.gather_rows(kv, 0, step, G), lam, scale=scale), pool

    def own_attention(u, pool, ai, li, window: bool):
        """Attention ``ai`` (a window layer, or the full layer) of layer ``li``,
        which writes K and V of its own: (out, pool)."""
        w = at(ai)
        lam, lam0 = lam_of(w("lam"), li)
        with jax.named_scope("proj"):
            q, k, v = jnp.split(u @ w("wqkv") + w("bqkv").astype(dtype), [pairs * wide, (pairs + G) * wide], axis=-1)
            qp, k, v = q.reshape(b, s, pairs, wide), k.reshape(b, s, G, wide), v.reshape(b, s, G, wide)
        if window:
            ring = {"ring_k": pool["ring_k"], "ring_v": pool["ring_v"]}
            if decode:
                at_row, held = step.positions[:, 0] % W, jnp.minimum(step.lengths, W)
                if ring_kernel:  # the kernel puts the row in its ring and scores the ring with it there
                    with jax.named_scope("ring_attn"):
                        o, ring["ring_k"], ring["ring_v"] = ring_window_attention(
                            qp[:, 0], k[:, 0], v[:, 0], ring["ring_k"], ring["ring_v"], ai, rows, held, at_row, lam,
                            kv_pairs=G, scale=scale)
                else:
                    with jax.named_scope("ring_scatter"):
                        ring = {name: _write_spans(ring[name], (ai, rows), at_row * G, t[:, 0])
                                for name, t in (("ring_k", k), ("ring_v", v))}
                    with jax.named_scope("ring_attn"):
                        mine = [ring[name][ai, rows].reshape(b, W, G, wide) for name in ("ring_k", "ring_v")]
                        o = diff_attention_rows(qp[:, 0], *mine, jnp.arange(W)[None, :] < held[:, None], lam, scale=scale)
                o = o[:, None]
            else:
                with jax.named_scope("ring_attn"):
                    o = diff_attention_prefill(qp, k, v, lam, scale=scale, window=W)
                with jax.named_scope("ring_scatter"):
                    # row r of the ring: the last real position p with p % W == r (none: a row behind the mask)
                    n, r = jnp.sum(live, axis=1)[:, None], jnp.arange(W)[None, :]
                    source = jnp.clip(r + W * ((n - 1 - r) // W), 0, s - 1)[:, :, None, None]
                    ring = {name: _write_spans(ring[name], (ai, rows), jnp.zeros((b,), jnp.int32),
                                               jnp.take_along_axis(t, source, axis=1).reshape(b, W * G, wide))
                            for name, t in (("ring_k", k), ("ring_v", v))}
            pool = {**pool, **ring}
        else:
            if not (decode and can_use_paged_kernel(qp, pool["kv"], bs, G)):  # else the kernel puts the row in the cache
                pool = {**pool, "kv": flat_kv.write_rows(pool["kv"], 0, step, k, v, G)}
            with jax.named_scope("paged_attn"):
                if decode:
                    o, pool = over_shared_cache(qp[:, 0], pool, lam, new_k=k[:, 0], new_v=v[:, 0])
                    o = o[:, None]
                else:
                    o = diff_attention_prefill(qp, k, v, lam, scale=scale)
        with jax.named_scope("out"):
            return out_of(o, ai, lam0), pool

    def cross_attention(u, pool, ci, ai, li):
        """Cross layer ``ci`` (attention ``ai``, layer ``li``): a query alone,
        over the shared cache. One position a sequence: a decode step, or a
        prefill's last position (a prompt's every position, where its section
        is not cut: the prompt's rows as the full layer wrote them)."""
        w = at(ci)
        lam, lam0 = lam_of(at(ai)("lam"), li)
        with jax.named_scope("proj"):
            qp = (u @ w("wq") + w("bq").astype(dtype)).reshape(b, s, pairs, wide)
        with jax.named_scope("paged_attn"):
            if decode:
                o = over_shared_cache(qp[:, 0], pool, lam)[0][:, None]
            else:  # every position of a prompt (a prefill cuts to its last: only a test asks for this)
                mine = step.write_slots[:, None] * G + jnp.arange(G)
                k, v = pool["kv"][0][:, mine].reshape(2, b, s, G, wide)
                o = diff_attention_prefill(qp, k, v, lam, scale=scale)
        with jax.named_scope("out"):
            return out_of(o, ai, lam0)

    # -- the sections: two layers a call ---------------------------------------------------

    def ssm_and_attention(window: bool):
        @jax.named_scope("block")
        def layers(x, pool, m, li):
            """Layers ``li`` (state-space) and ``li + 1``: a window layer, or the
            full layer, whose state-space layer's scan output goes on as ``m``."""
            p = li // 2
            with jax.named_scope("ssm"):
                mixed, pool, y = ssm(normed(x, li, "ln1"), pool, p)
            x = block(x, mixed, li)
            with jax.named_scope("window" if window else "full"):
                mixed, pool = own_attention(normed(x, li + 1, "ln1"), pool, p, li + 1, window)
            return block(x, mixed, li + 1), pool, (m if window else y)

        return layers

    @jax.named_scope("block")
    def memory_and_cross(x, pool, m, li):
        """Layers ``li`` (gated memory unit) and ``li + 1`` (cross)."""
        p = (li - (L // 2 + 2)) // 2
        with jax.named_scope("gmu"):
            w = at(p)
            gate = jax.nn.silu((normed(x, li, "ln1") @ w("gmu_in")).astype(jnp.float32))
            mixed = (m * gate).astype(dtype) @ w("gmu_out")
        x = block(x, mixed, li)
        with jax.named_scope("cross"):
            mixed = cross_attention(normed(x, li + 1, "ln1"), pool, p, cfg.n_window + 1 + p, li + 1)
        return block(x, mixed, li + 1), pool, m

    return paged.Carried(
        [(ssm_and_attention(True), L // 2, 2), (ssm_and_attention(False), 2, 2), (memory_and_cross, L // 2 - 2, 2, True)],
        jnp.zeros((b, s, d_in), jnp.float32))
