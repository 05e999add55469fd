"""LFM2-24B-A2B's language model (``model_type`` ``lfm2_moe``): the eighth
model kind ``serve.llm`` runs. Three **gated short-convolution** layers to
every **full-attention** layer (``layer_types``: conv, conv, full_attention,
conv; layer ``i`` is full where ``i % 4 == 2``), over a feed-forward half that
is a dense SwiGLU in the ``num_dense_layers`` leading layers and sigmoid-routed
experts in the rest, with no shared expert. ``N`` is RMSNorm with a weight:

    h = x + Op_l(N(x))         Op_l a short convolution or an attention, by the layer's type
    y = h + FF_l(N(h))         FF_l a SwiGLU MLP (l < num_dense_layers), else sum_k w_k E_k(u)
    logits = E^T N(y_L)        the head is the embedding's transpose

    ShortConv(u): [B | C | z] = u W_in                 three slices of D
                  s_t = B_t * z_t;  c_t = sum_{j<K} w_j * s_{t-K+1+j}    depthwise, causal, width
                                                       ``conv_L_cache``, no activation, no bias
                  out = (C_t * c_t) W_out
    Attn(u):      q = W_q u as H heads of d = D / H, k = W_k u, v = W_v u as G heads; an RMSNorm over
                  the d values of every q and k head (one weight for all q heads, one for all k heads);
                  a rotary over half-split pairs (j, j + d/2), theta ``rope_theta``;
                  softmax(q_h . k_{h // (H/G)} / sqrt(d)) in float32 over positions 0 .. t; W_o
    Experts(v):   s = sigmoid(W_r v); the top-k of s + b chosen (``expert_bias`` moves the choice and
                  never the weights); w = scale * s[chosen] / (sum of the chosen s + 1e-6)

The expert layer is ``models/moe.py`` under ``route_sigmoid`` with this
model's epsilon, this chip's share of the routed experts (all of them, as
served: 64 experts of 18.9 MB fit a chip). Key names follow the published
``config.json``.

This module gives ``models/paged.py`` a kind's things, and its layers as
**sections of whole periods**, as ``models/exaone_moe.py`` lays out its own:
the dense layers and the expert layers are each cut at the period's boundaries
into a run up to the next boundary, whole periods and a rest (published: (conv
conv) dense, (full conv) experts, (conv conv full conv) x 9 experts). What every
layer has (the two norms) is stacked over all layers; the convolutions' tensors
over the conv layers, the attentions' over the full layers, the dense MLPs' and
the expert layers' over theirs, each read by the layer's index among its kind.

**The pool holds two kinds of cache** behind one block table, and the routing
counts:

* ``kv`` (full layers, 2, slots x G / P, P x d): the full layers' rows a
  position in the flat pool (``models/flat_kv.py``: its format, how a call's
  rows are written and read back), **P K/V heads to a row** (``kv_pack``: two
  heads of 64 fill the 128 lanes ``ops/paged_attention.py`` scores; a position
  is G / P consecutive rows). Query head ``h`` is handed to the kernel as a row
  of P x d values that is zero outside the part of its own K/V head, so ``q .
  row`` is ``q . k`` to the bit; the kernel groups query heads over rows as it
  groups them over K/V heads (``h // (H / G x P)``), and of the output row the
  part of the head's own K/V head is kept. That call, which on a TPU writes a
  decode step's own row in the packed form, is this module's; elsewhere, and
  in every prefill, ``flat_kv.attend`` scatters the rows and a decode step
  gathers its table's back into heads. Full layer ``i`` is the pool's layer
  ``i // 4``, and a block holds the full layers' rows alone
  (``paged_block_bytes``).
* ``conv`` (conv layers, state rows, K x D) and ``state_pos`` (conv layers,
  state rows): the convolution's window of a sequence, its last K products
  ``B * z`` with the current one among them, flat in the lanes, in the
  sequence's state row (``models/olmo_hybrid.py`` says how a row is handed out
  and why a step dispatched twice at one position must leave it as it was:
  ``state_pos`` counts the positions a window has taken in).
* ``moe_counts``: ``moe.COUNTS`` summed over the expert layers and decode steps.

A prefill starts from empty windows: no chunked prefill, no prefix reuse.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import flat_kv, moe, paged
from ray_tpu.models.moe import routing_counts  # noqa: F401 - the engine asks the kind's module for it
from ray_tpu.ops.gated_delta import short_conv_step
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_tables, swiglu
from ray_tpu.ops.paged_attention import can_use_paged_kernel, paged_decode_attention

PERIOD = ("conv", "conv", "full_attention", "conv")
ROUTER_SCALE = 1.5
BIAS_SCALE = 1.5e-3
ROUTE_EPS = 1e-6  # the published rule divides by the chosen scores' sum + 1e-6
ROUTE = functools.partial(moe.route_sigmoid, eps=ROUTE_EPS)  # the published routing rule
LANES = 128


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Published keys (LiquidAI ``config.json`` names) plus this chip's share
    of each expert layer's routed experts: ``experts_held`` of the
    ``num_experts``, from ``expert_offset`` (all of them where none is named).
    Of the keys that choose a path the program runs what the checkpoint states
    and refuses the rest."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: Optional[Tuple[str, ...]] = None  # None: the period, ``num_hidden_layers`` long
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    tie_word_embeddings: bool = True
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.num_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        period = tuple(PERIOD[i % len(PERIOD)] for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", period if self.layer_types is None else tuple(self.layer_types))
        if self.layer_types != period:
            raise ValueError(f"layer_types {self.layer_types}: the program runs the period {PERIOD} from layer 0 on")
        if not 0 <= self.expert_offset <= self.num_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.num_experts}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError(f"{self.num_dense_layers} dense layers of {self.num_hidden_layers}")
        if not (self.norm_topk_prob and self.use_expert_bias) or self.conv_bias or not self.tie_word_embeddings:
            raise ValueError(f"norm_topk_prob {self.norm_topk_prob}, use_expert_bias {self.use_expert_bias}, conv_bias "
                             f"{self.conv_bias}, tie_word_embeddings {self.tie_word_embeddings}: the program renormalises "
                             "the chosen sigmoid scores, chooses under a bias, convolves without one and ties its head")
        if (self.hidden_size % self.num_attention_heads or self.num_attention_heads % self.num_key_value_heads
                or self.head_dim % 2):
            raise ValueError("the program runs whole groups of query heads a K/V head, and a rotary over half-split pairs")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    n_expert_layers = property(lambda self: self.num_hidden_layers - self.num_dense_layers)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    rms_norm_eps = property(lambda self: self.norm_eps)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    n_full = property(lambda self: self.layer_types.count("full_attention"))
    n_conv = property(lambda self: self.layer_types.count("conv"))
    kv_row = property(lambda self: self.num_key_value_heads * self.head_dim)  # values of one position's K (or V)

    @property
    def kv_pack(self) -> int:
        """K/V heads to a row of the pool: as many as fill the lanes (two heads
        of 64), where the heads part into whole rows; else one."""
        pack = max(1, min(self.num_key_value_heads, LANES // self.head_dim))
        return pack if self.num_key_value_heads % pack == 0 else 1


def is_full(li: int) -> bool:
    """Whether layer ``li`` is a full-attention layer."""
    return PERIOD[li % len(PERIOD)] == "full_attention"


def init_params(key, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own and
    says why each, ``benchmarks/families/lfm2_moe.py``): 1/sqrt(fan-in), the
    embedding 0.02, the dense paths' projections into the residual stream
    (``conv_out``, ``wo``, ``w_down``) scaled down by sqrt(2 x layers), norms 1,
    the taps 1/sqrt(K), the router's columns ``ROUTER_SCALE`` / sqrt(D), the
    choice bias ``BIAS_SCALE`` x normal. ``conv_in`` is B's, C's and z's
    columns side by side; ``wqkv`` q's, k's and v's, a head's d values
    together. No ``unembed``: the head is the embedding's transpose."""
    L, K, D, H, G, d = (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim)
    F, Fe, W = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.conv_L_cache
    E, held, n, nc, nf = L - K, cfg.experts_held, cfg.num_experts, cfg.n_conv, cfg.n_full
    keys = iter(jax.random.split(key, 24))
    s_res = (2 * L) ** -0.5

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    return {
        "embed": normal((cfg.vocab_size, D), 0.02),
        "op_norm": jnp.ones((L, D), jnp.float32), "ffn_norm": jnp.ones((L, D), jnp.float32),
        "conv_in": normal((nc, D, 3 * D), D ** -0.5), "conv_w": normal((nc, W, D), W ** -0.5),
        "conv_out": normal((nc, D, D), D ** -0.5 * s_res),
        "wqkv": normal((nf, D, (H + 2 * G) * d), D ** -0.5),
        "q_norm": jnp.ones((nf, d), jnp.float32), "k_norm": jnp.ones((nf, d), jnp.float32),
        "wo": normal((nf, H * d, D), (H * d) ** -0.5 * s_res),
        "w_gate": normal((K, D, F), D ** -0.5), "w_up": normal((K, D, F), D ** -0.5),
        "w_down": normal((K, F, D), F ** -0.5 * s_res),
        "router": normal((E, D, n), D ** -0.5 * ROUTER_SCALE),
        "router_bias": normal((E, n), BIAS_SCALE, jnp.float32),
        "e_gate": normal((E, held, D, Fe), D ** -0.5), "e_up": normal((E, held, D, Fe), D ** -0.5),
        "e_down": normal((E, held, Fe, D), Fe ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
    }


def init_paged_pool(cfg: Lfm2MoeConfig, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """The two kinds of cache and the routing counts (module docstring).
    ``state_rows`` counts the null row: the engine asks for ``max_batch + 1``."""
    P = cfg.kv_pack
    return {
        "kv": flat_kv.init_pool(cfg.n_full, num_blocks, block_size, cfg.num_key_value_heads // P, P * cfg.head_dim, cfg.dtype),
        "conv": jnp.zeros((cfg.n_conv, state_rows, cfg.conv_L_cache * cfg.hidden_size), cfg.dtype),
        "state_pos": jnp.zeros((cfg.n_conv, state_rows), jnp.int32),
        "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32),
    }


def paged_block_bytes(cfg: Lfm2MoeConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows of the full layers
    alone (a conv layer keeps nothing a position)."""
    return flat_kv.block_bytes(cfg.n_full, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype)


def paged_state_bytes(cfg: Lfm2MoeConfig) -> int:
    """Bytes one state row holds: the conv layers' windows and position
    counts, and nothing of the full layers."""
    return cfg.n_conv * (cfg.conv_L_cache * cfg.hidden_size * jnp.dtype(cfg.dtype).itemsize + 4)


def own_part(cfg: Lfm2MoeConfig) -> np.ndarray:
    """(H, P) bool: the part of a pool row that holds query head ``h``'s K/V
    head (``(h // (H / G)) % P``; the row itself is ``h // (H / G x P)``, which
    is how the paged kernel groups query heads over rows)."""
    H, G, P = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.kv_pack
    return (np.arange(H)[:, None] // (H // G)) % P == np.arange(P)[None, :]


def pack_queries(cfg: Lfm2MoeConfig, q):
    """q (B, H, d) -> (B, H, P x d): each head's values in the part of its own
    K/V head and zeros in the rest, so that its product with a pool row is its
    product with that head's key, to the bit."""
    b, H, d = q.shape
    return jnp.where(own_part(cfg)[None, :, :, None], q[:, :, None, :], 0).reshape(b, H, cfg.kv_pack * d)


def unpack_outputs(cfg: Lfm2MoeConfig, o):
    """The paged kernel's output rows (B, H, P x d) -> (B, H, d): of each
    head's weighted sum of whole rows, the part of its own K/V head."""
    b, H, wide = o.shape
    parts = o.reshape(b, H, cfg.kv_pack, wide // cfg.kv_pack)
    return jnp.sum(jnp.where(own_part(cfg)[None, :, :, None], parts, 0), axis=2)


def _qkv(cfg: Lfm2MoeConfig, w, u, rope):
    """``u`` (B, S, D) through the layer's fused projection, the per-head norms
    and the rotary: q (B, S, H, d), k, v (B, S, G, d). Norm and rotary in
    float32, rounded once."""
    b, s, _ = u.shape
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = jnp.split(u @ w("wqkv"), [H * d, (H + G) * d], axis=-1)
    q = rms_norm(q.reshape(b, s, H, d).astype(jnp.float32), w("q_norm"), cfg.norm_eps)
    k = rms_norm(k.reshape(b, s, G, d).astype(jnp.float32), w("k_norm"), cfg.norm_eps)
    q, k = (apply_rope(x.reshape(b * s, -1, d), *rope).reshape(x.shape) for x in (q, k))
    return q.astype(u.dtype), k.astype(u.dtype), v.reshape(b, s, G, d)


def _expert_ffn(cfg: Lfm2MoeConfig, w, stacks, u, layer, live):
    """The expert layer's feed-forward half over ``u`` (T, D): (routed,
    counts). ``w`` reads the layer's own router and bias, ``stacks`` holds
    ``e_gate``, ``e_up``, ``e_down`` (stacked over layers where ``layer`` is
    not None)."""
    with jax.named_scope("moe"):
        return moe.expert_layer(
            {**stacks, "router": w("router"), "router_bias": w("router_bias")}, u, layer=layer,
            n_routed=cfg.num_experts, top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            expert_offset=cfg.expert_offset, live=live, rule=ROUTE)


def paged_layer(cfg: Lfm2MoeConfig, params, step):
    """The model's sections for one call of a paged program (module
    docstring): the dense layers', then the expert layers', each cut at the
    period's boundaries. A decode step's expert layers add their routing counts
    to the pool's."""
    eps, dense_layers, dtype = cfg.norm_eps, cfg.num_dense_layers, cfg.dtype
    H, G, d, P, K, D = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.kv_pack, cfg.conv_L_cache,
                        cfg.hidden_size)
    Gp, wide = G // P, P * d  # a position's rows in the pool, and a row's values
    b, s = step.positions.shape
    rows, live, bs = step.state_rows, step.live.reshape(b, s), step.block_size
    decode = s == 1
    scale = d ** -0.5
    rope = rope_tables(step.positions, d, cfg.rope_theta)  # the same for every full layer: once a call

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def short_conv(u, pool, li):
        """Conv layer ``li``: (out (B, S, D), the pool with its windows written)."""
        ci = li - (li + 1) // len(PERIOD)  # among the conv layers: one full layer a period lies before it, from i % 4 == 2 on
        w = at(ci)
        with jax.named_scope("proj"):
            gate_in, gate_out, z = jnp.split(u @ w("conv_in"), 3, axis=-1)
            product = gate_in * z  # (B, S, D), in the served type: what the window keeps
        if decode:
            # a step advances a window only where it holds the positions before this one (a step dispatched
            # twice at one position reads the window as stored: ``models/olmo_hybrid.py``)
            seen = pool["state_pos"][ci]
            # the batch in the state rows' order: who holds which row (an inactive slot none, so the null row has
            # no owner) and the position each row's sequence is at
            owner = (rows[None, :] == jnp.arange(len(seen))[:, None]) & live[None, :, 0]
            at_row = jnp.sum(jnp.where(owner, step.positions[None, :, 0], 0), axis=1)
            advance_rows = jnp.any(owner, axis=1) & (seen == at_row)
            with jax.named_scope("conv"):
                c, windows = short_conv_step(pool["conv"][ci], product[:, 0], w("conv_w"), owner, advance_rows,
                                             activation=None)
                windows, c = pool["conv"].at[ci].set(windows), c[:, None]
            positions_seen = pool["state_pos"].at[ci].set(jnp.where(advance_rows, at_row + 1, seen))
        else:
            length = jnp.sum(live, axis=1)
            with jax.named_scope("conv"):
                padded, taps = jnp.pad(product, ((0, 0), (K, 0), (0, 0))), w("conv_w").astype(jnp.float32)
                # position t at index t + K: its K inputs are indices t + 1 .. t + K
                c = sum(padded[:, 1 + j:1 + j + s].astype(jnp.float32) * taps[j] for j in range(K))
                # the last K products of the real tokens (a padded position leaves the window alone): zeros
                # before the sequence's start
                last = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(p, n, K, axis=0))(padded, length)
                windows = pool["conv"].at[ci, rows].set(last.reshape(b, K * D))
            positions_seen = pool["state_pos"].at[ci, rows].set(length.astype(jnp.int32))
        with jax.named_scope("out"):
            out = (gate_out.astype(jnp.float32) * c).astype(dtype) @ w("conv_out")
        return out, {**pool, "conv": windows, "state_pos": positions_seen}

    def attention(u, pool, li):
        """Full layer ``li``: (out (B, S, D), the pool with its rows written)."""
        fi = li // len(PERIOD)
        w = at(fi)
        with jax.named_scope("proj"):
            q, k, v = _qkv(cfg, w, u, rope)
        kv = pool["kv"]
        packed = pack_queries(cfg, q[:, 0]) if decode else None
        if decode and can_use_paged_kernel(packed[:, None], kv, bs, Gp):
            # the kernel puts the packed row in its block and scores the blocks with it there
            with jax.named_scope("paged_attn"):
                o, kv = paged_decode_attention(
                    packed, kv, fi, step.block_tables, step.lengths, block_size=bs, kv_heads=Gp,
                    scale=scale, new_k=k[:, 0].reshape(b, Gp, wide), new_v=v[:, 0].reshape(b, Gp, wide))
                o = unpack_outputs(cfg, o)[:, None]
        else:  # its own rows, or the table's gathered back into heads
            o, kv = flat_kv.attend(kv, fi, step, q, k, v, kv_heads=Gp, scale=scale)
        with jax.named_scope("out"):
            out = o.astype(dtype).reshape(b, s, H * d) @ w("wo")
        return out, {**pool, "kv": kv}

    def operator(x, pool, li, full: bool):
        """The half every layer has: (h, N(h) as (T, D), the pool)."""
        w = at(li)
        u = rms_norm(x, w("op_norm"), eps)
        with jax.named_scope("attn" if full else "short_conv"):
            out, pool = (attention if full else short_conv)(u, pool, li)
        h = x + out
        return h, rms_norm(h, w("ffn_norm"), eps).reshape(b * s, -1), pool

    def dense_layer(x, pool, li, full):
        h, u, pool = operator(x, pool, li, full)
        own = at(li)
        with jax.named_scope("dense"):
            y = swiglu(u @ own("w_gate"), u @ own("w_up")) @ own("w_down")
        return h + y.reshape(h.shape), pool

    def expert_layer(x, pool, li, full):
        h, u, pool = operator(x, pool, li, full)
        y, counts = _expert_ffn(cfg, at(li - dense_layers), params, u, li - dense_layers, step.live)
        counts = pool["moe_counts"] + counts if decode else pool["moe_counts"]
        return h + y.reshape(h.shape), {**pool, "moe_counts": counts}

    def section(layer, lo, hi, each):
        """Layers ``lo .. hi`` as one section, ``each`` a call: a call's
        layers' kinds are those of the first ``each``, a whole number of
        periods on, every call."""
        fulls = [is_full(i) for i in range(lo, lo + each)]

        @jax.named_scope("block")
        def layers(x, pool, li):
            for j, full in enumerate(fulls):
                x, pool = layer(x, pool, li + j, full)
            return x, pool

        return layers, hi - lo, each

    return [section(layer, *run) for layer, lo, hi in ((dense_layer, 0, dense_layers),
                                                        (expert_layer, dense_layers, cfg.num_hidden_layers))
            for run in _runs(lo, hi)]


def _runs(lo: int, hi: int):
    """Layers ``lo .. hi`` cut at the period's boundaries: (first, end, layers
    a call) of the run up to the next boundary, of the whole periods and of
    the rest; an empty run is left out."""
    n = len(PERIOD)
    start = min(hi, -(-lo // n) * n)
    end = start + (hi - start) // n * n
    runs = [(lo, start, start - lo), (start, end, n), (end, hi, hi - end)]
    return [run for run in runs if run[1] > run[0]]
