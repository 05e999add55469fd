"""K-EXAONE-236B-A23B's language model (``model_type`` ``exaone_moe``): the
sixth model kind ``serve.llm`` runs. Grouped-query attention, three **window**
layers of ``sliding_window`` positions to every **full** layer
(``sliding_window_pattern`` ``LLLG``: layer ``i`` is full where ``i % 4 == 3``),
over DeepSeek-V3's feed-forward half: ``first_k_dense_replace`` dense layers
lead, expert layers follow. ``N`` is RMSNorm:

    h = x + Attn(N(x))
    y = h + F_l(N(h))      F_l a SwiGLU MLP (l < first_k_dense_replace), else
                           sum_k w_k E_k(u) + S(u): the routed experts chosen by
                           sigmoid scores with a choice bias, and a shared expert

    Attn(u): q = W_q u as H heads of d, k = W_k u, v = W_v u as G heads; an
             RMSNorm over the d values of every q and k head (``q_norm``,
             ``k_norm``); **in a window layer** a rotary over half-split pairs
             (j, j + d/2) at the absolute position, theta ``rope_theta``, and
             scores over positions t - W + 1 .. t; **in a full layer** no
             rotary and positions 0 .. t; softmax(q_h . k_{h // (H/G)} /
             sqrt(d)) in float32; W_o

The expert layer is ``models/moe.py`` under ``route_sigmoid`` (Kimi-K2's
call), this chip's share of the routed experts; the shared expert is a dense
SwiGLU here. Key names follow the published ``config.json``. The multi-token
prediction block (``num_nextn_predict_layers``) is a function of its own,
``mtp_logits``, outside the three paged programs: a step of ``serve.llm``
yields one token a sequence (ROADMAP M3), and a config that asks the served path
for it is refused.

This module gives ``models/paged.py`` a kind's four things, and its layers as
**sections of whole periods**: the dense layers and the expert layers (as
``models/kimi.py`` parts them) are each cut at the period's boundaries into a
run up to the next boundary, whole periods and a rest, a section each, whose
body is that many layers a call with every layer's attention chosen where the
program is traced (published: (dense W), (expert W W F), (expert W W W F) x 11).
A ``lax.cond`` on ``li % 4`` inside one body a section traces two layers in
place of eight, and was tried first: the TPU's compiler copies a conditional's
operands that a branch hands through untouched, so every full layer copied both
rings whole (2 x 77 MB read and written, by the compiled text). What every
layer has (``wqkv``, ``wo``, the four norms) is stacked over all
``num_hidden_layers`` and read by the layer's index; the dense and the expert
layers' own tensors over those layers, from their first layer on.

**The pool holds two kinds of cache** behind one block table, and the routing
counts:

* ``kv`` (full layers, 2, slots x G, d): the **full** layers' rows a
  position in the flat pool (``models/flat_kv.py``: its format, how a call's
  rows are written and read back, which kernel scores them), a K/V head a row
  (eight heads are no whole sublane tile of bfloat16). Full layer ``i`` is the
  pool's layer ``i // 4``. A block holds the full layers' rows alone
  (``paged_block_bytes``): a quarter of what every layer's would cost.
* ``ring_k``, ``ring_v`` (window layers, state rows, W x G, d): a **ring** of
  the last W positions' rows a sequence a window layer, in the sequence's state
  row (``models/phi4flash.py`` says how a state row is handed out). Window
  layer ``i`` is the rings' layer ``i - i // 4``. Position p lies at ``p % W``
  with its key **rotated at p before it is written**, so a score depends on
  ``t - s`` whatever row ``s % W`` is and the mask is a count: on a TPU
  ``ring_window_attention`` (its plain form) writes the step's own row and
  scores the ring; elsewhere the row is scattered and the ring gathered. A
  prefill attends over its own prompt in a band and leaves its last W rows. A
  write at one position twice is the same row.
* ``moe_counts``: ``moe.COUNTS`` summed over the expert layers and decode steps.

A prefill starts from empty rings: no chunked prefill, no prefix reuse.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import flat_kv, moe, paged
from ray_tpu.models.moe import routing_counts  # noqa: F401 - the engine asks the kind's module for it
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_tables, swiglu
from ray_tpu.ops.window_attention import (can_use_ring_kernel, ring_window_attention, window_attention_prefill,
                                          window_attention_rows, write_spans)

PERIOD = 4  # ``sliding_window_pattern`` LLLG: three window layers, then a full one
ROUTER_SCALE = 1.5
BIAS_SCALE = 1.5e-3


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """Published keys (LGAI-EXAONE ``config.json`` names) plus this chip's share
    of each expert layer's routed experts: ``experts_held`` of the
    ``num_experts``, from ``expert_offset``. The router keeps all
    ``num_experts`` outputs whatever is held. Of the keys that choose a path
    the program runs what the checkpoint states and refuses the rest."""

    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    num_nextn_predict_layers: int = 0  # the served path drafts nothing; ``mtp_logits`` takes a config that has it
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    tie_word_embeddings: bool = False
    experts_held: Optional[int] = None  # None: all of them
    expert_offset: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = self.num_experts if self.experts_held is None else self.experts_held
        object.__setattr__(self, "experts_held", held)
        if not 0 <= self.expert_offset <= self.num_experts - held:
            raise ValueError(f"experts {self.expert_offset}..{self.expert_offset + held} are not among "
                             f"{self.num_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"{self.first_k_dense_replace} dense layers of {self.num_hidden_layers}")
        routing = (self.scoring_func, self.n_group, self.topk_group, self.norm_topk_prob)
        if routing != ("sigmoid", 1, 1, True):
            raise ValueError(f"scoring_func, n_group, topk_group, norm_topk_prob = {routing}: the program routes by "
                             "sigmoid scores over one group of experts, renormalised")
        if self.sliding_window_pattern != "LLLG" or self.tie_word_embeddings:
            raise ValueError(f"sliding_window_pattern {self.sliding_window_pattern!r}, tie_word_embeddings "
                             f"{self.tie_word_embeddings}: the program runs three window layers to a full one (LLLG) "
                             "under an untied head")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("the program runs whole groups of query heads a K/V head, and a rotary over half-split pairs")

    def served(self) -> None:
        """A ValueError where the config asks the three paged programs for
        what they do not run."""
        if self.num_nextn_predict_layers:
            raise ValueError(f"num_nextn_predict_layers {self.num_nextn_predict_layers}: the served path yields one "
                             "token a sequence a step and drafts none (ROADMAP M3); serve with 0, as a deployment "
                             "with speculation off, and see mtp_logits")

    # the names ``models/paged.py`` and the engine read
    n_layers = property(lambda self: self.num_hidden_layers)
    n_expert_layers = property(lambda self: self.num_hidden_layers - self.first_k_dense_replace)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    n_full = property(lambda self: self.num_hidden_layers // PERIOD)  # layers i with i % 4 == 3
    n_window = property(lambda self: self.num_hidden_layers - self.num_hidden_layers // PERIOD)
    kv_row = property(lambda self: self.num_key_value_heads * self.head_dim)  # values of one position's K (or V)


def is_full(li: int) -> bool:
    """Whether layer ``li`` is a full-attention layer."""
    return li % PERIOD == PERIOD - 1


def init_params(key, cfg: ExaoneMoeConfig) -> Dict[str, Any]:
    """Seeded weights, a plain recipe (the benchmark's family seeds its own and
    says why each, ``benchmarks/families/exaone_moe.py``): 1/sqrt(fan-in), the
    embedding 0.02, the dense paths' projections into the residual stream
    (``wo``, ``w_down``, ``s_down``) scaled down by sqrt(2 x layers), norms 1,
    the router's columns ``ROUTER_SCALE`` / sqrt(D), the choice bias
    ``BIAS_SCALE`` x normal. ``wqkv`` is q's, k's and v's columns side by side,
    a head's d values together. With ``num_nextn_predict_layers`` the block
    ``mtp_logits`` runs, under ``mtp``."""
    L, K, D, H, G, d = (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim)
    F, Fe, Fs = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.num_shared_experts * cfg.moe_intermediate_size
    E, held, n = L - K, cfg.experts_held, cfg.num_experts
    keys = iter(jax.random.split(key, 40))
    s_res = (2 * L) ** -0.5

    def normal(shape, scale, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def attention(layers):
        return {
            "in_norm": jnp.ones((layers, D), jnp.float32), "post_norm": jnp.ones((layers, D), jnp.float32),
            "wqkv": normal((layers, D, (H + 2 * G) * d), D ** -0.5),
            "q_norm": jnp.ones((layers, d), jnp.float32), "k_norm": jnp.ones((layers, d), jnp.float32),
            "wo": normal((layers, H * d, D), (H * d) ** -0.5 * s_res),
        }

    def experts(layers):
        return {
            "router": normal((layers, D, n), D ** -0.5 * ROUTER_SCALE),
            "router_bias": normal((layers, n), BIAS_SCALE, jnp.float32),
            "e_gate": normal((layers, held, D, Fe), D ** -0.5), "e_up": normal((layers, held, D, Fe), D ** -0.5),
            "e_down": normal((layers, held, Fe, D), Fe ** -0.5),
            "s_gate": normal((layers, D, Fs), D ** -0.5), "s_up": normal((layers, D, Fs), D ** -0.5),
            "s_down": normal((layers, Fs, D), Fs ** -0.5 * s_res),
        }

    params = {
        "embed": normal((cfg.vocab_size, D), 0.02),
        **attention(L),
        "w_gate": normal((K, D, F), D ** -0.5), "w_up": normal((K, D, F), D ** -0.5),
        "w_down": normal((K, F, D), F ** -0.5 * s_res),
        **experts(E),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, cfg.vocab_size), D ** -0.5),
    }
    if cfg.num_nextn_predict_layers:
        params["mtp"] = {
            "h_norm": jnp.ones((D,), jnp.float32), "e_norm": jnp.ones((D,), jnp.float32),
            "proj": normal((2 * D, D), (2 * D) ** -0.5),
            **{k: v[0] for k, v in {**attention(1), **experts(1)}.items()},
        }
    return params


def init_paged_pool(cfg: ExaoneMoeConfig, num_blocks: int, block_size: int, state_rows: int) -> Dict:
    """The two kinds of cache and the routing counts (module docstring).
    ``state_rows`` counts the null row: the engine asks for ``max_batch + 1``."""
    cfg.served()
    G, d = cfg.num_key_value_heads, cfg.head_dim
    ring = (cfg.n_window, state_rows, cfg.sliding_window * G, d)
    return {
        "kv": flat_kv.init_pool(cfg.n_full, num_blocks, block_size, G, d, cfg.dtype),
        "ring_k": jnp.zeros(ring, cfg.dtype), "ring_v": jnp.zeros(ring, cfg.dtype),
        "moe_counts": jnp.zeros((len(moe.COUNTS),), jnp.uint32),
    }


def paged_block_bytes(cfg: ExaoneMoeConfig, block_size: int) -> int:
    """Bytes one block of the pool holds: K and V rows of the full layers
    alone (the window layers' live in the state row)."""
    return flat_kv.block_bytes(cfg.n_full, block_size, cfg.num_key_value_heads, cfg.head_dim, cfg.dtype)


def paged_ring(cfg: ExaoneMoeConfig) -> Dict[str, int]:
    """The rings of a state row: ``rows`` a ring (the window) and ``bytes``
    over the window layers, K and V."""
    return {"rows": cfg.sliding_window,
            "bytes": 2 * cfg.n_window * cfg.sliding_window * cfg.kv_row * jnp.dtype(cfg.dtype).itemsize}


def paged_state_bytes(cfg: ExaoneMoeConfig) -> int:
    """Bytes one state row holds: the window layers' rings and nothing else."""
    return paged_ring(cfg)["bytes"]


def _qkv(cfg: ExaoneMoeConfig, w, u, rope):
    """``u`` (B, S, D) through the layer's fused projection, the per-head norms
    and, with ``rope`` (cos, sin), the rotary: q (B, S, H, d), k, v (B, S, G,
    d). Norm and rotary in float32, rounded once."""
    b, s, _ = u.shape
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = jnp.split(u @ w("wqkv"), [H * d, (H + G) * d], axis=-1)
    q = rms_norm(q.reshape(b, s, H, d).astype(jnp.float32), w("q_norm"), cfg.rms_norm_eps)
    k = rms_norm(k.reshape(b, s, G, d).astype(jnp.float32), w("k_norm"), cfg.rms_norm_eps)
    if rope is not None:
        q, k = (apply_rope(x.reshape(b * s, -1, d), *rope).reshape(x.shape) for x in (q, k))
    return q.astype(u.dtype), k.astype(u.dtype), v.reshape(b, s, G, d)


def _expert_ffn(cfg: ExaoneMoeConfig, w, stacks, u, layer, live):
    """The expert layer's feed-forward half over ``u`` (T, D): (routed +
    shared, counts). ``w`` reads the layer's own tensors, ``stacks`` holds
    ``e_gate``, ``e_up``, ``e_down`` (stacked over layers where ``layer`` is
    not None)."""
    with jax.named_scope("moe"):
        routed, counts = moe.expert_layer(
            {**stacks, "router": w("router"), "router_bias": w("router_bias")}, u, layer=layer,
            n_routed=cfg.num_experts, top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
            expert_offset=cfg.expert_offset, live=live, rule=moe.route_sigmoid)
        with jax.named_scope("shared"):
            shared = swiglu(u @ w("s_gate"), u @ w("s_up")) @ w("s_down")
    return routed + shared, counts


def paged_layer(cfg: ExaoneMoeConfig, params, step):
    """The model's sections for one call of a paged program (module
    docstring): the dense layers', then the expert layers', each cut at the
    period's boundaries. A decode step's expert layers add their routing counts
    to the pool's."""
    cfg.served()
    eps, dense_layers, dtype = cfg.rms_norm_eps, cfg.first_k_dense_replace, cfg.dtype
    H, G, d, W = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window
    b, s = step.positions.shape
    rows, live = step.state_rows, step.live.reshape(b, s)
    decode = s == 1
    scale = d ** -0.5
    ring_kernel = decode and can_use_ring_kernel(W, G, d, dtype)
    rope = rope_tables(step.positions, d, cfg.rope_theta)  # the same for every window layer: once a call

    at = functools.partial(paged.at, params)  # a layer's tensors, each read out of its stack in place

    def window_attention(u, pool, li):
        """Window layer ``li``: (o (B, S, H, d), the pool with its rings written)."""
        wi = li - li // PERIOD
        with jax.named_scope("proj"):
            q, k, v = _qkv(cfg, at(li), u, rope)
        ring = {"ring_k": pool["ring_k"], "ring_v": pool["ring_v"]}
        if decode:
            at_row, held = step.positions[:, 0] % W, jnp.minimum(step.lengths, W)
            if ring_kernel:  # the kernel puts the row in its ring and scores the ring with it there
                with jax.named_scope("ring_attn"):
                    o, ring["ring_k"], ring["ring_v"] = ring_window_attention(
                        q[:, 0], k[:, 0], v[:, 0], ring["ring_k"], ring["ring_v"], wi, rows, held, at_row, None,
                        kv_pairs=G, scale=scale)
            else:
                with jax.named_scope("ring_scatter"):
                    ring = {name: write_spans(ring[name], (wi, rows), at_row * G, t[:, 0])
                            for name, t in (("ring_k", k), ("ring_v", v))}
                with jax.named_scope("ring_attn"):
                    mine = [ring[name][wi, rows].reshape(b, W, G, d) for name in ("ring_k", "ring_v")]
                    o = window_attention_rows(q[:, 0], *mine, jnp.arange(W)[None, :] < held[:, None], scale=scale)
            o = o[:, None]
        else:
            with jax.named_scope("ring_attn"):
                o = window_attention_prefill(q, k, v, scale=scale, window=W)
            with jax.named_scope("ring_scatter"):
                # row r of the ring: the last real position p with p % W == r (none: a row behind the mask)
                n, r = jnp.sum(live, axis=1)[:, None], jnp.arange(W)[None, :]
                source = jnp.clip(r + W * ((n - 1 - r) // W), 0, s - 1)[:, :, None, None]
                ring = {name: write_spans(ring[name], (wi, rows), jnp.zeros((b,), jnp.int32),
                                          jnp.take_along_axis(t, source, axis=1).reshape(b, W * G, d))
                        for name, t in (("ring_k", k), ("ring_v", v))}
        return o.astype(dtype), {**pool, **ring}

    def full_attention(u, pool, li):
        """Full layer ``li``: (o (B, S, H, d), the pool with its rows written)."""
        fi = li // PERIOD
        with jax.named_scope("proj"):
            q, k, v = _qkv(cfg, at(li), u, None)
        o, kv = flat_kv.attend(pool["kv"], fi, step, q, k, v, kv_heads=G)
        return o.astype(dtype), {**pool, "kv": kv}

    def attention(x, pool, li, full: bool):
        """The half every layer has: (h, N(h) as (T, D), the pool)."""
        w = at(li)
        u = rms_norm(x, w("in_norm"), eps)
        with jax.named_scope("full" if full else "window"):
            o, pool = (full_attention if full else window_attention)(u, pool, li)
        with jax.named_scope("out"):
            h = x + o.reshape(b, s, H * d) @ w("wo")
        return h, rms_norm(h, w("post_norm"), eps).reshape(b * s, -1), pool

    def dense_layer(x, pool, li, full):
        h, u, pool = attention(x, pool, li, full)
        own = at(li)
        with jax.named_scope("dense_ffn"):
            y = swiglu(u @ own("w_gate"), u @ own("w_up")) @ own("w_down")
        return h + y.reshape(h.shape), pool

    def expert_layer(x, pool, li, full):
        h, u, pool = attention(x, pool, li, full)
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
    start = min(hi, -(-lo // PERIOD) * PERIOD)
    end = start + (hi - start) // PERIOD * PERIOD
    runs = [(lo, start, start - lo), (start, end, PERIOD), (end, hi, hi - end)]
    return [run for run in runs if run[1] > run[0]]


def mtp_logits(cfg: ExaoneMoeConfig, params, hidden, tokens):
    """The multi-token prediction block (DeepSeek-V3's form), outside the
    served path: ``hidden`` (S, D) the model's last-layer residual stream
    (before the final norm) over one sequence from position 0, ``tokens`` (S,)
    the sequence; position t's row is the logits of token t + 2:

        g_t = W_p [N_a(h_t) ; N_b(Emb(x_{t+1}))]
        one full-attention expert block over g (no rotary, positions 0 .. t),
        the final norm and the shared head

    The last position has no next token and pairs with token 0: its row means
    nothing. The block's own tensors are ``params["mtp"]``, one layer's
    (``init_params``), this chip's share of its routed experts among them."""
    w = lambda name: params["mtp"][name]  # noqa: E731
    s, eps = hidden.shape[0], cfg.rms_norm_eps
    nxt = params["embed"][jnp.roll(tokens, -1)]
    g = jnp.concatenate([rms_norm(hidden, w("h_norm"), eps), rms_norm(nxt, w("e_norm"), eps)], axis=-1) @ w("proj")
    q, k, v = _qkv(cfg, w, rms_norm(g, w("in_norm"), eps)[None], None)
    o = window_attention_prefill(q, k, v, scale=cfg.head_dim ** -0.5, block=s)
    h = g + o.reshape(s, -1).astype(g.dtype) @ w("wo")
    y, _ = _expert_ffn(cfg, w, params["mtp"], rms_norm(h, w("post_norm"), eps), None, None)
    x = rms_norm(h + y, params["final_norm"], eps)
    return (x @ params["unembed"]).astype(jnp.float32)
