"""Plain reference of K-EXAONE-236B-A23B's language model (``model_type``
``exaone_moe``): the forward pass in straightforward ``jax.numpy``, float32,
every contraction at ``Precision.HIGHEST``. No cache, no ring, no kernel, no
batching, no grouped matmul, no import from ``ray_tpu``.

Layer ``i`` (``N`` is RMSNorm with a float32 weight, eps ``rms_norm_eps``):

    h = x + Attn_i(N(x; g_in))
    y = h + F_i(N(h; g_post))

    Attn_i(u): [q | k | v] = u W_qkv: H query heads, G key and G value heads of d
               q_h <- N(q_h; g_q), k_g <- N(k_g; g_k)     over a head's d values
               where i % 4 != 3 (a window layer): q, k rotated over the pairs
                   (j, j + d/2) by position x theta^(-2j/d); t sees t - W + 1 .. t
               where i % 4 == 3 (a full layer): no rotary; t sees 0 .. t
               p = softmax(q_h . k_{h // (H/G)} / sqrt(d));  out = concat_h(p v) W_o
    F_i(u)  = FFN(u; the dense layer's three tensors)            i < dense layers
            = sum over chosen held e of w_e Expert_e(u) + S(u)    the expert layers
    FFN(u)  = (silu(u Wg) * (u Wu)) Wd;  Expert_e, S (the shared expert) likewise
    route(u): s = sigmoid(f32(u) f32(Wr)) over every routed expert
              a group's score: the sum of its two largest (s + b); the
              topk_group best of n_group groups stay; chosen = top-k of (s + b)
              among them;  w_e = scale * s_e / (sum of the chosen s + 1e-20)

and after the last layer ``N`` again, then the head (untied). The multi-token
prediction block (``mtp_logits_at``; DeepSeek-V3's form) over the last layer's
output ``h`` and the next token's embedding:

    g_t = [N(h_t; g_a) ; N(Emb(x_{t+1}); g_b)] W_p            (2D -> D)
    one full-attention expert block over g, the final norm, the shared head:
    the logits of x_{t+2}

The weights are the dict the family made from the seed
(``families/exaone_moe.py``), stacked as the program stacks them: the
attention's tensors and the norms over all layers, the dense MLP's over the
leading dense layers, the expert layers' over the rest; ``mtp`` (where the
model has the block) one layer's, unstacked. Its ``hyper`` entry carries the
numbers no shape tells: ``num_attention_heads``, ``num_key_value_heads``,
``sliding_window``, ``expert_offset`` (the held experts are ``expert_offset ..``
of the router's, as many as ``e_gate`` has), ``num_experts_per_tok``,
``n_group``, ``topk_group``, ``routed_scaling_factor``, ``rms_norm_eps``,
``rope_theta``. A chosen expert that is not held adds nothing, here as in the
program: the reference is given the same share.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the router keeps float32 arithmetic on its rounded weights.

It has to fit beside 12 GB of served weights and the pool, so ``logits_at``
goes a tensor at a time: one contraction a jitted call, experts one at a time,
attention a K/V head's group and a block of query rows at a time, the head in
vocabulary chunks (those leaves are ``reference/longcat.py``'s, which know no
model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the leaves every reference shares (``reference/longcat.py``: one contraction a jitted call, the head in vocabulary
# chunks, the controls' roundings) and the two parts that are Kimi-K2's to the letter: the dense layer's MLP, the lookup
from benchmarks.reference.kimi import _embed, dense_ffn
from benchmarks.reference.longcat import HIGHEST, ROUND, ROW_BLOCK, _expert as _ffn, _head, _project, rms_norm

INTEGERS = ("num_attention_heads", "num_key_value_heads", "sliding_window", "expert_offset", "num_experts_per_tok",
            "n_group", "topk_group")
PERIOD = 4  # LLLG


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).item() for k, v in params["hyper"].items()}
    return {**h, **{k: int(h[k]) for k in INTEGERS}}


# -- what a layer is, each a function a planted fault can replace ------------------


def is_full(i: int) -> bool:
    return i % PERIOD == PERIOD - 1


def window_of(hy) -> int:
    return hy["sliding_window"]


def rotates(i: int) -> bool:
    """Whether layer ``i``'s queries and keys carry a rotary: the window layers."""
    return not is_full(i)


def head_norm(x, weight, eps):
    """The per-head norm of q and k: over the last axis, a head's values."""
    return rms_norm(x, weight, eps)


def renormalised(picked):
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def scaling(hy) -> float:
    return hy["routed_scaling_factor"]


def rope(x, positions, theta):
    """``x`` (S, heads, d): pairs (j, j + d/2) of the last axis rotated by
    ``positions * theta^(-2j/d)``."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * (1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)))
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


# -- the layer's parts ---------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, window):
    """Softmax attention of one K/V head's group of query heads over one
    sequence from position 0, query rows in blocks. q (S, R, d); k, v (S, d).
    Position t sees ``t - window + 1 .. t`` (from 0 where ``window`` is None)."""
    s, d = k.shape
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, s)
        first = 0 if window is None else max(0, lo - window + 1)
        scores = jnp.einsum("qrd,kd->rqk", q[lo:hi], k[first:hi], precision=HIGHEST) / np.sqrt(d)
        sees = pos[lo:hi, None] >= pos[None, first:hi]
        if window is not None:
            sees &= pos[None, first:hi] > pos[lo:hi, None] - window
        p = jax.nn.softmax(jnp.where(sees[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("rqk,kd->qrd", p, v[first:hi], precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def attention(u, w, at, i, hy, precision):
    """Layer ``i``'s attention over one sequence ``u`` (S, D); ``w`` holds
    ``wqkv``, ``q_norm``, ``k_norm``, ``wo``, read at index ``at``."""
    H, G, eps = hy["num_attention_heads"], hy["num_key_value_heads"], hy["rms_norm_eps"]
    s = u.shape[0]
    qkv = _project(u, w["wqkv"], at, "sd,dk->sk", (0,), precision)
    d = qkv.shape[-1] // (H + 2 * G)
    q, k, v = (t.reshape(s, -1, d) for t in jnp.split(qkv, [H * d, (H + G) * d], axis=-1))
    q, k = head_norm(q, w["q_norm"][at], eps), head_norm(k, w["k_norm"][at], eps)
    if rotates(i):
        q, k = rope(q, jnp.arange(s), hy["rope_theta"]), rope(k, jnp.arange(s), hy["rope_theta"])
    window = None if is_full(i) else window_of(hy)
    r = H // G
    o = jnp.concatenate([_attend(q[:, g * r:(g + 1) * r], k[:, g], v[:, g], window) for g in range(G)], axis=1)
    return _project(o.reshape(s, -1), w["wo"], at, "sk,kd->sd", (0,), precision)


def shared_part(u, w, at, precision):
    """The shared expert: every token, weight 1."""
    return _ffn(u, w["s_gate"], w["s_up"], w["s_down"], at, precision)


def route(u, router, bias, hy, precision):
    """(weights (S, K), chosen experts (S, K)), for any ``n_group`` and
    ``topk_group``: sigmoid scores in float32; the groups are scored by the
    sum of their two largest ``s + b`` and the ``topk_group`` best stay; the
    top-k of ``s + b`` among them are chosen; the weights are the chosen ``s``
    renormalised and scaled, the bias in the choice only."""
    w = ROUND[precision](router, (0,)) if precision in ROUND else router.astype(jnp.float32)
    s = jax.nn.sigmoid(jnp.einsum("sd,dn->sn", u.astype(jnp.float32), w, precision=HIGHEST))
    biased = s + bias.astype(jnp.float32)
    n, g = biased.shape[-1], hy["n_group"]
    group_score = jnp.sum(jax.lax.top_k(biased.reshape(-1, g, n // g), min(2, n // g))[0], axis=-1)  # (S, g)
    _, kept = jax.lax.top_k(group_score, hy["topk_group"])
    in_kept = jnp.zeros_like(group_score, bool).at[jnp.arange(kept.shape[0])[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(in_kept, n // g, axis=-1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(masked, hy["num_experts_per_tok"])
    return scaling(hy) * renormalised(jnp.take_along_axis(s, chosen, axis=-1)), chosen


def routed_part(u, weights, chosen, w, at, hy, precision):
    """What the held experts add, one expert at a time, each over the whole
    sequence and weighted by zero where a token did not choose it."""
    out = jnp.zeros_like(u)
    held = w["e_gate"].shape[-3]
    for e in range(held):
        mine = jnp.sum(jnp.where(chosen == hy["expert_offset"] + e, weights, 0.0), axis=-1, keepdims=True)
        out = out + mine * _ffn(u, w["e_gate"], w["e_up"], w["e_down"], (*at, e), precision)
    return out


def moe(u, w, at, hy, precision):
    weights, chosen = route(u, w["router"][at], w["router_bias"][at], hy, precision)
    return routed_part(u, weights, chosen, w, at, hy, precision) + shared_part(u, w, at, precision)


def block(x, params, li, hy, precision):
    """Layer ``li`` over one sequence. x (S, D) float32. The leading layers
    (as many as ``w_gate`` stacks) are dense, the rest expert layers."""
    eps, dense_layers = hy["rms_norm_eps"], params["w_gate"].shape[0]
    h = x + attention(rms_norm(x, params["in_norm"][li], eps), params, (li,), li, hy, precision)
    u = rms_norm(h, params["post_norm"][li], eps)
    if li < dense_layers:
        return h + dense_ffn(u, params, li, precision)
    return h + moe(u, params, (li - dense_layers,), hy, precision)


def hidden_states(params, tokens, precision="f32"):
    """The last layer's output over one sequence, before the final norm."""
    hy = hyper(params)
    x = _embed(params, jnp.asarray(tokens), precision)
    for li in range(params["wqkv"].shape[0]):
        x = block(x, params, li, hy, precision)
    return x


def _logits(params, x, hy, precision, vocab_chunks):
    v = params["unembed"].shape[1]
    step = -(-v // vocab_chunks)
    parts = [_head(x, params["final_norm"], params["unembed"], a, min(a + step, v), precision, hy["rms_norm_eps"])
             for a in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)


def logits_at(params, tokens, rows, precision="f32", vocab_chunks=4):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    x = hidden_states(params, tokens, precision)[jnp.asarray(rows)]
    return _logits(params, x, hyper(params), precision, vocab_chunks)


def mtp_logits_at(params, tokens, rows, precision="f32", vocab_chunks=4):
    """The multi-token prediction block's logits (len(rows), V) at the
    positions ``rows`` of one sequence ``tokens`` (S,): position t's are of
    token t + 2, from the model's output at t and token t + 1's embedding. The
    block is a full-attention layer (``mtp_layer_types``): no rotary, no
    window. ``rows`` stay below S - 1."""
    hy, w = hyper(params), params["mtp"]
    eps = hy["rms_norm_eps"]
    tokens = jnp.asarray(tokens)
    h = hidden_states(params, tokens, precision)
    nxt = _embed(params, jnp.roll(tokens, -1), precision)
    g = jnp.concatenate([rms_norm(h, w["h_norm"], eps), rms_norm(nxt, w["e_norm"], eps)], axis=-1)
    g = _project(g, w["proj"], (), "sk,kd->sd", (0,), precision)
    full = PERIOD - 1  # a layer index that is a full layer's
    a = g + attention(rms_norm(g, w["in_norm"], eps), w, (), full, hy, precision)
    y = a + moe(rms_norm(a, w["post_norm"], eps), w, (), hy, precision)
    return _logits(params, y[jnp.asarray(rows)], hy, precision, vocab_chunks)
