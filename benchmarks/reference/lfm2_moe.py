"""Plain reference of LFM2-24B-A2B's language model (``model_type``
``lfm2_moe``): the forward pass in straightforward ``jax.numpy``, float32,
every contraction at ``Precision.HIGHEST``. No cache, no window row, no kernel,
no batching, no grouped matmul, no import from ``ray_tpu``.

Layer ``i`` (``N`` is RMSNorm with a float32 weight, eps ``norm_eps``):

    h = x + Op_i(N(x; g_op))
    y = h + F_i(N(h; g_ffn))

    Op_i(u), where i % 4 != 2 (a conv layer):
               [B | C | z] = u W_in                        three slices of D
               s_t = B_t * z_t
               c_t = sum_{j<K} w_j * s_{t-K+1+j}           s before position 0 is zero: written as
                                                           the sum of K shifted copies of s
               out = (C_t * c_t) W_out                     no activation, no bias
    Op_i(u), where i % 4 == 2 (a full layer):
               [q | k | v] = u W_qkv: H query heads, G key and G value heads of d
               q_h <- N(q_h; g_q), k_g <- N(k_g; g_k)      over a head's d values
               q, k rotated over the pairs (j, j + d/2) by position x theta^(-2j/d)
               p = softmax(q_h . k_{h // (H/G)} / sqrt(d)) over 0 .. t;  out = concat_h(p v) W_o
    F_i(u)  = FFN(u; the dense layer's three tensors)            i < dense layers
            = sum over chosen held e of w_e Expert_e(u)          the expert layers: a loop over the experts
    FFN(u)  = (silu(u Wg) * (u Wu)) Wd;  Expert_e likewise
    route(u): s = sigmoid(f32(u) f32(Wr)) over every routed expert
              chosen = top-k of (s + b);  w_e = scale * s_e / (sum of the chosen s + 1e-6)

and after the last layer ``N`` again, then the head, which is the embedding's
transpose.

The weights are the dict the family made from the seed
(``families/lfm2_moe.py``), stacked as the program stacks them: the two norms
over all layers, the convolutions' tensors over the conv layers, the
attentions' over the full layers, the dense MLP's over the leading dense
layers, the expert layers' over the rest. Its ``hyper`` entry carries the
numbers no shape tells: ``num_attention_heads``, ``num_key_value_heads``,
``expert_offset`` (the held experts are ``expert_offset ..`` of the router's,
as many as ``e_gate`` has), ``num_experts_per_tok``,
``routed_scaling_factor``, ``norm_eps``, ``rope_theta``. A chosen expert that
is not held adds nothing, here as in the program: the reference is given the
same share (the served configuration holds them all).

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the router keeps float32 arithmetic on its rounded weights.

It has to fit beside 8 GB of served weights and the pool, so ``logits_at``
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

# the leaves every reference shares (``reference/longcat.py``: one contraction a jitted call, the controls'
# roundings), the two parts that are Kimi-K2's to the letter (the dense layers' MLP, the lookup) and K-EXAONE's
# attention of one K/V head's group with its half-split rotary
from benchmarks.reference.exaone_moe import _attend, rope
from benchmarks.reference.kimi import _embed, dense_ffn
from benchmarks.reference.longcat import HIGHEST, ROUND, _expert as _ffn, _mm, _project, rms_norm

INTEGERS = ("num_attention_heads", "num_key_value_heads", "expert_offset", "num_experts_per_tok")
PERIOD = 4  # conv, conv, full_attention, conv


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).item() for k, v in params["hyper"].items()}
    return {**h, **{k: int(h[k]) for k in INTEGERS}}


# -- what a layer is, each a function a planted fault can replace ------------------


def is_full(i: int) -> bool:
    return i % PERIOD == 2


def delayed(s, back: int):
    """``s`` (S, D) ``back`` positions late: row t is ``s_{t - back}``, zero
    before the sequence's start."""
    return jnp.pad(s, ((back, 0), (0, 0)))[: s.shape[0]]


def head_norm(x, weight, eps):
    """The per-head norm of q and k: over the last axis, a head's values."""
    return rms_norm(x, weight, eps)


def chosen_scores(s, biased, chosen):
    """The chosen experts' scores that become their weights: ``s`` itself, the
    bias in the choice only."""
    return jnp.take_along_axis(s, chosen, axis=-1)


def renormalised(picked):
    return picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)


def gated(gate, c):
    """The convolution's output under its second gate."""
    return gate * c


# -- the layer's parts ---------------------------------------------------------


def short_conv(u, w, at, precision):
    """Conv layer number ``at`` (among the conv layers) over one sequence
    ``u`` (S, D)."""
    gate_in, gate_out, z = jnp.split(_project(u, w["conv_in"], at, "sd,dk->sk", (0,), precision), 3, axis=-1)
    s = gate_in * z
    taps = w["conv_w"][at].astype(jnp.float32)  # (K, D): tap j weighs the product K - 1 - j positions back
    width = taps.shape[0]
    c = sum(taps[j] * delayed(s, width - 1 - j) for j in range(width))
    return _project(gated(gate_out, c), w["conv_out"], at, "sk,kd->sd", (0,), precision)


def attention(u, w, at, hy, precision):
    """Full layer number ``at`` (among the full layers) over one sequence
    ``u`` (S, D)."""
    H, G, eps = hy["num_attention_heads"], hy["num_key_value_heads"], hy["norm_eps"]
    s = u.shape[0]
    qkv = _project(u, w["wqkv"], at, "sd,dk->sk", (0,), precision)
    d = qkv.shape[-1] // (H + 2 * G)
    q, k, v = (t.reshape(s, -1, d) for t in jnp.split(qkv, [H * d, (H + G) * d], axis=-1))
    q, k = head_norm(q, w["q_norm"][at], eps), head_norm(k, w["k_norm"][at], eps)
    q, k = rope(q, jnp.arange(s), hy["rope_theta"]), rope(k, jnp.arange(s), hy["rope_theta"])
    r = H // G
    o = jnp.concatenate([_attend(q[:, g * r:(g + 1) * r], k[:, g], v[:, g], None) for g in range(G)], axis=1)
    return _project(o.reshape(s, -1), w["wo"], at, "sk,kd->sd", (0,), precision)


def route(u, router, bias, hy, precision):
    """(weights (S, K), chosen experts (S, K)): sigmoid scores in float32, the
    top-k of ``s + b`` chosen, the weights the chosen ``s`` renormalised and
    scaled."""
    w = ROUND[precision](router, (0,)) if precision in ROUND else router.astype(jnp.float32)
    s = jax.nn.sigmoid(jnp.einsum("sd,dn->sn", u.astype(jnp.float32), w, precision=HIGHEST))
    biased = s + bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, hy["num_experts_per_tok"])
    return hy["routed_scaling_factor"] * renormalised(chosen_scores(s, biased, chosen)), chosen


def routed_part(u, weights, chosen, w, at, hy, precision):
    """What the held experts add, one expert at a time, each over the whole
    sequence and weighted by zero where a token did not choose it."""
    out = jnp.zeros_like(u)
    for e in range(w["e_gate"].shape[1]):
        mine = jnp.sum(jnp.where(chosen == hy["expert_offset"] + e, weights, 0.0), axis=-1, keepdims=True)
        out = out + mine * _ffn(u, w["e_gate"], w["e_up"], w["e_down"], (at, e), precision)
    return out


def moe(u, w, at, hy, precision):
    weights, chosen = route(u, w["router"][at], w["router_bias"][at], hy, precision)
    return routed_part(u, weights, chosen, w, at, hy, precision)


def block(x, params, li, hy, precision):
    """Layer ``li`` over one sequence. x (S, D) float32. The leading layers
    (as many as ``w_gate`` stacks) are dense, the rest expert layers; full
    layers before ``li``: one a period from layer 2 on."""
    eps, dense_layers = hy["norm_eps"], params["w_gate"].shape[0]
    u = rms_norm(x, params["op_norm"][li], eps)
    fulls_before = (li + 1) // PERIOD
    if is_full(li):
        h = x + attention(u, params, fulls_before, hy, precision)
    else:
        h = x + short_conv(u, params, li - fulls_before, precision)
    u = rms_norm(h, params["ffn_norm"][li], eps)
    if li < dense_layers:
        return h + dense_ffn(u, params, li, precision)
    return h + moe(u, params, li - dense_layers, hy, precision)


def hidden_states(params, tokens, precision="f32"):
    """The last layer's output over one sequence, before the final norm."""
    hy = hyper(params)
    x = _embed(params, jnp.asarray(tokens), precision)
    for li in range(params["op_norm"].shape[0]):
        x = block(x, params, li, hy, precision)
    return x


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision", "eps"))
def _tied_head(x, final_norm, embed, lo, hi, precision, eps):
    """Rows ``lo .. hi`` of the vocabulary: the embedding's own rows, a row an output channel."""
    return _mm("sd,vd->sv", rms_norm(x, final_norm, eps), embed[lo:hi], precision, (1,))


def logits_at(params, tokens, rows, precision="f32", vocab_chunks=4):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    x = hidden_states(params, tokens, precision)[jnp.asarray(rows)]
    v, eps = params["embed"].shape[0], hyper(params)["norm_eps"]
    step = -(-v // vocab_chunks)
    parts = [_tied_head(x, params["final_norm"], params["embed"], a, min(a + step, v), precision, eps)
             for a in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)
