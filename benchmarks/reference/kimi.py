"""Plain reference of Kimi-K2's language model (DeepSeek-V3's layer): the
forward pass in straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``. No cache, no kernel, no batching, no absorbed
attention, no grouped matmul, no import from ``ray_tpu``.

Layer ``l`` (``N`` is RMSNorm with a float32 weight):

    h = x + MLA(N(x; g_in))
    y = h + F_l(N(h; g_post))

    F_l(u)  = FFN(u; the dense layer's three tensors)          l < dense layers
            = sum over chosen held e of w_e Expert_e(u) + S(u)  the expert layers
    FFN(u)  = (silu(u Wg) * (u Wu)) Wd;  Expert_e, S (the shared expert) likewise
    MLA(h)  : cq = N(h Wqa; g_qa);  [q_n | q_r] = cq Wqb per head
              [c | k_r] = h Wkva;  ckv = N(c; g_kva)
              q_r, k_r rotated over pairs (2j, 2j+1) at YaRN's frequencies,
              cos and sin times mscale / mscale_all_dim's mscale; k_r shared
              [k_n | v] = ckv Wkvb per head
              p = softmax((q_n . k_n + q_r . k_r) m^2 / sqrt(d_n + d_r)), causal
              out = (p v) Wo
    route(u): s = sigmoid(f32(u) f32(Wr)) over every routed expert
              a group's score: the sum of its two largest (s + b); the
              topk_group best of n_group groups stay; chosen = top-k of (s + b)
              among them;  w_e = scale * s_e / (sum of the chosen s + 1e-20)

The weights are the dict the family made from the seed
(``families/kimi.py``), stacked as the program stacks them: the attention's
tensors and the two norms over all layers, the dense MLP's over the leading
dense layers, the expert layers' over the rest. Its ``hyper`` entry carries the
numbers no shape tells: ``expert_offset`` (the held experts are
``expert_offset ..`` of the router's, as many as ``e_gate`` has),
``num_experts_per_tok``, ``n_group``, ``topk_group``, ``routed_scaling_factor``,
``rms_norm_eps``, ``rope_theta`` and YaRN's ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``, ``mscale``,
``mscale_all_dim`` (``factor`` 1: the plain rotary, and no ``m^2``). A chosen
expert that is not held adds nothing, here as in the program: the reference is
given the same share.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the router keeps float32 arithmetic on its rounded weights.

It has to fit beside 9.7 GB of served weights and the pool, so ``logits_at``
goes a tensor at a time: one contraction a jitted call, experts one at a time,
attention in blocks of heads and query rows, the head in vocabulary chunks
(those leaves are ``reference/longcat.py``'s, which know no model).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# the leaves every latent-attention reference shares: the roundings of the controls, one contraction
# (or one block of attention) a jitted call, the head in vocabulary chunks
from benchmarks.reference.longcat import (  # noqa: E402
    HEAD_BLOCK, HIGHEST, ROUND, _attend, _embed, _expert as _ffn, _head, _project, rms_norm, silu,
)

INTEGERS = ("expert_offset", "num_experts_per_tok", "n_group", "topk_group", "original_max_position_embeddings")


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).item() for k, v in params["hyper"].items()}
    return {**h, **{k: int(h[k]) for k in INTEGERS}}


# -- the rotary under YaRN ---------------------------------------------------------


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(d, hy):
    """The ``d / 2`` frequencies: ``f_j = theta^(-2j/d)``, blended with ``f_j /
    factor`` by a ramp from pair ``low`` to pair ``high``, the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    theta, factor = hy["rope_theta"], hy["factor"]
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return f

    def dim(n):
        return d * math.log(hy["original_max_position_embeddings"] / (2 * math.pi * n)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(hy["beta_fast"])), 0), min(math.ceil(dim(hy["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    r = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return f * (1 - r) + f / factor * r


def rope(x, positions, hy):
    """``x`` (S, d) or (S, H, d): pairs (2j, 2j+1) of the last axis rotated by
    ``positions * inv_freq_j``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(x.shape[-1], hy), jnp.float32)
    if x.ndim == 3:
        ang = ang[:, None, :]
    ratio = yarn_mscale(hy["factor"], hy["mscale"]) / yarn_mscale(hy["factor"], hy["mscale_all_dim"])
    c, s = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1).reshape(x.shape)


def score_scale(d_score, hy):
    m = yarn_mscale(hy["factor"], hy["mscale_all_dim"]) if hy["mscale_all_dim"] else 1.0
    return m * m / math.sqrt(d_score)


# -- the layer's parts ---------------------------------------------------------


def mla(h, params, li, hy, precision):
    """The latent attention of layer ``li`` over one sequence ``h`` (S, D)."""
    rkv, heads = params["kva_norm"].shape[-1], params["wkvb"].shape[-3]
    d_r = params["wkva"].shape[-2] - rkv
    d_n = params["wqb"].shape[-2] // heads - d_r
    pos, eps = jnp.arange(h.shape[0]), hy["rms_norm_eps"]
    cq = rms_norm(_project(h, params["wqa"], li, "sd,dr->sr", (0,), precision), params["qa_norm"][li], eps)
    q = _project(cq, params["wqb"], li, "sr,kr->sk", (1,), precision).reshape(h.shape[0], heads, d_n + d_r)
    kva = _project(h, params["wkva"], li, "sd,rd->sr", (1,), precision)
    ckv = rms_norm(kva[:, :rkv], params["kva_norm"][li], eps)
    k_r = rope(kva[:, rkv:], pos, hy)
    q_n, q_r = q[..., :d_n], rope(q[..., d_n:], pos, hy)
    kv = _project(ckv, params["wkvb"], li, "sr,hrk->shk", (1,), precision)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = score_scale(d_n + d_r, hy)
    att = jnp.concatenate([
        _attend(q_n[:, a:a + HEAD_BLOCK], q_r[:, a:a + HEAD_BLOCK], k_n[:, a:a + HEAD_BLOCK], k_r,
                v[:, a:a + HEAD_BLOCK], scale)
        for a in range(0, heads, HEAD_BLOCK)], axis=1)
    return _project(att.reshape(h.shape[0], -1), params["wo"], li, "sk,kd->sd", (0,), precision)


def dense_ffn(u, params, li, precision):
    """The leading layers' MLP, a tensor a call: one float32 copy of an 18432-wide
    matrix at a time."""
    hidden = silu(_project(u, params["w_gate"], li, "sd,df->sf", (0,), precision)) * _project(
        u, params["w_up"], li, "sd,df->sf", (0,), precision)
    return _project(hidden, params["w_down"], li, "sf,fd->sd", (0,), precision)


def shared_part(u, params, ei, precision):
    """The shared expert of expert layer ``ei``: every token, weight 1."""
    return _ffn(u, params["s_gate"], params["s_up"], params["s_down"], ei, precision)


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
    grouped = biased.reshape(-1, g, n // g)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # (S, g)
    _, kept = jax.lax.top_k(group_score, hy["topk_group"])
    in_kept = jnp.zeros_like(group_score, bool).at[jnp.arange(kept.shape[0])[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(in_kept, n // g, axis=-1), biased, -jnp.inf)
    _, chosen = jax.lax.top_k(masked, hy["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return hy["routed_scaling_factor"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20), chosen


def routed_part(u, weights, chosen, params, ei, hy, precision):
    """What the held experts of expert layer ``ei`` add, one expert at a time,
    each over the whole sequence and weighted by zero where a token did not
    choose it."""
    out = jnp.zeros_like(u)
    for e in range(params["e_gate"].shape[1]):
        w = jnp.sum(jnp.where(chosen == hy["expert_offset"] + e, weights, 0.0), axis=-1, keepdims=True)
        out = out + w * _ffn(u, params["e_gate"], params["e_up"], params["e_down"], (ei, e), precision)
    return out


def moe(u, params, ei, hy, precision):
    weights, chosen = route(u, params["router"][ei], params["router_bias"][ei], hy, precision)
    return routed_part(u, weights, chosen, params, ei, hy, precision) + shared_part(u, params, ei, precision)


def block(x, params, li, hy, precision):
    """Layer ``li`` over one sequence. x (S, D) float32. The leading layers
    (as many as ``w_gate`` stacks) are dense, the rest expert layers."""
    eps, dense_layers = hy["rms_norm_eps"], params["w_gate"].shape[0]
    h = x + mla(rms_norm(x, params["in_norm"][li], eps), params, li, hy, precision)
    u = rms_norm(h, params["post_norm"][li], eps)
    if li < dense_layers:
        return h + dense_ffn(u, params, li, precision)
    return h + moe(u, params, li - dense_layers, hy, precision)


def _embed(params, tokens, precision):
    e = params["embed"][tokens]
    return ROUND[precision](e, (1,)) if precision in ROUND else e.astype(jnp.float32)


def logits_at(params, tokens, rows, precision="f32", vocab_chunks=4):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    hy = hyper(params)
    x = _embed(params, jnp.asarray(tokens), precision)
    for li in range(params["wqa"].shape[0]):
        x = block(x, params, li, hy, precision)
    x = x[jnp.asarray(rows)]
    v = params["unembed"].shape[1]
    step = -(-v // vocab_chunks)
    parts = [_head(x, params["final_norm"], params["unembed"], a, min(a + step, v), precision, hy["rms_norm_eps"])
             for a in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)
