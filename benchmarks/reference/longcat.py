"""Plain reference of LongCat-Flash's language model: the forward pass in
straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``. No cache, no kernel, no batching, no absorbed
attention, no grouped matmul, no import from ``ray_tpu``.

One layer (``N`` is RMSNorm with a float32 weight; attentions are numbered
``2l`` and ``2l + 1``):

    a = x + MLA_0(N(x; g_in0))
    u = N(a; g_post0)
    m = MoE(u)                         # the shortcut: used in the last line only
    b = a + FFN_0(u)
    c = b + MLA_1(N(b; g_in1))
    y = c + FFN_1(N(c; g_post1)) + m

    FFN(u)  = (silu(u Wg) * (u Wu)) Wd
    MLA(h)  : cq = N(h Wqa; g_qa) * scale_q;  [q_n | q_r] = cq Wqb per head
              [ckv | k_r] = h Wkva;  ckv = N(ckv; g_kva) * scale_kv
              q_r, k_r rotated over pairs (2j, 2j+1); k_r shared by the heads
              [k_n | v] = ckv Wkvb per head
              p = softmax((q_n . k_n + q_r . k_r) / sqrt(d_n + d_r)), causal
              out = (p v) Wo
    MoE(u)  : p = softmax(f32(u) f32(Wr)) over every output (routed + zero)
              chosen = top-k of (p + bias);  w_e = s * p_e, not renormalised
              sum over chosen routed e of w_e Expert_e(u) + sum over chosen zero e of w_e u
              Expert_e = FFN with the expert's three tensors

The weights are the dict the family made from the seed
(``families/longcat.py``), stacked per layer, in the type they are served in.
Its ``hyper`` entry carries the numbers no shape tells: ``n_routed_experts``
(outputs at or above it are zero-compute experts), ``expert_offset`` (the held
experts are ``expert_offset ..`` of them, as many as ``e_gate`` has),
``moe_topk``, ``routed_scaling_factor``, ``rms_norm_eps``, ``rope_theta``,
``scale_q``, ``scale_kv``. A chosen expert that is not held adds nothing, here
as in the program: the reference is given the same share.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the router keeps float32 arithmetic on its rounded weights.

It has to fit beside 10 GB of served weights and the pool, so ``logits_at``
goes a tensor at a time: one contraction a jitted call, experts one at a time,
attention in blocks of heads and query rows, the head in vocabulary chunks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK, ROW_BLOCK = 8, 512


def _int8(w, contract_axes):
    """Symmetric int8 rounding with one scale per output channel."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _fp8(w, contract_axes):
    """float8 (e4m3) rounding with one scale per output channel."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


ROUND = {"int8": _int8, "fp8": _fp8}


def _mm(spec, x, w, precision, contract_axes):
    """One contraction of activations ``x`` with weights ``w``;
    ``contract_axes`` are the axes of ``w`` that are summed over."""
    if precision == "f32":
        return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32), precision=HIGHEST)
    if precision in ROUND:
        return jnp.einsum(spec, x.astype(jnp.bfloat16), ROUND[precision](w, contract_axes).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).item() for k, v in params["hyper"].items()}
    return {**h, **{k: int(h[k]) for k in ("n_routed_experts", "expert_offset", "moe_topk")}}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def rope(x, positions, theta):
    """``x`` (S, d) or (S, H, d): pairs (2j, 2j+1) of the last axis rotated by
    ``positions * theta ** (-2j / d)``."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * (1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)))
    if x.ndim == 3:
        ang = ang[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s], axis=-1).reshape(x.shape)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


# -- the jitted leaves: one contraction (or one block of attention) a call -----


@functools.partial(jax.jit, static_argnames=("spec", "contract_axes", "precision"))
def _project(x, w, index, spec, contract_axes, precision):
    return _mm(spec, x, w[index], precision, contract_axes)


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q_n, q_r, k_n, k_r, v, scale):
    """Causal softmax attention of one block of heads over one sequence from
    position 0, query rows in blocks. q_n, k_n (S, h, d_n); q_r (S, h, d_r);
    k_r (S, d_r) shared; v (S, h, d_v)."""
    s = q_n.shape[0]
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q_n[lo:hi], k_n[:hi], precision=HIGHEST)
        scores = (scores + jnp.einsum("qhd,kd->hqk", q_r[lo:hi], k_r[:hi], precision=HIGHEST)) * scale
        scores = jnp.where(pos[None, lo:hi, None] >= pos[None, None, :hi], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v[:hi], precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(u, e_gate, e_up, e_down, index, precision):
    hidden = silu(_mm("sd,df->sf", u, e_gate[index], precision, (0,))) * _mm("sd,df->sf", u, e_up[index], precision, (0,))
    return _mm("sf,fd->sd", hidden, e_down[index], precision, (0,))


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision", "eps"))
def _head(x, final_norm, unembed, lo, hi, precision, eps):
    return _mm("sd,dv->sv", rms_norm(x, final_norm, eps), unembed[:, lo:hi], precision, (0,))


# -- the layer's parts ---------------------------------------------------------


def mla(h, params, li, i, hy, precision):
    """Latent attention ``i`` of layer ``li`` over one sequence ``h`` (S, D)."""
    at = (li, i)
    rkv, heads = params["kva_norm"].shape[-1], params["wkvb"].shape[-3]
    d_r = params["wkva"].shape[-2] - rkv
    d_n = params["wqb"].shape[-2] // heads - d_r
    pos = jnp.arange(h.shape[0])
    cq = rms_norm(_project(h, params["wqa"], at, "sd,dr->sr", (0,), precision), params["qa_norm"][at],
                  hy["rms_norm_eps"]) * hy["scale_q"]
    q = _project(cq, params["wqb"], at, "sr,kr->sk", (1,), precision).reshape(h.shape[0], heads, d_n + d_r)
    kva = _project(h, params["wkva"], at, "sd,rd->sr", (1,), precision)
    ckv = rms_norm(kva[:, :rkv], params["kva_norm"][at], hy["rms_norm_eps"]) * hy["scale_kv"]
    k_r = rope(kva[:, rkv:], pos, hy["rope_theta"])
    q_n, q_r = q[..., :d_n], rope(q[..., d_n:], pos, hy["rope_theta"])
    kv = _project(ckv, params["wkvb"], at, "sr,hrk->shk", (1,), precision)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = 1.0 / math.sqrt(d_n + d_r)
    att = jnp.concatenate([
        _attend(q_n[:, a:a + HEAD_BLOCK], q_r[:, a:a + HEAD_BLOCK], k_n[:, a:a + HEAD_BLOCK], k_r,
                v[:, a:a + HEAD_BLOCK], scale)
        for a in range(0, heads, HEAD_BLOCK)], axis=1)
    return _project(att.reshape(h.shape[0], -1), params["wo"], at, "sk,kd->sd", (0,), precision)


def ffn(u, params, li, i, precision):
    at = (li, i)
    hidden = silu(_project(u, params["w_gate"], at, "sd,df->sf", (0,), precision)) * _project(
        u, params["w_up"], at, "sd,df->sf", (0,), precision)
    return _project(hidden, params["w_down"], at, "sf,fd->sd", (0,), precision)


def route(u, router, bias, hy, precision):
    """(weights (S, K), chosen outputs (S, K)): softmax in float32 over every
    output, the top-k of ``p + bias`` chosen, ``w = s * p`` not renormalised."""
    w = ROUND[precision](router, (0,)) if precision in ROUND else router.astype(jnp.float32)
    p = jax.nn.softmax(jnp.einsum("sd,dn->sn", u.astype(jnp.float32), w, precision=HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(p + bias.astype(jnp.float32), hy["moe_topk"])
    return hy["routed_scaling_factor"] * jnp.take_along_axis(p, chosen, axis=-1), chosen


def identity_part(u, weights, chosen, hy):
    """What the chosen zero-compute experts add: their weights times ``u``."""
    return jnp.sum(jnp.where(chosen >= hy["n_routed_experts"], weights, 0.0), axis=-1, keepdims=True) * u


def routed_part(u, weights, chosen, params, li, hy, precision):
    """What the held experts add, one expert at a time, each over the whole
    sequence and weighted by zero where a token did not choose it."""
    out = jnp.zeros_like(u)
    for e in range(params["e_gate"].shape[1]):
        w = jnp.sum(jnp.where(chosen == hy["expert_offset"] + e, weights, 0.0), axis=-1, keepdims=True)
        out = out + w * _expert(u, params["e_gate"], params["e_up"], params["e_down"], (li, e), precision)
    return out


def moe(u, params, li, hy, precision):
    weights, chosen = route(u, params["router"][li], params["router_bias"][li], hy, precision)
    return routed_part(u, weights, chosen, params, li, hy, precision) + identity_part(u, weights, chosen, hy)


def block(x, params, li, hy, precision):
    """One layer over one sequence. x (S, D) float32."""
    eps = hy["rms_norm_eps"]
    a = x + mla(rms_norm(x, params["in_norm"][li, 0], eps), params, li, 0, hy, precision)
    u = rms_norm(a, params["post_norm"][li, 0], eps)
    m = moe(u, params, li, hy, precision)
    b = a + ffn(u, params, li, 0, precision)
    c = b + mla(rms_norm(b, params["in_norm"][li, 1], eps), params, li, 1, hy, precision)
    return c + ffn(rms_norm(c, params["post_norm"][li, 1], eps), params, li, 1, precision) + m


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
