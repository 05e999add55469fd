"""Plain reference of the decoder the cells run: forward pass, loss and
gradients in straightforward ``jax.numpy``, float32, with
``jax.default_matmul_precision("highest")``. No cache, no kernel, no batching,
no import from ``ray_tpu.models`` or ``ray_tpu.ops``.

Shapes are GPT-J's as published (parallel attention + MLP block, 16 heads of
256, gelu_new MLP). The arithmetic follows this repo's block, so that the two
can be compared (the configurations list these under ``departures``): RMSNorm
where GPT-J has LayerNorm, rotary embedding over all 256 dims of a head where
GPT-J rotates ``rotary_dim`` 64, no biases.

``precision``:
  "f32"   the reference: weights upcast to float32, every contraction at
          "highest".
  "int8"  the control of `correct` (never the reference): weights rounded to
          int8 per output channel, matmul inputs in bfloat16. It is the step a
          later PR could be tempted by; the comparison has to refuse it.

Weights come in as the dict the benchmark made from the seed
(``harness/weights.py``): stacked per layer, in the type they are served in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6
ROPE_THETA = 10000.0
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_up", "w_down", "attn_norm")


def _int8(w, contract_axes):
    """Symmetric int8 rounding with one scale per output channel."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return w + jax.lax.stop_gradient(q - w)  # straight through: rounding has no slope


def _fp8(w, contract_axes):
    """float8 (e4m3) rounding with one scale per output channel."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return w + jax.lax.stop_gradient(q - w)


def _mm(spec, x, w, precision, contract_axes):
    """One contraction of activations ``x`` with weights ``w``;
    ``contract_axes`` are the axes of ``w`` that are summed over."""
    if precision == "f32":
        return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if precision == "int8":
        return jnp.einsum(spec, x.astype(jnp.bfloat16),
                          _int8(w, contract_axes).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        return jnp.einsum(spec, x.astype(jnp.bfloat16),
                          _fp8(w, contract_axes).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * weight.astype(jnp.float32)


def rope(x, positions):
    """x (S, H, Hd), positions (S,): rotate-half over the whole head."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (ROPE_THETA ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def attend(q, k, v):
    """Rotary embedding, then causal softmax attention. (S, H, Hd) each."""
    pos = jnp.arange(q.shape[0])
    q, k = rope(q, pos), rope(k, pos)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=jax.lax.Precision.HIGHEST)
    scores = scores / math.sqrt(q.shape[-1])
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=jax.lax.Precision.HIGHEST)


def block(x, lw, precision):
    """One parallel block over one sequence. x (S, D) float32."""
    h = rms_norm(x, lw["attn_norm"])
    q = _mm("sd,dhk->shk", h, lw["wq"], precision, (0,))
    k = _mm("sd,dhk->shk", h, lw["wk"], precision, (0,))
    v = _mm("sd,dhk->shk", h, lw["wv"], precision, (0,))
    att_out = _mm("shk,hkd->sd", attend(q, k, v), lw["wo"], precision, (0, 1))
    ff = gelu_new(_mm("sd,df->sf", h, lw["w_up"], precision, (0,)))
    return x + att_out + _mm("sf,fd->sd", ff, lw["w_down"], precision, (0,))


def _embed(params, tokens, precision):
    e = params["embed"]
    if precision in ("int8", "fp8"):
        return (_int8 if precision == "int8" else _fp8)(e[tokens], (1,))
    return e[tokens].astype(jnp.float32)


# -- serving: one sequence, one tensor at a time ---------------------------
#
# Beside 12 GB of served weights and the KV pool there is room for one float32
# tensor (the largest, 4096 x 16384, is 268 MB), not for a float32 layer. So
# the forward pass below is the same ``block`` cut at its contractions: each is
# one jitted call that takes the stacked tensor and the layer's index, and the
# head goes in vocabulary chunks.


@functools.partial(jax.jit, static_argnames=("spec", "contract_axes", "precision"))
def _project(x, w, li, spec, contract_axes, precision):
    return _mm(spec, x, w[li], precision, contract_axes)


@jax.jit
def _norm(x, w, li):
    return rms_norm(x, w[li])


_attend = jax.jit(attend)


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision"))
def _head(x, final_norm, unembed, lo, hi, precision):
    return _mm("sd,dv->sv", rms_norm(x, final_norm), unembed[:, lo:hi], precision, (0,))


def block_by_tensor(x, params, li, precision):
    """``block`` for layer ``li``, one contraction a call."""
    h = _norm(x, params["attn_norm"], li)
    q = _project(h, params["wq"], li, "sd,dhk->shk", (0,), precision)
    k = _project(h, params["wk"], li, "sd,dhk->shk", (0,), precision)
    v = _project(h, params["wv"], li, "sd,dhk->shk", (0,), precision)
    att_out = _project(_attend(q, k, v), params["wo"], li, "shk,hkd->sd", (0, 1), precision)
    ff = gelu_new(_project(h, params["w_up"], li, "sd,df->sf", (0,), precision))
    return x + att_out + _project(ff, params["w_down"], li, "sf,fd->sd", (0,), precision)


def logits_at(params, tokens, rows, precision="f32", vocab_chunks=4):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    x = _embed(params, jnp.asarray(tokens), precision)
    for li in range(params["wq"].shape[0]):
        x = block_by_tensor(x, params, li, precision)
    x = x[jnp.asarray(rows)]
    v = params["unembed"].shape[1]
    step = -(-v // vocab_chunks)
    parts = [_head(x, params["final_norm"], params["unembed"], a, min(a + step, v), precision)
             for a in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)


# -- training: loss and gradients --------------------------------------------


def loss(params, tokens, targets, precision="f32"):
    """Mean next-token cross-entropy over ``tokens`` (B, S), one sequence at
    a time; each block recomputed in the backward pass (same mathematics,
    one layer's activations alive at a time)."""
    n_layers = params["wq"].shape[0]
    blk = jax.checkpoint(functools.partial(block, precision=precision))
    total = 0.0
    for b in range(tokens.shape[0]):
        x = _embed(params, tokens[b], precision)
        for li in range(n_layers):
            x = blk(x, {k: params[k][li] for k in LAYER_KEYS})
        logits = _mm("sd,dv->sv", rms_norm(x, params["final_norm"]), params["unembed"],
                     precision, (0,))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[b][:, None], axis=-1)[:, 0]
        total = total + jnp.sum(logz - gold)
    return total / (tokens.shape[0] * tokens.shape[1])


@functools.partial(jax.jit, static_argnames=("precision", "leaves"))
def loss_and_grads(params, tokens, targets, precision="f32",
                   leaves=("attn_norm", "final_norm", "wq", "w_down")):
    """Loss and its float32 gradients with respect to the named leaves."""

    def f(sub):
        return loss({**params, **sub}, tokens, targets, precision)

    sub = {k: params[k].astype(jnp.float32) for k in leaves}
    return jax.value_and_grad(f)(sub)


def mean_loss_and_grads(params, tokens, targets, precision="f32",
                        leaves=("attn_norm", "final_norm", "wq", "w_down")):
    """``loss_and_grads`` over a whole batch, one sequence a call (one small
    program run B times, one sequence's activations alive at a time): the
    mean loss as a float and the mean float32 gradients."""
    n = tokens.shape[0]
    total, grads = 0.0, None
    for b in range(n):
        one, g = loss_and_grads(params, tokens[b:b + 1], targets[b:b + 1], precision=precision, leaves=leaves)
        total += float(one)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / n, jax.tree.map(lambda x: x / n, grads)
