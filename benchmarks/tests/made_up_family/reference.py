"""The made-up family's plain reference (``test_families.py`` writes this
file to ``reference/swiglu_gqa.py`` of a temporary copy): float32,
``highest``, no cache, no kernel, no import from the program. The serial
block: the attention's output joins the stream before the MLP's norm reads
it; SwiGLU over ``w_gate`` and ``w_up``; each K/V head serves
``n_heads / n_kv_heads`` query heads. The contraction, the norm and the
rotary attention are the GPT-J reference's."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.gptj import _embed, _mm, attend, rms_norm

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "attn_norm", "mlp_norm")


def attention(h, lw, precision):
    q = _mm("sd,dhk->shk", h, lw["wq"], precision, (0,))
    k = _mm("sd,dhk->shk", h, lw["wk"], precision, (0,))
    v = _mm("sd,dhk->shk", h, lw["wv"], precision, (0,))
    rep = q.shape[1] // k.shape[1]
    att = attend(q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1))
    return _mm("shk,hkd->sd", att, lw["wo"], precision, (0, 1))


def mlp(m, lw, precision):
    gate = _mm("sd,df->sf", m, lw["w_gate"], precision, (0,))
    up = _mm("sd,df->sf", m, lw["w_up"], precision, (0,))
    return _mm("sf,fd->sd", jax.nn.silu(gate) * up, lw["w_down"], precision, (0,))


def block(x, lw, precision):
    """One serial block over one sequence. x (S, D) float32."""
    x = x + attention(rms_norm(x, lw["attn_norm"]), lw, precision)
    return x + mlp(rms_norm(x, lw["mlp_norm"]), lw, precision)


def hidden(params, tokens, precision):
    x = _embed(params, tokens, precision)
    for li in range(params["wq"].shape[0]):
        x = block(x, {k: params[k][li] for k in LAYER_KEYS}, precision)
    return rms_norm(x, params["final_norm"])


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits_at(params, tokens, rows, precision):
    return _mm("sd,dv->sv", hidden(params, tokens, precision)[rows], params["unembed"], precision, (0,))


def logits_at(params, tokens, rows, precision="f32"):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``."""
    return _logits_at(params, jnp.asarray(tokens), jnp.asarray(rows), precision)


def loss(params, tokens, targets, precision="f32"):
    """Mean next-token cross-entropy over ``tokens`` (B, S), a sequence at a time."""
    total = 0.0
    for b in range(tokens.shape[0]):
        logits = _mm("sd,dv->sv", hidden(params, tokens[b], precision), params["unembed"], precision, (0,))
        gold = jnp.take_along_axis(logits, targets[b][:, None], axis=-1)[:, 0]
        total = total + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)
    return total / (tokens.shape[0] * tokens.shape[1])


@functools.partial(jax.jit, static_argnames=("precision", "leaves"))
def _loss_and_grads(params, tokens, targets, precision, leaves):
    sub = {k: params[k].astype(jnp.float32) for k in leaves}
    return jax.value_and_grad(lambda sub: loss({**params, **sub}, tokens, targets, precision))(sub)


def mean_loss_and_grads(params, tokens, targets, precision="f32", leaves=("attn_norm", "wq")):
    """The mean loss of the whole batch as a float and its float32 gradients
    with respect to the named leaves."""
    value, grads = _loss_and_grads(params, tokens, targets, precision, tuple(leaves))
    return float(value), grads
