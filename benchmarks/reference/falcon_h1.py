"""Plain reference of Falcon-H1's language model (``model_type`` ``falcon_h1``):
the forward pass in straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``. No cache, no kernel, no chunked form of the recurrence, no
batching, no import from ``ray_tpu``.

Every layer holds a Mamba-2 mixer and grouped-query attention side by side, on
one normed input. ``N`` is RMSNorm (eps ``rms_norm_eps``), ``m(name)`` a µP
multiplier of the published config, applied in the open where the published
forward pass applies it:

    x0 = m(embedding) E[token]
    u  = N_in(x);   h = x + m(ssm_out) SSM(m(ssm_in) u) + m(attention_out) Attn(m(attention_in) u)
    y  = h + m(mlp_1) W_d( W_u v * silu(m(mlp_0) W_g v) ),   v = N_ff(h)
    logits = m(lm_head) W_head N_final(x_L)        (untied)

    Attn(u): q = W_q u as H heads of d; k = m(key) W_k u, v = W_v u as G heads;
             rotary over half-split pairs (j, j + d/2) of q and k at the absolute
             position, theta ``rope_theta``, no scaling; softmax(q_h . k_{h // (H/G)}
             / sqrt(d)) over positions 0 .. t; W_o. No bias.

    SSM(u):  [z | x | B | C | dt] = (W_in u) * m(ssm_0..4) over the five segments
             [x | B | C] <- silu(b_conv + causal depthwise convolution of width K)
             dt_h = softplus(dt_h + dt_bias_h);  A_h = -exp(A_log_h)
             head h = P channels of x; group g(h) = h // (H_s / G_s) gives B_g, C_g
             S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T        S_h: P x N, from 0
             y_h = S_h C_g + D_h x_h
             y <- w * RMSNorm_group(y * silu(z))     the gate first, then a norm
                  over each of the G_s groups' channels
             W_out y        (no bias)

The state is stepped a token at a time (``_recurrence``: a scan over the
sequence); the program's chunked prefill and its kernel are held to this.

The weights are the dict the family made from the seed
(``families/falcon_h1.py``), stacked as the program stacks them: ``wqkv`` is q's,
k's and v's columns side by side, ``ssm_in`` [z | x | B | C | dt]. Its ``hyper``
entry carries what no shape tells: the head counts, ``mamba_n_groups``,
``mamba_d_state``, ``rms_norm_eps``, ``rope_theta`` and every multiplier.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the recurrence, the convolution, the softmax and the norms
stay float32. The MLP's down projection is rounded a block of its rows at a
time (a finer scale than a whole column's: the controls read no higher for it).

It has to fit beside 10.5 GB of served weights and a 2.5 GB pool, so
``logits_at`` goes a tensor at a time: one contraction a jitted call, the MLP in
blocks of its width, attention a K/V head and a block of query rows at a time,
the head in blocks of the vocabulary (the leaves are ``reference/longcat.py``'s
and ``reference/exaone_moe.py``'s, which know no model).

**Where the program departs from this file** (the configuration's
``departures`` repeat them):
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.exaone_moe import _attend, rope
from benchmarks.reference.longcat import ROUND, _mm, _project, rms_norm, silu
from benchmarks.reference.phi4flash import short_conv

departures = [
    "a prompt's recurrence runs in chunks of 128 positions as matrix products (the SSD form), the state carried between "
    "chunks; the reference steps it a token at a time",
    "a sequence's state lies as (256, 4096) float32, the state dimension in the sublanes and the 32 heads' channels side by "
    "side in the lanes; the reference keeps (heads, 128, 256)",
    "multipliers ahead of a bias-free projection (ssm_in with the five ssm_multipliers, attention_in with key_multiplier) "
    "are one float32 vector over that projection's outputs, and lm_head_multiplier is on the final norm's output: the same "
    "products, one rounding apart; embedding_multiplier is applied to the residual stream as layer 0 finds it",
    "K is stored rotated and multiplied, K and V flat (a position's four heads as four consecutive rows of 128), every "
    "layer's rows behind one block table; q, k and v are one fused projection",
]
__doc__ += "\n".join(f"* {d}" for d in departures) + "\n"

HYPER_INT = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads", "mamba_n_groups", "mamba_d_state")
MLP_BLOCKS, VOCAB_BLOCKS = 4, 16


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers (``ssm_multipliers`` and
    ``mlp_multipliers`` as lists)."""
    h = {k: np.asarray(v).tolist() for k, v in params["hyper"].items()}
    return {**h, **{k: int(h[k]) for k in HYPER_INT}}


def m(hy, name, index=None):
    """The µP multiplier ``name`` (its ``index``-th, of a list)."""
    return hy[name] if index is None else hy[name][index]


# -- the Mamba-2 mixer ------------------------------------------------------------------


def group_of(head: int, heads: int, groups: int) -> int:
    """The group whose ``B`` and ``C`` head ``head`` reads."""
    return head // (heads // groups)


def kept(state):
    """The state as it is carried from a token to the next: float32."""
    return state


@jax.jit
def _recurrence(x, dt, b, c, a):
    """One head a token at a time from an empty state. x (S, P), dt (S,), b, c
    (S, N) its group's, a () negative -> y (S, P), before the skip."""

    def token(state, xs):
        x, dt, b, c = xs
        state = kept(jnp.exp(dt * a) * state + (dt * x)[:, None] * b[None, :])
        return state, jnp.sum(state * c[None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((x.shape[1], b.shape[1]), jnp.float32), (x, dt, b, c))
    return y


def gated_norm(y, z, w, groups, eps):
    """``mamba_rms_norm`` true, ``norm_before_gate`` false: the gate first, then
    an RMSNorm over each group's channels."""
    s = y.shape[0]
    g = (y * silu(z)).reshape(s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, -1) * w.astype(jnp.float32)


def ssm_mixer(u, params, li, hy, precision):
    """Layer ``li``'s Mamba-2 mixer over one sequence ``u`` (S, D), already
    multiplied by ``ssm_in_multiplier``: (S, D)."""
    s = u.shape[0]
    heads, groups, n = hy["mamba_n_heads"], hy["mamba_n_groups"], hy["mamba_d_state"]
    d_ssm = params["ssm_out"].shape[1]
    p = d_ssm // heads
    proj = _project(u, params["ssm_in"], li, "sd,dc->sc", (0,), precision)
    z, x, b, c, dt = jnp.split(proj, np.cumsum([d_ssm, d_ssm, groups * n, groups * n]).tolist(), axis=-1)
    z, x, b, c, dt = (t * m(hy, "ssm_multipliers", i) for i, t in enumerate((z, x, b, c, dt)))
    conv = short_conv(jnp.concatenate([x, b, c], axis=-1), params["ssm_conv"][li], params["ssm_conv_b"][li])
    x, b, c = jnp.split(conv, [d_ssm, d_ssm + groups * n], axis=-1)
    x, b, c = x.reshape(s, heads, p), b.reshape(s, groups, n), c.reshape(s, groups, n)
    dt = jax.nn.softplus(dt + params["ssm_dt_b"][li].astype(jnp.float32))
    a = -jnp.exp(params["ssm_a_log"][li].astype(jnp.float32))
    ys = []
    for h in range(heads):
        g = group_of(h, heads, groups)
        ys.append(_recurrence(x[:, h], dt[:, h], b[:, g], c[:, g], a[h]) + params["ssm_d"][li, h].astype(jnp.float32) * x[:, h])
    y = gated_norm(jnp.concatenate(ys, axis=-1), z, params["ssm_norm"][li], groups, hy["rms_norm_eps"])
    return _project(y, params["ssm_out"], li, "sc,cd->sd", (0,), precision)


# -- attention ----------------------------------------------------------------------------


def attention(u, params, li, hy, precision):
    """Layer ``li``'s attention over one sequence ``u`` (S, D), already
    multiplied by ``attention_in_multiplier``: (S, D)."""
    H, G = hy["num_attention_heads"], hy["num_key_value_heads"]
    s = u.shape[0]
    qkv = _project(u, params["wqkv"], li, "sd,dk->sk", (0,), precision)
    d = qkv.shape[-1] // (H + 2 * G)
    q, k, v = (t.reshape(s, -1, d) for t in jnp.split(qkv, [H * d, (H + G) * d], axis=-1))
    k = k * m(hy, "key_multiplier")
    q, k = rope(q, jnp.arange(s), hy["rope_theta"]), rope(k, jnp.arange(s), hy["rope_theta"])
    r = H // G
    o = jnp.concatenate([_attend(q[:, g * r:(g + 1) * r], k[:, g], v[:, g], None) for g in range(G)], axis=1)
    return _project(o.reshape(s, -1), params["wo"], li, "sk,kd->sd", (0,), precision)


# -- the block and the model ------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision"))
def _mlp_block(v, w_gate, w_up, w_down, li, gate_multiplier, lo, hi, precision):
    g = _mm("sd,df->sf", v, w_gate[li, :, lo:hi], precision, (0,)) * gate_multiplier
    up = _mm("sd,df->sf", v, w_up[li, :, lo:hi], precision, (0,))
    return _mm("sf,fd->sd", up * silu(g), w_down[li, lo:hi], precision, (0,))


def mlp(v, params, li, hy, precision):
    width = params["w_gate"].shape[-1]
    step = -(-width // MLP_BLOCKS)
    y = sum(_mlp_block(v, params["w_gate"], params["w_up"], params["w_down"], li, m(hy, "mlp_multipliers", 0),
                       lo, min(lo + step, width), precision) for lo in range(0, width, step))
    return y * m(hy, "mlp_multipliers", 1)


def mixers(x, u, params, li, hy, precision):
    """The two mixers side by side on one normed input, summed into the stream."""
    return (x + m(hy, "ssm_out_multiplier") * ssm_mixer(m(hy, "ssm_in_multiplier") * u, params, li, hy, precision)
            + m(hy, "attention_out_multiplier") * attention(m(hy, "attention_in_multiplier") * u, params, li, hy, precision))


def block(x, params, li, hy, precision):
    eps = hy["rms_norm_eps"]
    h = mixers(x, rms_norm(x, params["in_norm"][li], eps), params, li, hy, precision)
    return h + mlp(rms_norm(h, params["ff_norm"][li], eps), params, li, hy, precision)


def _embed(params, tokens, precision):
    e = params["embed"][tokens]
    return ROUND[precision](e, (1,)) if precision in ROUND else e.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision"))
def _head(x, unembed, lo, hi, precision):
    return _mm("sd,dv->sv", x, unembed[:, lo:hi], precision, (0,))


def logits_at(params, tokens, rows, precision="f32", vocab_blocks=VOCAB_BLOCKS):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    hy = hyper(params)
    x = _embed(params, jnp.asarray(tokens), precision) * m(hy, "embedding_multiplier")
    for li in range(params["wqkv"].shape[0]):
        x = block(x, params, li, hy, precision)
    x = rms_norm(x[jnp.asarray(rows)], params["final_norm"], hy["rms_norm_eps"])
    v = params["unembed"].shape[1]
    step = -(-v // vocab_blocks)
    logits = jnp.concatenate([_head(x, params["unembed"], a, min(a + step, v), precision) for a in range(0, v, step)], axis=-1)
    return logits * m(hy, "lm_head_multiplier")
