"""Plain reference of Granite-4.0-H-Small's language model (``model_type``
``granitemoehybrid``): the forward pass in straightforward ``jax.numpy``,
float32, every contraction at ``Precision.HIGHEST``. No cache, no kernel, no
chunked form of the recurrence, no grouped matmul, no batching, no import from
``ray_tpu``.

Layer ``i`` of a period of ten is attention where ``i % 10 == 5`` and a Mamba-2
mixer otherwise; every layer's second half is the routed experts plus a shared
expert. ``N`` is RMSNorm (eps ``rms_norm_eps``), ``m(name)`` a multiplier of the
published config, applied in the open where the published forward pass applies
it:

    x_0 = m(embedding) E[token]
    h   = x + m(residual) Mix_i(N_in(x))
    y   = h + m(residual) ( sum_k w_k E_k(v) + S(v) ),   v = N_post(h)
    logits = E^T N_final(x_L) / m(logits_scaling)        (tied)

    Attn(u): q = W_q u as H heads of d; k = W_k u, v = W_v u as G heads;
             softmax(m(attention) q_h . k_{h // (H/G)}) over positions 0 .. t;
             W_o. No rotary, no position signal of any kind, no bias.
    SSM(u):  [z | x | B | C | dt] = W_in u, widths d_ssm, d_ssm, G N, G N, H_s
             [x | B | C] <- silu(b_conv + causal depthwise convolution of width K)
             dt_h = softplus(dt_h + dt_bias_h);  A_h = -exp(A_log_h)
             head h = P channels of x; group g(h) = h // (H_s / G_s) gives B_g, C_g
             S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T        S_h: P x N, from 0
             y_h = S_h C_g + D_h x_h
             y <- w * RMSNorm(y * silu(z))     the gate first, then a norm over
                  each group's channels (one group: all of them)
             W_out y        (no bias)
    Router:  l = W_r v in float32; the k largest chosen; w = softmax over those k
    E_k(v) = W_down,k( silu(W_gate,k v) * W_up,k v );  S likewise, every token

The state is stepped a token at a time, every head at once (``_recurrence``: a
scan over the sequence); the program's chunked prefill and its kernel are held
to this. The expert layer is a loop over the held experts, each over the whole
sequence and weighted by zero where a token did not choose it; a chosen expert
that is not held adds nothing, here as in the program: the reference is given
the same share (``expert_offset ..``, as many as ``e_gate`` has) and the same
rows of the vocabulary.

The weights are the dict the family made from the seed
(``families/granite_hybrid.py``), stacked as the program stacks them: the two
norms, the router, the experts and the shared expert over all layers, the
mixers' tensors over the Mamba layers, ``wqkv`` (q's, k's and v's columns side
by side) and ``wo`` over the attention layers. Its ``hyper`` entry carries what
no shape tells: the head counts, ``mamba_n_groups``, ``mamba_d_state``,
``expert_offset``, ``num_experts_per_tok``, ``rms_norm_eps`` and the four
multipliers.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the router keeps float32 arithmetic on its rounded weights;
the recurrence, the convolution, the softmaxes and the norms stay float32.

It has to fit beside 9.5 GB of served weights and a 2.3 GB pool, so
``logits_at`` goes a tensor at a time: one contraction a jitted call, experts
one at a time, attention a K/V head's group and a block of query rows at a
time, the head in blocks of the vocabulary (the leaves are
``reference/longcat.py``'s, which know no model).

**Where the program departs from this file** (the configuration's
``departures`` repeat them):
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.longcat import HIGHEST, ROUND, ROW_BLOCK, _expert as _ffn, _mm, _project, rms_norm, silu
from benchmarks.reference.phi4flash import short_conv

departures = [
    "a prompt's recurrence runs in chunks of 256 positions as matrix products (the SSD form), the state carried between "
    "chunks; the reference steps it a token at a time",
    "a sequence's state lies as (128, 8192) float32 a Mamba layer, the state dimension in the sublanes and the 128 heads' "
    "channels side by side in the lanes; the reference keeps (heads, 64, 128)",
    "1 / logits_scaling is on the final norm's weight, ahead of the head's matrix (a power of two: the same logits); "
    "embedding_multiplier is applied to the residual stream as layer 0 finds it",
    "an expert's published input_linear [gate | up] is two matrices (e_gate, e_up), as the shared expert's is; q, k and v are "
    "one fused projection",
    "the held experts' (token, choice) rows go through grouped matmuls in sorted order; the reference loops over the experts",
    "K and V are stored flat (a position's eight heads as eight consecutive rows of 128), the attention layers' rows alone "
    "behind the block table",
]
__doc__ += "\n".join(f"* {d}" for d in departures) + "\n"

HYPER_INT = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads", "mamba_n_groups", "mamba_d_state",
             "expert_offset", "num_experts_per_tok")
PERIOD, ATTENTION_AT = 10, 5
VOCAB_BLOCKS = 8


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).item() for k, v in params["hyper"].items()}
    return {**h, **{k: int(h[k]) for k in HYPER_INT}}


# -- what a layer is, each a function a planted fault can replace ------------------


def m(hy, name):
    """The published multiplier ``name``."""
    return hy[name]


def is_attention(i: int) -> bool:
    return i % PERIOD == ATTENTION_AT


def score_scale(hy, d: int) -> float:
    """What q . k is multiplied by ahead of the softmax: ``attention_multiplier``."""
    return m(hy, "attention_multiplier")


def positioned(q, k, hy):
    """q and k (S, heads, d) as the scores take them: as they are (``nope``)."""
    return q, k


def group_of(head: int, heads: int, groups: int) -> int:
    """The group whose ``B`` and ``C`` head ``head`` reads."""
    return head // (heads // groups)


def kept(state):
    """The state as it is carried from a token to the next: float32."""
    return state


def skip(d, x):
    """``D_h x_h``, the recurrence's way round the state. d (H,), x (S, H, P)."""
    return d.astype(jnp.float32)[None, :, None] * x


def gated_norm(y, z, w, groups, eps):
    """The gate first, then an RMSNorm over each group's channels."""
    s = y.shape[0]
    g = (y * silu(z)).reshape(s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(s, -1) * w.astype(jnp.float32)


def chosen_weights(logits, chosen):
    """The chosen experts' weights: a softmax over the chosen logits alone."""
    return jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)


def logits_scaled(logits, hy):
    return logits / m(hy, "logits_scaling")


# -- the Mamba-2 mixer ------------------------------------------------------------------


@jax.jit
def _recurrence(x, dt, b, c, a):
    """Every head a token at a time from an empty state. x (S, H, P), dt (S,
    H), b, c (S, H, N) each head's group's, a (H,) negative -> y (S, H, P),
    before the skip."""

    def token(state, xs):
        x, dt, b, c = xs
        state = kept(jnp.exp(dt * a)[:, None, None] * state + (dt[:, None] * x)[:, :, None] * b[:, None, :])
        return state, jnp.sum(state * c[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((*x.shape[1:], b.shape[-1]), jnp.float32), (x, dt, b, c))
    return y


def ssm_mixer(u, params, mi, hy, precision):
    """Mamba layer number ``mi`` (among the Mamba layers) over one sequence
    ``u`` (S, D): (S, D)."""
    s = u.shape[0]
    heads, groups, n = hy["mamba_n_heads"], hy["mamba_n_groups"], hy["mamba_d_state"]
    d_ssm = params["ssm_out"].shape[1]
    proj = _project(u, params["ssm_in"], mi, "sd,dc->sc", (0,), precision)
    z, x, b, c, dt = jnp.split(proj, np.cumsum([d_ssm, d_ssm, groups * n, groups * n]).tolist(), axis=-1)
    conv = short_conv(jnp.concatenate([x, b, c], axis=-1), params["ssm_conv"][mi], params["ssm_conv_b"][mi])
    x, b, c = jnp.split(conv, [d_ssm, d_ssm + groups * n], axis=-1)
    x, b, c = x.reshape(s, heads, -1), b.reshape(s, groups, n), c.reshape(s, groups, n)
    mine = np.asarray([group_of(h, heads, groups) for h in range(heads)])
    dt = jax.nn.softplus(dt + params["ssm_dt_b"][mi].astype(jnp.float32))
    a = -jnp.exp(params["ssm_a_log"][mi].astype(jnp.float32))
    y = _recurrence(x, dt, b[:, mine], c[:, mine], a) + skip(params["ssm_d"][mi], x)
    y = gated_norm(y.reshape(s, d_ssm), z, params["ssm_norm"][mi], groups, hy["rms_norm_eps"])
    return _project(y, params["ssm_out"], mi, "sc,cd->sd", (0,), precision)


# -- attention ----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q, k, v, scale):
    """Causal softmax attention of one K/V head's group of query heads over one
    sequence from position 0, query rows in blocks. q (S, R, d); k, v (S, d)."""
    s = k.shape[0]
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, s)
        scores = jnp.einsum("qrd,kd->rqk", q[lo:hi], k[:hi], precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where((pos[lo:hi, None] >= pos[None, :hi])[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("rqk,kd->qrd", p, v[:hi], precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def attention(u, params, ai, hy, precision):
    """Attention layer number ``ai`` (among the attention layers) over one
    sequence ``u`` (S, D): (S, D)."""
    H, G = hy["num_attention_heads"], hy["num_key_value_heads"]
    s = u.shape[0]
    qkv = _project(u, params["wqkv"], ai, "sd,dk->sk", (0,), precision)
    d = qkv.shape[-1] // (H + 2 * G)
    q, k, v = (t.reshape(s, -1, d) for t in jnp.split(qkv, [H * d, (H + G) * d], axis=-1))
    q, k = positioned(q, k, hy)
    r = H // G
    o = jnp.concatenate([_attend(q[:, g * r:(g + 1) * r], k[:, g], v[:, g], float(score_scale(hy, d))) for g in range(G)],
                        axis=1)
    return _project(o.reshape(s, -1), params["wo"], ai, "sk,kd->sd", (0,), precision)


# -- the expert layer ---------------------------------------------------------------------


def route(u, router, hy, precision):
    """(weights (S, K), chosen experts (S, K)): logits in float32, the k
    largest chosen, the weights a softmax over the chosen."""
    w = ROUND[precision](router, (0,)) if precision in ROUND else router.astype(jnp.float32)
    logits = jnp.einsum("sd,dn->sn", u.astype(jnp.float32), w, precision=HIGHEST)
    _, chosen = jax.lax.top_k(logits, hy["num_experts_per_tok"])
    return chosen_weights(logits, chosen), chosen


def routed_part(u, weights, chosen, params, li, hy, precision):
    """What the held experts add, one expert at a time."""
    out = jnp.zeros_like(u)
    for e in range(params["e_gate"].shape[1]):
        mine = jnp.sum(jnp.where(chosen == hy["expert_offset"] + e, weights, 0.0), axis=-1, keepdims=True)
        out = out + mine * _ffn(u, params["e_gate"], params["e_up"], params["e_down"], (li, e), precision)
    return out


def shared_part(u, params, li, precision):
    """The shared expert: every token, weight 1."""
    return _ffn(u, params["s_gate"], params["s_up"], params["s_down"], li, precision)


def moe(u, params, li, hy, precision):
    weights, chosen = route(u, params["router"][li], hy, precision)
    return routed_part(u, weights, chosen, params, li, hy, precision) + shared_part(u, params, li, precision)


# -- the block and the model ------------------------------------------------------------


def mix(u, params, li, hy, precision):
    """Layer ``li``'s mixer: attention layers before ``li`` are one a period
    from layer 5 on."""
    before = (li + PERIOD - 1 - ATTENTION_AT) // PERIOD
    if is_attention(li):
        return attention(u, params, before, hy, precision)
    return ssm_mixer(u, params, li - before, hy, precision)


def block(x, params, li, hy, precision):
    eps, m_r = hy["rms_norm_eps"], m(hy, "residual_multiplier")
    h = x + m_r * mix(rms_norm(x, params["in_norm"][li], eps), params, li, hy, precision)
    return h + m_r * moe(rms_norm(h, params["post_norm"][li], eps), params, li, hy, precision)


def embedded(params, tokens, hy, precision):
    e = params["embed"][tokens]
    e = ROUND[precision](e, (1,)) if precision in ROUND else e.astype(jnp.float32)
    return e * m(hy, "embedding_multiplier")


def hidden_states(params, tokens, precision="f32"):
    """The last layer's output over one sequence, before the final norm."""
    hy = hyper(params)
    x = embedded(params, jnp.asarray(tokens), hy, precision)
    for li in range(params["in_norm"].shape[0]):
        x = block(x, params, li, hy, precision)
    return x


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision", "eps"))
def _tied_head(x, final_norm, embed, lo, hi, precision, eps):
    """Rows ``lo .. hi`` of the vocabulary: the embedding's own rows, a row an output channel."""
    return _mm("sd,vd->sv", rms_norm(x, final_norm, eps), embed[lo:hi], precision, (1,))


def logits_at(params, tokens, rows, precision="f32", vocab_blocks=VOCAB_BLOCKS):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    hy = hyper(params)
    x = hidden_states(params, tokens, precision)[jnp.asarray(rows)]
    v = params["embed"].shape[0]
    step = -(-v // vocab_blocks)
    parts = [_tied_head(x, params["final_norm"], params["embed"], a, min(a + step, v), precision, hy["rms_norm_eps"])
             for a in range(0, v, step)]
    return logits_scaled(jnp.concatenate(parts, axis=-1), hy)
