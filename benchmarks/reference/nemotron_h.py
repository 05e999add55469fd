"""Plain reference of Nemotron-H's language model (``model_type``
``nemotron_h``; Nemotron 3 Super 120B-A12B's layer): the forward pass in
straightforward ``jax.numpy``, float32, every contraction at
``Precision.HIGHEST``. No cache, no kernel, no chunked form of the recurrence,
no grouped matmul, no batching, no import from ``ray_tpu``.

**A layer is one norm and one part.** ``hybrid_override_pattern[i]`` names layer
``i``'s part: ``M`` a Mamba-2 mixer, ``E`` an expert layer, ``*`` attention.
``N`` is RMSNorm (eps ``layer_norm_epsilon``, a learned weight):

    x_0 = E[token]
    x  <- x + Part_i(N_i(x))
    logits = W_head N_f(x_L)                       (untied)

    M(u):  [z | x | B | C | dt] = W_in u, widths d_ssm, d_ssm, G N, G N, H_s
           [x | B | C] <- silu(b_conv + causal depthwise convolution of width K)
           dt_h = softplus(dt_h + dt_bias_h), not clamped;  A_h = -exp(A_log_h)
           head h = P channels of x; group g(h) = h // (H_s / G_s) gives B_g, C_g
           S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T        S_h: P x N, from 0
           y_h = S_h C_g + D_h x_h
           y <- w * RMSNorm(y * silu(z))     the gate first, then a norm over
                each of the G_s groups' d_ssm / G_s channels
           W_out y        (no bias)
    *(u):  q = W_q u as H heads of d; k = W_k u, v = W_v u as G heads;
           softmax(q_h . k_{h // (H/G)} / sqrt(d)) over positions 0 .. t; W_o.
           No rotary, no position signal of any kind, no bias.
    E(u):  s = sigmoid(W_r u) in float32; the k largest of s + b chosen;
           w = scale * s / (sum of the chosen s + 1e-20)
           v = W_in^lat u;  E_k(v) = W_down,k relu(W_up,k v)^2   (no gate)
           W_out^lat (sum_k w_k E_k(v)) + S(u),  S(u) = W_down^s relu(W_up^s u)^2

The state is stepped a token at a time, every head at once (``_recurrence``: a
scan over the sequence); the program's chunked prefill and its kernel are held
to this. The expert layer is a loop over the held experts, each over the whole
sequence's latent rows and weighted by zero where a token did not choose it; a
chosen expert that is not held adds nothing, here as in the program: the
reference is given the same share (``expert_offset ..``, as many as ``e_up``
has) and the same rows of the vocabulary.

The weights are the dict the family made from the seed
(``families/nemotron_h.py``), stacked as the program stacks them: ``norm`` over
all layers; the mixers' tensors over the ``M`` layers; ``wqkv`` (q's, k's and
v's columns side by side) and ``wo`` over the ``*`` layers; ``router``,
``router_bias``, ``lat_in``, ``lat_out``, ``e_up``, ``e_down``, ``s_up``,
``s_down`` over the ``E`` layers; ``embed``, ``unembed``, ``final_norm``. Its
``hyper`` entry carries what no shape tells: ``pattern`` (the layers' kinds, a
character's code a layer), the head counts, ``head_dim``, ``n_groups``,
``ssm_state_size``, ``expert_offset``, ``num_experts_per_tok``,
``layer_norm_epsilon`` and ``routed_scaling_factor``.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the router keeps float32 arithmetic on its rounded weights;
the recurrence, the convolution, the softmax, the sigmoid and the norms stay
float32.

It has to fit beside 9.3 GB of served weights and a 1.2 GB pool, so
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

from benchmarks.reference.longcat import HIGHEST, ROUND, ROW_BLOCK, _head, _mm, _project, rms_norm, silu
from benchmarks.reference.phi4flash import short_conv

departures = [
    "a prompt's recurrence runs in chunks of 128 positions as matrix products (the SSD form), the state carried between "
    "chunks; the reference steps it a token at a time",
    "a sequence's state lies as (128, 8192) float32 a mixer layer, the state dimension in the sublanes and the 128 heads' "
    "channels side by side in the lanes; the reference keeps (heads, 64, 128)",
    "the held experts' (token, choice) rows go through grouped matmuls in sorted order, two a window (up, down), the "
    "square taken in float32 between them; the reference loops over the experts",
    "q, k and v are one fused projection",
    "K and V are stored flat (a position's two heads as two consecutive rows of 128), the attention layers' rows alone "
    "behind the block table",
]
__doc__ += "\n".join(f"* {d}" for d in departures) + "\n"

HYPER_INT = ("num_attention_heads", "num_key_value_heads", "head_dim", "mamba_num_heads", "n_groups", "ssm_state_size",
             "expert_offset", "num_experts_per_tok")
VOCAB_BLOCKS = 8


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers, and the layers' kinds
    as the published string."""
    h = {k: np.asarray(v) for k, v in params["hyper"].items()}
    pattern = "".join(chr(c) for c in h.pop("pattern").tolist())
    return {**{k: (int(v.item()) if k in HYPER_INT else float(v.item())) for k, v in h.items()}, "pattern": pattern}


# -- what a layer is, each a function a planted fault can replace ------------------


def score_scale(hy, d: int) -> float:
    """What q . k is multiplied by ahead of the softmax."""
    return d ** -0.5


def positioned(q, k, hy):
    """q and k (S, heads, d) as the scores take them: as they are (no rotary)."""
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


def scores(z):
    """The router's scores over every output, from its float32 logits."""
    return jax.nn.sigmoid(z)


def chosen_weights(s, chosen, hy):
    """The chosen experts' weights: their scores renormalised over the chosen,
    times ``routed_scaling_factor``."""
    mine = jnp.take_along_axis(s, chosen, axis=-1)
    return routed_scale(hy) * mine / (jnp.sum(mine, axis=-1, keepdims=True) + 1e-20)


def routed_scale(hy):
    return hy["routed_scaling_factor"]


def expert_act(h):
    """A routed expert's activation: relu squared, no gate."""
    return jnp.square(jnp.maximum(h, 0.0))


def shared_act(h):
    """The shared expert's: the same."""
    return jnp.square(jnp.maximum(h, 0.0))


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
    """Mixer layer number ``mi`` (among the ``M`` layers) over one sequence
    ``u`` (S, D): (S, D)."""
    s = u.shape[0]
    heads, groups, n = hy["mamba_num_heads"], hy["n_groups"], hy["ssm_state_size"]
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
    y = gated_norm(y.reshape(s, d_ssm), z, params["ssm_norm"][mi], groups, hy["layer_norm_epsilon"])
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
        sc = jnp.einsum("qrd,kd->rqk", q[lo:hi], k[:hi], precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where((pos[lo:hi, None] >= pos[None, :hi])[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("rqk,kd->qrd", p, v[:hi], precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def attention(u, params, ai, hy, precision):
    """Attention layer number ``ai`` (among the ``*`` layers) over one sequence
    ``u`` (S, D): (S, D)."""
    H, G, d = hy["num_attention_heads"], hy["num_key_value_heads"], hy["head_dim"]
    s = u.shape[0]
    qkv = _project(u, params["wqkv"], ai, "sd,dk->sk", (0,), precision)
    q, k, v = (t.reshape(s, -1, d) for t in jnp.split(qkv, [H * d, (H + G) * d], axis=-1))
    q, k = positioned(q, k, hy)
    r = H // G
    o = jnp.concatenate([_attend(q[:, g * r:(g + 1) * r], k[:, g], v[:, g], float(score_scale(hy, d))) for g in range(G)],
                        axis=1)
    return _project(o.reshape(s, -1), params["wo"], ai, "sk,kd->sd", (0,), precision)


# -- the expert layer ---------------------------------------------------------------------


def route(u, router, bias, hy, precision):
    """(weights (S, K), chosen experts (S, K)): scores in float32 over every
    output, the k largest of ``s + bias`` chosen, the weights of the scores
    without the bias."""
    w = ROUND[precision](router, (0,)) if precision in ROUND else router.astype(jnp.float32)
    s = scores(jnp.einsum("sd,dn->sn", u.astype(jnp.float32), w, precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), hy["num_experts_per_tok"])
    return chosen_weights(s, chosen, hy), chosen


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(v, e_up, e_down, index, precision):
    return _mm("sf,fc->sc", expert_act(_mm("sc,cf->sf", v, e_up[index], precision, (0,))), e_down[index], precision, (0,))


@functools.partial(jax.jit, static_argnames=("precision",))
def _shared(u, s_up, s_down, index, precision):
    return _mm("sf,fd->sd", shared_act(_mm("sd,df->sf", u, s_up[index], precision, (0,))), s_down[index], precision, (0,))


def routed_sum(v, weights, chosen, params, ei, hy, precision):
    """The held experts' weighted sum over the latent rows ``v`` (S, C), one
    expert at a time: (S, C), ``W_out^lat``'s input."""
    out = jnp.zeros_like(v)
    for e in range(params["e_up"].shape[1]):
        mine = jnp.sum(jnp.where(chosen == hy["expert_offset"] + e, weights, 0.0), axis=-1, keepdims=True)
        out = out + mine * _expert(v, params["e_up"], params["e_down"], (ei, e), precision)
    return out


def routed_part(u, params, ei, hy, precision):
    """What the held experts add to the stream: in the latent and back."""
    weights, chosen = route(u, params["router"][ei], params["router_bias"][ei], hy, precision)
    v = _project(u, params["lat_in"], ei, "sd,dc->sc", (0,), precision)
    return _project(routed_sum(v, weights, chosen, params, ei, hy, precision), params["lat_out"], ei, "sc,cd->sd", (0,),
                    precision)


def shared_part(u, params, ei, precision):
    """The shared expert: every token, weight 1, over the residual width."""
    return _shared(u, params["s_up"], params["s_down"], ei, precision)


def moe(u, params, ei, hy, precision):
    """Expert layer number ``ei`` (among the ``E`` layers)."""
    return routed_part(u, params, ei, hy, precision) + shared_part(u, params, ei, precision)


# -- the block and the model ------------------------------------------------------------



def block(x, params, li, hy, precision):
    """Layer ``li``: one norm, and the part its character names, the
    ``index``-th of its kind."""
    kind = hy["pattern"][li]
    part = {"M": ssm_mixer, "*": attention, "E": moe}[kind]
    index = hy["pattern"][:li].count(kind)
    return x + part(rms_norm(x, params["norm"][li], hy["layer_norm_epsilon"]), params, index, hy, precision)


def embedded(params, tokens, precision):
    e = params["embed"][tokens]
    return ROUND[precision](e, (1,)) if precision in ROUND else e.astype(jnp.float32)


def hidden_states(params, tokens, precision="f32"):
    """The last layer's output over one sequence, before the final norm."""
    hy = hyper(params)
    x = embedded(params, jnp.asarray(tokens), precision)
    for li in range(len(hy["pattern"])):
        x = block(x, params, li, hy, precision)
    return x


def logits_at(params, tokens, rows, precision="f32", vocab_blocks=VOCAB_BLOCKS):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    hy = hyper(params)
    x = hidden_states(params, tokens, precision)[jnp.asarray(rows)]
    v = params["unembed"].shape[1]
    step = -(-v // vocab_blocks)
    parts = [_head(x, params["final_norm"], params["unembed"], a, min(a + step, v), precision, hy["layer_norm_epsilon"])
             for a in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)
