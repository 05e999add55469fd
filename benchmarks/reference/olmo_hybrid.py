"""Plain reference of Olmo-Hybrid's language model (``model_type``
``olmo_hybrid``): the forward pass in straightforward ``jax.numpy``, float32,
every contraction at ``Precision.HIGHEST``. No cache, no kernel, no batching,
no chunkwise form, no import from ``ray_tpu``.

Layer ``l`` (``N`` is RMSNorm with a float32 weight; OLMo 2's order, the norms
on the branches' outputs):

    h = x + N(mixer_l(x); g_mixer)
    y = h + N((silu(h Wg) * (h Wu)) Wd; g_mlp)

``mixer_l`` by ``layer_types[l]``:

    linear_attention (a Gated DeltaNet layer; a token x_t, H heads):
        [q~ | k~ | v~] = x_t W_qkv   (H d_k, H d_k, H d_v);  z = x_t W_gate;  [b | a] = x_t W_ba
        c_t = silu(sum_{j<K} w_j * u_{t-K+1+j}) over each of q~, k~, v~ (zeros before the start)
        q = c^q / ||c^q|| / sqrt(d_k);  k = c^k / ||c^k||;  v = c^v            a head
        beta = 2 sigmoid(b) (allow_neg_eigval; else sigmoid(b))
        alpha = exp(-exp(A_log) softplus(a + dt_bias))
        S' = alpha S_{t-1};  u = beta (v - S'^T k);  S_t = S' + k u^T;  o = S_t^T q   (S_0 = 0)
        out = (N_{d_v}(o; g_o) * silu(z)) W_o
    full_attention:
        q = N(x Wq; g_q);  k = N(x Wk; g_k) over the whole projection;  v = x Wv
        p = softmax(q k^T / sqrt(head_dim)), causal, a head;  out = (p v) Wo
        **no rotary** (the published ``rope_theta`` is null): ``rotate`` is the
        identity and stands where a rotary would

The state is stepped a token at a time (``_recurrence``: a scan over the
sequence); the program's chunkwise form is held to this.

The weights are the dict the family made from the seed
(``families/olmo_hybrid.py``), stacked as the program stacks them: ``gdn_*``
over the linear layers, ``wq``, ``wk``, ``wv``, ``wo``, ``q_norm``, ``k_norm``
over the full ones, the two norms and the MLP over all. Its ``hyper`` entry
carries what no shape tells: ``layer_types`` (1: linear), ``num_attention_heads``,
``rms_norm_eps``, ``allow_neg_eigval``.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the recurrence, the convolution and the norms stay float32.

It has to fit beside 8.2 GB of served weights and a 6.2 GB pool, so
``logits_at`` goes a tensor at a time: one contraction a jitted call, attention
in blocks of heads and query rows, the head in vocabulary chunks (the leaves
are ``reference/longcat.py``'s, which know no model).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.longcat import HEAD_BLOCK, HIGHEST, ROUND, ROW_BLOCK, _head, _project, rms_norm, silu

L2_EPS = 1e-6  # under the root of q's and k's norm, as the published kernels have it


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).tolist() for k, v in params["hyper"].items()}
    return {**h, "num_attention_heads": int(h["num_attention_heads"]), "allow_neg_eigval": bool(h["allow_neg_eigval"])}


# -- the linear-attention mixer ---------------------------------------------------


def short_conv(u, w):
    """Depthwise, causal, no bias, then SiLU: ``u`` (S, C), ``w`` (K, C);
    position t sees u_{t-K+1} .. u_t, zeros before the sequence's start."""
    taps = w.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return silu(sum(padded[j:j + u.shape[0]] * w[j].astype(jnp.float32) for j in range(taps)))


def strength(b, hy):
    beta = jax.nn.sigmoid(b)
    return 2.0 * beta if hy["allow_neg_eigval"] else beta


def decay(a, a_log, dt_bias):
    """The log of a token's decay a head, <= 0."""
    return -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(a + dt_bias.astype(jnp.float32))


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@jax.jit
def _recurrence(q, k, v, alpha, beta):
    """The gated delta rule a token at a time from an empty state. q, k (S, H,
    d_k), v (S, H, d_v), alpha, beta (S, H) -> o (S, H, d_v)."""

    def token(state, xs):  # state (H, d_k, d_v)
        q, k, v, alpha, beta = xs
        state = alpha[:, None, None] * state
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k, precision=HIGHEST))
        state = state + k[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q, precision=HIGHEST)

    heads, d_k, d_v = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(token, jnp.zeros((heads, d_k, d_v), jnp.float32), (q, k, v, alpha, beta))
    return o


def linear_mixer(x, params, ll, hy, precision):
    """Linear layer ``ll``'s mixer over one sequence ``x`` (S, D)."""
    s, heads, d_v = x.shape[0], params["gdn_a_log"].shape[-1], params["gdn_onorm"].shape[-1]
    d_k = (params["gdn_qkv"].shape[-1] // heads - d_v) // 2
    u = _project(x, params["gdn_qkv"], ll, "sd,dc->sc", (0,), precision)
    z = _project(x, params["gdn_gate"], ll, "sd,dc->sc", (0,), precision).reshape(s, heads, d_v)
    ba = _project(x, params["gdn_ba"], ll, "sd,dc->sc", (0,), precision)
    c = short_conv(u, params["gdn_conv"][ll])
    q, k, v = (t.reshape(s, heads, -1) for t in jnp.split(c, [heads * d_k, 2 * heads * d_k], axis=-1))
    g = decay(ba[:, heads:], params["gdn_a_log"][ll], params["gdn_dt_bias"][ll])
    o = _recurrence(unit(q) * d_k ** -0.5, unit(k), v, jnp.exp(g), strength(ba[:, :heads], hy))
    y = rms_norm(o, params["gdn_onorm"][ll], hy["rms_norm_eps"]) * silu(z)
    return _project(y.reshape(s, -1), params["gdn_out"], ll, "sc,cd->sd", (0,), precision)


# -- the full-attention mixer ------------------------------------------------------


def rotate(x, positions):
    """Where a rotary would stand: the published ``rope_theta`` is null."""
    return x


@jax.jit
def _attend(q, k, v):
    """Causal softmax attention of one block of heads over one sequence from
    position 0, query rows in blocks. q, k, v (S, h, d)."""
    s, scale = q.shape[0], q.shape[-1] ** -0.5
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, s)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi], precision=HIGHEST) * scale
        scores = jnp.where(pos[None, lo:hi, None] >= pos[None, None, :hi], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v[:hi], precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def full_mixer(x, params, fi, hy, precision):
    """Full layer ``fi``'s mixer over one sequence ``x`` (S, D)."""
    s, heads, eps = x.shape[0], hy["num_attention_heads"], hy["rms_norm_eps"]
    pos = jnp.arange(s)
    q = rms_norm(_project(x, params["wq"], fi, "sd,dk->sk", (0,), precision), params["q_norm"][fi], eps)
    k = rms_norm(_project(x, params["wk"], fi, "sd,dk->sk", (0,), precision), params["k_norm"][fi], eps)
    v = _project(x, params["wv"], fi, "sd,dk->sk", (0,), precision).reshape(s, heads, -1)
    q, k = rotate(q.reshape(s, heads, -1), pos), rotate(k.reshape(s, heads, -1), pos)
    att = jnp.concatenate([_attend(q[:, a:a + HEAD_BLOCK], k[:, a:a + HEAD_BLOCK], v[:, a:a + HEAD_BLOCK])
                           for a in range(0, heads, HEAD_BLOCK)], axis=1)
    return _project(att.reshape(s, -1), params["wo"], fi, "sk,kd->sd", (0,), precision)


# -- the block and the model -------------------------------------------------------


def mlp(h, params, li, precision):
    hidden = silu(_project(h, params["w_gate"], li, "sd,df->sf", (0,), precision)) * _project(
        h, params["w_up"], li, "sd,df->sf", (0,), precision)
    return _project(hidden, params["w_down"], li, "sf,fd->sd", (0,), precision)


def block(x, params, li, hy, precision):
    """Layer ``li`` over one sequence. x (S, D) float32."""
    eps, types = hy["rms_norm_eps"], hy["layer_types"]
    own = sum(1 for t in types[:li] if t == types[li])  # this layer among its kind's
    mixed = (linear_mixer if types[li] else full_mixer)(x, params, own, hy, precision)
    h = x + rms_norm(mixed, params["mixer_norm"][li], eps)
    return h + rms_norm(mlp(h, params, li, precision), params["mlp_norm"][li], eps)


def _embed(params, tokens, precision):
    e = params["embed"][tokens]
    return ROUND[precision](e, (1,)) if precision in ROUND else e.astype(jnp.float32)


def logits_at(params, tokens, rows, precision="f32", vocab_chunks=16):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    hy = hyper(params)
    x = _embed(params, jnp.asarray(tokens), precision)
    for li in range(len(hy["layer_types"])):
        x = block(x, params, li, hy, precision)
    x = x[jnp.asarray(rows)]
    v = params["unembed"].shape[1]
    step = -(-v // vocab_chunks)
    parts = [_head(x, params["final_norm"], params["unembed"], a, min(a + step, v), precision, hy["rms_norm_eps"])
             for a in range(0, v, step)]
    return jnp.concatenate(parts, axis=-1)
