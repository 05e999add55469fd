"""Plain reference of Phi-4-mini-flash-reasoning's language model
(``model_type`` ``phi4flash``; the decoder-hybrid-decoder of arXiv:2507.06607,
SambaY with differential attention): the forward pass in straightforward
``jax.numpy``, float32, every contraction at ``Precision.HIGHEST``. No cache, no
ring, no kernel, no batching, no packed pairs, no import from ``ray_tpu``.

Layer ``i`` of ``L`` (``LN`` is LayerNorm with weight and bias, eps 1e-5):

    h = x + mixer_i(LN(x));    y = h + W_d(u * silu(g)),  [g, u] = W_gu LN(h)

and after the last layer ``LN`` again, then the embedding transposed.
``mixer_i`` by ``kind_of(i, L)``:

    ssm (i even, i <= L/2; a token u_t):
        [x_t, z_t] = W_in u_t                                  (d_in each)
        c_t = silu(b_conv + sum_{j<K} w_j * x_{t-K+1+j})       zeros before the start
        [dl, B_t, C_t] = W_x c_t                               (R, N, N)
        Dl_t = softplus(W_dt dl + b_dt);   A = -exp(A_log)     (d_in; N x d_in)
        h_t = exp(Dl_t A) * h_{t-1} + (Dl_t c_t) B_t^T;  h_0 = 0
        y_t = C_t h_t + D_skip * c_t;      out = W_out(y_t * silu(z_t))
        layer L/2's y (before the gate) is the memory m of every gmu layer
    window (i odd, i < L/2), full (i = L/2 + 1):
        [q, k, v] = W_qkv u + b: 2P query heads, 2G key and 2G value heads of d;
        pair j is (q1_j, q2_j) = query heads (2j, 2j + 1), K/V pair g = j // (P/G)
        is (k1_g, k2_g) = key heads (2g, 2g + 1) and v_g = [value head 2g; value head 2g + 1]
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(i),  lam0(i) = 0.8 - 0.6 exp(-0.3 i)
        o_j = (softmax(q1_j K1_g^T / sqrt(d)) - lam softmax(q2_j K2_g^T / sqrt(d))) V_g
            over positions t-W+1 .. t (window) or 0 .. t (full)
        out = W_o concat_j(RMSNorm_{2d}(o_j; w_sub) (1 - lam0(i))) + b_o
    cross (i odd, i >= L/2 + 3): q = W_q u + b alone; K and V are layer L/2 + 1's,
        positions 0 .. t; the same form with the layer's own lam vectors, w_sub, W_o
    gmu (i even, i >= L/2 + 2): out = W_out(m_t * silu(W_in u_t))

**No rotary anywhere.** The state is stepped a token at a time (``_recurrence``:
a scan over the sequence); the program's kernels are held to this.

The weights are the dict the family made from the seed
(``families/phi4flash.py``), stacked as the program stacks them. Its ``hyper``
entry carries what no shape tells: ``num_attention_heads``,
``num_key_value_heads``, ``sliding_window``, ``layer_norm_eps``.

``precision``: "f32" is the reference; "fp8" and "int8" are the controls of
`correct` (never the reference): weights rounded per output channel, matmul
inputs in bfloat16; the recurrence, the convolution, the softmaxes and the norms
stay float32.

It has to fit beside 7.7 GB of served weights and a 3 GB pool, so ``logits_at``
goes a tensor at a time: one contraction a jitted call, attention a pair and a
block of query rows at a time, the head in vocabulary chunks (the leaves are
``reference/longcat.py``'s, which know no model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.longcat import HIGHEST, ROUND, ROW_BLOCK, _mm, _project, silu


def hyper(params) -> dict:
    """The numbers no shape tells, as Python numbers."""
    h = {k: np.asarray(v).tolist() for k, v in params["hyper"].items()}
    return {**{k: int(v) for k, v in h.items()}, "layer_norm_eps": float(h["layer_norm_eps"])}


def kind_of(i: int, layers: int) -> str:
    half = layers // 2
    if i % 2 == 0:
        return "ssm" if i <= half else "gmu"
    return "window" if i < half else "full" if i == half + 1 else "cross"


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)


# -- the state-space mixer ------------------------------------------------------------


def short_conv(x, w, bias):
    """Depthwise, causal, with a bias, then SiLU: ``x`` (S, C), ``w`` (K, C);
    position t sees x_{t-K+1} .. x_t, zeros before the sequence's start."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return silu(bias.astype(jnp.float32) + sum(padded[j:j + x.shape[0]] * w[j].astype(jnp.float32) for j in range(taps)))


def kept(state):
    """The state as it is carried from a token to the next: float32."""
    return state


def skip(d, c):
    """``D_skip * c_t``, the scan's way round the state."""
    return d.astype(jnp.float32) * c


@jax.jit
def _recurrence(c, dl, b, cm, a):
    """The recurrence a token at a time from an empty state. c, dl (S, d_in),
    b, cm (S, N), a (N, d_in) -> y (S, d_in), before the skip."""

    def token(h, xs):
        c, dl, b, cm = xs
        h = kept(jnp.exp(dl[None, :] * a) * h + (dl * c)[None, :] * b[:, None])
        return h, jnp.sum(h * cm[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros(a.shape, jnp.float32), (c, dl, b, cm))
    return y


def ssm_mixer(x, params, si, precision):
    """State-space layer ``si``'s mixer over one sequence ``x`` (S, D) =
    LN(input): (out (S, D), y (S, d_in) before the gate)."""
    n = params["ssm_a_log"].shape[1]
    r = params["ssm_dt"].shape[1]
    xs, z = jnp.split(_project(x, params["ssm_in"], si, "sd,dc->sc", (0,), precision), 2, axis=-1)
    c = short_conv(xs, params["ssm_conv"][si], params["ssm_conv_b"][si])
    low, b, cm = jnp.split(_project(c, params["ssm_x"], si, "sc,cr->sr", (0,), precision), [r, r + n], axis=-1)
    dl = jax.nn.softplus(_project(low, params["ssm_dt"], si, "sr,rc->sc", (0,), precision)
                         + params["ssm_dt_b"][si].astype(jnp.float32))
    y = _recurrence(c, dl, b, cm, -jnp.exp(params["ssm_a_log"][si].astype(jnp.float32))) + skip(params["ssm_d"][si], c)
    return _project(y * silu(z), params["ssm_out"], si, "sc,cd->sd", (0,), precision), y


# -- differential attention ----------------------------------------------------------


def lam0_of(i: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * i))


def lam_of(vectors, i: int):
    """The layer's ``lam`` from its four vectors (4, d) and its index."""
    lq1, lk1, lq2, lk2 = vectors.astype(jnp.float32)
    return jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0_of(i)


def sub_norm(o, w, eps):
    """RMSNorm over a pair's 2d values, after the subtraction."""
    return o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def window_of(hy):
    """How many positions a window layer sees, the current one among them."""
    return hy["sliding_window"]


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q1, q2, k1, k2, v, lam, window):
    """One pair over one sequence from position 0, query rows in blocks: q1,
    q2, k1, k2 (S, d), v (S, 2d) -> (S, 2d). ``window`` None: every earlier
    position."""
    s, scale = q1.shape[0], q1.shape[-1] ** -0.5
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, s)
        sees = pos[lo:hi, None] >= pos[None, :hi]
        if window is not None:
            sees &= pos[None, :hi] > pos[lo:hi, None] - window
        weights = []
        for q, k in ((q1, k1), (q2, k2)):
            scores = jnp.einsum("qd,kd->qk", q[lo:hi], k[:hi], precision=HIGHEST) * scale
            weights.append(jax.nn.softmax(jnp.where(sees, scores, -jnp.inf), axis=-1))
        outs.append(jnp.einsum("qk,kd->qd", weights[0] - lam * weights[1], v[:hi], precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def attention(q, k, v, params, ai, i, hy, precision, window):
    """Differential attention ``ai`` of layer ``i``: ``q`` (S, 2P, d) query
    heads against ``k``, ``v`` (S, 2G, d), through the norm after the
    subtraction and ``W_o``."""
    s, heads, kv_heads = q.shape[0], q.shape[1], k.shape[1]
    rep = heads // kv_heads
    lam, eps = lam_of(params["lam"][ai], i), hy["layer_norm_eps"]
    pairs = []
    for j in range(heads // 2):
        g = j // rep
        o = _attend(q[:, 2 * j], q[:, 2 * j + 1], k[:, 2 * g], k[:, 2 * g + 1],
                    jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1), lam, window)
        pairs.append(sub_norm(o, params["subln"][ai], eps) * (1.0 - lam0_of(i)))
    out = _project(jnp.concatenate(pairs, axis=-1), params["wo"], ai, "sk,kd->sd", (0,), precision)
    return out + params["bo"][ai].astype(jnp.float32)


def own_attention(x, params, ai, i, hy, precision, window):
    """A window layer's or the full layer's mixer: (out, its K, its V)."""
    s, heads, kv_heads = x.shape[0], hy["num_attention_heads"], hy["num_key_value_heads"]
    d = params["wo"].shape[1] // heads
    qkv = _project(x, params["wqkv"], ai, "sd,dk->sk", (0,), precision) + params["bqkv"][ai].astype(jnp.float32)
    q, k, v = jnp.split(qkv, [heads * d, (heads + kv_heads) * d], axis=-1)
    k, v = k.reshape(s, kv_heads, d), v.reshape(s, kv_heads, d)
    return attention(q.reshape(s, heads, d), k, v, params, ai, i, hy, precision, window), k, v


def cross_attention(x, shared, params, ci, ai, i, hy, precision):
    s, heads = x.shape[0], hy["num_attention_heads"]
    q = _project(x, params["wq"], ci, "sd,dk->sk", (0,), precision) + params["bq"][ci].astype(jnp.float32)
    return attention(q.reshape(s, heads, -1), *shared, params, ai, i, hy, precision, None)


def memory(m):
    """What a gated memory unit gates: layer L/2's scan output."""
    return m


# -- the block and the model ------------------------------------------------------------


def mlp(h, params, li, precision):
    g, u = jnp.split(_project(h, params["w_gu"], li, "sd,df->sf", (0,), precision), 2, axis=-1)
    return _project(u * silu(g), params["w_down"], li, "sf,fd->sd", (0,), precision)


def _embed(params, tokens, precision):
    e = params["embed"][tokens]
    return ROUND[precision](e, (1,)) if precision in ROUND else e.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("lo", "hi", "precision"))
def _head(x, embed, lo, hi, precision):
    return _mm("sd,vd->sv", x, embed[lo:hi], precision, (1,))


def logits_at(params, tokens, rows, precision="f32", vocab_chunks=16):
    """Full forward pass over one sequence ``tokens`` (S,), no cache; the
    logits (len(rows), V) float32 of the positions ``rows``. Causal, so
    padding after the last wanted row changes nothing."""
    hy = hyper(params)
    layers, eps = params["ln1_w"].shape[0], hy["layer_norm_eps"]
    n_window = layers // 4
    x = _embed(params, jnp.asarray(tokens), precision)
    m = shared = None
    for i in range(layers):
        kind, p = kind_of(i, layers), i // 2
        u = layer_norm(x, params["ln1_w"][i], params["ln1_b"][i], eps)
        if kind == "ssm":
            mixed, y = ssm_mixer(u, params, p, precision)
            if i == layers // 2:
                m = y
        elif kind == "window":
            mixed, _, _ = own_attention(u, params, p, i, hy, precision, window_of(hy))
        elif kind == "full":
            mixed, *shared = own_attention(u, params, p, i, hy, precision, None)
        elif kind == "gmu":
            g = p - n_window - 1
            gate = silu(_project(u, params["gmu_in"], g, "sd,dc->sc", (0,), precision))
            mixed = _project(memory(m) * gate, params["gmu_out"], g, "sc,cd->sd", (0,), precision)
        else:
            mixed = cross_attention(u, shared, params, p - n_window - 1, p, i, hy, precision)
        h = x + mixed
        x = h + mlp(layer_norm(h, params["ln2_w"][i], params["ln2_b"][i], eps), params, i, precision)
    x = layer_norm(x[jnp.asarray(rows)], params["final_norm"], params["final_norm_b"], eps)
    v = params["embed"].shape[0]
    step = -(-v // vocab_chunks)
    return jnp.concatenate([_head(x, params["embed"], a, min(a + step, v), precision) for a in range(0, v, step)], axis=-1)
