"""The Phi-4-mini-flash family (``model_type`` ``phi4flash``): state-space
layers and window attention alternating, one full-attention layer whose K and
V every cross layer reads, gated memory units on the middle layer's scan
output, as ``ray_tpu.models.phi4flash`` runs it. Configuration files carry
microsoft ``config.json`` key names and, under ``model_extra``, the
state-space sizes the config does not carry.

The seeded weights (names and stacked shapes are the program's interface:
``ssm_*`` over the state-space layers, ``wqkv`` over the window layers and the
full one, ``wq`` over the cross layers, ``wo``, ``lam``, ``subln`` over every
attention, ``gmu_*`` over the gated memory units, the norms and the MLP over
all; the plain reference gets the same arrays). **Each choice lets `correct`
see a part**:

* 1/sqrt(fan-in) for every matrix, the embedding 0.02 (tied: the logits'
  size), LayerNorm weights 1 and biases 0.05 normal, attention and convolution
  biases 0.05 normal. **No projection into the residual stream is scaled down
  with depth**, ``W_out`` of the mixers no more than the MLPs': a mixer that
  computes nothing is a part of the stream missing;
* the state: ``A = -exp(A_log)`` the published 1..N a state dimension, ``D_skip``
  1, and the step ``dt`` log-uniform in 0.0005-0.01 with ``dt_bias =
  softplus^-1(dt)`` (the published layer draws dt in 0.001-0.1: with A up to 16
  most of a state forgets within a token or two; narrowed so that ``Dl A`` lies
  in about 0.0005-0.16, a memory of six to two thousand tokens, and a window
  ignored is seen); the columns of ``W_x`` that give ``B`` and ``C``
  ``BC_SCALE`` (3) times the rest: with all of ``W_x`` at 1/sqrt(fan-in) the
  state is about a seventieth of the scan's output beside the skip, and a
  state rounded to bfloat16 after every token read 0.037-0.057 on the chip,
  under any limit that passes the sound runs; at 3 it reads 0.145-0.217;
* the four ``lam`` vectors 0.2 normal (the published 0.1 leaves ``lam`` within
  0.1 of ``lam0``: here ``exp(lq . lk)`` swings by a factor of about 1.4 each
  way, so ``lam`` left out, or ``lam0`` alone, is seen).

``hyper`` in the weights' dict carries what no shape tells, for the plain
reference (the program takes it from its config and ignores the entry).
"""

from __future__ import annotations

import math

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "max_position_embeddings", "layer_norm_eps", "sliding_window", "mb_per_layer",
    "tie_word_embeddings", "mlp_bias", "lm_head_bias", "dtype",
)
EXTRA = {"ssm_state_size": 16, "ssm_conv_kernel": 4, "ssm_expand": 2}  # the published modeling file's constants
DT_RANGE, LAM_SCALE, BIAS_SCALE, BC_SCALE = (0.0005, 0.01), 0.2, 0.05, 3.0


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds a ``Phi4FlashConfig`` from (``kind`` names the
    model), from a configuration file's published keys."""
    out = {"kind": "phi4flash", **{k: config[k] for k in PUBLISHED}, **EXTRA}
    out.update(config.get("model_extra", {}))
    out.setdefault("ssm_dt_rank", out["hidden_size"] // 16)
    return out


def train_config(model: dict):
    raise NotImplementedError("the phi4flash family has no training cell: build_lm_train_step runs the dense "
                              "block only (ROADMAP M8)")


def reference():
    """The plain reference, ``benchmarks/reference/phi4flash.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import phi4flash

    return phi4flash


def dims(m: dict) -> dict:
    L, D = m["num_hidden_layers"], m["hidden_size"]
    d = D // m["num_attention_heads"]
    return dict(L=L, Ls=L // 4 + 1, Lw=L // 4, Lc=L // 4 - 1, La=L // 2, D=D, F=m["intermediate_size"],
                V=m["vocab_size"], d=d, Q=m["num_attention_heads"] * d, KV=m["num_key_value_heads"] * d,
                d_in=m["ssm_expand"] * D, N=m["ssm_state_size"], K=m["ssm_conv_kernel"], R=m["ssm_dt_rank"],
                W=m["sliding_window"])


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    z = dims(model)
    L, Ls, Lw, Lc, La, D, F, V, d, Q, KV, d_in, N, K, R = (z[k] for k in (
        "L", "Ls", "Lw", "Lc", "La", "D", "F", "V", "d", "Q", "KV", "d_in", "N", "K", "R"))
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    def bias(*shape):
        return normal(shape, BIAS_SCALE, jnp.float32)

    dt = jnp.exp(jax.random.uniform(next(keys), (Ls, d_in), jnp.float32, *map(math.log, DT_RANGE)))
    return {
        "embed": normal((V, D), 0.02),
        "ln1_w": jnp.ones((L, D), jnp.float32), "ln1_b": bias(L, D),
        "ln2_w": jnp.ones((L, D), jnp.float32), "ln2_b": bias(L, D),
        "w_gu": normal((L, D, 2 * F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
        "ssm_in": normal((Ls, D, 2 * d_in), D ** -0.5),
        "ssm_conv": normal((Ls, K, d_in), K ** -0.5),
        "ssm_conv_b": bias(Ls, d_in),
        # the columns that give B and C, BC_SCALE times the rest: the state's share of the scan's output goes by its square
        "ssm_x": (normal((Ls, d_in, R + 2 * N), d_in ** -0.5, jnp.float32)
                  * jnp.where(jnp.arange(R + 2 * N) < R, 1.0, BC_SCALE)).astype(dtype),
        "ssm_dt": normal((Ls, R, d_in), R ** -0.5),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.broadcast_to(jnp.log(jnp.arange(1.0, N + 1.0))[None, :, None], (Ls, N, d_in)),
        "ssm_d": jnp.ones((Ls, d_in), jnp.float32),
        "ssm_out": normal((Ls, d_in, D), d_in ** -0.5),
        "wqkv": normal((Lw + 1, D, Q + 2 * KV), D ** -0.5), "bqkv": bias(Lw + 1, Q + 2 * KV),
        "wq": normal((Lc, D, Q), D ** -0.5), "bq": bias(Lc, Q),
        "wo": normal((La, Q, D), Q ** -0.5), "bo": bias(La, D),
        "lam": normal((La, 4, d), LAM_SCALE, jnp.float32),
        "subln": jnp.ones((La, 2 * d), jnp.float32),
        "gmu_in": normal((Lc, D, d_in), D ** -0.5),
        "gmu_out": normal((Lc, d_in, D), d_in ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32), "final_norm_b": bias(D),
        "hyper": {
            "num_attention_heads": jnp.int32(model["num_attention_heads"]),
            "num_key_value_heads": jnp.int32(model["num_key_value_heads"]),
            "sliding_window": jnp.int32(model["sliding_window"]),
            "layer_norm_eps": jnp.float32(model["layer_norm_eps"]),
        },
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads (the model's own count: the embedding is
    tied, so the head's matrix is the embedding's). ``mlp``: the fused gate and
    up projection and the down projection; ``ssm_mixer``: in, convolution with
    its bias, the three low projections, the step's with its bias, ``A_log``,
    ``D_skip``, out; ``own_mixer`` (a window layer, the full one): the fused
    q/k/v with its bias, ``W_o`` with its; ``cross_mixer``: q and ``W_o`` with
    theirs; ``gmu_mixer``: in and out; every attention its four ``lam`` vectors
    and the norm after the subtraction; every layer its two LayerNorms."""
    z = dims(m)
    D, F, d_in, N, K, R, Q, KV, d = (z[k] for k in ("D", "F", "d_in", "N", "K", "R", "Q", "KV", "d"))
    mlp, norms, diff = 3 * D * F, 4 * D, 4 * d + 2 * d
    ssm_mixer = D * 2 * d_in + K * d_in + d_in + d_in * (R + 2 * N) + R * d_in + d_in + N * d_in + d_in + d_in * D
    own_mixer = D * (Q + 2 * KV) + (Q + 2 * KV) + Q * D + D + diff
    cross_mixer = D * Q + Q + Q * D + D + diff
    gmu_mixer = 2 * D * d_in
    layer = {k: v + mlp + norms for k, v in (("ssm", ssm_mixer), ("own", own_mixer), ("cross", cross_mixer),
                                             ("gmu", gmu_mixer))}
    head = D * z["V"]
    total = (z["Ls"] * layer["ssm"] + (z["Lw"] + 1) * layer["own"] + z["Lc"] * (layer["cross"] + layer["gmu"])
             + head + 2 * D)
    return {"mlp": mlp, "ssm_mixer": ssm_mixer, "own_mixer": own_mixer, "cross_mixer": cross_mixer,
            "gmu_mixer": gmu_mixer, "ssm_layer": layer["ssm"], "own_layer": layer["own"],
            "cross_layer": layer["cross"], "gmu_layer": layer["gmu"], "head": head, "total": total}


def state_row_bytes(m: dict, itemsize: int = 2) -> dict:
    """A sequence's state row, a layer: a state-space layer's float32 state
    and its convolution's window of K inputs in the served type; a window
    layer's ring, K and V of ``sliding_window`` rows."""
    z = dims(m)
    return {"state": z["N"] * z["d_in"] * 4, "window": z["K"] * z["d_in"] * itemsize,
            "ring": 2 * z["W"] * z["KV"] * itemsize}


def ssm_update_need(m: dict, live: float) -> dict:
    """The ``selective_scan_update`` calls of one decode step, one a
    state-space layer, over ``live`` sequences: each row's state read once and
    written once (float32), ``c`` and ``Dl`` (d_in each) and ``B`` and ``C`` (N
    each) read and ``y`` (d_in) written, float32 as the kernel takes them.
    FLOPs: six an entry of the state (the step times A, the decay's product,
    the input's product and sum, the product with C and its sum; the
    exponential counts none)."""
    z = dims(m)
    state = z["N"] * z["d_in"]
    vectors = 3 * z["d_in"] + 2 * z["N"]
    return {"flops": 6.0 * state * live * z["Ls"], "bytes": (2 * state + vectors) * 4.0 * live * z["Ls"]}


def window_attention_need(m: dict, ring_rows: float, live: float, itemsize: int = 2) -> dict:
    """The ``ring_window_attention`` calls of one decode step, one a window
    layer: ``ring_rows`` live rows of the dispatched sequences' rings in all
    (the engine's count: the sum of min(length, window)), K and V of every
    published head read, each sequence's queries read and its output (float32)
    written. FLOPs: four a live row a value of a query head (scores and
    weighted sums; both softmaxes of a pair share V)."""
    z = dims(m)
    nbytes = ring_rows * 2 * z["KV"] * itemsize + live * (z["Q"] * itemsize + z["Q"] * 4)
    return {"flops": 4.0 * ring_rows * z["Q"] * z["Lw"], "bytes": nbytes * z["Lw"]}


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step over the **one**
    stored layer: the full layer's and every cross layer's (``Lc + 1`` calls).
    Whole copied blocks of the published K and V heads (the pool stores no
    padding: a stored head that the model does not have would read as lost
    roofline), each sequence's packed queries (two a pair, each as wide as a
    pair) read and their outputs written; four FLOPs a copied row a value of a
    query head."""
    z = dims(m)
    rows, calls = blocks * block_size, z["Lc"] + 1
    return {"flops": 4.0 * rows * z["Q"] * calls,
            "bytes": (rows * 2 * z["KV"] + batch * 2 * 2 * z["Q"]) * itemsize * calls}


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the weights once; a state-space layer a sequence
    its state and its window read and written; a window layer a sequence its
    ring's live rows read (at most ``sliding_window`` of them: a sequence's
    mean context stands for its length) and the new row written; the shared
    cache's live rows read once an attention over it (``Lc + 1``) and the
    batch's new rows written once. FLOPs: two a weight a sequence, four a
    row read a value of a query head, and the state updates'."""
    w, z = weight_count(m), dims(m)
    row = state_row_bytes(m, itemsize)
    kv_row = 2 * z["KV"] * itemsize  # one position's K and V of one layer
    ring_rows = batch * min(live_rows / batch, z["W"]) if batch else 0.0
    update = ssm_update_need(m, batch)
    nbytes = (w["total"] * itemsize + batch * z["Ls"] * 2 * (row["state"] + row["window"])
              + (ring_rows + batch) * kv_row * z["Lw"] + (live_rows * (z["Lc"] + 1) + batch) * kv_row)
    flops = (2.0 * w["total"] * batch + 4.0 * z["Q"] * (ring_rows * z["Lw"] + live_rows * (z["Lc"] + 1))
             + update["flops"])
    return {"flops": flops, "bytes": nbytes}
