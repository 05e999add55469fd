"""The Falcon-H1 family (``model_type`` ``falcon_h1``): a Mamba-2 mixer and
grouped-query attention side by side in every layer, under µP multipliers, as
``ray_tpu.models.falcon_h1`` runs it. Configuration files carry tiiuae
``config.json`` key names.

The seeded weights (names and stacked shapes are the program's interface, all
over every layer: ``wqkv`` q's, k's and v's columns side by side, ``wo``,
``ssm_in`` [z | x | B | C | dt], the convolution and its bias, ``ssm_dt_b``,
``ssm_a_log``, ``ssm_d`` a head, ``ssm_norm``, ``ssm_out``, the MLP's three, the
two norms; the plain reference gets the same arrays). **Each choice lets
`correct` see a part through the multipliers**:

* **a matrix times its multiplier is what a plain recipe seeds**: every matrix
  is 1/sqrt(fan-in) *over* the multipliers that scale its output (``wo`` over
  ``attention_out_multiplier``, ``ssm_out`` over ``ssm_out_multiplier``,
  ``w_down`` over ``mlp_multipliers[1]``, ``w_gate`` over ``mlp_multipliers[0]``,
  k's columns of ``wqkv`` over ``key_multiplier``, every column over
  ``attention_in_multiplier``, the five segments of ``ssm_in`` over
  ``ssm_in_multiplier`` and their own of ``ssm_multipliers``, the head over
  ``lm_head_multiplier``, the embedding 1 over ``embedding_multiplier``).
  Trained weights do that themselves; seeded at 1/sqrt(fan-in) alone the MLP
  would add a thousandth of the stream and the keys a hundredth of a score, and
  no fault in either could be seen. So a multiplier left out is a part 3 to 128
  times too large;
* **no projection into the residual stream is scaled down with depth**: a mixer
  left out is a part of the stream missing. ``wo`` carries ``WO_GAIN`` and q's
  columns ``Q_GAIN`` besides: a softmax over a thousand positions averages its
  values down to a few hundredths of their size, and the Mamba mixer's output is
  normed to 1 whatever it computed, so attention summed beside it at 1 / sqrt(H
  d) would not reach the logits;
* the state: ``A = -exp(A_log)`` log-uniform in 1-16 a head (the published
  layer's range) and the step ``dt`` log-uniform in ``DT_RANGE`` with ``dt_bias =
  softplus^-1(dt)`` (the published layer draws 0.001-0.1; narrowed as Phi's so
  that ``dt A`` lies in about 0.0005-0.16, a memory of six to two thousand
  tokens: a state kept in bfloat16 loses what it adds a token under its own
  rounding); dt's columns of ``ssm_in`` at ``DT_GAIN`` so that the step swings
  by a factor of about 1.6 each way over the tokens; ``D`` 1, the convolution's
  bias ``BIAS_SCALE`` normal. The columns of ``ssm_in`` that give ``B`` and
  ``C`` are ``BC_GAIN`` (2) times the rest: the state's share of the mixer's
  output before its norm goes by the gain's fourth power in variance, beside
  the skip ``D x``. On the CPU twin (a state of 256, 256 positions) the state
  kept in bfloat16 read 0.0041 of the logits at gain 1, 0.0100 at 2, 0.0129 at
  3; on the chip at 2 (PR 47, prompts of 300 and 1,000 and 32 steps) 0.0290
  where the sound runs read 0.0065-0.0070, and ``B``/``C`` of the other group
  0.078;
* ``ssm_norm`` is 1 + ``NORM_SPREAD`` x normal: uneven over a group's channels.

The readings under this recipe on the chip (PR 47; ``PERF.md`` section 2 has
them with their seeds, the configuration's ``limits_why`` the limit's reason),
``logits_rel_err_mean``: sound 0.0065-0.0070; int8 0.0298-0.0318, fp8
0.092-0.095; the state in bfloat16 0.029; ``B``/``C`` of the other group 0.078;
the norm over all 4,096 channels 0.171; ``ssm_multipliers[4]`` (dt's) left out
0.372; the norm ahead of the gate 0.431; ``embedding_multiplier`` left out
0.461; a mixer left out 0.98 and 1.08; the mixers in series 1.02;
``ssm_out_multiplier``, ``mlp_multipliers[0]``, ``key_multiplier`` left out
1.17, 1.24, 1.25.

``hyper`` in the weights' dict carries what no shape tells, for the plain
reference (the program takes it from its config and ignores the entry).
"""

from __future__ import annotations

import math

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "max_position_embeddings", "rms_norm_eps", "rope_theta", "rope_scaling", "attn_layer_indices",
    "tie_word_embeddings", "attention_bias", "mlp_bias", "projectors_bias", "mamba_d_ssm", "mamba_d_state", "mamba_d_head",
    "mamba_n_heads", "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
    "mamba_rms_norm", "mamba_norm_before_gate", "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
    "mlp_multipliers", "dtype",
)
HYPER_INT = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads", "mamba_n_groups", "mamba_d_state")
HYPER_FLOAT = ("rms_norm_eps", "rope_theta", "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
               "mlp_multipliers")
DT_RANGE, A_RANGE, DT_GAIN, BIAS_SCALE, NORM_SPREAD = (0.0005, 0.01), (1.0, 16.0), 0.5, 0.05, 0.25
Q_GAIN, WO_GAIN, BC_GAIN = 1.5, 3.0, 2.0


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds a ``FalconH1Config`` from (``kind`` names the
    model), from a configuration file's published keys."""
    out = {"kind": "falcon_h1", **{k: config[k] for k in PUBLISHED}}
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the falcon_h1 family has no training cell: trained at 16 bytes a parameter its smallest "
                              "cut of four layers needs 27.5 GB, and build_lm_train_step runs the dense block only")


def reference():
    """The plain reference, ``benchmarks/reference/falcon_h1.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import falcon_h1

    return falcon_h1


def dims(m: dict) -> dict:
    d, n, g = m["head_dim"], m["mamba_d_state"], m["mamba_n_groups"]
    d_ssm = m["mamba_d_ssm"]
    return dict(L=m["num_hidden_layers"], D=m["hidden_size"], F=m["intermediate_size"], V=m["vocab_size"], d=d,
                Q=m["num_attention_heads"] * d, KV=m["num_key_value_heads"] * d, d_ssm=d_ssm, N=n, Gs=g, Hs=m["mamba_n_heads"],
                K=m["mamba_d_conv"], BC=g * n, conv=d_ssm + 2 * g * n, d_in=2 * d_ssm + 2 * g * n + m["mamba_n_heads"])


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    m, z = model, dims(model)
    L, D, F, V, Q, KV, d_ssm, BC, Hs, K = (z[k] for k in ("L", "D", "F", "V", "Q", "KV", "d_ssm", "BC", "Hs", "K"))
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    def columns(shape, scale, widths_and_gains):
        """A matrix whose columns carry a gain a segment."""
        gains = jnp.concatenate([jnp.full((w,), g, jnp.float32) for w, g in widths_and_gains])
        return (normal(shape, scale, jnp.float32) * gains).astype(dtype)

    def log_uniform(lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), (L, Hs), jnp.float32, math.log(lo), math.log(hi)))

    ai, si, sm = m["attention_in_multiplier"], m["ssm_in_multiplier"], m["ssm_multipliers"]
    dt = log_uniform(*DT_RANGE)
    return {
        "embed": normal((V, D), 1.0 / m["embedding_multiplier"]),
        "in_norm": jnp.ones((L, D), jnp.float32), "ff_norm": jnp.ones((L, D), jnp.float32),
        "wqkv": columns((L, D, Q + 2 * KV), D ** -0.5,
                        ((Q, Q_GAIN / ai), (KV, 1.0 / (ai * m["key_multiplier"])), (KV, 1.0 / ai))),
        "wo": normal((L, Q, D), Q ** -0.5 * WO_GAIN / m["attention_out_multiplier"]),
        "ssm_in": columns((L, D, z["d_in"]), D ** -0.5,
                          tuple((w, g / (si * mult)) for w, g, mult in zip((d_ssm, d_ssm, BC, BC, Hs), (1, 1, BC_GAIN, BC_GAIN, DT_GAIN), sm))),
        "ssm_conv": normal((L, K, z["conv"]), K ** -0.5),
        "ssm_conv_b": normal((L, z["conv"]), BIAS_SCALE, jnp.float32),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.log(log_uniform(*A_RANGE)),
        "ssm_d": jnp.ones((L, Hs), jnp.float32),
        "ssm_norm": 1.0 + NORM_SPREAD * jax.random.normal(next(keys), (L, d_ssm), jnp.float32),
        "ssm_out": normal((L, d_ssm, D), d_ssm ** -0.5 / m["ssm_out_multiplier"]),
        "w_gate": normal((L, D, F), D ** -0.5 / m["mlp_multipliers"][0]),
        "w_up": normal((L, D, F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5 / m["mlp_multipliers"][1]),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, V), D ** -0.5 / m["lm_head_multiplier"]),
        "hyper": {**{k: jnp.int32(m[k]) for k in HYPER_INT}, **{k: jnp.asarray(m[k], jnp.float32) for k in HYPER_FLOAT}},
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``attention``: the fused q/k/v
    projection and ``wo``; ``ssm_mixer``: the input projection, the convolution
    with its bias, ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm's
    weight, the output projection; ``mlp``: three matrices; ``layer``: those and
    the two norms; ``head`` (untied; the embedding is a gather of ``batch``
    rows) with the final norm."""
    z = dims(m)
    D, F, Q, KV, d_ssm = z["D"], z["F"], z["Q"], z["KV"], z["d_ssm"]
    attention = D * (Q + 2 * KV) + Q * D
    ssm_mixer = D * z["d_in"] + z["K"] * z["conv"] + z["conv"] + 3 * z["Hs"] + d_ssm + d_ssm * D
    mlp = 3 * D * F
    layer, head = attention + ssm_mixer + mlp + 2 * D, D * z["V"] + D
    return {"attention": attention, "ssm_mixer": ssm_mixer, "mlp": mlp, "layer": layer, "head": head,
            "total": z["L"] * layer + head}


def state_row_bytes(m: dict, itemsize: int = 2) -> dict:
    """A sequence's state row, a layer: the float32 state (N x d_ssm) and the
    convolution's window of K inputs in the served type."""
    z = dims(m)
    return {"state": z["N"] * z["d_ssm"] * 4, "window": z["K"] * z["conv"] * itemsize}


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's K and V of one layer: the published K/V heads."""
    return 2 * dims(m)["KV"] * itemsize


def ssm_update_need(m: dict, live: float) -> dict:
    """The ``selective_scan_update`` calls of one decode step, one a layer, over
    ``live`` sequences, as Mamba-2 needs them: each row's state read once and
    written once (float32), ``x`` (d_ssm) read and ``y`` (d_ssm) written, ``B``
    and ``C`` of every group (N each) and a ``dt`` a head read, float32. FLOPs:
    six an entry of the state (the decay's product, the step times the input,
    the input's product and sum, the product with C and its sum). What the
    kernel reads beyond this (``dt`` and the decay over a head's channels, ``B``
    and ``C`` over a lane tile) shows as lost share; it is not need."""
    z = dims(m)
    state = z["N"] * z["d_ssm"]
    vectors = 2 * z["d_ssm"] + 2 * z["BC"] + z["Hs"]
    return {"flops": 6.0 * state * live * z["L"], "bytes": (2 * state + vectors) * 4.0 * live * z["L"]}


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step, one a layer over
    the flat pool: whole copied blocks of the published K and V heads (four
    stored heads; the pool stores no padding), each sequence's queries read and
    their outputs written; four FLOPs a copied row a value of a query head."""
    z = dims(m)
    rows = blocks * block_size
    return {"flops": 4.0 * rows * z["Q"] * z["L"],
            "bytes": (rows * kv_row_bytes(m, itemsize) + batch * 2 * z["Q"] * itemsize) * z["L"]}


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the weights once; every layer a sequence its
    state and its window read and written, its live K/V rows read and the new
    row written. FLOPs: two a weight a sequence, four a row read a value of a
    query head, and the state updates'."""
    w, z = weight_count(m), dims(m)
    row = state_row_bytes(m, itemsize)
    update = ssm_update_need(m, batch)
    nbytes = (w["total"] * itemsize + batch * z["L"] * 2 * (row["state"] + row["window"])
              + (live_rows + batch) * kv_row_bytes(m, itemsize) * z["L"])
    flops = 2.0 * w["total"] * batch + 4.0 * z["Q"] * live_rows * z["L"] + update["flops"]
    return {"flops": flops, "bytes": nbytes}
