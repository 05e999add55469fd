"""The Granite 4.0-H family (``model_type`` ``granitemoehybrid``): nine Mamba-2
layers to every attention layer without positions, softmax-over-the-chosen
experts beside a shared expert in every layer, a tied head under
``logits_scaling``, as ``ray_tpu.models.granite_hybrid`` runs it. Configuration
files carry ibm-granite ``config.json`` key names; the file's
``num_local_experts`` counts the experts this chip holds (from ``expert_offset``)
and ``published.num_local_experts`` the router's outputs.

The seeded weights (names and stacked shapes are the program's interface: the
two norms, ``router``, ``e_gate``, ``e_up``, ``e_down``, ``s_gate``, ``s_up``,
``s_down`` over all layers; ``ssm_in`` [z | x | B | C | dt], the convolution
and its bias, ``ssm_dt_b``, ``ssm_a_log``, ``ssm_d`` a head, ``ssm_norm``,
``ssm_out`` over the Mamba layers; ``wqkv`` and ``wo`` over the attention
layers; no ``unembed``; the plain reference gets the same arrays). **Each gain
lets `correct` see a part**:

* **a matrix times its multiplier is what a plain recipe seeds** (trained
  weights do that themselves; ``families/falcon_h1.py``): the embedding is 1
  over ``embedding_multiplier``, so the stream starts at unit size and a
  multiplier left out is a stream twelve times too small; every projection into
  the residual stream (``ssm_out``, ``wo``, ``e_down``, ``s_down``) is 1 /
  sqrt(fan-in) **over ``residual_multiplier``** and none is scaled down with
  depth, so 0.22 of a branch is a whole part of the stream and a branch that
  forgets the multiplier is 4.5 times too large; q's columns of ``wqkv`` are
  ``Q_GAIN`` over ``attention_multiplier`` x sqrt(d), so that
  ``attention_multiplier`` (1/128 for heads of 128, an eleventh of 1/sqrt(d))
  times q . k is a plain recipe's q . k / sqrt(d) at ``Q_GAIN``: with 1/sqrt(d)
  in its place the scores are eleven times too sharp;
* ``wo`` carries ``WO_GAIN``: a softmax over a thousand positions without a
  position signal averages its values down to a few hundredths of their size,
  and one layer in ten is all the attention there is;
* the state: ``A = -exp(A_log)`` log-uniform in 1-16 a head and the step ``dt``
  log-uniform in ``DT_RANGE`` with ``dt_bias = softplus^-1(dt)``, dt's columns of
  ``ssm_in`` at ``DT_GAIN``, B's and C's at ``BC_GAIN``, ``D`` 1, the
  convolution's bias ``BIAS_SCALE`` normal, ``ssm_norm`` 1 + ``NORM_SPREAD`` x
  normal: Falcon-H1's, for its reasons (a state kept in bfloat16 must show
  beside the skip ``D x``);
* the router's columns ``ROUTER_SCALE`` / sqrt(D): logits of deviation 2 over a
  normed token, so the ten chosen of 72 lie about 2.2-4.8 and their softmax
  spans an order of magnitude, the first choice near 0.35 and **the tenth near
  0.03**: where bfloat16 and float32 disagree on a token's tenth expert the
  swap moves a thirtieth of the routed part (LFM2's four sigmoid choices weigh a
  quarter each, and a flipped one drowned its comparison at gain 1: PERF.md
  section 6, PR 50);
* ``e_down`` and ``s_down`` carry ``E_DOWN_GAIN`` and ``S_DOWN_GAIN``: what the
  routed experts write is both the signal `correct` has to see (the reference
  without them must fail) and, where bfloat16 and float32 disagree on a token's
  tenth choice, the noise, and a state-space layer carries a changed token's
  error on to every later position. **Set on the chip (PR 55; PERF.md section
  2):** at ``E_DOWN_GAIN`` 2, as first written, served runs read 0.0153-0.0219
  over 15 seeds with single positions at 0.04-0.09 (a flip moves the next
  layers' choices), under an int8 control of 0.0495: no limit under half the
  control left the tail room. In process at the seed that had read 0.0219
  (sound / int8 / the largest position / the weights not renormalised / the
  routed experts left out): **2: 0.0151 / 0.0499 / 0.064 / 0.141 / 0.447; 1:
  0.0120 / 0.0403 / 0.021 / 0.066 / 0.234; 0.5: 0.0106 / 0.0373 / 0.016 / 0.034
  / 0.120**. 0.5 is served: the spikes are gone and every planted fault still
  fails the limit, the nearest by 1.9 times.

``hyper`` in the weights' dict carries what no shape tells, for the plain
reference (the program takes it from its config and ignores the entry).
"""

from __future__ import annotations

import math

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "shared_intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "num_experts_per_tok", "layer_types", "max_position_embeddings",
    "rms_norm_eps", "normalization_function", "hidden_act", "position_embedding_type", "rope_theta", "rope_scaling",
    "attention_bias", "tie_word_embeddings", "attention_multiplier", "embedding_multiplier", "residual_multiplier",
    "logits_scaling", "mamba_d_state", "mamba_d_head", "mamba_n_heads", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
    "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias", "dtype",
)
HYPER_INT = ("num_attention_heads", "num_key_value_heads", "mamba_n_heads", "mamba_n_groups", "mamba_d_state",
             "expert_offset", "num_experts_per_tok")
HYPER_FLOAT = ("rms_norm_eps", "attention_multiplier", "embedding_multiplier", "residual_multiplier", "logits_scaling")
DT_RANGE, A_RANGE, DT_GAIN, BC_GAIN, BIAS_SCALE, NORM_SPREAD = (0.0005, 0.01), (1.0, 16.0), 0.5, 2.0, 0.05, 0.25
Q_GAIN, WO_GAIN = 1.5, 3.0
ROUTER_SCALE, E_DOWN_GAIN, S_DOWN_GAIN = 2.0, 0.5, 1.0
PERIOD, ATTENTION_AT = 10, 5  # layer i is an attention layer where i % 10 == 5


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds a ``GraniteHybridConfig`` from (``kind`` names
    the model), from a configuration file's published keys: the router at its
    published outputs, the file's ``num_local_experts`` of them held from
    ``expert_offset``."""
    out = {"kind": "granite_hybrid", **{k: config[k] for k in PUBLISHED}}
    out.update(num_local_experts=config["published"]["num_local_experts"], experts_held=config["num_local_experts"],
               expert_offset=config.get("expert_offset", 0))
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the granite_hybrid family has no training cell: at 16 bytes a parameter a whole period "
                              "with 8 experts a layer and an eighth of the vocabulary is 31 GB, and build_lm_train_step "
                              "runs the dense block alone (PERF.md, section 4)")


def reference():
    """The plain reference, ``benchmarks/reference/granite_hybrid.py`` (it
    imports JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import granite_hybrid

    return granite_hybrid


def layers_of(m: dict) -> dict:
    """How many of the model's layers are attention and Mamba layers; every
    layer is an expert layer."""
    L = m["num_hidden_layers"]
    attention = sum(1 for i in range(L) if i % PERIOD == ATTENTION_AT)
    return {"attention": attention, "mamba": L - attention, "expert": L}


def dims(m: dict) -> dict:
    D, n, g, hs = m["hidden_size"], m["mamba_d_state"], m["mamba_n_groups"], m["mamba_n_heads"]
    d, d_ssm = D // m["num_attention_heads"], m["mamba_expand"] * D
    return dict(L=m["num_hidden_layers"], D=D, V=m["vocab_size"], d=d, Q=m["num_attention_heads"] * d,
                KV=m["num_key_value_heads"] * d, Fe=m["intermediate_size"], Fs=m["shared_intermediate_size"],
                d_ssm=d_ssm, N=n, Gs=g, Hs=hs, K=m["mamba_d_conv"], BC=g * n, conv=d_ssm + 2 * g * n,
                d_in=2 * d_ssm + 2 * g * n + hs, **layers_of(m))


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    m, z = model, dims(model)
    L, D, V, d, Q, KV, Fe, Fs, d_ssm, BC, Hs, K = (z[k] for k in ("L", "D", "V", "d", "Q", "KV", "Fe", "Fs", "d_ssm", "BC",
                                                                  "Hs", "K"))
    nm, na, held, n = z["mamba"], z["attention"], m["experts_held"], m["num_local_experts"]
    m_r = m["residual_multiplier"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    def columns(shape, scale, widths_and_gains):
        """A matrix whose columns carry a gain a segment."""
        gains = jnp.concatenate([jnp.full((w,), g, jnp.float32) for w, g in widths_and_gains])
        return (normal(shape, scale, jnp.float32) * gains).astype(dtype)

    def log_uniform(lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), (nm, Hs), jnp.float32, math.log(lo), math.log(hi)))

    dt = log_uniform(*DT_RANGE)
    return {
        "embed": normal((V, D), 1.0 / m["embedding_multiplier"]),
        "in_norm": jnp.ones((L, D), jnp.float32), "post_norm": jnp.ones((L, D), jnp.float32),
        "wqkv": columns((na, D, Q + 2 * KV), D ** -0.5,
                        ((Q, Q_GAIN / (m["attention_multiplier"] * math.sqrt(d))), (KV, 1.0), (KV, 1.0))),
        "wo": normal((na, Q, D), Q ** -0.5 * WO_GAIN / m_r),
        "ssm_in": columns((nm, D, z["d_in"]), D ** -0.5, ((d_ssm, 1.0), (d_ssm, 1.0), (BC, BC_GAIN), (BC, BC_GAIN), (Hs, DT_GAIN))),
        "ssm_conv": normal((nm, K, z["conv"]), K ** -0.5),
        "ssm_conv_b": normal((nm, z["conv"]), BIAS_SCALE, jnp.float32),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.log(log_uniform(*A_RANGE)),
        "ssm_d": jnp.ones((nm, Hs), jnp.float32),
        "ssm_norm": 1.0 + NORM_SPREAD * jax.random.normal(next(keys), (nm, d_ssm), jnp.float32),
        "ssm_out": normal((nm, d_ssm, D), d_ssm ** -0.5 / m_r),
        "router": normal((L, D, n), D ** -0.5 * ROUTER_SCALE),
        "e_gate": normal((L, held, D, Fe), D ** -0.5), "e_up": normal((L, held, D, Fe), D ** -0.5),
        "e_down": normal((L, held, Fe, D), Fe ** -0.5 * E_DOWN_GAIN / m_r),
        "s_gate": normal((L, D, Fs), D ** -0.5), "s_up": normal((L, D, Fs), D ** -0.5),
        "s_down": normal((L, Fs, D), Fs ** -0.5 * S_DOWN_GAIN / m_r),
        "final_norm": jnp.ones((D,), jnp.float32),
        "hyper": {**{k: jnp.int32(m[k]) for k in HYPER_INT}, **{k: jnp.float32(m[k]) for k in HYPER_FLOAT}},
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``ssm_mixer``: a Mamba layer's input
    projection, the convolution with its bias, ``dt_bias``, ``A_log`` and ``D``
    a head, the gated norm's weight, the output projection; ``attention``: an
    attention layer's fused q/k/v projection and ``wo``; ``shared``: the shared
    expert's three matrices; ``router``; ``head`` (the embedding's held rows,
    read whole as the head, with the final norm; the lookup is a gather of
    ``batch`` rows). ``expert``: one routed expert's three tensors; a step
    reads those of the held experts that got a row, so ``total`` is what every
    step reads (none of them; the two norms a layer counted) and ``held`` is all
    the held experts of all layers."""
    z = dims(m)
    D, Q, KV, d_ssm = z["D"], z["Q"], z["KV"], z["d_ssm"]
    ssm_mixer = D * z["d_in"] + z["K"] * z["conv"] + z["conv"] + 3 * z["Hs"] + d_ssm + d_ssm * D
    attention = D * (Q + 2 * KV) + Q * D
    shared, router, expert = 3 * D * z["Fs"], D * m["num_local_experts"], 3 * D * z["Fe"]
    head = D * z["V"] + D
    return {"ssm_mixer": ssm_mixer, "attention": attention, "shared": shared, "router": router, "expert": expert,
            "head": head, "held": z["L"] * m["experts_held"] * expert,
            "total": z["mamba"] * ssm_mixer + z["attention"] * attention + z["L"] * (shared + router + 2 * D) + head}


def experts_touched(m: dict, batch: float) -> float:
    """Held experts of one layer that get at least one row from ``batch``
    tokens under uniform choice: each token's ``top_k`` distinct choices miss
    a given expert with probability ``1 - top_k / num_local_experts``."""
    return m["experts_held"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["num_local_experts"]) ** batch)


def state_row_bytes(m: dict, itemsize: int = 2) -> dict:
    """A sequence's state row, a Mamba layer: the float32 state (N x d_ssm)
    and the convolution's window of K inputs in the served type."""
    z = dims(m)
    return {"state": z["N"] * z["d_ssm"] * 4, "window": z["K"] * z["conv"] * itemsize}


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's K and V of one attention layer: the published K/V heads."""
    return 2 * dims(m)["KV"] * itemsize


def ssm_update_need(m: dict, live: float) -> dict:
    """The ``selective_scan_update`` calls of one decode step, one a Mamba
    layer, over ``live`` sequences (``families/falcon_h1.py`` counts the same
    call): each row's state read once and written once (float32), ``x`` read
    and ``y`` written, ``B`` and ``C`` of every group and a ``dt`` a head read,
    float32; six FLOPs an entry of the state."""
    z = dims(m)
    state = z["N"] * z["d_ssm"]
    vectors = 2 * z["d_ssm"] + 2 * z["BC"] + z["Hs"]
    return {"flops": 6.0 * state * live * z["mamba"], "bytes": (2 * state + vectors) * 4.0 * live * z["mamba"]}


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step, one an
    **attention** layer (the pool holds no other layer's rows): whole copied
    blocks of the published K and V heads, each sequence's queries read and
    their outputs written; four FLOPs a copied row a value of a query head."""
    z = dims(m)
    rows, calls = blocks * block_size, z["attention"]
    return {"flops": 4.0 * rows * z["Q"] * calls,
            "bytes": (rows * kv_row_bytes(m, itemsize) + batch * 2 * z["Q"] * itemsize) * calls}


def expert_matmul_need(m: dict, touched: float, rows: float, itemsize: int = 2) -> dict:
    """The three grouped matmuls of one decode step's expert layers (gate, up,
    down), one set a layer: ``touched`` held experts a layer got a row and
    ``rows`` (token, choice) rows a layer went to held experts (the engine's
    ``llm_moe`` counts). Bytes: the touched experts' three matrices once, each
    row read by gate and by up, the hidden rows written twice and read once,
    the result written in float32. FLOPs: two a weight a row."""
    z = dims(m)
    expert = 3 * z["D"] * z["Fe"]
    nbytes = touched * expert * itemsize + rows * (2 * z["D"] * itemsize + 3 * z["Fe"] * itemsize + z["D"] * 4)
    return {"flops": 2.0 * expert * rows * z["L"], "bytes": nbytes * z["L"]}


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the mixers, attention, the shared experts, the
    routers and the head once, the held experts the step is expected to touch
    (``experts_touched``, not all of them: a share of this need must not pass
    100%); a Mamba layer a sequence its state and its window read and written;
    an attention layer a sequence its live rows read and the new row written.
    FLOPs: two a weight a sequence outside the routed experts, the routed
    rows' expert FLOPs (``batch x top_k x held / num_local_experts`` rows a
    layer), four a row read a value of a query head, and the state updates'."""
    w, z = weight_count(m), dims(m)
    row = state_row_bytes(m, itemsize)
    touched = z["L"] * experts_touched(m, batch)
    nbytes = ((w["total"] + touched * w["expert"]) * itemsize
              + batch * z["mamba"] * 2 * (row["state"] + row["window"])
              + (live_rows + batch) * kv_row_bytes(m, itemsize) * z["attention"])
    routed_rows = z["L"] * batch * m["num_experts_per_tok"] * m["experts_held"] / m["num_local_experts"]
    flops = (2.0 * w["total"] * batch + 2.0 * w["expert"] * routed_rows + 4.0 * z["Q"] * live_rows * z["attention"]
             + ssm_update_need(m, batch)["flops"])
    return {"flops": flops, "bytes": nbytes}
