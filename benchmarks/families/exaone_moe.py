"""The K-EXAONE family (``model_type`` ``exaone_moe``): grouped-query attention
with a per-head norm on q and k, three window layers (a rotary, 128 positions)
to every full layer (no rotary), a leading dense layer and then expert layers of
sigmoid-routed experts beside a shared expert, as ``ray_tpu.models.exaone_moe``
runs it. Configuration files carry LGAI-EXAONE ``config.json`` key names;
``num_experts`` in a file is how many of a layer's routed experts this chip
holds (listed in ``reduced``), ``num_experts_published`` is the router's count,
and ``expert_offset`` the first held expert.

The seeded weights (names and stacked shapes are the program's interface:
``wqkv``, ``wo`` and the four norms over all layers, the dense MLP's over the
``first_k_dense_replace`` leading layers, the expert layers' over the rest; the
plain reference gets the same arrays). **Each choice lets `correct` see a
part** (the sizes below were set on the chip: PERF.md section 6, PR 43):

* 1/sqrt(fan-in) for every matrix, the embedding 0.02, the block's two norms 1;
* ``q_norm`` and ``k_norm`` are ``Q_NORM`` x (1 + ``NORM_SPREAD`` x normal) and
  ``K_NORM`` x (...), 128 values a layer each: off 1, unlike each other and
  uneven over a head's values, so that a program that leaves the per-head norm
  out, or norms q with k's weights, computes other scores; their product keeps
  the scores near unit deviation (a softmax much sharper doubles what bf16
  rounding does to the logits: ``families/kimi.py``);
* ``wo`` is ``WO_GAIN`` / sqrt(H d) and **not** scaled down with depth: a softmax
  over a hundred positions averages its values down to a tenth of their size,
  and a window one position too long or too short moves that average by a
  hundred-and-twenty-eighth part; with ``wo`` shrunk as the dense paths'
  projections are, nothing a window layer computes would reach the logits;
* the dense paths' projections into the residual stream (``w_down``, the shared
  expert's ``s_down``) scaled down by ``sqrt(2 x layers)``, as Kimi's;
* the router's columns ``ROUTER_SCALE / sqrt(D)``: logits of deviation 1.5 over
  a normed token, so the eight chosen scores of 128 lie in 0.83-0.99 and the
  renormalised weights near ``2.5 / 8`` each;
* ``router_bias`` (the published ``e_score_correction_bias``, a trained buffer;
  added to the scores for the choice only) ``BIAS_SCALE`` x normal: 3e-3 where
  Kimi's 384 experts take 1.5e-3, because the eighth and the ninth of 128 scores
  lie twice as far apart;
* ``e_down`` is ``E_DOWN_GAIN / sqrt(F_e)``, not scaled down with depth: what the
  held experts write is both the signal `correct` has to see (the reference
  without their routed part, or with the scaling factor 1.0, must fail) and, where
  bf16 and float32 disagree on a token's eighth expert, the noise
  (``families/kimi.py`` says how that compromise was found there).

What the chip read under this recipe (my chip runs, PR 43; seed 3043000109, four
requests of 300-1,000 positions and 32 steps each): the sound program 0.0067 in
the mean; int8 0.034, fp8 0.096; a window of 127 or 129 0.028; the scaling
factor 1.0 0.036; the held experts left out 0.060; the shared expert 0.12; no
norm on q and k 0.17; a rotary on the full layers 0.21; weights not
renormalised 0.37. With ``WO_GAIN`` 6 and ``E_DOWN_GAIN`` 0.35 (the same seed)
the windows read 0.026 but the held experts 0.022 and the scaling factor 0.014,
too near the sound 0.0063: the recipe stayed as first written.

``hyper`` in the weights' dict carries the numbers no shape tells, for the
plain reference (the program takes them from its config and ignores the
entry).
"""

from __future__ import annotations

import math

ROUTER_SCALE = 1.5
BIAS_SCALE = 3e-3
E_DOWN_GAIN = 0.5
WO_GAIN = 3.0
Q_NORM, K_NORM, NORM_SPREAD = 1.4, 0.7, 0.25
PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "num_key_value_heads", "head_dim", "num_shared_experts",
    "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob", "scoring_func", "routed_scaling_factor",
    "sliding_window", "sliding_window_pattern", "num_nextn_predict_layers", "max_position_embeddings",
    "rms_norm_eps", "tie_word_embeddings", "dtype",
)
HYPER_INT = ("num_attention_heads", "num_key_value_heads", "sliding_window", "expert_offset", "num_experts_per_tok",
             "n_group", "topk_group")
HYPER_FLOAT = ("routed_scaling_factor", "rms_norm_eps", "rope_theta")
PERIOD = 4  # LLLG: layer i is a full layer where i % 4 == 3


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds an ``ExaoneMoeConfig`` from (``kind`` names
    the model), from a configuration file's published keys."""
    out = {"kind": "exaone_moe", **{k: config[k] for k in PUBLISHED}}
    out.update(
        num_experts=config["num_experts_published"], experts_held=config["num_experts"],
        expert_offset=config.get("expert_offset", 0), rope_theta=config["rope_parameters"]["rope_theta"],
    )
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the exaone_moe family has no training cell: trained at 16 bytes a parameter its "
                              "smallest cut needs 96 GB (PERF.md, section 4)")


def reference():
    """The plain reference, ``benchmarks/reference/exaone_moe.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import exaone_moe

    return exaone_moe


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument. A model with
    ``num_nextn_predict_layers`` gets the block's tensors under ``mtp``."""
    import jax
    import jax.numpy as jnp

    m = model
    L, K, D, H, G, d, V = (m["num_hidden_layers"], m["first_k_dense_replace"], m["hidden_size"],
                           m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["vocab_size"])
    F, Fe, Fs = m["intermediate_size"], m["moe_intermediate_size"], m["num_shared_experts"] * m["moe_intermediate_size"]
    E, held, n = L - K, m["experts_held"], m["num_experts"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 48))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    def spread(shape, mean):
        return mean * (1.0 + NORM_SPREAD * jax.random.normal(next(keys), shape, jnp.float32))

    s_res = 1 / math.sqrt(2 * L)

    def attention(*layers):
        return {
            "in_norm": jnp.ones((*layers, D), jnp.float32), "post_norm": jnp.ones((*layers, D), jnp.float32),
            "wqkv": normal((*layers, D, (H + 2 * G) * d), D ** -0.5),
            "q_norm": spread((*layers, d), Q_NORM), "k_norm": spread((*layers, d), K_NORM),
            "wo": normal((*layers, H * d, D), (H * d) ** -0.5 * WO_GAIN),
        }

    def experts(*layers):
        return {
            "router": normal((*layers, D, n), D ** -0.5 * ROUTER_SCALE),
            "router_bias": normal((*layers, n), BIAS_SCALE, jnp.float32),
            "e_gate": normal((*layers, held, D, Fe), D ** -0.5), "e_up": normal((*layers, held, D, Fe), D ** -0.5),
            "e_down": normal((*layers, held, Fe, D), Fe ** -0.5 * E_DOWN_GAIN),
            "s_gate": normal((*layers, D, Fs), D ** -0.5), "s_up": normal((*layers, D, Fs), D ** -0.5),
            "s_down": normal((*layers, Fs, D), Fs ** -0.5 * s_res),
        }

    weights = {
        "embed": normal((V, D), 0.02),
        **attention(L),
        "w_gate": normal((K, D, F), D ** -0.5), "w_up": normal((K, D, F), D ** -0.5),
        "w_down": normal((K, F, D), F ** -0.5 * s_res),
        **experts(E),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, V), D ** -0.5),
        "hyper": {**{k: jnp.int32(m[k]) for k in HYPER_INT}, **{k: jnp.float32(m[k]) for k in HYPER_FLOAT}},
    }
    if m.get("num_nextn_predict_layers"):
        weights["mtp"] = {"h_norm": jnp.ones((D,), jnp.float32), "e_norm": jnp.ones((D,), jnp.float32),
                          "proj": normal((2 * D, D), (2 * D) ** -0.5), **attention(), **experts()}
    return weights


# -- what a decode step needs, from shapes -------------------------------------


def layers_of(m: dict) -> dict:
    """How many of the model's layers are full-attention, window, dense and
    expert layers."""
    L, K = m["num_hidden_layers"], m["first_k_dense_replace"]
    return {"full": L // PERIOD, "window": L - L // PERIOD, "dense": K, "expert": L - K}


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``attention``: one layer's fused q/k/v
    projection and ``wo``; ``dense_layer``: attention and the 18432-wide MLP;
    ``expert_layer``: an expert layer outside its routed experts (attention,
    the shared expert, the router); ``head`` (the embedding is a gather of
    ``batch`` rows). ``expert``: one routed expert's three tensors; a step
    reads those of the held experts that got a row, so ``total`` is what every
    step reads (none of them) and ``held`` is all the held experts of all
    expert layers. Norm weights (a few thousand a layer) are not counted."""
    d_model, h, g, d = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    f, fe, fs = m["intermediate_size"], m["moe_intermediate_size"], m["num_shared_experts"] * m["moe_intermediate_size"]
    n = layers_of(m)
    attention = d_model * (h + 2 * g) * d + h * d * d_model
    dense_layer, expert_layer = attention + 3 * d_model * f, attention + 3 * d_model * fs + d_model * m["num_experts"]
    expert, head = 3 * d_model * fe, d_model * m["vocab_size"]
    return {"attention": attention, "dense_layer": dense_layer, "expert_layer": expert_layer, "expert": expert,
            "head": head, "held": n["expert"] * m["experts_held"] * expert,
            "total": n["dense"] * dense_layer + n["expert"] * expert_layer + head}


def experts_touched(m: dict, batch: float) -> float:
    """Held experts of one layer that get at least one row from ``batch``
    tokens under uniform choice: each token's ``top_k`` distinct choices miss
    a given expert with probability ``1 - top_k / num_experts``."""
    return m["experts_held"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["num_experts"]) ** batch)


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's K and V of one layer: the published K/V heads."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step, one a **full**
    layer (the pool holds no other layer's rows): whole copied blocks of the
    published K and V heads, each sequence's queries read and their outputs
    written; four FLOPs a copied row a value of a query head."""
    rows, calls, q = blocks * block_size, layers_of(m)["full"], m["num_attention_heads"] * m["head_dim"]
    return {"flops": 4.0 * rows * q * calls, "bytes": (rows * kv_row_bytes(m, itemsize) + batch * 2 * q * itemsize) * calls}


def window_attention_need(m: dict, ring_rows: float, live: float, itemsize: int = 2) -> dict:
    """The ``ring_window_attention`` calls of one decode step, one a window
    layer: ``ring_rows`` live rows of the dispatched sequences' rings in all
    (the engine's count: the sum of min(length, window)), K and V of every
    published head read. **The queries and the outputs are not counted**
    (``live`` sequences' 16 KB in and 32 KB out a layer, a tenth of a ring's
    512 KB): the compiler keeps both in fast memory between the kernel and its
    neighbours, and with them counted the share read 100.5% on the chip (PERF.md
    section 6, PR 43). FLOPs: four a live row a value of a query head."""
    calls, q = layers_of(m)["window"], m["num_attention_heads"] * m["head_dim"]
    return {"flops": 4.0 * ring_rows * q * calls, "bytes": ring_rows * kv_row_bytes(m, itemsize) * calls}


def expert_matmul_need(m: dict, touched: float, rows: float, itemsize: int = 2) -> dict:
    """The three grouped matmuls of one decode step's expert layers (gate, up,
    down), one set a layer: ``touched`` held experts a layer got a row and
    ``rows`` (token, choice) rows a layer went to held experts (the engine's
    ``llm_moe`` counts). Bytes: the touched experts' three matrices once, each
    row read by gate and by up, the hidden rows written twice and read once
    (the product of the two is the third matmul's input), the result written
    in float32. FLOPs: two a weight a row."""
    d_model, fe, layers = m["hidden_size"], m["moe_intermediate_size"], layers_of(m)["expert"]
    expert = 3 * d_model * fe
    nbytes = touched * expert * itemsize + rows * (2 * d_model * itemsize + 3 * fe * itemsize + d_model * 4)
    return {"flops": 2.0 * expert * rows * layers, "bytes": nbytes * layers}


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the dense layers, the expert layers outside their
    routed experts and the head once, the held experts the step is expected to
    touch (``experts_touched``, not all of them: a share of this need must not
    pass 100%); a full layer a sequence its live rows read and the new row
    written; a window layer a sequence its ring's live rows read (at most
    ``sliding_window`` of them: a sequence's mean context stands for its
    length) and the new row written. FLOPs: two a weight a sequence outside the
    routed experts, the routed rows' expert FLOPs (``batch x top_k x held /
    num_experts`` rows a layer), four a row read a value of a query head."""
    w, n = weight_count(m), layers_of(m)
    touched = n["expert"] * experts_touched(m, batch)
    ring_rows = batch * min(live_rows / batch, m["sliding_window"]) if batch else 0.0
    row = kv_row_bytes(m, itemsize)
    nbytes = ((w["total"] + touched * w["expert"]) * itemsize
              + (live_rows + batch) * row * n["full"] + (ring_rows + batch) * row * n["window"])
    routed_rows = n["expert"] * batch * m["num_experts_per_tok"] * m["experts_held"] / m["num_experts"]
    q = m["num_attention_heads"] * m["head_dim"]
    flops = (2.0 * w["total"] * batch + 2.0 * w["expert"] * routed_rows
             + 4.0 * q * (live_rows * n["full"] + ring_rows * n["window"]))
    return {"flops": flops, "bytes": nbytes}
