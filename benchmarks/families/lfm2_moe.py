"""The LFM2-MoE family (``model_type`` ``lfm2_moe``): three gated
short-convolution layers to every full-attention layer (grouped K/V heads of
64 with a per-head norm on q and k and a rotary), ``num_dense_layers`` leading
dense layers and then sigmoid-routed experts with a choice bias and no shared
expert, a head tied to the embedding, as ``ray_tpu.models.lfm2_moe`` runs it.
Configuration files carry LiquidAI ``config.json`` key names; a chip holds
every expert of a layer unless the file's ``model_extra`` names a share
(``experts_held``, ``expert_offset``).

The seeded weights (names and stacked shapes are the program's interface:
``op_norm`` and ``ffn_norm`` over all layers, ``conv_in``, ``conv_w``,
``conv_out`` over the conv layers, ``wqkv``, ``q_norm``, ``k_norm``, ``wo`` over
the full layers, the dense MLP's over the leading layers, the expert layers'
over the rest; no ``unembed``; the plain reference gets the same arrays).
**Each choice lets `correct` see a part** (readings: PERF.md section 6, PR 50):

* 1/sqrt(fan-in) for every matrix, the embedding 0.02, the block's two norms
  and the final one 1;
* ``conv_in`` makes ``B``, ``C`` and ``z`` of unit size over a normed token, the
  taps ``conv_w`` are 1/sqrt(K) x normal (a tap each position, none
  dominant: a convolution one position late or a tap short must show), and
  ``conv_out`` is **not** scaled down with depth: three layers of four are
  convolutions, and what they write is what the comparison is there to see;
* ``q_norm`` and ``k_norm`` are ``Q_NORM`` x (1 + ``NORM_SPREAD`` x normal) and
  ``K_NORM`` x (...), 64 values a layer each, and ``wo`` is ``WO_GAIN`` /
  sqrt(H d), not scaled down with depth: ``families/exaone_moe.py`` says why (a
  softmax over hundreds of positions averages its values down);
* the dense MLPs' ``w_down`` scaled down by ``sqrt(2 x layers)``;
* the router's columns ``ROUTER_SCALE / sqrt(D)``: logits of deviation 1.5 over
  a normed token, so the four chosen scores of 64 lie in 0.8-0.99 and the
  renormalised weights near a quarter each;
* ``router_bias`` (the published ``expert_bias``, a trained buffer; added to
  the scores for the choice only) ``BIAS_SCALE`` x normal: 6e-3 where
  K-EXAONE's 128 experts take 3e-3, the fourth and the fifth of 64 scores
  lying about twice as far apart;
* ``e_down`` is ``E_DOWN_GAIN / sqrt(F_e)``, not scaled down with depth: what
  the experts write is both the signal `correct` has to see (the reference
  without them must fail) and, where bf16 and float32 disagree on a token's
  fourth expert, the noise (``families/kimi.py`` says how that compromise was
  found there). Every expert is held, so a flipped choice swaps one expert's
  output for another's, both computed.

What the chip read under this recipe (my chip runs, PR 50; seed 3050000031,
four requests of 300-1,024 positions and 32 steps each, in process): with
``E_DOWN_GAIN`` 1, as first written, the sound program read 0.104 (0.099-0.134
in five served runs) and int8 0.208: a flipped choice a token a layer swaps a
whole expert, and the error feeds the next layer's choice. At 0 (the dense
skeleton alone) 0.0205; **at 0.35 the sound program 0.038, int8 0.100, fp8
0.269**, the experts left out 0.214, no norm on q and k 0.161, the weights not
renormalised 0.565, the convolution one position late or its second gate left
out 1.41; at 0.5: 0.056, 0.119, 0.294, and 0.303 without the experts. **Not
caught on the chip: the bias added to the weights (0.041 beside 0.038:
``BIAS_SCALE`` is a hundred-and-fiftieth of a chosen score) and the epsilon
1e-20 (0.041); the CPU tests hold both** (``tests/test_lfm2_moe.py``;
``benchmarks/tests/test_lfm2_moe.py`` with a bias a hundred times this one).

``hyper`` in the weights' dict carries the numbers no shape tells, for the
plain reference (the program takes them from its config and ignores the
entry).
"""

from __future__ import annotations

import math

ROUTER_SCALE = 1.5
BIAS_SCALE = 6e-3
E_DOWN_GAIN = 0.35
WO_GAIN = 3.0
CONV_OUT_GAIN = 1.0
Q_NORM, K_NORM, NORM_SPREAD = 1.4, 0.7, 0.25
PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
    "num_attention_heads", "num_key_value_heads", "num_experts", "num_experts_per_tok", "norm_topk_prob",
    "use_expert_bias", "routed_scaling_factor", "conv_L_cache", "conv_bias", "layer_types", "max_position_embeddings",
    "norm_eps", "dtype",
)
HYPER_INT = ("num_attention_heads", "num_key_value_heads", "expert_offset", "num_experts_per_tok")
HYPER_FLOAT = ("routed_scaling_factor", "norm_eps", "rope_theta")
PERIOD = 4  # conv, conv, full_attention, conv: layer i is a full layer where i % 4 == 2


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds an ``Lfm2MoeConfig`` from (``kind`` names the
    model), from a configuration file's published keys: every expert held, from
    expert 0, unless ``model_extra`` names a share."""
    out = {"kind": "lfm2_moe", **{k: config[k] for k in PUBLISHED}}
    out.update(rope_theta=config["rope_parameters"]["rope_theta"], experts_held=config["num_experts"], expert_offset=0)
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the lfm2_moe family has no training cell: build_lm_train_step runs the dense block alone, "
                              "and at 16 bytes a parameter a chip would hold 8 of a layer's 64 experts (PERF.md, section 4)")


def reference():
    """The plain reference, ``benchmarks/reference/lfm2_moe.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import lfm2_moe

    return lfm2_moe


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    m = model
    L, K, D, H, G, V = (m["num_hidden_layers"], m["num_dense_layers"], m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["vocab_size"])
    F, Fe, W, d = m["intermediate_size"], m["moe_intermediate_size"], m["conv_L_cache"], m["hidden_size"] // H
    n = layers_of(m)
    E, held, routed = L - K, m["experts_held"], m["num_experts"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    def spread(shape, mean):
        return mean * (1.0 + NORM_SPREAD * jax.random.normal(next(keys), shape, jnp.float32))

    return {
        "embed": normal((V, D), 0.02),
        "op_norm": jnp.ones((L, D), jnp.float32), "ffn_norm": jnp.ones((L, D), jnp.float32),
        "conv_in": normal((n["conv"], D, 3 * D), D ** -0.5), "conv_w": normal((n["conv"], W, D), W ** -0.5),
        "conv_out": normal((n["conv"], D, D), D ** -0.5 * CONV_OUT_GAIN),
        "wqkv": normal((n["full"], D, (H + 2 * G) * d), D ** -0.5),
        "q_norm": spread((n["full"], d), Q_NORM), "k_norm": spread((n["full"], d), K_NORM),
        "wo": normal((n["full"], H * d, D), (H * d) ** -0.5 * WO_GAIN),
        "w_gate": normal((K, D, F), D ** -0.5), "w_up": normal((K, D, F), D ** -0.5),
        "w_down": normal((K, F, D), F ** -0.5 / math.sqrt(2 * L)),
        "router": normal((E, D, routed), D ** -0.5 * ROUTER_SCALE),
        "router_bias": normal((E, routed), BIAS_SCALE, jnp.float32),
        "e_gate": normal((E, held, D, Fe), D ** -0.5), "e_up": normal((E, held, D, Fe), D ** -0.5),
        "e_down": normal((E, held, Fe, D), Fe ** -0.5 * E_DOWN_GAIN),
        "final_norm": jnp.ones((D,), jnp.float32),
        "hyper": {**{k: jnp.int32(m[k]) for k in HYPER_INT}, **{k: jnp.float32(m[k]) for k in HYPER_FLOAT}},
    }


# -- what a decode step needs, from shapes -------------------------------------


def layers_of(m: dict) -> dict:
    """How many of the model's layers are full-attention, conv, dense and
    expert layers."""
    L, K = m["num_hidden_layers"], m["num_dense_layers"]
    full = sum(1 for i in range(L) if i % PERIOD == 2)
    return {"full": full, "conv": L - full, "dense": K, "expert": L - K}


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``conv``: one conv layer's operator
    (the in and out projections and the taps); ``attention``: one full layer's
    (the fused q/k/v projection and ``wo``); ``dense_ffn``: the 11776-wide MLP;
    ``router``: an expert layer's; ``head`` (the embedding, read whole as the
    head; the lookup is a gather of ``batch`` rows). ``expert``: one routed
    expert's three tensors; a step reads those of the held experts that got a
    row, so ``total`` is what every step reads (none of them) and ``held`` is all
    the held experts of all expert layers. Norm weights (a few thousand a
    layer) are not counted."""
    D, H, G = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    d, n = D // H, layers_of(m)
    conv = 4 * D * D + m["conv_L_cache"] * D
    attention = D * (H + 2 * G) * d + H * d * D
    dense_ffn, expert = 3 * D * m["intermediate_size"], 3 * D * m["moe_intermediate_size"]
    router, head = D * m["num_experts"], D * m["vocab_size"]
    return {"conv": conv, "attention": attention, "dense_ffn": dense_ffn, "expert": expert, "router": router,
            "head": head, "held": n["expert"] * m["experts_held"] * expert,
            "total": n["conv"] * conv + n["full"] * attention + n["dense"] * dense_ffn + n["expert"] * router + head}


def experts_touched(m: dict, batch: float) -> float:
    """Held experts of one layer that get at least one row from ``batch``
    tokens under uniform choice: each token's ``top_k`` distinct choices miss
    a given expert with probability ``1 - top_k / num_experts``."""
    return m["experts_held"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["num_experts"]) ** batch)


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's K and V of one full layer: the published K/V heads of
    ``hidden_size / num_attention_heads`` values."""
    return 2 * m["num_key_value_heads"] * (m["hidden_size"] // m["num_attention_heads"]) * itemsize


def conv_window_bytes(m: dict, itemsize: int = 2) -> int:
    """One sequence's window of one conv layer: its last ``conv_L_cache``
    products of ``hidden_size`` values."""
    return m["conv_L_cache"] * m["hidden_size"] * itemsize


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step, one a **full**
    layer (the pool holds no other layer's rows): whole copied blocks of the
    published K and V heads, each sequence's queries read and their outputs
    written (at the heads' own width: that the kernel is handed rows of two
    heads is the implementation's to pay); four FLOPs a copied row a value of a
    query head."""
    rows, calls, q = blocks * block_size, layers_of(m)["full"], m["hidden_size"]
    return {"flops": 4.0 * rows * q * calls, "bytes": (rows * kv_row_bytes(m, itemsize) + batch * 2 * q * itemsize) * calls}


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
    positions in all. Bytes: every layer's operator, the dense MLPs, the
    routers and the head once, the held experts the step is expected to touch
    (``experts_touched``, not all of them: a share of this need must not pass
    100%); a full layer a sequence its live rows read and the new row written
    (no conv layer keeps a row a position); a conv layer a sequence its window
    read and written. FLOPs: two a weight a sequence outside the routed
    experts, the routed rows' expert FLOPs (``batch x top_k x held /
    num_experts`` rows a layer), four a row read a value of a query head, two a
    tap a value of a window."""
    w, n = weight_count(m), layers_of(m)
    touched = n["expert"] * experts_touched(m, batch)
    nbytes = ((w["total"] + touched * w["expert"]) * itemsize
              + (live_rows + batch) * kv_row_bytes(m, itemsize) * n["full"]
              + 2 * batch * conv_window_bytes(m, itemsize) * n["conv"])
    routed_rows = n["expert"] * batch * m["num_experts_per_tok"] * m["experts_held"] / m["num_experts"]
    flops = (2.0 * w["total"] * batch + 2.0 * w["expert"] * routed_rows
             + 4.0 * m["hidden_size"] * live_rows * n["full"]
             + 2.0 * m["conv_L_cache"] * m["hidden_size"] * batch * n["conv"])
    return {"flops": flops, "bytes": nbytes}
