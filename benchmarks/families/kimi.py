"""The Kimi-K2 family (``model_type`` ``kimi_k2``, DeepSeek-V3's layer): latent
attention under YaRN, a leading dense layer, then expert layers of sigmoid-routed
experts beside a shared expert, as ``ray_tpu.models.kimi`` runs it.
Configuration files carry moonshotai ``config.json`` key names;
``n_routed_experts`` in a file is how many of a layer's routed experts this
chip holds (listed in ``reduced``), ``n_routed_experts_published`` is the
router's count, and ``expert_offset`` the first held expert.

The seeded weights (names and stacked shapes are the program's interface: the
attention's tensors and the two norms over all layers, the dense MLP's over the
``first_k_dense_replace`` leading layers, the expert layers' over the rest; the
plain reference gets the same arrays):

* longcat's recipe for the matrices: 1/sqrt(fan-in); the dense paths'
  projections into the residual stream (``wo``, ``w_down``, the shared
  expert's ``s_down``) scaled down by ``sqrt(2 x layers)``; the embedding
  0.02; every norm weight 1 but the query latent's;
* ``qa_norm`` is ``1 / m^2`` (0.5515): the published score scale carries YaRN's
  ``m^2 = 1.813`` and the program and the reference apply it; with a seeded
  norm weight of 1 the scores would have deviation 1.8 where a trained
  checkpoint's weights have absorbed the factor, and softmax that sharp
  doubles what bf16 rounding does to the logits (0.027 against 0.015 in the
  mean with no routed expert at all, PERF.md section 6, PR 34), and with it how
  often bf16 and float32 disagree on a token's eighth expert;
* the router's columns ``ROUTER_SCALE / sqrt(D)``: logits of deviation 1.5
  over a normed token, so the eight chosen scores of 384 lie in 0.92-0.99 and
  the renormalised weights in 0.34-0.37 each (whatever the scale they are
  ~``2.827 / 8``: the scale sets how far apart the chosen scores lie, and with
  them how often bf16 and float32 disagree on the eighth choice);
* ``router_bias`` (the published ``e_score_correction_bias``, a trained
  buffer; added to the scores for the choice only) ``BIAS_SCALE`` x normal:
  at 1.5e-3 it changes the chosen set of 15-20% of the tokens of a layer
  (a program that ignores it is caught) while each expert still gets
  ``top_k / 384`` of the rows within a few percent, which is what
  ``decode_step_need`` counts on (``experts_touched``);
* ``e_down`` is ``E_DOWN_GAIN / sqrt(F_e)`` = 0.35 / sqrt(2048), not scaled
  down with depth. The gain is a compromise that the chip set (PERF.md,
  section 2): the eight renormalised weights are all ~0.35, so where bf16 and
  float32 disagree on a token's eighth expert (a near tie among 384 sigmoid
  scores; the further down the layers, the more often) a whole expert's
  output differs, unlike longcat's softmax weights whose last choice is worth
  little. What the held experts write is both the signal `correct` has to see
  (the reference without their routed part must fail) and the size of that
  noise: at gain 0.35 the sound program reads 0.024 in the mean where no
  routed part at all reads 0.013, int8 0.085 and the routed part left out
  0.23; at gain 2 (this file's first recipe) the sound program read 0.19-0.22
  and `correct` was false. The shared expert meets every token in every expert
  layer and is a dense path (``s_down`` scaled like ``w_down``): without it the
  logits are other logits (0.96).

``hyper`` in the weights' dict carries the numbers no shape tells, for the
plain reference (the program takes them from its config and ignores the
entry).
"""

from __future__ import annotations

import math

ROUTER_SCALE = 1.5
BIAS_SCALE = 1.5e-3
E_DOWN_GAIN = 0.35
PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "qk_nope_head_dim", "v_head_dim", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "norm_topk_prob", "scoring_func", "topk_method", "routed_scaling_factor", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "rope_scaling", "dtype",
)
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds a ``KimiConfig`` from (``kind`` names the
    model), from a configuration file's published keys."""
    out = {"kind": "kimi_k2", **{k: config[k] for k in PUBLISHED}}
    out.update(
        n_routed_experts=config["n_routed_experts_published"], experts_held=config["n_routed_experts"],
        expert_offset=config.get("expert_offset", 0),
    )
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the kimi family has no training cell: trained at 16 bytes a parameter its "
                              "smallest cut needs 78 GB (PERF.md, section 4)")


def reference():
    """The plain reference, ``benchmarks/reference/kimi.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import kimi

    return kimi


def score_gain(m: dict) -> float:
    """YaRN's ``m^2`` on the attention scores: ``(0.1 x mscale_all_dim x
    ln(factor) + 1)^2``."""
    ys = m["rope_scaling"]
    return (0.1 * ys["mscale_all_dim"] * math.log(ys["factor"]) + 1.0) ** 2 if ys["factor"] > 1 else 1.0


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    m = model
    L, K, D, H, V = (m["num_hidden_layers"], m["first_k_dense_replace"], m["hidden_size"], m["num_attention_heads"],
                     m["vocab_size"])
    F, Fe, Fs = m["intermediate_size"], m["moe_intermediate_size"], m["n_shared_experts"] * m["moe_intermediate_size"]
    rq, rkv, dn, dr, dv = m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    E, held, n = L - K, m["experts_held"], m["n_routed_experts"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 24))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    s_res = 1 / math.sqrt(2 * L)
    yarn = m["rope_scaling"]
    return {
        "embed": normal((V, D), 0.02),
        "in_norm": jnp.ones((L, D), jnp.float32),
        "post_norm": jnp.ones((L, D), jnp.float32),
        "wqa": normal((L, D, rq), D ** -0.5),
        "qa_norm": jnp.full((L, rq), score_gain(m) ** -1, jnp.float32),
        "wqb": normal((L, H * (dn + dr), rq), rq ** -0.5),
        "wkva": normal((L, rkv + dr, D), D ** -0.5),
        "kva_norm": jnp.ones((L, rkv), jnp.float32),
        "wkvb": normal((L, H, rkv, dn + dv), rkv ** -0.5),
        "wo": normal((L, H * dv, D), (H * dv) ** -0.5 * s_res),
        "w_gate": normal((K, D, F), D ** -0.5),
        "w_up": normal((K, D, F), D ** -0.5),
        "w_down": normal((K, F, D), F ** -0.5 * s_res),
        "router": normal((E, D, n), D ** -0.5 * ROUTER_SCALE),
        "router_bias": normal((E, n), BIAS_SCALE, jnp.float32),
        "e_gate": normal((E, held, D, Fe), D ** -0.5),
        "e_up": normal((E, held, D, Fe), D ** -0.5),
        "e_down": normal((E, held, Fe, D), Fe ** -0.5 * E_DOWN_GAIN),
        "s_gate": normal((E, D, Fs), D ** -0.5),
        "s_up": normal((E, D, Fs), D ** -0.5),
        "s_down": normal((E, Fs, D), Fs ** -0.5 * s_res),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, V), D ** -0.5),
        "hyper": {
            "expert_offset": jnp.int32(m["expert_offset"]), "num_experts_per_tok": jnp.int32(m["num_experts_per_tok"]),
            "n_group": jnp.int32(m["n_group"]), "topk_group": jnp.int32(m["topk_group"]),
            "routed_scaling_factor": jnp.float32(m["routed_scaling_factor"]),
            "rms_norm_eps": jnp.float32(m["rms_norm_eps"]), "rope_theta": jnp.float32(m["rope_theta"]),
            **{k: jnp.float32(yarn[k]) for k in YARN_KEYS},
        },
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``attention``: one layer's seven
    matrices; ``dense_layer``: attention and the 18432-wide MLP;
    ``expert_layer``: an expert layer outside its routed experts (attention,
    the shared expert, the router); ``head`` (the embedding is a gather of
    ``batch`` rows). ``expert``: one routed expert's three tensors; a step
    reads those of the held experts that got a row, so ``total`` is what every
    step reads (none of them) and ``held`` is all the held experts of all
    expert layers."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    f, fe, fs = m["intermediate_size"], m["moe_intermediate_size"], m["n_shared_experts"] * m["moe_intermediate_size"]
    rq, rkv, dn, dr, dv = m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attention = d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d
    dense_layer, expert_layer = attention + 3 * d * f, attention + 3 * d * fs + d * m["n_routed_experts"]
    k = m["first_k_dense_replace"]
    e, expert, head = m["num_hidden_layers"] - k, 3 * d * fe, d * m["vocab_size"]
    return {"attention": attention, "dense_layer": dense_layer, "expert_layer": expert_layer, "expert": expert,
            "head": head, "held": e * m["experts_held"] * expert, "total": k * dense_layer + e * expert_layer + head}


def experts_touched(m: dict, batch: float) -> float:
    """Held experts of one layer that get at least one row from ``batch``
    tokens under uniform choice: each token's ``top_k`` distinct choices miss
    a given expert with probability ``1 - top_k / n_routed_experts``."""
    return m["experts_held"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["n_routed_experts"]) ** batch)


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the dense layers, the expert layers outside their
    routed experts and the head once, the held experts the step is expected to
    touch (``experts_touched``, not all of them: a share of this need must not
    pass 100%), every live latent row read once in each layer's attention and
    the batch's new rows written. FLOPs: two a weight a sequence outside the
    routed experts, the routed rows' expert FLOPs (``batch x top_k x held /
    n_routed`` rows a layer), and per cached position a layer the absorbed
    form's scores and weighted sum over all heads, ``2 x heads x (2 x r_kv +
    d_r)``."""
    w = weight_count(m)
    layers, h = m["num_hidden_layers"], m["num_attention_heads"]
    e = layers - m["first_k_dense_replace"]
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    dense = w["total"]
    touched = e * experts_touched(m, batch)
    cache_row = row * itemsize * layers  # one position's rows over all layers
    nbytes = (dense + touched * w["expert"]) * itemsize + (live_rows + batch) * cache_row
    routed_rows = e * batch * m["num_experts_per_tok"] * m["experts_held"] / m["n_routed_experts"]
    flops = 2.0 * dense * batch + 2.0 * w["expert"] * routed_rows + 2.0 * h * (row + m["kv_lora_rank"]) * layers * live_rows
    return {"flops": flops, "bytes": nbytes}
