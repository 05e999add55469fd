"""The LongCat-Flash family: a layer of two latent attentions (MLA), two dense
SwiGLU MLPs and one shortcut-connected expert layer with zero-compute
(identity) experts, as ``ray_tpu.models.longcat`` runs it. Configuration files
carry meituan-longcat ``config.json`` key names; ``n_routed_experts`` in a
file is how many of a layer's routed experts this chip holds (listed in
``reduced``), ``n_routed_experts_published`` is the router's count, and
``expert_offset`` the first held expert.

The seeded weights (names and stacked shapes are the program's interface; the
plain reference gets the same arrays):

* every matrix 1/sqrt(fan-in); the projections that write into the residual
  stream (``wo``, ``w_down``, ``e_down``) scaled down by ``sqrt(2 x layers)``
  as ``families/gptj.py`` does; the embedding 0.02;
* the two latent norms' weights are ``1 / scale_q`` and ``1 / scale_kv``: the
  published factors (``mla_scale_q_lora``, ``mla_scale_kv_lora``) are applied
  by the program and by the reference, and a seeded norm weight of 1 would
  leave attention scores with a standard deviation of ``scale_q x scale_kv``
  (6.9) where a trained checkpoint's norms have absorbed the factors;
* the router's columns ``ROUTER_SCALE / sqrt(D)``: logits of deviation 4, so
  that a token's twelve chosen weights ``s x p`` span 0.05 to 3 with the
  twelfth the smallest, as a trained router's do. Which outputs are chosen
  does not depend on this scale (the order of the logits is the same); what a
  choice is worth does. A flat softmax (deviation 0.1, this file's first
  recipe) gives every chosen output ``6 / 768``: the held experts' part of the
  logits is then 0.3% and a grouped matmul that computes nothing still reads
  `correct: true` (PERF.md, section 6, PR 29);
* ``e_down`` is ``1 / sqrt(F_e)``, not scaled down with depth as the dense
  paths' output projections are: what an expert writes is weighted by
  ``s x p`` before it reaches the residual stream, and this chip's sixteen
  experts of 768 outputs meet a token in a quarter of its layers. With both,
  the reference with the held experts' part left out reads an error of 0.085-0.10
  in the mean where the sound program reads 0.010-0.016 (PERF.md, section 2);
* ``router_bias`` (added to ``p`` for the choice only; the published one is a
  trained buffer) ``BIAS_SCALE`` x normal: a thousandth of a chosen ``p``, so
  that every expert is still chosen about ``top_k / outputs`` of the time,
  which is what ``decode_step_need`` counts on.

``hyper`` in the weights' dict carries the numbers no shape tells, for the
plain reference (the program takes them from its config and ignores the
entry).
"""

from __future__ import annotations

import math

ROUTER_SCALE = 4.0
BIAS_SCALE = 1e-5
PUBLISHED = (
    "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers", "num_attention_heads",
    "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "mla_scale_q_lora",
    "mla_scale_kv_lora", "routed_scaling_factor", "zero_expert_num", "moe_topk", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "dtype",
)


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds a ``LongcatConfig`` from (``kind`` names the
    model), from a configuration file's published keys."""
    out = {"kind": "longcat", **{k: config[k] for k in PUBLISHED}}
    out.update(
        n_routed_experts=config["n_routed_experts_published"], experts_held=config["n_routed_experts"],
        expert_offset=config.get("expert_offset", 0),
    )
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the longcat family has no training cell: trained at 16 bytes a parameter its "
                              "smallest cut needs 60 GB (PERF.md, section 4)")


def reference():
    """The plain reference, ``benchmarks/reference/longcat.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import longcat

    return longcat


def scales(m: dict) -> dict:
    d = m["hidden_size"]
    return {
        "scale_q": math.sqrt(d / m["q_lora_rank"]) if m["mla_scale_q_lora"] else 1.0,
        "scale_kv": math.sqrt(d / m["kv_lora_rank"]) if m["mla_scale_kv_lora"] else 1.0,
    }


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    m = model
    L, D, F, Fe, H, V = (m["num_layers"], m["hidden_size"], m["ffn_hidden_size"], m["expert_ffn_hidden_size"],
                         m["num_attention_heads"], m["vocab_size"])
    rq, rkv, dn, dr, dv = m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    held, outputs = m["experts_held"], m["n_routed_experts"] + m["zero_expert_num"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    s_res, sc = 1 / math.sqrt(2 * L), scales(m)
    return {
        "embed": normal((V, D), 0.02),
        "in_norm": jnp.ones((L, 2, D), jnp.float32),
        "post_norm": jnp.ones((L, 2, D), jnp.float32),
        "wqa": normal((L, 2, D, rq), D ** -0.5),
        "qa_norm": jnp.full((L, 2, rq), 1 / sc["scale_q"], jnp.float32),
        "wqb": normal((L, 2, H * (dn + dr), rq), rq ** -0.5),
        "wkva": normal((L, 2, rkv + dr, D), D ** -0.5),
        "kva_norm": jnp.full((L, 2, rkv), 1 / sc["scale_kv"], jnp.float32),
        "wkvb": normal((L, 2, H, rkv, dn + dv), rkv ** -0.5),
        "wo": normal((L, 2, H * dv, D), (H * dv) ** -0.5 * s_res),
        "w_gate": normal((L, 2, D, F), D ** -0.5),
        "w_up": normal((L, 2, D, F), D ** -0.5),
        "w_down": normal((L, 2, F, D), F ** -0.5 * s_res),
        "router": normal((L, D, outputs), D ** -0.5 * ROUTER_SCALE),
        "router_bias": normal((L, outputs), BIAS_SCALE, jnp.float32),
        "e_gate": normal((L, held, D, Fe), D ** -0.5),
        "e_up": normal((L, held, D, Fe), D ** -0.5),
        "e_down": normal((L, held, Fe, D), Fe ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, V), D ** -0.5),
        "hyper": {
            "n_routed_experts": jnp.int32(m["n_routed_experts"]), "expert_offset": jnp.int32(m["expert_offset"]),
            "moe_topk": jnp.int32(m["moe_topk"]), "routed_scaling_factor": jnp.float32(m["routed_scaling_factor"]),
            "rms_norm_eps": jnp.float32(m["rms_norm_eps"]), "rope_theta": jnp.float32(m["rope_theta"]),
            "scale_q": jnp.float32(sc["scale_q"]), "scale_kv": jnp.float32(sc["scale_kv"]),
        },
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. Outside the experts: a layer's two
    attentions, two dense MLPs and its router, and the output head (the
    embedding is a gather of ``batch`` rows). ``expert``: one expert's three
    tensors; a step reads those of the held experts that got a row, so
    ``total`` is what every step reads (none of them) and ``held`` is all the
    held experts of all layers."""
    d, f, fe, h = m["hidden_size"], m["ffn_hidden_size"], m["expert_ffn_hidden_size"], m["num_attention_heads"]
    rq, rkv, dn, dr, dv = m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attention = d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d
    per_layer = 2 * attention + 2 * 3 * d * f + d * (m["n_routed_experts"] + m["zero_expert_num"])
    expert, head, n = 3 * d * fe, d * m["vocab_size"], m["num_layers"]
    return {"per_layer": per_layer, "expert": expert, "head": head, "held": n * m["experts_held"] * expert,
            "total": n * per_layer + head}


def experts_touched(m: dict, batch: float) -> float:
    """Held experts of one layer that get at least one row from ``batch``
    tokens under uniform choice: each token's ``top_k`` distinct choices miss
    a given expert with probability ``1 - top_k / outputs``."""
    outputs = m["n_routed_experts"] + m["zero_expert_num"]
    return m["experts_held"] * (1.0 - (1.0 - m["moe_topk"] / outputs) ** batch)


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the weights outside the experts and the head
    once, the held experts the step is expected to touch (``experts_touched``,
    not all of them: a share of this need must not pass 100%), every live
    latent row read once in each of the ``2 x layers`` attentions and the
    batch's new rows written. FLOPs: two a weight a sequence outside the
    experts, the routed rows' expert FLOPs (``batch x top_k x held /
    outputs`` rows a layer), and per cached position a attention the absorbed
    form's scores and weighted sum over all heads, ``2 x heads x (2 x r_kv +
    d_r)``."""
    w = weight_count(m)
    n, h = m["num_layers"], m["num_attention_heads"]
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    outputs = m["n_routed_experts"] + m["zero_expert_num"]
    dense = w["total"]
    touched = n * experts_touched(m, batch)
    cache_row = row * itemsize * 2 * n  # one position's rows over all attentions
    nbytes = (dense + touched * w["expert"]) * itemsize + (live_rows + batch) * cache_row
    routed_rows = n * batch * m["moe_topk"] * m["experts_held"] / outputs
    flops = 2.0 * dense * batch + 2.0 * w["expert"] * routed_rows + 2.0 * h * (row + m["kv_lora_rank"]) * 2 * n * live_rows
    return {"flops": flops, "bytes": nbytes}
