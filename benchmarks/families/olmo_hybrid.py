"""The Olmo-Hybrid family (``model_type`` ``olmo_hybrid``): Gated DeltaNet
layers, each with a recurrent state a sequence, beside full-attention layers
over paged K/V, in a period (published: three linear, one full), as
``ray_tpu.models.olmo_hybrid`` runs it. Configuration files carry allenai
``config.json`` key names; ``rope_parameters.rope_theta`` is null and stays so.

The seeded weights (names and stacked shapes are the program's interface:
``gdn_*`` over the linear layers, ``wq`` .. ``wo`` with the two QK norms over
the full ones, the branches' norms and the MLP over all; the plain reference
gets the same arrays):

* 1/sqrt(fan-in) for every matrix, the embedding 0.02, every norm weight 1.
  **No projection into the residual stream is scaled down with depth**: the
  block norms each branch's *output* (OLMo 2's order), so a mixer's and an
  MLP's contributions are of one size whatever ``W_o`` is, and a mixer that
  computes nothing is a third of the stream missing (the planted controls of
  `correct` need that; PERF.md, section 2);
* the decay, so that `correct` can see the state: ``A = exp(A_log)`` uniform in
  0.5-2 and the time step ``dt`` log-uniform in 0.002-0.05 with ``dt_bias =
  softplus^-1(dt)`` (the published layer draws A in 0-16 and dt in 0.001-0.1
  the same way: decays down to 0.2, a memory of a token or two for most heads;
  narrowed here to ``alpha = exp(-A dt)`` in about 0.9-0.999, tens to a
  thousand tokens), and the two gates' projections ``W_b``, ``W_a`` at a fifth
  of 1/sqrt(D): the stream's size grows with depth under seeded unit norms, and
  at full size ``a`` would swing the decay over many orders a token;
* the short convolution's taps 1/sqrt(K).

``hyper`` in the weights' dict carries what no shape tells, for the plain
reference (the program takes it from its config and ignores the entry).
"""

from __future__ import annotations

import math

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "max_position_embeddings", "rms_norm_eps", "layer_types", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "dtype",
)
LINEAR = "linear_attention"
GATES_GAIN = 0.2
A_RANGE, DT_RANGE = (0.5, 2.0), (0.002, 0.05)
SUBLANE_BYTES = 32  # a sublane tile's rows x the type's size: K/V heads are stored in whole tiles of it


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds an ``OlmoHybridConfig`` from (``kind`` names
    the model), from a configuration file's published keys."""
    out = {"kind": "olmo_hybrid", **{k: config[k] for k in PUBLISHED},
           "rope_theta": config["rope_parameters"]["rope_theta"]}
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the olmo_hybrid family has no training cell: build_lm_train_step runs the dense "
                              "block only, and the chunkwise scan has no training pass (ROADMAP M2)")


def reference():
    """The plain reference, ``benchmarks/reference/olmo_hybrid.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import olmo_hybrid

    return olmo_hybrid


def dims(m: dict) -> dict:
    types = list(m["layer_types"])
    h, dk, dv = m["linear_num_key_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    return dict(L=len(types), Ll=types.count(LINEAR), Lf=len(types) - types.count(LINEAR), D=m["hidden_size"],
                F=m["intermediate_size"], V=m["vocab_size"], A=m["hidden_size"], H=h, dk=dk, dv=dv,
                K=m["linear_conv_kernel_dim"], C=h * (2 * dk + dv))


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    d = dims(model)
    L, Ll, Lf, D, F, V, A, H, dk, dv, K, C = (d[k] for k in ("L", "Ll", "Lf", "D", "F", "V", "A", "H", "dk", "dv", "K", "C"))
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 20))

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    a = jax.random.uniform(next(keys), (Ll, H), jnp.float32, *A_RANGE)
    dt = jnp.exp(jax.random.uniform(next(keys), (Ll, H), jnp.float32, *map(math.log, DT_RANGE)))
    return {
        "embed": normal((V, D), 0.02),
        "mixer_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_gate": normal((L, D, F), D ** -0.5),
        "w_up": normal((L, D, F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
        "gdn_qkv": normal((Ll, D, C), D ** -0.5),
        "gdn_gate": normal((Ll, D, H * dv), D ** -0.5),
        "gdn_ba": normal((Ll, D, 2 * H), GATES_GAIN * D ** -0.5),
        "gdn_conv": normal((Ll, K, C), K ** -0.5),
        "gdn_a_log": jnp.log(a),
        "gdn_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "gdn_onorm": jnp.ones((Ll, dv), jnp.float32),
        "gdn_out": normal((Ll, H * dv, D), (H * dv) ** -0.5),
        "wq": normal((Lf, D, A), D ** -0.5),
        "wk": normal((Lf, D, A), D ** -0.5),
        "wv": normal((Lf, D, A), D ** -0.5),
        "q_norm": jnp.ones((Lf, A), jnp.float32),
        "k_norm": jnp.ones((Lf, A), jnp.float32),
        "wo": normal((Lf, A, D), A ** -0.5),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal((D, V), D ** -0.5),
        "hyper": {
            "layer_types": jnp.asarray([int(t == LINEAR) for t in model["layer_types"]], jnp.int32),
            "num_attention_heads": jnp.int32(model["num_attention_heads"]),
            "rms_norm_eps": jnp.float32(model["rms_norm_eps"]),
            "allow_neg_eigval": jnp.int32(bool(model["linear_allow_neg_eigval"])),
        },
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``mlp``: a layer's three tensors;
    ``linear_mixer``: the fused q/k/v projection, the output gate, the two
    gates' projections, ``W_o``, the convolution's taps and the head's three
    vectors; ``full_mixer``: four square projections and the two QK norms;
    ``head`` (the embedding is a gather of ``batch`` rows)."""
    d = dims(m)
    D, H, dv = d["D"], d["H"], d["dv"]
    mlp = 3 * D * d["F"]
    linear_mixer = D * (d["C"] + H * dv + 2 * H) + H * dv * D + d["K"] * d["C"] + 2 * H + dv
    full_mixer = 4 * D * d["A"] + 2 * d["A"]
    linear_layer, full_layer, head = mlp + linear_mixer + 2 * D, mlp + full_mixer + 2 * D, D * d["V"]
    return {"mlp": mlp, "linear_mixer": linear_mixer, "full_mixer": full_mixer, "linear_layer": linear_layer,
            "full_layer": full_layer, "head": head, "total": d["Ll"] * linear_layer + d["Lf"] * full_layer + head}


def kv_heads_stored(m: dict, itemsize: int = 2) -> int:
    """K/V heads as the pool stores them: whole sublane tiles (30 as 32)."""
    tile = SUBLANE_BYTES // itemsize
    return -(-m["num_key_value_heads"] // tile) * tile


def state_row_bytes(m: dict, itemsize: int = 2) -> dict:
    """One linear layer's share of a state row: the float32 state and the
    convolution's window of K inputs in the served type."""
    d = dims(m)
    return {"state": d["dk"] * d["H"] * d["dv"] * 4, "window": d["K"] * d["C"] * itemsize}


def state_update_need(m: dict, live: float) -> dict:
    """The ``gated_delta_update`` calls of one decode step, one a linear
    layer, over ``live`` sequences: each row's state read once and written once
    (float32), q and k (H x d_k each), v, the decay and the strength spread over
    a head's lanes (H x d_v each) read and o (H x d_v) written, float32 as the
    kernel takes them. FLOPs: seven an entry of the state (the decay, the two
    products with k and their sums, the product with q and its sum)."""
    d = dims(m)
    state = d["dk"] * d["H"] * d["dv"]
    vectors = 2 * d["H"] * d["dk"] + 4 * d["H"] * d["dv"]
    return {"flops": 7.0 * state * live * d["Ll"], "bytes": (2 * state + vectors) * 4.0 * live * d["Ll"]}


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the weights once; a linear layer a sequence its
    state read and written and its window read and written, whatever the
    context; a full layer the live K and V rows of the heads the model has
    (not the stored 32) read and the batch's new rows written. FLOPs: two a
    weight a sequence, four a cached position a model dim a full layer, and
    the state updates'."""
    w, d = weight_count(m), dims(m)
    row = state_row_bytes(m, itemsize)
    kv_row = 2 * d["A"] * itemsize * d["Lf"]  # one position's K and V over the full layers
    update = state_update_need(m, batch)
    nbytes = w["total"] * itemsize + batch * d["Ll"] * 2 * (row["state"] + row["window"]) + (live_rows + batch) * kv_row
    flops = 2.0 * w["total"] * batch + 4.0 * d["A"] * d["Lf"] * live_rows + update["flops"]
    return {"flops": flops, "bytes": nbytes}


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step, one a full
    layer: ``batch`` sequences whose tables hold ``blocks`` live blocks in all.
    Whole copied blocks of the *stored* heads (the kernel moves all 32), each
    sequence's padded query read and its output written; four FLOPs a copied
    row a stored value."""
    wide = kv_heads_stored(m, itemsize) * (m["hidden_size"] // m["num_attention_heads"])
    rows, n = blocks * block_size, dims(m)["Lf"]
    return {"flops": 4.0 * rows * wide * n, "bytes": (rows * 2 * wide + batch * 2 * wide) * itemsize * n}
