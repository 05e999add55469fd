"""The Nemotron-H family (``model_type`` ``nemotron_h``; Nemotron 3 Super's
layer): layers of one norm and one part, a Mamba-2 mixer at eight B/C groups,
attention without positions over two K/V heads, or sigmoid-routed experts of
two matrices (relu squared, no gate) inside a latent beside a full-width shared
expert, and an untied head, as ``ray_tpu.models.nemotron_h`` runs it.
Configuration files carry nvidia ``config.json`` key names; the file's
``n_routed_experts`` counts the experts this chip holds (from ``expert_offset``)
and ``published.n_routed_experts`` the router's outputs.

The seeded weights (names and stacked shapes are the program's interface:
``norm`` over all layers; ``ssm_in`` [z | x | B | C | dt], the convolution and
its bias, ``ssm_dt_b``, ``ssm_a_log``, ``ssm_d`` a head, ``ssm_norm``,
``ssm_out`` over the ``M`` layers; ``wqkv`` and ``wo`` over the ``*`` layers;
``router``, ``router_bias``, ``lat_in``, ``lat_out``, ``e_up``, ``e_down``,
``s_up``, ``s_down`` over the ``E`` layers; ``embed``, ``unembed``,
``final_norm``; the plain reference gets the same arrays). Every matrix is 1 /
sqrt(fan-in) and no projection into the stream is scaled down with depth: the
published layer has no multiplier, a branch is a whole part of a stream that
starts at unit size. **Each gain lets `correct` see a part**:

* q's columns of ``wqkv`` carry ``Q_GAIN`` and ``wo`` ``WO_GAIN``: a softmax
  over a thousand positions without a position signal averages its values down
  to a few hundredths of their size, and one layer in eleven is all the
  attention there is (``families/granite_hybrid.py``'s, for its reasons);
* the state: ``A = -exp(A_log)`` log-uniform in 1-16 a head and the step ``dt``
  log-uniform in ``DT_RANGE`` with ``dt_bias = softplus^-1(dt)``, dt's columns
  of ``ssm_in`` at ``DT_GAIN``, B's and C's at ``BC_GAIN``, ``D`` 1, the
  convolution's bias ``BIAS_SCALE`` normal, ``ssm_norm`` 1 + ``NORM_SPREAD`` x
  normal: Falcon-H1's and Granite's, for their reasons (a state kept in
  bfloat16 must show beside the skip ``D x``);
* x's columns of ``ssm_in`` carry a gain a B/C group, ``GROUP_GAINS`` (0.5 to 2,
  log-spaced over the groups): a trained mixer's groups do not run at one size,
  and with seeded groups all of one size a gated norm over all 8,192 channels
  in place of eight norms over 1,024 would differ by the few percent that a
  mean square over 1,024 channels wanders, which no limit sees;
* **a direction common to every token's stream, which only the routers
  read.** Under a sigmoid the 22 largest of 512 logits of a normed token are
  all positive at any deviation, so their scores lie between a half and one and
  the renormalised weights are each 5 / 22 = 0.23: flat, and a flipped 22nd
  choice moves a whole expert's part. It flips often: 512 logits lie 0.02
  deviations apart at the boundary and the stream carries ~0.5% of bfloat16's
  rounding by its middle layers, so a third of a layer's tokens choose another
  22nd expert than float32 does. The first chip reading of this PR, under that
  flat recipe, was a sound 0.064-0.102 beside an int8 control of 0.164: no
  limit. Chosen scores in the sigmoid's exponential range, whose last is worth a
  tenth of the first, need a negative logit common to every expert, and a router
  without a bias in its logits has that only from a direction common to every
  token's stream, as a trained model's has. So the embedding carries ``COMMON``
  along one direction of +-1 for every token, each router subtracts
  ``router_offsets`` along it (every output alike, so the choice is the random
  part's) and **every other matrix that reads the stream is made blind to it**
  (``along``: a rank-one change of a 4,096th of a matrix's variance), so that no
  part answers to it and writes a constant of its own that the later routers
  would prefer some experts for (with the direction visible to every matrix 49
  of 128 held experts got a row from 48 tokens in the last expert layer of a
  twelfth-width pass; blind, 98, and 110 in the first: an even router's 112).
  The routers' columns are ``ROUTER_SCALE`` / sqrt(D): the first choice's logit
  lies near ``TOP_LOGIT`` and the 22nd three below, weights 0.6-0.9 down to
  0.07-0.10;
* **every projection into the stream sums to zero over what it reads**
  (``centred``: ``ssm_out``, ``wo``, ``lat_out``, ``e_down``, ``s_down``): the
  mean of a squared activation (0.5 of a unit normal's) and of a gated mixer's
  channels is a constant that a plain seeded projection writes into every
  token's stream alike, and the next routers prefer experts for it: 48 tokens
  touched 98 of 128 held experts in the fifth expert layer of a twelfth-width
  pass and 102 a layer at the published width (an even router's 112.5);
  centred, 107-110 and 108 (the step's time follows: 0.079 ms an expert). A
  trained router's choice bias is what evens this in the published model;
* ``router_bias`` (the published ``e_score_correction_bias``, a trained buffer;
  added to the scores for the choice only) ``BIAS_SCALE_ROUTER`` x normal:
  ``families/kimi.py``'s, for its reasons (the scores at the boundary are near
  0.05 and 0.004 apart, so it changes a third of the chosen sets);
* ``e_down`` and ``s_down`` carry ``E_DOWN_GAIN`` and ``S_DOWN_GAIN``: relu(h)^2
  of a unit normal ``h`` has mean 0.5 and root mean square 1.22; a part's input
  is ``rho`` of a unit's (the rest of the normed stream is the common
  component), so the shared expert writes 1.22 ``rho``^2 times its gain (0.36 to
  0.58 at 0.6) and the held experts (5.5 of a token's 22 on this chip) 0.86
  ``rho``^2 times theirs; what the routed experts write is both the signal
  `correct` has to see (a reference with relu in relu^2's place must fail) and,
  where bfloat16 and float32 disagree on a token's 22nd choice, the noise, and a
  state-space layer carries a changed token's error on to every later position.
  **Set on the chip (PR 57; the configuration's ``limits_why`` has the
  readings).**

``hyper`` in the weights' dict carries what no shape tells, for the plain
reference (the program takes it from its config and ignores the entry):
``pattern`` is the layers' kinds, a character's code a layer.
"""

from __future__ import annotations

import math

PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern", "max_position_embeddings",
    "layer_norm_epsilon", "norm_eps", "tie_word_embeddings", "num_attention_heads", "num_key_value_heads", "head_dim",
    "attention_bias", "sliding_window", "rope_theta", "partial_rotary_factor", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "expand", "chunk_size", "mamba_hidden_act", "mamba_proj_bias",
    "use_conv_bias", "use_bias", "use_mamba_kernels", "time_step_min", "time_step_max", "time_step_floor",
    "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size", "moe_shared_expert_intermediate_size",
    "n_shared_experts", "moe_shared_expert_overlap", "routed_scaling_factor", "norm_topk_prob", "n_group", "topk_group",
    "mlp_hidden_act", "mlp_bias", "intermediate_size", "num_nextn_predict_layers", "mtp_hybrid_override_pattern",
    "num_logits_to_keep", "rescale_prenorm_residual", "residual_in_fp32", "dtype",
)
HYPER_INT = ("num_attention_heads", "num_key_value_heads", "head_dim", "mamba_num_heads", "n_groups", "ssm_state_size",
             "expert_offset", "num_experts_per_tok")
HYPER_FLOAT = ("layer_norm_epsilon", "routed_scaling_factor")
DT_RANGE, A_RANGE, DT_GAIN, BC_GAIN, BIAS_SCALE, NORM_SPREAD = (0.0005, 0.01), (1.0, 16.0), 0.5, 2.0, 0.05, 0.25
GROUP_GAINS = (0.5, 2.0)  # x's columns of ``ssm_in``: the first group's and the last's, log-spaced between
Q_GAIN, WO_GAIN = 1.5, 3.0
ROUTER_SCALE, BIAS_SCALE_ROUTER, E_DOWN_GAIN, S_DOWN_GAIN = 3.0, 1.5e-3, 1.0, 0.6
COMMON, TOP_LOGIT = 1.5, 0.5  # the embedding's component common to every token; the logit the first choice is sent to
RELU2_RMS, ROUTED_RMS, ATTENTION_MS = 1.22, 0.86, 0.3  # what the recipe expects a part to write (``router_offsets``)


def router_offsets(m: dict) -> list:
    """What each expert layer's router subtracts from every logit a unit of
    the stream's common component, so that a token's largest logit lies near
    ``TOP_LOGIT`` and its 22 chosen scores in the sigmoid's exponential range.
    The normed stream holds ``COMMON / rms`` of the common direction, which only
    the routers read (every other matrix over the stream is blind to it), and
    ``rho = sqrt(1 - COMMON^2 / ms)`` of everything else; the largest of 512
    logits of deviation ``ROUTER_SCALE x rho`` lies 3.1 deviations up. The
    stream's mean square ``ms`` as a layer finds it is what the recipe expects:
    ``COMMON``^2 + 1 of the embedding, and of each earlier part a mixer's 1 +
    ``NORM_SPREAD``^2 (its gated norm leaves unit groups), attention's
    ``ATTENTION_MS`` and an expert layer's held experts' sum and shared expert,
    ``(ROUTED_RMS x E_DOWN_GAIN)^2 + (RELU2_RMS x S_DOWN_GAIN)^2`` times
    ``rho^4`` (a squared activation of inputs ``rho`` times a unit's). The
    constants were read off a float32 pass at a twelfth of the width and hold
    the chosen logits within one of their aim at the published one (PR 57)."""
    out, ms = [], COMMON ** 2 + 1.0
    for kind in m["hybrid_override_pattern"]:
        rho2 = 1.0 - COMMON ** 2 / ms
        if kind == "E":
            out.append((3.1 * ROUTER_SCALE * math.sqrt(rho2) - TOP_LOGIT) * math.sqrt(ms) / COMMON)
        ms += {"M": 1.0 + NORM_SPREAD ** 2, "*": ATTENTION_MS,
               "E": ((ROUTED_RMS * E_DOWN_GAIN) ** 2 + (RELU2_RMS * S_DOWN_GAIN) ** 2) * rho2 ** 2}[kind]
    return out


def model_kwargs(config: dict) -> dict:
    """What ``LLMServer`` builds a ``NemotronHConfig`` from (``kind`` names the
    model), from a configuration file's published keys: the router at its
    published outputs, the file's ``n_routed_experts`` of them held from
    ``expert_offset``."""
    out = {"kind": "nemotron_h", **{k: config[k] for k in PUBLISHED}}
    out.update(n_routed_experts=config["published"]["n_routed_experts"], experts_held=config["n_routed_experts"],
               expert_offset=config.get("expert_offset", 0))
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    raise NotImplementedError("the nemotron_h family has no training cell: at 16 bytes a parameter one period with 8 "
                              "experts a layer and an eighth of the vocabulary is 19.4 GB, and build_lm_train_step runs "
                              "the dense block alone (PERF.md, section 4)")


def reference():
    """The plain reference, ``benchmarks/reference/nemotron_h.py`` (it imports
    JAX, so only the process that holds the chip asks for it)."""
    from benchmarks.reference import nemotron_h

    return nemotron_h


def layers_of(m: dict) -> dict:
    """How many of the model's layers are mixers, attention and expert layers."""
    pattern = m["hybrid_override_pattern"]
    return {"mamba": pattern.count("M"), "attention": pattern.count("*"), "expert": pattern.count("E")}


def dims(m: dict) -> dict:
    D, n, g, hs, d = m["hidden_size"], m["ssm_state_size"], m["n_groups"], m["mamba_num_heads"], m["head_dim"]
    d_ssm = hs * m["mamba_head_dim"]
    return dict(L=m["num_hidden_layers"], D=D, V=m["vocab_size"], d=d, Q=m["num_attention_heads"] * d,
                KV=m["num_key_value_heads"] * d, C=m["moe_latent_size"], Fe=m["moe_intermediate_size"],
                Fs=m["moe_shared_expert_intermediate_size"], d_ssm=d_ssm, N=n, Gs=g, Hs=hs, K=m["conv_kernel"], BC=g * n,
                conv=d_ssm + 2 * g * n, d_in=2 * d_ssm + 2 * g * n + hs, **layers_of(m))


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument."""
    import jax
    import jax.numpy as jnp

    m, z = model, dims(model)
    L, D, V, Q, KV, C, Fe, Fs, d_ssm, BC, Hs, K, Gs = (z[k] for k in ("L", "D", "V", "Q", "KV", "C", "Fe", "Fs", "d_ssm", "BC",
                                                                      "Hs", "K", "Gs"))
    nm, na, ne, held, n = z["mamba"], z["attention"], z["expert"], m["experts_held"], m["n_routed_experts"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = iter(jax.random.split(key, 32))
    common = jax.random.rademacher(next(keys), (D,), jnp.float32)  # the direction of the stream's common component

    def normal(shape, scale, as_type=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(as_type)

    def columns(shape, scale, widths_and_gains):
        """A matrix over the stream whose columns carry a gain a segment,
        blind to the common component."""
        gains = jnp.concatenate([jnp.full((w,), g, jnp.float32) for w, g in widths_and_gains])
        return (along(normal(shape, scale, jnp.float32)) * gains).astype(dtype)

    def blind(shape, scale):
        return along(normal(shape, scale, jnp.float32)).astype(dtype)

    def centred(shape, scale):
        """A projection into the stream (or into the latent's sum) whose every
        output's weights sum to zero over what it reads: a constant in its
        input, as the mean of a squared activation is, writes nothing."""
        w = normal(shape, scale, jnp.float32)
        return (w - jnp.mean(w, axis=-2, keepdims=True)).astype(dtype)

    def log_uniform(lo, hi):
        return jnp.exp(jax.random.uniform(next(keys), (nm, Hs), jnp.float32, math.log(lo), math.log(hi)))

    def along(w, value=0.0):
        """``w`` (.., D, n) float32 with every column's component along the
        common direction set so that a stream of ``c x common`` gives every
        output ``c x value`` (a number, or one a layer): 0 makes a matrix blind
        to the common component, and every output of a router gets the same."""
        value = jnp.asarray(value, jnp.float32).reshape((-1,) + (1,) * (w.ndim - 1)) if jnp.ndim(value) else value
        return w + common[:, None] * (value - jnp.einsum("d,...dn->...n", common, w)[..., None, :]) / D

    offsets = jnp.asarray(router_offsets(m), jnp.float32)
    lo, hi = GROUP_GAINS
    groups = [(d_ssm // Gs, lo * (hi / lo) ** (g / max(Gs - 1, 1))) for g in range(Gs)]
    dt = log_uniform(*DT_RANGE)
    return {
        "embed": (normal((V, D), 1.0, jnp.float32) + COMMON * common).astype(dtype), "unembed": blind((D, V), D ** -0.5),
        "norm": jnp.ones((L, D), jnp.float32), "final_norm": jnp.ones((D,), jnp.float32),
        "wqkv": columns((na, D, Q + 2 * KV), D ** -0.5, ((Q, Q_GAIN), (KV, 1.0), (KV, 1.0))),
        "wo": centred((na, Q, D), Q ** -0.5 * WO_GAIN),
        "ssm_in": columns((nm, D, z["d_in"]), D ** -0.5,
                          ((d_ssm, 1.0), *groups, (BC, BC_GAIN), (BC, BC_GAIN), (Hs, DT_GAIN))),
        "ssm_conv": normal((nm, K, z["conv"]), K ** -0.5),
        "ssm_conv_b": normal((nm, z["conv"]), BIAS_SCALE, jnp.float32),
        "ssm_dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_a_log": jnp.log(log_uniform(*A_RANGE)),
        "ssm_d": jnp.ones((nm, Hs), jnp.float32),
        "ssm_norm": 1.0 + NORM_SPREAD * jax.random.normal(next(keys), (nm, d_ssm), jnp.float32),
        "ssm_out": centred((nm, d_ssm, D), d_ssm ** -0.5),
        "router": along(normal((ne, D, n), D ** -0.5 * ROUTER_SCALE, jnp.float32), -offsets).astype(dtype),
        "router_bias": normal((ne, n), BIAS_SCALE_ROUTER, jnp.float32),
        "lat_in": blind((ne, D, C), D ** -0.5), "lat_out": centred((ne, C, D), C ** -0.5),
        "e_up": normal((ne, held, C, Fe), C ** -0.5), "e_down": centred((ne, held, Fe, C), Fe ** -0.5 * E_DOWN_GAIN),
        "s_up": blind((ne, D, Fs), D ** -0.5), "s_down": centred((ne, Fs, D), Fs ** -0.5 * S_DOWN_GAIN),
        "hyper": {"pattern": jnp.asarray([ord(c) for c in m["hybrid_override_pattern"]], jnp.int32),
                  **{k: jnp.int32(m[k]) for k in HYPER_INT}, **{k: jnp.float32(m[k]) for k in HYPER_FLOAT}},
    }


# -- what a decode step needs, from shapes -------------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads. ``ssm_mixer``: a mixer layer's input
    projection, the convolution with its bias, ``dt_bias``, ``A_log`` and ``D``
    a head, the gated norm's weight, the output projection; ``attention``: an
    attention layer's fused q/k/v projection and ``wo``; of an expert layer
    ``router`` (with the choice bias), ``latent`` (the two projections into and
    out of the experts' width) and ``shared`` (the shared expert's two
    matrices); ``head`` (the head's held columns and the final norm; the lookup
    is a gather of ``batch`` rows of the embedding, which ``embed`` counts
    whole). ``expert``: one routed expert's two matrices; a step reads those of
    the held experts that got a row, so ``total`` is what every step reads
    (none of them; a norm a layer counted) and ``held`` is all the held experts
    of all expert layers."""
    z = dims(m)
    D, Q, KV, d_ssm, C = z["D"], z["Q"], z["KV"], z["d_ssm"], z["C"]
    ssm_mixer = D * z["d_in"] + z["K"] * z["conv"] + z["conv"] + 3 * z["Hs"] + d_ssm + d_ssm * D
    attention = D * (Q + 2 * KV) + Q * D
    n = m["n_routed_experts"]
    router, latent, shared, expert = D * n + n, 2 * D * C, 2 * D * z["Fs"], 2 * C * z["Fe"]
    head = D * z["V"] + D
    return {"ssm_mixer": ssm_mixer, "attention": attention, "router": router, "latent": latent, "shared": shared,
            "expert": expert, "head": head, "embed": D * z["V"], "held": z["expert"] * m["experts_held"] * expert,
            "total": (z["mamba"] * ssm_mixer + z["attention"] * attention + z["expert"] * (router + latent + shared)
                      + z["L"] * D + head)}


def experts_touched(m: dict, batch: float) -> float:
    """Held experts of one layer that get at least one row from ``batch``
    tokens under uniform choice: each token's ``top_k`` distinct choices miss
    a given expert with probability ``1 - top_k / n_routed_experts``."""
    return m["experts_held"] * (1.0 - (1.0 - m["num_experts_per_tok"] / m["n_routed_experts"]) ** batch)


def state_row_bytes(m: dict, itemsize: int = 2) -> dict:
    """A sequence's state row, a mixer layer: the float32 state (N x d_ssm)
    and the convolution's window of K inputs in the served type."""
    z = dims(m)
    return {"state": z["N"] * z["d_ssm"] * 4, "window": z["K"] * z["conv"] * itemsize}


def kv_row_bytes(m: dict, itemsize: int = 2) -> int:
    """One position's K and V of one attention layer: the published K/V heads."""
    return 2 * dims(m)["KV"] * itemsize


def ssm_update_need(m: dict, live: float) -> dict:
    """The ``selective_scan_update`` calls of one decode step, one a mixer
    layer, over ``live`` sequences (``families/falcon_h1.py`` and
    ``families/granite_hybrid.py`` count the same call): each row's state read
    once and written once (float32), ``x`` read and ``y`` written, ``B`` and
    ``C`` of every group and a ``dt`` a head read, float32; six FLOPs an entry
    of the state."""
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
    """The **two** grouped matmuls of one decode step's expert layers (up,
    down), one pair an expert layer: ``touched`` held experts a layer got a row
    and ``rows`` (token, choice) rows a layer went to held experts (the engine's
    ``llm_moe`` counts). Bytes: the touched experts' two matrices once, each
    latent row read once, the hidden rows written once and read once, the
    result written latent-wide in float32. FLOPs: two a weight a row."""
    z = dims(m)
    expert = 2 * z["C"] * z["Fe"]
    nbytes = touched * expert * itemsize + rows * (z["C"] * itemsize + 2 * z["Fe"] * itemsize + z["C"] * 4)
    return {"flops": 2.0 * expert * rows * z["expert"], "bytes": nbytes * z["expert"]}


def decode_step_need(m: dict, batch: float, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all. Bytes: the mixers, attention, the routers, the latent
    projections, the shared experts and the head once, the held experts the
    step is expected to touch (``experts_touched``, not all of them: a share
    of this need must not pass 100%); a mixer layer a sequence its state and
    its window read and written; an attention layer a sequence its live rows
    read and the new row written. FLOPs: two a weight a sequence outside the
    routed experts, the routed rows' expert FLOPs (``batch x top_k x held /
    n_routed_experts`` rows an expert layer), four a row read a value of a query
    head, and the state updates'."""
    w, z = weight_count(m), dims(m)
    row = state_row_bytes(m, itemsize)
    touched = z["expert"] * experts_touched(m, batch)
    nbytes = ((w["total"] + touched * w["expert"]) * itemsize
              + batch * z["mamba"] * 2 * (row["state"] + row["window"])
              + (live_rows + batch) * kv_row_bytes(m, itemsize) * z["attention"])
    routed_rows = z["expert"] * batch * m["num_experts_per_tok"] * m["experts_held"] / m["n_routed_experts"]
    flops = (2.0 * w["total"] * batch + 2.0 * w["expert"] * routed_rows + 4.0 * z["Q"] * live_rows * z["attention"]
             + ssm_update_need(m, batch)["flops"])
    return {"flops": flops, "bytes": nbytes}
