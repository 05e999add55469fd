"""The GPT-J family: the parallel attention + MLP block with a gelu MLP of two
tensors and as many K/V heads as query heads, as ``TransformerConfig(
parallel_block=True, use_swiglu=False)`` runs it. Configuration files carry
EleutherAI/gpt-j-6b ``config.json`` key names.

The weights' layout (names, stacked shapes) is the program's interface; the
scales are the usual 1/sqrt(fan-in), with the two projections that write into
the residual stream scaled down by sqrt(2 x layers). The program gets the
weights through its own hooks (``LLMServer(params_loader=...)``, the trainer's
state), the plain reference gets the same arrays: nothing the reference reads
was made by the program.
"""

from __future__ import annotations

import math


def model_kwargs(config: dict) -> dict:
    """``TransformerConfig`` keyword arguments from a configuration file's
    published keys (EleutherAI/gpt-j-6b ``config.json`` names)."""
    out = dict(
        vocab_size=config["vocab_size"], d_model=config["n_embd"], n_layers=config["n_layer"],
        n_heads=config["n_head"], d_ff=config["n_inner"], max_seq_len=config["n_positions"],
        parallel_block=True, use_swiglu=False, tie_embeddings=False, dtype=config["dtype"],
    )
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    """What ``parallel.spmd.build_lm_train_step`` takes."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(**{**model, "dtype": jnp.dtype(model["dtype"]).type})


def reference():
    """The plain reference, ``benchmarks/reference/gptj.py`` (it imports JAX,
    so only the process that holds the chip asks for it)."""
    from benchmarks.reference import gptj

    return gptj


def model_dims(model: dict) -> dict:
    d, h = model["d_model"], model["n_heads"]
    return dict(L=model["n_layers"], D=d, H=h, Hd=d // h, F=model["d_ff"], V=model["vocab_size"])


def make_weights(words, model: dict, dtype):
    """``words`` is ``seed_words(seed)``. Traceable: call under ``jax.jit``
    with ``words`` as its argument (eagerly, each stacked tensor would exist
    in float32 first: 7.5 GB for one MLP tensor of GPT-J-6B)."""
    import jax
    import jax.numpy as jnp

    m = model_dims(model)
    L, D, H, Hd, F, V = m["L"], m["D"], m["H"], m["Hd"], m["F"], m["V"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = jax.random.split(key, 8)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    s_in, s_ff, s_res = 1 / math.sqrt(D), 1 / math.sqrt(F), 1 / math.sqrt(2 * L)
    return {
        "embed": normal(keys[0], (V, D), 0.02),
        "wq": normal(keys[1], (L, D, H, Hd), s_in),
        "wk": normal(keys[2], (L, D, H, Hd), s_in),
        "wv": normal(keys[3], (L, D, H, Hd), s_in),
        "wo": normal(keys[4], (L, H, Hd, D), s_in * s_res),
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_up": normal(keys[5], (L, D, F), s_in),
        "w_down": normal(keys[6], (L, F, D), s_ff * s_res),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal(keys[7], (D, V), s_in),
    }


# -- what a decode step needs, from shapes (``harness/rooflines.py`` turns
# operations and bytes into a least time) -----------------------------------


def weight_count(m: dict) -> dict:
    """Parameters a decode step reads: every layer's six matrices and the
    output head (the embedding is a gather of ``batch`` rows)."""
    d, f, v, n = m["d_model"], m["d_ff"], m["vocab_size"], m["n_layers"]
    per_layer = 4 * d * d + 2 * d * f
    return {"per_layer": per_layer, "head": d * v, "total": n * per_layer + d * v}


def decode_step_need(m: dict, batch: int, live_rows: float, itemsize: int = 2) -> dict:
    """One decode step of ``batch`` sequences that hold ``live_rows`` cached
    positions in all (their sum). Bytes: the weights once, the live K and V
    rows read once, the batch's new rows written. FLOPs: two per weight per
    sequence, and four per cached position per model dim (scores and the
    weighted sum)."""
    w = weight_count(m)
    d, n = m["d_model"], m["n_layers"]
    kv_row = 2 * d * itemsize * n  # one position's K and V over all layers
    nbytes = w["total"] * itemsize + live_rows * kv_row + batch * kv_row
    flops = 2.0 * w["total"] * batch + 4.0 * d * n * live_rows
    return {"flops": flops, "bytes": nbytes}


def paged_attention_need(m: dict, blocks: float, block_size: int, batch: float, itemsize: int = 2) -> dict:
    """The paged-attention kernel's calls of one decode step, one a layer:
    ``batch`` sequences whose tables hold ``blocks`` live blocks in all.
    Bytes: the whole blocks the kernel copies (K and V, ``block_size`` rows
    each, whatever part of a sequence's last block is filled), each
    sequence's query read and its output written. FLOPs: four per copied row
    per model dim (scores and the weighted sum). Whole blocks, not live rows:
    the kernel moves them, so a share of this need cannot pass 100%."""
    d, n = m["d_model"], m["n_layers"]
    rows = blocks * block_size
    nbytes = (rows * 2 * d + batch * 2 * d) * itemsize * n
    flops = 4.0 * rows * d * n
    return {"flops": flops, "bytes": nbytes}
