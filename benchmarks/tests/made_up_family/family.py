"""A made-up second family for the tests (``test_families.py`` writes this
file to ``families/swiglu_gqa.py`` of a temporary copy): the serial block
(attention, then the MLP over the updated stream) with a SwiGLU MLP of three
tensors and fewer K/V heads than query heads, as ``TransformerConfig(
parallel_block=False, use_swiglu=True, n_kv_heads < n_heads)`` runs it.
Another key mapping (Llama's ``config.json`` names), another weights layout,
another reference, other counts. No configuration of ``BENCHMARK.json`` has
it."""

from __future__ import annotations

import math


def model_kwargs(config: dict) -> dict:
    out = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=config["max_position_embeddings"],
        parallel_block=False, use_swiglu=True, tie_embeddings=False, dtype=config["dtype"],
    )
    out.update(config.get("model_extra", {}))
    return out


def train_config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(**{**model, "dtype": jnp.dtype(model["dtype"]).type})


def reference():
    from benchmarks.reference import swiglu_gqa

    return swiglu_gqa


def make_weights(words, model: dict, dtype):
    import jax
    import jax.numpy as jnp

    L, D, H, KV = model["n_layers"], model["d_model"], model["n_heads"], model["n_kv_heads"]
    Hd, F, V = D // H, model["d_ff"], model["vocab_size"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    keys = jax.random.split(key, 9)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    s_in, s_ff, s_res = 1 / math.sqrt(D), 1 / math.sqrt(F), 1 / math.sqrt(2 * L)
    return {
        "embed": normal(keys[0], (V, D), 0.02),
        "wq": normal(keys[1], (L, D, H, Hd), s_in),
        "wk": normal(keys[2], (L, D, KV, Hd), s_in),
        "wv": normal(keys[3], (L, D, KV, Hd), s_in),
        "wo": normal(keys[4], (L, H, Hd, D), s_in * s_res),
        "attn_norm": jnp.ones((L, D), jnp.float32),
        "mlp_norm": jnp.ones((L, D), jnp.float32),
        "w_gate": normal(keys[5], (L, D, F), s_in),
        "w_up": normal(keys[6], (L, D, F), s_in),
        "w_down": normal(keys[7], (L, F, D), s_ff * s_res),
        "final_norm": jnp.ones((D,), jnp.float32),
        "unembed": normal(keys[8], (D, V), s_in),
    }


def weight_count(m: dict) -> dict:
    d, f, v, n = m["d_model"], m["d_ff"], m["vocab_size"], m["n_layers"]
    kv = d // m["n_heads"] * m["n_kv_heads"]
    per_layer = 2 * d * d + 2 * d * kv + 3 * d * f
    return {"per_layer": per_layer, "head": d * v, "total": n * per_layer + d * v}


def decode_step_need(m: dict, batch: int, live_rows: float, itemsize: int = 2) -> dict:
    w = weight_count(m)
    d, n = m["d_model"], m["n_layers"]
    kv_row = 2 * (d // m["n_heads"] * m["n_kv_heads"]) * itemsize * n
    return {"flops": 2.0 * w["total"] * batch + 4.0 * d * n * live_rows,
            "bytes": w["total"] * itemsize + (live_rows + batch) * kv_row}
